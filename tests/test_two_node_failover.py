"""Timed two-host elastic failover drill with a real jax.distributed world.

The north-star SLA (BASELINE.md): recovery from a lost host in <60s.
Topology: one DistributedJobMaster + two launcher agents on this machine,
each supervising a training process; the two processes form a real
2-process jax.distributed world (CPU backend, gloo collectives) and psum
gradients every step. The drill SIGKILLs host 1's whole process group
mid-run and asserts host 0:
  - detects the loss (coordination-service heartbeat + master watchdog
    pruning the dead node -> num_nodes_waiting shrink signal),
  - re-rendezvouses into a 1-node world,
  - restores from the flash checkpoint,
  - resumes stepping, all within 60 seconds of the kill.

Parity: the reference's node-failure system tests
(.github/actions/dlrover-system-test-*) and SURVEY §4.3's
multi-node-without-cluster pattern.
"""

import os
import re
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(env):
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), REPO) if p]
    )
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _start_master(tmp):
    env = _cpu_env(dict(os.environ))
    out_path = os.path.join(tmp, "master.out")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.main",
         "--platform", "tpu_vm", "--node_num", "2", "--port", "0",
         "--heartbeat_timeout", "8"],
        cwd=REPO, env=env,
        stdout=open(out_path, "w"), stderr=open(
            os.path.join(tmp, "master.err"), "w"),
        start_new_session=True,
    )
    # poll the log file instead of readline() so a hung master can't block
    # past the deadline
    deadline = time.time() + 30
    port = None
    while time.time() < deadline:
        m = re.search(r"DLROVER_TPU_MASTER_PORT=(\d+)",
                      open(out_path).read())
        if m:
            port = int(m.group(1))
            break
        if proc.poll() is not None:
            raise RuntimeError("master died during startup")
        time.sleep(0.1)
    assert port, "master did not report a port"
    return proc, f"localhost:{port}"


def _start_agent(tmp, rank, master_addr, steps=200):
    env = _cpu_env(dict(os.environ))
    # fast peer-death detection inside the training process
    env["DLROVER_TPU_DIST_HEARTBEAT_TIMEOUT"] = "10"
    progress = os.path.join(tmp, f"progress_{rank}.txt")
    out = os.path.join(tmp, f"out_{rank}.txt")
    log = open(os.path.join(tmp, f"agent_{rank}.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
         "--master_addr", master_addr,
         "--nnodes", "1:2", "--node_rank", str(rank),
         "--rdzv_timeout", "2", "--monitor_interval", "0.3",
         "--heartbeat_interval", "2", "--max_restarts", "3",
         os.path.join(REPO, "examples", "dist_train.py"), "--",
         "--steps", str(steps),
         "--ckpt-dir", os.path.join(tmp, f"ckpt_{rank}"),
         "--progress", progress, "--out", out],
        cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
        start_new_session=True,
    )
    return proc, progress, out


def _read_progress(path):
    """[(step, world, loss, ts)] parsed from the progress file."""
    if not os.path.exists(path):
        return []
    rows = []
    for line in open(path):
        parts = line.strip().split(",")
        if len(parts) == 4:
            rows.append((int(parts[0]), int(parts[1]),
                         float(parts[2]), float(parts[3])))
    return rows


def _killpg(proc, sig=signal.SIGKILL):
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def test_two_node_failover_under_60s(tmp_path):
    tmp = str(tmp_path)
    master_proc, master_addr = _start_master(tmp)
    a0 = a1 = None
    try:
        a0, progress0, out0 = _start_agent(tmp, 0, master_addr)
        a1, progress1, _ = _start_agent(tmp, 1, master_addr)

        # phase 1: the 2-process world trains past a checkpoint (step 5)
        deadline = time.time() + 120
        while time.time() < deadline:
            rows = _read_progress(progress0)
            if any(r[0] >= 7 and r[1] == 2 for r in rows):
                break
            assert a0.poll() is None, open(
                os.path.join(tmp, "agent_0.log")).read()[-2000:]
            time.sleep(0.2)
        rows = _read_progress(progress0)
        assert any(r[1] == 2 for r in rows), (
            f"2-process world never formed: {rows[-5:]}")
        assert any(r[0] >= 7 and r[1] == 2 for r in rows), (
            f"did not reach step 7 in the 2-node world: {rows[-5:]}")

        # phase 2: kill host 1 (agent + its training process)
        t_kill = time.time()
        _killpg(a1)
        step_at_kill = max(r[0] for r in rows)

        # phase 3: host 0 must resume stepping in a 1-process world
        recovery_seconds = None
        deadline = t_kill + 120
        while time.time() < deadline:
            rows = _read_progress(progress0)
            resumed = [r for r in rows
                       if r[1] == 1 and r[3] > t_kill]
            if resumed:
                recovery_seconds = resumed[0][3] - t_kill
                break
            time.sleep(0.2)
        assert recovery_seconds is not None, (
            "survivor never resumed in a 1-node world; tail: "
            + str(_read_progress(progress0)[-5:])
            + open(os.path.join(tmp, "agent_0.log")).read()[-3000:]
        )
        print(f"RECOVERY_SECONDS={recovery_seconds:.1f} "
              f"(killed at step {step_at_kill})")
        assert recovery_seconds < 60.0, (
            f"recovery took {recovery_seconds:.1f}s, SLA is <60s")

        # the resumed run restored from a flash checkpoint, not step 0
        log0 = open(os.path.join(tmp, "agent_0.log")).read()
        assert "RESTORED from step" in log0
        m = re.search(r"RESTORED from step (\d+)", log0)
        assert int(m.group(1)) >= 5
    finally:
        for p in (a0, a1):
            if p is not None:
                _killpg(p)
        _killpg(master_proc, signal.SIGTERM)
        time.sleep(0.5)
        _killpg(master_proc)
