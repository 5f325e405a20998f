"""Goodput chaos drill: time attribution across a worker crash AND a
master kill.

A real master serves two protocol-speaking workers
(``_goodput_drill_worker.py``), each with a live goodput ledger armed.
``DLROVER_FAULT_INJECT=crash@4`` kills worker 0 mid-epoch (rc 17, the
ledger dies open); the test relaunches the same node id.
``DLROVER_FAULT_INJECT=master_crash@8`` then kills the master (rc 28);
a second master restores the goodput aggregator from the state journal
(its own downtime becomes a recovered ``master_restart`` fault) and
the job finishes clean.

Asserted: the live ``/goodput`` endpoint on master #2 serves the
restored job account; ≥95% of every process's wall-clock is
attributed (non-idle); per-process phase durations sum to elapsed time
(±1%); both injected faults land inside recovered restart windows and
the worker-crash gap is charged as ``restart`` badput; and ``python -m
dlrover_tpu.telemetry.dump --goodput`` reproduces the live totals the
master journaled at shutdown (``goodput.job_summary``).
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from dlrover_tpu.fault_tolerance.injection import MASTER_CRASH_EXIT_CODE
from dlrover_tpu.telemetry import goodput
from dlrover_tpu.telemetry.goodput import Phase
from dlrover_tpu.telemetry.journal import read_journal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER_CRASH_RC = 17
DATASET_SIZE = 192
BATCH_SIZE = 4
SHARD_SECS = 0.2


def _drill_env(journal_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), REPO) if p]
    )
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DLROVER_FAULT_INJECT", None)
    env.pop("DLROVER_TPU_METRICS_PORT", None)
    env.pop("DLROVER_TPU_RESTART_COUNT", None)
    env["DLROVER_TPU_JOURNAL"] = journal_path
    env["DLROVER_TPU_LOG_LEVEL"] = "INFO"
    return env


def _spawn_master(tmp, env, state_dir, port, tag):
    cmd = [
        sys.executable, "-m", "dlrover_tpu.master.main",
        "--platform", "process", "--node_num", "0",
        "--job_name", "goodput-drill", "--port", str(port),
        "--state_dir", state_dir,
        "--autoscale_interval", "600", "--check_interval", "0.2",
    ]
    return subprocess.Popen(
        cmd, cwd=REPO, env=env,
        stdout=open(os.path.join(tmp, f"master-{tag}.out"), "w"),
        stderr=open(os.path.join(tmp, f"master-{tag}.err"), "w"),
        start_new_session=True,
    )


def _spawn_worker(tmp, env, port, node_id, tag):
    return subprocess.Popen(
        [sys.executable,
         os.path.join(REPO, "tests", "_goodput_drill_worker.py"),
         "--master_addr", f"localhost:{port}",
         "--node_id", str(node_id),
         "--out", os.path.join(tmp, f"worker-{tag}.txt"),
         "--dataset_size", str(DATASET_SIZE),
         "--batch_size", str(BATCH_SIZE),
         "--shard_secs", str(SHARD_SECS)],
        cwd=REPO, env=env,
        stdout=open(os.path.join(tmp, f"worker-{tag}.out"), "w"),
        stderr=subprocess.STDOUT,
        start_new_session=True,
    )


def _master_port(tmp, tag, proc, timeout=30):
    path = os.path.join(tmp, f"master-{tag}.out")
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            for line in open(path):
                if line.startswith("DLROVER_TPU_MASTER_PORT="):
                    return int(line.strip().split("=", 1)[1])
        assert proc.poll() is None, _tail(tmp, f"master-{tag}.err")
        time.sleep(0.2)
    raise AssertionError(
        f"master-{tag} never printed its port; "
        + _tail(tmp, f"master-{tag}.err")
    )


def _tail(tmp, name, n=3000):
    path = os.path.join(tmp, name)
    try:
        return f"{name}: " + open(path).read()[-n:]
    except OSError:
        return f"{name}: <missing>"


def _wait(proc, timeout, what, tmp, logs):
    try:
        return proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise AssertionError(
            f"{what} did not exit in {timeout}s; "
            + " | ".join(_tail(tmp, l) for l in logs)
        )


def _killpg(proc, sig=signal.SIGKILL):
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _poll_goodput(port, timeout=30):
    """GET /goodput on a live master until it serves a job account."""
    deadline = time.time() + timeout
    last_err = None
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/goodput", timeout=2
            ) as resp:
                payload = json.loads(resp.read().decode())
            if (payload.get("job") or {}).get("procs", 0) >= 1:
                return payload
        except Exception as e:
            last_err = e
        time.sleep(0.2)
    raise AssertionError(f"/goodput never served a job account: {last_err}")


def test_goodput_chaos_drill(tmp_path):
    tmp = str(tmp_path)
    state_dir = os.path.join(tmp, "state")
    journal_path = os.path.join(tmp, "journal.jsonl")
    env = _drill_env(journal_path)
    master_env = dict(env, DLROVER_TPU_CTX_TASK_PROCESS_TIMEOUT="20")
    worker_env = dict(env, DLROVER_TPU_MASTER_RECONNECT_TIMEOUT="90")
    metrics_port = _free_port()

    procs = []
    try:
        # master #1 dies once the reported global step reaches 8
        m1 = _spawn_master(
            tmp, dict(master_env, DLROVER_FAULT_INJECT="master_crash@8"),
            state_dir, 0, "1",
        )
        procs.append(m1)
        port = _master_port(tmp, "1", m1)

        # worker 0 crashes at its own step 4 (first incarnation only)
        w0a = _spawn_worker(
            tmp, dict(worker_env, DLROVER_FAULT_INJECT="crash@4",
                      DLROVER_TPU_NODE_RANK="0"),
            port, 0, "0-a",
        )
        w1 = _spawn_worker(tmp, worker_env, port, 1, "1")
        procs += [w0a, w1]

        rc = _wait(w0a, 120, "worker 0 (crash expected)", tmp,
                   ["worker-0-a.out", "master-1.err"])
        assert rc == WORKER_CRASH_RC, (
            f"worker 0 exited rc={rc}, wanted injected crash "
            f"rc={WORKER_CRASH_RC}; " + _tail(tmp, "worker-0-a.out")
        )

        # relaunch the SAME node id: RESTART_COUNT=1 gates the env
        # injection off, exercising first-incarnation-only semantics
        w0b = _spawn_worker(
            tmp, dict(worker_env, DLROVER_FAULT_INJECT="crash@4",
                      DLROVER_TPU_NODE_RANK="0",
                      DLROVER_TPU_RESTART_COUNT="1"),
            port, 0, "0-b",
        )
        procs.append(w0b)

        rc1 = _wait(m1, 120, "master #1 (crash expected)", tmp,
                    ["master-1.err", "worker-1.out"])
        assert rc1 == MASTER_CRASH_EXIT_CODE, (
            f"master #1 exited rc={rc1}, wanted injected crash "
            f"rc={MASTER_CRASH_EXIT_CODE}; " + _tail(tmp, "master-1.err")
        )

        # master #2: same state dir + port, metrics server pinned so the
        # test can read the live /goodput account it restored
        m2 = _spawn_master(
            tmp,
            dict(master_env, DLROVER_TPU_METRICS_PORT=str(metrics_port)),
            state_dir, port, "2",
        )
        procs.append(m2)
        _master_port(tmp, "2", m2)

        # ---- live /goodput: the restored account is served while the
        # job is still running — procs observed by master #1 are there,
        # and master #1's downtime is a recovered master_restart fault
        live = _poll_goodput(metrics_port)
        assert live["job"]["procs"] >= 2, live["job"]
        assert any(
            f["cause"] == "master_restart" and f.get("recovered_ts")
            for f in live["faults"]
        ), live["faults"]

        for tag, w in (("0-b", w0b), ("1", w1)):
            rc = _wait(w, 180, f"worker {tag}", tmp,
                       ["worker-0-b.out", "worker-1.out", "master-2.err"])
            assert rc == 0, (
                f"worker {tag} exited rc={rc}; "
                + _tail(tmp, f"worker-{tag}.out")
            )
        rc2 = _wait(m2, 60, "master #2", tmp, ["master-2.err"])
        assert rc2 == 0, _tail(tmp, "master-2.err")
    finally:
        for p in procs:
            _killpg(p, signal.SIGTERM)
        time.sleep(0.5)
        for p in procs:
            _killpg(p)

    # ---- the work still completed exactly once -----------------------
    ranges = []
    for tag in ("0-a", "0-b", "1"):
        lines = open(os.path.join(tmp, f"worker-{tag}.txt")).read()
        for line in lines.splitlines():
            parts = line.split()
            if parts and parts[0] == "SHARD":
                ranges.append((int(parts[1]), int(parts[2])))
    ranges.sort()
    assert ranges[0][0] == 0 and ranges[-1][1] == DATASET_SIZE, ranges
    for (_, end), (start, _) in zip(ranges, ranges[1:]):
        assert end == start, f"shard gap/overlap at {start}: {ranges}"

    # ---- offline reconstruction -------------------------------------
    events = read_journal(journal_path)
    kinds = [e.get("kind") for e in events]
    injected = [e for e in events if e.get("kind") == "fault.injected"]
    injected_causes = {e["data"]["fault"] for e in injected}
    assert {"crash", "master_crash"} <= injected_causes, injected
    assert "master.restored" in kinds
    # both surviving workers closed their ledgers; the crashed
    # incarnation died with its ledger open (no snapshot)
    assert kinds.count("goodput.snapshot") == 2, kinds

    report = goodput.reconstruct(events)
    job = report["job"]

    # two worker nodes; three process incarnations, all ledgered exactly
    assert job["nodes"] == 2, report["nodes"]
    assert job["procs"] == 3, report["procs"]
    assert all(p["exact"] for p in report["procs"].values())

    # >= 95% of wall-clock attributed to a named phase
    assert job["attributed_percent"] >= 95.0, job
    assert job["goodput_percent"] > 0.0, job
    assert job["training_s"] > 0.0, job

    # per-process phase durations sum to elapsed time (+/- 1%)
    for key, p in report["procs"].items():
        total = sum(p["phases"].values())
        tol = max(0.01 * p["elapsed_s"], 0.05)
        assert abs(total - p["elapsed_s"]) <= tol, (
            f"{key}: phases sum {total} != elapsed {p['elapsed_s']}"
        )

    # ---- restart badput brackets the injected faults -----------------
    t_worker_crash = next(
        e["ts"] for e in injected if e["data"]["fault"] == "crash"
    )
    t_master_crash = next(
        e["ts"] for e in injected if e["data"]["fault"] == "master_crash"
    )
    # the node-0 incarnation gap contains the worker-crash instant and
    # is charged as restart badput
    node0_procs = sorted(
        (p for p in report["procs"].values() if p["node_id"] == 0),
        key=lambda p: p["start_ts"],
    )
    assert len(node0_procs) == 2, report["procs"]
    died = node0_procs[0]["start_ts"] + node0_procs[0]["elapsed_s"]
    reborn = node0_procs[1]["start_ts"]
    assert died <= t_worker_crash + 0.5, (died, t_worker_crash)
    assert reborn >= t_worker_crash, (reborn, t_worker_crash)
    assert report["nodes"]["0"]["restart_gap_s"] > 0.0, report["nodes"]
    assert job["badput_s"][Phase.RESTART] > 0.0, job
    # both injected faults carry recovered restart windows opening at
    # the injection instant
    for cause, t in (("crash", t_worker_crash),
                     ("master_crash", t_master_crash)):
        win = next(f for f in report["faults"] if f["cause"] == cause)
        assert abs(win["ts"] - t) < 0.001, (win, t)
        assert win["recovered_ts"] and win["recovered_ts"] >= t, win
    assert job["mttr_s"] is not None and job["mttr_s"] > 0.0, job
    assert job["mtbf_s"] is not None and job["mtbf_s"] > 0.0, job

    # ---- dump --goodput reproduces the live totals -------------------
    # master #2 journaled its aggregator's final account at shutdown
    # (goodput.job_summary == what /goodput was serving); the offline
    # replay of the same journal must tell the same story
    summaries = [e for e in events if e.get("kind") == "goodput.job_summary"]
    assert len(summaries) == 1, summaries
    live_job = summaries[0]["data"]
    assert live_job["procs"] == 3, live_job
    assert live_job["attributed_percent"] >= 95.0, live_job

    out = subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.telemetry.dump",
         "--goodput", "--json", journal_path],
        cwd=REPO, env=_drill_env(os.path.join(tmp, "unused.jsonl")),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    cli_job = json.loads(out.stdout)["job"]
    for field in ("training_s", "wall_s"):
        a, b = float(cli_job[field]), float(live_job[field])
        assert abs(a - b) <= max(1.0, 0.1 * max(a, b)), (
            f"{field}: offline {a} vs live {b}"
        )
    assert abs(cli_job["goodput_percent"]
               - live_job["goodput_percent"]) <= 10.0, (cli_job, live_job)
    assert cli_job["procs"] == live_job["procs"] == 3
