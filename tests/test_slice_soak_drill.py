"""Two-slice preemption + composite-fault soak drill (VERDICT r3 #4).

Eight agents as two mocked slices (DLROVER_TPU_SLICE_SIZE=4,
node_unit=4), training examples/hybrid_train.py — which builds the
hybrid ICI x DCN mesh LIVE over every re-formed world. One continuous
run exercises, in order:

  T1  whole-slice preemption: slice 1's processes die (and keep dying
      on relaunch — preempted capacity has nowhere to come back) until
      the master prunes them; the survivors re-rendezvous at the
      node_unit-aligned world of 4, the DCN axis of the live hybrid
      mesh shrinks 2 -> 1, and training resumes from the flash
      checkpoint (loss continuity, no restart from step 0);

  T2  a straggler verdict against the minimum world: rank 2 (slice 0,
      a T1 SURVIVOR) had its pre-flight network probe delayed, so the
      initial check's two-round localization already marked it. Once
      training progresses at world 4, the auto-scaler reads the
      verdict — and the shrink plan must be VETOED: at
      min_nodes=4/node_unit=4 evicting the straggler would destroy
      the world, and a soak's accumulated faults must never let the
      straggler policy do that. (The live shrink itself is drilled in
      test_four_node_drill.py, where the world has room.)

  T3  OOM on one surviving rank (master-KV injection, crash rc 137):
      the agent escalates instead of relaunching locally (a local
      restart cannot outgrow a memory limit), the master grows the
      node's memory plan and relaunches it, and the world returns to 4
      — again resuming from checkpoint, with loss continuity over the
      whole soak.

Parity role: the reference's multi-node system tests
(.github/actions/dlrover-system-test-*) + SURVEY §5.8's slice mapping.
"""

import os
import re
import signal
import subprocess
import sys
import time

from dlrover_tpu.common.grpc_utils import find_free_port
import pytest

# tier-1 budget (ISSUE 2 satellite): this module costs >50s of the
# 870s budget on a 1-core box; the nightly/full shard still runs it
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(env):
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), REPO) if p]
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["DLROVER_TPU_LOG_LEVEL"] = "INFO"
    return env


def _write_spec(tmp, dead_file):
    progress = os.path.join(tmp, "progress.txt")
    spec = f"""
apiVersion: dlrover-tpu/v1
kind: ElasticTpuJob
metadata:
  name: slice-soak
spec:
  platform: process
  distributionStrategy: allreduce
  nodeUnit: 4
  heartbeatTimeout: 8
  worker:
    replicas: 8
    minReplicas: 4
    maxRelaunchCount: 3
    criticalWorkerIndex: none
    env:
      DLROVER_TPU_SLICE_SIZE: "4"
      DLROVER_TPU_DEAD_SLICE_FILE: {dead_file}
      DLROVER_TPU_PROBE_DELAY: "2:40"
      DLROVER_TPU_REPORT_GATE: {os.path.join(tmp, "report_gate")}
      DLROVER_TPU_DIST_HEARTBEAT_TIMEOUT: "10"
      JAX_PLATFORMS: cpu
    command:
      - {sys.executable}
      - -m
      - dlrover_tpu.trainer.elastic_run
      - --nnodes
      - "4:8"
      - --node_unit
      - "4"
      - --network-check
      - --rdzv_timeout
      - "10"
      - --monitor_interval
      - "0.3"
      - --heartbeat_interval
      - "2"
      - --max_restarts
      - "1"
      - {os.path.join(REPO, 'examples', 'hybrid_train.py')}
      - --
      - --steps
      - "800"
      - --ckpt-dir
      - {os.path.join(tmp, 'ckpt')}
      - --progress
      - {progress}
"""
    path = os.path.join(tmp, "job.yaml")
    with open(path, "w") as f:
        f.write(spec)
    return path, progress


def _rows(path):
    """[(step, world, dcn, loss, ts)]"""
    if not os.path.exists(path):
        return []
    out = []
    for line in open(path):
        parts = line.strip().split(",")
        if len(parts) == 5:
            try:
                out.append((int(parts[0]), int(parts[1]),
                            int(parts[2]), float(parts[3]),
                            float(parts[4])))
            except ValueError:
                pass
    return out


def _killpg(proc, sig=signal.SIGKILL):
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def _wait(predicate, timeout, master, tmp, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = predicate()
        if got:
            return got
        assert master.poll() is None, (
            f"master died while waiting for {what}: "
            + open(os.path.join(tmp, "master.err")).read()[-3000:]
        )
        time.sleep(0.5)
    raise AssertionError(
        f"timed out waiting for {what}; master.err tail: "
        + open(os.path.join(tmp, "master.err")).read()[-3000:]
    )


def test_two_slice_preemption_composite_soak(tmp_path):
    tmp = str(tmp_path)
    dead_file = os.path.join(tmp, "dead_slices")
    spec_path, progress = _write_spec(tmp, dead_file)
    env = _cpu_env(dict(os.environ))
    port = find_free_port()
    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.main",
         "--job_spec", spec_path, "--port", str(port),
         "--autoscale_interval", "8"],
        cwd=REPO, env=env,
        stdout=open(os.path.join(tmp, "master.out"), "w"),
        stderr=open(os.path.join(tmp, "master.err"), "w"),
        start_new_session=True,
    )
    err_path = os.path.join(tmp, "master.err")
    try:
        # ---- phase 1: 2 slices / 8 hosts, dcn=2, training past step 6
        _wait(
            lambda: [r for r in _rows(progress)
                     if r[1] == 8 and r[2] == 2 and r[0] >= 6],
            300, master, tmp, "the 8-host/2-slice world to train",
        )
        w8 = [r for r in _rows(progress) if r[1] == 8][-1]

        # ---- T1: preempt slice 1 entirely
        with open(dead_file, "w") as f:
            f.write("1")
        w4_rows = _wait(
            lambda: [r for r in _rows(progress)
                     if r[1] == 4 and r[2] == 1],
            420, master, tmp,
            "the world to re-form at 4 with the DCN axis shrunk",
        )
        first_w4 = min(w4_rows, key=lambda r: r[0])
        # flash-checkpoint resume: not from scratch, and near where the
        # 8-world died (checkpoint cadence is 5 steps)
        assert first_w4[0] > 0, "world-4 run restarted from step 0"
        assert first_w4[0] >= w8[0] - 10, (first_w4, w8)
        # loss continuity across the slice loss: the resumed loss is in
        # family with the pre-fault loss, not the step-0 loss
        step0_loss = _rows(progress)[0][3]
        assert first_w4[3] <= max(w8[3] * 2.0, step0_loss * 0.5), (
            first_w4, w8, step0_loss,
        )

        # ---- T2: the straggler verdict against the minimum world.
        # Rank 2 (a T1 survivor) was localized by the initial
        # pre-flight check. Wait for the master's node view to settle
        # at exactly the 4 survivors (pending slice-1 relaunches would
        # let the shrink think it has room), then open the report gate
        # so the auto-scaler acts — and must VETO the shrink
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(f"localhost:{port}", -1, "drill")
        # the preempted slice has no capacity to come back: manual
        # scaling (the reference's manualScaling CRD verb) retargets
        # the job at 4 so the restore loop stops provisioning into the
        # dead pool
        assert client.request_scale(4)

        def settled_at_4():
            try:
                live = [
                    n for n in client.query_running_nodes()
                    if n.get("status") == "running"
                    and not n.get("is_released")
                ]
            except Exception:
                return False
            return live if len(live) == 4 else False

        _wait(settled_at_4, 300, master, tmp,
              "the master's node view to settle at 4")
        with open(os.path.join(tmp, "report_gate"), "w") as f:
            f.write("on")

        def veto_seen():
            err = open(err_path).read()
            return re.search(
                r"Keeping \d+ stragglers: shrinking to \d+ breaks "
                r"min_nodes=4/node_unit=4", err,
            )

        _wait(veto_seen, 240, master, tmp,
              "the straggler shrink veto at min_nodes")

        # ---- T3: OOM one survivor via the master-KV fault injector
        # (pick a live rank that is neither the progress reporter 0
        # nor the straggler 2, from the master's own node view)
        pre_oom = max(r[0] for r in _rows(progress))
        live = [
            n.get("rank_index", n.get("id"))
            for n in client.query_running_nodes()
            if n.get("status") == "running"
            and not n.get("is_released")
        ]
        target = next(
            r for r in live if r not in (0, 2) and r is not None
        )
        client.kv_store_set(
            f"fault_inject/{target}", b"crash@now:137"
        )

        def oom_grown():
            err = open(err_path).read()
            return re.search(r"OOM on .*: host memory \d+ -> \d+ MB",
                             err)

        _wait(oom_grown, 300, master, tmp,
              "the master's OOM grow-and-relaunch plan")

        # the world returns to 4 and trains PAST the pre-OOM step
        _wait(
            lambda: [r for r in _rows(progress)
                     if r[1] == 4 and r[0] > pre_oom + 3],
            420, master, tmp, "the world to recover to 4 after OOM",
        )

        # ---- loss continuity over the whole soak: the latest loss is
        # below the run's starting loss despite three fault transitions
        rows = _rows(progress)
        assert rows[-1][3] < rows[0][3], (rows[0], rows[-1])
    finally:
        _killpg(master, signal.SIGTERM)
        time.sleep(1.0)
        _killpg(master)
        subprocess.run(
            ["pkill", "-9", "-f", "slice-soak"], capture_output=True,
        )
