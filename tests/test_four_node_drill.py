"""Four-node elastic drill with LIVE straggler shrink (VERDICT r2 Next
#7): a 4-agent job (node_unit=2) whose rank-3 network probe is delayed
past the straggler threshold; the master's auto-scaler must read the
network-check verdict, generate the straggler shrink plan, evict down
to the aligned world of 2, and the survivors must re-rendezvous and
resume from the flash checkpoint.

Covers live the path that was previously only unit-tested
(master/resource/local_optimizer.generate_straggler_shrink_plan +
master/node/job_auto_scaler._maybe_shrink_stragglers). Parity role:
dlrover rdzv_manager.py:368 straggler handling + the reference's
node-failure system tests.
"""

import os
import re
import signal
import subprocess
import sys
import time
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
    # the drill asserts on master INFO logs (straggler plan); the test
    # conftest's WARNING default would hide them
    env["DLROVER_TPU_LOG_LEVEL"] = "INFO"
    return env


def _write_spec(tmp):
    progress = os.path.join(tmp, "progress.txt")
    spec = f"""
apiVersion: dlrover-tpu/v1
kind: ElasticTpuJob
metadata:
  name: straggler-drill
spec:
  platform: process
  distributionStrategy: allreduce
  nodeUnit: 2
  relaunchStrategy: always
  heartbeatTimeout: 8
  worker:
    replicas: 4
    minReplicas: 2
    maxRelaunchCount: 2
    criticalWorkerIndex: none
    env:
      DLROVER_TPU_PROBE_DELAY: "3:35"
      DLROVER_TPU_DIST_HEARTBEAT_TIMEOUT: "10"
      JAX_PLATFORMS: cpu
    command:
      - {sys.executable}
      - -m
      - dlrover_tpu.trainer.elastic_run
      - --nnodes
      - "2:4"
      - --node_unit
      - "2"
      - --network-check
      - --rdzv_timeout
      - "10"
      - --monitor_interval
      - "0.3"
      - --heartbeat_interval
      - "2"
      - --max_restarts
      - "4"
      - {os.path.join(REPO, 'examples', 'dist_train.py')}
      - --
      - --steps
      - "600"
      - --ckpt-dir
      - {os.path.join(tmp, 'ckpt')}
      - --progress
      - {progress}
"""
    path = os.path.join(tmp, "job.yaml")
    with open(path, "w") as f:
        f.write(spec)
    return path, progress


def _read_progress(path):
    """[(step, world, loss, ts)] rows."""
    if not os.path.exists(path):
        return []
    rows = []
    for line in open(path):
        parts = line.strip().split(",")
        if len(parts) == 4:
            try:
                rows.append((int(parts[0]), int(parts[1]),
                             float(parts[2]), float(parts[3])))
            except ValueError:
                pass
    return rows


def _killpg(proc, sig=signal.SIGKILL):
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def test_four_node_straggler_shrink_live(tmp_path):
    tmp = str(tmp_path)
    spec_path, progress = _write_spec(tmp)
    env = _cpu_env(dict(os.environ))
    master_out = os.path.join(tmp, "master.out")
    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.main",
         "--job_spec", spec_path, "--port", "0",
         "--autoscale_interval", "10"],
        cwd=REPO, env=env,
        stdout=open(master_out, "w"),
        stderr=open(os.path.join(tmp, "master.err"), "w"),
        start_new_session=True,
    )
    try:
        # phase 1: the 4-node world forms and trains (agents launched
        # by the master's ProcessScaler from the job spec)
        deadline = time.time() + 240
        world4_step = None
        while time.time() < deadline:
            rows = _read_progress(progress)
            hi = [r for r in rows if r[1] == 4 and r[0] >= 7]
            if hi:
                world4_step = hi[-1][0]
                break
            assert master.poll() is None, (
                open(master_out).read()[-2000:]
                + open(os.path.join(tmp, "master.err")).read()[-2000:]
            )
            time.sleep(0.5)
        assert world4_step is not None, (
            "4-node world never trained past step 7; progress tail: "
            + str(_read_progress(progress)[-5:])
            + " master.err: "
            + open(os.path.join(tmp, "master.err")).read()[-3000:]
        )

        # phase 2: the auto-scaler's straggler shrink fires (rank 3's
        # probe was 15s slower than the median) and the world reforms
        # at the node_unit-aligned size of 2
        deadline = time.time() + 240
        world2_rows = []
        while time.time() < deadline:
            rows = _read_progress(progress)
            world2_rows = [r for r in rows if r[1] == 2]
            if world2_rows:
                break
            time.sleep(0.5)
        err = open(os.path.join(tmp, "master.err")).read()
        assert world2_rows, (
            "world never reformed at 2 after straggler shrink; "
            "progress tail: " + str(_read_progress(progress)[-5:])
            + " master.err: " + err[-3000:]
        )

        # the master really took the straggler path (not a generic
        # failure relaunch)
        assert re.search(r"shrink past stragglers \[3\]", err), (
            err[-3000:]
        )

        # phase 3: no flash-checkpoint loss — the shrunk world resumed
        # from a checkpointed step, not from scratch
        first_w2 = min(r[0] for r in world2_rows)
        assert first_w2 > 0, (
            f"world-2 run restarted from step 0 (checkpoint lost); "
            f"rows: {world2_rows[:3]}"
        )
    finally:
        _killpg(master, signal.SIGTERM)
        time.sleep(1.0)
        _killpg(master)
        # the master's scaler kills its agents on teardown; sweep any
        # stragglers of our own process tree
        subprocess.run(
            ["pkill", "-9", "-f", "straggler-drill"],
            capture_output=True,
        )
