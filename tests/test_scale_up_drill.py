"""Live throughput scale-UP drill (VERDICT r4 Missing #2 / item #3) —
the DeepRec autoscaling story: a shard-fed job starts BELOW its
elasticity ceiling, the speed-window optimizer emits a throughput-grow
plan off the measured window, the scaler launches NEW agents (the
survivors' agent processes are never relaunched), the world re-forms
larger, and job throughput measurably rises; shard delivery stays
exactly-once across the transition.

Parity: docs/blogs/deeprec_autoscale_cn.md:223 (30 -> 100 steps/s by
adding workers), AllreduceTrainingAutoScaler job_auto_scaler.py:251,
WorkerManager worker.py:102.
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

DATASET = 15000
BATCH = 50


def _cpu_env(env):
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), REPO) if p]
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["DLROVER_TPU_LOG_LEVEL"] = "INFO"
    return env


def _write_spec(tmp):
    progress = os.path.join(tmp, "progress.txt")
    spec = f"""
apiVersion: dlrover-tpu/v1
kind: ElasticTpuJob
metadata:
  name: scaleup-drill
spec:
  platform: process
  distributionStrategy: allreduce
  nodeUnit: 2
  heartbeatTimeout: 10
  worker:
    replicas: 2
    minReplicas: 2
    maxReplicas: 4
    maxRelaunchCount: 2
    criticalWorkerIndex: none
    env:
      JAX_PLATFORMS: cpu
    command:
      - {sys.executable}
      - -m
      - dlrover_tpu.trainer.elastic_run
      - --nnodes
      - "2:4"
      - --node_unit
      - "2"
      - --rdzv_timeout
      - "10"
      - --monitor_interval
      - "0.3"
      - --heartbeat_interval
      - "2"
      - --max_restarts
      - "4"
      - {os.path.join(REPO, 'examples', 'shard_train.py')}
      - --
      - --dataset-size
      - "{DATASET}"
      - --batch-size
      - "{BATCH}"
      - --batch-seconds
      - "0.5"
      - --progress
      - {progress}
"""
    path = os.path.join(tmp, "job.yaml")
    with open(path, "w") as f:
        f.write(spec)
    return path, progress


def _read_progress(path):
    """[(start, end, rank, world, ts)] completion rows."""
    if not os.path.exists(path):
        return []
    rows = []
    for line in open(path):
        parts = line.strip().split(",")
        if len(parts) == 5:
            try:
                rows.append((int(parts[0]), int(parts[1]),
                             int(parts[2]), int(parts[3]),
                             float(parts[4])))
            except ValueError:
                pass
    return rows


def _rate(rows):
    """Completed samples per second over the rows' time span."""
    if len(rows) < 5:
        return 0.0
    span = max(r[4] for r in rows) - min(r[4] for r in rows)
    if span <= 0:
        return 0.0
    return sum(r[1] - r[0] for r in rows) / span


def _killpg(proc, sig=signal.SIGKILL):
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def test_throughput_scale_up_live(tmp_path):
    tmp = str(tmp_path)
    spec_path, progress = _write_spec(tmp)
    env = _cpu_env(dict(os.environ))
    master_out = os.path.join(tmp, "master.out")
    master_err = os.path.join(tmp, "master.err")
    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.main",
         "--job_spec", spec_path, "--port", "0",
         "--autoscale_interval", "4"],
        cwd=REPO, env=env,
        stdout=open(master_out, "w"),
        stderr=open(master_err, "w"),
        start_new_session=True,
    )
    try:
        # phase 1: the 2-worker world consumes shards
        deadline = time.time() + 180
        while time.time() < deadline:
            if [r for r in _read_progress(progress) if r[3] == 2]:
                break
            assert master.poll() is None, (
                open(master_err).read()[-3000:]
            )
            time.sleep(0.5)
        assert [r for r in _read_progress(progress) if r[3] == 2], (
            "2-worker world never produced completions; master.err: "
            + open(master_err).read()[-3000:]
        )

        # phase 2: the speed-window grow plan fires and the world
        # re-forms at 4 — with NO relaunch of the surviving agents
        deadline = time.time() + 240
        while time.time() < deadline:
            if [r for r in _read_progress(progress) if r[3] == 4]:
                break
            assert master.poll() is None, (
                open(master_err).read()[-3000:]
            )
            time.sleep(0.5)
        rows = _read_progress(progress)
        err = open(master_err).read()
        assert [r for r in rows if r[3] == 4], (
            "world never grew to 4; master.err: " + err[-3000:]
        )
        assert re.search(r"throughput grow 2 -> 4", err), err[-3000:]

        # phase 3: the job drains the dataset; throughput in the grown
        # phase beats the initial phase (the DeepRec claim)
        rc = None
        deadline = time.time() + 300
        while time.time() < deadline:
            rc = master.poll()
            if rc is not None:
                break
            time.sleep(0.5)
        rows = _read_progress(progress)
        assert rc == 0, (
            f"master rc={rc}; err: "
            + open(master_err).read()[-3000:]
        )

        w2 = [r for r in rows if r[3] == 2]
        w4 = [r for r in rows if r[3] == 4]
        rate2, rate4 = _rate(w2), _rate(w4)
        assert rate4 > 1.4 * rate2, (
            f"throughput did not rise: {rate2:.0f} -> {rate4:.0f} "
            f"samples/s (w2={len(w2)} w4={len(w4)} rows)"
        )

        # phase 4: exactly-once shard delivery across the transition —
        # completed ranges are disjoint and cover the dataset fully
        ranges = sorted((r[0], r[1]) for r in rows)
        covered = 0
        prev_end = 0
        for start, end in ranges:
            assert start == prev_end, (
                f"gap or overlap at {start} (prev end {prev_end})"
            )
            covered += end - start
            prev_end = end
        assert covered == DATASET, (covered, DATASET)

        # the survivors' AGENT processes were never relaunched: no
        # node relaunch messages for ranks 0/1 in the master log
        assert not re.search(r"[Rr]elaunch.*worker-[01]\b", err), (
            err[-3000:]
        )
    finally:
        _killpg(master, signal.SIGTERM)
        time.sleep(1.0)
        _killpg(master)
        subprocess.run(
            ["pkill", "-9", "-f", "scaleup-drill"],
            capture_output=True,
        )
