"""Measured warm-restart drill on the flagship model -> FAILOVER.json
(an earlier chip run's record is not reproduced).

VERDICT r4 Missing #1: the <60s failover SLA was only ever timed on a
dim-16 toy where compile is free; at 1B+ the restart budget is
dominated by XLA recompilation, which the persistent compilation cache
(trainer/compile_cache.py) converts into a disk read. This script
produces the measured evidence on the real chip:

1. COLD: a fresh trainer process (empty cache) on the bench flagship
   (llama 1.1B, bf16, seq 2048 on TPU; tiny config on CPU) — records
   process-start -> first-step-retired, then saves a flash checkpoint
   and exits (simulating the pre-failure incarnation).
2. WARM: a second process, same cache dir + checkpoint present (the
   restart-in-place case: same program, same topology) — records
   restore + re-jit-from-cache -> first-new-step.
3. The JSON records both, their delta (= the compile time the cache
   refunds), and the SLA verdict for the measured model.
4. --aot7b additionally times the 7B north-star AOT compile
   (northstar_7b.abstract_dryrun) cold vs warm-cache, re-grounding the
   7B <60s argument with a measured compile magnitude instead of an
   assumption.

Run:  python benchmarks/failover_warm.py            # on the chip
      JAX_PLATFORMS=cpu python benchmarks/failover_warm.py  # dev run
Parity: the reference's restart-in-place intent
(dlrover/python/elastic_agent/torch/training.py:441) — restarting
without re-setup cost is the entire point of its agent design.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dlrover_tpu.common.cachedir import resolve_cache_dir  # noqa: E402


def worker(args) -> int:
    """One trainer incarnation; prints a single TIMING line."""
    t_start = time.time()
    import jax

    if os.getenv("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    from dlrover_tpu.trainer.compile_cache import (
        cache_entries,
        setup_compilation_cache,
    )

    os.environ.setdefault("DLROVER_TPU_COMPILE_CACHE_MIN_SECS", "0.0")
    cache_dir = setup_compilation_cache()

    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import create_mesh
    from dlrover_tpu.trainer.checkpoint import FlashCheckpointer
    from dlrover_tpu.trainer.sharded import make_trainer_for_llama

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = llama.llama_1b(remat="dots_attn_out")
        batch, seq = 3, 2048
    else:
        cfg = llama.llama_tiny()
        batch, seq = 8, 128

    mesh = create_mesh([("data", 1), ("fsdp", len(jax.devices()))])
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy="ddp" if on_tpu else "fsdp",
        optimizer=optax.adamw(1e-3),
    )
    params, opt_state = trainer.init(jax.random.key(0))

    ckpt = FlashCheckpointer(
        persist_dir=os.path.join(args.ckpt_dir, "persist"),
        ram_dir=os.path.join(args.ckpt_dir, "ram"),
        persist_interval=0, use_orbax=False,
    )
    state = {"params": params, "opt_state": opt_state}
    t_restore0 = time.time()
    restored, got = ckpt.restore(target=state)
    t_restore = time.time() - t_restore0
    if restored is not None:
        params, opt_state = restored["params"], restored["opt_state"]

    import numpy as np

    rng = np.random.default_rng(0)
    tokens = rng.integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32
    )
    mb = trainer.shard_batch(trainer.microbatch((tokens, tokens)))

    params, opt_state, loss = trainer.train_step(params, opt_state, mb)
    loss.block_until_ready()
    t_first = time.time() - t_start

    # steady-state step time so compile share can be derived
    t0 = time.time()
    for _ in range(3):
        params, opt_state, loss = trainer.train_step(
            params, opt_state, mb
        )
    loss.block_until_ready()
    steady = (time.time() - t0) / 3

    if restored is None:
        ckpt.save(10, {"params": params, "opt_state": opt_state})
        ckpt.wait()

    print("TIMING " + json.dumps({
        "restored_step": got,
        "t_restore_secs": round(t_restore, 3),
        "t_first_step_secs": round(t_first, 3),
        "steady_step_secs": round(steady, 3),
        "cache_entries": cache_entries(cache_dir),
        "platform": jax.devices()[0].platform,
        "params_m": round(llama.param_count(cfg) / 1e6, 1),
    }), flush=True)
    return 0


def _run_worker(cache_dir: str, ckpt_dir: str) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         "--ckpt_dir", ckpt_dir],
        capture_output=True, text=True, timeout=1800, cwd=REPO,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache_dir),
    )
    if proc.returncode != 0:
        raise RuntimeError(f"worker failed:\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("TIMING "):
            return json.loads(line[len("TIMING "):])
    raise RuntimeError(f"no TIMING line:\n{proc.stdout[-2000:]}")


def _aot7b(cache_dir: str) -> dict:
    """Cold-vs-warm wall time of the 7B north-star AOT compile
    (northstar_7b.py --full run twice against one persistent cache;
    abstract_dryrun's compile is the dominant cost of the run)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # 32 virtual devices
    # jax's own env knobs: northstar_7b.py doesn't run init_from_env,
    # so point the cache at jax directly
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # the cold phase must BE cold: a previous run's populated cache
    # here would report the 7B compile magnitude as ~0
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir, exist_ok=True)
    out = {}
    for phase in ("cold", "warm"):
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "benchmarks", "northstar_7b.py"),
             "--full", "--out", os.path.join(cache_dir, "ns.json")],
            env=env, cwd=REPO,
            capture_output=True, text=True, timeout=3600,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"7B AOT {phase} failed:\n{proc.stderr[-3000:]}"
            )
        out[f"aot_run_{phase}_secs"] = round(time.time() - t0, 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--ckpt_dir", default="")
    ap.add_argument("--aot7b", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        REPO, "FAILOVER.json"
    ))
    args = ap.parse_args(argv)
    if args.worker:
        return worker(args)

    # the caches live where every cache of this repo lives
    # (common/cachedir.py), in a corner of their own that the cold
    # run finds empty
    cache_root = resolve_cache_dir()
    cache_dir = os.path.join(cache_root, "failover_warm")
    shutil.rmtree(cache_dir, ignore_errors=True)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        cold = _run_worker(cache_dir, ckpt_dir)
        warm = _run_worker(cache_dir, ckpt_dir)

    refund = cold["t_first_step_secs"] - warm["t_first_step_secs"]
    doc = {
        "what": (
            "restart->first-step, cold (empty compilation cache) vs "
            "warm (same cache+topology, flash-checkpoint restore) on "
            "the bench flagship; the delta is the compile time a "
            "same-topology failover no longer pays"
        ),
        "cold": cold,
        "warm": warm,
        "compile_refund_secs": round(refund, 3),
        "warm_restart_within_60s": warm["t_first_step_secs"] < 60.0,
        "cold_restart_within_60s": cold["t_first_step_secs"] < 60.0,
        "notes": (
            "warm additionally pays checkpoint restore "
            f"({warm['t_restore_secs']}s) and still must beat cold; "
            "rendezvous+process-spawn are measured by the drill suite "
            "(tests/test_warm_restart_drill.py, "
            "tests/test_two_node_failover.py) and are O(seconds)"
        ),
    }
    if args.aot7b:
        doc["aot_7b"] = _aot7b(
            os.path.join(cache_root, "failover_warm_7b")
        )
        doc["aot_7b"]["what"] = (
            "wall time of the full 7B north-star AOT compile "
            "(northstar_7b --full, 32 virtual devices), cold vs "
            "warm persistent cache — the measured magnitude of the "
            "compile a cold 7B failover would pay"
        )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
