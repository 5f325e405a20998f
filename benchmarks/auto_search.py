"""auto_accelerate measured ON THE CHIP -> AUTO.json (an earlier chip
run's record is not reproduced): the full search loop — enumerate -> analytic rank ->
measured dryruns -> warm start on a second run — executed against real
hardware for the flagship config, with the trace archived: candidates
considered, dryruns spent, the chosen strategy, and how it compares to
the hand-picked bench config (bench.py: ddp + dots_attn_out @ batch 3
x seq 2048, 56.7% MFU in an earlier chip run, not reproduced).

Run:  python benchmarks/auto_search.py              # on the chip
      JAX_PLATFORMS=cpu python benchmarks/auto_search.py   # dev run
Parity: atorch auto/accelerate.py:390 task loop (ANALYSE/TUNE/DRYRUN)
+ the engine's strategy ranking.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "AUTO.json"))
    ap.add_argument("--dryrun-top-k", type=int, default=3)
    ap.add_argument("--model", choices=["llama", "dlrm"],
                    default="llama",
                    help="dlrm: run the search over the recommender "
                         "family (rowwise candidates) instead of the "
                         "hand-picked bench strategy (VERDICT r4 "
                         "Weak #5)")
    args = ap.parse_args(argv)

    import jax

    if os.getenv("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")
    import optax

    from dlrover_tpu.auto.accelerate import auto_accelerate
    from dlrover_tpu.brain.client import BrainClient
    from dlrover_tpu.models import llama, model_module_for
    from dlrover_tpu.util.state_store import FileStore

    on_tpu = jax.devices()[0].platform == "tpu"
    if args.model == "dlrm":
        from dlrover_tpu.models import dlrm

        cfg = dlrm.criteo_wide_deep()
        global_batch = 4096 if on_tpu else 256
        seq_len = 1
        hand_picked = {"sharding": "rowwise", "remat": "dots"}
    elif on_tpu:
        cfg = llama.llama_1b()
        global_batch, seq_len = 3, 2048  # the bench frontier point
        hand_picked = {"sharding": "ddp", "remat": "dots_attn_out"}
    else:
        cfg = llama.llama_tiny()
        global_batch, seq_len = 8, 128
        hand_picked = {"sharding": "ddp", "remat": "dots_attn_out"}

    import tempfile

    store = FileStore(os.path.join(
        tempfile.mkdtemp(prefix="auto_search_"), "brain"
    ))
    brain = BrainClient(store)

    def run_search(tag):
        t0 = time.time()
        res = auto_accelerate(
            cfg, global_batch=global_batch, seq_len=seq_len,
            dryrun_top_k=args.dryrun_top_k,
            optimizer=optax.adamw(1e-4, b1=0.9, b2=0.95),
            job_name="auto-search-r05", brain_client=brain,
        )
        elapsed = time.time() - t0
        dryruns = [
            r for r in res.reports
            if r.measured_step_seconds is not None
        ]
        return res, {
            "tag": tag,
            "wall_seconds": round(elapsed, 1),
            "candidates_considered": len(res.reports),
            "candidates_fitting": len(
                [r for r in res.reports if r.fits]
            ),
            "dryruns_spent": len(dryruns),
            "dryrun_results": [
                {
                    "strategy": {
                        "mesh": dict(r.strategy.mesh_spec),
                        "sharding": r.strategy.sharding,
                        "remat": r.strategy.remat,
                    },
                    "analytic_est_ms": round(
                        r.est_step_seconds * 1e3, 1
                    ),
                    "measured_ms": round(
                        r.measured_step_seconds * 1e3, 1
                    ),
                }
                for r in dryruns
            ],
            "chosen": {
                "mesh": dict(res.strategy.mesh_spec),
                "sharding": res.strategy.sharding,
                "remat": res.strategy.remat,
                "precision": res.strategy.precision,
            },
        }

    res_cold, cold = run_search("cold")
    # second run of the same job: the archived winner warm-starts the
    # search (re-validate vs the analytic top-1 instead of a full
    # top-k sweep) — the cross-run learning loop, measured
    _, warm = run_search("warm_start")

    chosen = res_cold.strategy
    doc = {
        "what": (
            "full auto_accelerate search executed on this hardware "
            f"for the {args.model} bench config; cold search then a "
            "second run warm-started from the archived winner"
        ),
        "model_family": args.model,
        "platform": jax.devices()[0].platform,
        "model_params_m": round(
            model_module_for(cfg).param_count(cfg) / 1e6, 1
        ),
        "global_batch": global_batch,
        "seq_len": seq_len,
        "cold": cold,
        "warm_start": warm,
        "warm_start_dryrun_savings": (
            cold["dryruns_spent"] - warm["dryruns_spent"]
        ),
        "hand_picked_bench_config": hand_picked,
        "search_matches_hand_picked": (
            chosen.sharding == hand_picked["sharding"]
            and chosen.remat == hand_picked["remat"]
        ),
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
