"""Headline benchmark: Llama decoder training throughput on one chip.

Prints ONE JSON line:
  {"metric": "mfu_percent", "value": N, "unit": "%", "vs_baseline": N,
   ...detail fields}

Baseline: the reference's published HFU with ATorch is 49.6% on A100/H100
clusters (docs/blogs/stabilize_llm_training_cn.md:281, BASELINE.md);
vs_baseline = our MFU / 49.6.

On a real TPU this runs a ~1.1B-param Llama (bf16, seq 2048) sized for a
single chip; on CPU (driver-less dev runs) it degrades to the tiny config
so the script always produces a line.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_HFU_PERCENT = 49.6
#: the reference's CRITEO Wide&Deep rate AFTER DeepRec PS autoscaling
#: added 3 workers (docs/blogs/deeprec_autoscale_cn.md:223, BASELINE.md)
BASELINE_DLRM_STEPS_PER_SEC = 100.0


def bench_dlrm():
    """Single-chip recommender throughput (BASELINE config #4).

    The reference's comparable is steps/sec on the CRITEO Wide&Deep
    job: 30 -> 100 step/s after DeepRec's PS autoscaler added 3
    workers (CPU cluster). Here the same model shape (dim-8 deep
    embeddings + wide tower over the CRITEO vocab stats) trains on one
    TPU chip with the vocab-stacked table — no PS tier at all;
    vs_baseline = our steps/sec over their post-scaling 100."""
    import optax

    from dlrover_tpu.models import dlrm
    from dlrover_tpu.parallel.mesh import create_mesh

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg = dlrm.criteo_wide_deep()
    batch = 4096 if on_tpu else 256
    steps, warmup = (30, 5) if on_tpu else (6, 2)

    mesh = create_mesh([("data", 1), ("fsdp", 1)], devices=[dev])
    trainer = dlrm.make_trainer(
        cfg, mesh, optimizer=optax.adagrad(0.05)
    )
    params, opt_state = trainer.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    dense = rng.standard_normal(
        (1, batch, cfg.dense_dim), dtype=np.float32
    )
    cat = np.stack(
        [rng.integers(0, s, (1, batch)) for s in cfg.vocab_sizes], -1
    ).astype(np.int32)
    labels = rng.integers(0, 2, (1, batch)).astype(np.int32)
    mb = trainer.shard_batch((dense, cat, labels))

    for _ in range(warmup):
        params, opt_state, loss = trainer.train_step(
            params, opt_state, mb
        )
    loss.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = trainer.train_step(
            params, opt_state, mb
        )
    loss_val = float(loss)
    dt = time.perf_counter() - t0

    step_time = dt / steps
    sps = 1.0 / step_time
    print(json.dumps({
        "metric": "dlrm_steps_per_sec",
        "value": round(sps, 1),
        "unit": "steps/s",
        "vs_baseline": round(sps / BASELINE_DLRM_STEPS_PER_SEC, 3),
        "baseline": "DeepRec CRITEO Wide&Deep 100 step/s after PS "
        "autoscale (deeprec_autoscale_cn.md:223)",
        "examples_per_sec": round(batch * sps, 1),
        "batch": batch,
        "step_time_ms": round(step_time * 1e3, 2),
        "table_rows": cfg.padded_vocab,
        "embed_dim": cfg.embed_dim,
        "device": getattr(dev, "device_kind", dev.platform),
        "platform": dev.platform,
        "final_loss": round(loss_val, 4),
    }))


class _BenchProducer:
    """Module-level (spawn-picklable) synthetic batch stream for the
    --data shm path."""

    def __init__(self, n_batches, batch, seq, vocab):
        self.n_batches = n_batches
        self.batch = batch
        self.seq = seq
        self.vocab = vocab

    def __call__(self):
        rng = np.random.default_rng(0)
        for _ in range(self.n_batches):
            t = rng.integers(
                0, self.vocab, (self.batch, self.seq), dtype=np.int32
            )
            yield t, t


def _guard_backend_discovery(metric: str, unit: str,
                             timeout_s: float = 300.0):
    """A wedged device service makes jax.devices() block FOREVER —
    the bench must emit its one
    JSON line either way, so discovery runs under a watchdog and a
    fast init failure also becomes the error line. 300s is far above
    healthy backend init (seconds) and unrelated to compile time,
    which happens after discovery."""
    import threading

    done = threading.Event()
    err = []

    def probe():
        try:
            jax.devices()
        except Exception as e:
            err.append(e)
        done.set()

    def bail(reason):
        print(json.dumps({
            "metric": metric, "value": 0.0, "unit": unit,
            "vs_baseline": 0.0, "error": reason,
        }))
        raise SystemExit(2)

    t = threading.Thread(target=probe, daemon=True,
                         name="bench-device-probe")
    t.start()
    if not done.wait(timeout_s):
        bail(
            f"device discovery hung >{timeout_s:.0f}s (wedged "
            "backend); no measurement possible"
        )
    if err:
        bail(f"backend init failed: {err[0]}")


def main():
    import argparse

    import optax

    from dlrover_tpu.auto.device_context import peak_flops_per_chip
    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.mesh import create_mesh
    from dlrover_tpu.trainer.sharded import make_trainer_for_llama

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--data", choices=["inmem", "shm"], default="inmem",
        help="shm: feed every step from coworker processes over the "
        "C++ shm ring + DevicePrefetch (the production data plane) "
        "instead of reusing one in-memory batch",
    )
    ap.add_argument(
        "--model", choices=["llama", "dlrm"], default="llama",
        help="dlrm: the CRITEO recommender bench (steps/sec vs the "
        "reference's DeepRec autoscaling claim) instead of the "
        "headline Llama MFU",
    )
    ap.add_argument(
        "--ckpt-interval", type=int, default=0,
        help="flash-save (params, opt_state) every N timed steps and "
        "report the measured train-thread stall (ckpt_stall_ms) in "
        "the JSON line; 0 disables checkpointing (default); llama "
        "bench only",
    )
    ap.add_argument(
        "--ckpt-dir", default="",
        help="checkpoint directory for --ckpt-interval (default: a "
        "fresh temp dir, removed after the run)",
    )
    args = ap.parse_args()
    if args.model == "dlrm":
        _guard_backend_discovery("dlrm_steps_per_sec", "steps/s")
        bench_dlrm()
        return
    _guard_backend_discovery("mfu_percent", "%")

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        # sized for a 16GB-HBM chip (v5e): params+adam ≈ 8.8GB bf16.
        # "dots_attn_out" remat keeps the Pallas flash-attention call
        # OUTSIDE the checkpointed segments, so its custom_vjp
        # residuals (q,k,v,o,lse ≈ 77MB/layer at batch 3) are saved
        # and the backward never re-runs the forward kernel — official
        # line: 401 ms / 56.8% MFU vs 430 ms / 52.99% for plain "dots"
        # at the same batch (batch 4 + the residuals does not fit)
        cfg = llama.llama_1b(remat="dots_attn_out")
        batch, seq, steps, warmup = 3, 2048, 20, 3
    else:
        cfg = llama.llama_tiny()
        batch, seq, steps, warmup = 8, 128, 6, 2

    mesh = create_mesh([("data", 1)], devices=[dev])
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy="ddp", accum_steps=1,
        optimizer=optax.adamw(1e-4, b1=0.9, b2=0.95),
    )
    params, opt_state = trainer.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    tokens = rng.integers(
        0, cfg.vocab_size, (batch, seq), dtype=np.int32
    )
    mb = trainer.shard_batch(trainer.microbatch((tokens, tokens)))

    batches = loader = prefetch = None
    if args.data == "shm":
        from dlrover_tpu.data.shm_dataloader import (
            DevicePrefetch,
            ShmDataLoader,
        )

        loader = ShmDataLoader(
            _BenchProducer(
                warmup + steps + 1, batch, seq, cfg.vocab_size
            ),
            num_workers=2,
            slot_bytes=max(1 << 20, 4 * batch * seq * 2 + 4096),
        )
        # microbatch reshape runs on the fill thread (transform=), so
        # the train loop only dequeues device-ready microbatches and
        # the data.fetch/data.stage spans split source wait from
        # reshape+H2D staging
        prefetch = DevicePrefetch(
            loader, depth=2, sharding=trainer.microbatch_sharding,
            transform=trainer.microbatch,
        )
        batches = iter(prefetch)

    def next_mb():
        return mb if batches is None else next(batches)

    ckpt = None
    ckpt_tmp = None
    ckpt_stalls = []
    ckpt_waits = []
    if args.ckpt_interval > 0:
        import tempfile

        from dlrover_tpu.trainer.checkpoint import FlashCheckpointer

        ckpt_dir = args.ckpt_dir
        if not ckpt_dir:
            ckpt_tmp = tempfile.TemporaryDirectory(prefix="bench_ckpt_")
            ckpt_dir = ckpt_tmp.name
        # RAM tier only: the bench measures the train-thread stall of
        # the zero-stall save path (benchmarks/ckpt_stall.py covers
        # the persist pipeline under a slow store)
        ckpt = FlashCheckpointer(
            persist_dir=os.path.join(ckpt_dir, "persist"),
            ram_dir=os.path.join(ckpt_dir, "ram"),
            persist_interval=0, use_orbax=False,
        )

    for _ in range(warmup):
        params, opt_state, loss = trainer.train_step(
            params, opt_state, next_mb()
        )
    loss.block_until_ready()

    # per-phase breakdown via span tracing (docs/TELEMETRY.md): ring
    # only, armed AFTER warmup so compile time never pollutes the
    # phase means. The spans measure TRAIN-THREAD time: "data" is the
    # host-side wait on the feed, "dispatch" the step call (async
    # dispatch until the device queue back-pressures)
    from dlrover_tpu.telemetry import tracing

    tracing.clear()
    tracing.enable()

    # goodput over the timed window (telemetry/goodput.py): the bench
    # is single-process and fault-free, so training is the whole
    # window minus the measured checkpoint stalls — the same ledger
    # arithmetic the elastic trainer runs, so BENCH_*.json tracks
    # effective throughput with the fields the job-level account uses
    from dlrover_tpu.telemetry.goodput import Phase, PhaseLedger

    ledger = PhaseLedger(phase=Phase.TRAINING, journal_events=False)

    t0 = time.perf_counter()
    ckpt_pending = False
    for i in range(steps):
        if ckpt_pending:
            # donation-safety contract (docs/CHECKPOINT.md): the
            # trainer donates (params, opt_state) when resharding
            # donation is safe, so the async-staged save must own its
            # host copies before this dispatch invalidates the source
            # buffers; reported separately from the dispatch stall
            tw = time.perf_counter()
            with tracing.span("ckpt.wait_staged"):
                ckpt.wait_staged()
            ckpt_waits.append((time.perf_counter() - tw) * 1e3)
            ckpt_pending = False
        with tracing.span("data"):
            b = next_mb()
        with tracing.span("dispatch"):
            params, opt_state, loss = trainer.train_step(
                params, opt_state, b
            )
        if ckpt is not None and (i + 1) % args.ckpt_interval == 0:
            ckpt_stalls.append(
                ckpt.save(i + 1, (params, opt_state))
            )
            ckpt_pending = True
    # one sync at the end: the final loss depends on the whole step chain,
    # so this waits for all 20 steps without a per-step host round-trip
    loss_val = float(loss)
    dt = time.perf_counter() - t0
    # silent-failure guard on the bench output itself: a non-finite
    # final loss means the throughput was measured over garbage math —
    # the row says so instead of publishing a clean-looking number.
    # Checked outside the timed window (the loop deliberately avoids
    # per-step host syncs); rollbacks are structurally 0 in this
    # single-process bench, present so BENCH_*.json rows compare
    # field-for-field with elastic runs.
    from dlrover_tpu.fault_tolerance.sentinel import TrainingSentinel

    sentinel = TrainingSentinel()
    sentinel.check(steps, loss_val)
    # re-label the measured checkpoint costs (stalls + staging waits)
    # inside the window as ckpt_stall badput
    ledger.credit(
        Phase.CKPT_STALL,
        (sum(ckpt_stalls) + sum(ckpt_waits)) / 1e3,
    )
    goodput_snap = ledger.close()
    phases = tracing.summarize(
        ("data", "dispatch", "ckpt.wait_staged", "ckpt.stage",
         "data.fetch", "data.stage")
    )
    tracing.disable()

    if ckpt is not None:
        ckpt.close()  # outside the timed window: drains the pipeline
        if ckpt_tmp is not None:
            ckpt_tmp.cleanup()

    if loader is not None:
        # same shutdown order as ElasticShmDataLoader.shutdown: EOF the
        # ring, let the prefetch thread drain to the source's end, and
        # only unmap once no native pop can be in flight
        loader.close()
        joined = prefetch.join(timeout=10.0)
        loader.shutdown(destroy=joined)

    step_time = dt / steps
    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step / step_time
    flops_per_tok = llama.flops_per_token(cfg, seq)
    model_flops_per_step = tokens_per_step * flops_per_tok
    peak = peak_flops_per_chip(dev)

    from dlrover_tpu.trainer import profiler

    # MFU: analytic model flops over the measured step time (the
    # headline); HFU: the XLA-counted hardware flops (remat recompute
    # included) over the same denominator. CAVEAT on HFU: the backend
    # flop counter excludes custom-call (Pallas) kernels, so on the
    # flash-attention path it UNDERCOUNTS — reported as a floor, not a
    # claim. Off-TPU both are 0 (peak undefined).
    mfu = (
        profiler.utilization(model_flops_per_step, step_time, peak)
        if on_tpu else 0.0
    )

    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (params, opt_state, mb),
    )
    prof = profiler.profile_step(
        trainer.train_step, *abstract, params=params
    )
    hfu = (
        profiler.utilization(prof.flops, step_time, peak)
        if on_tpu else 0.0
    )

    # which flash-attention blocks the step actually ran with
    # (ops/tuning.py's static rule); null off-TPU where the Pallas
    # path never dispatches
    from dlrover_tpu.ops import tuning

    sel = tuning.last_selection()

    result = {
        "metric": "mfu_percent",
        "value": round(mfu, 2),
        "unit": "%",
        "vs_baseline": round(mfu / BASELINE_HFU_PERCENT, 3),
        "mfu_percent": round(mfu, 2),
        "hfu_percent": round(hfu, 2),
        "model_flops_per_step": model_flops_per_step,
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "step_time_ms": round(step_time * 1e3, 1),
        "params_m": round(llama.param_count(cfg) / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "device": getattr(dev, "device_kind", dev.platform),
        "platform": dev.platform,
        "final_loss": round(loss_val, 4),
        "xla_counted_flops_per_step": prof.flops,
        "hbm_gb_per_step": round(prof.hbm_bytes / 2**30, 2),
        "param_count": prof.param_count,
        "data_path": args.data,
        "attn_block_q": sel["block_q"] if sel else None,
        "attn_block_k": sel["block_k"] if sel else None,
        "attn_tuning_source": sel["source"] if sel else None,
        # per-phase train-thread breakdown from the span layer (where
        # step time goes: feed wait vs dispatch; docs/TELEMETRY.md) —
        # ckpt_wait_staged_ms / ckpt_stall_ms below stay the donation
        # and staging costs when --ckpt-interval is on
        "data_ms": round(
            phases.get("data", {}).get("mean_ms", 0.0), 3
        ),
        "data_ms_max": round(
            phases.get("data", {}).get("max_ms", 0.0), 3
        ),
        "dispatch_ms": round(
            phases.get("dispatch", {}).get("mean_ms", 0.0), 3
        ),
        "dispatch_ms_max": round(
            phases.get("dispatch", {}).get("max_ms", 0.0), 3
        ),
        # feed-side costs (docs/DATA_PIPELINE.md BENCH conventions):
        # data_stall_ms = the train thread blocked on the feed (same
        # series as data_ms; named for cross-bench comparison),
        # shard_dispatch_ms = prefetch-THREAD wait on the upstream
        # source per batch (data.fetch span; 0.0 on the inmem path
        # where no prefetch thread runs)
        "data_stall_ms": round(
            phases.get("data", {}).get("mean_ms", 0.0), 3
        ),
        "shard_dispatch_ms": round(
            phases.get("data.fetch", {}).get("mean_ms", 0.0), 3
        ),
        "data_stage_ms": round(
            phases.get("data.stage", {}).get("mean_ms", 0.0), 3
        ),
        # effective-throughput account (docs/TELEMETRY.md Goodput):
        # fraction of the timed window spent training, and the badput
        # breakdown in the job-level causes. rendezvous/restart are
        # structurally 0 in this single-process bench; they exist so
        # BENCH_*.json rows compare field-for-field with elastic runs
        "goodput_percent": goodput_snap["goodput_percent"],
        "badput_ms": {
            "rendezvous": round(
                goodput_snap["phases"][Phase.RENDEZVOUS] * 1e3, 3
            ),
            "ckpt_stall": round(
                goodput_snap["phases"][Phase.CKPT_STALL] * 1e3, 3
            ),
            "restart": round(
                goodput_snap["phases"][Phase.RESTART] * 1e3, 3
            ),
            "rollback": round(
                goodput_snap["phases"][Phase.ROLLBACK] * 1e3, 3
            ),
        },
        "anomaly_count": sentinel.anomaly_count,
        "rollbacks": 0,
    }
    if ckpt_stalls:
        # train-thread cost of the flash saves inside the timed loop
        # (docs/CHECKPOINT.md "BENCH conventions"); step_time_ms above
        # already absorbs these stalls AND the staging waits —
        # checkpointing overhead is visible, not hidden
        result["ckpt_stall_ms"] = round(
            sum(ckpt_stalls) / len(ckpt_stalls), 3
        )
        result["ckpt_stall_ms_max"] = round(max(ckpt_stalls), 3)
        if ckpt_waits:
            result["ckpt_wait_staged_ms"] = round(
                sum(ckpt_waits) / len(ckpt_waits), 3
            )
            result["ckpt_wait_staged_ms_max"] = round(
                max(ckpt_waits), 3
            )
        result["ckpt_saves"] = len(ckpt_stalls)
        result["ckpt_interval"] = args.ckpt_interval
        # archives written by this run are sharded format v2
        # (topology-elastic manifest; docs/CHECKPOINT.md "Format v2")
        result["ckpt_format"] = 2
    print(json.dumps(result))


if __name__ == "__main__":
    main()
