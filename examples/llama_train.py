"""Flagship elastic training workload: Llama fed by the shm data plane.

Run under the elastic launcher::

    python -m dlrover_tpu.trainer.elastic_run --standalone \
        examples/llama_train.py -- --steps 50 --ckpt-dir /tmp/llama_ckpt

The full production-shaped stack (VERDICT #9): agent rendezvous ->
master dataset sharding -> coworker shm producers (ElasticShmDataLoader:
each coworker owns a ShardingClient and pushes materialized batches into
the C++ ring) -> DevicePrefetch -> ShardedTrainer jitted step -> flash
checkpoint, with step-progress hang detection and fault injection armed.

Parity role: the reference's model-zoo Llama entrypoints
(atorch/examples/llama2) with the coworker shm context
(atorch/atorch/data/shm_context.py:527) — here the data plane and the
elastic control plane come from one framework.
"""

import argparse
import functools
import json
import os
import re
import sys
import time

import jax
import numpy as np
import optax

from dlrover_tpu.agent.master_client import build_master_client
from dlrover_tpu.data.elastic_shm import ElasticShmDataLoader
from dlrover_tpu.models import llama
from dlrover_tpu.ops import tuning
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.parallel.moe import (
    set_bias_abs_max_gauge, set_bias_changed_gauge,
    set_chunks_walked_gauge,
    set_expert_load_gauges, set_rows_held_gauge, set_sums_visited_gauge,
)
from dlrover_tpu.trainer.checkpoint import FlashCheckpointer
from dlrover_tpu.trainer.compile_cache import cache_events
from dlrover_tpu.trainer.distributed import init_from_env
from dlrover_tpu.trainer.elastic import ElasticTrainer
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

MODELS = {
    "llama_tiny": llama.llama_tiny,
    # sized for a 16 GB chip at 3 x 2048 (chip_smoke.py)
    "llama_1b": functools.partial(llama.llama_1b, remat="dots_attn_out"),
    # 4 experts, top-2: dropless on one device (parallel/moe.py)
    "llama_moe_tiny": llama.llama_moe_tiny,
    "llama_latent_tiny": llama.llama_latent_tiny,
    "llama_linear_tiny": llama.llama_linear_tiny,
    "llama_sandwich_tiny": llama.llama_sandwich_tiny,
    "llama_mamba_tiny": llama.llama_mamba_tiny,
    # three layers of the delta rule with one decay a head to one of
    # full attention, blocks normed on their branches' results alone
    "llama_gdn_tiny": llama.llama_gdn_tiny,
    # two layers walked four times a step, an exit gate a position
    "llama_loop_tiny": llama.llama_loop_tiny,
}


def synth_batch(start: int, end: int, seq_len: int = 128,
                vocab: int = 256):
    """Materialize one shard's batch (coworker-side). A real job reads
    and tokenizes a corpus slice here; the synthetic stream is seeded by
    the sample index so every shard is reproducible."""
    rng = np.random.default_rng(start)
    tokens = rng.integers(
        0, vocab, (end - start, seq_len), dtype=np.int32
    )
    return tokens, tokens


class _BatchFn:
    """Picklable batch_fn with bound shape params (spawn-safe)."""

    def __init__(self, seq_len: int, vocab: int):
        self.seq_len = seq_len
        self.vocab = vocab

    def __call__(self, start, end):
        return synth_batch(start, end, self.seq_len, self.vocab)


def _append_report(path: str, **fields):
    """One JSON line per event: a crashed incarnation's lines survive
    it, and the next incarnation adds its own."""
    with open(path, "a") as f:
        f.write(json.dumps(fields) + "\n")


def _compile_step(trainer, params, opt_state, mb):
    """Compile the train step ahead of its first call (the call then
    reuses the executable) and say what only this process can know
    about it."""
    t0 = time.time()
    with cache_events() as events:
        compiled = trainer.train_step.lower(
            params, opt_state, mb
        ).compile()
    text = compiled.as_text()
    collectives = {
        op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
        for op in ("all-gather", "reduce-scatter", "all-reduce",
                   "all-to-all", "collective-permute")
    }
    leaves = jax.tree.leaves(params)
    return {
        "step_compile_secs": round(time.time() - t0, 3),
        "step_cache_requests": events["requests"],
        "step_cache_hits": events["hits"],
        "kernel_in_step": "tpu_custom_call" in text,
        "collectives": collectives,
        "tuning": tuning.last_selection(),
        # where the parameters actually live
        "param_bytes_total": sum(x.nbytes for x in leaves),
        "param_bytes_by_device": {
            str(dev): int(n) for dev, n in sorted(_bytes_by_device(
                leaves
            ).items())
        },
    }


def _bytes_by_device(arrays):
    out = {}
    for x in arrays:
        for shard in x.addressable_shards:
            out[shard.device.id] = (
                out.get(shard.device.id, 0) + shard.data.nbytes
            )
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=sorted(MODELS),
                        default="llama_tiny")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch-size", type=int, default=8,
                        help="global batch per optimizer step")
    parser.add_argument("--accum-steps", type=int, default=1,
                        help="microbatches the global batch is cut "
                             "into")
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--num-devices", type=int, default=0,
                        help="mesh size; 0 = every device")
    parser.add_argument("--num-workers", type=int, default=2)
    parser.add_argument("--strategy", type=str, default="fsdp")
    parser.add_argument("--ckpt-dir", type=str,
                        default="/tmp/llama_ckpt")
    parser.add_argument("--out", type=str, default="")
    parser.add_argument("--report", type=str, default="",
                        help="append JSON lines saying what this "
                             "process saw: device, compiled step, "
                             "tuning, cache, losses, peak memory")
    parser.add_argument("--timing-out", type=str, default="",
                        help="append '<restart_count>,<secs_to_first_"
                             "step>' per incarnation (the failover "
                             "drill's cold/warm compile probe)")
    args = parser.parse_args()

    t_proc_start = time.time()
    env = init_from_env()
    client = build_master_client()
    cfg = MODELS[args.model]()

    devices = jax.devices()[:args.num_devices or None]
    mesh = create_mesh(
        [("data", 1), ("fsdp", len(devices))], devices=devices
    )
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy=args.strategy,
        accum_steps=args.accum_steps,
        optimizer=optax.adamw(1e-3),
    )

    ckpt = FlashCheckpointer(
        persist_dir=os.path.join(args.ckpt_dir, "persist"),
        ram_dir=os.path.join(args.ckpt_dir, "ram"),
        persist_interval=0, use_orbax=False,
    )
    # an abstract target: a resume at full width holds ONE state on
    # the device, not a fresh one beside the restored one
    abs_params, abs_opt = trainer.abstract_state()
    restored, _ = ckpt.restore(target={
        "params": abs_params, "opt_state": abs_opt,
        "step": jax.ShapeDtypeStruct((), jax.numpy.int32),
    })
    start_step = 0
    if restored is not None:
        params = restored["params"]
        opt_state = restored["opt_state"]
        start_step = int(restored["step"])
        print(f"RESTORED from step {start_step}", flush=True)
    else:
        params, opt_state = trainer.init(jax.random.key(0))

    # hang detection + fault injection ride on the elastic reporter
    reporter = ElasticTrainer(
        lambda p, b: 0.0, optax.identity(), max_nodes=1, cur_nodes=1,
        master_client=client, report_interval=5,
    )

    dataset_size = args.steps * args.batch_size
    loader = ElasticShmDataLoader(
        _BatchFn(args.seq_len, cfg.vocab_size),
        dataset_name="llama-train",
        batch_size=args.batch_size,
        dataset_size=dataset_size,
        num_epochs=10**6,  # stream until --steps
        num_workers=args.num_workers,
        slot_bytes=8 << 20,
        sharding=trainer.batch_sharding,
    )

    routing_stats = bias_changed_stats = None
    if cfg.num_experts > 0:
        routing_stats = jax.jit(
            functools.partial(llama.routing_stats, cfg=cfg)
        )
        if cfg.use_expert_bias:
            bias_changed_stats = jax.jit(
                functools.partial(llama.bias_changed_stats, cfg=cfg)
            )

    mtp_loss = None
    if cfg.mtp_layers:
        mtp_loss = jax.jit(functools.partial(llama.mtp_loss, cfg=cfg))
    loop_stats = None
    if cfg.total_ut_steps > 1:
        loop_stats = jax.jit(functools.partial(llama.loop_stats, cfg=cfg))
    decay_min = None
    state_space = "M" in (cfg.hybrid_override_pattern or "") or (
        "mamba" in (cfg.layer_types or ()))
    a_head = "gated_delta_net" in (cfg.layer_types or ())
    if ("linear_attention" in (cfg.layer_types or ()) or state_space
            or a_head):
        decay_min = jax.jit(functools.partial(llama.decay_min, cfg=cfg))

    device = devices[0]
    step, loss, losses = start_step, None, []
    first_step_done = False
    try:
        for batch in loader:
            mb = trainer.microbatch(batch)
            if args.report and not first_step_done:
                step_facts = _compile_step(
                    trainer, params, opt_state, mb
                )
            params, opt_state, loss = trainer.train_step(
                params, opt_state, mb
            )
            if not first_step_done:
                # the restart tax this incarnation actually paid:
                # process start -> first optimizer step retired
                # (bootstrap + restore + trace + XLA compile or a
                # persistent-cache read — compile_cache.py)
                loss.block_until_ready()
                t_first = time.time() - t_proc_start
                first_step_done = True
                print(
                    f"FIRST_STEP restart={env.restart_count} "
                    f"secs={t_first:.3f}", flush=True,
                )
                if args.timing_out:
                    with open(args.timing_out, "a") as f:
                        f.write(f"{env.restart_count},{t_first:.3f}\n")
                if args.report:
                    _append_report(
                        args.report, event="first_step",
                        restart_count=env.restart_count,
                        start_step=start_step,
                        platform=device.platform,
                        device_kind=device.device_kind,
                        device_count=len(jax.devices()),
                        model=args.model, vocab_size=cfg.vocab_size,
                        batch=args.batch_size,
                        accum=args.accum_steps, seq=args.seq_len,
                        strategy=args.strategy,
                        mesh=dict(mesh.shape),
                        first_step_secs=round(t_first, 3),
                        compile_cache_dir=(
                            jax.config.jax_compilation_cache_dir
                        ),
                        **step_facts,
                    )
            step += 1
            losses.append((step, loss))
            reporter.report_step(step)
            if step % 10 == 0 or step >= args.steps:
                if routing_stats is not None:
                    # the periodic evaluation: how evenly the router
                    # spreads this batch (GET /metrics)
                    counts = routing_stats(params, mb[0][0])
                    most, least = set_expert_load_gauges(counts)
                    here = (cfg.moe_first_expert_held,
                            cfg.moe_experts_held)
                    held = set_rows_held_gauge(counts, *here)
                    walked = set_chunks_walked_gauge(
                        counts, *here, cfg.hidden_size
                    )
                    visited = set_sums_visited_gauge(
                        counts, *here, cfg.hidden_size
                    )
                    line = (f"EXPERT_LOAD step={step} max/mean="
                            f"{most:.3f} min/mean={least:.3f} "
                            f"held={held:.3f} walked={walked:.3f} "
                            f"sums_visited={visited:.3f}")
                    if bias_changed_stats is not None:
                        changed = set_bias_changed_gauge(
                            bias_changed_stats(params, mb[0][0]),
                            mb[0][0].size * cfg.moe_top_k,
                        )
                        line += f" bias_changed={changed:.3f}"
                        moved = set_bias_abs_max_gauge(
                            llama.expert_bias_abs_max(params, cfg)
                        )
                        line += f" bias_abs_max={moved:.4f}"
                    print(line, flush=True)
                if mtp_loss is not None:
                    # the prediction module's own term of the loss
                    # on this step's first microbatch, beside the
                    # step's loss (GET /metrics)
                    term = llama.set_mtp_loss_gauge(
                        mtp_loss(params, (mb[0][0], mb[1][0]))
                    )
                    print(f"MTP_LOSS step={step} loss={float(loss):.4f} "
                          f"mtp_loss={term:.4f}", flush=True)
                if loop_stats is not None:
                    # each pass's own cross entropy and the share of
                    # the exit distribution it holds, on this step's
                    # first microbatch (GET /metrics)
                    per_pass, shares = llama.set_loop_gauges(
                        *loop_stats(params, (mb[0][0], mb[1][0]))
                    )
                    print(
                        f"LOOP_EXIT step={step} loss={float(loss):.4f} "
                        "pass_loss="
                        + ",".join(f"{v:.4f}" for v in per_pass)
                        + " exit_share="
                        + ",".join(f"{v:.3f}" for v in shares),
                        flush=True)
                if decay_min is not None:
                    # how fast the delta rule's fastest channel (or,
                    # with one decay a head, its fastest head), or the
                    # state-space scan's fastest head, forgets on this
                    # batch (GET /metrics)
                    least = llama.set_decay_min_gauge(
                        decay_min(params, mb[0][0]),
                        "ssm_decay_min" if state_space else
                        "gdn_decay_min" if a_head else "kda_decay_min",
                    )
                    print(
                        f"SSM_DECAY step={step} min_a={least:.3e}"
                        if state_space else
                        f"GDN_DECAY step={step} min_alpha={least:.3e}"
                        if a_head else
                        f"KDA_DECAY step={step} min_alpha={least:.3e}",
                        flush=True)
                ckpt.save(
                    step,
                    {"params": params, "opt_state": opt_state,
                     "step": jax.numpy.array(step)},
                    # durable: the failover drills hard-kill (os._exit)
                    # shortly after a cadence step — the archive must
                    # already be on tmpfs, not in the async serializer.
                    # What each mode costs this loop at 6.8 GB on a
                    # v5e chip (PERF.md section 5): durable 26.7-27.6 s
                    # a save (PR 26), async 6.7-12.6 s (wait_staged,
                    # before the next donating dispatch; PR 56) with
                    # the other 20 s on the lane, beside the steps
                    durable=True,
                )
            if step >= args.steps:
                break
    finally:
        loader.shutdown()

    loss_val = float(loss) if loss is not None else float("nan")
    # flush the async save pipeline before exit: the final
    # checkpoint must land even though save() no longer blocks
    ckpt.close()
    print(f"FINAL step={step} loss={loss_val:.6f}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(f"{step},{loss_val:.6f},{start_step}")
    if args.report:
        _append_report(
            args.report, event="final",
            restart_count=env.restart_count, step=step,
            losses=[[s, float(x)] for s, x in losses],
            peak_bytes_in_use=(device.memory_stats() or {}).get(
                "peak_bytes_in_use"
            ),
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
