"""Pipeline parallelism: GPipe microbatch schedule over the "pipe" axis.

Parity reference: atorch/atorch/auto/opt_lib/pipeline_parallel_optimization
.py:53 and compilers/pipe_compiler/distributed_pippy_compiler.py — the
reference splits the module graph into PiPPy stages driven over a torch
RPC fabric (distributed.py:425 builds the RPC net).

TPU-native redesign (SURVEY §7 "pipeline without RPC"): the scan-stacked
layer dim is sharded over the "pipe" mesh axis, so each device holds
L/P contiguous blocks. A GPipe schedule runs under ``shard_map``:
each tick every stage applies its local blocks to its current microbatch
and hands the activation to the next stage with ``lax.ppermute`` —
neighbor ICI traffic, no RPC fabric, no driver process. The bubble is the
standard (P-1)/(M+P-1) fraction; ticks in the bubble compute on zeros
(predication would save power, not latency). Backward is plain autodiff:
the transpose of ppermute is the reverse ppermute, giving the 1F1B-style
reverse schedule for free.
"""

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from dlrover_tpu.parallel.compat import shard_map

from dlrover_tpu.parallel.mesh import PIPE_AXIS


def _stage_body(local_params, x, *, block_fn):
    """Apply this stage's local stack of blocks via scan."""

    def step(carry, layer_params):
        x, aux = carry
        x, a = block_fn(x, layer_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        step, (x, jnp.zeros((), jnp.float32)), local_params
    )
    return x, aux


def _cpu_needs_f32_boundary() -> bool:
    """XLA CPU only: NO 16-bit all-reduce may cross the partial-manual
    shard_map (fwd or transpose) — under partial-manual tracing the
    psum's reduction region carries an sdy Sharding custom-call that
    optimizes to a `copy`, and the CPU-only AllReducePromotion pass
    (which touches 16-bit all-reduces) check-fails cloning it
    (hlo_instruction.cc CreateBinary). The f32 boundary is lossless for
    bf16 and skipped on TPU, where bf16 collectives are native."""
    return jax.default_backend() == "cpu"


def _gpipe_local(params, x_mb, *, block_fn, axis_name, pp, num_micro,
                 compute_dtype):
    """Per-device GPipe schedule (runs under shard_map).

    params: this stage's local layer stack (leading dim L/P).
    x_mb: [M, mb, ...] microbatched input (replicated over pipe),
    possibly f32 at the boundary (_cpu_needs_f32_boundary) — restored
    to ``compute_dtype`` here.
    Returns ([M, mb, ...] outputs, aux scalar), replicated via psum.
    """
    x_mb = x_mb.astype(compute_dtype)
    stage = jax.lax.axis_index(axis_name)
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    m_shape = x_mb.shape[1:]
    cur = jnp.zeros(m_shape, x_mb.dtype)
    ybuf = jnp.zeros_like(x_mb)
    aux_total = jnp.zeros((), jnp.float32)

    for t in range(num_micro + pp - 1):
        feed = x_mb[min(t, num_micro - 1)]
        inp = jnp.where(stage == 0, feed, cur)
        y, aux = _stage_body(params, inp, block_fn=block_fn)
        active = jnp.logical_and(t >= stage, t - stage < num_micro)
        aux_total = aux_total + jnp.where(active, aux, 0.0)
        out_idx = t - (pp - 1)
        if out_idx >= 0:
            is_last = stage == pp - 1
            ybuf = ybuf.at[out_idx].set(
                jnp.where(is_last, y, ybuf[out_idx])
            )
        if pp > 1:
            cur = jax.lax.ppermute(y, axis_name, fwd_perm)

    # replicate the last stage's outputs (and per-stage aux) to all
    # stages; psum dtype per _cpu_needs_f32_boundary
    psum_dtype = (
        jnp.float32 if _cpu_needs_f32_boundary() else ybuf.dtype
    )
    mask = (jax.lax.axis_index(axis_name) == pp - 1).astype(psum_dtype)
    ybuf = jax.lax.psum(
        ybuf.astype(psum_dtype) * mask, axis_name
    ).astype(x_mb.dtype)
    # mean over microbatches so aux matches the un-pipelined forward's
    # semantics regardless of the microbatch count
    aux_total = jax.lax.psum(aux_total, axis_name) / num_micro
    return ybuf, aux_total


def bubble_fraction(pp: int, num_micro: int, num_chunks: int = 1) -> float:
    """Idle fraction of the pipeline schedule.

    GPipe (num_chunks=1): (P-1)/(M+P-1). Circular/interleaved with V
    chunks per device: (P-1)/(M*V+P-1) — the V× smaller bubble that
    Megatron's interleaved 1F1B buys, obtained here with a conflict-free
    static ring schedule (see interleaved_pipeline_apply)."""
    if pp <= 1:
        return 0.0
    return (pp - 1) / (num_micro * num_chunks + pp - 1)


def _interleaved_local(params, x_mb, *, block_fn, axis_name, pp,
                       num_micro, num_chunks, compute_dtype,
                       count_work=False):
    """Per-device circular-pipeline schedule (runs under shard_map).

    params: this device's [V, K_local_layers, ...] chunk stack — chunk v
    on device s covers global layers [(v*P+s)*K, (v*P+s+1)*K).
    x_mb: [M, mb, ...] microbatches (replicated over pipe).

    Schedule: microbatch m = a*P + r, chunk v is processed by device s
    at tick t = a*V*P + v*P + r + s. For fixed (t, s) the mixed-radix
    decomposition of t-s into (a, v, r) is unique, so every device does
    exactly one unit of work per tick and activations flow around the
    FULL ring (wrap P-1 -> 0 advances a microbatch to its next chunk).
    Total ticks M*V + P - 1 against M*V units of work per device —
    the bubble is (P-1)/(M*V+P-1), V times smaller than GPipe's.
    Backward is plain autodiff: the transpose of the wrapped ppermute
    is the reverse ring, giving the mirrored drain schedule for free.
    """
    # local leaves arrive as [V, 1, K, ...] (the sharded P dim keeps
    # size 1 under shard_map) -> squeeze to [V, K, ...]
    params = jax.tree.map(
        lambda p: p.reshape((p.shape[0],) + p.shape[2:]), params
    )
    x_mb = x_mb.astype(compute_dtype)  # f32 boundary, see _gpipe_local
    v_total = num_chunks * pp
    stage = jax.lax.axis_index(axis_name)
    ring = [(i, (i + 1) % pp) for i in range(pp)]
    m_shape = x_mb.shape[1:]
    cur = jnp.zeros(m_shape, x_mb.dtype)
    ybuf = jnp.zeros_like(x_mb)
    aux_total = jnp.zeros((), jnp.float32)
    work_done = jnp.zeros((), jnp.float32)
    n_ticks = num_micro * num_chunks + pp - 1

    for t in range(n_ticks):
        # decompose this device's work item at tick t
        rel = t - stage  # traced (stage is per-device)
        a = rel // v_total  # microbatch group
        v = (rel % v_total) // pp  # chunk index on this device
        r = rel % pp  # offset within the group
        m = a * pp + r
        valid = jnp.logical_and(rel >= 0, m < num_micro)
        # device 0 injects fresh microbatches at chunk 0
        inject = jnp.logical_and(stage == 0, v == 0)
        feed = x_mb[jnp.clip(m, 0, num_micro - 1)]
        inp = jnp.where(inject, feed, cur)
        chunk_params = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(
                p, jnp.clip(v, 0, num_chunks - 1), keepdims=False
            ),
            params,
        )
        y, aux = _stage_body(chunk_params, inp, block_fn=block_fn)
        aux_total = aux_total + jnp.where(valid, aux, 0.0)
        work_done = work_done + jnp.where(valid, 1.0, 0.0)
        # device P-1 finishing chunk V-1 emits the final output
        emit = jnp.logical_and(
            jnp.logical_and(stage == pp - 1, v == num_chunks - 1),
            valid,
        )
        ybuf = jax.lax.dynamic_update_index_in_dim(
            ybuf,
            jnp.where(emit, y, jax.lax.dynamic_index_in_dim(
                ybuf, jnp.clip(m, 0, num_micro - 1), keepdims=False
            )),
            jnp.clip(m, 0, num_micro - 1),
            axis=0,
        )
        if pp > 1:
            cur = jax.lax.ppermute(y, axis_name, ring)

    # psum dtype: see _cpu_needs_f32_boundary
    psum_dtype = (
        jnp.float32 if _cpu_needs_f32_boundary() else ybuf.dtype
    )
    mask = (stage == pp - 1).astype(psum_dtype)
    ybuf = jax.lax.psum(
        ybuf.astype(psum_dtype) * mask, axis_name
    ).astype(x_mb.dtype)
    aux_total = jax.lax.psum(aux_total, axis_name) / num_micro
    if count_work:
        # executed-schedule occupancy: total valid work items across
        # the ring vs pp*n_ticks device-tick slots — the MEASURED
        # bubble the dryrun asserts against bubble_fraction()'s
        # prediction (it counts what this compiled program actually
        # issued, not the closed form)
        return ybuf, aux_total, jax.lax.psum(work_done, axis_name)
    return ybuf, aux_total


def interleaved_pipeline_apply(
    block_fn: Callable,
    stacked_params: Any,  # leaves [L, ...], L % (pp*num_chunks) == 0
    x: jax.Array,
    mesh: Mesh,
    num_microbatches: int,
    num_chunks: int = 2,
    axis_name: str = PIPE_AXIS,
    schedule_stats: bool = False,
) -> Tuple[jax.Array, ...]:
    """Circular/interleaved pipeline over ``axis_name`` with
    ``num_chunks`` virtual stages per device (parity role: Megatron/
    PiPPy interleaved 1F1B, ref distributed_pippy_compiler.py — bubble
    cut by the virtual-stage factor).

    Returns (output [batch, ...], aux scalar); with
    ``schedule_stats=True`` additionally a dict with the executed
    schedule's measured occupancy (``bubble_measured`` = idle
    device-tick slots / all slots) for validation against
    :func:`bubble_fraction`."""
    pp = mesh.shape.get(axis_name, 1)
    if num_chunks < 1:
        raise ValueError("num_chunks >= 1")
    if pp == 1:
        return _stage_body(stacked_params, x, block_fn=block_fn)
    leaves = jax.tree.leaves(stacked_params)
    n_layers = leaves[0].shape[0]
    if n_layers % (pp * num_chunks):
        raise ValueError(
            f"{n_layers} layers not divisible by "
            f"pp*chunks={pp}*{num_chunks}"
        )
    if num_microbatches % pp:
        raise ValueError(
            f"microbatches={num_microbatches} must be a multiple of "
            f"pp={pp} for the circular schedule"
        )
    if x.shape[0] % num_microbatches:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by "
            f"microbatches={num_microbatches}"
        )
    mb = x.shape[0] // num_microbatches
    x_mb = x.reshape((num_microbatches, mb) + x.shape[1:])
    k = n_layers // (pp * num_chunks)
    # [L, ...] -> [V, P, K, ...]; dim 1 shards over pipe so device s
    # holds chunks {v*P+s : v} — the circular (non-contiguous) layout
    chunked = jax.tree.map(
        lambda p: p.reshape(
            (num_chunks, pp, k) + p.shape[1:]
        ),
        stacked_params,
    )
    params_spec = jax.tree.map(
        lambda _: P(None, axis_name), stacked_params
    )
    fn = shard_map(
        functools.partial(
            _interleaved_local, block_fn=block_fn, axis_name=axis_name,
            pp=pp, num_micro=num_microbatches, num_chunks=num_chunks,
            compute_dtype=x_mb.dtype, count_work=schedule_stats,
        ),
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=(P(), P(), P()) if schedule_stats else (P(), P()),
        # only pipe is manual: data/tensor axes of a combined 3D mesh
        # stay GSPMD-automatic, so TP/DP collectives are still inserted
        # by XLA inside each stage (PP x TP x DP composition)
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )
    if _cpu_needs_f32_boundary():
        x_mb = x_mb.astype(jnp.float32)
    if schedule_stats:
        y_mb, aux, work = fn(chunked, x_mb)
        n_ticks = num_microbatches * num_chunks + pp - 1
        slots = pp * n_ticks
        stats = {
            "ticks": n_ticks,
            "slots_total": slots,
            # jnp values so the stats path stays jit-traceable
            "work_slots_used": work,
            "bubble_measured": 1.0 - work / slots,
        }
        return y_mb.reshape(x.shape), aux, stats
    y_mb, aux = fn(chunked, x_mb)
    return y_mb.reshape(x.shape), aux


def gpipe_apply(
    block_fn: Callable,  # block_fn(x, layer_params) -> (x, aux)
    stacked_params: Any,  # leaves [L, ...], L % pp == 0
    x: jax.Array,  # [batch, ...] full batch (will be microbatched)
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = PIPE_AXIS,
) -> Tuple[jax.Array, jax.Array]:
    """Run the stacked blocks as a GPipe pipeline over ``axis_name``.

    Returns (output [batch, ...], aux scalar). Callable under jit; with
    pp == 1 it degrades to a plain scan over layers.
    """
    pp = mesh.shape.get(axis_name, 1)
    leaves = jax.tree.leaves(stacked_params)
    n_layers = leaves[0].shape[0]
    if n_layers % pp:
        raise ValueError(f"{n_layers} layers not divisible by pipe={pp}")
    if pp == 1:
        return _stage_body(stacked_params, x, block_fn=block_fn)
    if x.shape[0] % num_microbatches:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by "
            f"microbatches={num_microbatches}"
        )
    mb = x.shape[0] // num_microbatches
    x_mb = x.reshape((num_microbatches, mb) + x.shape[1:])

    params_spec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    fn = shard_map(
        functools.partial(
            _gpipe_local, block_fn=block_fn, axis_name=axis_name,
            pp=pp, num_micro=num_microbatches,
            compute_dtype=x_mb.dtype,
        ),
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=(P(), P()),
        axis_names=frozenset({axis_name}),  # data/tensor stay GSPMD
        check_vma=False,
    )
    if _cpu_needs_f32_boundary():
        x_mb = x_mb.astype(jnp.float32)
    y_mb, aux = fn(stacked_params, x_mb)
    return y_mb.reshape(x.shape), aux


def pipeline_llama_forward(
    params, tokens, cfg, mesh: Mesh, num_microbatches: int = 4,
    attn_fn=None, return_aux: bool = False, num_chunks: int = 1,
    schedule_stats: bool = False,
):
    """Llama forward with the block stack pipelined over the pipe axis.

    ``num_chunks > 1`` switches from GPipe to the circular/interleaved
    schedule (V virtual stages per device, bubble cut by V).
    ``schedule_stats=True`` (interleaved only) returns
    ``(logits, aux, stats)`` with the executed schedule's measured
    occupancy — see :func:`interleaved_pipeline_apply`.

    Embed / final-norm / lm_head stay outside the pipeline (they live on
    every stage; XLA shards them by the surrounding jit's rules)."""
    from dlrover_tpu.models import llama
    from dlrover_tpu.ops.attention import flash_attention

    if attn_fn is None:
        attn_fn = functools.partial(flash_attention, causal=True)
    s = tokens.shape[1]
    cos, sin = llama.rope_tables(s, cfg.rope_dim, cfg.rope_theta)
    x = params["embed"][tokens]

    lead, (kind, *others) = cfg.layer_plan()
    if lead or others:
        raise ValueError(
            "the pipeline's stages take a stack of like layers: this "
            "configuration has a layer pattern or leading dense layers"
        )

    def block_fn(x, layer_params):
        # (x, aux): a stage moves no selection bias, the counts go unread
        return llama._block(
            cfg, x, layer_params, cos, sin, attn_fn, kind=kind
        )[:2]

    # honor the config's activation-checkpointing policy per block, same
    # as the un-pipelined llama.forward. "dots_attn_out" maps to "dots"
    # here: under pipelining the activation budget scales with in-flight
    # microbatches, so saving the attention residuals (its single-chip
    # throughput win) is the wrong trade — and silently running with NO
    # remat would be worse than either.
    if cfg.remat in ("dots", "dots_attn_out"):
        block_fn = jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    elif cfg.remat == "minimal":
        block_fn = jax.checkpoint(
            block_fn, policy=jax.checkpoint_policies.nothing_saveable
        )
    elif cfg.remat != "off":
        raise ValueError(f"unknown remat policy {cfg.remat!r}")

    stats = None
    if num_chunks > 1:
        out = interleaved_pipeline_apply(
            block_fn, params["blocks"], x, mesh, num_microbatches,
            num_chunks=num_chunks, schedule_stats=schedule_stats,
        )
        if schedule_stats:
            x, aux, stats = out
        else:
            x, aux = out
    else:
        if schedule_stats:
            raise ValueError("schedule_stats needs num_chunks > 1")
        x, aux = gpipe_apply(
            block_fn, params["blocks"], x, mesh, num_microbatches
        )
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    if stats is not None:
        return logits, aux, stats
    if return_aux:
        return logits, aux
    return logits
