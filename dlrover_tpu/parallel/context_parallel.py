"""Long-context sequence/context parallelism: ring + Ulysses attention.

Parity reference: atorch/atorch/modules/distributed_transformer/
distributed_attention.py:21,79 — the reference shards the sequence over
ranks, all-gathers micro-queries (AllGatherQMicro) and restores softmax
correctness with a global max/sum allreduce (DistributedSoftmax).

TPU-native redesign (supersedes the gather-based scheme, SURVEY §5.7):
 - **Ring attention**: K/V chunks rotate around the sequence axis with
   ``lax.ppermute`` over ICI; each step computes blockwise attention of
   the local queries against the visiting chunk, carrying online-softmax
   (o, lse) accumulators — the reference's DistributedSoftmax max/sum
   trick, folded into the per-chunk logsumexp combination. Communication
   is neighbor-to-neighbor (rides ICI), overlapping with compute.
 - **Ulysses attention**: ``lax.all_to_all`` re-shards seq -> heads, runs
   dense (flash) attention on full sequences for h/sp local heads, then
   re-shards back. One all-to-all pair per call; better when
   heads >= sp and the per-chunk ring bubble hurts.

Both are drop-in ``attn_fn`` for models.llama.forward; autodiff flows
through ppermute/all_to_all transposes, so no custom backward is needed.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from dlrover_tpu.parallel.compat import shard_map

from dlrover_tpu.ops.attention import (
    NEG_INF, mha_reference, whole_q_and_k,
)
from dlrover_tpu.parallel.mesh import SEQ_AXIS, batch_axes


def _ring_local(q, k, v, *, axis_name: str, sp: int, causal: bool,
                scale: Optional[float]):
    """Per-device ring attention body (runs under shard_map).

    q: [b, s_loc, h, d]; k, v: [b, s_loc, kvh, d] (GQA chunks rotate
    un-broadcast, so ppermute bytes stay kvh-sized). Sequence sharded.

    Memory is O(local): the per-chunk (o, lse) pairs fold into RUNNING
    online-softmax accumulators (num, den, m_run) each step — the
    reference's DistributedSoftmax max/sum allreduce, restated as a
    streaming logsumexp merge.
    """
    s_loc = q.shape[1]
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def chunk(q, k_cur, v_cur, src):
        """Attention of local q against the chunk that ORIGINATED at
        device ``src``; global causal mask from chunk positions."""
        if causal:
            q_pos = me * s_loc + jnp.arange(s_loc)
            k_pos = src * s_loc + jnp.arange(s_loc)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        return mha_reference(
            q, k_cur, v_cur, causal=False, scale=scale, mask=mask,
            return_lse=True,
        )

    def body(r, carry):
        num, den, m_run, k_cur, v_cur = carry
        src = (me - r) % sp
        o_r, lse_r = chunk(q, k_cur, v_cur, src)  # lse_r: [b, h, s_loc]
        m_new = jnp.maximum(m_run, lse_r)
        # NEG_INF-safe weights (skipped/fully-masked chunks contribute 0)
        alpha = jnp.where(
            m_run <= NEG_INF, 0.0, jnp.exp(m_run - m_new)
        )
        w = jnp.where(lse_r <= NEG_INF, 0.0, jnp.exp(lse_r - m_new))
        # [b, h, s] -> [b, s, h, 1] to weight o
        a_t = jnp.moveaxis(alpha, 1, 2)[..., None]
        w_t = jnp.moveaxis(w, 1, 2)[..., None]
        num = num * a_t + o_r.astype(jnp.float32) * w_t
        den = den * alpha + w
        # rotate K/V to the next neighbor over ICI
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        return num, den, m_new, k_cur, v_cur

    b, _, h, d = q.shape
    num0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    den0 = jnp.zeros((b, h, s_loc), jnp.float32)
    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    num, den, _, _, _ = jax.lax.fori_loop(
        0, sp, body, (num0, den0, m0, k, v)
    )
    den = jnp.where(den == 0.0, 1.0, den)
    out = num / jnp.moveaxis(den, 1, 2)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [batch, seq, heads, head_dim] (seq sharded on mesh)
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Ring attention over the mesh's sequence axis (callable under jit)."""
    sp = mesh.shape.get(axis_name, 1)
    if sp == 1:
        return mha_reference(q, k, v, causal=causal, scale=scale)
    # GQA chunks rotate un-broadcast (mha_reference groups natively)
    h, kvh = q.shape[2], k.shape[2]
    if kvh == 0 or h % kvh:
        raise ValueError(f"heads {h} not a multiple of kv_heads {kvh}")
    batch_spec = batch_axes(mesh) or None
    spec = P(batch_spec, axis_name, None, None)
    fn = shard_map(
        functools.partial(
            _ring_local, axis_name=axis_name, sp=sp, causal=causal,
            scale=scale,
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, sp: int, causal: bool,
                   scale: Optional[float], attn_impl):
    """seq-sharded -> all_to_all -> head-sharded full-seq attention."""
    # local [b, s/sp, h, d] -> [b, s, h/sp, d]
    q = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)
    k = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)
    v = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)
    o = attn_impl(q, k, v, causal=causal, scale=scale)
    # back: [b, s, h/sp, d] -> [b, s/sp, h, d]
    return jax.lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention(
    q: jax.Array,  # [batch, seq, heads, head_dim] (seq sharded on mesh)
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    axis_name: str = SEQ_AXIS,
    attn_impl=None,
) -> jax.Array:
    """Ulysses (all-to-all head-scatter) attention over the seq axis."""
    from dlrover_tpu.ops.attention import flash_attention

    sp = mesh.shape.get(axis_name, 1)
    attn_impl = attn_impl or (
        lambda q, k, v, causal, scale: flash_attention(
            q, k, v, causal=causal, scale=scale
        )
    )
    if sp == 1:
        return attn_impl(q, k, v, causal, scale)
    h, kvh = q.shape[2], k.shape[2]
    if h % sp:
        raise ValueError(f"heads {h} must divide by seq-parallel size {sp}")
    if kvh == 0 or h % kvh:
        raise ValueError(f"heads {h} not a multiple of kv_heads {kvh}")
    if kvh != h and kvh % sp:
        # the all_to_all splits the head dim; only broadcast KV heads when
        # they cannot be split sp ways themselves
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    batch_spec = batch_axes(mesh) or None
    spec = P(batch_spec, axis_name, None, None)
    fn = shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis_name, sp=sp, causal=causal,
            scale=scale, attn_impl=attn_impl,
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def make_context_parallel_attn(mesh: Mesh, kind: str = "ring",
                               axis_name: str = SEQ_AXIS):
    """Build an ``attn_fn`` for models.llama.forward. Latent
    attention's rotated parts (``q_rope``, ``k_rope``) are put beside
    q and k before the sequence is cut: the chunks rotate whole."""
    attend = {"ring": ring_attention, "ulysses": ulysses_attention}.get(kind)
    if attend is None:
        raise ValueError(f"unknown context-parallel kind {kind!r}")
    return lambda q, k, v, q_rope=None, k_rope=None: attend(
        *whole_q_and_k(q, k, q_rope, k_rope), v, mesh, causal=True,
        axis_name=axis_name,
    )
