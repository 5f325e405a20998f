"""Attention ops: XLA reference implementation + Pallas TPU kernel dispatch.

Parity reference: atorch/atorch/modules/transformer/layers.py:706
(FlashAttention module injection) — the reference injects the Tri-Dao CUDA
kernel; here the hot path is a Pallas TPU kernel
(dlrover_tpu/ops/pallas/flash_attention.py) with an XLA fallback that
compiles everywhere (CPU tests, interpret mode, non-TPU backends).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.ops import tuning

NEG_INF = -1e30


def whole_q_and_k(q, k, q_rope=None, k_rope=None):
    """q and k each in one array from the parts ``flash_attention``
    takes: a head's ``q | q_rope`` and ``k | k_rope``, the one rotated
    key copied to every kv head. Without the rotated parts, q and k as
    they came."""
    if q_rope is None:
        return q, k
    every_heads = jnp.broadcast_to(k_rope, (*k.shape[:3], k_rope.shape[3]))
    return (
        jnp.concatenate([q, q_rope], axis=-1),
        jnp.concatenate([k, every_heads], axis=-1),
    )


def mha_reference(
    q: jax.Array,  # [batch, q_len, heads, head_dim]
    k: jax.Array,  # [batch, kv_len, kv_heads, head_dim]
    v: jax.Array,  # [batch, kv_len, kv_heads, v_head_dim]
    causal: bool = True,
    scale: Optional[float] = None,
    # bool [q_len, kv_len], or a kv head's own [batch, kv_heads,
    # q_len, kv_len]; True=keep
    mask: Optional[jax.Array] = None,
    return_lse: bool = False,
    window: Optional[int] = None,
    q_rope: Optional[jax.Array] = None,  # [batch, q_len, heads, rope_dim]
    k_rope: Optional[jax.Array] = None,  # [batch, kv_len, 1, rope_dim]
):
    """Plain XLA attention with GQA head-group broadcast.

    Computes in float32 for softmax stability, returns q.dtype. XLA fuses
    the mask/softmax chain; on TPU the two einsums hit the MXU directly.
    With ``return_lse`` also returns the logsumexp [batch, heads, q_len]
    (float32) for blockwise/ring combination. With ``window`` (causal
    only) query i sees key j iff ``j <= i`` and ``i - j < window``.
    With ``q_rope`` and ``k_rope`` q and k are ``whole_q_and_k``'s.
    """
    q, k = whole_q_and_k(q, k, q_rope, k_rope)
    b, qlen, h, d = q.shape
    _, klen, kvh, _ = k.shape
    if h % kvh:
        raise ValueError(f"heads {h} not a multiple of kv_heads {kvh}")
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # fold the GQA group into the query head dim: [b, qlen, kvh, group, d]
    qf = qf.reshape(b, qlen, kvh, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf)
    if causal:
        tril = jnp.tril(jnp.ones((qlen, klen), dtype=bool), k=klen - qlen)
        if window is not None:
            tril &= ~jnp.tril(
                jnp.ones((qlen, klen), dtype=bool), k=klen - qlen - window
            )
        mask = tril if mask is None else (mask & tril)
    elif window is not None:
        raise ValueError("a window is a causal band: causal=False")
    def over_heads(mask):
        """``mask`` over [b, kv_heads, group, q_len, kv_len]."""
        return mask[None, None, None] if mask.ndim == 2 else mask[:, :, None]

    if mask is not None:
        scores = jnp.where(over_heads(mask), scores, NEG_INF)
    # explicit online-softmax form; p hard-zeroed under the mask so a
    # fully-masked row yields zeros (not the mean of V)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    if mask is not None:
        p = jnp.where(over_heads(mask), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p / l_safe, vf)
    out = out.reshape(b, qlen, h, v.shape[3]).astype(q.dtype)
    if not return_lse:
        return out
    lse = (m + jnp.log(l_safe))[..., 0]  # [b, kvh, group, qlen]
    lse = jnp.where(l[..., 0] == 0.0, NEG_INF, lse)
    lse = lse.reshape(b, h, qlen)
    return out, lse


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "window"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
    q_rope: Optional[jax.Array] = None,
    k_rope: Optional[jax.Array] = None,
    selected: Optional[jax.Array] = None,
) -> jax.Array:
    """Memory-efficient attention: Pallas kernel on TPU; off the TPU
    (CPU tests, rehearsals) the dense reference, which no TPU run
    reaches at a shape the kernel takes.

    Layout [batch, seq, heads, head_dim] (the models' native layout);
    v, and so the result, may have a width of its own, and the default
    ``scale`` is q and k's ``head_dim ** -0.5``.
    ``block_q``/``block_k`` cap the kernel block sizes; the GQA group
    folds into the kernel's matmul rows, so the effective q-block is
    ``group * block_q`` rows. The blocks are ``ops/tuning.py``'s static
    rules on the shapes (the pair, and past a group of 8 a wider key
    block for the forward kernel), and ``tuning.last_selection()``
    names them and the form the backward takes.
    ``window`` (causal only, static in the kernel): query i sees key
    j iff ``j <= i`` and ``i - j < window``.

    Latent attention hands a head's q and k in the parts its products
    make: ``q`` and ``k`` the un-rotated columns, ``q_rope`` [batch,
    seq, heads, r] and ``k_rope`` [batch, seq, 1, r] the rotated ones,
    the key's one for every head. What a caller hands decides what
    runs: with the parts the kernels read them as they are, with whole
    q and k the kernels are the ones they always were.

    ``selected`` (bool [batch, kv_heads, seq, blocks], causal only;
    ops/sparse_attention.py makes it and calls this entry): query t
    of every head of a kv head sees key j iff ``j <= t`` and it
    selected j's block of ``seq / blocks`` adjacent keys. One more
    operand of the same kernels, which no gradient reaches: a trace
    names them, as every call of this function's, ``flash_attention``.
    """
    if not _use_pallas(q, k):
        mask = None
        if selected is not None:
            mask = jnp.repeat(
                selected, q.shape[1] // selected.shape[-1], axis=-1)
        return mha_reference(
            q, k, v, causal=causal, scale=scale, window=window,
            q_rope=q_rope, k_rope=k_rope, mask=mask,
        )
    from dlrover_tpu.ops.pallas.flash_attention import (
        backward_form,
        flash_attention_tpu,
    )

    seq, group = q.shape[1], q.shape[2] // k.shape[2]
    blocks = tuning.heuristic_blocks(seq, group, block_q, block_k)
    if blocks is None:
        # a dense [s, s] fallback here would pass every check and
        # cost the run its memory and its speed in silence
        raise ValueError(
            f"no kernel blocks tile seq={seq} under caps "
            f"block_q={block_q} block_k={block_k}"
        )
    bq, bk = blocks
    fwd_bk = tuning.forward_key_block(
        seq, group, blocks, block_k, window,
        None if selected is None else seq // selected.shape[-1],
    )
    head_dim = q.shape[3] + (0 if q_rope is None else q_rope.shape[3])
    tuning.record(
        kernel="flash_attention", seq=seq, head_dim=head_dim,
        gqa_group=group, dtype=jnp.dtype(q.dtype).name, causal=causal,
        block_q=bq, block_k=bk, window=window,
        backward=backward_form(group, seq, max(head_dim, v.shape[3])),
        # the forward kernel's key block where it has one of its own
        **({} if fwd_bk == bk else {"fwd_block_k": fwd_bk}),
        # v's width where it is not q and k's (latent attention), and
        # how many of q and k's columns came as rotated parts
        **({} if v.shape[3] == head_dim else {"v_head_dim": v.shape[3]}),
        **({} if q_rope is None else {"rope_head_dim": q_rope.shape[3]}),
    )
    return flash_attention_tpu(
        q, k, v, causal=causal, scale=scale, block_q=bq, block_k=bk,
        window=window, q_rope=q_rope, k_rope=k_rope, selected=selected,
        fwd_block_k=fwd_bk,
    )


def _use_pallas(q: jax.Array, k: jax.Array) -> bool:
    if jax.default_backend() != "tpu":
        return False
    # kernel tiling constraints: lanes divide head_dim (64 = half-lane
    # still wins, measured 2x over XLA), seq divides into >=128 blocks;
    # the kernel also assumes kv_len == q_len (cross-attention falls back)
    d = q.shape[-1]
    s = q.shape[1]
    return d % 64 == 0 and s % 128 == 0 and k.shape[1] == s


def make_sharded_attention(mesh, q_spec, kv_spec, causal: bool = True):
    """An ``attn_fn(q, k, v)`` for a GSPMD-partitioned step: the same
    ``flash_attention`` under ``shard_map``, because a Pallas kernel
    cannot be partitioned automatically. ``q_spec``/``kv_spec`` split
    the batch dim (and the heads dim over a tensor axis); the sequence
    stays whole, so no collective is needed inside. A dim its mesh
    axes do not divide is left whole instead."""
    from jax.sharding import PartitionSpec as P

    from dlrover_tpu.parallel.compat import shard_map
    from dlrover_tpu.parallel.sharding import fit_spec

    def attn_fn(q, k, v, window=None, q_rope=None, k_rope=None):
        qp = list(fit_spec(q_spec, q.shape, mesh))
        kp = list(fit_spec(kv_spec, k.shape, mesh))
        if qp[2] is None or kp[2] is None:
            qp[2] = kp[2] = None  # heads split together or not at all
        kp[0] = qp[0]
        qs, ks = P(*qp), P(*kp)
        operands, specs = (q, k, v), (qs, ks, ks)
        if q_rope is not None:
            # the one rotated key is whole wherever its heads are
            operands += (q_rope, k_rope)
            specs += (qs, P(kp[0], kp[1], None, None))

        def attend(q, k, v, q_rope=None, k_rope=None):
            return flash_attention(
                q, k, v, causal=causal, window=window,
                q_rope=q_rope, k_rope=k_rope,
            )

        return shard_map(
            attend, mesh=mesh, in_specs=specs, out_specs=qs,
            check_vma=False,
        )(*operands)

    return attn_fn
