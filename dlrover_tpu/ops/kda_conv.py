"""What stands between a delta-rule layer's q, k and v projections
and its scan (``KimiDeltaAttention``'s short convolutions): a causal
depthwise convolution over a few taps along the sequence, ``silu``,
and for q and k the l2 norm of each head.

``x`` [batch, seq, heads x d] as the projection wrote it, ``w``
[heads x d, taps] a channel's taps, oldest first::

    a[t, c] = sum_j w[c, j] x[t - (taps - 1) + j, c]   # x = 0 before t = 0
    a[t, c] += bias[c]                                 # where one is given
    s = silu(a) = a * sigmoid(a)
    n = s * rsqrt(sum over a head's d columns of s^2 + L2_NORM_EPS)

in float32, rounded once to ``x.dtype``: ``n`` where ``l2_heads``
(the number of heads) is given, ``s`` where it is not. A sequence is
a row of the batch, so nothing crosses a sequence's start. The bias
(a Mamba-2 layer's ``use_conv_bias``; a delta-rule layer has none) is
a float32 number a channel, added before ``silu``. On the TPU
one Pallas pass forward and one backward (ops/pallas/kda_conv.py);
elsewhere, and as the tests' other side, the shifted multiply-adds of
``ops/short_conv.py causal_taps`` and the norm through a view that
names a head's columns, in ``jax.numpy``.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.short_conv import causal_taps

#: added to a head's sum of squares before the root, in ``l2norm``
L2_NORM_EPS = 1e-6

#: rows of a float32 tile on the TPU: ``[s, columns]`` lies in tiles of
#: (8, 128)
_TILE_ROWS = 8


def l2norm(x):
    """``x`` [..., d] float32 over its last axis's length."""
    return x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + L2_NORM_EPS
    )


def heads_apart(x, heads):
    """Rows ``x`` [b, s, heads x d] with a head's columns an axis of
    their own, for a reduction over one head: ``[b, s / 8, 8, heads,
    d]`` (``[b, s, 1, heads, d]`` where 8 does not divide ``s``). The
    eight positions are there for the TPU's sake: a float32 ``[s,
    heads x 128]`` lies in tiles of (8 rows, 128 columns), which this
    shape names axis by axis, so the compiler takes the reshape for
    the same bytes and the reduction and the multiply by its result
    join the fusions on either side. ``[b, s, heads, d]`` tiles
    (heads, d): other bytes, a pass over the array each way and the
    factor written out at full width between them (PERF.md, PR 45)."""
    b, s, width = x.shape
    rows = _TILE_ROWS if s % _TILE_ROWS == 0 else 1
    return x.reshape(b, s // rows, rows, heads, width // heads)


def _use_pallas(x: jax.Array, w: jax.Array, l2_heads) -> bool:
    if jax.default_backend() != "tpu":
        return False
    from dlrover_tpu.ops.pallas.kda_conv import tiles_the_kernel

    return tiles_the_kernel(x.shape, w.shape, l2_heads)


def conv_silu_norm_plain(x: jax.Array, w: jax.Array, l2_heads=None,
                         bias=None):
    """The equations above as they stand, in float32, rounded once."""
    a = causal_taps(x.astype(jnp.float32), w)
    if bias is not None:
        a = a + bias.astype(jnp.float32)
    s = jax.nn.silu(a)
    if l2_heads:
        s = l2norm(heads_apart(s, l2_heads)).reshape(x.shape)
    return s.astype(x.dtype)


def _count(path: str):
    """Say, at trace time, which path a call of the entry took: the
    counters of docs/TELEMETRY.md."""
    from dlrover_tpu.telemetry.registry import counter

    counter(
        f"kda_conv_{path}_calls",
        "calls of a delta-rule layer's convolution, silu and l2 norm "
        f"traced on the {path} path",
    ).inc()


def conv_silu_norm(x: jax.Array, w: jax.Array, l2_heads=None, bias=None):
    """``[batch, seq, heads x d]`` and ``[heads x d, taps]`` to
    ``[batch, seq, heads x d]``; with ``l2_heads`` heads, each head's
    columns of unit length; with ``bias`` [heads x d], a channel's
    number added ahead of ``silu``."""
    if w.shape[0] != x.shape[-1] or (l2_heads and w.shape[0] % l2_heads) or (
            bias is not None and bias.shape != w.shape[:1]):
        raise ValueError(
            f"taps of {w.shape[0]} channels for rows of {x.shape[-1]} "
            f"in {l2_heads or 'no'} heads"
            + ("" if bias is None else f", a bias of {bias.shape}")
        )
    if _use_pallas(x, w, l2_heads):
        from dlrover_tpu.ops.pallas.kda_conv import (
            kda_conv_bias_tpu, kda_conv_tpu,
        )

        _count("kernel")
        if bias is None:
            return kda_conv_tpu(x, w, l2_heads)
        return kda_conv_bias_tpu(x, w, bias, l2_heads)
    _count("plain")
    return conv_silu_norm_plain(x, w, l2_heads, bias)
