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
#: and its columns
_LANE = 128


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


def head_sums_by_product(x, heads):
    """``head_sums`` with no view of a head's columns: the rows times
    a ``[heads x d, heads]`` matrix of ones where a column is a
    head's, and the sums back over the columns by its transpose.
    Float32 products at the highest precision: a term times one is
    the term, so the sums are the view's up to the order they are
    added in."""
    width = x.shape[-1]
    of_head = (
        jnp.arange(width)[:, None] // (width // heads)
        == jnp.arange(heads)[None, :]
    ).astype(jnp.float32)
    highest = jax.lax.Precision.HIGHEST
    sums = jnp.einsum("bsw,wh->bsh", x, of_head, precision=highest)
    return jnp.einsum("bsh,wh->bsw", sums, of_head, precision=highest)


def _sums_by_product(d: int) -> bool:
    """Whether ``head_sums`` of heads ``d`` wide takes the products:
    on the TPU, a head of no whole lane tiles."""
    return d % _LANE != 0 and jax.default_backend() == "tpu"


def head_sums(x, heads):
    """The sum over each head's columns of the float32 rows ``x`` [b,
    s, heads x d], at every column of the head: ``[b, s, heads x d]``.
    Through ``heads_apart``'s view, but on the TPU for a head that is
    no whole lane tiles (96 columns, or 192): there the view is other
    bytes than the rows, the compiler copies the array into it and
    back around the reduction (20 ms a pass over ``[16384, 2880]``
    float32: PERF.md section 6, PR 70), and two thin products on the
    MXU make the same sums from the rows as they lie."""
    if _sums_by_product(x.shape[-1] // heads):
        return head_sums_by_product(x, heads)
    apart = heads_apart(x, heads)
    return jnp.broadcast_to(
        jnp.sum(apart, axis=-1, keepdims=True), apart.shape
    ).reshape(x.shape)


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
        # ``l2norm``'s line on the rows as they lie
        s = s * jax.lax.rsqrt(head_sums(s * s, l2_heads) + L2_NORM_EPS)
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
