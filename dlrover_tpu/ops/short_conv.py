"""The gated short convolution of a convolution/attention hybrid
(``Lfm2ShortConv``): ``y = C * conv(B * u)``, the three gates the
thirds of one projection's result, the convolution depthwise and
causal over a few taps along the sequence.

``bcu`` [batch, seq, 3 x hidden] holds ``B``, ``C`` and ``u`` side
by side, ``w`` [hidden, taps] a channel's taps, oldest first::

    v = B * u
    c[t, d] = sum_j w[d, j] v[t - (taps - 1) + j, d]   # v = 0 before t = 0
    y = C * c

No bias, no activation. A sequence is a row of the batch, so nothing
crosses a sequence's start. On the TPU one Pallas pass forward and
one backward (ops/pallas/short_conv.py); elsewhere, and as the tests'
other side, ``taps`` shifted multiply-adds in ``jax.numpy`` (a
depthwise convolution of ``hidden`` groups has no business on the
MXU, so no ``conv_general_dilated``).
"""

import jax
import jax.numpy as jnp


def _use_pallas(bcu: jax.Array, w: jax.Array) -> bool:
    if jax.default_backend() != "tpu":
        return False
    from dlrover_tpu.ops.pallas.short_conv import tiles_the_kernel

    return tiles_the_kernel(bcu.shape, w.shape)


def causal_taps(v: jax.Array, w: jax.Array) -> jax.Array:
    """The depthwise causal convolution alone: ``v`` [batch, seq,
    channels] float32 against ``w`` [channels, taps], a channel's
    taps oldest first, as ``taps`` shifted multiply-adds."""
    seq, taps = v.shape[1], w.shape[1]
    wf = w.astype(jnp.float32)
    acc = jnp.zeros_like(v)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads the row ``back`` earlier
        acc = acc + wf[:, j] * jnp.pad(
            v, ((0, 0), (back, 0), (0, 0))
        )[:, :seq]
    return acc


def gated_short_conv_plain(bcu: jax.Array, w: jax.Array) -> jax.Array:
    """The equations above as they stand, in float32, rounded once."""
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    return (c * causal_taps(b * u, w)).astype(bcu.dtype)


def gated_short_conv(bcu: jax.Array, w: jax.Array) -> jax.Array:
    """``[batch, seq, 3 x hidden]`` and ``[hidden, taps]`` to
    ``[batch, seq, hidden]``, the two gates inside."""
    if 3 * w.shape[0] != bcu.shape[-1]:
        raise ValueError(
            f"taps of {w.shape[0]} channels for a projection of "
            f"{bcu.shape[-1]}: not three times as wide"
        )
    if _use_pallas(bcu, w):
        from dlrover_tpu.ops.pallas.short_conv import short_conv_tpu

        return short_conv_tpu(bcu, w)
    return gated_short_conv_plain(bcu, w)
