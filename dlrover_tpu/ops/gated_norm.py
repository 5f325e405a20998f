"""What stands between a Mamba-2 mixer's scan and its output
projection (``MambaRMSNormGated`` with ``norm_before_gate`` false):
the gate ``silu(z)`` on the scan's result, then an RMSNorm over each
group's columns.

``o`` and ``z`` [batch, seq, groups x w] as the scan and the
projection wrote them, ``scale`` [groups x w] a column's learned
factor::

    g = o * silu(z)
    r = rsqrt(mean over a group's w columns of g^2 + eps)
    y = g * r * scale

in float32, rounded once to ``o.dtype``. On the TPU one Pallas pass
forward and one backward (ops/pallas/gated_norm.py has the gradients'
equations); elsewhere, and as the tests' other side, the same lines in
``jax.numpy`` through a view that names a group's columns
(``ops/kda_conv.py heads_apart``).
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.kda_conv import heads_apart


def _use_pallas(o: jax.Array, groups: int) -> bool:
    if jax.default_backend() != "tpu":
        return False
    from dlrover_tpu.ops.pallas.gated_norm import tiles_the_kernel

    return tiles_the_kernel(o.shape, groups)


def gated_group_norm_plain(o: jax.Array, z: jax.Array, scale: jax.Array,
                           groups: int, eps: float):
    """The equations above as they stand, in float32, rounded once."""
    gated = heads_apart(
        o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)), groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return (normed.reshape(o.shape) * scale).astype(o.dtype)


def _count(path: str):
    """Say, at trace time, which path a call of the entry took: the
    counters of docs/TELEMETRY.md."""
    from dlrover_tpu.telemetry.registry import counter

    counter(
        f"gated_norm_{path}_calls",
        "calls of a Mamba-2 mixer's gate and grouped norm traced on "
        f"the {path} path",
    ).inc()


def gated_group_norm(o: jax.Array, z: jax.Array, scale: jax.Array,
                     groups: int, eps: float):
    """``o`` and ``z`` [batch, seq, groups x w] and ``scale``
    [groups x w] to ``[batch, seq, groups x w]``: ``o`` through the
    gate ``silu(z)``, each group's columns over their root mean
    square, times ``scale``."""
    if (z.shape != o.shape or scale.shape != o.shape[-1:]
            or o.shape[-1] % groups):
        raise ValueError(
            f"a gate of {z.shape} and a scale of {scale.shape} for rows "
            f"of {o.shape} in {groups} groups"
        )
    if _use_pallas(o, groups):
        from dlrover_tpu.ops.pallas.gated_norm import gated_norm_tpu

        _count("kernel")
        return gated_norm_tpu(o, z, scale, groups, eps)
    _count("plain")
    return gated_group_norm_plain(o, z, scale, groups, eps)
