"""What stands between a scan and its output projection: a gate and
an RMSNorm over each group's columns, in the one order or the other.

``o`` and ``z`` [batch, seq, groups x w] as the scan and the
projection wrote them. A Mamba-2 mixer's (``MambaRMSNormGated`` with
``norm_before_gate`` false), ``gated_group_norm``: the gate
``silu(z)`` on the scan's result, then the norm, ``scale`` [groups x
w] a column's learned factor::

    g = o * silu(z)
    r = rsqrt(mean over a group's w columns of g^2 + eps)
    y = g * r * scale

A linear-attention layer's, ``head_norm_gate``: the norm a head, then
a sigmoid gate, ``scale`` [d] one for every head and ``bias`` [heads x
d] or None::

    r   = rsqrt(mean over a head's d columns of o^2 + eps)
    sig = sigmoid(z + bias)
    y   = o * r * scale * sig

A Gated DeltaNet layer's, ``head_norm_silu``: the norm a head, then
``silu`` of the gate's pre-activation, ``scale`` [d] one for every
head and no bias::

    r = rsqrt(mean over a head's d columns of o^2 + eps)
    y = o * r * scale * silu(z)

On the TPU one Pallas pass forward and one backward, in float32 and
rounded once to ``o.dtype`` (ops/pallas/gated_norm.py has the one
frame, the three bodies and the gradients' equations); elsewhere,
where a head is no whole lane tiles (192 is a tile and a half), and as
the tests' other side, the same lines in ``jax.numpy`` through a view
that names a group's columns (``ops/kda_conv.py heads_apart``): the
mixer's rounded once too, the heads' where the model's own lines
rounded before these took them over.
"""

import jax
import jax.numpy as jnp

from dlrover_tpu.ops.kda_conv import head_sums, heads_apart


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


def head_norm_gate_plain(o: jax.Array, z: jax.Array, scale: jax.Array,
                         bias, eps: float):
    """The equations above as ``models/llama.py _operator_out`` had
    them: the norm and the gate in float32, each rounded to
    ``o.dtype`` before their product, so off the TPU a bf16 program
    reads to the bit what it read (its check against the float32
    reference at the tiny sizes is the noise of flipped expert choices
    and follows every rounding: PERF.md section 6, PR 67)."""
    heads = heads_apart(o.astype(jnp.float32), o.shape[-1] // scale.shape[0])
    normed = heads * jax.lax.rsqrt(
        jnp.mean(heads * heads, axis=-1, keepdims=True) + eps
    ) * scale.astype(jnp.float32)
    z = z.astype(jnp.float32)
    gate = jax.nn.sigmoid(z if bias is None else z + bias)
    return normed.astype(o.dtype).reshape(o.shape) * gate.astype(o.dtype)


def head_norm_silu_plain(o: jax.Array, z: jax.Array, scale: jax.Array,
                         eps: float):
    """The equations above as they stand, in float32, rounded once;
    a head's mean square through ``ops/kda_conv.py head_sums``, which
    on the TPU takes no view of a head of 192 columns."""
    d = scale.shape[0]
    o = o.astype(jnp.float32)
    normed = o * jax.lax.rsqrt(
        head_sums(o * o, o.shape[-1] // d) / d + eps
    ) * jnp.tile(scale.astype(jnp.float32), o.shape[-1] // d)
    return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


#: what a counter's help calls each entry
_ENTRIES = {
    "gated_norm": "a Mamba-2 mixer's gate and grouped norm",
    "head_norm_gate": "a linear-attention layer's heads' norm and gate",
    "head_norm_silu": "a Gated DeltaNet layer's heads' norm and silu gate",
}


def _count(entry: str, path: str):
    """Say, at trace time, which path a call of an entry took: the
    counters of docs/TELEMETRY.md."""
    from dlrover_tpu.telemetry.registry import counter

    counter(
        f"{entry}_{path}_calls",
        f"calls of {_ENTRIES[entry]} traced on the {path} path",
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

        _count("gated_norm", "kernel")
        return gated_norm_tpu(o, z, (scale,), "gate, norm", groups, eps)
    _count("gated_norm", "plain")
    return gated_group_norm_plain(o, z, scale, groups, eps)


def head_norm_gate(o: jax.Array, z: jax.Array, scale: jax.Array, bias,
                   eps: float):
    """``o`` and ``z`` [batch, seq, heads x d], ``scale`` [d] and
    ``bias`` [heads x d] or None to ``[batch, seq, heads x d]``: each
    head's columns of ``o`` over their root mean square, times
    ``scale``, through the gate ``sigmoid(z + bias)``."""
    width = o.shape[-1]
    if (z.shape != o.shape or scale.ndim != 1 or width % scale.shape[0]
            or (bias is not None and bias.shape != (width,))):
        raise ValueError(
            f"a gate of {z.shape}, a scale of {scale.shape} and a bias of "
            f"{None if bias is None else bias.shape} for rows of {o.shape}"
        )
    heads = width // scale.shape[0]
    if _use_pallas(o, heads):
        from dlrover_tpu.ops.pallas.gated_norm import gated_norm_tpu

        _count("head_norm_gate", "kernel")
        vectors = (scale,) if bias is None else (scale, bias)
        return gated_norm_tpu(o, z, vectors, "norm, gate", heads, eps)
    _count("head_norm_gate", "plain")
    return head_norm_gate_plain(o, z, scale, bias, eps)


def head_norm_silu(o: jax.Array, z: jax.Array, scale: jax.Array,
                   eps: float):
    """``o`` and ``z`` [batch, seq, heads x d] and ``scale`` [d] to
    ``[batch, seq, heads x d]``: each head's columns of ``o`` over
    their root mean square, times ``scale``, times ``silu(z)``: the
    norm first, then the gate."""
    width = o.shape[-1]
    if z.shape != o.shape or scale.ndim != 1 or width % scale.shape[0]:
        raise ValueError(
            f"a gate of {z.shape} and a scale of {scale.shape} for rows "
            f"of {o.shape}"
        )
    heads = width // scale.shape[0]
    if _use_pallas(o, heads):
        from dlrover_tpu.ops.pallas.gated_norm import gated_norm_tpu

        _count("head_norm_silu", "kernel")
        return gated_norm_tpu(o, z, (scale,), "norm, silu", heads, eps)
    _count("head_norm_silu", "plain")
    return head_norm_silu_plain(o, z, scale, eps)
