"""Pallas TPU kernels of a gate and a grouped RMSNorm between a scan
and its output projection (ops/gated_norm.py has the equations): one
pass forward, one backward, over the rows ``[batch, seq, groups x w]``
that the scan's kernels write and the projection reads. One frame (the
grid, the specs, the walk) and three bodies, ``BODIES``: a Mamba-2
mixer's gate and then a group's norm, a linear-attention layer's
norm a head and then its sigmoid gate, and a Gated DeltaNet layer's
norm a head and then ``silu`` of its gate.

Forward reads ``o`` and ``z`` and writes ``y``: 6 bytes a token and
column in bf16. Backward reads ``o``, ``z`` and ``dy``, writes ``do``
and ``dz``, 10 bytes, and sums the gradient of each of the body's
vectors (``scale``; a head's body ``bias`` too) in float32 over the
grid; it keeps nothing of the forward but its operands and makes the
gate and the norm's factor ``r`` again. The mixer's, ``g = o
silu(z)``::

    dn = dy * scale
    d scale = sum over rows of dy * g * r
    dg = r * dn - g * r^3 * mean_group(dn * g)
    do = dg * silu(z)
    dz = dg * o * sigmoid(z) * (1 + z * (1 - sigmoid(z)))

and the heads', ``sig = sigmoid(z + bias)``::

    dn = dy * scale * sig
    d scale = sum over rows of dy * o * r * sig
    do = r * dn - o * r^3 * mean_head(dn * o)
    dz = dy * o * r * scale * sig * (1 - sig);  d bias = sum over rows of dz

and the heads' with ``silu``, ``sil = silu(z) = z sigmoid(z)``, the
same lines with ``sil`` for ``sig`` but the last::

    dz = dy * o * r * scale * sigmoid(z) * (1 + z * (1 - sigmoid(z)))

A grid step is a block of time steps of one sequence at a run of whole
groups on the lanes (one group of 1,024; eight heads of 128), walked
``WALK_ROWS`` rows at a time in a rolled loop and a group at a time
inside a walk: a group is whole lane tiles, so its mean is a lane
reduction and no relayout, and the kernel's text is one walk's however
long the block. The lane blocks are the grid's outermost axis, so a
block of ``d scale`` stays resident while the batch and the sequence go
by; it is kept as a tile's eight rows of partial sums, which the
caller adds, so the walk adds whole tiles and reduces nothing across
sublanes.

Both calls are made inside one jitted function, ``gated_norm``: a
device trace names a Pallas call after the innermost jitted function
that holds it, and no reader of the benchmark goes by that name.
"""

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.short_conv import TILE, _rows

#: lanes of a tile: a group's width is whole tiles
LANE = 128
#: time steps of a block, in both passes, and of one walk inside it;
#: the most lanes of a block (benchmarks/profile_gated_norm.py reads
#: them on the chip)
BLOCK_ROWS = 512
WALK_ROWS = {"forward": 64, "backward": 32}
BLOCK_LANES = 1024
#: the widest group whose rows a walk holds
MOST_GROUP = 1024
F32 = jnp.float32


def tiles_the_kernel(o_shape, groups) -> bool:
    """Whether the kernels take these shapes: a group in whole lane
    tiles, time in whole blocks."""
    width = o_shape[-1]
    group = width // groups
    return (
        width % groups == 0 and group % LANE == 0 and group <= MOST_GROUP
        and _rows(o_shape[1], BLOCK_ROWS) is not None
    )


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _each_walk(rows, walk, body):
    """``body(at)`` for each ``walk`` rows ``at`` of a block, in a
    rolled loop (``ops/pallas/kda_conv.py _each_head``'s reason)."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * walk, walk), walk))
        return carry

    jax.lax.fori_loop(0, rows // walk, step, 0)


class Body(NamedTuple):
    """What a pass makes of a walk's rows at one group's lanes, ``at``,
    in float32, on the refs of a grid step's blocks. ``forward(at,
    o_ref, z_ref, vector_refs, y_ref, eps)`` writes ``y``;
    ``backward(at, o_ref, z_ref, dy_ref, vector_refs, do_ref, dz_ref,
    sum_refs, eps)`` writes ``do`` and ``dz`` and adds to each
    vector's resident sums the rows whose sum is its gradient
    (``_add_rows``)."""
    forward: Callable
    backward: Callable


def _add_rows(sum_ref, at, by_row):
    """A walk's rows added, a tile's eight at a time, to a vector's
    resident partial sums at the lanes of ``at``."""
    sum_ref[:, at[1]] += sum(
        by_row[tile:tile + TILE] for tile in range(0, by_row.shape[0], TILE))


def _gated(o_ref, z_ref, at, eps):
    """``(o, z, sigmoid(z), g, r)`` of the rows ``at`` in float32:
    ``r`` the norm's factor [rows, 1]."""
    o, z = o_ref[at].astype(F32), z_ref[at].astype(F32)
    gate = jax.nn.sigmoid(z)
    g = o * (z * gate)
    r = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return o, z, gate, g, r


def _gate_then_norm(at, o_ref, z_ref, vector_refs, y_ref, eps):
    scale_ref, = vector_refs
    _, _, _, g, r = _gated(o_ref, z_ref, at, eps)
    y_ref[at] = (g * r * scale_ref[:, at[1]]).astype(y_ref.dtype)


def _gate_then_norm_back(at, o_ref, z_ref, dy_ref, vector_refs, do_ref,
                         dz_ref, sum_refs, eps):
    (scale_ref,), (ds_ref,) = vector_refs, sum_refs
    o, z, gate, g, r = _gated(o_ref, z_ref, at, eps)
    dy = dy_ref[at].astype(F32)
    n = g * r
    _add_rows(ds_ref, at, dy * n)
    dn = dy * scale_ref[:, at[1]]
    dg = r * (dn - n * (r * jnp.mean(dn * g, axis=-1, keepdims=True)))
    do_ref[at] = (dg * (z * gate)).astype(do_ref.dtype)
    dz_ref[at] = (
        dg * o * gate * (1.0 + z * (1.0 - gate))).astype(dz_ref.dtype)


def _normed(o_ref, z_ref, bias_ref, at, eps):
    """``(n, r, sig)`` of the rows ``at`` in float32: a head's rows
    over their root mean square, the factor [rows, 1] and the gate."""
    o, z = o_ref[at].astype(F32), z_ref[at].astype(F32)
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    if bias_ref:
        z = z + bias_ref[0][:, at[1]]
    return o * r, r, jax.nn.sigmoid(z)


def _norm_then_gate(at, o_ref, z_ref, vector_refs, y_ref, eps):
    scale_ref, *bias_ref = vector_refs
    n, _, sig = _normed(o_ref, z_ref, bias_ref, at, eps)
    y_ref[at] = (n * scale_ref[:, at[1]] * sig).astype(y_ref.dtype)


def _norm_then_gate_back(at, o_ref, z_ref, dy_ref, vector_refs, do_ref,
                         dz_ref, sum_refs, eps):
    (scale_ref, *bias_ref), (ds_ref, *db_ref) = vector_refs, sum_refs
    n, r, sig = _normed(o_ref, z_ref, bias_ref, at, eps)
    dy = dy_ref[at].astype(F32)
    _add_rows(ds_ref, at, dy * n * sig)
    dn = dy * scale_ref[:, at[1]] * sig
    by_n = dn * n
    do_ref[at] = (r * (
        dn - n * jnp.mean(by_n, axis=-1, keepdims=True))).astype(do_ref.dtype)
    dz = by_n * (1.0 - sig)
    dz_ref[at] = dz.astype(dz_ref.dtype)
    if db_ref:
        _add_rows(db_ref[0], at, dz)


def _normed_silu(o_ref, z_ref, at, eps):
    """``(n, r, z, sigmoid(z))`` of the rows ``at`` in float32: a
    head's rows over their root mean square, the factor [rows, 1],
    the gate's pre-activation and its sigmoid."""
    o, z = o_ref[at].astype(F32), z_ref[at].astype(F32)
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * r, r, z, jax.nn.sigmoid(z)


def _norm_then_silu(at, o_ref, z_ref, vector_refs, y_ref, eps):
    scale_ref, = vector_refs
    n, _, z, sig = _normed_silu(o_ref, z_ref, at, eps)
    y_ref[at] = (n * scale_ref[:, at[1]] * (z * sig)).astype(y_ref.dtype)


def _norm_then_silu_back(at, o_ref, z_ref, dy_ref, vector_refs, do_ref,
                         dz_ref, sum_refs, eps):
    (scale_ref,), (ds_ref,) = vector_refs, sum_refs
    n, r, z, sig = _normed_silu(o_ref, z_ref, at, eps)
    dy = dy_ref[at].astype(F32)
    sil = z * sig
    _add_rows(ds_ref, at, dy * n * sil)
    by_scale = dy * scale_ref[:, at[1]]
    dn = by_scale * sil
    do_ref[at] = (r * (
        dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))).astype(do_ref.dtype)
    dz_ref[at] = (
        by_scale * n * sig * (1.0 + z * (1.0 - sig))).astype(dz_ref.dtype)


#: a body by the name a caller gives it: ``gate, norm`` a Mamba-2
#: mixer's, ``norm, gate`` a linear-attention layer's heads' (with a
#: second vector, the gate's bias), ``norm, silu`` a Gated DeltaNet
#: layer's heads'
BODIES = {
    "gate, norm": Body(_gate_then_norm, _gate_then_norm_back),
    "norm, gate": Body(_norm_then_gate, _norm_then_gate_back),
    "norm, silu": Body(_norm_then_silu, _norm_then_silu_back),
}


def _each_group(ref, group, walk, one):
    """``one(at)`` for each ``walk`` rows of a block like ``ref``'s and
    each group's lanes in them (all of them where the block is one
    group)."""
    lanes = ref.shape[1]
    groups = [slice(None)] if lanes == group else [
        pl.ds(start, group) for start in range(0, lanes, group)]

    def a_walk(rows):
        for lanes in groups:
            one((rows, lanes))

    _each_walk(ref.shape[0], walk, a_walk)


def _fwd_kernel(o_ref, z_ref, *refs, body, group, walk, eps):
    *vector_refs, y_ref = refs
    _each_group(y_ref, group, walk, lambda at: body.forward(
        at, o_ref, z_ref, vector_refs, y_ref, eps))


def _bwd_kernel(o_ref, z_ref, dy_ref, *refs, body, group, walk, eps):
    # the body's vectors, then ``do``, ``dz`` and a sum a vector
    vectors = (len(refs) - 2) // 2
    vector_refs, (do_ref, dz_ref, *sum_refs) = (
        refs[:vectors], refs[vectors:])

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        for ref in sum_refs:
            ref[...] = jnp.zeros_like(ref)

    _each_group(do_ref, group, walk, lambda at: body.backward(
        at, o_ref, z_ref, dy_ref, vector_refs, do_ref, dz_ref, sum_refs,
        eps))


def _whole(g, b, t):
    return (b, t, g)


def _a_block(g, b, t):
    return (0, g)


def _blocks(o, groups, rows, walk):
    """``(the rows' spec, a vector's, a vector's sums', a group's
    lanes, walk, grid)`` of a pass whose walk is ``walk`` rows."""
    batch, seq, width = o.shape
    group = width // groups
    # the most whole groups within the cap that divide the width
    lanes = group * max(
        k for k in range(1, groups + 1)
        if groups % k == 0 and k * group <= max(BLOCK_LANES, group))
    rows = _rows(seq, rows or BLOCK_ROWS)
    return (
        pl.BlockSpec((None, rows, lanes), _whole),
        pl.BlockSpec((1, lanes), _a_block),
        # one block a lane block, through the batch and the sequence:
        # a vector's gradient, a tile's rows of partial sums
        pl.BlockSpec((TILE, lanes), _a_block),
        group,
        min(walk, rows),
        (width // lanes, batch, seq // rows),
    )


def _forward(body, o, z, vectors, groups, eps, rows, walk):
    whole, a_row, _, group, walk, grid = _blocks(
        o, groups, rows, walk or WALK_ROWS["forward"])
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, body=body, group=group, walk=walk, eps=eps),
        grid=grid,
        in_specs=[whole, whole] + [a_row] * len(vectors),
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        interpret=_interpret(),
    )(o, z, *vectors)


def _backward(body, o, z, vectors, dy, groups, eps, rows, walk):
    whole, a_row, sums, group, walk, grid = _blocks(
        o, groups, rows, walk or WALK_ROWS["backward"])
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, body=body, group=group, walk=walk, eps=eps),
        grid=grid,
        in_specs=[whole, whole, whole] + [a_row] * len(vectors),
        out_specs=[whole, whole] + [sums] * len(vectors),
        out_shape=[
            jax.ShapeDtypeStruct(o.shape, o.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
        ] + [jax.ShapeDtypeStruct((TILE, o.shape[-1]), F32)] * len(vectors),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
    )(o, z, dy, *vectors)


@functools.partial(
    jax.jit, static_argnames=("body", "groups", "eps", "rows", "walk"))
def gated_norm(o, z, vectors, dy=None, *, body, groups, eps, rows=None,
               walk=None):
    """The forward kernel's result, or with its cotangent ``dy`` the
    backward kernel's ``(do, dz, the vectors' gradients)``. One jitted
    name for both, which is what a device trace calls them. ``body``
    names one of ``BODIES`` and ``vectors`` are its learned ones, a
    column's factor each or, ``[w]``, the same for every group.
    ``rows`` caps a block's time steps and ``walk`` gives one walk's
    rows (``BLOCK_ROWS``, ``WALK_ROWS`` where None)."""
    width = o.shape[-1]
    # [1, groups x w]: lanes
    lane_rows = [
        jnp.tile(v.astype(F32), width // v.shape[0])[None] for v in vectors]
    blocks = (groups, eps, rows, walk)
    if dy is None:
        return _forward(BODIES[body], o, z, lane_rows, *blocks)
    do, dz, *sums = _backward(BODIES[body], o, z, lane_rows, dy, *blocks)
    return do, dz, tuple(
        s.reshape(-1, v.shape[0]).sum(axis=0).astype(v.dtype)
        for s, v in zip(sums, vectors))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gated_norm_tpu(o, z, vectors, body, groups, eps):
    return gated_norm(o, z, vectors, body=body, groups=groups, eps=eps)


def _vjp_fwd(o, z, vectors, body, groups, eps):
    return gated_norm(
        o, z, vectors, body=body, groups=groups, eps=eps), (o, z, vectors)


def _vjp_bwd(body, groups, eps, saved, dy):
    return gated_norm(*saved, dy, body=body, groups=groups, eps=eps)


gated_norm_tpu.defvjp(_vjp_fwd, _vjp_bwd)
