"""Pallas TPU kernels of a Mamba-2 mixer's gate and grouped RMSNorm
(ops/gated_norm.py has the equations): one pass forward, one
backward, over the rows ``[batch, seq, groups x w]`` that the scan's
kernels write and the output projection reads.

Forward reads ``o`` and ``z`` and writes ``y``: 6 bytes a token and
column in bf16. Backward reads ``o``, ``z`` and ``dy``, writes ``do``
and ``dz``, 10 bytes, and sums ``d scale`` in float32 over the grid; it
keeps nothing of the forward but its operands and makes ``g`` and the
norm's factor ``r`` again::

    dn = dy * scale
    d scale = sum over rows of dy * g * r
    dg = r * dn - g * r^3 * mean_group(dn * g)
    do = dg * silu(z)
    dz = dg * o * sigmoid(z) * (1 + z * (1 - sigmoid(z)))

A grid step is a block of time steps of one sequence at one group's
lanes, walked ``WALK_ROWS`` rows at a time in a rolled loop: a group
is whole lane tiles, so its mean is a lane reduction and no relayout,
and the kernel's text is one walk's however long the block. The
groups are the grid's outermost axis, so a group's block of ``d
scale`` stays resident while the batch and the sequence go by; it is
kept as a tile's eight rows of partial sums, which the caller adds,
so the walk adds whole tiles and reduces nothing across sublanes.

Both calls are made inside one jitted function, ``gated_norm``: a
device trace names a Pallas call after the innermost jitted function
that holds it, and no reader of the benchmark goes by that name.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.short_conv import TILE, _rows

#: lanes of a tile: a group's width is whole tiles
LANE = 128
#: time steps of a block, in both passes, and of one walk inside it
#: (benchmarks/profile_gated_norm.py reads them on the chip)
BLOCK_ROWS = 512
WALK_ROWS = {"forward": 64, "backward": 32}
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


def _gated(o_ref, z_ref, at, eps):
    """``(o, z, sigmoid(z), g, r)`` of the rows ``at`` in float32:
    ``r`` the norm's factor [rows, 1]."""
    o, z = o_ref[at, :].astype(F32), z_ref[at, :].astype(F32)
    gate = jax.nn.sigmoid(z)
    g = o * (z * gate)
    r = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return o, z, gate, g, r


def _fwd_kernel(o_ref, z_ref, scale_ref, y_ref, *, walk, eps):
    def one(at):
        _, _, _, g, r = _gated(o_ref, z_ref, at, eps)
        y_ref[at, :] = (g * r * scale_ref[...]).astype(y_ref.dtype)

    _each_walk(o_ref.shape[0], walk, one)


def _bwd_kernel(o_ref, z_ref, dy_ref, scale_ref, do_ref, dz_ref, ds_ref, *,
                walk, eps):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def one(at):
        o, z, gate, g, r = _gated(o_ref, z_ref, at, eps)
        dy = dy_ref[at, :].astype(F32)
        n = g * r
        by_row = dy * n
        ds_ref[...] += sum(
            by_row[tile:tile + TILE] for tile in range(0, walk, TILE))
        dn = dy * scale_ref[...]
        dg = r * (dn - n * (r * jnp.mean(dn * g, axis=-1, keepdims=True)))
        do_ref[at, :] = (dg * (z * gate)).astype(do_ref.dtype)
        dz_ref[at, :] = (
            dg * o * gate * (1.0 + z * (1.0 - gate))).astype(dz_ref.dtype)

    _each_walk(o_ref.shape[0], walk, one)


def _whole(g, b, t):
    return (b, t, g)


def _a_group(g, b, t):
    return (0, g)


def _blocks(o, groups, rows, walk):
    """``(the rows' spec, the scale's, walk, grid)`` of a pass whose
    walk is ``walk`` rows."""
    batch, seq, width = o.shape
    group = width // groups
    rows = _rows(seq, rows or BLOCK_ROWS)
    return (
        pl.BlockSpec((None, rows, group), _whole),
        pl.BlockSpec((1, group), _a_group),
        min(walk, rows),
        (groups, batch, seq // rows),
    )


def _forward(o, z, scale_row, groups, eps, rows, walk):
    whole, a_group, walk, grid = _blocks(
        o, groups, rows, walk or WALK_ROWS["forward"])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, walk=walk, eps=eps),
        grid=grid,
        in_specs=[whole, whole, a_group],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        interpret=_interpret(),
    )(o, z, scale_row)


def _backward(o, z, scale_row, dy, groups, eps, rows, walk):
    whole, a_group, walk, grid = _blocks(
        o, groups, rows, walk or WALK_ROWS["backward"])
    group = o.shape[-1] // groups
    return pl.pallas_call(
        functools.partial(_bwd_kernel, walk=walk, eps=eps),
        grid=grid,
        in_specs=[whole, whole, whole, a_group],
        out_specs=[
            whole, whole,
            # one block a group, through the batch and the sequence:
            # the scale's gradient, a tile's rows of partial sums
            pl.BlockSpec((TILE, group), _a_group),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(o.shape, o.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((TILE, o.shape[-1]), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
    )(o, z, dy, scale_row)


@functools.partial(
    jax.jit, static_argnames=("groups", "eps", "rows", "walk"))
def gated_norm(o, z, scale, dy=None, *, groups, eps, rows=None, walk=None):
    """The forward kernel's result, or with its cotangent ``dy`` the
    backward kernel's ``(do, dz, d scale)``. One jitted name for both,
    which is what a device trace calls them. ``rows`` caps a block's
    time steps and ``walk`` gives one walk's (``BLOCK_ROWS``,
    ``WALK_ROWS`` where None)."""
    scale_row = scale.astype(F32)[None]  # [1, groups x w]: lanes
    if dy is None:
        return _forward(o, z, scale_row, groups, eps, rows, walk)
    do, dz, ds = _backward(o, z, scale_row, dy, groups, eps, rows, walk)
    return do, dz, ds.sum(axis=0).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_norm_tpu(o, z, scale, groups, eps):
    return gated_norm(o, z, scale, groups=groups, eps=eps)


def _vjp_fwd(o, z, scale, groups, eps):
    return gated_norm(o, z, scale, groups=groups, eps=eps), (o, z, scale)


def _vjp_bwd(groups, eps, saved, dy):
    return gated_norm(*saved, dy, groups=groups, eps=eps)


gated_norm_tpu.defvjp(_vjp_fwd, _vjp_bwd)
