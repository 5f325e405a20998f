"""Pallas TPU kernels of the gated short convolution
(ops/short_conv.py has the equations): one pass forward, one
backward, both bound by the memory's speed.

Forward reads ``B, C, u`` and writes ``y``: 8 x hidden bytes a token
in bf16. Backward reads ``B, C, u, dy`` and writes ``dB, dC, du``, 14
x hidden bytes, and sums ``dw`` in float32 over the grid. A grid step
is a block of time steps of one sequence at the whole width, channels
on the lanes, walked a lane chunk at a time so that the float32
temporaries stay a few hundred KB; the rows a block's first outputs
need from before it (``taps - 1`` of ``v``), and in the backward pass
those its last need from after it (of ``dy * C``), come as a second,
``HALO``-row view of the same array, zeroed at a sequence's two ends:
a sequence is a row of the batch, and no view crosses it.

Both calls are made inside one jitted function, ``short_conv``: a
device trace names a Pallas call after the innermost jitted function
that holds it, and the benchmark's ``short_conv_ms`` tells the
kernels by that name.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of the view that brings a block its neighbours' edge rows: a
#: bf16 tile's sixteen, of which ``taps - 1`` are used
HALO = 16
#: rows of a float32 tile: shifted rows are patched a tile at a time
TILE = 8
#: time steps of a block, forward and backward (the backward holds
#: three results the size of its input), and the lanes of a chunk
BLOCK_ROWS = {"forward": 256, "backward": 128}
LANE_CHUNK = 512


def _largest_divisor(n, candidates):
    return next((c for c in candidates if n % c == 0), None)


def _rows(seq, cap):
    return _largest_divisor(
        seq, [r for r in (512, 256, 128, 64, 32, 16) if r <= cap]
    )


def _lanes(hidden):
    return _largest_divisor(hidden, (LANE_CHUNK, 256, 128))


def tiles_the_kernel(bcu_shape, w_shape) -> bool:
    """Whether the kernels take these shapes: channels in whole lane
    tiles, time in whole blocks, no more taps than a tile has rows."""
    hidden, taps = w_shape
    return (
        _lanes(hidden) is not None
        and _rows(bcu_shape[1], BLOCK_ROWS["backward"]) is not None
        and 1 <= taps <= TILE
    )


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _earlier(v, before, back):
    """``v[t - back]`` [rows, lanes] float32, the rows ahead of the
    block from ``before`` [TILE, lanes] (the TILE rows just before
    it). A rotation of the whole block, its first tile patched."""
    if back == 0:
        return v
    head = pltpu.roll(
        jnp.concatenate([before, v[:TILE]], axis=0), back, 0
    )[TILE:]
    return jnp.concatenate([head, pltpu.roll(v, back, 0)[TILE:]], axis=0)


def _later(g, after, ahead):
    """``g[t + ahead]``, the rows past the block from ``after`` (the
    TILE rows just after it)."""
    if ahead == 0:
        return g
    n = g.shape[0]
    tail = pltpu.roll(
        jnp.concatenate([g[n - TILE:], after], axis=0),
        2 * TILE - ahead, 0,
    )[:TILE]
    return jnp.concatenate(
        [pltpu.roll(g, n - ahead, 0)[:n - TILE], tail], axis=0
    )


def _f32(ref, rows, lanes):
    return ref[rows, lanes].astype(jnp.float32)


def _chunks(hidden):
    step = _lanes(hidden)
    return [
        (pl.ds(at, step), pl.ds(hidden + at, step),
         pl.ds(2 * hidden + at, step))
        for at in range(0, hidden, step)
    ]


def _fwd_kernel(bcu_ref, before_ref, w_ref, y_ref, *, taps, hidden):
    first = pl.program_id(1) == 0
    edge = slice(HALO - TILE, HALO)
    every = slice(None)
    for b_at, c_at, u_at in _chunks(hidden):
        v = _f32(bcu_ref, every, b_at) * _f32(bcu_ref, every, u_at)
        before = jnp.where(
            first, 0.0,
            _f32(before_ref, edge, b_at) * _f32(before_ref, edge, u_at),
        )
        acc = jnp.zeros_like(v)
        for j in range(taps):
            acc += w_ref[j:j + 1, b_at] * _earlier(
                v, before, taps - 1 - j
            )
        y_ref[every, b_at] = (
            _f32(bcu_ref, every, c_at) * acc
        ).astype(y_ref.dtype)


def _bwd_kernel(bcu_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                w_ref, dbcu_ref, dw_ref, *, taps, hidden):
    start = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    edge, near = slice(HALO - TILE, HALO), slice(0, TILE)
    every = slice(None)

    @pl.when(start)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for b_at, c_at, u_at in _chunks(hidden):
        b, u = _f32(bcu_ref, every, b_at), _f32(bcu_ref, every, u_at)
        dy = _f32(dy_ref, every, b_at)
        v = b * u
        g = dy * _f32(bcu_ref, every, c_at)
        before = jnp.where(
            first, 0.0,
            _f32(before_ref, edge, b_at) * _f32(before_ref, edge, u_at),
        )
        after = jnp.where(
            last, 0.0,
            _f32(dy_after_ref, near, b_at) * _f32(after_ref, near, c_at),
        )
        acc, dv = jnp.zeros_like(v), jnp.zeros_like(v)
        for j in range(taps):
            back = taps - 1 - j
            tap = w_ref[j:j + 1, b_at]
            shifted = _earlier(v, before, back)
            acc += tap * shifted
            dv += tap * _later(g, after, back)
            dw_ref[j:j + 1, b_at] += jnp.sum(
                g * shifted, axis=0, keepdims=True
            )
        dbcu_ref[every, b_at] = (dv * u).astype(dbcu_ref.dtype)
        dbcu_ref[every, c_at] = (dy * acc).astype(dbcu_ref.dtype)
        dbcu_ref[every, u_at] = (dv * b).astype(dbcu_ref.dtype)


def _halo_before(rows):
    def index(b, t):
        return (b, jnp.maximum(t * (rows // HALO) - 1, 0), 0)

    return index


def _halo_after(rows, seq):
    def index(b, t):
        return (b, jnp.minimum((t + 1) * (rows // HALO),
                               seq // HALO - 1), 0)

    return index


def _whole(b, t):
    return (b, t, 0)


def _forward(bcu, taps_first, rows):
    batch, seq, wide = bcu.shape
    taps, hidden = taps_first.shape
    rows = _rows(seq, rows or BLOCK_ROWS["forward"])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, hidden=hidden),
        grid=(batch, seq // rows),
        in_specs=[
            pl.BlockSpec((None, rows, wide), _whole),
            pl.BlockSpec((None, HALO, wide), _halo_before(rows)),
            pl.BlockSpec((taps, hidden), lambda b, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, rows, hidden), _whole),
        out_shape=jax.ShapeDtypeStruct((batch, seq, hidden), bcu.dtype),
        interpret=_interpret(),
    )(bcu, bcu, taps_first)


def _backward(bcu, taps_first, dy, rows):
    batch, seq, wide = bcu.shape
    taps, hidden = taps_first.shape
    rows = _rows(seq, rows or BLOCK_ROWS["backward"])
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, hidden=hidden),
        grid=(batch, seq // rows),
        in_specs=[
            pl.BlockSpec((None, rows, wide), _whole),
            pl.BlockSpec((None, HALO, wide), _halo_before(rows)),
            pl.BlockSpec((None, HALO, wide), _halo_after(rows, seq)),
            pl.BlockSpec((None, rows, hidden), _whole),
            pl.BlockSpec((None, HALO, hidden), _halo_after(rows, seq)),
            pl.BlockSpec((taps, hidden), lambda b, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, rows, wide), _whole),
            # one block for the whole grid: the taps' gradient, summed
            pl.BlockSpec((taps, hidden), lambda b, t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
            jax.ShapeDtypeStruct((taps, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
    )(bcu, bcu, bcu, dy, dy, taps_first)


@functools.partial(jax.jit, static_argnames=("rows",))
def short_conv(bcu, w, dy=None, rows=None):
    """The forward kernel's ``y``, or with the result's cotangent
    ``dy`` the backward kernel's ``(dbcu, dw)``. One jitted name for
    both, which is what a device trace calls them. ``rows`` caps a
    block's time steps (``BLOCK_ROWS`` where None)."""
    taps_first = w.astype(jnp.float32).T  # [taps, hidden]: lanes
    if dy is None:
        return _forward(bcu, taps_first, rows)
    dbcu, dw = _backward(bcu, taps_first, dy, rows)
    return dbcu, dw.T.astype(w.dtype)


@jax.custom_vjp
def short_conv_tpu(bcu, w):
    return short_conv(bcu, w)


def _vjp_fwd(bcu, w):
    return short_conv(bcu, w), (bcu, w)


def _vjp_bwd(saved, dy):
    return short_conv(*saved, dy)


short_conv_tpu.defvjp(_vjp_fwd, _vjp_bwd)
