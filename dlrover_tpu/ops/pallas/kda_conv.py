"""Pallas TPU kernels of a delta-rule layer's convolution, ``silu``
and l2 norm (ops/kda_conv.py has the equations): one pass forward,
one backward, over the rows ``[batch, seq, heads x d]`` that the
projections write and the scan's kernels read.

Forward reads ``x`` and writes ``n``: 4 bytes a token and channel in
bf16. Backward reads ``x`` and ``dy``, writes ``dx``, 6 bytes, and
sums ``dw`` in float32 over the grid; it keeps nothing of the forward
but ``x`` and ``w`` and makes ``a``, ``s`` and the norm's factor ``r``
again::

    ds = r dn - s r^3 sum_head(dn s)          # ds = dn without the norm
    da = ds sigmoid(a) (1 + a (1 - sigmoid(a)))
    dx[t] = sum_j w[j] da[t + (taps - 1) - j]
    dw[j] = sum_t da[t] x[t - (taps - 1) + j]
    db = sum_t da[t]                          # with a bias a channel

(``a`` the taps' sum plus the bias, where a Mamba-2 layer's call gives
one: one more float32 row beside the taps, and one more beside ``dw``;
a call without one builds the kernels it built before the bias).

A grid step is a block of time steps of one sequence at a block of
whole heads on the lanes, walked a head (or, without the norm, a lane
tile) at a time in a rolled loop: a head of 128 is one lane tile, so
its sum of squares is a lane reduction and no relayout. The ``taps - 1`` rows of ``x``
before a block, and in the backward pass the rows of ``x`` and ``dy``
after it from which the later rows' ``da`` is made, come as
``HALO``-row views of the same arrays, zeroed at a sequence's two
ends: a sequence is a row of the batch, and no view crosses it. The
lane blocks are the grid's outermost axis, so a block of ``dw`` stays
resident while the batch and the sequence go by.

Both calls are made inside one jitted function, ``kda_conv``: a device
trace names a Pallas call after the innermost jitted function that
holds it. The halo's helpers are ``ops/pallas/short_conv.py``'s, and
nothing else is shared with that kernel.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.kda_conv import L2_NORM_EPS
from dlrover_tpu.ops.pallas.short_conv import (
    HALO, TILE, _earlier, _halo_after, _halo_before, _later, _rows,
)

#: lanes of a tile: a block's and a head's width are whole tiles
LANE = 128
#: time steps of a block, in both passes, and the most lanes of one
#: (benchmarks/profile_kda_conv.py reads them on the chip)
BLOCK_ROWS = 256
BLOCK_LANES = 1024
#: the widest head whose float32 temporaries a block's walk holds
MOST_HEAD = 512
F32 = jnp.float32


def _block_lanes(width, head, cap=None):
    """The lanes of a block: the most within ``cap``, halved until
    they are whole heads and divide the width; None where none do."""
    lanes = cap or BLOCK_LANES
    while lanes >= head and (width % lanes or lanes % head):
        lanes //= 2
    return lanes if lanes >= head else None


def _head(width, l2_heads):
    """The lanes walked at a time: a head's where the norm is asked
    for, a tile's where not."""
    return width // l2_heads if l2_heads else LANE


def tiles_the_kernel(x_shape, w_shape, l2_heads=None) -> bool:
    """Whether the kernels take these shapes: a head in whole lane
    tiles, time in whole blocks, no more taps than a tile has rows."""
    width, taps = w_shape
    head = _head(width, l2_heads)
    return (
        width % LANE == 0 and head % LANE == 0 and head <= MOST_HEAD
        and _block_lanes(width, head) is not None
        and _rows(x_shape[1], BLOCK_ROWS) is not None
        and 1 <= taps <= TILE
    )


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _shifted(x, before, taps):
    """``x[t - (taps - 1) + j]`` for each tap ``j``, the rows ahead of
    the block from ``before``."""
    return [_earlier(x, before, taps - 1 - j) for j in range(taps)]


def _tile_after(x, after, taps):
    """``_shifted`` for the ``TILE`` rows ``after`` that follow the
    block ``x``: one tile's rows, so one rotation of two tiles."""
    both = jnp.concatenate([x[x.shape[0] - TILE:], after], axis=0)
    return [
        after if j == taps - 1
        else pltpu.roll(both, taps - 1 - j, 0)[TILE:]
        for j in range(taps)
    ]


def _silu_norm(shifted, w_ref, at, l2, b_ref=None):
    """``(a, sigmoid(a), s, r)`` of one head's lanes ``at``: ``r`` the
    norm's factor [rows, 1], None without the norm."""
    a = jnp.zeros_like(shifted[0])
    for j, rows in enumerate(shifted):
        a += w_ref[j:j + 1, at] * rows
    if b_ref is not None:
        a += b_ref[:, at]
    gate = jax.nn.sigmoid(a)
    s = a * gate
    r = jax.lax.rsqrt(
        jnp.sum(s * s, axis=-1, keepdims=True) + L2_NORM_EPS
    ) if l2 else None
    return a, gate, s, r


def _da(shifted, dy, w_ref, at, l2, b_ref=None):
    """The cotangent of ``a`` from the result's, everything between
    made again."""
    a, gate, s, r = _silu_norm(shifted, w_ref, at, l2, b_ref)
    ds = dy
    if l2:
        ds = r * dy - s * (r * r * r) * jnp.sum(
            dy * s, axis=-1, keepdims=True)
    return ds * gate * (1.0 + a * (1.0 - gate))


def _each_head(lanes, head, body):
    """``body(at)`` for each head's lanes ``at`` of a block, in a
    rolled loop: a kernel is traced and compiled in the time of one
    head's, however many its block holds (a body a head in the
    kernel's text cost the step's trace 8 s of every start: PERF.md,
    PR 51)."""
    def step(h, carry):
        body(pl.ds(pl.multiple_of(h * head, head), head))
        return carry

    jax.lax.fori_loop(0, lanes // head, step, 0)


def _fwd_kernel(x_ref, before_ref, w_ref, *rest, taps, head, l2):
    b_ref, y_ref = rest if len(rest) == 2 else (None, *rest)
    first = pl.program_id(2) == 0
    edge = slice(HALO - TILE, HALO)

    def one(at):
        before = jnp.where(first, 0.0, before_ref[edge, at].astype(F32))
        _, _, s, r = _silu_norm(
            _shifted(x_ref[:, at].astype(F32), before, taps), w_ref, at, l2,
            b_ref)
        y_ref[:, at] = (s * r if l2 else s).astype(y_ref.dtype)

    _each_head(x_ref.shape[1], head, one)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                *rest, taps, head, l2):
    b_ref, dx_ref, dw_ref, db_ref = (
        rest if len(rest) == 4 else (None, *rest, None))
    start = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    edge, near = slice(HALO - TILE, HALO), slice(0, TILE)

    @pl.when(start)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if db_ref is not None:
            db_ref[...] = jnp.zeros_like(db_ref)

    def one(at):
        x = x_ref[:, at].astype(F32)
        before = jnp.where(first, 0.0, before_ref[edge, at].astype(F32))
        shifted = _shifted(x, before, taps)
        da = _da(shifted, dy_ref[:, at].astype(F32), w_ref, at, l2, b_ref)
        da_after = jnp.where(last, 0.0, _da(
            _tile_after(x, after_ref[near, at].astype(F32), taps),
            dy_after_ref[near, at].astype(F32), w_ref, at, l2, b_ref))
        dx = jnp.zeros_like(x)
        for j in range(taps):
            dx += w_ref[j:j + 1, at] * _later(da, da_after, taps - 1 - j)
            dw_ref[j:j + 1, at] += jnp.sum(
                da * shifted[j], axis=0, keepdims=True)
        dx_ref[:, at] = dx.astype(dx_ref.dtype)
        if db_ref is not None:
            db_ref[:, at] += jnp.sum(da, axis=0, keepdims=True)

    _each_head(x_ref.shape[1], head, one)


def _at_lanes(rows_index):
    """A (batch, time) index map of the halo's helpers under the grid
    (lane block, batch, time)."""
    def index(c, b, t):
        return (*rows_index(b, t)[:2], c)

    return index


def _whole(c, b, t):
    return (b, t, c)


def _taps(c, b, t):
    return (0, c)


def _blocks(x, l2_heads, rows, lanes):
    """``(head, rows, lanes, grid)`` of either pass."""
    _, seq, width = x.shape
    head = _head(width, l2_heads)
    rows = _rows(seq, rows or BLOCK_ROWS)
    lanes = _block_lanes(width, head, lanes)
    return head, rows, lanes, (width // lanes, x.shape[0], seq // rows)


def _with_bias(bias, lanes):
    """``(the bias's spec, the bias as a row)`` for either pass: none
    of either where a call has no bias."""
    if bias is None:
        return [], []
    return [pl.BlockSpec((1, lanes), _taps)], [bias.astype(F32)[None]]


def _forward(x, taps_first, bias, l2_heads, rows, lanes):
    taps = taps_first.shape[0]
    head, rows, lanes, grid = _blocks(x, l2_heads, rows, lanes)
    bias_spec, bias_row = _with_bias(bias, lanes)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, taps=taps, head=head, l2=bool(l2_heads)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, rows, lanes), _whole),
            pl.BlockSpec((None, HALO, lanes), _at_lanes(_halo_before(rows))),
            pl.BlockSpec((taps, lanes), _taps),
            *bias_spec,
        ],
        out_specs=pl.BlockSpec((None, rows, lanes), _whole),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_interpret(),
    )(x, x, taps_first, *bias_row)


def _backward(x, taps_first, bias, dy, l2_heads, rows, lanes):
    taps, width = taps_first.shape
    head, rows, lanes, grid = _blocks(x, l2_heads, rows, lanes)
    after = _at_lanes(_halo_after(rows, x.shape[1]))
    bias_spec, bias_row = _with_bias(bias, lanes)
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, taps=taps, head=head, l2=bool(l2_heads)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, rows, lanes), _whole),
            pl.BlockSpec((None, HALO, lanes), _at_lanes(_halo_before(rows))),
            pl.BlockSpec((None, HALO, lanes), after),
            pl.BlockSpec((None, rows, lanes), _whole),
            pl.BlockSpec((None, HALO, lanes), after),
            pl.BlockSpec((taps, lanes), _taps),
            *bias_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, rows, lanes), _whole),
            # one block a lane block, through the batch and the
            # sequence: the taps' gradient, summed (and the bias's)
            pl.BlockSpec((taps, lanes), _taps),
            *bias_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((taps, width), F32),
            *(jax.ShapeDtypeStruct((1, width), F32) for _ in bias_row),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=_interpret(),
    )(x, x, x, dy, dy, taps_first, *bias_row)


@functools.partial(jax.jit, static_argnames=("l2_heads", "rows", "lanes"))
def kda_conv(x, w, dy=None, l2_heads=None, rows=None, lanes=None,
             bias=None):
    """The forward kernel's result, or with its cotangent ``dy`` the
    backward kernel's ``(dx, dw)``, with a ``bias`` ``(dx, dw, db)``.
    One jitted name for both, which is what a device trace calls
    them. ``rows`` and ``lanes`` cap a block's time steps and lanes
    (``BLOCK_ROWS``, ``BLOCK_LANES`` where None)."""
    taps_first = w.astype(F32).T  # [taps, heads x d]: lanes
    if dy is None:
        return _forward(x, taps_first, bias, l2_heads, rows, lanes)
    dx, dw, *db = _backward(x, taps_first, bias, dy, l2_heads, rows, lanes)
    return (dx, dw.T.astype(w.dtype),
            *(d[0].astype(bias.dtype) for d in db))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def kda_conv_tpu(x, w, l2_heads=None):
    return kda_conv(x, w, l2_heads=l2_heads)


def _vjp_fwd(x, w, l2_heads):
    return kda_conv(x, w, l2_heads=l2_heads), (x, w)


def _vjp_bwd(l2_heads, saved, dy):
    return kda_conv(*saved, dy, l2_heads=l2_heads)


kda_conv_tpu.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def kda_conv_bias_tpu(x, w, bias, l2_heads=None):
    """``kda_conv_tpu`` with a bias a channel ahead of ``silu``."""
    return kda_conv(x, w, l2_heads=l2_heads, bias=bias)


def _bias_vjp_fwd(x, w, bias, l2_heads):
    return kda_conv(x, w, l2_heads=l2_heads, bias=bias), (x, w, bias)


def _bias_vjp_bwd(l2_heads, saved, dy):
    x, w, bias = saved
    return kda_conv(x, w, dy, l2_heads=l2_heads, bias=bias)


kda_conv_bias_tpu.defvjp(_bias_vjp_fwd, _bias_vjp_bwd)
