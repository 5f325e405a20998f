"""Pallas TPU kernels of a Mamba-1 mixer's selective scan
(ops/selective_scan.py has the recurrence).

The decay ``exp(Delta_t[d] A[d, n])`` is a number a channel and state,
so nothing here is a matrix product: the recurrence is walked position
by position on the vector unit, the state resident. A grid step is one
chunk of ``CHUNK`` positions of ``LANES`` channels of one sequence.
The channels lie on the lanes and the ``n`` states down the sublanes:
a step's state is ``[n, LANES]`` float32 (16 x 1,024: sixteen registers)
and is carried through the chunk's loop; between chunks it waits in
VMEM (``[channels / LANES, n, LANES]``: 320 KB at 5,120 channels of
16 states). What a position brings:

- ``Delta_t`` and ``Delta_t x_t``, rows ``[1, LANES]`` of the blocks
  as they lie in memory, spread down the sublanes;
- ``B_t`` and ``C_t``, which every channel shares, as columns ``[n,
  1]`` spread along the lanes. A column of a ``[seq, n]`` block is a
  transposition a position, so XLA hands both in by groups of eight
  positions, ``[seq / 8, n, 128]`` with position ``8 g + i`` of a group
  in lane ``i`` (8 MB a layer at 8,192 positions: 16 times the array,
  not the 128 times a ready-spread copy would be); the loop walks a
  group a step with the eight lanes static;
- the result, a sum down the sublanes, written a row.

The grid is ``(batch, chunks, channel tiles)``, the tiles innermost:
``B``'s and ``C``'s blocks stay where they are through a chunk's tiles.
``A``'s gradient, a sum over every position, and ``D``'s are float32
blocks resident through the whole grid, as ``short_conv``'s taps and
``gated_norm``'s scales are.

What the backward's position cannot afford is the unit that moves
values across lanes (PERF.md section 6, PR 69): a column spread along
the lanes and a sum across them queue there, and with four of each a
position the kernel waited on that unit more than it computed, whatever
the tile's width. So the backward kernel

- spreads ``B_t`` and ``C_t`` along the lanes once a chunk, at its
  first tile, into VMEM (``[CHUNK, n, 128]`` float32 each): every tile
  of the chunk, making the states again and walking them down, reads a
  position's column as two whole registers;
- sums ``B``'s and ``C``'s gradients, sums over every channel, over the
  tile's lane tiles only at a position (adds of whole registers) and
  adds them to a lane tile of the position's own in VMEM (``[CHUNK / 8,
  n, 8 x 128]`` float32 each, summed over the chunk's channel tiles
  where it lies); the 128 lanes are summed once a chunk, after its last
  tile, position ``8 g + i`` into lane ``i`` of group ``g``, as ``B``
  and ``C`` came in.

The forward kernel goes up the sequence. Differentiated, it also writes
each chunk's entry state (``[batch, chunks, n, channels]`` float32: 42
MB a layer at 8,192 positions, alive for that layer's backward only).
The backward kernel goes down the sequence over them: a chunk's states
are made again from its entry state into VMEM (``[CHUNK + 1, n,
LANES]``, 4.3 MB of the 11 MB the call holds there), then walked in
reverse with the state's cotangent carried. No division by a decay:
``h_{t-1}`` is read, never recovered from ``h_t``.

Both calls are made inside one jitted function, ``selective_scan``: a
device trace names a Pallas call after the innermost jitted function
that holds it, and the benchmark's ``selective_scan_ms`` tells the
kernels by that name.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.selective_scan import CHUNK

#: lanes of a tile, rows of a float32 tile (the states lie in whole
#: ones), and the positions of a group of ``B``/``C`` columns
LANE = 128
SUBLANES = 8
GROUP = 8
#: the channels of a grid step: the widest of these that divides them
#: (at 5,120 channels a layer's forward with its backward read 6.9 ms
#: at 1,024, 6.9 at 512 and 8.6 at 256, the forward alone 2.1, 2.4 and
#: 3.6, the backward alone 5.0, 4.7 and 5.3: PERF.md section 6, PR 69;
#: 8.1, 12.0 and 20.9 ms while the backward crossed lanes at every
#: position). Only 1,024 has a benchmark cell behind it; the narrower
#: widths are for channel counts that 1,024 does not divide, and no
#: workload measures them
LANES = (1024, 512, 256, 128)

F32 = jnp.float32


def _lanes(channels):
    return next((w for w in LANES if channels % w == 0), None)


def tiles_the_kernel(x_shape, b_shape) -> bool:
    """Whether the kernels take rows ``[batch, seq, channels]`` and
    ``[batch, seq, n]``: channels in whole lane tiles, the states in
    whole sublane tiles, the sequence in whole chunks."""
    return (
        _lanes(x_shape[2]) is not None and b_shape[2] % SUBLANES == 0
        and x_shape[1] % CHUNK == 0
    )


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _row(ref, t):
    return ref[pl.ds(t, 1), :]


def _states_up(dl_ref, u_ref, bg_ref, cg_ref, y_ref, A, h):
    """The chunk's recurrence from the entry state ``h`` [n, lanes]:
    the results' rows into ``y_ref``, and the last state."""
    def group(g, h):
        bt, ct = bg_ref[g], cg_ref[g]
        for i in range(GROUP):
            t = g * GROUP + i
            h = (jnp.exp(_row(dl_ref, t) * A) * h
                 + bt[:, i:i + 1] * _row(u_ref, t))
            y_ref[pl.ds(t, 1), :] = jnp.sum(
                h * ct[:, i:i + 1], axis=0, keepdims=True)
        return h

    return jax.lax.fori_loop(0, CHUNK // GROUP, group, h)


def _fwd_kernel(x_ref, dl_ref, bg_ref, cg_ref, a_ref, d_ref, o_ref, *rest):
    *entry_ref, h_scr, u_scr, y_scr = rest
    tile = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_scr[tile] = jnp.zeros(h_scr.shape[1:], F32)

    x = x_ref[...].astype(F32)
    u_scr[...] = dl_ref[...] * x
    h = h_scr[tile]
    if entry_ref:
        entry_ref[0][...] = h
    h_scr[tile] = _states_up(
        dl_ref, u_scr, bg_ref, cg_ref, y_scr, a_ref[...], h)
    o_ref[...] = (y_scr[...] + d_ref[...] * x).astype(o_ref.dtype)


def _over_lane_tiles(p):
    """``[n, lanes]`` summed over its lane tiles, ``[n, 128]``: adds of
    whole registers."""
    parts = [p[:, k:k + LANE] for k in range(0, p.shape[1], LANE)]
    return sum(parts[1:], parts[0])


def _over_lanes(scr):
    """``[groups, n, GROUP x 128]``, a position of a group a lane tile,
    summed over each tile's lanes into ``[groups, n, 128]`` with
    position ``i`` in lane ``i``."""
    groups, n, _ = scr.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (groups, n, LANE), 2)
    out = jnp.zeros((groups, n, LANE), F32)
    for i in range(GROUP):
        out = jnp.where(lane == i, jnp.sum(
            scr[:, :, i * LANE:(i + 1) * LANE], axis=2, keepdims=True), out)
    return out


def _bwd_kernel(x_ref, dl_ref, do_ref, bg_ref, cg_ref, a_ref, d_ref,
                entry_ref, dx_ref, ddl_ref, dbg_ref, dcg_ref, da_ref,
                dd_ref, g_scr, h_scr, u_scr, du_scr, do_scr, b_scr, c_scr,
                db_scr, dc_scr):
    last_chunk = pl.program_id(1) == 0  # the grid walks the chunks down
    tile = pl.program_id(2)

    @pl.when((pl.program_id(0) == 0) & last_chunk)
    def _():
        da_ref[tile] = jnp.zeros(da_ref.shape[1:], F32)
        dd_ref[tile] = jnp.zeros(dd_ref.shape[1:], F32)

    @pl.when(last_chunk)
    def _():
        g_scr[tile] = jnp.zeros(g_scr.shape[1:], F32)

    @pl.when(tile == 0)
    def _():
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

        # B_t and C_t along the lanes, once for all the chunk's tiles
        def group(g, _):
            bt, ct = bg_ref[g], cg_ref[g]
            for i in range(GROUP):
                b_scr[g * GROUP + i] = jnp.broadcast_to(
                    bt[:, i:i + 1], bt.shape)
                c_scr[g * GROUP + i] = jnp.broadcast_to(
                    ct[:, i:i + 1], ct.shape)

        jax.lax.fori_loop(0, CHUNK // GROUP, group, None)

    x = x_ref[...].astype(F32)
    do = do_ref[...].astype(F32)
    do_scr[...] = do
    u_scr[...] = dl_ref[...] * x
    A = a_ref[...]

    def column(scr, t):  # [n, 128], every lane the same, to the tile
        return jnp.concatenate([scr[t]] * (A.shape[1] // LANE), axis=1)

    def up(g, h):  # the chunk's states again, h_t at t + 1
        for i in range(GROUP):
            t = g * GROUP + i
            h = (jnp.exp(_row(dl_ref, t) * A) * h
                 + column(b_scr, t) * _row(u_scr, t))
            h_scr[t + 1] = h
        return h

    h_scr[0] = entry_ref[...]
    jax.lax.fori_loop(0, CHUNK // GROUP, up, entry_ref[...])

    def down(k, carry):
        g, dA = carry
        at = CHUNK // GROUP - 1 - k
        for i in reversed(range(GROUP)):
            t = at * GROUP + i
            delta, do_t = _row(dl_ref, t), _row(do_scr, t)
            g = g + column(c_scr, t) * do_t
            # B's and C's gradients, sums over the channels: over the
            # lane tiles here, into the position's own lane tile of the
            # chunk's sums; over the lanes once a chunk, below
            mine = pl.ds(i * LANE, LANE)
            dc_scr[at, :, mine] += _over_lane_tiles(h_scr[t + 1] * do_t)
            db_scr[at, :, mine] += _over_lane_tiles(g * _row(u_scr, t))
            du_scr[pl.ds(t, 1), :] = jnp.sum(
                g * column(b_scr, t), axis=0, keepdims=True)
            g = g * jnp.exp(delta * A)  # the cotangent of h_{t-1}
            q = g * h_scr[t]  # d a_t's, times a_t
            dA = dA + q * delta
            ddl_ref[pl.ds(t, 1), :] = jnp.sum(q * A, axis=0, keepdims=True)
        return g, dA

    g, dA = jax.lax.fori_loop(
        0, CHUNK // GROUP, down, (g_scr[tile], jnp.zeros_like(A)))
    g_scr[tile] = g
    da_ref[tile] += dA
    dd_ref[tile] += jnp.sum(do * x, axis=0, keepdims=True)
    du = du_scr[...]  # the cotangent of Delta x
    ddl_ref[...] = ddl_ref[...] + du * x
    dx_ref[...] = (du * dl_ref[...] + d_ref[...] * do).astype(dx_ref.dtype)

    @pl.when(tile == pl.num_programs(2) - 1)
    def _():
        dbg_ref[...] = _over_lanes(db_scr)
        dcg_ref[...] = _over_lanes(dc_scr)


def _specs(chunks, wide, n, reverse):
    """The block specs of what both kernels read: rows, grouped
    columns, ``A^T``'s and ``D``'s tiles; and the index of a chunk."""
    def chunk(c):
        return chunks - 1 - c if reverse else c

    rows = pl.BlockSpec(
        (None, CHUNK, wide), lambda b, c, j: (b, chunk(c), j))
    columns = pl.BlockSpec(
        (None, CHUNK // GROUP, n, LANE),
        lambda b, c, j: (b, chunk(c), 0, 0))
    return rows, columns, [
        pl.BlockSpec((n, wide), lambda b, c, j: (0, j)),
        pl.BlockSpec((1, wide), lambda b, c, j: (0, j)),
    ], chunk


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    )


def _forward(x, dl, bg, cg, at, d, keep_states):
    batch, seq, channels = x.shape
    n, wide, chunks = at.shape[0], _lanes(channels), seq // CHUNK
    tiles = channels // wide
    rows, columns, leaves, _ = _specs(chunks, wide, n, False)
    out_specs, out_shape = [rows], [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if keep_states:
        out_specs.append(pl.BlockSpec(
            (None, None, n, wide), lambda b, c, j: (b, c, 0, j)))
        out_shape.append(
            jax.ShapeDtypeStruct((batch, chunks, n, channels), F32))
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(batch, chunks, tiles),
        in_specs=[rows, rows, columns, columns, *leaves],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((tiles, n, wide), F32),
            pltpu.VMEM((CHUNK, wide), F32),
            pltpu.VMEM((CHUNK, wide), F32),
        ],
        compiler_params=_params(), interpret=_interpret(),
    )(x, dl, bg, cg, at, d)
    return out if keep_states else out[0]


def _backward(x, dl, bg, cg, at, d, entry, do):
    batch, seq, channels = x.shape
    n, wide, chunks = at.shape[0], _lanes(channels), seq // CHUNK
    tiles = channels // wide
    rows, columns, leaves, chunk = _specs(chunks, wide, n, True)

    def whole(*shape):  # one block for the whole grid: a float32 sum
        return pl.BlockSpec(shape, lambda b, c, j: (0,) * len(shape))

    return pl.pallas_call(
        _bwd_kernel,
        grid=(batch, chunks, tiles),
        in_specs=[
            rows, rows, rows, columns, columns, *leaves,
            pl.BlockSpec((None, None, n, wide),
                         lambda b, c, j: (b, chunk(c), 0, j)),
        ],
        out_specs=[
            rows, rows, columns, columns,
            whole(tiles, n, wide), whole(tiles, 1, wide),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(x.shape, F32),
            jax.ShapeDtypeStruct(bg.shape, F32),
            jax.ShapeDtypeStruct(cg.shape, F32),
            jax.ShapeDtypeStruct((tiles, n, wide), F32),
            jax.ShapeDtypeStruct((tiles, 1, wide), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tiles, n, wide), F32),
            pltpu.VMEM((CHUNK + 1, n, wide), F32),
            pltpu.VMEM((CHUNK, wide), F32),
            pltpu.VMEM((CHUNK, wide), F32),
            pltpu.VMEM((CHUNK, wide), F32),
            pltpu.VMEM((CHUNK, n, LANE), F32),
            pltpu.VMEM((CHUNK, n, LANE), F32),
            pltpu.VMEM((CHUNK // GROUP, n, GROUP * LANE), F32),
            pltpu.VMEM((CHUNK // GROUP, n, GROUP * LANE), F32),
        ],
        compiler_params=_params(), interpret=_interpret(),
    )(x, dl, do, bg, cg, at, d, entry)


def _grouped(a):
    """``[batch, seq, n]`` as ``[batch, seq / 8, n, 128]`` float32:
    position ``8 g + i`` of a group in lane ``i``, zeros past eight."""
    batch, seq, n = a.shape
    a = jnp.swapaxes(a.astype(F32).reshape(batch, seq // GROUP, GROUP, n),
                     2, 3)
    return jnp.pad(a, ((0, 0),) * 3 + ((0, LANE - GROUP),))


def _ungrouped(a, dtype):
    batch, groups, n, _ = a.shape
    return jnp.swapaxes(a[..., :GROUP], 2, 3).reshape(
        batch, groups * GROUP, n).astype(dtype)


@functools.partial(jax.jit, static_argnames=("keep_states",))
def selective_scan(x, delta, B, C, A, D, entry=None, do=None,
                   keep_states=False):
    """The forward kernel's ``o`` (with ``keep_states`` ``(o, the
    chunks' entry states)``), or with those states and the result's
    cotangent ``do`` the backward kernel's six gradients. One jitted
    name for both, which is what a device trace calls them. What a
    kernel is handed beside the rows is made here, by XLA: ``B`` and
    ``C`` in groups of eight positions, ``A`` transposed, ``D`` a
    row."""
    operands = (
        x, delta.astype(F32), _grouped(B), _grouped(C), A.astype(F32).T,
        D.astype(F32)[None],
    )
    if do is None:
        return _forward(*operands, keep_states)
    dx, ddl, dbg, dcg, da, dd = _backward(*operands, entry, do)
    return (
        dx, ddl.astype(delta.dtype), _ungrouped(dbg, B.dtype),
        _ungrouped(dcg, C.dtype),
        # [tiles, n, lanes] -> [channels, n]
        jnp.moveaxis(da, 1, 0).reshape(A.shape[::-1]).T.astype(A.dtype),
        dd.reshape(D.shape).astype(D.dtype),
    )


@jax.custom_vjp
def selective_scan_tpu(x, delta, B, C, A, D):
    return selective_scan(x, delta, B, C, A, D)


def _vjp_fwd(*operands):
    o, entry = selective_scan(*operands, keep_states=True)
    return o, (*operands, entry)


def _vjp_bwd(saved, do):
    return selective_scan(*saved, do=do)


selective_scan_tpu.defvjp(_vjp_fwd, _vjp_bwd)
