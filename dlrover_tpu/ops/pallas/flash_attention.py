"""Flash attention as Pallas TPU kernels (forward + backward), GQA-native.

Parity reference: the reference injects Tri-Dao's CUDA FlashAttention
(atorch/atorch/modules/transformer/layers.py:706, inject.py:58) — here the
same O(seq) memory algorithm is a native TPU kernel: online-softmax
accumulators live in VMEM scratch that persists across the k-block grid
dimension; the two matmuls per block ride the MXU in fp32 accumulation.

GQA is handled *inside* the kernel: all ``group = heads // kv_heads``
query heads that share a KV head are folded into the matmul row
dimension, so
  - K/V are never materialized per-query-head (8x less VMEM traffic for
    llama-style 32q/4kv),
  - the QK^T and PV matmuls are ``group``-times taller (MXU likes tall),
  - the dK/dV group reduction falls out of the contraction for free.
Layout inside the kernels is [batch*kv_heads, group, seq, head_dim]; the
public wrapper maps the models' [batch, seq, heads, head_dim] (query head
i uses kv head i // group, matching jnp.repeat semantics).

Backward follows the FlashAttention-2 structure: a dQ kernel (grid over
q-blocks, accumulating over k-blocks) and a dK/dV kernel (grid over
k-blocks, accumulating over q-blocks), with the softmax re-derived from
the saved logsumexp. The two recompute the scores, the softmax and dS
of every live block, seven products where the mathematics has five.
Where the smaller gradient can stay in VMEM in float32 across a head's
grid steps (``_one_backward_kernel``), the backward is one kernel in
which every operand is read once and every score computed once, and
the larger gradient leaves a block at a time. Without a group the
smaller one is a head's dQ: the dK/dV kernel with one product more,
dQ += dS K on the dS it has formed (``_dqkv_kernel``). With a group dQ
is g times a head's and the smaller ones are the kv head's dK and dV,
[seq, head_dim] whatever g is: the dQ kernel with two products more,
dK += dS^T Q and dV += P^T dO (``_dq_dkv_kernel``). Either states the
VMEM it needs (``_dq_resident_vmem_bytes``,
``_dkv_resident_vmem_bytes``) and both live by one budget,
``RESIDENT_BYTES`` of float32 in rows of whole lanes: 16,384
positions of a 192-wide head's dQ, or of a 128-wide kv head's dK and
dV. The pair is what a head too long for it keeps.

The kernels of a call need not share a key block: the backward's run
the pair of ``ops/tuning.py heuristic_blocks``, the forward
``forward_key_block``, which past a group of 8 is wider than the
pair's (its grid step is bound by its rows, and 128 rows a head of 16
leave the pair 512 columns), and states what its scores take
(``_fwd_vmem_bytes``). A windowed call's band and a selection's words
are each kernel's own, made from its own key block.

What a causally live grid step computes (``_walk``). Blocks wholly
above the diagonal are skipped by the grid (``pl.when`` + the clamped
index maps). Where a kernel is given an edge (``_sub_tiles``: for each
kernel from the shapes it sees, as read on the chip; none with a
group or with unequal blocks), a block under the diagonal is one
product without the mask, and the block on the diagonal is walked in
rows of square sub-tiles: row n is ONE product over the block's
leading n + 1 column tiles, the ones in which it has an element on or
under the diagonal, and the mask. The loop over rows is a
``fori_loop`` that the lowering unrolls, so that rows are sliced at
static offsets and the ``lax.switch`` that picks a row's width has a
constant index: the jaxpr holds one body for each width (one a column
tile of the block, not one a sub-tile), the lowered kernel one a row.
Rolled loops over column tiles (trip counts from ``program_id``, one
masked and one unmasked body) read 1.3 to 4 times slower than the
whole block on a v5e: every iteration pays the MXU's fill and drain, a
lane reduction a row and dynamic slices (PERF.md section 6, PR 31).
Where no edge is given, and without ``causal``, the body is the whole
block's single product as before. ``causal_tile_census`` counts what
the walk skips.

v may be narrower or wider than q and k (latent attention: q and k
192 wide, v 128). Nothing in a kernel's body knows: the scores
contract over q and k's width, the forward's accumulator, o, dO and
dV are v's wide, dQ and dK q's. A block's last dimension is then the
array's whole width, a lane and a half at 192. The one-kernel rule
reads the wider of the two, in whole lanes; a head's resident dQ of
8,192 or 16,384 x 192 is past the default scoped limit's room, and
that call states what it takes as the grouped one does.

q and k may each come in two parts (latent attention: a head's 128
un-rotated and 64 rotated columns, the products that make them being
apart, and the rotated key one [batch, seq, 64] for every head). The
parts are further refs of the same kernels: a ``pallas_call`` takes
its block specs as trees and hands the body a tuple of refs where the
caller handed a tuple of arrays. The rotated key's spec maps a head's
grid index to its batch row, so its block is fetched where a head's
copy would be and no copy is made; a grid step puts the [block, 192]
tiles of q and k together in VMEM (``_read``) and from there the body
is the one it is for whole operands, product for product; dQ and dK
are summed 192 wide in VMEM and leave in the parts they came in
(``_write``), a head's part of the rotated key's gradient summed over
the heads outside the kernel. A caller that hands whole q and k gets
the kernels it always got: the helpers below are the plain read and
the plain write for a ref that is no tuple.

A window (``window``, static: query i sees key j iff ``j <= i`` and
``i - j < window``) is a second edge, under the diagonal, and a
windowed call computes the band and nothing else (``_Band``, the one
statement of its geometry). Its grid's minor dimension counts the
blocks of the band, not of the sequence: step n of a query block is
the n-th key block from the first that holds a key it sees (3 steps
at a window of 2048 in (128, 1024) blocks and 5 at 4096, where 16
blocks tile 16,384 positions), the index maps and the kernels reading
the block from the same expression; the few short rows at the
sequence's start re-reference the block on the diagonal and compute
nothing there. The grid by key blocks is the mirror image. A block
between the edges is one product without a mask. A block that the
diagonal or the window's edge crosses is one masked product over its
live column tiles (``_window_tile``: 512 wide in the backward
kernels, the whole block in the forward), the leading ones of a block
on the diagonal and the trailing ones of a block on the window's
edge, at a static width: a kernel holds one body for each run of
tiles that the call's geometry has (``_Band.crossed_tiles``: three at
the two shapes above) and a step takes its own. Rows are not cut, so a
group folds as ever. As read on a v5e (PERF.md section 6, PR 52), a
call of 32 query heads on 4 with the window 2048, forward and backward:
22.81 ms with the sequence's grid and whole blocks, 20.20 with the
band's grid, 18.50 with the backward's tiles as well.

A selection (``selected``, bool [batch, kv_heads, seq, seq / block]:
whether a query, for every head of its kv head, sees a block of
``block`` adjacent keys; ops/sparse_attention.py makes it) is one more
mask, beside the diagonal's, that differs query by query. It reaches a
kernel as one further ref of int32 words, ``[batch x kv_heads, key
blocks of the grid, 1, seq]``: bit c of a query's word for a key block
of the grid says whether it selected the c-th ``block`` keys of it
(``_selection_words``), so a grid step reads ``block_q`` words, a
column like ``lse``'s, and spreads them over the scores' columns by a
shift (``_selected_mask``). The result is exact for the selection:
every query takes its own word, no union over a tile. The blocks are
taken whole (no sub-tiles) and the grid is the causal one: with seeded
weights a query's free picks are near uniform over the earlier blocks
and no (128, 512) tile is without one (PERF.md section 6, PR 64).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import tuning

NEG_INF = -1e30
LANES = 128


def _causal_mask(q_start, k_start, g, rows, cols):
    """[g*rows, cols] bool: row token >= col token.

    Rows are g-major (row = g_idx*rows + q_idx), so the query position
    is ``q_start + row % rows`` — computed with a bitwise AND
    (``rows`` is a power of two: a block, or a sub-tile's edge) to stay
    on Mosaic's supported ops.
    """
    shape = (g * rows, cols)
    q_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jax.lax.ge(
        jax.lax.add(q_start, jax.lax.bitwise_and(q_idx, rows - 1)),
        jax.lax.add(k_start, k_idx),
    )


def _band_mask(q_start, k_start, g, rows, cols, window):
    """``_causal_mask`` and the window's lower edge: row token >= col
    token > row token - window."""
    shape = (g * rows, cols)
    q_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # query position less key position
    ahead = jax.lax.sub(
        jax.lax.add(q_start, jax.lax.bitwise_and(q_idx, rows - 1)),
        jax.lax.add(k_start, k_idx),
    )
    return jax.lax.bitwise_and(
        jax.lax.ge(ahead, 0), jax.lax.lt(ahead, window)
    )


def _each(x):
    """An operand's parts: q or k handed whole is its own one part, in
    parts (a tuple; latent attention's un-rotated and rotated columns)
    those. Arrays, refs, or what ``_parts_of`` made of them."""
    return x if isinstance(x, tuple) else (x,)


def _parts_of(fn, x):
    """``fn`` of an operand handed whole, or the tuple of ``fn`` of
    each of its parts: a ``pallas_call`` takes block specs and shapes
    as trees and hands the kernel its refs in the same trees."""
    return tuple(fn(p) for p in x) if isinstance(x, tuple) else fn(x)


def _width(x):
    """An operand's last dimension, over its parts."""
    return sum(p.shape[-1] for p in _each(x))


def _dtype(x):
    """An operand's dtype, which its parts share."""
    return _each(x)[0].dtype


def _read(ref, index):
    """``ref[index]``; of an operand in parts, its parts' side by side
    on the lanes: the [block, d] tile the products below read, put
    together in VMEM and nowhere else."""
    if isinstance(ref, tuple):
        return jax.lax.concatenate([r[index] for r in ref], 1)
    return ref[index]


def _write(ref, index, value):
    """``ref[index] = value``; to an operand's gradient in parts, each
    part its own columns of ``value``."""
    if not isinstance(ref, tuple):
        ref[index] = value
        return
    start = 0
    for r in ref:
        r[index] = value[:, start:start + r.shape[-1]]
        start += r.shape[-1]


def _stack_groups(ref, g, rows=slice(None)):
    """[1, g, block, d] ref -> [g*rows, d] value, via per-group slices
    stacked on sublanes (the relayout Mosaic supports; a direct 4-D
    reshape hits "unsupported shape cast")."""
    if g == 1:
        return _read(ref, (0, 0, rows))
    return jnp.concatenate(
        [_read(ref, (0, gi, rows)) for gi in range(g)], axis=0
    )


def _stack_cols(ref, g, rows=slice(None)):
    """[1, g, 1, block] ref (lanes) -> [g*rows, 1] column (sublanes)."""
    if g == 1:
        return ref[0, 0, 0, rows][:, None]
    return jnp.concatenate(
        [ref[0, gi, 0, rows][:, None] for gi in range(g)], axis=0
    )


# ---------------------------------------------------------------------------
# sub-tiles of a causal block

def _sub_tiles(kernel, block_q, block_k, g, head_dim):
    """The edge of the square sub-tiles in which ``kernel`` ("fwd",
    "dq", "dkv", "dqkv": the dk/dv kernel that accumulates dQ too, or
    "dq_dkv": the dq kernel that accumulates dK and dV too, which only
    a group runs) walks the (block_q, block_k) grid block on the
    diagonal; None where it takes the block whole.

    As read on a v5e at the blocks ``ops/tuning.py heuristic_blocks``
    gives (``benchmarks/profile_attn_subtiles.py``; PERF.md section 6,
    PR 31): the two backward kernels gain at every shape tried, most at
    256 (128 reads the same and holds twice the bodies, 512 skips too
    little). The forward gains only at 64-wide heads, and there only
    at 128; at 128-wide heads every edge reads level or slower than
    the whole block. With a group the backward kernels gain 0.5% of a
    Mistral step and a body reads g times as many slices: tracing them
    cost a second of every process's set-up, so a group stays whole."""
    if kernel == "fwd":
        return _fits(128 if head_dim <= 64 else None, g, block_q, block_k)
    return _fits(256, g, block_q, block_k)


def _fits(edge, g, block_q, block_k):
    """``edge`` where there is no group (the kernels slice a sub-tile's
    rows out of one head's), the blocks are equal (a row's width is
    then its number in the block: ``_walk``; no cell runs unequal
    blocks without a group, and none was timed), and it tiles them in
    more than one."""
    if edge is None or g > 1 or block_q != block_k or block_q % edge:
        return None
    return edge if edge < block_q else None


def _band(q0, rows, k0, cols, window):
    """Of the [rows, cols] scores whose first (query, key) positions
    are (q0, k0): ``(live, whole)``: whether any is inside the band
    ``key <= query < key + window`` (all of ``key <= query`` without a
    window), and whether all are. Python ints or traced values."""
    live = q0 + rows - 1 >= k0
    whole = q0 >= k0 + cols - 1
    if window is not None:
        live &= q0 - (k0 + cols - 1) < window
        whole &= q0 + rows - 1 - k0 < window
    return live, whole


# ``min`` and ``max`` of block arithmetic that is Python's at trace
# time (the grid, the census) and traced in an index map or a kernel

def _least(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _window_tile(kernel, block_k):
    """The edge of the column tiles in which a windowed ``kernel``
    ("fwd", or "bwd": each of the backward's) takes a key block that
    an edge of the band crosses; the block where it takes it whole, as
    one masked product.

    As read on a v5e at (128, 1024) blocks, groups of 8 and 7 and
    windows of 2048 and 4096 of 16,384 positions
    (``benchmarks/profile_attn_subtiles.py`` and the two cells' traced
    steps; PERF.md section 6, PR 52). The forward reads level at
    every edge (6.68 ms a call whole, 6.74 at 512, 6.71 at 256: its
    step is bound by what its 1,024 rows cost, the reductions, the
    rescale and the state's traffic, not by its columns), so it keeps
    the one masked body. The backward has no such cost a row and
    gains with the columns it leaves out: 13.7 ms a call -> 9.8 at 512
    in ``trinity-mini``'s step and 16.7 -> 13.7 in ``smallthinker``'s.
    At 256 the kernel alone reads a further 0.5 to 0.9 ms less, but in
    both cells' steps 1.7 to 2.0 ms MORE than at 512 (11.6 and 15.7),
    and at 128 (fifteen masked bodies) 6 times the whole block's
    time: past some size of the kernel's program every body more
    costs, and a body is as long as its columns. So the widest edge
    that tiles a block in two."""
    edge = None if kernel == "fwd" else 512
    if edge is None or block_k % edge or block_k <= edge:
        return block_k
    return edge


class _Band(NamedTuple):
    """A windowed call's grid and what a grid step computes, from
    ``(seq, block_q, block_k, window)`` and the column tile's edge
    alone: the one statement of both, for the index maps, the kernels
    and the census. Block indices are Python ints or traced values.

    The grid's minor dimension counts the blocks of the band, not of
    the sequence: step ``n`` of query block ``i`` is key block
    ``first_key_block(i) + n`` (of key block ``j``, query block
    ``first_query_block(j) + n``), and a step past the row's (the
    column's) last live block computes nothing and fetches nothing new
    (the index maps clamp it to the last)."""

    seq: int
    block_q: int
    block_k: int
    window: int
    tile: int

    def first_key_block(self, i):
        """The block of the first key that query block ``i`` sees."""
        return _most(i * self.block_q - self.window + 1, 0) // self.block_k

    def last_key_block(self, i):
        """The block on the diagonal."""
        return (i * self.block_q + self.block_q - 1) // self.block_k

    def first_query_block(self, j):
        return (j * self.block_k) // self.block_q

    def last_query_block(self, j):
        """The block of the last query that sees key block ``j``'s
        last key, or the sequence's last."""
        return _least(
            (j * self.block_k + self.block_k + self.window - 2)
            // self.block_q,
            self.seq // self.block_q - 1,
        )

    def key_steps(self):
        """The most key blocks a query block's band meets."""
        return max(
            self.last_key_block(i) - self.first_key_block(i) + 1
            for i in range(self.seq // self.block_q)
        )

    def query_steps(self):
        """The most query blocks a key block's band meets."""
        return max(
            self.last_query_block(j) - self.first_query_block(j) + 1
            for j in range(self.seq // self.block_k)
        )

    def tiles(self, q_start, k_start):
        """``(first, end)``: the column tiles of the key block at
        ``k_start`` in which some query of the block at ``q_start``
        sees a key (of a live block)."""
        first = _most(q_start - self.window + 1 - k_start, 0) // self.tile
        end = (_least(q_start + self.block_q - k_start, self.block_k)
               + self.tile - 1) // self.tile
        return first, end

    def crossed_tiles(self):
        """``tiles`` of every live block that an edge of the band
        crosses, each once: the static widths a kernel holds a body
        of."""
        found = set()
        for i in range(self.seq // self.block_q):
            q_start = i * self.block_q
            for j in range(self.first_key_block(i),
                           self.last_key_block(i) + 1):
                k_start = j * self.block_k
                if not _band(q_start, self.block_q, k_start,
                             self.block_k, self.window)[1]:
                    found.add(self.tiles(q_start, k_start))
        return sorted(found)


def causal_tile_census(seq, block_q, block_k, sub_q, sub_k, window=None):
    """Over one head's causal [seq, seq] scores, in (sub_q, sub_k)
    sub-tiles: (sub-tiles the live grid blocks cover, which is what a
    whole-block body computes; sub-tiles with an element inside the
    band, on or under the diagonal and within ``window`` of it, which
    is what the walk computes; those of them that an edge of the band
    crosses, where the mask decides something)."""
    covered = computed = masked = 0
    for q_start in range(0, seq, block_q):
        for k_start in range(0, seq, block_k):
            if not _band(q_start, block_q, k_start, block_k, window)[0]:
                continue
            covered += (block_q // sub_q) * (block_k // sub_k)
            for q0 in range(q_start, q_start + block_q, sub_q):
                for k0 in range(k_start, k_start + block_k, sub_k):
                    live, whole = _band(q0, sub_q, k0, sub_k, window)
                    computed += live
                    masked += live and not whole
    return covered, computed, masked


def _set_census_gauges(kernel, seq, block_q, block_k, sub_q, sub_k, window,
                       grid_steps):
    """Where a causal kernel is built (trace time): what share of the
    (sub_q, sub_k) sub-tiles its live blocks cover it computes, how
    many of them an edge of the band crosses, and what share of a
    head's ``grid_steps`` meet a live block."""
    from dlrover_tpu.telemetry.registry import gauge

    covered, computed, masked = causal_tile_census(
        seq, block_q, block_k, sub_q, sub_k, window
    )
    labels = dict(kernel=kernel, window=str(window or "none"))
    gauge(
        "attn_tiles_computed_share",
        "sub-tiles a causal attention kernel computes over those its "
        "live grid blocks cover, at the last one built",
        labelnames=("kernel", "window"),
    ).labels(**labels).set(computed / covered)
    gauge(
        "attn_tiles_masked_share",
        "sub-tiles that the diagonal or the window's edge crosses, "
        "over the same",
        labelnames=("kernel", "window"),
    ).labels(**labels).set(masked / covered)
    live_blocks = covered // ((block_q // sub_q) * (block_k // sub_k))
    gauge(
        "attn_grid_steps_live_share",
        "grid steps of a head that meet a live block over all of its "
        "grid steps, at the last causal attention kernel built",
        labelnames=("kernel", "window"),
    ).labels(**labels).set(live_blocks / grid_steps)


def _rows_of(r0, size, block_q):
    """Index of ``size`` query positions from ``r0`` on in their block
    (a part of it only without a group: ``_sub_tiles``)."""
    return slice(None) if size == block_q else pl.ds(r0, size)


# The kernels' elementwise math is written with ``jax.lax``'s own
# operations, not jnp's: a jnp operator is a jitted function that is
# traced anew for each shape it meets (0.35 ms against 0.09 when first
# met, traced in the sandbox; a chip host's shared cores are slower),
# and a kernel that holds a body a width meets many. The jaxpr is the
# same.

def _selection_words(selected, block_k):
    """``selected`` [rows, seq, seq / block] bool as the kernels read
    it: int32 [rows, seq / block_k, 1, seq], bit c of word [r, j, 0,
    t] whether query t of row r selected the c-th block of key block
    j of the grid. The queries lie along the lanes, as ``lse``'s."""
    rows, seq, blocks = selected.shape
    per = blocks * block_k // seq  # selection blocks a key block
    if (per < 1 or per > tuning.WORD_BITS
            or (seq // blocks) * per != block_k):
        raise ValueError(
            f"a selection over {blocks} blocks of {seq} keys and key "
            f"blocks of {block_k}: 1 to 32 whole blocks to a key block"
        )
    bits = selected.reshape(rows, seq, -1, per).astype(jnp.int32)
    words = jnp.sum(bits << jnp.arange(per, dtype=jnp.int32), axis=-1)
    return words.transpose(0, 2, 1)[:, :, None, :]


def _selected_mask(words, cols, block):
    """[rows, cols] bool from the rows' words [rows, 1]: column c is
    of the key block's ``c // block``-th block, a power of two wide."""
    shape = (words.shape[0], cols)
    which = jax.lax.shift_right_logical(
        jax.lax.broadcasted_iota(jnp.int32, shape, 1),
        jnp.int32(block.bit_length() - 1),
    )
    picked = jax.lax.shift_right_logical(
        jax.lax.broadcast_in_dim(words, shape, (0, 1)), which)
    return jax.lax.ne(
        jax.lax.bitwise_and(picked, jnp.int32(1)), jnp.int32(0))


def _words_of(sel_ref, g, rows=slice(None)):
    """A grid step's selection words as the g-major rows' column,
    [g*rows, 1]: every head of the group reads its kv head's."""
    column = sel_ref[0, 0, 0, rows][:, None]
    return column if g == 1 else jnp.concatenate([column] * g, axis=0)


def _selection(sel_ref, block, g, rows):
    """What ``_scores`` takes of a grid step's selection; None for a
    call without one."""
    if sel_ref is None:
        return None
    return _words_of(sel_ref, g, rows), block


def _scores(q, k, scale, g, diagonal, window=None, selection=None):
    """Scaled scores of g-major rows ``q`` over columns ``k``, masked
    where ``diagonal`` gives their first (query, key) positions, and
    by ``selection`` (the rows' words and the selection's block:
    ``_selected_mask``) where a call has one."""
    # bf16 x bf16 -> fp32 accumulate: the MXU's native mode. Casting
    # inputs to fp32 first would fall off the fast path (~4x slower).
    s = jax.lax.mul(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ), scale)  # [g*rows, cols]
    if diagonal is not None:
        edges = (*diagonal, g, q.shape[0] // g, k.shape[0])
        mask = (_causal_mask(*edges) if window is None
                else _band_mask(*edges, window))
        s = jax.lax.select(mask, s, jax.lax.full_like(s, NEG_INF))
    if selection is not None:
        words, block = selection
        s = jax.lax.select(
            _selected_mask(words, k.shape[0], block), s,
            jax.lax.full_like(s, NEG_INF),
        )
    return s


def _row_reduce(reduce, x):
    """``reduce`` over the columns of ``x``, kept as a [rows, 1] column."""
    return jax.lax.expand_dims(reduce(x, (1,)), (1,))


def _walk(causal, block_q, block_k, sub, q_start, k_start, compute,
          band=None):
    """What one grid step computes, as calls of
    ``compute(r0, size, cols, diagonal)``: the ``size`` query positions
    of the block from ``r0`` on against the block's key positions
    ``cols``, in one product; ``diagonal`` is None, or the product's
    first (query, key) positions where it takes the mask.

    Without ``causal``, one call over the block. With it, a block
    wholly above the diagonal is skipped. Where ``sub`` is None every
    other block is one masked call. Else (the blocks are equal:
    ``_fits``) a block under the diagonal is one call without the
    mask, and the one on it is one call a row of sub-tiles of edge
    ``sub``, over the row's live column tiles: n + 1 in row n.

    With a ``band`` (a window): a block wholly outside the band is
    skipped, one wholly inside it is one call without the mask, and
    one that the diagonal or the window's edge crosses is one masked
    call over its live column tiles (``_Band.tiles``: the leading ones
    of a block on the diagonal, the trailing ones of a block on the
    window's edge), at a static width: one body for each run of tiles
    that the call's geometry holds (``_Band.crossed_tiles``), picked
    by the step's own run. A width from ``program_id`` or a rolled
    loop over the tiles reads 1.3 to 4 times slower (PR 31)."""

    def whole(masked):
        compute(0, block_q, slice(None),
                (q_start, k_start) if masked else None)

    if not causal:
        return whole(False)
    if band is not None:
        live, inside = _band(
            q_start, block_q, k_start, block_k, band.window
        )
        # a step past a key block's last query block (the grid by key
        # blocks, at the sequence's end) is no block at all
        within = q_start < band.seq
        pl.when(jnp.logical_and(inside, within))(lambda: whole(False))
        crossed = jnp.logical_and(
            jnp.logical_and(live, jnp.logical_not(inside)), within
        )
        runs = band.crossed_tiles()
        tiles = block_k // band.tile
        if runs == [(0, tiles)]:
            return pl.when(crossed)(lambda: whole(True))
        first, end = band.tiles(q_start, k_start)
        for a, b in runs:
            cols = slice(None) if (a, b) == (0, tiles) else slice(
                a * band.tile, b * band.tile)
            pl.when(jnp.logical_and(
                crossed, jnp.logical_and(first == a, end == b)
            ))(functools.partial(
                compute, 0, block_q, cols,
                (q_start, k_start + a * band.tile),
            ))
        return None
    live = q_start + block_q - 1 >= k_start
    if sub is None:
        return pl.when(live)(lambda: whole(True))
    under = q_start >= k_start + block_k - 1
    straddling = jnp.logical_and(live, jnp.logical_not(under))
    pl.when(under)(lambda: whole(False))
    n_tiles = block_q // sub

    def row_tile(n, _):
        """Row n of the diagonal block's sub-tiles. Where the unrolled
        loop is lowered n is a constant, and only its width's body is
        lowered."""
        r0 = pl.multiple_of(n * sub, sub)
        return jax.lax.switch(n, [
            functools.partial(
                compute, r0, sub, slice(0, w * sub),
                (q_start + r0, k_start),
            ) for w in range(1, n_tiles + 1)
        ])

    @pl.when(straddling)
    def _rows():
        jax.lax.fori_loop(0, n_tiles, row_tile, None, unroll=True)


def _key_block(band, i, j):
    """The key block of step ``j`` of query block ``i``'s row of the
    grid: the j-th of the sequence, with a band the j-th of the row's
    band."""
    return j if band is None else band.first_key_block(i) + j


def _query_block(band, j, i):
    """The query block of step ``i`` of key block ``j``'s row."""
    return i if band is None else band.first_query_block(j) + i


# ---------------------------------------------------------------------------
# forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, g,
                block_q, block_k, sub, band=None, sel_ref=None,
                sel_block=None):
    i = pl.program_id(1)  # q block
    # k block (minor: sequential, scratch persists); with a band, the
    # row's j-th live one
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = _key_block(band, i, j) * block_k

    def compute(r0, size, cols, diagonal):
        rows = _rows_of(r0, size, block_q)
        s = _scores(
            _stack_groups(q_ref, g, rows), _read(k_ref, (0, cols)), scale,
            g, diagonal, band and band.window,
            _selection(sel_ref, sel_block, g, rows),
        )
        m_prev = m_scr[rows, :1]  # [g*size, 1]
        m_new = jax.lax.max(m_prev, _row_reduce(jax.lax.reduce_max, s))
        p = jax.lax.exp(jax.lax.sub(s, m_new))  # [g*size, cols]
        corr = jax.lax.exp(jax.lax.sub(m_prev, m_new))  # [g*size, 1]
        l_new = jax.lax.add(
            jax.lax.mul(l_scr[rows, :1], corr),
            _row_reduce(jax.lax.reduce_sum, p),
        )
        acc_scr[rows] = jax.lax.add(
            jax.lax.mul(acc_scr[rows], corr),
            jax.lax.dot_general(
                jax.lax.convert_element_type(p, v_ref.dtype), v_ref[0, cols],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
        )
        lanes = (m_new.shape[0], LANES)
        m_scr[rows] = jnp.broadcast_to(m_new, lanes)
        l_scr[rows] = jnp.broadcast_to(l_new, lanes)

    _walk(causal, block_q, block_k, sub, q_start, k_start, compute,
          band)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, 0] + jnp.log(l_safe[:, 0])  # (g*block_q,)
        for gi in range(g):
            o_ref[0, gi] = out[gi * block_q:(gi + 1) * block_q]
            lse_ref[0, gi, 0] = lse[gi * block_q:(gi + 1) * block_q]


def _check_blocks(seq, block_q, block_k):
    if seq % block_q or seq % block_k:
        raise ValueError(
            f"seq {seq} must be divisible by block_q={block_q} and "
            f"block_k={block_k}; pad the sequence or pick smaller blocks"
        )
    if block_q & (block_q - 1):
        # the causal mask derives query positions with `rows & (block_q-1)`
        raise ValueError(f"block_q must be a power of two, got {block_q}")


def _band_of(kernel, seq, block_q, block_k, window):
    """The geometry of a windowed call of ``kernel`` ("fwd" or "bwd");
    None without a window."""
    if window is None:
        return None
    return _Band(
        seq, block_q, block_k, window, _window_tile(kernel, block_k)
    )


def _grid(seq, block_q, block_k, band, by_key_blocks=False):
    """A head's grid: its query blocks by the key blocks each meets
    (all of them, or a band's), or the other way about."""
    nq, nk = seq // block_q, seq // block_k
    if by_key_blocks:
        return nk, (nq if band is None else band.query_steps())
    return nq, (nk if band is None else band.key_steps())


def _kernel(body, name, seq, causal, g, block_q, block_k, q, scale,
            band=None, sel_block=None):
    """``(kernel, grid)``: ``body`` ("fwd", "dq", "dkv", "dqkv" or
    "dq_dkv" by ``name``) with its static arguments, for ``q`` whole
    or in parts, and a head's grid; building one sets the gauge of the
    parts, a causal one the census gauges. A windowed one walks its
    band in column tiles (``_walk``), another the diagonal in
    ``_sub_tiles``, one with a selection (``sel_block``, the keys of
    a block of it) takes its blocks whole."""
    from dlrover_tpu.telemetry.registry import gauge

    gauge(
        "attn_operand_parts",
        "arrays in which an attention kernel reads q and k each, at "
        "the last one built: 1 (whole) or 2 (a head's un-rotated and "
        "rotated columns apart, the rotated key one for every head)",
        labelnames=("kernel",),
    ).labels(kernel=name).set(len(_each(q)))
    gauge(
        "attn_key_block",
        "key positions of a grid block of an attention kernel, at the "
        "last one built: the forward's (fwd) beside the backward's, "
        "whose kernels name the form it took (dqkv: a head's dq "
        "resident; dq_dkv: a kv head's dk and dv; dq and dkv: the pair)",
        labelnames=("kernel",),
    ).labels(kernel=name).set(block_k)
    head_dim = _width(q)
    grid = _grid(seq, block_q, block_k, band, name in ("dkv", "dqkv"))
    sub = None
    if causal:
        if band is None:
            if sel_block is None:
                sub = _sub_tiles(name, block_q, block_k, g, head_dim)
            tile = (sub or block_q, sub or block_k)
        else:
            tile = (block_q, band.tile)
        _set_census_gauges(
            name, seq, block_q, block_k, *tile, band and band.window,
            grid[0] * grid[1],
        )
    static = dict(
        scale=scale, causal=causal, g=g,
        block_q=block_q, block_k=block_k, sub=sub,
    )
    if band is not None:
        static["band"] = band
    if sel_block is not None:
        static["sel_block"] = sel_block
    return functools.partial(body, **static), grid


def _selecting(kernel, operands):
    """``kernel`` for a call whose ref after its ``operands`` others
    is the selection's words: handed on as ``sel_ref``."""

    def body(*refs):
        return kernel(
            *refs[:operands], *refs[operands + 1:], sel_ref=refs[operands]
        )

    return body


def _selection_of(selected, seq, block_k):
    """``(the words the kernels read, the keys of a selection's
    block)`` of ``selected`` [rows, seq, blocks]; two Nones of None."""
    if selected is None:
        return None, None
    return (_selection_words(selected, block_k),
            seq // selected.shape[-1])


def _kv_index(causal, block_q, block_k, band=None):
    """K/V block index for grid step (b, i, j), clamped to the
    diagonal; with a band, step j is the j-th block from the first
    that holds a key the q block's first query sees.

    A causally SKIPPED (j, i) step computes nothing (pl.when), but the
    pipeline would still stream its K/V block from HBM — dead traffic
    that is ~half of all fetches at causal. Clamping the index to the
    diagonal makes every skipped step re-reference the block the live
    diagonal step fetches; Mosaic elides copies whose index didn't
    change, so skipped steps cost no bandwidth."""

    def index(b, i, j):
        j = _key_block(band, i, j)
        if causal:
            diag = (i * block_q + block_q - 1) // block_k
            j = jnp.minimum(j, diag)
        return (b, j, 0)

    return index


def _specs(x, rows, index):
    """Block specs of ``rows`` of a head of q or k, or of their
    gradients, by ``index``: each part its own width."""
    return _parts_of(
        lambda part: pl.BlockSpec((1, *rows, part.shape[-1]), index), x
    )


def _k_specs(k, bkh, block_k, index):
    """``_specs`` of k's key positions. A part with fewer rows than
    the grid has kv heads (latent attention's rotated key, [batch,
    seq, r]) is one that ``bkh // rows`` heads in a row share: each
    reads its batch row's block and none holds a copy."""

    def spec(part):
        shared = bkh // part.shape[0]

        def of_its_row(b, i, j):
            _, *rest = index(b, i, j)
            return (b // shared, *rest)

        return _specs(
            part, (block_k,), index if shared == 1 else of_its_row
        )

    return _parts_of(spec, k)


#: the float32 scores and ``p`` of a grid step that fit beside the
#: rest under the default scoped limit, 16 MiB of a v5e core's 128:
#: ``ops/tuning.py ROWS_CAP`` rows by 1024 columns, which every pair of
#: ``heuristic_blocks`` is within. A forward call within it is the one
#: it always was; past it the call states what it needs
UNSTATED_SCORE_BYTES = tuning.score_bytes(1, tuning.ROWS_CAP, 1024)


def _fwd_vmem_bytes(rows, block_k, d, dv, itemsize):
    """What the forward kernel asks of VMEM where it has to ask
    (``ops/tuning.py forward_key_block``: a group of 16's [2048, 1024]
    scores), None where it does not: a grid step's float32 scores and
    ``p`` (``tuning.score_bytes``); the streamed blocks of q, k, v
    and o, two buffers each, rows of whole lanes; and the rows'
    state, ``m``, ``l`` and the accumulator. 22 MiB at [2048, 1024],
    of which the chip's compiler allocates between 15 and 19; more
    buys nothing (at [2048, 2048] a call read 5.31 ms stating 32 or
    40 MiB and 5.41 stating 64 or 96: PERF.md section 6, PR 66)."""
    scores = tuning.score_bytes(1, rows, block_k)
    if scores <= UNSTATED_SCORE_BYTES:
        return None
    streamed = 2 * itemsize * (
        rows * (_lanes(d) + _lanes(dv))
        + block_k * (_lanes(d) + _lanes(dv)))
    state = 4 * rows * (2 * LANES + _lanes(dv))
    return scores + streamed + state


def _fwd(q, k, v, scale, causal, block_q, block_k, window=None,
         selected=None):
    """q: [bk_h, g, seq, d]; k,v: [bk_h, seq, d] ->
    (o [bk_h, g, seq, dv], lse [bk_h, g, 1, seq] f32). ``v`` may be
    narrower or wider than q and k (``dv``): the scores contract over
    ``d``, the result and its accumulator are ``dv`` wide. q and k may
    each come in parts (tuples: ``_each``), ``d`` wide together."""
    bkh, g, seq, _ = _each(q)[0].shape
    dv = v.shape[-1]
    block_q = min(block_q, seq)
    block_k = min(block_k, seq)
    _check_blocks(seq, block_q, block_k)
    band = _band_of("fwd", seq, block_q, block_k, window)
    words, sel_block = _selection_of(selected, seq, block_k)
    kernel, grid = _kernel(
        _fwd_kernel, "fwd", seq, causal, g, block_q, block_k, q, scale,
        band, sel_block,
    )
    kv_idx = _kv_index(causal, block_q, block_k, band)
    selection = ()
    if words is not None:
        kernel, selection = _selecting(kernel, 3), (words,)
    stated = _fwd_vmem_bytes(
        g * block_q, block_k, _width(q), dv, _dtype(q).itemsize)
    return pl.pallas_call(
        kernel,
        grid=(bkh, *grid),
        in_specs=[
            _specs(q, (g, block_q), lambda b, i, j: (b, 0, i, 0)),
            _k_specs(k, bkh, block_k, kv_idx),
            pl.BlockSpec((1, block_k, dv), kv_idx),
        ] + [
            # the query block's words for the step's key block
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, i, j: (b, kv_idx(b, i, j)[1], 0, i))
            for _ in selection
        ],
        out_specs=[
            pl.BlockSpec((1, g, block_q, dv), lambda b, i, j: (b, 0, i, 0)),
            # [bkh, g, 1, seq]: keeps the lse block's last two dims
            # (1, block_q) under the TPU (8,128)-or-full tiling rule
            pl.BlockSpec((1, g, 1, block_q), lambda b, i, j: (b, 0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkh, g, seq, dv), _dtype(q)),
            jax.ShapeDtypeStruct((bkh, g, 1, seq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g * block_q, LANES), jnp.float32),
            pltpu.VMEM((g * block_q, LANES), jnp.float32),
            pltpu.VMEM((g * block_q, dv), jnp.float32),
        ],
        compiler_params=stated and pltpu.CompilerParams(
            vmem_limit_bytes=stated),
        interpret=_interpret(),
    )(q, k, v, *selection)


# ---------------------------------------------------------------------------
# backward

# The order of the reads and products below is the order in which the
# whole-block kernels have always issued them, and it matters. With a
# group's whole blocks, dS computed before dV is accumulated (P and dS
# both held whole) and V read ahead of the scores read 64.0 ms of
# ``attn_kernel_ms`` at Mistral's shapes where this order reads 62.5.
# The kernels that walk sub-tiles are the other way about in dK/dV
# alone: dS first reads 62.3 ms at gpt2-xl's shape, dV first 75.1
# (PERF.md section 6, PR 31).

def _cols_of(k_ref, cols):
    """A read of k's key positions ``cols``, made where a product
    wants it: of k handed whole from the ref each time, in the order
    above; of k in parts the tile put together once a call."""
    if isinstance(k_ref, tuple):
        k = _read(k_ref, (0, cols))
        return lambda: k
    return lambda: k_ref[0, cols]


def _q_side(q_ref, do_ref, lse_ref, delta_ref, g, rows):
    """What the backward kernels read of the query positions ``rows``:
    q, dO [g*rows, d], lse, delta [g*rows, 1]."""
    return (
        _stack_groups(q_ref, g, rows), _stack_groups(do_ref, g, rows),
        _stack_cols(lse_ref, g, rows), _stack_cols(delta_ref, g, rows),
    )


def _p(q, lse, k, scale, g, diagonal, window=None, selection=None):
    """The softmax re-derived from the saved logsumexp: [g*rows, cols]."""
    return jax.lax.exp(jax.lax.sub(
        _scores(q, k, scale, g, diagonal, window, selection), lse
    ))


def _ds(p, do, v, delta):
    """dS (before its ``scale``) from the softmax and dO."""
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return jax.lax.mul(p, jax.lax.sub(dp, delta))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, g, block_q, block_k, sub,
               add_dkv=None, band=None, sel_ref=None, sel_block=None):
    """``add_dkv(cols, p, ds, q, do)``, where given, takes the softmax
    and each dS the walk forms (cast for the products) of the block's
    key positions ``cols`` on to dV and dK: ``_dq_dkv_kernel``."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = _key_block(band, i, j) * block_k

    def compute(r0, size, cols, diagonal):
        rows = _rows_of(r0, size, block_q)
        q, do, lse, delta = _q_side(
            q_ref, do_ref, lse_ref, delta_ref, g, rows
        )
        k = _cols_of(k_ref, cols)
        p = _p(q, lse, k(), scale, g, diagonal, band and band.window,
               _selection(sel_ref, sel_block, g, rows))
        ds = jax.lax.convert_element_type(
            _ds(p, do, v_ref[0, cols], delta), q.dtype
        )
        acc_scr[rows] += jax.lax.dot_general(
            ds, k(), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if add_dkv is not None:
            add_dkv(cols, p, ds, q, do)

    _walk(causal, block_q, block_k, sub, q_start, k_start, compute,
          band)

    @pl.when(j == nk - 1)
    def _finalize():
        dq = (acc_scr[:] * scale).astype(_dtype(dq_ref))
        for gi in range(g):
            _write(dq_ref, (0, gi), dq[gi * block_q:(gi + 1) * block_q])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, g, block_q, block_k, sub, add_dq=None,
                band=None, sel_ref=None, sel_block=None):
    """``add_dq(r0, size, k, ds)``, where given, takes each dS the
    walk forms (cast for the products) and the read of its key
    positions (``_cols_of``) on to dQ: ``_dqkv_kernel``."""
    j = pl.program_id(1)  # k block (major)
    # q block (minor: accumulates); with a band, the column's i-th
    # live one
    i = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = _query_block(band, j, i) * block_q
    k_start = j * block_k

    def compute(r0, size, cols, diagonal):
        rows = _rows_of(r0, size, block_q)
        q, do, lse, delta = _q_side(
            q_ref, do_ref, lse_ref, delta_ref, g, rows
        )
        k = _cols_of(k_ref, cols)
        p = _p(q, lse, k(), scale, g, diagonal, band and band.window,
               _selection(sel_ref, sel_block, g, rows))
        if sub is not None:  # dS first where sub-tiles are walked (above)
            ds = _ds(p, do, v_ref[0, cols], delta)
        # dV += P^T @ dO — contracting over the g*size rows also sums
        # the GQA group's contributions (the repeat-bwd reduction, for
        # free)
        dv_scr[cols] += jax.lax.dot_general(
            jax.lax.convert_element_type(p, do.dtype), do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if sub is None:
            ds = _ds(p, do, v_ref[0, cols], delta)
        # dK += dS^T @ Q (scale applied once at finalize), spelled out
        # to keep dS as cast: read, cast, product, as ``+=`` issues them
        dk = dk_scr[cols]
        ds = jax.lax.convert_element_type(ds, q.dtype)
        dk_scr[cols] = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if add_dq is not None:
            add_dq(r0, size, k, ds)

    _walk(causal, block_q, block_k, sub, q_start, k_start, compute,
          band)

    @pl.when(i == nq - 1)
    def _finalize():
        _write(dk_ref, (0,),
               (dk_scr[:] * scale).astype(_dtype(dk_ref)))
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                 *, scale, block_q, band=None, **static):
    """The dK/dV kernel, and dQ of the whole head (no group) summed in
    ``dq_scr`` [seq, d] over the grid's (j, i) steps of one head: its
    output block does not move with them. A query block meets its key
    blocks in ascending j, as in ``_dq_kernel``."""
    j, i = pl.program_id(1), pl.program_id(2)
    seq = dq_scr.shape[0]

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def add_dq(r0, size, k, ds):
        if block_q == seq:  # one block a head: static offsets
            rows = _rows_of(r0, size, seq)
        else:
            rows = pl.ds(pl.multiple_of(
                _query_block(band, j, i) * block_q + r0, size), size)
        dq_scr[rows] += jax.lax.dot_general(
            ds, k(), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _dkv_kernel(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        dk_ref, dv_ref, dk_scr, dv_scr,
        scale=scale, block_q=block_q, add_dq=add_dq, band=band, **static,
    )

    @pl.when(jnp.logical_and(
        j == pl.num_programs(1) - 1, i == pl.num_programs(2) - 1
    ))
    def _finalize():
        _write(dq_ref, (0, 0),
               (dq_scr[:] * scale).astype(_dtype(dq_ref)))


def _dq_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                   *, scale, block_k, band=None, **static):
    """The dQ kernel, and dK and dV of the whole kv head summed in
    ``dk_scr`` and ``dv_scr`` [seq, d] over the grid's (i, j) steps of
    one head: their output blocks do not move with them. The dual of
    ``_dqkv_kernel``, for a group: g query heads' dQ is g times a
    head's, dK and dV are a head's whatever g is. A key block meets
    its query blocks in ascending i, as in ``_dkv_kernel``."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def add_dkv(cols, p, ds, q, do):
        # the head's key positions of the block's ``cols``: the whole
        # block, or with a band a run of its column tiles
        start = _key_block(band, i, j) * block_k
        if cols == slice(None):
            cols = pl.ds(pl.multiple_of(start, block_k), block_k)
        else:
            cols = pl.ds(
                pl.multiple_of(start + cols.start, band.tile),
                cols.stop - cols.start,
            )
        # contracting over the g*block_q rows also sums the group; dK
        # ahead of dV reads 2 to 3% faster than after it at every
        # grouped cell's shape (PERF.md section 6, PR 37)
        dk_scr[cols] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dv_scr[cols] += jax.lax.dot_general(
            jax.lax.convert_element_type(p, do.dtype), do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _dq_kernel(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
        scale=scale, block_k=block_k, add_dkv=add_dkv, band=band, **static,
    )

    @pl.when(jnp.logical_and(
        i == pl.num_programs(1) - 1, j == pl.num_programs(2) - 1
    ))
    def _finalize():
        _write(dk_ref, (0,),
               (dk_scr[:] * scale).astype(_dtype(dk_ref)))
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


#: what the resident gradient may hold of VMEM in float32, rows padded
#: to whole lanes, beside the other kernel's own blocks and scratch:
#: a head's dQ without a group, a kv head's dK and dV with one. One
#: budget for both, since both calls state what they take
#: (``_dq_resident_vmem_bytes``, ``_dkv_resident_vmem_bytes``: 60 MiB
#: of a v5e core's 128 at the budget's edge) and neither lives inside
#: the 16 MiB default: the largest that was compiled and timed, 16,384
#: positions of 128 columns' dK and dV (PR 37) and of 192 columns' dQ
#: (two lanes a row; PERF.md section 6, PR 66)
RESIDENT_BYTES = 16 * 1024 * 1024
#: up to here the head's dQ fits beside the rest under the default
#: scoped limit (4096 positions of 128) and the call is the one it
#: always was; past it the call states what it needs
#: (``_dq_resident_vmem_bytes``)
DQ_UNSTATED_BYTES = 2 * 1024 * 1024


def _lanes(head_dim):
    """What a row of ``head_dim`` holds of VMEM: whole lanes."""
    return -(-head_dim // LANES) * LANES


def _one_backward_kernel(g, seq, head_dim):
    """Whether the backward is one kernel, which keeps the smaller
    gradient in VMEM over a head's grid steps and lets the larger
    leave a block at a time: where that gradient, in float32 rows of
    whole lanes, is within ``RESIDENT_BYTES``. Without a group it is
    the head's dQ (``_dqkv_kernel``); with one (dQ is g times a
    head's) the kv head's dK and dV (``_dq_dkv_kernel``).

    As read on a v5e (``benchmarks/profile_attn_subtiles.py``, forward
    and backward of a call; PERF.md section 6, PRs 33 and 37): 4.68 ms
    against the pair's 5.08 at one (1024, 1024) block a 64-wide head,
    6.46 against 7.83 at 4 x 4 such blocks of a 128-wide head, where
    dQ's rows are sliced at an offset from ``program_id``.
    At 16,384 positions of 192 columns, 32 heads, q and k in parts:
    77.9 ms a call forward and backward against the pair's 98.2, and
    in ``kimi``'s step the one kernel 49.5 ms where the pair took 69.4
    (PERF.md section 6, PR 66).
    ``head_dim``: the wider of q and k's and v's, where they differ."""
    held = seq * _lanes(head_dim) * 4
    return (held if g == 1 else 2 * held) <= RESIDENT_BYTES


def backward_form(g, seq, head_dim):
    """The backward a call takes, by the name the gauge
    ``attn_backward_kernels`` and ``ops/tuning.py last_selection``
    give it: ``dq_resident`` or ``dkv_resident`` (one kernel,
    ``_one_backward_kernel``), else ``pair``."""
    if not _one_backward_kernel(g, seq, head_dim):
        return "pair"
    return "dq_resident" if g == 1 else "dkv_resident"


#: what ``_dq_dkv_kernel`` asks of VMEM beside the resident dK and dV,
#: for everything the dQ kernel had (its streamed blocks, dQ's sum and
#: a block's scores, which ``ops/tuning.py ROWS_CAP`` bounds). The
#: chip's compiler allocates 2.5 to 9.1 MiB of it in the cells' steps,
#: but the limit is not only a ceiling: with the default 16 MiB here
#: ``mistral-7b-l4.steady`` read 0.15% under what it reads with 27
#: (PERF.md section 6, PR 37)
OTHER_VMEM_BYTES = 28 * 1024 * 1024


def _dkv_resident_vmem_bytes(seq, head_dim, itemsize):
    """What ``_dq_dkv_kernel`` asks of VMEM: the kv head's float32 dK
    and dV and their whole-head output blocks (two buffers each), rows
    of whole lanes, and ``OTHER_VMEM_BYTES``."""
    return (2 * seq * _lanes(head_dim) * (4 + 2 * itemsize)
            + OTHER_VMEM_BYTES)


def _dq_resident_vmem_bytes(seq, head_dim, itemsize):
    """What ``_dqkv_kernel`` asks of VMEM where it has to ask: the
    head's float32 dQ and its whole-head output block (two buffers),
    rows of whole lanes, and ``OTHER_VMEM_BYTES``."""
    return (seq * _lanes(head_dim) * (4 + 2 * itemsize)
            + OTHER_VMEM_BYTES)


def _bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
         window=None, selected=None):
    from dlrover_tpu.telemetry.registry import gauge

    bkh, g, seq, _ = _each(q)[0].shape
    # v, o and dO's width; q, k, dQ and dK are d wide, over their parts
    d, dv = _width(q), v.shape[-1]
    itemsize = _dtype(q).itemsize
    block_q = min(block_q, seq)
    block_k = min(block_k, seq)
    _check_blocks(seq, block_q, block_k)
    delta = jnp.sum(
        o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )[:, :, None, :]  # [bkh, g, 1, seq] (4-D for TPU block tiling)

    form = backward_form(g, seq, max(d, dv))
    gauge(
        "attn_backward_kernels",
        "Pallas kernels of the attention backward at the last one "
        "built of a form: 1 (dq, dk and dv together, the head's dq or "
        "the kv head's dk and dv resident) or 2 (dq; dk and dv)",
        labelnames=("form",),
    ).labels(form=form).set(2 if form == "pair" else 1)

    band = _band_of("bwd", seq, block_q, block_k, window)
    words, sel_block = _selection_of(selected, seq, block_k)
    selection = () if words is None else (words,)

    def build(body, name):
        kernel, grid = _kernel(
            body, name, seq, causal, g, block_q, block_k, q, scale, band,
            sel_block,
        )
        return (_selecting(kernel, 6) if selection else kernel), grid

    def shapes(x, *rows):
        """dQ's or dK's, ``rows`` a head; of k in parts each part a kv
        head's own, the part that the heads share too
        (``_flash_bwd_rule`` sums it)."""
        return _parts_of(lambda part: jax.ShapeDtypeStruct(
            (bkh, *rows, part.shape[-1]), part.dtype
        ), x)

    def by_query_blocks(resident):
        """The dq kernel's grid, (b, i, j): dq, and where the kv head's
        dK and dV are ``resident`` those too: their whole [seq, d],
        their blocks the same at every (i, j), written back once a
        head. That call says what VMEM it takes (the default scoped
        limit is 16 MiB of a v5e core's 128)."""
        kv_idx = _kv_index(causal, block_q, block_k, band)

        def q_idx(b, i, j):
            return (b, 0, i, 0)

        def lse_idx(b, i, j):
            return (b, 0, 0, i)

        kernel, grid = build(*((_dq_dkv_kernel, "dq_dkv") if resident
                               else (_dq_kernel, "dq")))
        return pl.pallas_call(
            kernel,
            grid=(bkh, *grid),
            in_specs=[
                _specs(q, (g, block_q), q_idx),
                _k_specs(k, bkh, block_k, kv_idx),
                pl.BlockSpec((1, block_k, dv), kv_idx),  # v
                pl.BlockSpec((1, g, block_q, dv), q_idx),
                pl.BlockSpec((1, g, 1, block_q), lse_idx),
                pl.BlockSpec((1, g, 1, block_q), lse_idx),
            ] + [
                pl.BlockSpec(
                    (1, 1, 1, block_q),
                    lambda b, i, j: (b, kv_idx(b, i, j)[1], 0, i))
                for _ in selection
            ],
            out_specs=[_specs(q, (g, block_q), q_idx)] + resident * [
                _specs(k, (seq,), lambda b, i, j: (b, 0, 0)),
                pl.BlockSpec((1, seq, dv), lambda b, i, j: (b, 0, 0)),
            ],
            out_shape=[shapes(q, g, seq)] + resident * [
                shapes(k, seq),
                jax.ShapeDtypeStruct((bkh, seq, dv), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((g * block_q, d), jnp.float32),
            ] + resident * [
                pltpu.VMEM((seq, d), jnp.float32),
                pltpu.VMEM((seq, dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_dkv_resident_vmem_bytes(
                    seq, max(d, dv), itemsize)
            ) if resident else None,
            interpret=_interpret(),
        )(q, k, v, do, lse, delta, *selection)

    def q_side_idx(sublane):
        """Q/dO/lse/delta block index for dkv's (b, j, i) grid, clamped
        UP to the first causally-live q block of k-block j — skipped
        steps (q entirely above the diagonal) re-reference the block
        the first live step fetches, so they cost no bandwidth (same
        trick as _kv_index); with a band, step i is the i-th block
        from that one, clamped DOWN to the block of the last query
        that sees the k block's last key."""

        def index(b, j, i):
            if band is not None:
                i = jnp.minimum(
                    _query_block(band, j, i), band.last_query_block(j)
                )
            elif causal:
                i = jnp.maximum(i, (j * block_k) // block_q)
            return (b, 0, i, 0) if sublane else (b, 0, 0, i)

        return index

    def by_key_blocks(resident):
        """The dk/dv kernel's grid, (b, j, i): dk and dv, and ahead of
        them where the head's dQ is ``resident`` that too: its whole
        [seq, d], its block the same at every (j, i), written back once
        a head."""
        kernel, grid = build(*((_dqkv_kernel, "dqkv") if resident
                               else (_dkv_kernel, "dkv")))
        return pl.pallas_call(
            kernel,
            grid=(bkh, *grid),
            in_specs=[
                _specs(q, (g, block_q), q_side_idx(True)),
                _k_specs(k, bkh, block_k, lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, dv), lambda b, j, i: (b, j, 0)),  # v
                pl.BlockSpec((1, g, block_q, dv), q_side_idx(True)),
                pl.BlockSpec((1, g, 1, block_q), q_side_idx(False)),
                pl.BlockSpec((1, g, 1, block_q), q_side_idx(False)),
            ] + [
                pl.BlockSpec(
                    (1, 1, 1, block_q),
                    lambda b, j, i: (
                        b, j, 0, q_side_idx(False)(b, j, i)[3]))
                for _ in selection
            ],
            out_specs=resident * [
                _specs(q, (1, seq), lambda b, j, i: (b, 0, 0, 0)),
            ] + [
                _specs(k, (block_k,), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, dv), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=resident * [shapes(q, 1, seq)] + [
                shapes(k, seq),
                jax.ShapeDtypeStruct((bkh, seq, dv), v.dtype),
            ],
            scratch_shapes=resident * [pltpu.VMEM((seq, d), jnp.float32)] + [
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_dq_resident_vmem_bytes(
                    seq, d, itemsize)
            ) if resident and seq * d * 4 > DQ_UNSTATED_BYTES else None,
            interpret=_interpret(),
        )(q, k, v, do, lse, delta, *selection)

    if form == "dkv_resident":
        return by_query_blocks(True)
    if form == "dq_resident":
        return by_key_blocks(True)
    return (*by_query_blocks(False), *by_key_blocks(False))


# ---------------------------------------------------------------------------
# public wrapper with custom VJP

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_gqa(q, k, v, selected, scale, causal, block_q, block_k,
               window=None, fwd_block_k=None):
    """``selected``: None, or the selection [rows, seq, blocks] bool,
    an operand that no gradient reaches. ``fwd_block_k``: the forward
    kernel's key block where it is not the backward's ``block_k``."""
    o, _ = _fwd(q, k, v, scale, causal, block_q, fwd_block_k or block_k,
                window, selected)
    return o


def _flash_fwd_rule(q, k, v, selected, scale, causal, block_q, block_k,
                    window, fwd_block_k):
    o, lse = _fwd(q, k, v, scale, causal, block_q, fwd_block_k or block_k,
                  window, selected)
    return o, (q, k, v, selected, o, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, window, fwd_block_k,
                    res, do):
    q, k, v, selected, o, lse = res
    dq, dk, dv = _bwd(
        q, k, v, o, lse, do, scale, causal, block_q, block_k, window,
        selected,
    )
    return dq, jax.tree.map(_summed_over_its_heads, dk, k), dv, None


def _summed_over_its_heads(dk, k):
    """Of k's gradient as the kernels write it, a kv head's own, the
    gradient of a part that several heads read ([rows, seq, r]: latent
    attention's rotated key): the heads' sum, in float32 from the
    kernels' parts, as a broadcast's transpose is. Any other part's
    as it came."""
    if dk.shape == k.shape:
        return dk
    rows, seq, r = k.shape
    return jnp.sum(
        dk.reshape(rows, -1, seq, r), axis=1, dtype=jnp.float32
    ).astype(k.dtype)


_flash_gqa.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_tpu(
    q: jax.Array,  # [batch, seq, heads, head_dim]
    k: jax.Array,  # [batch, seq, kv_heads, head_dim]
    v: jax.Array,  # [batch, seq, kv_heads, v_head_dim]
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
    q_rope: Optional[jax.Array] = None,  # [batch, seq, heads, rope_dim]
    k_rope: Optional[jax.Array] = None,  # [batch, seq, 1, rope_dim]
    selected: Optional[jax.Array] = None,  # [batch, kv_heads, seq, blocks]
    fwd_block_k: Optional[int] = None,
) -> jax.Array:
    """Flash attention in the models' [batch, seq, heads, head_dim]
    layout; GQA folded into the kernels' matmul rows (no KV repeat).
    ``fwd_block_k``: a key block of the forward kernel's own
    (``ops/tuning.py forward_key_block``); None is ``block_k``.
    ``window``: query i sees key j iff ``j <= i`` and ``i - j <
    window`` (causal only); one that reaches every key is no window.
    ``v`` may have a width of its own, which is the result's; the
    default ``scale`` is q and k's ``head_dim ** -0.5``.

    With ``q_rope`` and ``k_rope`` (latent attention) a head's q and k
    are ``q | q_rope`` and ``k | k_rope``, ``k_rope`` one key for
    every head: the kernels read the parts and put a block's tile
    together in VMEM, so neither the whole q and k nor a copy of
    ``k_rope`` a head is ever in memory, and the gradients come back
    in the same parts. ``head_dim`` is then the two widths' sum.

    ``selected`` (bool, causal only and without a window): query t
    sees key j iff ``j <= t`` and ``selected[batch, kv head, t, j //
    block]``, ``block = seq / blocks`` a power of two that divides
    ``block_k``; every head of a kv head has its selection. No
    gradient reaches it."""
    b, s, h, d = q.shape
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together or not at all")
    if q_rope is not None:
        if k_rope.shape[2] != 1:
            raise ValueError(
                f"k_rope {k_rope.shape}: one rotated key for every head"
            )
        d += q_rope.shape[3]
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"window={window}: a window is a causal band of at "
                "least one key"
            )
        if window >= s:
            window = None
    if selected is not None:
        block = s // selected.shape[-1]
        if (not causal or window is not None or block & (block - 1)
                or selected.shape != (b, k.shape[2], s, s // block)):
            raise ValueError(
                f"selected {selected.shape} for q {q.shape}, k {k.shape}, "
                f"causal={causal}, window={window}: a causal call "
                "without a window, [batch, kv_heads, seq, blocks] of "
                "a power of two keys each"
            )
        selected = selected.reshape(-1, *selected.shape[2:])
    kvh = k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    # [b, s, h, d] -> [b*kvh, g, s, d]: query head i = (i // g, i % g)
    def q_layout(x):
        return x.transpose(0, 2, 1, 3).reshape(b * kvh, g, s, x.shape[3])

    def kv_layout(x):
        return x.transpose(0, 2, 1, 3).reshape(b * kvh, s, x.shape[3])

    qg, kg = q_layout(q), kv_layout(k)
    if q_rope is not None:
        qg, kg = (qg, q_layout(q_rope)), (kg, k_rope[:, :, 0])
    o = _flash_gqa(
        qg, kg, kv_layout(v), selected, scale, causal, block_q, block_k,
        window, fwd_block_k,
    )
    return o.reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"
