"""Grouped matmul over ragged groups: the expert projections of a
dropless mixture-of-experts layer (parallel/moe.py).

``lhs`` holds rows sorted by group, ``rhs`` one matrix a group, and
``group_sizes`` how many consecutive rows belong to each: row ``r`` of
group ``g`` is multiplied by ``rhs[g]``. Groups may be empty. Their
sizes sum to the number of rows, or to fewer where the caller says the
groups do not fill the buffer (``filled=False``: a layer that holds a
share of its experts sorts the rows of the absent ones last): rows
past the sum then come back zero, take no product's time and give
their operand no gradient. What a kernel leaves in them is never read.

Two routes to the same product, chosen by what one v5e chip read at
98,304 rows against 64 matrices of 2048 x 1024 (PERF.md, PR 29; ms
for the forward product / for it and its two backward products):

- ``jax.lax.ragged_dot``, which the chip's compiler lowers to a
  Mosaic kernel of its own over 512 x 512 x 512 tiles
  (``ragged-dot-none`` in a trace): 4.03 / 13.99. Also what runs off
  the TPU, as XLA's plain expansion, and for shapes ``tiles`` cannot
  tile.
- ``jax.experimental.pallas.ops.tpu.megablox``'s ``gmm`` (and, in the
  backward pass, ``gmm`` against the transposed matrices for the
  rows' gradient and ``tgmm`` for the matrices'), the same walk over
  tiles group by group with the rows of a neighbouring group masked,
  over the active tiles only. At its default 128-cubed tiles 38.7 /
  128.5; at (512, 1024, 1024) 3.15 / 9.85, which is why it is the
  route on the chip. Larger tiles in any dimension do not fit the
  kernel's VMEM.

A caller that multiplies the same matrices in several pieces
(parallel/moe.py's walk in chunks) keeps float32 sums over the pieces
and adds a piece's part in place: ``add_rhs_gradient`` for the
matrices' gradient, ``add_rows`` for the rows' sum into their tokens.
Both are the repo's own in-place ``tgmm`` (ops/pallas/grouped_sum.py:
megablox's kernel with its sum required), which gives a group a grid
step only where the piece holds a row of it, so a piece moves the sums
of the experts it has rows of and not of all (PERF.md, PR 39; one
v5e chip, 10,240 rows of 3 of 8 experts into ``f32[8, 2048, 1792]``:
0.49 ms a call where megablox's, which visits every group to write
it, reads 0.73). With no sum to add to (``_gmm_bwd``, one product
over all rows) every group has to be written once, and megablox's
``tgmm`` stays.

The tiles are a static rule on the shapes (``tiles``), as the
attention blocks are (ops/tuning.py): each product of the three, the
forward's and the two backward ones, gets the tiles of its own
contraction and columns, so a 2560 x 768 expert is walked in (512,
640, 768) forward and (512, 768, 640) for the rows' gradient.
"""

import functools

import jax
import jax.numpy as jnp

#: the most rows, and the widest contraction and columns, of one tile
#: of the megablox kernels: what was timed, and what fits their VMEM
TILE_CAPS = (512, 1024, 1024)
LANES = 128


#: the most elements of a [contraction, columns] tile of a float32
#: result that the in-place ``tgmm`` adds to: it holds the tile five
#: times (the sum read and written, each double-buffered, and its own
#: accumulator), and 1024 x 896 of them are refused at 21.25M of the
#: 16M of scoped VMEM where 640 x 768 and 512 x 896 compile
IN_PLACE_TILE = 512 * 1024


def tiles(rows: int, contraction: int, columns: int, most: int = None):
    """(rows, contraction, columns) of one tile: in each dimension the
    largest multiple of 128 that divides it within ``TILE_CAPS``
    ((512, 1024, 1024) at 2048 x 1024, (512, 640, 768) at 2560 x
    768, (512, 1024, 896) at 2048 x 1792, (512, 1024, 640) at 4096 x
    1280 and (512, 640, 1024) at its reverse); None where a dimension
    has no such divisor. ``most``: the most elements of the
    [contraction, columns] face, where the caller's result is wider
    than bf16; the largest face within it, the longer contraction
    among equals ((512, 512, 896) at 2048 x 1792, (512, 512, 640) at
    4096 x 1280, and 2560 x 768's as above)."""
    fits = [
        [t for t in range(LANES, cap + 1, LANES) if size % t == 0]
        for size, cap in zip((rows, contraction, columns), TILE_CAPS)
    ]
    if not all(fits):
        return None
    faces = [
        (k, n) for k in fits[1] for n in fits[2]
        if most is None or k * n <= most
    ]
    if not faces:
        return None
    return (max(fits[0]), *max(faces, key=lambda f: (f[0] * f[1], f[0])))


def _use_pallas(lhs: jax.Array, rhs: jax.Array) -> bool:
    if jax.default_backend() != "tpu":
        return False
    return lhs.dtype == rhs.dtype == jnp.bfloat16 and tiles(
        lhs.shape[0], rhs.shape[1], rhs.shape[2]
    ) is not None


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _within(rows: int, group_sizes: jax.Array) -> jax.Array:
    """bool [rows, 1]: the rows that belong to a group."""
    return (jnp.arange(rows, dtype=jnp.int32)
            < jnp.sum(group_sizes))[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, filled):
    return _gmm_fwd(lhs, rhs, group_sizes, filled)[0]


def _gmm_fwd(lhs, rhs, group_sizes, filled):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    out = gmm(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
        tiling=tiles(lhs.shape[0], rhs.shape[1], rhs.shape[2]),
        interpret=_interpret(),
    )
    if not filled:
        out = jnp.where(_within(out.shape[0], group_sizes), out, 0)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(filled, residual, grad):
    """As megablox's own ``custom_vjp``, with each product's own
    tiles, and the rows past the groups' sum zero."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        gmm, tgmm as whole_tgmm,
    )

    lhs, rhs, group_sizes = residual
    rows, (_, k, n) = lhs.shape[0], rhs.shape
    grad_lhs = gmm(
        grad, rhs, group_sizes, preferred_element_type=lhs.dtype,
        tiling=tiles(rows, n, k), transpose_rhs=True,
        interpret=_interpret(),
    )
    if not filled:
        grad_lhs = jnp.where(_within(rows, group_sizes), grad_lhs, 0)
    grad_rhs = whole_tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes,
        preferred_element_type=rhs.dtype, tiling=tiles(rows, k, n),
        num_actual_groups=rhs.shape[0], interpret=_interpret(),
    )
    return grad_lhs, grad_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(
    lhs: jax.Array,  # [rows, k], rows sorted by group
    rhs: jax.Array,  # [groups, k, n]
    group_sizes: jax.Array,  # int32 [groups], sums to rows or fewer
    filled: bool = True,
) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[group of r]``, [rows, n] in ``lhs``'s
    dtype, accumulated in float32. ``filled``: the sizes sum to the
    number of rows; where the caller says they may not, rows past the
    sum are zero, forward and in ``lhs``'s gradient."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape} against rhs {rhs.shape}"
        )
    if group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"grouped_matmul: {rhs.shape[0]} groups, group_sizes "
            f"{group_sizes.shape}"
        )
    group_sizes = group_sizes.astype(jnp.int32)
    if _use_pallas(lhs, rhs):
        return _gmm(lhs, rhs, group_sizes, filled)
    if not filled:
        # the mask on both sides of the product: a row past the sum
        # reads as zero and, by the select's own transpose, is given
        # no gradient
        within = _within(lhs.shape[0], group_sizes)
        lhs = jnp.where(within, lhs, 0)
    out = jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype
    )
    return out if filled else jnp.where(within, out, 0)


def add_rhs_gradient(
    into: jax.Array,  # [groups, k, n]
    lhs: jax.Array,  # [rows, k], rows sorted by group
    grad: jax.Array,  # [rows, n]
    group_sizes: jax.Array,  # int32 [groups], sums to rows or fewer
) -> jax.Array:
    """``into[g] + lhs[rows of g].T @ grad[rows of g]``: what
    ``grouped_matmul(lhs, rhs, group_sizes)`` gives ``rhs`` for the
    cotangent ``grad`` of its result, accumulated in float32 and
    added to ``into`` in ``into``'s dtype. For a caller that
    multiplies the same matrices in several pieces (parallel/moe.py's
    walk in chunks) and keeps a float32 ``into`` until the last: the
    sum is then rounded to the matrices' dtype once, as one product
    over all rows rounds it. On the TPU the in-place ``tgmm``
    (ops/pallas/grouped_sum.py) reads and writes ``into`` where it
    is, a tile of a group that has rows in the piece once and a group
    without a row not at all, where a product of its own and an add
    would pass over every matrix three times a piece. Rows past the
    sum take no part."""
    group_sizes = group_sizes.astype(jnp.int32)
    if _use_pallas(lhs, jax.ShapeDtypeStruct(into.shape, grad.dtype)):
        return tgmm(into, lhs, grad, group_sizes)
    _, to_rhs = jax.vjp(
        lambda rhs: jax.lax.ragged_dot(
            lhs.astype(into.dtype), rhs, group_sizes
        ),
        jnp.zeros_like(into),
    )
    within = _within(lhs.shape[0], group_sizes)
    return into + to_rhs(jnp.where(within, grad, 0).astype(into.dtype))[0]


#: indices of one group of ``add_rows``' product: a tile's contraction
ROW_BLOCK = 512


def _add_on_mxu(out: jax.Array, rows: jax.Array) -> bool:
    if jax.default_backend() != "tpu":
        return False
    return (
        rows.dtype == jnp.bfloat16 and out.dtype == jnp.float32
        and out.shape[0] % ROW_BLOCK == 0
        and tiles(rows.shape[0], ROW_BLOCK, out.shape[1]) is not None
    )


def add_rows(out: jax.Array, index: jax.Array, rows: jax.Array) -> jax.Array:
    """``out.at[index].add(rows)``: ``out`` [n, h] float32, ``rows``
    [m, h], ``index`` int32 [m] within ``0 .. n - 1``, an index as
    often as it likes. How a layer that walks its rows in chunks
    (parallel/moe.py) adds a chunk's results to their tokens.

    The chip's scatter-add takes a row at a time (7.4 ms for 8,192
    rows of 2560 into 16,384, 1.75 with the indices sorted and said
    to be; a gather of as many rows 0.35: PERF.md, PR 35). On the TPU
    the sum is therefore a grouped product: the rows sorted by index,
    a group for each ``ROW_BLOCK`` consecutive indices, and the
    in-place ``tgmm`` of the one-hot place of each row in its block
    against the rows, added into ``out`` where it is: exact, products
    of 0 or 1 summed in float32. A block that gets no row is not
    visited (at the cells' shapes nearly every block gets one from
    every chunk but a layer's last)."""
    if not _add_on_mxu(out, rows):
        return out.at[index].add(rows.astype(out.dtype))
    n, h = out.shape
    by_index = jnp.argsort(index)
    index, rows = index[by_index], rows[by_index]
    starts = jnp.searchsorted(
        index, jnp.arange(0, n + 1, ROW_BLOCK, dtype=index.dtype)
    )
    place = (
        (index % ROW_BLOCK)[:, None]
        == jnp.arange(ROW_BLOCK, dtype=index.dtype)[None, :]
    ).astype(rows.dtype)
    return _rows_by_place(
        place, rows, (starts[1:] - starts[:-1]).astype(jnp.int32),
        out.reshape(n // ROW_BLOCK, ROW_BLOCK, h),
    ).reshape(n, h)


@jax.jit
def tgmm(into, lhs, grad, group_sizes):
    """``add_rhs_gradient``'s product on the TPU, jitted under
    megablox's name for it: a device trace names a Pallas call after
    the innermost jitted function that holds it, and ``tgmm.<n>`` in
    a trace is an expert matmul's gradient, ``_gmm_bwd``'s whole or
    a piece's part here (tests/test_chip_compile.py holds the name)."""
    from dlrover_tpu.ops.pallas.grouped_sum import add_grouped_product

    return add_grouped_product(
        into, lhs, grad, group_sizes,
        tiles(
            lhs.shape[0], *into.shape[1:],
            most=IN_PLACE_TILE if into.dtype.itemsize > 2 else None,
        ),
        interpret=_interpret(),
    )


@jax.jit
def _rows_by_place(place, rows, group_sizes, out):
    """``add_rows``' product, the same kernel jitted under a name of
    its own: ``tgmm.<n>`` in a trace is an expert matmul's gradient,
    which this is not (tests/test_chip_compile.py holds both
    names). A block of indices that gets no row of the piece is
    neither read nor written."""
    from dlrover_tpu.ops.pallas.grouped_sum import add_grouped_product

    return add_grouped_product(
        out, place, rows, group_sizes,
        tiles(rows.shape[0], ROW_BLOCK, rows.shape[1]),
        interpret=_interpret(),
    )
