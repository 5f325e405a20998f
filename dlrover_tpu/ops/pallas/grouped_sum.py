"""Pallas TPU kernel that adds a grouped product to sums that are
there: ``into[g] += lhs[rows of g].T @ rhs[rows of g]``, in place.

It is megablox's ``tgmm`` (``jax.experimental.pallas.ops.tpu.
megablox``) with ``existing_out``, the kernel and the call as they
are there: the grid ``(column tiles, contraction tiles, row tiles)``,
a row tile that holds two groups' rows visited once for each with
the other's masked, a float32 accumulator over a group's row tiles,
the sum aliased onto the result. One thing differs. Megablox gives
every group a grid step, because a result of its own has to be
written everywhere, and with ``existing_out`` the step of a group
without a row reads the group's tile of the sum and writes it back.
Here the sum is required, so nothing has to be written where nothing
is added: the grid is built as megablox's ``gmm`` builds its own
(``make_group_metadata(..., visit_empty_groups=False)``), a group
without a row has no step, and its tiles are neither fetched nor
written and keep ``into``'s values through the alias. With no group
that has a row the grid is empty and the result is ``into``.

For a caller that multiplies in pieces and whose piece holds rows of
a few of the groups (parallel/moe.py's walk in chunks: rows sorted by
expert, so 3 of 8 or 6 of 16 experts a chunk), which then moves the
sums of those and not of all (PERF.md, PR 39).
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def add_grouped_product(
    into: jax.Array,  # [groups, k, n]
    lhs: jax.Array,  # [m, k], rows sorted by group
    rhs: jax.Array,  # [m, n], in ``lhs``'s dtype
    group_sizes: jax.Array,  # int32 [groups], sums to m or fewer
    tiling,  # (rows, k, n) of a tile, each dividing its dimension
    interpret: bool = False,
) -> jax.Array:
    """``into`` with each group's ``lhs[rows].T @ rhs[rows]`` added,
    accumulated in float32 and rounded to ``into``'s dtype where it
    is stored. Rows past the groups' sum take no part. Not jitted: a
    device trace names the call after the innermost jitted function
    that holds it, which is the caller's to choose
    (ops/grouped_matmul.py)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    (m, k), n = lhs.shape, rhs.shape[1]
    tm, tk, tn = tiling
    if lhs.dtype != rhs.dtype or rhs.shape[0] != m or (
        into.shape != (group_sizes.shape[0], k, n)
    ):
        raise ValueError(
            f"add_grouped_product: {lhs.dtype}{lhs.shape} against "
            f"{rhs.dtype}{rhs.shape} into {into.shape}"
        )
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiles {tiling} of ({m}, {k}, {n})")
    group_metadata, live_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=0,
        num_nonzero_groups=into.shape[0], visit_empty_groups=False,
    )

    def rows_of_group(grid_id, group_metadata, width):
        """bool [tm, width]: the tile's rows that are the group's."""
        group_offsets, group_ids, m_tile_ids = group_metadata
        group = group_ids[grid_id]
        row = m_tile_ids[grid_id] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, width), 0
        )
        return jnp.logical_and(
            row >= group_offsets[group], row < group_offsets[group + 1]
        )

    def kernel(group_metadata, lhs, rhs, into, out, acc):
        grid_id = pl.program_id(2)
        group_ids = group_metadata[1]
        group = group_ids[grid_id]
        # every step is of a group with rows: the first and the last
        # of a group's run are told by its neighbours in ``group_ids``
        first = jnp.logical_or(
            grid_id == 0, group_ids[jnp.maximum(grid_id - 1, 0)] != group
        )

        @pl.when(first)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        # through float32: v5e's vector unit selects no bf16
        mine = jax.lax.select(
            rows_of_group(grid_id, group_metadata, tk),
            lhs[...].astype(jnp.float32),
            jnp.zeros(lhs.shape, jnp.float32),
        ).swapaxes(0, 1)
        theirs = jax.lax.select(
            rows_of_group(grid_id, group_metadata, tn),
            rhs[...].astype(jnp.float32),
            jnp.zeros(rhs.shape, jnp.float32),
        )
        acc[...] += jax.lax.dot(
            mine.astype(lhs.dtype), theirs.astype(rhs.dtype),
            preferred_element_type=jnp.float32,
        )

        at_end = grid_id == pl.num_programs(2) - 1
        last = jnp.logical_or(
            at_end,
            group_ids[jnp.where(at_end, grid_id, grid_id + 1)] != group,
        )

        @pl.when(last)
        def _store():
            out[...] = (
                acc[...] + into[...].astype(jnp.float32)
            ).astype(out.dtype)

    def lhs_block(n_i, k_i, grid_id, group_metadata):
        return group_metadata[2][grid_id], k_i

    def rhs_block(n_i, k_i, grid_id, group_metadata):
        return group_metadata[2][grid_id], n_i

    def sum_block(n_i, k_i, grid_id, group_metadata):
        return group_metadata[1][grid_id], k_i, n_i

    sum_spec = pl.BlockSpec((None, tk, tn), sum_block)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(into.shape, into.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_block),
                pl.BlockSpec((tm, tn), rhs_block),
                sum_spec,
            ],
            out_specs=sum_spec,
            grid=(n // tn, k // tk, live_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        # the sum is the sixth flat operand: the metadata's three
        # arrays and the two operands come before it
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        # megablox's estimate, the sums counted once whatever is
        # visited: XLA's scheduler reads it, and the step around the
        # call should be scheduled as it was
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n,
            bytes_accessed=(
                lhs.size * lhs.dtype.itemsize * (n // tn)
                + rhs.size * rhs.dtype.itemsize * (k // tk)
                + into.size * into.dtype.itemsize
            ),
            transcendentals=0,
        ),
    )(group_metadata, lhs, rhs, into)
