"""Grouped matmul over ragged groups: the expert projections of a
dropless mixture-of-experts layer (parallel/moe.py).

``lhs`` holds rows sorted by group, ``rhs`` one matrix a group, and
``group_sizes`` how many consecutive rows belong to each: row ``r`` of
group ``g`` is multiplied by ``rhs[g]``. Groups may be empty; their
sizes sum to the number of rows.

Two routes to the same product, chosen by what one v5e chip read at
98,304 rows against 64 matrices of 2048 x 1024 (PERF.md, PR 29; ms
for the forward product / for it and its two backward products):

- ``jax.lax.ragged_dot``, which the chip's compiler lowers to a
  Mosaic kernel of its own over 512 x 512 x 512 tiles
  (``ragged-dot-none`` in a trace): 4.03 / 13.99. Also what runs off
  the TPU, as XLA's plain expansion, and for shapes the tiles below
  do not divide.
- ``jax.experimental.pallas.ops.tpu.megablox``'s ``gmm`` (and, behind
  its ``custom_vjp``, ``gmm`` against the transposed matrices for the
  rows' gradient and ``tgmm`` for the matrices'), the same walk over
  tiles group by group with the rows of a neighbouring group masked.
  At its default 128-cubed tiles 38.7 / 128.5; at ``TILES`` 3.15 /
  9.85, which is why it is the route on the chip. Larger tiles in any
  dimension do not fit the kernel's VMEM.
"""

import jax
import jax.numpy as jnp

#: (rows, contraction, columns) of one tile of the megablox kernels
TILES = (512, 1024, 1024)


def _use_pallas(lhs: jax.Array, rhs: jax.Array) -> bool:
    if jax.default_backend() != "tpu":
        return False
    shape = (lhs.shape[0], rhs.shape[1], rhs.shape[2])
    return lhs.dtype == rhs.dtype == jnp.bfloat16 and not any(
        size % tile for size, tile in zip(shape, TILES)
    )


def grouped_matmul(
    lhs: jax.Array,  # [rows, k], rows sorted by group
    rhs: jax.Array,  # [groups, k, n]
    group_sizes: jax.Array,  # int32 [groups], sums to rows
) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[group of r]``, [rows, n] in ``lhs``'s
    dtype, accumulated in float32."""
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
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=TILES,
        )
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype
    )
