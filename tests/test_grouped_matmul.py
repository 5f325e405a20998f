"""``ops/grouped_matmul.py``: the tile rule from the shapes, and
buffers that the groups do not fill (a share of the experts sorts the
absent ones' rows last): forward and both gradients against a loop
over the groups, on both routes (megablox in interpret mode); the two
sums that a walk in chunks adds a chunk's part to (``add_rows``,
``add_rhs_gradient``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# -- the tile rule, and buffers that the groups do not fill ---------------

def test_tiles_follow_the_shapes():
    from dlrover_tpu.ops.grouped_matmul import tiles

    # OLMoE's experts keep the tiles that were timed (PR 29)
    assert tiles(98304, 2048, 1024) == (512, 1024, 1024)
    assert tiles(98304, 1024, 2048) == (512, 1024, 1024)
    # 2560 x 768: neither is a multiple of 1024, or of 512 and 1024
    assert tiles(98304, 2560, 768) == (512, 640, 768)
    assert tiles(98304, 768, 2560) == (512, 768, 640)
    assert tiles(384, 256, 128) == (384, 256, 128)
    assert tiles(1000, 256, 128) is None  # rows in no tile
    assert tiles(512, 200, 128) is None


def _ragged_case(seed, rows, k, n, sizes, dtype):
    keys = jax.random.split(jax.random.key(seed), 2)
    lhs = jax.random.normal(keys[0], (rows, k), jnp.float32).astype(dtype)
    rhs = (jax.random.normal(keys[1], (len(sizes), k, n), jnp.float32)
           * k ** -0.5).astype(dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _loop_over_groups(lhs, rhs, sizes):
    """Each group's rows by its matrix; rows past the sum zero."""
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32), 0
    for g, size in enumerate(np.asarray(sizes)):
        rows = slice(start, start + int(size))
        out = out.at[rows].set(
            lhs[rows].astype(jnp.float32) @ rhs[g].astype(jnp.float32))
        start += int(size)
    return out


@pytest.mark.parametrize("route", ["ragged_dot", "megablox"])
@pytest.mark.parametrize("k,n", [(2560, 768), (768, 2560)],
                         ids=["gate_up", "down"])
def test_rows_past_the_groups_sum(route, k, n, monkeypatch):
    """A share's buffer: 1,024 rows of which the four groups fill 700
    (one empty, one ending inside a tile), the rest left as they came
    (here: large numbers, which must not be read as data). Forward,
    and both gradients, against a loop over the groups; on the Pallas
    route (interpret mode) at the tiles the rule gives a 2560 x 768
    expert."""
    from dlrover_tpu.ops import grouped_matmul as gm

    dtype = jnp.float32
    if route == "megablox":
        dtype = jnp.bfloat16
        monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
        assert gm.tiles(1024, k, n) == (
            (512, 640, 768) if k == 2560 else (512, 768, 640))
    lhs, rhs, sizes = _ragged_case(11, 1024, k, n, (300, 0, 250, 150), dtype)
    lhs = lhs.at[700:].set(1e4)

    def ours(lhs, rhs):
        return gm.grouped_matmul(lhs, rhs, sizes, filled=False)

    want = _loop_over_groups(lhs, rhs, sizes)
    got = ours(lhs, rhs)
    tol = dict(rtol=2e-2, atol=2e-2) if route == "megablox" else dict(
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got.astype(jnp.float32), want, **tol)
    assert float(jnp.abs(got[700:]).max()) == 0.0

    weight = jax.random.normal(jax.random.key(12), want.shape)
    d_lhs, d_rhs = jax.grad(
        lambda a, b: jnp.sum(ours(a, b).astype(jnp.float32) * weight),
        argnums=(0, 1))(lhs, rhs)
    w_lhs, w_rhs = jax.grad(
        lambda a, b: jnp.sum(_loop_over_groups(a, b, sizes) * weight),
        argnums=(0, 1))(lhs, rhs)
    assert float(jnp.abs(d_lhs[700:]).max()) == 0.0
    scale = dict(rtol=3e-2, atol=0.3) if route == "megablox" else dict(
        rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(
        d_lhs.astype(jnp.float32), w_lhs.astype(jnp.float32), **scale)
    np.testing.assert_allclose(
        d_rhs.astype(jnp.float32), w_rhs.astype(jnp.float32), **scale)
    assert float(jnp.abs(d_rhs[1]).max()) == 0.0  # the empty group


# -- the sums a chunk of parallel/moe.py's walk adds its part to ----------

@pytest.mark.parametrize("rows,low,high", [
    (256, 0, 300), (384, 0, 300), (256, 128, 256), (384, 130, 380),
    (256, 500, 512),
], ids=["256", "384", "one_block_of_four", "first_and_last_empty",
        "last_block_alone"])
def test_add_rows_as_a_grouped_product(monkeypatch, rows, low, high):
    """The TPU's way to add rows to their indices (sorted by index, a
    group a block of indices, the one-hot places against the rows) in
    interpret mode, against the scatter-add that runs elsewhere:
    indices that occur several times, blocks that get no row (the
    kernel gives them no grid step: they come back to the bit), a
    block's edge inside a row tile."""
    from dlrover_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "ROW_BLOCK", 128)
    ks = jax.random.split(jax.random.key(rows), 3)
    out = jax.random.normal(ks[0], (512, 256))
    update = jax.random.normal(ks[1], (rows, 256)).astype(jnp.bfloat16)
    index = jax.random.randint(ks[2], (rows,), low, high)
    index = index.at[:5].set(low + 7)
    want = gm.add_rows(out, index, update)
    monkeypatch.setattr(gm, "_add_on_mxu", lambda out, rows: True)
    got = gm.add_rows(out, index, update)
    np.testing.assert_allclose(got, want, atol=1e-5)
    untouched = np.ones(512, bool)
    for block in np.unique(np.asarray(index) // 128):
        untouched[block * 128:(block + 1) * 128] = False
    assert untouched.any()
    np.testing.assert_array_equal(got[untouched], out[untouched])


#: (rows, sizes) of a piece against four groups of 128 x 256: groups
#: that are empty, rows past the groups' sum, all rows in one group of
#: the four, the first and the last group empty, no group with a row,
#: and (768 rows: two row tiles of 384) groups whose edges lie inside
#: a row tile
PIECES = [
    (256, (100, 0, 156, 0)), (256, (30, 40, 50, 8)),
    (256, (0, 0, 200, 0)), (256, (0, 100, 156, 0)),
    (256, (0, 0, 0, 0)), (768, (100, 300, 0, 200)),
    (768, (0, 0, 0, 500)),
]
PIECE_IDS = ["empty_groups", "rows_past_the_sum", "one_group_of_four",
             "first_and_last_empty", "no_group_with_a_row",
             "edges_inside_a_row_tile", "last_group_over_two_tiles"]


def _piece(rows, sizes):
    ks = jax.random.split(jax.random.key(rows + sum(sizes)), 3)
    lhs = jax.random.normal(ks[0], (rows, 128)).astype(jnp.bfloat16)
    grad = jax.random.normal(ks[1], (rows, 256)).astype(jnp.bfloat16)
    into = jax.random.normal(ks[2], (len(sizes), 128, 256))
    return into, lhs, grad, jnp.array(sizes, jnp.int32)


@pytest.mark.parametrize("rows,sizes", PIECES, ids=PIECE_IDS)
def test_add_rhs_gradient_in_place(monkeypatch, rows, sizes):
    """A chunk's part of the matrices' gradient added to the float32
    sum that earlier chunks left: the kernel that reads and writes
    the sum in place (interpret mode) and the product and the add
    that run off the TPU, with groups that are empty and rows past
    the groups' sum. Neither rounds to the rows' bfloat16, and the
    kernel gives a group without a row back to the bit: it has no
    grid step."""
    from dlrover_tpu.ops import grouped_matmul as gm

    into, lhs, grad, group_sizes = _piece(rows, sizes)
    by_hand, start = [], 0
    for g, size in enumerate(sizes):
        at = slice(start, start + size)
        by_hand.append(into[g] + jnp.dot(
            lhs[at].T.astype(jnp.float32), grad[at].astype(jnp.float32),
            precision="highest"))
        start += size
    want = gm.add_rhs_gradient(into, lhs, grad, group_sizes)
    monkeypatch.setattr(gm, "_use_pallas", lambda lhs, rhs: True)
    got = gm.add_rhs_gradient(into, lhs, grad, group_sizes)
    for result in (want, got):
        assert result.dtype == jnp.float32
        # a bfloat16 sum would be off by up to 0.06 at these sizes
        np.testing.assert_allclose(result, jnp.stack(by_hand), atol=2e-4)
    empty = np.asarray(sizes) == 0
    np.testing.assert_array_equal(got[empty], into[empty])
    if not sum(sizes):
        np.testing.assert_array_equal(got, into)


@pytest.mark.parametrize("rows,sizes", PIECES, ids=PIECE_IDS)
def test_in_place_kernel_is_megabloxs_without_the_empty_visits(rows, sizes):
    """``ops/pallas/grouped_sum.py`` against megablox's
    ``tgmm(existing_out=...)`` on the same inputs, both in interpret
    mode: equal to the bit, the groups with rows and those without."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    from dlrover_tpu.ops.pallas.grouped_sum import add_grouped_product

    into, lhs, grad, group_sizes = _piece(rows, sizes)
    tiling = (rows // 2, 128, 128)
    want = tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes,
        preferred_element_type=jnp.float32, tiling=tiling,
        existing_out=into, interpret=True,
    )
    got = add_grouped_product(
        into, lhs, grad, group_sizes, tiling, interpret=True)
    np.testing.assert_array_equal(got, want)
    empty = np.asarray(sizes) == 0
    np.testing.assert_array_equal(got[empty], into[empty])
    if not empty.all():
        assert not np.array_equal(got[~empty], into[~empty])


def test_in_place_kernel_refuses_what_it_cannot_tile():
    from dlrover_tpu.ops.pallas.grouped_sum import add_grouped_product

    into, lhs, grad, group_sizes = _piece(256, (100, 0, 156, 0))
    with pytest.raises(ValueError, match="tiles"):
        add_grouped_product(into, lhs, grad, group_sizes, (96, 128, 128))
    with pytest.raises(ValueError, match="against"):
        add_grouped_product(
            into, lhs, grad.astype(jnp.float32), group_sizes,
            (128, 128, 128))
