"""``ops/grouped_matmul.py``: the tile rule from the shapes, and
buffers that the groups do not fill (a share of the experts sorts the
absent ones' rows last): forward and both gradients against a loop
over the groups, on both routes (megablox in interpret mode)."""

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
