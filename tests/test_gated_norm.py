"""ops/gated_norm.py: a Mamba-2 mixer's gate and grouped RMSNorm as one
operator, the Pallas kernels (interpret mode here) against the plain
path: the result and all three gradients (``do``, ``dz``, ``d scale``),
several blocks of time and several groups, two sequences in a batch,
bf16 operands rounded once, and the dispatch by shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import gated_norm
from dlrover_tpu.ops.pallas import gated_norm as kernels
from dlrover_tpu.telemetry.registry import counter

F32 = jnp.float32
EPS = 1e-5


def _case(dtype, batch=2, seq=64, groups=2, w=128):
    keys = jax.random.split(jax.random.key(0), 4)
    shape = (batch, seq, groups * w)
    o = jax.random.normal(keys[0], shape).astype(dtype)
    z = jax.random.normal(keys[1], shape).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], shape[-1:])
    dy = jax.random.normal(keys[3], shape).astype(dtype)
    return o, z, scale, dy


def _plain_with_gradients(o, z, scale, dy, groups):
    y, back = jax.vjp(
        lambda o, z, scale: gated_norm.gated_group_norm_plain(
            o, z, scale, groups, EPS), o, z, scale)
    return (y, *back(dy))


def _calls():
    return (counter("gated_norm_kernel_calls", "").value,
            counter("gated_norm_plain_calls", "").value)


def test_the_plain_path_is_the_equations():
    """The gate first, then a group's columns over their root mean
    square, then the scale: against numpy a group at a time."""
    o, z, scale, _ = _case(F32, seq=8, groups=3, w=4)
    o_, z_ = np.asarray(o), np.asarray(z)
    g = (o_ * z_ / (1 + np.exp(-z_))).reshape(2, 8, 3, 4)
    want = g / np.sqrt((g * g).mean(-1, keepdims=True) + EPS)
    np.testing.assert_allclose(
        gated_norm.gated_group_norm(o, z, scale, 3, EPS),
        want.reshape(o.shape) * np.asarray(scale), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("batch,seq,groups,w,rows,walk", [
    (1, 16, 1, 128, None, None),   # one block, one walk, one group
    (2, 64, 2, 128, 16, 16),       # four blocks of time, two groups
    (2, 128, 4, 256, 32, 16),      # two walks a block, two lane tiles
    (1, 64, 2, 128, None, None),   # the blocks the kernels choose
], ids=["tiny", "4 blocks 2 groups", "2 walks 4 groups", "whole"])
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_kernels_agree_with_the_plain_path(dtype, batch, seq, groups, w,
                                               rows, walk):
    """Forward and every gradient: float32 within 1e-5 of the plain
    path, bf16 within one rounding of a result of its size (``d
    scale`` is float32 either way)."""
    o, z, scale, dy = _case(dtype, batch, seq, groups, w)
    want = _plain_with_gradients(o, z, scale, dy, groups)
    blocks = dict(groups=groups, eps=EPS, rows=rows, walk=walk)
    got = (kernels.gated_norm(o, z, scale, **blocks),
           *kernels.gated_norm(o, z, scale, dy, **blocks))
    for name, a, b in zip(("y", "do", "dz", "d scale"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(F32), b.astype(F32)
        if dtype == F32 or name == "d scale":
            np.testing.assert_allclose(
                a, b, rtol=2e-5, atol=2e-5 * (seq ** 0.5), err_msg=name)
        else:
            # one rounding: half a unit of bf16's eight bits in the
            # last place of the larger of the two
            assert float(jnp.max(
                jnp.abs(a - b) - 2.0 ** -8 * jnp.maximum(
                    jnp.abs(a), jnp.abs(b)))) <= 2.0 ** -9, name


def test_bf16_operands_are_rounded_once():
    """The kernels' bf16 result is the float32 result of the same
    bf16 operands, rounded: nothing between is held in bf16."""
    o, z, scale, dy = _case(jnp.bfloat16)
    wide = _plain_with_gradients(
        o.astype(F32), z.astype(F32), scale, dy.astype(F32), 2)
    got = (kernels.gated_norm(o, z, scale, groups=2, eps=EPS, rows=16),
           *kernels.gated_norm(o, z, scale, dy, groups=2, eps=EPS, rows=16))
    for a, b in zip(got[:3], wide[:3]):
        assert a.dtype == jnp.bfloat16
        a, b = a.astype(F32), b.astype(F32)
        # half a unit in the last place, and a float32 sum's order
        assert float(jnp.max(
            jnp.abs(a - b) - 2.0 ** -8 * jnp.abs(b))) <= 1e-5


def test_a_group_sees_nothing_of_its_neighbour():
    """A spike in one group's columns moves neither the other group's
    rows nor their gradients."""
    o, z, scale, dy = _case(F32)
    spiked = o.at[..., :128].multiply(1e3)
    blocks = dict(groups=2, eps=EPS, rows=16)
    for a, b in zip(
            (kernels.gated_norm(o, z, scale, **blocks),
             *kernels.gated_norm(o, z, scale, dy, **blocks)),
            (kernels.gated_norm(spiked, z, scale, **blocks),
             *kernels.gated_norm(spiked, z, scale, dy, **blocks))):
        np.testing.assert_array_equal(a[..., 128:], b[..., 128:])


def test_the_kernels_differentiate_as_one_function():
    o, z, scale, dy = _case(F32, seq=32)
    got = jax.grad(lambda *a: jnp.sum(
        kernels.gated_norm_tpu(*a, 2, EPS) * dy), (0, 1, 2))(o, z, scale)
    want = jax.grad(lambda *a: jnp.sum(
        gated_norm.gated_group_norm_plain(*a, 2, EPS) * dy), (0, 1, 2))(
            o, z, scale)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-4)


def test_the_cotangent_comes_in_the_results_dtype(monkeypatch):
    """A bf16 result's cotangent reaches the backward kernel as bf16,
    whatever the product after it accumulates in."""
    o, z, scale, _ = _case(jnp.bfloat16, seq=16)
    w = jax.random.normal(jax.random.key(5), (256, 8), jnp.bfloat16)
    seen = []
    real = kernels.gated_norm

    def spy(*args, **kwargs):
        seen.extend(a.dtype for a in args[3:])
        return real(*args, **kwargs)

    monkeypatch.setattr(kernels, "gated_norm", spy)
    jax.grad(lambda o: jnp.sum(
        (kernels.gated_norm_tpu(o, z, scale, 2, EPS) @ w).astype(F32)))(o)
    assert seen == [jnp.bfloat16]


@pytest.mark.parametrize("shape,groups,tiles", [
    ((1, 8192, 8192), 8, True),    # the cell's mixer
    ((2, 64, 256), 2, True),
    ((2, 64, 256), 1, True),       # one group of two lane tiles
    ((2, 32, 64), 4, False),       # groups of 16 columns
    ((2, 32, 192), 1, False),      # a tile and a half
    ((1, 72, 256), 2, False),      # no whole block of time
    ((1, 64, 4096), 2, False),     # a group wider than a walk holds
], ids=["the cell", "two groups", "one wide group", "small group",
        "ragged group", "ragged time", "too wide a group"])
def test_the_shape_decides_the_path(shape, groups, tiles, monkeypatch):
    """``tiles_the_kernel`` by shape alone; and through the entry,
    where a TPU process stands, the path it names is the one counted
    (the kernels themselves run only at the small shapes)."""
    assert kernels.tiles_the_kernel(shape, groups) is tiles
    if shape[1] > 72:
        return
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "_interpret", lambda: True)
    keys = jax.random.split(jax.random.key(0), 2)
    o, z = (jax.random.normal(k, shape) for k in keys)
    scale = jnp.ones(shape[-1:])
    before = _calls()
    got = gated_norm.gated_group_norm(o, z, scale, groups, EPS)
    assert _calls() == (before[0] + tiles, before[1] + (not tiles))
    np.testing.assert_allclose(
        got, gated_norm.gated_group_norm_plain(o, z, scale, groups, EPS),
        rtol=1e-5, atol=1e-5)


def test_off_the_tpu_the_entry_takes_the_plain_path():
    o, z, scale, _ = _case(F32)
    before = _calls()
    got = gated_norm.gated_group_norm(o, z, scale, 2, EPS)
    assert _calls() == (before[0], before[1] + 1)
    np.testing.assert_array_equal(
        got, gated_norm.gated_group_norm_plain(o, z, scale, 2, EPS))
    with pytest.raises(ValueError, match="in 3 groups"):
        gated_norm.gated_group_norm(o, z, scale, 3, EPS)
    with pytest.raises(ValueError, match="a scale of"):
        gated_norm.gated_group_norm(o, z, scale[:128], 2, EPS)
    with pytest.raises(ValueError, match="a gate of"):
        gated_norm.gated_group_norm(o, z[:, :32], scale, 2, EPS)


def test_a_traced_call_counts_once():
    """The counters move at trace time: a jitted caller counts its
    call once however often it runs."""
    o, z, scale, _ = _case(F32, seq=16)
    run = jax.jit(lambda o: gated_norm.gated_group_norm(o, z, scale, 2, EPS))
    before = _calls()
    run(o), run(o)
    assert _calls() == (before[0], before[1] + 1)
