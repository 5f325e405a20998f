"""ops/kda_conv.py: a delta-rule layer's convolution, ``silu`` and l2
norm as one operator, the Pallas kernels (interpret mode here) against
the plain path: the result and all three gradients (``dx``, ``dw``,
and both through the heads' norm), several blocks of time and of lanes
so that both halos are crossed, two sequences in a batch that must not
see each other, one tap and four, and the dispatch by shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import kda_conv
from dlrover_tpu.ops.pallas import kda_conv as kernels
from dlrover_tpu.telemetry.registry import counter

F32 = jnp.float32


def _case(dtype, batch=2, seq=64, heads=2, d=128, taps=4):
    keys = jax.random.split(jax.random.key(0), 3)
    shape = (batch, seq, heads * d)
    x = jax.random.normal(keys[0], shape).astype(dtype)
    w = (jax.random.normal(keys[1], (heads * d, taps))
         * taps ** -0.5).astype(dtype)
    dy = jax.random.normal(keys[2], shape).astype(dtype)
    return x, w, dy


def _plain_with_gradients(x, w, dy, l2_heads):
    def loss(x, w):
        out = kda_conv.conv_silu_norm_plain(x, w, l2_heads)
        return jnp.sum(out.astype(F32) * dy.astype(F32))

    return (kda_conv.conv_silu_norm_plain(x, w, l2_heads),
            *jax.grad(loss, (0, 1))(x, w))


def _calls():
    return (counter("kda_conv_kernel_calls", "").value,
            counter("kda_conv_plain_calls", "").value)


def test_the_plain_path_is_the_equations():
    """Taps oldest first, zeros before a sequence's start, ``silu``,
    a head's columns over their length: against a loop over
    positions."""
    x, w, _ = _case(F32, seq=8, heads=2, d=4)
    a = np.zeros(x.shape, np.float32)
    for t in range(8):
        for j in range(4):
            if t - 3 + j >= 0:
                a[:, t] += np.asarray(w)[:, j] * np.asarray(x)[:, t - 3 + j]
    s = a / (1 + np.exp(-a))
    np.testing.assert_allclose(
        kda_conv.conv_silu_norm(x, w), s, rtol=1e-5, atol=1e-6)
    heads = s.reshape(2, 8, 2, 4)
    unit = heads / np.sqrt(
        (heads * heads).sum(-1, keepdims=True) + kda_conv.L2_NORM_EPS)
    np.testing.assert_allclose(
        kda_conv.conv_silu_norm(x, w, l2_heads=2), unit.reshape(x.shape),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("taps", [4, 1], ids=["four taps", "one tap"])
@pytest.mark.parametrize("l2_heads", [2, None], ids=["l2", "no norm"])
@pytest.mark.parametrize("dtype,rows,lanes", [
    (F32, 16, 128), (F32, 32, 256), (F32, None, None),
    (jnp.bfloat16, 16, 128),
], ids=["f32 16x128", "f32 32x256", "f32 whole", "bf16 16x128"])
def test_the_kernels_agree_with_the_plain_path(dtype, rows, lanes, l2_heads,
                                               taps):
    """Forward and every gradient over two sequences in one batch, in
    four blocks of time and two of lanes, two and one, and one of
    each: float32 within 1e-5 of the plain path, bf16 within one
    rounding of a result of its size."""
    x, w, dy = _case(dtype, taps=taps)
    want = _plain_with_gradients(x, w, dy, l2_heads)
    got = (
        kernels.kda_conv(x, w, l2_heads=l2_heads, rows=rows, lanes=lanes),
        *kernels.kda_conv(x, w, dy, l2_heads=l2_heads, rows=rows,
                          lanes=lanes),
    )
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = a.astype(F32), b.astype(F32)
        if dtype == F32:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        else:
            # one rounding: half a unit of bf16's eight bits in the last
            # place of the larger of the two, and the sums of sixty-four
            # rows' products that a rounding of ``a`` may move beside it
            assert float(jnp.max(
                jnp.abs(a - b) - 2.0 ** -8 * jnp.maximum(
                    jnp.abs(a), jnp.abs(b)))) <= 2.0 ** -9


@pytest.mark.parametrize("l2_heads", [2, None], ids=["l2", "no norm"])
def test_a_sequence_sees_nothing_of_the_one_before_it(l2_heads):
    """A spike in the first row's last positions reaches neither the
    second row's first results nor, through ``dy``, the first row's
    gradient from the second: each row of the batch reads as it does
    alone."""
    x, w, dy = _case(F32)
    x = x.at[0, -3:].set(1e4)
    dy = dy.at[1, :3].set(1e4)
    got = kernels.kda_conv(x, w, l2_heads=l2_heads, rows=16, lanes=128)
    dx, _ = kernels.kda_conv(
        x, w, dy, l2_heads=l2_heads, rows=16, lanes=128)
    for row in (0, 1):
        alone = slice(row, row + 1)
        np.testing.assert_allclose(
            got[alone], kernels.kda_conv(
                x[alone], w, l2_heads=l2_heads, rows=16, lanes=128),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            dx[alone], kernels.kda_conv(
                x[alone], w, dy[alone], l2_heads=l2_heads, rows=16,
                lanes=128)[0], rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(dx[0]).max()) < 1e3


@pytest.mark.parametrize("l2_heads", [2, None], ids=["l2", "no norm"])
def test_the_kernels_differentiate_as_one_function(l2_heads):
    x, w, dy = _case(F32, seq=32)
    got = jax.grad(lambda x, w: jnp.sum(
        kernels.kda_conv_tpu(x, w, l2_heads) * dy), (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(
        kda_conv.conv_silu_norm_plain(x, w, l2_heads) * dy), (0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("x_shape,w_shape,l2_heads,tiles", [
    ((1, 8192, 8192), (8192, 4), 64, True),   # the cell's q and k
    ((1, 8192, 8192), (8192, 4), None, True),  # and its v
    ((2, 64, 512), (512, 1), 2, True),  # a head of two lane tiles
    ((2, 32, 64), (64, 4), 4, False),  # heads of 16
    ((2, 32, 64), (64, 4), None, False),  # half a lane tile
    ((1, 8200, 256), (256, 4), 2, False),  # no whole block of time
    ((1, 64, 256), (256, 9), 2, False),  # more taps than a tile's rows
    ((1, 64, 2048), (2048, 4), 2, False),  # a head wider than a walk
], ids=["q k", "v", "wide head", "small head", "narrow", "ragged time",
        "taps", "too wide a head"])
def test_the_shape_decides_the_path(x_shape, w_shape, l2_heads, tiles,
                                    monkeypatch):
    """``tiles_the_kernel`` by shape alone; and through the entry,
    where a TPU process stands, the path it names is the one counted
    (the kernels themselves run only at the small shapes)."""
    assert kernels.tiles_the_kernel(x_shape, w_shape, l2_heads) is tiles
    if x_shape[1] > 64:
        return
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "_interpret", lambda: True)
    x = jax.random.normal(jax.random.key(0), x_shape)
    w = jax.random.normal(jax.random.key(1), w_shape)
    before = _calls()
    got = kda_conv.conv_silu_norm(x, w, l2_heads)
    assert _calls() == (before[0] + tiles, before[1] + (not tiles))
    np.testing.assert_allclose(
        got, kda_conv.conv_silu_norm_plain(x, w, l2_heads), rtol=1e-5,
        atol=1e-5)


def test_off_the_tpu_the_entry_takes_the_plain_path():
    x, w, _ = _case(F32)
    before = _calls()
    got = kda_conv.conv_silu_norm(x, w, l2_heads=2)
    assert _calls() == (before[0], before[1] + 1)
    np.testing.assert_array_equal(
        got, kda_conv.conv_silu_norm_plain(x, w, 2))
    with pytest.raises(ValueError, match="taps of 256 channels"):
        kda_conv.conv_silu_norm(x[..., :128], w)
    with pytest.raises(ValueError, match="in 3 heads"):
        kda_conv.conv_silu_norm(x, w, l2_heads=3)


# -- a bias a channel (a Mamba-2 layer's ``use_conv_bias``) -------------------

def _bias(width, dtype=F32):
    return jax.random.normal(jax.random.key(7), (width,)).astype(dtype)


def test_the_plain_path_adds_the_bias_ahead_of_silu():
    x, w, _ = _case(F32, seq=8, heads=2, d=4)
    bias = _bias(8)
    a = np.asarray(kda_conv.causal_taps(x, w)) + np.asarray(bias)
    np.testing.assert_allclose(
        kda_conv.conv_silu_norm(x, w, bias=bias), a / (1 + np.exp(-a)),
        rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="bias"):
        kda_conv.conv_silu_norm(x, w, bias=bias[:4])


@pytest.mark.parametrize("l2_heads", [None, 2], ids=["no norm", "l2"])
@pytest.mark.parametrize("dtype,rows,lanes", [
    (F32, 16, 128), (F32, None, None), (jnp.bfloat16, 16, 128),
], ids=["f32 16x128", "f32 whole", "bf16 16x128"])
def test_the_kernels_with_a_bias_agree_with_the_plain_path(
        dtype, rows, lanes, l2_heads):
    """Forward and the three gradients, the bias's summed in float32
    over the batch and the sequence beside ``dw``'s."""
    x, w, dy = _case(dtype)
    bias = _bias(w.shape[0])

    def loss(x, w, bias):
        out = kda_conv.conv_silu_norm_plain(x, w, l2_heads, bias)
        return jnp.sum(out.astype(F32) * dy.astype(F32))

    want = (kda_conv.conv_silu_norm_plain(x, w, l2_heads, bias),
            *jax.grad(loss, (0, 1, 2))(x, w, bias))
    got = (
        kernels.kda_conv(x, w, l2_heads=l2_heads, rows=rows, lanes=lanes,
                         bias=bias),
        *kernels.kda_conv(x, w, dy, l2_heads=l2_heads, rows=rows,
                          lanes=lanes, bias=bias),
    )
    assert len(got) == 4 and got[3].dtype == F32
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = a.astype(F32), b.astype(F32)
        if dtype == F32:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
        else:
            assert float(jnp.max(
                jnp.abs(a - b) - 2.0 ** -8 * jnp.maximum(
                    jnp.abs(a), jnp.abs(b)))) <= 2.0 ** -8


def test_a_call_without_a_bias_builds_what_it_built():
    """The bias is an operand of its own kernels: a call without one
    traces the Pallas calls it traced before the bias, with the
    operands and results they had."""
    x, w, dy = _case(F32)

    def calls(f, *args):
        return [
            (len(e.invars), len(e.outvars))
            for e in jax.make_jaxpr(f)(*args).jaxpr.eqns[0].params[
                "jaxpr"].eqns if e.primitive.name == "pallas_call"
        ]

    assert calls(lambda x, w: kernels.kda_conv(x, w), x, w) == [(3, 1)]
    assert calls(
        lambda x, w, dy: kernels.kda_conv(x, w, dy), x, w, dy) == [(6, 2)]
    bias = _bias(w.shape[0])
    assert calls(
        lambda x, w, b: kernels.kda_conv(x, w, bias=b), x, w, bias
    ) == [(4, 1)]
    assert calls(
        lambda x, w, dy, b: kernels.kda_conv(x, w, dy, bias=b),
        x, w, dy, bias) == [(7, 3)]


def test_the_custom_rule_hands_the_bias_its_gradient():
    x, w, dy = _case(F32)
    bias = _bias(w.shape[0])

    def through(f):
        return jax.grad(
            lambda x, w, b: jnp.sum(f(x, w, b) * dy), (0, 1, 2))(x, w, bias)

    for a, b in zip(
            through(lambda x, w, b: kernels.kda_conv_bias_tpu(x, w, b, None)),
            through(lambda x, w, b: kda_conv.conv_silu_norm_plain(
                x, w, None, b))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)


# -- a head of 96 in rows of 2,880 (a Gated DeltaNet layer's q and k) ---------

#: (x's shape, w's, heads, whether the kernels take them): the new
#: cell's q and k, whose head is three quarters of a lane tile and
#: whose rows are 22.5 tiles, take the plain path; its v tiles as it is
GDN_SHAPES = {
    "q k": ((1, 16384, 2880), (2880, 4), 30, False),
    "v": ((1, 16384, 5760), (5760, 4), None, True),
    "rows of 2,880 without the norm": (
        (1, 16384, 2880), (2880, 4), None, False),
}


@pytest.mark.parametrize("name", list(GDN_SHAPES))
def test_a_gated_delta_net_layers_shapes_decide_their_paths(name):
    x_shape, w_shape, l2_heads, tiles = GDN_SHAPES[name]
    assert kernels.tiles_the_kernel(x_shape, w_shape, l2_heads) is tiles


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_head_of_96_in_rows_of_2880_is_the_equations(backend, monkeypatch):
    """30 heads of 96 columns, four taps: the plain path against numpy
    a head at a time, forward; off the TPU through the view of a
    head's columns and, where a TPU process stands, through
    ``head_sums_by_product`` (no view of a head of 96 exists there
    without a copy): the same numbers and the same gradients."""
    x, w, dy = _case(F32, batch=2, seq=16, heads=30, d=96)
    want = _plain_with_gradients(x, w, dy, 30)
    a = np.zeros(x.shape, np.float32)
    for j in range(4):
        a[:, 3 - j:] += np.asarray(w)[:, j] * np.asarray(x)[:, :16 - (3 - j)]
    s = (a / (1 + np.exp(-a))).reshape(2, 16, 30, 96)
    unit = s / np.sqrt((s * s).sum(-1, keepdims=True) + kda_conv.L2_NORM_EPS)
    np.testing.assert_allclose(
        want[0], unit.reshape(x.shape), rtol=2e-5, atol=2e-6)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    before = _calls()
    got = kda_conv.conv_silu_norm(x, w, l2_heads=30)
    assert _calls() == (before[0], before[1] + 1)  # plain on either
    np.testing.assert_allclose(got, want[0], rtol=2e-5, atol=2e-6)
    for a, b in zip(_plain_with_gradients(x, w, dy, 30), want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_head_sums_take_the_view_where_it_is_the_same_bytes(monkeypatch):
    """A head of whole lane tiles, or any head off the TPU, is summed
    through ``heads_apart``'s view; only a head of no whole lane tiles
    on the TPU goes through the two products."""
    seen = []
    monkeypatch.setattr(
        kda_conv, "head_sums_by_product",
        lambda x, heads: seen.append(heads) or x)
    x = jnp.ones((1, 8, 256))
    kda_conv.head_sums(x, 2), kda_conv.head_sums(x[..., :192], 2)
    assert seen == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kda_conv.head_sums(x, 2)  # heads of 128
    assert seen == []
    kda_conv.head_sums(x[..., :192], 2)  # heads of 96
    assert seen == [2]
