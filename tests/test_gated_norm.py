"""ops/gated_norm.py: a Mamba-2 mixer's gate and grouped RMSNorm, and a
linear-attention layer's norm a head and gate, as one operator each:
the Pallas kernels (one frame, a body each; interpret mode here)
against the plain paths: the result and every gradient (``do``, ``dz``,
``d scale`` and, a head's gate with one, ``d bias``), several blocks of
time and several groups, two sequences in a batch, bf16 operands
rounded once in the kernels (the heads' plain path keeps the three
roundings of the lines it took over), and the dispatch by shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import rms_norm
from dlrover_tpu.ops import gated_norm
from dlrover_tpu.ops.kda_conv import heads_apart
from dlrover_tpu.ops.pallas import gated_norm as kernels
from dlrover_tpu.telemetry.registry import counter

F32 = jnp.float32
EPS = 1e-5


#: the bodies of the one frame: the mixer's, the heads' with the
#: gate's bias and without, and the heads' with ``silu`` for the gate
BODIES = ["gate, norm", "norm, gate, bias", "norm, gate", "norm, silu"]
#: the bodies whose plain path rounds once, as the kernels do (the
#: heads' sigmoid bodies keep the three roundings of the lines they
#: took over)
ROUNDED_ONCE = ("gate, norm", "norm, silu")
#: the name the counters of each body's entry go by
ENTRY = {"gate, norm": "gated_norm", "norm, gate, bias": "head_norm_gate",
         "norm, gate": "head_norm_gate", "norm, silu": "head_norm_silu"}


def _kernels_body(body):
    """The name ``kernels.BODIES`` has a body by: the bias is a second
    vector, not another body."""
    return body.removesuffix(", bias")


def _case(dtype, batch=2, seq=64, groups=2, w=128, body="gate, norm"):
    """``(o, z, the body's vectors, dy)``: the mixer's scale a
    column's, the heads' one for every head and their bias a
    column's."""
    keys = jax.random.split(jax.random.key(0), 5)
    shape = (batch, seq, groups * w)
    o = jax.random.normal(keys[0], shape).astype(dtype)
    z = jax.random.normal(keys[1], shape).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], shape[-1:])
    dy = jax.random.normal(keys[3], shape).astype(dtype)
    if body == "gate, norm":
        return o, z, (scale,), dy
    bias = 0.5 * jax.random.normal(keys[4], shape[-1:])
    return o, z, (scale[:w], bias)[:1 + body.endswith("bias")], dy


def _plain(body, o, z, vectors, groups):
    if body == "gate, norm":
        return gated_norm.gated_group_norm_plain(o, z, *vectors, groups, EPS)
    if body == "norm, silu":
        return gated_norm.head_norm_silu_plain(o, z, *vectors, EPS)
    scale, bias = (*vectors, None)[:2]
    return gated_norm.head_norm_gate_plain(o, z, scale, bias, EPS)


def _through_the_entry(body, o, z, vectors, groups):
    if body == "gate, norm":
        return gated_norm.gated_group_norm(o, z, *vectors, groups, EPS)
    if body == "norm, silu":
        return gated_norm.head_norm_silu(o, z, *vectors, EPS)
    scale, bias = (*vectors, None)[:2]
    return gated_norm.head_norm_gate(o, z, scale, bias, EPS)


def _plain_with_gradients(body, o, z, vectors, dy, groups):
    """``(y, do, dz, d scale[, d bias])`` of the plain path."""
    y, back = jax.vjp(
        lambda o, z, vectors: _plain(body, o, z, vectors, groups),
        o, z, vectors)
    do, dz, dvectors = back(dy)
    return (y, do, dz, *dvectors)


def _kernels_with_gradients(body, o, z, vectors, dy, **blocks):
    """The same of the two kernels."""
    blocks = dict(body=_kernels_body(body), eps=EPS, **blocks)
    do, dz, dvectors = kernels.gated_norm(o, z, vectors, dy, **blocks)
    return (kernels.gated_norm(o, z, vectors, **blocks), do, dz, *dvectors)


def _calls(body="gate, norm"):
    return (counter(f"{ENTRY[body]}_kernel_calls", "").value,
            counter(f"{ENTRY[body]}_plain_calls", "").value)


def test_the_plain_path_is_the_equations():
    """The gate first, then a group's columns over their root mean
    square, then the scale: against numpy a group at a time."""
    o, z, (scale,), _ = _case(F32, seq=8, groups=3, w=4)
    o_, z_ = np.asarray(o), np.asarray(z)
    g = (o_ * z_ / (1 + np.exp(-z_))).reshape(2, 8, 3, 4)
    want = g / np.sqrt((g * g).mean(-1, keepdims=True) + EPS)
    np.testing.assert_allclose(
        gated_norm.gated_group_norm(o, z, scale, 3, EPS),
        want.reshape(o.shape) * np.asarray(scale), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("body", BODIES[1:])
def test_the_heads_plain_path_is_the_equations(body):
    """A head's columns over their root mean square, the one scale,
    then the gate ``sigmoid(z + bias)``: against numpy a head at a
    time; and in bf16 the lines ``models/llama.py _operator_out`` had
    (the norm and the gate each rounded before their product), to the
    bit."""
    o, z, vectors, _ = _case(F32, seq=8, groups=3, w=4, body=body)
    scale, bias = (*vectors, None)[:2]
    o_ = np.asarray(o).reshape(2, 8, 3, 4)
    logits = np.asarray(z) + (0 if bias is None else np.asarray(bias))
    want = o_ / np.sqrt((o_ * o_).mean(-1, keepdims=True) + EPS) * np.asarray(
        scale)
    np.testing.assert_allclose(
        gated_norm.head_norm_gate(o, z, scale, bias, EPS),
        want.reshape(o.shape) / (1 + np.exp(-logits)), rtol=1e-5, atol=1e-6)
    o, z = o.astype(jnp.bfloat16), z.astype(jnp.bfloat16)
    got = gated_norm.head_norm_gate(o, z, scale, bias, EPS)
    assert got.dtype == jnp.bfloat16
    gate = jax.nn.sigmoid(z.astype(F32) + (0 if bias is None else bias))
    np.testing.assert_array_equal(got, rms_norm(
        heads_apart(o, 3), scale, EPS).reshape(o.shape) * gate.astype(o.dtype))


@pytest.mark.parametrize("heads,d", [(3, 4), (30, 192)],
                         ids=["small", "30 heads of 192"])
def test_the_norm_then_silu_plain_path_is_the_equations(heads, d):
    """A head's columns over their root mean square, the one scale,
    THEN ``silu`` of the gate's pre-activation, no bias: against numpy
    a head at a time, at the cell's head of 192 (a lane tile and a
    half: the plain path on the chip too) and at a small one; and the
    other order, the mixer's, is another function."""
    o, z, (scale,), _ = _case(F32, seq=8, groups=heads, w=d,
                              body="norm, silu")
    o_ = np.asarray(o).reshape(2, 8, heads, d)
    z_ = np.asarray(z)
    want = (o_ / np.sqrt((o_ * o_).mean(-1, keepdims=True) + EPS)
            * np.asarray(scale)).reshape(o.shape) * z_ / (1 + np.exp(-z_))
    got = gated_norm.head_norm_silu(o, z, scale, EPS)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert not kernels.tiles_the_kernel((1, 512, heads * d), heads)
    other = gated_norm.gated_group_norm(
        o, z, jnp.tile(scale, heads), heads, EPS)
    assert float(jnp.abs(other - got).max()) > 1e-2
    sigmoid = gated_norm.head_norm_gate(o, z, scale, None, EPS)
    assert float(jnp.abs(sigmoid - got).max()) > 1e-2


def test_head_sums_by_product_are_the_views():
    """``ops/kda_conv.py head_sums`` on the TPU for a head of no whole
    lane tiles: two thin products in the view's place, the same sums
    (30 heads of 192, and of 96) and, through the plain path, the
    same result and gradients."""
    from dlrover_tpu.ops import kda_conv

    for d in (192, 96):
        x = jax.random.normal(jax.random.key(d), (2, 16, 30 * d)) ** 2
        np.testing.assert_allclose(
            kda_conv.head_sums_by_product(x, 30), kda_conv.head_sums(x, 30),
            rtol=1e-6)
    o, z, (scale,), dy = _case(F32, seq=16, groups=30, w=192,
                               body="norm, silu")
    want = _plain_with_gradients("norm, silu", o, z, (scale,), dy, 30)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        got = _plain_with_gradients("norm, silu", o, z, (scale,), dy, 30)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("batch,seq,groups,w,rows,walk", [
    (1, 16, 1, 128, None, None),   # one block, one walk, one group
    (2, 64, 2, 128, 16, 16),       # four blocks of time, two groups
    (2, 128, 4, 256, 32, 16),      # two walks a block, two lane tiles
    (1, 64, 2, 128, None, None),   # the blocks the kernels choose
    (1, 16, 32, 128, None, None),  # kimi's and minicpm-sala's 4,096
    (1, 32, 64, 128, 16, None),    # solar's 8,192: eight blocks of lanes
], ids=["tiny", "4 blocks 2 groups", "2 walks 4 groups", "whole",
        "32 heads", "64 heads"])
@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("body", BODIES)
def test_the_kernels_agree_with_the_plain_path(body, dtype, batch, seq,
                                               groups, w, rows, walk):
    """Forward and every gradient: float32 within 1e-5 of the plain
    path, bf16 within one rounding of a result of its size (``d
    scale`` and ``d bias`` are float32 either way; the heads' one
    scale sums over the heads too, so its room grows with them). The
    heads' plain path rounds as the old lines did, three times: in
    bf16 the kernels are within those and their own one of it, and as
    near as the mixer's to its float32 result rounded once."""
    o, z, vectors, dy = _case(dtype, batch, seq, groups, w, body)
    want = _plain_with_gradients(body, o, z, vectors, dy, groups)
    got = _kernels_with_gradients(
        body, o, z, vectors, dy, groups=groups, rows=rows, walk=walk)
    assert len(got) == len(want) == 3 + len(vectors)
    if dtype != F32 and body not in ROUNDED_ONCE:
        wide = _plain_with_gradients(
            body, o.astype(F32), z.astype(F32), vectors, dy.astype(F32),
            groups)
        for name, a, b in zip(("y", "do", "dz"), got, want):
            a, b = a.astype(F32), b.astype(F32)
            assert float(jnp.max(jnp.abs(a - b) - 4 * 2.0 ** -8 * jnp.maximum(
                jnp.abs(a), jnp.abs(b)))) <= 2.0 ** -9, name
        want = tuple(a.astype(b.dtype) for a, b in zip(wide, want))
    for name, a, b in zip(("y", "do", "dz", "d scale", "d bias"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(F32), b.astype(F32)
        if dtype == F32 or name in ("d scale", "d bias"):
            terms = seq * (groups if name == "d scale" and len(b) == w else 1)
            np.testing.assert_allclose(
                a, b, rtol=2e-5, atol=2e-5 * terms ** 0.5, err_msg=name)
        else:
            # one rounding: half a unit of bf16's eight bits in the
            # last place of the larger of the two
            half = 2.0 ** -8 * jnp.maximum(jnp.abs(a), jnp.abs(b))
            over = jnp.abs(a - b) - half
            if groups >= 32 or (body == "norm, silu" and w > 128):
                # of the cells' 65,536 and 262,144 elements (and of
                # the 262,144 of the ``silu`` body's two lane tiles a
                # head) a few lie where two orders of a float32 sum
                # straddle a rounding: a whole unit there
                assert float(jnp.mean(over > 2.0 ** -9)) <= 1e-4, name
                over = over - half
            assert float(jnp.max(over)) <= 2.0 ** -9, name


@pytest.mark.parametrize("body", BODIES)
def test_bf16_operands_are_rounded_once(body):
    """The kernels' bf16 result is the float32 result of the same
    bf16 operands, rounded: nothing between is held in bf16. So is
    the mixer's plain path's."""
    o, z, vectors, dy = _case(jnp.bfloat16, body=body)
    wide = _plain_with_gradients(
        body, o.astype(F32), z.astype(F32), vectors, dy.astype(F32), 2)
    got = _kernels_with_gradients(body, o, z, vectors, dy, groups=2, rows=16)
    plain = _plain(body, o, z, vectors, 2)
    assert plain.dtype == jnp.bfloat16
    if body in ROUNDED_ONCE:
        np.testing.assert_array_equal(plain, wide[0].astype(jnp.bfloat16))
    for a, b in zip(got[:3], wide[:3]):
        assert a.dtype == jnp.bfloat16
        a, b = a.astype(F32), b.astype(F32)
        # half a unit in the last place, and a float32 sum's order
        assert float(jnp.max(
            jnp.abs(a - b) - 2.0 ** -8 * jnp.abs(b))) <= 1e-5


@pytest.mark.parametrize("groups", [2, 16], ids=["one block", "two"])
@pytest.mark.parametrize("body", BODIES)
def test_a_group_sees_nothing_of_its_neighbour(body, groups):
    """A spike in one group's columns moves neither the other groups'
    rows nor their gradients (a head's either: of one block's lanes
    with its neighbours, or of two blocks of eight heads), and a
    column's own sums beside them stay its own."""
    o, z, vectors, dy = _case(F32, groups=groups, body=body)
    spiked = o.at[..., :128].multiply(1e3)
    blocks = dict(groups=groups, rows=16)
    was = _kernels_with_gradients(body, o, z, vectors, dy, **blocks)
    now = _kernels_with_gradients(body, spiked, z, vectors, dy, **blocks)
    for a, b in zip(was[:3], now[:3]):
        np.testing.assert_array_equal(a[..., 128:], b[..., 128:])
    for a, b in zip(was[3:], now[3:]):
        if a.shape == o.shape[-1:]:  # a column's: the mixer's, the bias's
            np.testing.assert_array_equal(a[128:], b[128:])


@pytest.mark.parametrize("body", BODIES)
def test_the_kernels_differentiate_as_one_function(body):
    o, z, vectors, dy = _case(F32, seq=32, body=body)
    name = _kernels_body(body)
    got = jax.grad(lambda *a: jnp.sum(
        kernels.gated_norm_tpu(*a, name, 2, EPS) * dy), (0, 1, 2))(
            o, z, vectors)
    want = jax.grad(lambda *a: jnp.sum(
        _plain(body, *a, 2) * dy), (0, 1, 2))(o, z, vectors)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("body", BODIES)
def test_the_cotangent_comes_in_the_results_dtype(body, monkeypatch):
    """A bf16 result's cotangent reaches the backward kernel as bf16,
    whatever the product after it accumulates in."""
    o, z, vectors, _ = _case(jnp.bfloat16, seq=16, body=body)
    w = jax.random.normal(jax.random.key(5), (256, 8), jnp.bfloat16)
    seen = []
    real = kernels.gated_norm

    def spy(*args, **kwargs):
        seen.extend(a.dtype for a in args[3:])
        return real(*args, **kwargs)

    monkeypatch.setattr(kernels, "gated_norm", spy)
    jax.grad(lambda o: jnp.sum(
        (kernels.gated_norm_tpu(o, z, vectors, _kernels_body(body), 2, EPS)
         @ w).astype(F32)))(o)
    assert seen == [jnp.bfloat16]


@pytest.mark.parametrize("shape,groups,tiles", [
    ((1, 8192, 8192), 8, True),    # nemotron's mixer
    ((1, 8192, 8192), 64, True),   # solar's heads
    ((1, 16384, 4096), 32, True),  # kimi's and minicpm-sala's
    ((2, 64, 256), 2, True),
    ((2, 64, 256), 1, True),       # one group of two lane tiles
    ((2, 32, 64), 4, False),       # groups of 16 columns
    ((2, 32, 192), 1, False),      # a tile and a half
    ((1, 72, 256), 2, False),      # no whole block of time
    ((1, 64, 4096), 2, False),     # a group wider than a walk holds
], ids=["nemotron", "solar", "kimi", "two groups", "one wide group",
        "small group", "ragged group", "ragged time", "too wide a group"])
@pytest.mark.parametrize("body", BODIES)
def test_the_shape_decides_the_path(body, shape, groups, tiles, monkeypatch):
    """``tiles_the_kernel`` by shape alone; and through either entry,
    where a TPU process stands, the path it names is the one counted
    (the kernels themselves run only at the small shapes)."""
    assert kernels.tiles_the_kernel(shape, groups) is tiles
    if shape[1] > 72:
        return
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernels, "_interpret", lambda: True)
    o, z, vectors, _ = _case(
        F32, *shape[:2], groups, shape[2] // groups, body)
    before = _calls(body)
    got = _through_the_entry(body, o, z, vectors, groups)
    assert _calls(body) == (before[0] + tiles, before[1] + (not tiles))
    np.testing.assert_allclose(
        got, _plain(body, o, z, vectors, groups), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("body", BODIES)
def test_off_the_tpu_the_entry_takes_the_plain_path(body):
    o, z, vectors, _ = _case(F32, body=body)
    before = _calls(body)
    got = _through_the_entry(body, o, z, vectors, 2)
    assert _calls(body) == (before[0], before[1] + 1)
    np.testing.assert_array_equal(got, _plain(body, o, z, vectors, 2))
    scale = vectors[0]
    if body == "gate, norm":
        with pytest.raises(ValueError, match="in 3 groups"):
            gated_norm.gated_group_norm(o, z, scale, 3, EPS)
        with pytest.raises(ValueError, match="a scale of"):
            gated_norm.gated_group_norm(o, z, scale[:128], 2, EPS)
        with pytest.raises(ValueError, match="a gate of"):
            gated_norm.gated_group_norm(o, z[:, :32], scale, 2, EPS)
        return
    bias = jnp.zeros(o.shape[-1:])
    with pytest.raises(ValueError, match=r"a scale of \(96,\)"):
        gated_norm.head_norm_gate(o, z, scale[:96], None, EPS)
    with pytest.raises(ValueError, match=r"a bias of \(128,\)"):
        gated_norm.head_norm_gate(o, z, scale, bias[:128], EPS)
    with pytest.raises(ValueError, match="a gate of"):
        gated_norm.head_norm_gate(o, z[:, :32], scale, bias, EPS)


@pytest.mark.parametrize("body", BODIES)
def test_a_traced_call_counts_once(body):
    """The counters move at trace time: a jitted caller counts its
    call once however often it runs."""
    o, z, vectors, _ = _case(F32, seq=16, body=body)
    run = jax.jit(lambda o: _through_the_entry(body, o, z, vectors, 2))
    before = _calls(body)
    run(o), run(o)
    assert _calls(body) == (before[0], before[1] + 1)
