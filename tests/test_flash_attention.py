"""Flash-attention kernel correctness vs the XLA reference.

Runs the Pallas kernels in interpret mode on CPU (the reference's CUDA
flash-attn tests are GPU-gated; interpret mode gives us full coverage
without a TPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.pallas import flash_attention
from dlrover_tpu.ops.pallas.flash_attention import flash_attention_tpu


def _rand_qkv(key, b, s, h, kvh, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, kvh, d), dtype)
    v = jax.random.normal(kv, (b, s, kvh, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)])
def test_forward_matches_reference(causal, blocks):
    bq, bk = blocks
    q, k, v = _rand_qkv(jax.random.key(0), 2, 256, 4, 4, 64)
    out = flash_attention_tpu(q, k, v, causal=causal,
                              block_q=bq, block_k=bk)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_forward_gqa():
    q, k, v = _rand_qkv(jax.random.key(1), 2, 256, 8, 2, 64)
    out = flash_attention_tpu(q, k, v, causal=True,
                              block_q=128, block_k=128)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(128, 128), (64, 128), (128, 64)])
def test_gradients_match_reference(causal, bq, bk):
    # mixed blocks lock in the backward kernels' causal index-clamp
    # math ((j*bk)//bq and (i*bq+bq-1)//bk), which degenerates to the
    # trivial case at bq == bk
    q, k, v = _rand_qkv(jax.random.key(2), 1, 256, 2, 2, 64)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention_tpu(
                q, k, v, causal=causal, block_q=bq, block_k=bk
            ) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, rtol=5e-3, atol=5e-3,
            err_msg=f"d{name} mismatch",
        )


def test_gradients_gqa():
    q, k, v = _rand_qkv(jax.random.key(3), 1, 128, 4, 2, 64)

    def loss(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v) ** 2)
        return f

    flash = lambda q, k, v: flash_attention_tpu(  # noqa: E731
        q, k, v, causal=True, block_q=128, block_k=128
    )
    ref = lambda q, k, v: mha_reference(q, k, v, causal=True)  # noqa: E731
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, rtol=5e-3, atol=5e-3, err_msg=f"d{name} mismatch"
        )


def test_bf16_forward_close():
    q, k, v = _rand_qkv(jax.random.key(4), 1, 256, 2, 2, 64,
                        dtype=jnp.bfloat16)
    out = flash_attention_tpu(q, k, v, causal=True,
                              block_q=128, block_k=128)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32),
        rtol=5e-2, atol=5e-2,
    )


# (seq, block_q, block_k, g, head_dim, causal): at the three cells'
# blocks and groupings (gpt2-xl's one block a head and OLMoE's grid of
# 1024-blocks, the diagonal ones walked in sub-tiles; Mistral's (256,
# 1024) at a group of 4, whole: a group is not sub-tiled), a mixed pair
# with a group and two pairs of blocks that differ without one (whole:
# only equal blocks are walked), and without causal the whole-block
# body
SUB_TILED = [
    (1024, 1024, 1024, 1, 64, True),
    (2048, 1024, 1024, 1, 128, True),
    (2048, 256, 1024, 4, 128, True),
    (1024, 256, 512, 2, 64, True),
    (1024, 1024, 512, 1, 64, True),
    (2048, 512, 1024, 1, 64, True),
    (1024, 1024, 1024, 1, 64, False),
]


@pytest.fixture(scope="module", params=SUB_TILED, ids=str)
def sub_tiled(request):
    """Forward and the three gradients, kernel and reference, once a
    case."""
    seq, bq, bk, g, d, causal = request.param
    q, k, v = _rand_qkv(jax.random.key(5), 1, seq, g, 1, d)

    def both(attn):
        out, grads = jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v) ** 2),
            argnums=(0, 1, 2), has_aux=False,
        )(q, k, v)
        return dict(zip(("dq", "dk", "dv"), grads), fwd=attn(q, k, v))

    flash = both(lambda q, k, v: flash_attention_tpu(
        q, k, v, causal=causal, block_q=bq, block_k=bk))
    return flash, both(lambda q, k, v: mha_reference(q, k, v, causal=causal))


@pytest.mark.parametrize("what", ["fwd", "dq", "dk", "dv"])
def test_sub_tiled_blocks_match_reference(sub_tiled, what):
    flash, ref = sub_tiled
    tol = 2e-3 if what == "fwd" else 5e-3
    np.testing.assert_allclose(
        flash[what], ref[what], rtol=tol, atol=tol,
        err_msg=f"{what} mismatch",
    )


@pytest.mark.parametrize("sub", [128, 256, 512])
@pytest.mark.parametrize(
    "seq,bq,bk", [c[:3] for c in SUB_TILED[:6]] + [(4096, 1024, 512)]
)
def test_causal_tile_census_counts_the_mask(seq, bq, bk, sub):
    sub_q, sub_k = min(sub, bq), min(sub, bk)
    keep = np.tril(np.ones((seq, seq), bool))

    def tiles(rows, cols):
        """[seq/rows, seq/cols] of (any kept, all kept)."""
        t = keep.reshape(seq // rows, rows, seq // cols, cols)
        return t.any(axis=(1, 3)), t.all(axis=(1, 3))

    live_blocks, _ = tiles(bq, bk)
    some, every = tiles(sub_q, sub_k)
    assert flash_attention.causal_tile_census(
        seq, bq, bk, sub_q, sub_k
    ) == (
        int(live_blocks.sum()) * (bq // sub_q) * (bk // sub_k),
        int(some.sum()), int((some & ~every).sum()),
    )


def test_census_at_gpt2_xl_and_its_gauges():
    assert flash_attention.causal_tile_census(
        1024, 1024, 1024, 128, 128) == (64, 36, 8)
    from dlrover_tpu.telemetry.registry import default_registry

    q, k, v = _rand_qkv(jax.random.key(6), 1, 512, 1, 1, 64)
    jax.grad(lambda q: jnp.sum(flash_attention_tpu(
        q, k, v, causal=True, block_q=512, block_k=512)))(q)
    text = default_registry().to_prometheus_text()
    # without a group the backward is one kernel, under its own name
    for kernel in ("fwd", "dqkv"):
        edge = flash_attention._sub_tiles(kernel, 512, 512, 1, 64) or 512
        covered, computed, masked = flash_attention.causal_tile_census(
            512, 512, 512, edge, edge)
        for name, value in (
            ("attn_tiles_computed_share", computed / covered),
            ("attn_tiles_masked_share", masked / covered),
        ):
            line = next(ln for ln in text.splitlines()
                        if ln.startswith(
                            f'{name}{{kernel="{kernel}",window="none"}} '))
            assert float(line.split()[1]) == value


# (seq, block_q, block_k, g, head_dim, causal, window) where the
# backward is one kernel. Without a group (the head's dQ resident): one
# block a head and several (the resident dQ sums over the key blocks a
# query block meets), both head widths, and at blocks of 512 the
# diagonal walked in sub-tiles of 256. With a group (the kv head's dK
# and dV resident, summed over the query blocks a key block meets):
# groups of 4 and 7, both head widths, equal and unequal blocks, more
# than one each way (four query blocks on two key blocks at 256
# positions), without a window and with one that leaves the last
# query block's first key block dead and crosses the one before
ONE_BACKWARD_KERNEL = [
    (256, 256, 256, 1, 64, True, None),
    (256, 256, 256, 1, 128, False, None),
    (512, 128, 128, 1, 64, True, None),
    (512, 128, 128, 1, 128, True, None),
    (512, 128, 128, 1, 64, False, None),
    (512, 512, 512, 1, 128, True, None),
    (1024, 512, 512, 1, 64, True, None),
    (256, 64, 128, 4, 64, True, None),
    (256, 64, 128, 4, 128, True, 60),
    (256, 64, 128, 7, 128, True, None),
    (256, 64, 128, 7, 64, True, 44),
    (256, 128, 128, 4, 64, False, None),
]


def _kernels_of_grads(attn, q, k, v):
    """The kernels in the jaxpr of ``attn``'s gradients, by name of
    their body."""
    names = []

    def find(jaxpr):
        for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["jaxpr"].debug_info.func_name)
                continue
            for value in eqn.params.values():
                if hasattr(getattr(value, "jaxpr", value), "eqns"):
                    find(value)

    find(jax.make_jaxpr(functools.partial(_grads, attn))(q, k, v))
    return names


def _grads(attn, q, k, v):
    return jax.grad(
        lambda q, k, v: jnp.sum(attn(q, k, v) ** 2), argnums=(0, 1, 2)
    )(q, k, v)


@pytest.fixture(scope="module", params=ONE_BACKWARD_KERNEL, ids=str)
def one_backward_kernel(request):
    """dq, dk, dv of the one backward kernel, of the dq and dk/dv pair
    at the same blocks, and of the reference, once a case."""
    seq, block_q, block_k, g, d, causal, window = request.param
    # two kv heads a sequence: the resident scratch is zeroed anew
    # where the grid moves to the next head
    q, k, v = _rand_qkv(
        jax.random.key(7), 2 if g == 1 else 1, seq, 2 * g, 2, d)

    def attn(q, k, v):
        return flash_attention_tpu(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            window=window)

    assert _kernels_of_grads(attn, q, k, v) == [
        "_fwd_kernel", "_dqkv_kernel" if g == 1 else "_dq_dkv_kernel"]
    one = _grads(attn, q, k, v)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            flash_attention, "_one_backward_kernel", lambda g, seq, d: False)
        assert _kernels_of_grads(attn, q, k, v) == [
            "_fwd_kernel", "_dq_kernel", "_dkv_kernel"]
        pair = _grads(attn, q, k, v)
    ref = _grads(
        lambda q, k, v: mha_reference(q, k, v, causal=causal, window=window),
        q, k, v)
    return tuple(dict(zip(("dq", "dk", "dv"), g)) for g in (one, pair, ref))


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
def test_one_backward_kernel_matches_reference_and_the_pair(
    one_backward_kernel, what
):
    one, pair, ref = one_backward_kernel
    np.testing.assert_allclose(
        one[what], ref[what], rtol=5e-3, atol=5e-3,
        err_msg=f"{what} against the reference",
    )
    # the same products on the same values, dQ's sums over key blocks
    # in the dq kernel's order and dK's and dV's over query blocks in
    # the dk/dv kernel's: float32 agrees to the last bit
    np.testing.assert_array_equal(
        one[what], pair[what],
        err_msg=f"{what} against the dq and dk/dv kernels",
    )


def _backward_kernels_gauge(form):
    """The gauge's child under ``form``, set anew by the next backward
    built of that form."""
    from dlrover_tpu.telemetry.registry import default_registry

    return default_registry().get(
        "attn_backward_kernels").labels(form=form)


@pytest.mark.parametrize("g,kernel,form", [
    (1, "_dqkv_kernel", "dq_resident"),
    (4, "_dq_dkv_kernel", "dkv_resident"),
    (7, "_dq_dkv_kernel", "dkv_resident"),
])
def test_a_group_keeps_its_kv_heads_gradients_resident(g, kernel, form):
    q, k, v = _rand_qkv(jax.random.key(8), 1, 256, g, 1, 64)

    def attn(q, k, v):
        return flash_attention_tpu(
            q, k, v, causal=True, block_q=128, block_k=128)

    _grads(attn, q, k, v)  # the gauge is there once a backward was built
    _backward_kernels_gauge(form).set(0)
    assert _kernels_of_grads(attn, q, k, v) == ["_fwd_kernel", kernel]
    assert _backward_kernels_gauge(form).value == 1
    for got, want in zip(
        _grads(attn, q, k, v),
        _grads(lambda q, k, v: mha_reference(q, k, v, causal=True), q, k, v),
    ):
        np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("g", [1, 4])
def test_a_head_too_long_for_vmem_keeps_two_backward_kernels(monkeypatch, g):
    """The rule reads shapes, and one budget: without a group the
    head's float32 dQ, with one the kv head's float32 dK and dV,
    whatever the group is, either in rows of whole lanes."""
    rule = flash_attention._one_backward_kernel
    budget = flash_attention.RESIDENT_BYTES
    assert rule(1, 1024, 64) and rule(1, 4096, 128)  # gpt2-xl, OLMoE
    assert rule(1, budget // (4 * 128), 128)
    assert not rule(1, 2 * budget // (4 * 128), 128)
    # a 192-wide row holds two lanes' rows, a 64-wide one a whole one
    assert rule(1, budget // (4 * 256), 192)
    assert not rule(1, 2 * budget // (4 * 256), 192)
    assert not rule(1, 2 * budget // (4 * 128), 64)
    # Mistral, lfm2, smallthinker, chip_smoke.py's llama_1b
    assert rule(4, 4096, 128) and rule(4, 8192, 64)
    assert rule(7, 16384, 128) and rule(8, 2048, 64)
    for group in (2, 7, 16):
        assert rule(group, budget // (2 * 4 * 128), 128)
        assert not rule(group, 2 * budget // (2 * 4 * 128), 128)
        assert rule(group, budget // (2 * 4 * 128), 64)
        assert not rule(group, 2 * budget // (2 * 4 * 128), 64)
    assert [flash_attention.backward_form(*shape) for shape in (
        (1, 16384, 192), (1, 32768, 192), (16, 16384, 128),
        (16, 32768, 128))] == [
            "dq_resident", "pair", "dkv_resident", "pair"]
    # 256 positions of a lane's row, once without a group and twice
    # with one
    monkeypatch.setattr(
        flash_attention, "RESIDENT_BYTES", min(g, 2) * 256 * 128 * 4)
    q, k, v = _rand_qkv(jax.random.key(9), 1, 512, g, 1, 64)
    assert _kernels_of_grads(
        functools.partial(flash_attention_tpu, block_q=128, block_k=128),
        q, k, v,
    ) == ["_fwd_kernel", "_dq_kernel", "_dkv_kernel"]
    assert _backward_kernels_gauge("pair").value == 2
    # at the edge: 256 positions are within both
    assert _kernels_of_grads(
        functools.partial(flash_attention_tpu, block_q=128, block_k=128),
        q[:, :256], k[:, :256], v[:, :256],
    )[1:] == ["_dqkv_kernel" if g == 1 else "_dq_dkv_kernel"]



# -- q and k wider than v (latent attention) ---------------------------------

# [seq, block_q, block_k, causal]: q and k 192 wide (a lane and a
# half), v, and so o, dO and dV, 128 wide; no group. Several blocks
# each way, one block walked in sub-tiles of 256, and no mask
UNEQUAL_WIDTHS = [
    (256, 128, 128, True),
    (512, 512, 512, True),
    (256, 64, 128, True),
    (256, 128, 128, False),
]


@pytest.fixture(scope="module", params=UNEQUAL_WIDTHS, ids=str)
def unequal_widths(request):
    """o, dq, dk, dv at (192, 128) of the kernels with the one
    backward kernel, with the dq and dk/dv pair (what a head of 8,192
    positions took until the rule admitted its dQ), and of the
    reference."""
    seq, block_q, block_k, causal = request.param
    kq, kk, kv = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(kq, (2, seq, 2, 192))
    k = jax.random.normal(kk, (2, seq, 2, 192))
    v = jax.random.normal(kv, (2, seq, 2, 128))

    def attn(q, k, v):
        return flash_attention_tpu(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k)

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=causal)

    def all_four(fn):
        return dict(zip(
            ("fwd", "dq", "dk", "dv"), (fn(q, k, v), *_grads(fn, q, k, v))))

    assert attn(q, k, v).shape == (2, seq, 2, 128)
    assert _kernels_of_grads(attn, q, k, v) == [
        "_fwd_kernel", "_dqkv_kernel"]
    one = all_four(attn)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            flash_attention, "_one_backward_kernel", lambda g, seq, d: False)
        assert _kernels_of_grads(attn, q, k, v) == [
            "_fwd_kernel", "_dq_kernel", "_dkv_kernel"]
        pair = all_four(attn)
    return one, pair, all_four(ref)


@pytest.mark.parametrize("what", ["fwd", "dq", "dk", "dv"])
def test_unequal_widths_match_reference(unequal_widths, what):
    one, pair, ref = unequal_widths
    assert one[what].shape == ref[what].shape
    assert ref[what].shape[-1] == (128 if what in ("fwd", "dv") else 192)
    for got in (one, pair):
        np.testing.assert_allclose(
            got[what], ref[what], rtol=5e-3, atol=5e-3, err_msg=what)


def test_the_default_scale_is_q_and_ks_width():
    """192 ** -0.5, not v's 128 ** -0.5, in the kernel and in the
    reference alike."""
    kq, kk, kv = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(kq, (1, 128, 1, 192))
    k = jax.random.normal(kk, (1, 128, 1, 192))
    v = jax.random.normal(kv, (1, 128, 1, 128))
    want = mha_reference(q, k, v, scale=192 ** -0.5)
    np.testing.assert_array_equal(mha_reference(q, k, v), want)
    np.testing.assert_allclose(
        flash_attention_tpu(q, k, v, block_q=128, block_k=128), want,
        rtol=2e-3, atol=2e-3)
    other = mha_reference(q, k, v, scale=128 ** -0.5)
    assert float(jnp.abs(other - want).max()) > 0.01


def test_a_long_head_at_latent_widths_keeps_its_dq_resident():
    """The cells' shapes: 8,192 positions of 192 (``joyai``) are 8
    MiB of float32 dQ in rows of two lanes, 16,384 (``kimi``) the
    budget's 16; past what fits beside the rest under the default
    scoped limit the call states its VMEM (rows of whole lanes: the
    float32 sum and two buffers of the output block, and
    ``OTHER_VMEM_BYTES``): at the edge what the grouped backward asks
    at its own, 60 MiB."""
    rule = flash_attention._one_backward_kernel
    assert rule(1, 8192, 192) and rule(1, 16384, 192)
    assert not rule(1, 32768, 192)
    assert 16384 * 256 * 4 == flash_attention.RESIDENT_BYTES
    assert 8192 * 192 * 4 > flash_attention.DQ_UNSTATED_BYTES
    assert 4096 * 128 * 4 <= flash_attention.DQ_UNSTATED_BYTES  # OLMoE
    assert flash_attention._dq_resident_vmem_bytes(8192, 192, 2) == (
        8192 * 256 * 8 + flash_attention.OTHER_VMEM_BYTES)
    assert flash_attention._dq_resident_vmem_bytes(16384, 192, 2) == (
        flash_attention._dkv_resident_vmem_bytes(16384, 128, 2)
    ) == 60 * 2 ** 20


# -- q and k in the parts their products make (latent attention) -------------

# [seq, block_q, block_k, causal, g, window, backward]: a head's q and
# k 128 + 64 wide, the rotated key one for every head, v 128. The
# cell's form first (no group, the one backward kernel with the head's
# dQ resident) over several blocks each way, one block walked in
# sub-tiles, unequal blocks and no mask; then the forms no cell runs
# in parts and a caller may: the dq and dk/dv pair, and with a group
# the kv head's dK and dV resident, with a window too
IN_PARTS = [
    (256, 128, 128, True, 1, None, "_dqkv_kernel"),
    (512, 512, 512, True, 1, None, "_dqkv_kernel"),
    (256, 64, 128, True, 1, None, "_dqkv_kernel"),
    (256, 128, 128, False, 1, None, "_dqkv_kernel"),
    (256, 128, 128, True, 1, None, "pair"),
    (256, 64, 128, True, 4, None, "_dq_dkv_kernel"),
    (256, 64, 128, True, 2, 60, "_dq_dkv_kernel"),
]
PARTS = ("q", "q_rope", "k", "k_rope", "v")


def _rand_parts(key, b, s, h, kvh, dtype=jnp.float32):
    shapes = dict(q=(b, s, h, 128), q_rope=(b, s, h, 64),
                  k=(b, s, kvh, 128), k_rope=(b, s, 1, 64),
                  v=(b, s, kvh, 128))
    return {name: jax.random.normal(k, shapes[name], dtype)
            for name, k in zip(PARTS, jax.random.split(key, 5))}


def _whole(attn):
    """``attn(q, k, v)`` on the parts put side by side outside it."""
    from dlrover_tpu.ops.attention import whole_q_and_k

    def on_parts(q, q_rope, k, k_rope, v):
        return attn(*whole_q_and_k(q, k, q_rope, k_rope), v)

    return on_parts


def _forward_and_grads(fn, parts):
    """o, and the gradient of each of the five operands; jitted, which
    interpret mode repays twice over (a fresh trace a call: what a
    caller patches is read while it is traced)."""
    def both(*operands):
        return fn(*operands), jax.grad(
            lambda *operands: jnp.sum(fn(*operands) ** 2), argnums=range(5)
        )(*operands)

    o, grads = jax.jit(both)(*parts.values())
    return dict(zip(PARTS, grads), o=o)


@pytest.fixture(scope="module", params=IN_PARTS, ids=str)
def in_parts(request):
    """o and the five gradients of the kernels on the parts, of the
    same kernels on whole q and k, and of the reference, once a
    case."""
    seq, block_q, block_k, causal, g, window, backward = request.param
    parts = _rand_parts(jax.random.key(13), 2, seq, 2 * g, 2)
    kw = dict(causal=causal, window=window)

    def attn(q, k, v, **rope):
        return flash_attention_tpu(
            q, k, v, block_q=block_q, block_k=block_k, **kw, **rope)

    def on_parts(q, q_rope, k, k_rope, v):
        return attn(q, k, v, q_rope=q_rope, k_rope=k_rope)

    want = ["_dq_kernel", "_dkv_kernel"] if backward == "pair" else [backward]
    with pytest.MonkeyPatch.context() as patch:
        if backward == "pair":
            patch.setattr(flash_attention, "_one_backward_kernel",
                          lambda g, seq, d: False)
        assert _kernels_of_grads(
            lambda q, k, v: on_parts(
                q, parts["q_rope"], k, parts["k_rope"], v),
            parts["q"], parts["k"], parts["v"],
        ) == ["_fwd_kernel", *want]
        got = _forward_and_grads(on_parts, parts)
        whole = _forward_and_grads(_whole(attn), parts)
    ref = _forward_and_grads(
        _whole(functools.partial(mha_reference, **kw)), parts)
    return got, whole, ref


@pytest.mark.parametrize("what", ("o",) + PARTS)
def test_kernels_on_parts_are_the_kernels_on_whole_q_and_k(in_parts, what):
    got, whole, ref = in_parts
    assert got[what].shape == ref[what].shape
    np.testing.assert_allclose(
        got[what], ref[what], rtol=5e-3, atol=5e-3,
        err_msg=f"{what} against the reference")
    if what == "k_rope":
        # whole, the heads' parts are summed in the concatenation's
        # transpose: another order of the same float32 terms
        np.testing.assert_allclose(
            got[what], whole[what], rtol=1e-5, atol=1e-5, err_msg=what)
    else:
        # a block's tile is put together in VMEM and the body is the
        # one it was: the same products in the same order
        np.testing.assert_array_equal(got[what], whole[what], err_msg=what)


@pytest.fixture(scope="module")
def bf16_parts():
    parts = _rand_parts(jax.random.key(14), 1, 256, 2, 2, jnp.bfloat16)

    def attn(q, k, v, **rope):
        return flash_attention_tpu(
            q, k, v, block_q=128, block_k=128, **rope).astype(jnp.float32)

    got = _forward_and_grads(
        lambda q, q_rope, k, k_rope, v: attn(
            q, k, v, q_rope=q_rope, k_rope=k_rope), parts)
    return got, _forward_and_grads(_whole(attn), parts)


@pytest.mark.parametrize("what", ("o",) + PARTS)
def test_bf16_parts_are_within_a_step_of_whole_q_and_k(bf16_parts, what):
    got, whole = bf16_parts
    assert got[what].dtype == whole[what].dtype
    scale = float(jnp.abs(whole[what].astype(jnp.float32)).max())
    np.testing.assert_allclose(
        got[what].astype(np.float32), whole[what].astype(np.float32),
        rtol=0, atol=2 ** -7 * scale, err_msg=what)


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("what", ("o",) + PARTS + ("lse",))
def test_the_reference_on_parts_is_the_reference_on_their_concatenation(
        what, window):
    parts = _rand_parts(jax.random.key(15), 2, 64, 4, 2)
    kw = dict(causal=True, window=window)
    if what == "lse":
        q, q_rope, k, k_rope, v = parts.values()
        from dlrover_tpu.ops.attention import whole_q_and_k

        got = mha_reference(
            q, k, v, return_lse=True, q_rope=q_rope, k_rope=k_rope, **kw)
        want = mha_reference(
            *whole_q_and_k(q, k, q_rope, k_rope), v, return_lse=True, **kw)
        np.testing.assert_array_equal(got[1], want[1])
        return
    got = _forward_and_grads(
        lambda q, q_rope, k, k_rope, v: mha_reference(
            q, k, v, q_rope=q_rope, k_rope=k_rope, **kw), parts)
    want = _forward_and_grads(
        _whole(functools.partial(mha_reference, **kw)), parts)
    np.testing.assert_array_equal(got[what], want[what])


def test_the_parts_decide_the_kernels_and_the_record_says_which(monkeypatch):
    """What a caller hands decides: the dispatch under ``jax.jit``
    passes the parts on and records ``rope_head_dim``, the gauge reads
    2 for each kernel built on parts and 1 for one built on whole q
    and k, and the default scale is the whole width's."""
    from dlrover_tpu.ops import attention, tuning
    from dlrover_tpu.telemetry.registry import default_registry

    def parts_gauge(kernel):
        return default_registry().get(
            "attn_operand_parts").labels(kernel=kernel).value

    monkeypatch.setattr(attention, "_use_pallas", lambda q, k: True)
    parts = _rand_parts(jax.random.key(16), 1, 256, 2, 2)
    q, q_rope, k, k_rope, v = parts.values()
    # the undecorated function: traced whatever this process has
    # traced before
    dispatch = attention.flash_attention.__wrapped__
    got = _forward_and_grads(
        lambda q, q_rope, k, k_rope, v: dispatch(
            q, k, v, q_rope=q_rope, k_rope=k_rope), parts)
    record = tuning.last_selection()
    assert (record["head_dim"], record["rope_head_dim"]) == (192, 64)
    assert "v_head_dim" in record and record["source"] == "static"
    assert parts_gauge("fwd") == parts_gauge("dqkv") == 2
    want = _forward_and_grads(_whole(functools.partial(
        mha_reference, scale=192 ** -0.5)), parts)
    for what in ("o",) + PARTS:
        np.testing.assert_allclose(
            got[what], want[what], rtol=5e-3, atol=5e-3, err_msg=what)
    _forward_and_grads(_whole(dispatch), parts)
    assert "rope_head_dim" not in tuning.last_selection()
    assert parts_gauge("fwd") == parts_gauge("dqkv") == 1
    # off the TPU the same call is the reference's
    monkeypatch.undo()
    np.testing.assert_array_equal(
        attention.flash_attention(q, k, v, q_rope=q_rope, k_rope=k_rope),
        mha_reference(q, k, v, q_rope=q_rope, k_rope=k_rope))


@pytest.mark.parametrize("change,sentence", [
    (dict(k_rope=None), "come together"),
    (dict(q_rope=None), "come together"),
    (dict(k_rope=jnp.zeros((1, 128, 2, 64))), "one rotated key"),
])
def test_parts_that_do_not_fit_are_refused(change, sentence):
    parts = _rand_parts(jax.random.key(17), 1, 128, 2, 2)
    q, k, v = parts["q"], parts["k"], parts["v"]
    rope = {**dict(q_rope=parts["q_rope"], k_rope=parts["k_rope"]), **change}
    with pytest.raises(ValueError, match=sentence):
        flash_attention_tpu(q, k, v, block_q=128, block_k=128, **rope)
