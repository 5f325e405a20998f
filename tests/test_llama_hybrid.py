"""What models/llama.py, parallel/moe.py and ops/ gained for LFM2: a
stack of two kinds of operator whose positions own their parameter
stacks, the gated short convolution (plain and Pallas), leading dense
layers ahead of the scan, a norm on each head's q and k, a tied head,
a sigmoid router that selects by a biased score, and a bias the
optimizer leaves alone; and that a config with none of it keeps the
parameter tree it always had."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops import short_conv
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.grouped_matmul import IN_PLACE_TILE, tiles
from dlrover_tpu.ops.pallas import short_conv as kernels
from dlrover_tpu.parallel import moe
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

REMATS = ("off", "dots", "dots_attn_out", "minimal")
TYPES = ("conv",) + ("full_attention", "conv", "conv", "conv") * 2


def _hybrid(**kw):
    """Nine layers: a leading conv layer with a dense MLP, then
    [attention, conv, conv, conv] twice with 16 experts of which the
    first 4 are held, top-4 by sigmoid score plus a bias."""
    kw = {**dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=24, num_layers=9, num_dense_layers=1,
        layer_types=TYPES, num_heads=4, num_kv_heads=2, head_dim=16,
        qk_head_norm=True, tie_word_embeddings=True, max_seq_len=32,
        dtype=jnp.float32, remat="off", num_experts=16, moe_top_k=4,
        moe_capacity_factor=0.0, router_z_loss_coef=0.0,
        moe_gate="sigmoid", use_expert_bias=True, moe_experts_held=4,
        embed_init_std=0.1,
    ), **kw}
    return llama.LlamaConfig(**kw)


def _biased(params, std=0.1):
    """``params`` with every layer's selection bias drawn at ``std``:
    ``init_params`` starts the buffer at zero, where it changes no
    assignment."""
    drawn = iter(jax.random.split(jax.random.key(9), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: std * jax.random.normal(
            next(drawn), leaf.shape, leaf.dtype
        ) if path[-1].key == "expert_bias" else leaf,
        params,
    )


def _init(cfg):
    return _biased(llama.init_params(jax.random.key(0), cfg))


def _batch(cfg, seed=1, shape=(2, 32)):
    tokens = jax.random.randint(
        jax.random.key(seed), shape, 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


# -- the gated short convolution -------------------------------------------

def _conv_case(dtype, batch=2, seq=64, hidden=256, taps=3):
    keys = jax.random.split(jax.random.key(0), 3)
    bcu = jax.random.normal(keys[0], (batch, seq, 3 * hidden)).astype(dtype)
    w = jax.random.normal(keys[1], (hidden, taps)).astype(dtype)
    dy = jax.random.normal(keys[2], (batch, seq, hidden)).astype(dtype)
    return bcu, w, dy


def test_plain_convolution_is_the_equations():
    """Three taps, oldest first, zeros before a sequence's start, the
    two gates around them: against a loop over positions."""
    bcu, w, _ = _conv_case(jnp.float32, batch=2, seq=8, hidden=4)
    b, c, u = np.split(np.asarray(bcu), 3, axis=-1)
    v, want = b * u, np.zeros((2, 8, 4), np.float32)
    for t in range(8):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += np.asarray(w)[:, j] * v[:, t - 2 + j]
    np.testing.assert_allclose(
        short_conv.gated_short_conv(bcu, w), c * want, rtol=1e-5,
        atol=1e-6)
    with pytest.raises(ValueError, match="three times"):
        short_conv.gated_short_conv(bcu[..., :8], w)


@pytest.mark.parametrize("dtype,rows", [
    (jnp.float32, 16), (jnp.float32, 32), (jnp.float32, None),
    (jnp.bfloat16, 16),
])
def test_pallas_convolution_agrees_with_the_plain_one(dtype, rows):
    """Interpret mode, forward and every gradient, ``dw`` among them,
    over two sequences in one batch and several blocks of time: a
    block's first rows read the block before it, never the sequence
    before it."""
    bcu, w, dy = _conv_case(dtype)
    f32 = jnp.float32

    def plain(bcu, w):
        out = short_conv.gated_short_conv_plain(bcu, w)
        return jnp.sum(out.astype(f32) * dy.astype(f32))

    want = short_conv.gated_short_conv_plain(bcu, w)
    want_bcu, want_w = jax.grad(plain, (0, 1))(bcu, w)
    got = kernels.short_conv(bcu, w, rows=rows)
    got_bcu, got_w = kernels.short_conv(bcu, w, dy, rows=rows)
    tol = dict(rtol=1e-5, atol=2e-5) if dtype == f32 else dict(
        rtol=2e-2, atol=2e-2)
    for a, b in ((got, want), (got_bcu, want_bcu), (got_w, want_w)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            a.astype(f32), b.astype(f32), **tol)
    # the second sequence's first outputs see nothing of the first's
    alone = kernels.short_conv(bcu[1:], w, rows=rows)
    np.testing.assert_array_equal(got[1:, :4], alone[:, :4])


def test_the_kernel_differentiates_as_one_function():
    bcu, w, dy = _conv_case(jnp.float32, seq=32, hidden=128)
    got = jax.grad(
        lambda b, w: jnp.sum(kernels.short_conv_tpu(b, w) * dy), (0, 1)
    )(bcu, w)
    want = jax.grad(
        lambda b, w: jnp.sum(
            short_conv.gated_short_conv_plain(b, w) * dy), (0, 1)
    )(bcu, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
    assert kernels.tiles_the_kernel((4, 8192, 6144), (2048, 3))
    assert not kernels.tiles_the_kernel((4, 8192, 300), (100, 3))
    assert not kernels.tiles_the_kernel((4, 100, 768), (256, 3))


def test_tiles_of_a_float32_sum_in_place():
    """2048 x 1792: the widest tiles forward, and a face within
    ``IN_PLACE_TILE`` where ``tgmm`` adds to a float32 sum; 2560 x
    768's are what they were."""
    assert tiles(8192, 2048, 1792) == (512, 1024, 896)
    assert tiles(8192, 1792, 2048) == (512, 896, 1024)
    assert tiles(8192, 2048, 1792, IN_PLACE_TILE) == (512, 512, 896)
    assert tiles(8192, 1792, 2048, IN_PLACE_TILE) == (512, 896, 512)
    assert tiles(8192, 2560, 768, IN_PLACE_TILE) == (512, 640, 768)
    assert tiles(8192, 768, 2560, IN_PLACE_TILE) == (512, 768, 640)
    assert tiles(8192, 2048, 1792, 100) is None


# -- the router --------------------------------------------------------------

def test_sigmoid_router_selects_by_the_biased_score():
    logits = jax.random.normal(jax.random.key(0), (64, 16))
    bias = 0.5 * jax.random.normal(jax.random.key(1), (16,))
    score = jax.nn.sigmoid(logits)
    weights, experts, aux = moe.route_logits(
        logits, 4, True, z_coef=0.0, gate="sigmoid", bias=bias)
    _, want = jax.lax.top_k(score + bias, 4)
    np.testing.assert_array_equal(experts, want)
    picked = jnp.take_along_axis(score, want, axis=-1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    _, unbiased, _ = moe.route_logits(
        logits, 4, True, z_coef=0.0, gate="sigmoid")
    assert not np.array_equal(np.sort(experts), np.sort(unbiased))
    raw, _, _ = moe.route_logits(
        logits, 4, False, gate="sigmoid", bias=bias)
    np.testing.assert_allclose(raw, picked, rtol=1e-6)
    # the balance term reads the scores normalised over the experts
    shares = score / score.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        aux, moe.BALANCE_LOSS_COEF * moe.balance_loss(shares, want),
        rtol=1e-6)
    changed = int(moe.bias_changed(logits, 4, bias, gate="sigmoid"))
    same = (np.asarray(experts)[:, :, None]
            == np.asarray(unbiased)[:, None, :]).any(-1)
    assert changed == int((~same).sum()) > 0
    assert int(moe.bias_changed(
        logits, 4, jnp.zeros(16), gate="sigmoid")) == 0


def test_no_gradient_reaches_the_bias():
    cfg = _hybrid()
    params = _init(cfg)
    grads = jax.grad(llama.next_token_loss)(params, _batch(cfg), cfg)
    for position in grads["period"]:
        assert not np.asarray(position["expert_bias"]).any()
        assert np.asarray(position["router"]).any()


def test_the_optimizer_leaves_the_bias_bit_equal():
    """A step of AdamW with weight decay moves every leaf but the
    bias: its update is dropped, decay and all."""
    cfg = _hybrid()
    mesh = create_mesh([("data", 1), ("fsdp", 1)],
                       devices=jax.devices()[:1])
    trainer = make_trainer_for_llama(
        cfg, mesh, optimizer=optax.adamw(1e-2, weight_decay=0.1))
    params, opt_state = trainer.init(jax.random.key(0))
    assert not np.asarray(params["period"][0]["expert_bias"]).any()
    params = _biased(params)
    before = jax.tree.map(np.asarray, params)
    assert np.asarray(before["period"][0]["expert_bias"]).any()
    mb = trainer.microbatch(_batch(cfg))
    params, opt_state, _ = trainer.train_step(params, opt_state, mb)
    frozen = llama.frozen_params(cfg)
    moved = jax.tree.map(
        lambda a, b: not np.array_equal(a, np.asarray(b)), before, params)
    assert jax.tree.leaves(frozen).count(True) == 4
    for still, did in zip(jax.tree.leaves(frozen),
                          jax.tree.leaves(moved)):
        assert still != did
    assert llama.frozen_params(llama.llama_moe_tiny()) is None


# -- the stack ---------------------------------------------------------------

def test_positions_own_their_stacks():
    cfg = _hybrid()
    lead, period = cfg.layer_plan()
    assert lead == (llama.LayerKind("conv", None, False, "dense"),)
    assert period == (
        llama.LayerKind("full_attention", None, True, "experts"),
    ) + (llama.LayerKind("conv", None, False, "experts"),) * 3
    params = llama.init_params(jax.random.key(0), cfg)
    assert set(params) == {"embed", "final_norm", "lead", "period"}
    assert len(params["lead"]) == 1 and len(params["period"]) == 4
    (first,) = params["lead"]
    assert set(first) == {"attn_norm", "conv_in", "conv_w", "conv_out",
                          "mlp_norm", "w_gate", "w_up", "w_down"}
    assert first["conv_in"].shape == (64, 192)
    assert first["conv_w"].shape == (64, 3)
    assert first["w_gate"].shape == (64, 96)  # the dense width
    attention, conv = params["period"][0], params["period"][1]
    assert set(attention) == {
        "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
        "mlp_norm", "router", "expert_bias", "w_gate", "w_up", "w_down"}
    assert attention["q_norm"].shape == attention["k_norm"].shape == (
        2, 16)  # a head's width, a period each
    assert attention["w_gate"].shape == (2, 4, 64, 24)  # held, moe width
    assert attention["router"].shape == (2, 64, 16)
    assert attention["expert_bias"].dtype == jnp.float32
    assert not np.asarray(attention["expert_bias"]).any()  # starts flat
    assert set(conv) == {
        "attn_norm", "conv_in", "conv_w", "conv_out", "mlp_norm",
        "router", "expert_bias", "w_gate", "w_up", "w_down"}
    assert conv["conv_out"].shape == (2, 64, 64)
    axes = llama.param_axes(cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(params))
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        a is None or isinstance(a, str) for a in x)
    for path, names in jax.tree_util.tree_leaves_with_path(
            axes, is_leaf=is_axes):
        assert len(names) == flat[path].ndim, path
    assert len(flat) == len(jax.tree.leaves(axes, is_leaf=is_axes))
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        llama.param_count(cfg))


def test_a_config_without_the_keys_builds_the_parents_tree():
    """One stack ``blocks`` of like layers and an ``lm_head``, the
    draws what they were (the jaxprs: tests/test_llama_pattern.py)."""
    for cfg in (llama.llama_tiny(), llama.llama_moe_tiny(qk_norm=True)):
        assert not cfg.by_position
        assert cfg.layer_plan()[0] == ()
        params = llama.init_params(jax.random.key(0), cfg)
        assert set(params) == {"embed", "blocks", "final_norm", "lm_head"}
        assert all(leaf.shape[0] == cfg.num_layers
                   for leaf in params["blocks"].values())
        assert "expert_bias" not in params["blocks"]
        assert set(llama.param_axes(cfg)) == set(params)
    ks = jax.random.split(jax.random.split(jax.random.key(0), 3)[1], 8)
    want = (jax.random.normal(ks[0], (2, 64, 64)) * 64 ** -0.5).astype(
        jnp.bfloat16)
    np.testing.assert_array_equal(
        llama.init_params(jax.random.key(0), llama.llama_tiny())[
            "blocks"]["wq"], want)


def _loop_over_layers(params, batch, cfg):
    """The loss with every layer walked in a Python loop by its own
    entry of ``layer_types``: no scan, no period, no lead."""
    tokens, targets = batch
    cos, sin = llama.rope_tables(
        tokens.shape[1], cfg.head_dim, cfg.rope_theta)
    x, aux_sum = params["embed"][tokens], 0.0
    for i, operator in enumerate(cfg.layer_types):
        if i < cfg.num_dense_layers:
            p = params["lead"][i]
        else:
            at = i - cfg.num_dense_layers
            p = jax.tree.map(
                lambda a: a[at // 4], params["period"][at % 4])
        kind = llama.LayerKind(
            operator, None, operator != "conv",
            "dense" if i < cfg.num_dense_layers else "experts")
        operate = (short_conv.gated_short_conv if operator == "conv"
                   else mha_reference)
        x, aux, _ = llama._block(cfg, x, p, cos, sin, operate, kind=kind)
        aux_sum = aux_sum + aux
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    nll, count = llama._masked_nll(
        (x @ params["embed"].T).astype(jnp.float32), targets)
    return nll / count + aux_sum


@pytest.mark.parametrize("remat", REMATS)
def test_lead_and_period_scan_against_a_loop_over_layers(remat):
    cfg = _hybrid(remat=remat)
    params = _init(cfg)
    batch = _batch(cfg)
    want, want_g = jax.jit(
        jax.value_and_grad(_loop_over_layers), static_argnums=2
    )(params, batch, dataclasses.replace(cfg, remat="off"))
    got, got_g = jax.jit(
        jax.value_and_grad(llama.next_token_loss), static_argnums=2
    )(params, batch, cfg)
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)
    # one scan over the two periods; the leading layer is outside it
    text = str(jax.make_jaxpr(
        lambda p: llama.next_token_loss(p, batch, cfg))(params))
    assert len(re.findall(r"length=2\b", text)) == 1
    assert "length=9" not in text and "length=8" not in text


def test_every_op_of_the_convolution_carries_its_scope():
    cfg = _hybrid()
    params = llama.init_params(jax.random.key(0), cfg)
    text = jax.jit(llama.next_token_loss, static_argnums=2).lower(
        params, _batch(cfg), cfg).as_text(debug_info=True)
    for scope in ("conv.in_proj", "conv.mix", "conv.out_proj",
                  "attn.full", "moe.route"):
        assert scope in text, scope


def test_chunked_loss_over_the_tied_head():
    cfg = _hybrid()
    params = llama.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg)
    whole = float(llama.next_token_loss(params, batch, cfg))
    chunked = float(llama.next_token_loss(
        params, batch, dataclasses.replace(cfg, loss_chunk=16)))
    assert abs(whole - chunked) < 1e-5
    logits = llama.forward(params, batch[0], cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)


def test_routing_stats_count_the_expert_layers():
    cfg = _hybrid()
    flat = llama.init_params(jax.random.key(0), cfg)
    params = _biased(flat)
    tokens = _batch(cfg)[0]
    counts = np.asarray(jax.jit(
        llama.routing_stats, static_argnums=2)(params, tokens, cfg))
    assert counts.shape == (8, 16)  # no row for the leading layer
    assert (counts.sum(-1) == tokens.size * 4).all()
    changed = np.asarray(llama.bias_changed_stats(params, tokens, cfg))
    assert changed.shape == (8,) and (changed > 0).all()
    share = moe.set_bias_changed_gauge(changed, tokens.size * 4)
    assert share == pytest.approx(changed.sum() / (8 * tokens.size * 4))
    assert not np.asarray(
        llama.bias_changed_stats(flat, tokens, cfg)).any()


def test_flops_per_token_counts_each_kind_of_layer():
    cfg = _hybrid()
    h, met = 64, 4 * 4 / 16
    conv, attention = 4 * h * h + 3 * h, 2 * h * 64 + 2 * h * 32 + 2 * 16
    experts = h * 16 + 16 + met * 3 * h * 24
    n = (7 * conv + 2 * attention + 8 * experts + 3 * h * 96
         + 9 * 2 * h + h + 128 * h)
    assert llama.flops_per_token(cfg, 32) == (
        6.0 * n + 12 * 4 * 16 * 2 * 32)


# -- what it refuses ----------------------------------------------------------

@pytest.mark.parametrize("change,sentence", [
    (dict(layer_types=TYPES[:8]), "8 entries for 9 layers"),
    (dict(layer_types=("state_space",) + TYPES[1:]), "state_space"),
    (dict(layer_types=TYPES + ("conv",)), "10 entries for 9 layers"),
    (dict(num_dense_layers=9), "num_dense_layers 9"),
    (dict(num_dense_layers=1, num_experts=0), "num_dense_layers 1"),
    (dict(moe_gate="tanh"), "moe_gate"),
])
def test_the_config_refuses_what_it_cannot_run(change, sentence):
    with pytest.raises(ValueError, match=sentence):
        _hybrid(**change)


def test_an_expert_axis_refuses_the_sigmoid_router():
    with pytest.raises(ValueError, match="sigmoid router"):
        llama._expert_mlp(_hybrid(moe_experts_held=16), True)
    from dlrover_tpu.parallel import pipeline

    cfg = _hybrid()
    with pytest.raises(ValueError, match="like layers"):
        pipeline.pipeline_llama_forward(
            llama.init_params(jax.random.key(0), cfg), _batch(cfg)[0],
            cfg, mesh=None)
