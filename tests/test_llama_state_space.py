"""models/llama.py with ``hybrid_override_pattern``: blocks of one
branch (a Mamba-2 mixer, attention or experts, never two), the three
kinds' disjoint leaves, the mixer written out against the recurrence,
experts without a gate in a latent, a prediction module of its own
sublayers, the scopes under every remat policy, and what
``__post_init__`` refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.models.llama import LayerKind

PATTERN = "MEMEMEM*EME"
SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.dt", "ssm.scan", "ssm.gate_norm",
          "ssm.out_proj", "moe.latent_down", "moe.latent_up")


def config(**kw):
    return llama.llama_tiny(**{**dict(
        num_layers=len(kw.get("hybrid_override_pattern", PATTERN)),
        hybrid_override_pattern=PATTERN,
        rope_layout=(0,) * len(kw.get("hybrid_override_pattern", PATTERN)),
        mamba_num_heads=8, mamba_head_dim=16, n_groups=4, ssm_state_size=16,
        chunk_size=32, num_experts=16, moe_top_k=4, moe_experts_held=4,
        moe_intermediate_size=24, moe_gate="sigmoid", use_expert_bias=True,
        moe_topk_norm_eps=1e-20, moe_routed_scaling=5.0,
        moe_shared_experts=1, moe_shared_expert_intermediate_size=48,
        moe_expert_act="relu2", moe_expert_gated=False, moe_latent_size=32,
        moe_capacity_factor=0.0, mtp_layers=1,
        mtp_hybrid_override_pattern="*E", dtype=jnp.float32,
    ), **kw})


def batch(cfg, sequences=2, seq=128):
    tokens = jax.random.randint(
        jax.random.key(1), (sequences, seq), 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)


MIXER = LayerKind("state_space", None, False, "none")
ATTENTION = LayerKind("full_attention", None, False, "none")
EXPERTS = LayerKind("none", None, False, "experts")


def test_the_pattern_names_blocks_of_one_branch():
    cfg = config()
    lead, period = cfg.layer_plan()
    assert lead == () and cfg.by_position
    assert period == tuple(
        {"M": MIXER, "*": ATTENTION, "E": EXPERTS}[c] for c in PATTERN)
    assert cfg.mtp_kinds() == (ATTENTION, EXPERTS)
    # a pattern that repeats is scanned a period at a time
    twice = config(hybrid_override_pattern="MEM*" * 2,
                   mtp_hybrid_override_pattern=None)
    assert twice.layer_plan() == ((), (MIXER, EXPERTS, MIXER, ATTENTION))
    assert twice.mtp_kinds() == (ATTENTION,)  # the stack's last kind
    # an attention layer is rotated where the layout says so
    rotated = config(rope_layout=(1,) * 11)
    assert rotated.layer_plan()[1][7].rope and rotated.mtp_kinds()[0].rope
    # a config without the key is what it was
    plain = llama.llama_tiny()
    assert plain.layer_plan() == ((), (LayerKind(),))
    assert not plain.by_position and plain.mtp_kinds() == (LayerKind(),)


def test_the_three_kinds_own_disjoint_leaves():
    cfg = config()
    mixer, attention, experts = (
        set(llama._leaves(cfg, kind)) for kind in (MIXER, ATTENTION, EXPERTS))
    assert mixer == {
        "attn_norm", "ssm_in", "ssm_out", "ssm_norm", "ssm_conv_w",
        "ssm_conv_b", "A_log", "dt_bias", "D"}
    assert attention == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert experts == {
        "mlp_norm", "router", "expert_bias", "w_up", "w_down",
        "w_latent_down", "w_latent_up", "ws_up", "ws_down"}
    assert mixer & experts == attention & experts == set()
    leaves = llama._leaves(cfg, MIXER)
    assert leaves["ssm_in"][0] == (64, 128 + 128 + 2 * 4 * 16 + 8)
    assert leaves["ssm_conv_w"][0] == (256, 4)
    assert leaves["ssm_norm"][0] == (128,) and leaves["D"][0] == (8,)
    assert llama._leaves(cfg, EXPERTS)["w_up"][0] == (4, 32, 24)
    assert llama._leaves(cfg, EXPERTS)["ws_up"][0] == (64, 48)
    no_bias = dataclasses.replace(cfg, use_conv_bias=False)
    assert "ssm_conv_b" not in llama._leaves(no_bias, MIXER)
    gated = dataclasses.replace(cfg, moe_expert_gated=True)
    assert {"w_gate", "ws_gate"} <= set(llama._leaves(gated, EXPERTS))


def test_the_tree_the_axes_and_the_count_agree():
    cfg = config()
    params = llama.init_params(jax.random.key(0), cfg)
    assert len(params["period"]) == 11 and "lead" in params
    (module,) = params["mtp"]
    assert [set(b) for b in module["block"]] == [
        set(llama._leaves(cfg, kind)) for kind in (ATTENTION, EXPERTS)]
    assert sum(a.size for a in jax.tree.leaves(params)) == (
        llama.param_count(cfg))
    axes = llama.param_axes(cfg)
    assert jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple)
    ) == jax.tree.structure(params)
    frozen = llama.frozen_params(cfg)
    flagged = [
        path[-1].key for path, on in
        jax.tree_util.tree_leaves_with_path(frozen) if on]
    assert flagged == ["expert_bias"] * 6  # five layers and the module's
    mixer = params["period"][0]
    # the draws: D at one, the bias at zero, the decay's two in float32
    assert float(mixer["D"].min()) == float(mixer["D"].max()) == 1.0
    assert float(jnp.abs(mixer["ssm_conv_b"]).max()) == 0.0
    rate = jnp.exp(mixer["A_log"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) < 16.0
    step = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 0.1 + 1e-6
    assert mixer["A_log"].dtype == mixer["dt_bias"].dtype == jnp.float32


def rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def mixer_by_hand(cfg, x, p):
    """``x + branch(RMSNorm(x))`` of a Mamba-2 block, the scan a
    position at a time."""
    b, s, _ = x.shape
    heads, groups, n = cfg.mamba_num_heads, cfg.n_groups, cfg.ssm_state_size
    inner = heads * cfg.mamba_head_dim
    proj = rms(x, p["attn_norm"]) @ p["ssm_in"]
    z, xbc, dt = jnp.split(proj, [inner, proj.shape[-1] - heads], axis=-1)
    conv = sum(
        p["ssm_conv_w"][:, j] * jnp.pad(
            xbc, ((0, 0), (3 - j, 0), (0, 0)))[:, :s] for j in range(4))
    xbc = jax.nn.silu(conv + p["ssm_conv_b"])
    xs = xbc[..., :inner].reshape(b, s, heads, -1)
    B, C = (
        jnp.repeat(part.reshape(b, s, groups, n), heads // groups, axis=2)
        for part in jnp.split(xbc[..., inner:], 2, axis=-1))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    rate = -jnp.exp(p["A_log"])

    def step(state, at):
        x_t, b_t, c_t, dt_t = at
        state = jnp.exp(rate * dt_t)[..., None, None] * state + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt_t, x_t, b_t)
        return state, jnp.einsum(
            "bhpn,bhn->bhp", state, c_t) + p["D"][:, None] * x_t

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, cfg.mamba_head_dim, n)),
        tuple(jnp.moveaxis(a, 1, 0) for a in (xs, B, C, dt)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, inner) * jax.nn.silu(z)
    o = o.reshape(b, s, groups, -1)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
    return x + (o.reshape(b, s, inner) * p["ssm_norm"]) @ p["ssm_out"]


def one_block(cfg, kind, x, p):
    operate = llama._operator_of(
        cfg, lambda q, k, v: llama.flash_attention(q, k, v, causal=True),
        kind)
    return llama._block(cfg, x, p, None, None, operate, kind=kind)


def drawn(layer):
    """A layer's biases, scales and ``D`` drawn, where the program
    starts them at zero and one."""
    keys = iter(jax.random.split(jax.random.key(4), 8))
    out = dict(layer)
    for name in ("ssm_conv_b", "expert_bias"):
        if name in out:
            out[name] = 0.3 * jax.random.normal(next(keys), out[name].shape)
    for name in ("ssm_norm", "D", "attn_norm", "mlp_norm"):
        if name in out:
            out[name] = out[name] * jax.random.uniform(
                next(keys), out[name].shape, minval=0.5, maxval=1.5)
    return out


def test_a_mixer_block_is_the_equations():
    cfg = config()
    p = drawn(llama._init_layers(jax.random.key(2), cfg, MIXER))
    x = jax.random.normal(jax.random.key(3), (2, 96, 64))
    got, aux, counts = one_block(cfg, MIXER, x, p)
    want = mixer_by_hand(cfg, x, p)
    assert float(aux) == 0.0 and counts is None
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    # one norm group for the four is another function
    one_group = one_block(dataclasses.replace(cfg, n_groups=1), MIXER, x, {
        **p, "ssm_in": p["ssm_in"][:, :128 + 128 + 32 + 8],
        "ssm_conv_w": p["ssm_conv_w"][:160],
        "ssm_conv_b": p["ssm_conv_b"][:160]})[0]
    assert one_group.shape == got.shape


def test_an_attention_block_and_an_expert_block_have_one_branch():
    cfg = config()
    x = jax.random.normal(jax.random.key(3), (2, 64, 64))
    p = drawn(llama._init_layers(jax.random.key(2), cfg, ATTENTION))
    got, aux, _ = one_block(cfg, ATTENTION, x, p)
    y = rms(x, p["attn_norm"])
    q, k, v = (
        (y @ p[w]).reshape(2, 64, heads, 16)
        for w, heads in (("wq", 4), ("wk", 2), ("wv", 2)))
    from dlrover_tpu.ops.attention import mha_reference

    want = x + mha_reference(q, k, v, causal=True).reshape(2, 64, -1) @ p["wo"]
    assert float(jnp.abs(got - want).max()) < 1e-4 and float(aux) == 0.0

    p = drawn(llama._init_layers(jax.random.key(2), cfg, EXPERTS))
    got, aux, _ = one_block(cfg, EXPERTS, x, p)
    y = rms(x, p["mlp_norm"])
    score = jax.nn.sigmoid(y @ p["router"])
    _, chosen = jax.lax.top_k(score + p["expert_bias"], 4)
    picked = jnp.take_along_axis(score, chosen, -1)
    picked = 5.0 * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    weights = jnp.einsum("bsk,bske->bse", picked, jax.nn.one_hot(chosen, 16))
    u = y @ p["w_latent_down"]
    routed = sum(
        weights[..., e, None] * (
            jnp.square(jax.nn.relu(u @ p["w_up"][e])) @ p["w_down"][e])
        for e in range(4))
    want = x + routed @ p["w_latent_up"] + jnp.square(
        jax.nn.relu(y @ p["ws_up"])) @ p["ws_down"]
    assert float(jnp.abs(got - want).max()) < 1e-4 and float(aux) > 0.0


def test_every_leaf_but_the_bias_gets_a_gradient():
    cfg = config()
    params = llama.init_params(jax.random.key(0), cfg)
    grads = jax.grad(
        lambda p: llama.next_token_loss(p, batch(cfg), cfg))(params)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        most = float(jnp.abs(g).max())
        assert np.isfinite(most), path
        assert (most == 0.0) == (name == "expert_bias"), (path, most)


def test_the_module_runs_its_own_two_sublayers():
    cfg = config()
    params = llama.init_params(jax.random.key(0), cfg)
    b = batch(cfg)
    main, mtp, aux = llama._losses(params, b, cfg)
    (module,) = params["mtp"]
    for at, leaf in ((0, "wo"), (1, "ws_down")):
        block = list(module["block"])
        block[at] = {**block[at], leaf: 2.0 * block[at][leaf]}
        other = {**params, "mtp": [{**module, "block": block}]}
        main2, mtp2, _ = llama._losses(other, b, cfg)
        assert float(main2) == float(main), leaf
        assert abs(float(mtp2) - float(mtp)) > 1e-4, leaf
    total = llama.next_token_loss(params, b, cfg)
    assert float(total) == pytest.approx(
        float(main + 0.3 * mtp + aux), rel=1e-6)


@pytest.mark.parametrize("remat", ["off", "dots", "dots_attn_out", "minimal"])
def test_the_scopes_are_held_under_every_remat_policy(remat):
    cfg = config(remat=remat, hybrid_override_pattern="ME*")
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(jax.grad(
        lambda p, b: llama.next_token_loss(p, b, cfg))).lower(
            params, (tokens, tokens)).as_text(debug_info=True)
    assert [s for s in SCOPES if s not in text] == []
    for scope in ("attn.full", "moe.shared", "moe.route", "mtp.block"):
        assert scope in text, scope
    want = jax.jit(lambda p, b: llama.next_token_loss(p, b, cfg))
    real = llama.init_params(jax.random.key(0), cfg)
    assert float(want(real, batch(cfg))) == pytest.approx(
        float(llama.next_token_loss(
            real, batch(cfg), dataclasses.replace(cfg, remat="off"))),
        abs=2e-5)


def test_a_config_without_the_pattern_has_none_of_the_scopes():
    cfg = llama.llama_linear_tiny(dtype=jnp.float32)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(jax.grad(
        lambda p, b: llama.next_token_loss(p, b, cfg))).lower(
            params, (tokens, tokens)).as_text(debug_info=True)
    assert [s for s in SCOPES if s in text] == []


def test_the_stats_read_the_expert_layers_and_the_mixers():
    cfg = config()
    params = llama.init_params(jax.random.key(0), cfg)
    tokens, _ = batch(cfg)
    counts = llama.routing_stats(params, tokens, cfg)
    assert counts.shape == (5, 16)  # the stack's five expert layers
    assert (np.asarray(counts).sum(axis=1) == 2 * 128 * 4).all()
    least = np.asarray(llama.decay_min(params, tokens, cfg))
    assert least.shape == (11,)
    mixers = [i for i, c in enumerate(PATTERN) if c == "M"]
    assert (least[[i for i in range(11) if i not in mixers]] == 1.0).all()
    assert (0.0 < least[mixers]).all() and (least[mixers] < 1.0).all()
    assert llama.set_decay_min_gauge(least, "ssm_decay_min") == float(
        least.min())
    from dlrover_tpu.telemetry.registry import gauge

    assert gauge("ssm_decay_min", "").value == float(least.min())


def test_flops_count_the_two_matrices_of_a_latent_expert():
    cfg = config(mtp_layers=0, mtp_hybrid_override_pattern=None)
    h, vocab = 64, 256
    mixer = 64 * (128 + 128 + 128 + 8) + 128 * 64
    attention = 2 * 64 * 64 + 2 * 64 * 32
    met = 4 * 4 / 16
    experts = (64 * 16 + 2 * 64 * 32 + 2 * 64 * 48 + met * 2 * 32 * 24)
    small = 5 * (64 + 128 + 256 * 4 + 256 + 3 * 8) + 64 + 5 * (64 + 16) + h
    want = 6 * (5 * mixer + attention + 5 * experts + h * vocab + small) + (
        6 * 4 * 32 * 128)
    assert llama.flops_per_token(cfg, 128) == pytest.approx(want, rel=1e-12)


def test_what_the_config_refuses():
    with pytest.raises(ValueError, match="names \\['-'\\]"):
        config(hybrid_override_pattern="ME-EMEM*EME")
    with pytest.raises(ValueError, match="a character a layer"):
        config(num_layers=10, rope_layout=(0,) * 10)
    for beside in (dict(post_norms=True), dict(num_dense_layers=1),
                   dict(moe_bias_update_rate=1e-3),
                   dict(layer_types=("full_attention",) * 11)):
        with pytest.raises(ValueError, match="hybrid_override_pattern"):
            config(**beside)
    with pytest.raises(ValueError, match="gives it no heads"):
        config(mamba_num_heads=6)
    with pytest.raises(ValueError, match="gives it no expert"):
        config(num_experts=0, use_expert_bias=False, moe_experts_held=0)
    with pytest.raises(ValueError, match="mtp_hybrid_override_pattern"):
        llama.llama_tiny(mtp_hybrid_override_pattern="*E")
    with pytest.raises(ValueError, match="mtp_layers"):
        config(mtp_layers=2)
    with pytest.raises(ValueError, match="moe_expert_act"):
        config(moe_expert_act="gelu")
    # experts over an ``expert`` mesh axis: the dropless path's only
    with pytest.raises(ValueError, match="one-branch"):
        llama._expert_mlp(config(moe_experts_held=16), True)
