"""What models/llama.py gained for a delta-rule/full-attention hybrid:
the operator ``linear_attention`` (the gated delta rule behind four-tap
convolutions, a decay for every key channel, an RMSNorm a head and a
low-rank sigmoid gate on its result), its leaves in the stacks by
position, a sigmoid gate on full attention; and that a config with
none of it keeps the tree and the draws it always had."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops import delta_rule, kda_conv
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.ops.pallas import delta_rule as kernels
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

from .test_kda_conv import _calls as _conv_calls

REMATS = ("off", "dots", "dots_attn_out", "minimal")
PERIOD = ("full_attention",) + ("linear_attention",) * 3
SCOPES = ("kda.proj", "kda.conv", "kda.decay", "kda.scan", "kda.out",
          "attn.gate", "attn.full")
LINEAR_LEAVES = {
    "wq", "wk", "wv", "wo", "f_a", "f_b", "g_a", "g_b", "g_bias",
    "w_beta", "A_log", "dt_bias", "conv_q", "conv_k", "conv_v", "o_norm",
}


def _linear(**kw):
    """Eight layers, [full, linear, linear, linear] twice: 4 query
    heads on 2 of 16 without positions and with a gate; 4 linear heads
    of 16; 16 experts of which the first 4 are held, top-4 by sigmoid
    score plus a bias, and a shared one."""
    kw = {**dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=24, num_layers=8, layer_types=PERIOD * 2,
        rope_layout=(0,) * 8, num_heads=4, num_kv_heads=2, head_dim=16,
        attn_out_gate=True, linear_num_heads=4, linear_head_dim=16,
        linear_gate_rank=16, linear_allow_neg_eigval=True, max_seq_len=32,
        dtype=jnp.float32, remat="off", num_experts=16, moe_top_k=4,
        moe_capacity_factor=0.0, router_z_loss_coef=0.0,
        moe_gate="sigmoid", use_expert_bias=True, moe_experts_held=4,
        moe_topk_norm_eps=1e-20, moe_shared_experts=1, embed_init_std=0.1,
    ), **kw}
    return llama.LlamaConfig(**kw)


def _init(cfg, seed=0):
    return llama.init_params(jax.random.key(seed), cfg)


def _batch(cfg, shape=(2, 32), seed=1):
    tokens = jax.random.randint(
        jax.random.key(seed), shape, 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def test_the_plan_is_the_period_and_positions_own_their_leaves():
    cfg = _linear()
    lead, period = cfg.layer_plan()
    assert lead == ()
    assert [k.operator for k in period] == list(PERIOD)
    assert all(k.ffn == "experts" and not k.rope and k.window is None
               for k in period)
    assert cfg.by_position
    params = _init(cfg)
    assert set(params) == {
        "embed", "final_norm", "lead", "period", "lm_head"}
    assert params["lead"] == []
    full, *linear = params["period"]
    assert {"wq", "wk", "wv", "wo", "wg"} <= set(full)
    assert not set(full) & {"f_a", "A_log", "conv_q", "o_norm"}
    experts = {"router", "expert_bias", "w_gate", "w_up", "w_down",
               "ws_gate", "ws_up", "ws_down", "attn_norm", "mlp_norm"}
    for stack in linear:
        assert set(stack) == LINEAR_LEAVES | experts
        assert stack["wq"].shape == (2, 64, 64)  # two periods
        assert stack["f_a"].shape == (2, 64, 16)
        assert stack["f_b"].shape == (2, 16, 64)
        assert stack["w_beta"].shape == (2, 64, 4)
        assert stack["conv_k"].shape == (2, 64, 4)
        assert stack["A_log"].shape == (2, 4)
        assert stack["dt_bias"].shape == (2, 64)
        assert stack["o_norm"].shape == (2, 16)
        for name in ("A_log", "dt_bias", "g_bias", "o_norm"):
            assert stack[name].dtype == jnp.float32
    assert full["wg"].shape == (2, 64, 64)
    assert llama.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    axes = llama.param_axes(cfg)
    for stack, names in zip(params["period"], axes["period"]):
        assert set(stack) == set(names)
        for name, leaf in stack.items():
            assert len(names[name]) == leaf.ndim, name


def test_the_decays_draws_are_the_familys():
    """``exp(A_log)`` in [1, 16), ``softplus(dt_bias)`` in [0.001,
    0.1]: at a pre-activation of zero a channel forgets between a
    thousandth and 1.6 a step."""
    cfg = _linear(linear_num_heads=64, linear_head_dim=16,
                  hidden_size=64)
    stack = _init(cfg)["period"][1]
    rate = np.exp(np.asarray(stack["A_log"]))
    assert 1.0 <= rate.min() < 2.5 and 12.0 < rate.max() < 16.0
    dt = np.asarray(jax.nn.softplus(stack["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() < 2e-3 and 0.05 < dt.max() <= 0.1001
    assert not np.asarray(stack["g_bias"]).any()
    assert (np.asarray(stack["o_norm"]) == 1).all()


def test_a_config_without_the_keys_keeps_its_tree_and_its_draws():
    """Every older leaf draws what it drew: the new leaves draw from
    keys folded with numbers of their own."""
    plain = llama.llama_moe_tiny(dtype=jnp.float32)
    params = _init(plain)
    assert set(params) == {"embed", "final_norm", "blocks", "lm_head"}
    gated = _init(dataclasses.replace(plain, attn_out_gate=True))
    for name, leaf in params["blocks"].items():
        np.testing.assert_array_equal(leaf, gated["blocks"][name])
    assert set(gated["blocks"]) - set(params["blocks"]) == {"wg"}
    # in a stack by position too: the gate draws from a key of its own
    cfg = _linear()
    mine, ungated = _init(cfg), _init(
        dataclasses.replace(cfg, attn_out_gate=False))
    assert set(mine["period"][0]) - set(ungated["period"][0]) == {"wg"}
    for stack, other in zip(ungated["period"], mine["period"]):
        for name, leaf in stack.items():
            np.testing.assert_array_equal(leaf, other[name])
    for preset in (llama.llama_tiny, llama.llama_latent_tiny):
        old = preset(dtype=jnp.float32)
        assert "linear_attention" not in (old.layer_types or ())
        tree = jax.eval_shape(lambda: _init(old))
        names = {
            path[-1].key for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]
            if hasattr(path[-1], "key")
        }
        assert not names & (LINEAR_LEAVES - {"wq", "wk", "wv", "wo"})
        assert "wg" not in names


def _operator_by_hand(cfg, y, p):
    """A linear-attention layer's operator from the normed stream, the
    recurrence a position at a time."""
    b, s, _ = y.shape
    heads, d = cfg.linear_num_heads, cfg.linear_head_dim

    def conv_silu(x, w):
        out = jnp.zeros_like(x)
        for j in range(w.shape[1]):
            back = w.shape[1] - 1 - j
            out = out + w[:, j] * jnp.pad(
                x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        return jax.nn.silu(out).reshape(b, s, heads, d)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(conv_silu(y @ p["wq"], p["conv_q"]))
    k = unit(conv_silu(y @ p["wk"], p["conv_k"]))
    v = conv_silu(y @ p["wv"], p["conv_v"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        y @ p["f_a"] @ p["f_b"] + p["dt_bias"]).reshape(b, s, heads, d)
    beta = 2 * jax.nn.sigmoid(y @ p["w_beta"])
    state = jnp.zeros((b, heads, d, d))
    rows = []
    for t in range(s):
        state = state * jnp.exp(g[:, t])[..., None]
        held = jnp.einsum("bhk,bhkv->bhv", k[:, t], state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", beta[:, t][..., None] * k[:, t],
            v[:, t] - held)
        rows.append(jnp.einsum("bhk,bhkv->bhv", q[:, t], state) / math.sqrt(d))
    o = llama.rms_norm(jnp.stack(rows, 1), p["o_norm"], cfg.norm_eps)
    gate = jax.nn.sigmoid(y @ p["g_a"] @ p["g_b"] + p["g_bias"])
    return (o.reshape(b, s, -1) * gate) @ p["wo"]


def _drawn(params):
    """``params`` with the leaves that start at one or zero drawn: the
    heads' norm's scale, the gate's bias, the selection bias."""
    keys = iter(jax.random.split(jax.random.key(5), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name in ("g_bias", "expert_bias"):
            return 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name == "o_norm":
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_the_operator_is_the_equations():
    cfg = _linear()
    p = jax.tree.map(lambda a: a[1], _drawn(_init(cfg))["period"][2])
    y = jax.random.normal(jax.random.key(3), (2, 32, 64))
    kind = cfg.layer_plan()[1][2]
    operands, logits = llama._pre_attn(
        cfg, y, dict(p, attn_norm=jnp.ones(64)), None, None, kind=kind)
    assert logits is None
    normed = llama.rms_norm(y, jnp.ones(64), cfg.norm_eps)
    got = llama._operator_out(
        y, llama._operator_of(cfg, None, kind)(*operands), p, kind,
        cfg.norm_eps)
    want = _operator_by_hand(cfg, normed, p)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    # the step size reaches past one, and every log decay is negative
    assert 1.0 < float(operands[4].max()) < 2.0
    assert float(operands[3].max()) < 0
    # the convolutions' entry alone: four taps a position at a time,
    # silu, and with heads each head's 16 columns over their length
    x, w = normed @ p["wk"], np.asarray(p["conv_k"])
    a = np.zeros(x.shape, np.float32)
    for pos in range(32):
        for j in range(4):
            if pos - 3 + j >= 0:
                a[:, pos] += w[:, j] * np.asarray(x)[:, pos - 3 + j]
    s = a / (1 + np.exp(-a))
    np.testing.assert_allclose(
        kda_conv.conv_silu_norm(x, p["conv_k"]), s, rtol=1e-5, atol=1e-6)
    heads = s.reshape(2, 32, 4, 16)
    unit = heads / np.sqrt((heads * heads).sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(
        kda_conv.conv_silu_norm(x, p["conv_k"], l2_heads=4),
        unit.reshape(2, 32, 64), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(operands[1], unit.reshape(2, 32, 64),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="taps of 64 channels"):
        kda_conv.conv_silu_norm(x[..., :32], p["conv_k"])


def test_the_gate_on_attention_is_elementwise_from_the_normed_input():
    cfg = _linear()
    p = jax.tree.map(lambda a: a[0], _init(cfg)["period"][0])
    y = jax.random.normal(jax.random.key(3), (2, 32, 64))
    kind = cfg.layer_plan()[1][0]
    attn = lambda q, k, v: mha_reference(q, k, v, causal=True)  # noqa: E731
    operands, _ = llama._pre_attn(
        cfg, y, dict(p, attn_norm=jnp.ones(64)), None, None, kind=kind)
    got = llama._operator_out(
        y, llama._operator_of(cfg, attn, kind)(*operands), p, kind)
    normed = llama.rms_norm(y, jnp.ones(64), cfg.norm_eps)
    q = (normed @ p["wq"]).reshape(2, 32, 4, 16)
    k = (normed @ p["wk"]).reshape(2, 32, 2, 16)
    v = (normed @ p["wv"]).reshape(2, 32, 2, 16)
    a = mha_reference(q, k, v, causal=True).reshape(2, 32, 64)
    want = (jax.nn.sigmoid(normed @ p["wg"]) * a) @ p["wo"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    ungated = dataclasses.replace(cfg, attn_out_gate=False)
    operands, _ = llama._pre_attn(
        ungated, y, dict(p, attn_norm=jnp.ones(64)), None, None, kind=kind)
    assert len(operands) == 3


def _loop_over_layers(params, batch, cfg):
    """The loss with the layers walked one by one, no scan."""
    tokens, targets = batch
    _, period = cfg.layer_plan()
    attn = lambda q, k, v: mha_reference(q, k, v, causal=True)  # noqa: E731
    x, aux = params["embed"][tokens], 0.0
    for l in range(cfg.num_layers):
        kind = period[l % len(period)]
        p = jax.tree.map(
            lambda a: a[l // len(period)], params["period"][l % len(period)])
        x, layer_aux, _ = llama._block(
            cfg, x, p, None, None, llama._operator_of(cfg, attn, kind),
            kind=kind)
        aux = aux + layer_aux
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return llama._mean_ce(x, params["lm_head"], targets, 0) + aux


@pytest.mark.parametrize("remat", REMATS)
def test_the_scanned_period_against_a_loop_over_layers(remat):
    cfg = _linear(remat=remat)
    params = _drawn(_init(cfg))
    batch = _batch(cfg)
    want, want_g = jax.jit(
        jax.value_and_grad(_loop_over_layers), static_argnums=2
    )(params, batch, dataclasses.replace(cfg, remat="off"))
    got, got_g = jax.jit(
        jax.value_and_grad(llama.next_token_loss), static_argnums=2
    )(params, batch, cfg)
    assert abs(float(got) - float(want)) < 1e-5
    # a log decay that the two programs round to either side of the
    # scan's floor gets its gradient in one and none in the other
    # (ops/delta_rule.py G_FLOOR): what it can carry there, exp(-10)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=1e-5)
    for name in ("A_log", "dt_bias", "w_beta", "conv_k", "f_b", "g_bias",
                 "o_norm"):
        assert float(jnp.abs(got_g["period"][1][name]).max()) > 0, name
    assert float(jnp.abs(got_g["period"][0]["wg"]).max()) > 0
    # one scan over the two periods
    text = str(jax.make_jaxpr(
        lambda p: llama.next_token_loss(p, batch, cfg))(params))
    assert len(re.findall(r"length=2\b", text)) >= 1
    assert "length=8" not in text


def test_the_model_runs_the_kernels_where_they_tile(monkeypatch):
    """Heads of 128 and whole chunks: with the dispatch a TPU process
    takes (the kernels in interpret mode here) the loss and every
    gradient are the plain path's."""
    cfg = _linear(num_layers=4, layer_types=PERIOD, rope_layout=(0,) * 4,
                  linear_num_heads=2, linear_head_dim=128, max_seq_len=128)
    params = _init(cfg)
    batch = _batch(cfg, shape=(1, 128))
    step = jax.value_and_grad(llama.next_token_loss)
    want, want_g = step(params, batch, cfg)
    calls, convs = [], []
    monkeypatch.setattr(
        delta_rule, "_use_pallas",
        lambda q, heads: calls.append((q.shape, heads)) or True)
    monkeypatch.setattr(
        kda_conv, "_use_pallas",
        lambda x, w, l2_heads: convs.append(
            (x.shape, w.shape, l2_heads)) or True)
    before = _conv_calls()
    got, got_g = step(params, batch, cfg)
    # rows, as the projections wrote them
    assert calls and set(calls) == {((1, 128, 256), 2)}
    # q and k with their heads, v without, at each of three layers;
    # every one on the kernels' path
    assert sorted(convs, key=str) == sorted(
        3 * [((1, 128, 256), (256, 4), 2)] * 2
        + 3 * [((1, 128, 256), (256, 4), None)], key=str)
    assert _conv_calls() == (before[0] + 9, before[1])
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-6)


def _calls():
    from dlrover_tpu.telemetry.registry import counter

    labels = ("decay", "head")  # ops/pallas/delta_rule.py CALL_LABELS
    return tuple(
        counter(f"delta_rule_{handed}_calls", "", labels).labels(
            decay="channel", head="128x128").value
        for handed in ("rows", "folded"))


def test_the_steps_scans_are_handed_rows(monkeypatch):
    """The cell's step in small (one period: a full-attention layer
    and three delta-rule layers, heads of 128, the kernels where a
    TPU process takes them): under ``minimal`` each delta-rule layer
    builds the forward, the forward again that keeps the entry states
    and the backward, nine Pallas calls, every one on rows and none on
    heads that were folded; and no value of the step's program, as
    traced, has a head of the delta rule as an axis of its own outside
    the norms' view ``[b, s / 8, 8, heads, d]``."""
    cfg = _linear(num_layers=4, layer_types=PERIOD, rope_layout=(0,) * 4,
                  linear_num_heads=2, linear_head_dim=128, max_seq_len=128,
                  remat="minimal")
    monkeypatch.setattr(delta_rule, "_use_pallas", lambda q, heads: True)
    before = _calls()
    text = str(jax.make_jaxpr(
        jax.grad(llama.next_token_loss), static_argnums=2
    )(_init(cfg), _batch(cfg, shape=(1, 128)), cfg))
    assert _calls() == (before[0] + 9, before[1])
    assert "[1,16,8,2,128]" in text
    assert "[1,128,2,128]" not in text


@pytest.mark.parametrize("seq", [32, 30], ids=["tiles", "no tiles"])
@pytest.mark.parametrize("norm", ["l2", "rms"])
def test_a_heads_norm_in_rows_is_the_norm_on_heads(norm, seq):
    """``heads_apart``'s view under ``l2norm`` and ``rms_norm``
    against the same on ``[b, s, heads, d]``, the result and the
    gradients to 1e-6; a sequence that 8 does not divide is viewed
    position by position."""
    heads, d = 4, 16
    x = jax.random.normal(jax.random.key(3), (2, seq, heads * d))
    scale = jax.random.uniform(jax.random.key(4), (d,), minval=0.5,
                               maxval=1.5)
    cotangent = jax.random.normal(jax.random.key(5), x.shape)
    assert kda_conv.heads_apart(x, heads).shape == (
        (2, 4, 8, heads, d) if seq == 32 else (2, 30, 1, heads, d))

    def normed(view):
        def fn(x, scale):
            y = view(x)
            y = (kda_conv.l2norm(y) if norm == "l2"
                 else llama.rms_norm(y, scale, 1e-5))
            return jnp.sum(y.reshape(x.shape) * cotangent)

        return jax.value_and_grad(fn, argnums=(0, 1))(x, scale)

    want, want_g = normed(lambda x: x.reshape(2, seq, heads, d))
    got, got_g = normed(lambda x: kda_conv.heads_apart(x, heads))
    assert abs(float(got) - float(want)) < 1e-6 * seq
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_the_operators_operands_are_rows():
    cfg = _linear()
    p = jax.tree.map(lambda a: a[1], _init(cfg)["period"][2])
    y = jax.random.normal(jax.random.key(3), (2, 32, 64))
    kind = cfg.layer_plan()[1][2]
    (q, k, v, g, beta, gate), _ = llama._pre_attn(
        cfg, y, dict(p, attn_norm=jnp.ones(64)), None, None, kind=kind)
    for x in (q, k, v, g, gate):
        assert x.shape == (2, 32, 4 * 16)
    assert beta.shape == (2, 32, 4) and g.dtype == jnp.float32
    # a head's 16 columns of q and of k are of unit length
    np.testing.assert_allclose(
        jnp.sum(q.reshape(2, 32, 4, 16) ** 2, -1), 1.0, atol=1e-4)
    o, passed = llama._operator_of(cfg, None, kind)(q, k, v, g, beta, gate)
    assert o.shape == (2, 32, 64) and passed is gate


def test_every_new_op_carries_its_scope(monkeypatch):
    cfg = _linear()
    text = jax.jit(llama.next_token_loss, static_argnums=2).lower(
        _init(cfg), _batch(cfg), cfg).as_text(debug_info=True)
    for scope in SCOPES + ("moe.route", "moe.shared"):
        assert scope in text, scope
    plain = llama.llama_moe_tiny(dtype=jnp.float32)
    text = jax.jit(llama.next_token_loss, static_argnums=2).lower(
        _init(plain), _batch(plain), plain).as_text(debug_info=True)
    for scope in SCOPES[:-1]:
        assert scope not in text, scope
    # where the kernels take the convolutions, the jitted name that a
    # device trace calls them by stands under ``kda.conv`` wherever
    # the lowered step names it, as the plain ops did
    tiled = _linear(num_layers=4, layer_types=PERIOD, rope_layout=(0,) * 4,
                    linear_num_heads=2, linear_head_dim=128,
                    max_seq_len=128)
    monkeypatch.setattr(kda_conv, "_use_pallas", lambda x, w, h: True)
    text = jax.jit(jax.grad(llama.next_token_loss), static_argnums=2).lower(
        _init(tiled), _batch(tiled, shape=(1, 128)), tiled
    ).as_text(debug_info=True)
    named = re.findall(r'loc\("([^"]*jit\(kda_conv\)[^"]*)"', text)
    assert named and all("kda.conv" in name for name in named), named


def test_routing_stats_walk_both_kinds_of_layer():
    cfg = _linear()
    params, (tokens, _) = _init(cfg), _batch(cfg)
    counts = np.asarray(llama.routing_stats(params, tokens, cfg))
    assert counts.shape == (8, 16)
    assert (counts.sum(axis=1) == tokens.size * 4).all()


def test_the_trainer_steps_and_every_new_leaf_moves():
    cfg = _linear()
    mesh = create_mesh([("data", 4), ("fsdp", 2)])
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy="fsdp", optimizer=optax.adamw(1e-2))
    params, opt_state = trainer.init(jax.random.key(0))
    before = jax.tree.map(np.asarray, params)
    tokens, targets = _batch(cfg, shape=(8, 32))
    mb = trainer.microbatch((np.asarray(tokens), np.asarray(targets)))
    losses = []
    for _ in range(3):
        params, opt_state, loss = trainer.train_step(params, opt_state, mb)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(
        params["period"][1]["expert_bias"],
        before["period"][1]["expert_bias"])
    for name in LINEAR_LEAVES:
        assert float(jnp.abs(
            params["period"][1][name] - before["period"][1][name]
        ).max()) > 0, name
    assert float(jnp.abs(
        params["period"][0]["wg"] - before["period"][0]["wg"]).max()) > 0


def test_decay_min_reads_the_least_alpha_a_layer():
    from dlrover_tpu.telemetry.registry import gauge

    cfg = _linear()
    params, (tokens, _) = _init(cfg), _batch(cfg)
    least = np.asarray(jax.jit(
        lambda p, t: llama.decay_min(p, t, cfg))(params, tokens))
    assert least.shape == (8,)
    assert (least[[0, 4]] == 1.0).all()  # the attention layers
    linear = least[[1, 2, 3, 5, 6, 7]]
    assert ((linear > 0) & (linear < 1)).all()
    # by hand at one layer: exp of the least log decay
    p = jax.tree.map(lambda a: a[0], params["period"][1])
    x = params["embed"][tokens]
    kind = cfg.layer_plan()[1][0]
    attn = lambda q, k, v: mha_reference(q, k, v, causal=True)  # noqa: E731
    x, _, _ = llama._block(
        cfg, x, jax.tree.map(lambda a: a[0], params["period"][0]), None,
        None, llama._operator_of(cfg, attn, kind), kind=kind)
    y = llama.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        y @ p["f_a"] @ p["f_b"] + p["dt_bias"]).reshape(2, 32, 4, 16)
    assert least[1] == pytest.approx(float(jnp.exp(g.min())), rel=1e-4)
    assert llama.set_decay_min_gauge(least) == pytest.approx(least.min())
    assert gauge("kda_decay_min", "").value == pytest.approx(least.min())
    plain = llama.llama_moe_tiny(dtype=jnp.float32)
    assert (np.asarray(llama.decay_min(
        _init(plain), _batch(plain)[0], plain)) == 1.0).all()


def test_the_preset_is_the_period():
    cfg = llama.llama_linear_tiny()
    assert [k.operator for k in cfg.layer_plan()[1]] == list(PERIOD)
    assert cfg.attn_out_gate and cfg.linear_allow_neg_eigval
    loss = llama.next_token_loss(_init(cfg), _batch(cfg), cfg)
    assert bool(jnp.isfinite(loss))


def test_tiles_of_the_4096_by_1280_experts():
    """Contraction 4096 and columns 1280, and the reverse: the largest
    multiples of 128 that divide them within the caps; the in-place
    float32 sum's face within ``IN_PLACE_TILE`` (1024 x 640 is past
    it), the longer contraction among equals."""
    from dlrover_tpu.ops.grouped_matmul import IN_PLACE_TILE, tiles

    assert tiles(5120, 4096, 1280) == (512, 1024, 640)
    assert tiles(5120, 1280, 4096) == (512, 640, 1024)
    assert 1024 * 640 > IN_PLACE_TILE
    assert tiles(5120, 4096, 1280, most=IN_PLACE_TILE) == (512, 512, 640)
    assert tiles(5120, 1280, 4096, most=IN_PLACE_TILE) == (512, 640, 512)
    # the rows' sum into their tokens: 512 indices a group, 4096 wide
    assert tiles(5120, 512, 4096) == (512, 512, 1024)


def test_flops_per_token_counts_the_projections_and_no_scores():
    cfg = _linear()
    h, met = 64, 4 * 4 / 16
    linear = (4 * h * 64 + 2 * 16 * (h + 64) + h * 4  # matrices
              + 3 * 64 * 4 + 2 * 64 + 4 + 16)  # taps and vectors
    attention = 3 * h * 64 + 2 * h * 32
    experts = h * 16 + 16 + (1 + met) * 3 * h * 24
    n = (6 * linear + 2 * attention + 8 * experts + 8 * 2 * h + h
         + 128 * h)
    # scores and weighted values in the two attention layers alone
    assert llama.flops_per_token(cfg, 32) == (
        6.0 * n + 6 * 4 * (16 + 16) * 2 * 32)


@pytest.mark.parametrize("change,sentence", [
    (dict(layer_types=PERIOD), "4 entries for 8 layers"),
    (dict(linear_num_heads=0), "linear_num_heads"),
    (dict(layer_types=("state_space",) + PERIOD[1:] + PERIOD),
     "state_space"),
])
def test_the_config_refuses_what_it_cannot_run(change, sentence):
    with pytest.raises(ValueError, match=sentence):
        _linear(**change)
