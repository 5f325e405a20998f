"""models/llama.py's ``gated_delta_net`` operator (the gated delta rule
with one decay a head on heads of two widths) and a block whose norms
stand on its branches' results alone: the leaves, axes and draws, a
layer through the operator against the recurrence walked a position at
a time, the scopes in every op's name under every remat policy, the
counters' labels, where the block's norms stand, what the config
refuses, and what the trainer says of the model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops import delta_rule
from dlrover_tpu.ops.pallas import delta_rule as scan_kernels

SEQ = 64
SCOPES = ("gdn.proj", "gdn.conv", "gdn.decay", "gdn.scan", "gdn.out",
          "norm.post_attn", "norm.post_mlp", "attn.full")


def gdn_tiny(**kw):
    return llama.llama_gdn_tiny(**{**dict(
        dtype=jnp.float32, remat="off"), **kw})


def batch(cfg, sequences=2, seq=SEQ):
    tokens = jax.random.randint(
        jax.random.key(1), (sequences, seq), 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def test_the_plan_the_leaves_and_the_counts():
    cfg = gdn_tiny()
    lead, period = cfg.layer_plan()
    assert lead == ()
    assert [(k.operator, k.rope, k.window, k.ffn) for k in period] == [
        ("gated_delta_net", False, None, "dense")] * 3 + [
        ("full_attention", False, None, "dense")]
    assert cfg.by_position and "gated_delta_net" in llama.OPERATORS
    assert llama.operator_layers(cfg) == {
        "gated_delta_net": 3, "full_attention": 1}
    params = llama.init_params(jax.random.key(0), cfg)
    linear, full = params["period"][0], params["period"][3]
    # leaves of its own: no low rank, no gate bias, a number a head for
    # the decay's bias; and no norm ahead of either branch
    assert set(linear) == {
        "wq", "wk", "wv", "wg", "wo", "w_a", "w_beta", "conv_q", "conv_k",
        "conv_v", "A_log", "dt_bias", "o_norm", "post_attn_norm",
        "post_mlp_norm", "w_gate", "w_up", "w_down"}
    assert set(full) == {
        "wq", "wk", "wv", "wo", "q_norm", "k_norm", "post_attn_norm",
        "post_mlp_norm", "w_gate", "w_up", "w_down"}
    shapes = {name: leaf.shape[1:] for name, leaf in linear.items()}
    assert (shapes["wq"], shapes["wk"]) == ((64, 72), (64, 72))
    assert (shapes["wv"], shapes["wg"], shapes["wo"]) == (
        (64, 120), (64, 120), (120, 64))
    assert (shapes["w_a"], shapes["w_beta"]) == ((64, 3), (64, 3))
    assert (shapes["conv_q"], shapes["conv_v"]) == ((72, 4), (120, 4))
    assert (shapes["A_log"], shapes["dt_bias"], shapes["o_norm"]) == (
        (3,), (3,), (40,))
    # q and k normed over their whole projections, three heads of 16
    assert full["q_norm"].shape == full["k_norm"].shape == (1, 48)
    for name in ("A_log", "dt_bias", "o_norm", "post_attn_norm"):
        assert linear[name].dtype == jnp.float32, name
    rate = np.exp(np.asarray(linear["A_log"]))
    assert ((rate >= 1) & (rate < 16)).all()
    step = np.log1p(np.exp(np.asarray(linear["dt_bias"])))
    assert ((step >= 1e-3 * 0.999) & (step <= 0.1 * 1.001)).all()
    assert np.asarray(linear["conv_q"]).std() == pytest.approx(0.5, rel=0.2)
    axes = llama.param_axes(cfg)["period"]
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree.structure(params["period"])
    assert axes[0]["wq"] == ("layers", "embed", "heads")
    assert axes[0]["wo"] == ("layers", "heads", "embed")
    assert axes[0]["w_a"] == ("layers", "embed", None)
    assert axes[0]["dt_bias"] == axes[0]["A_log"] == ("layers", "norm")
    assert axes[0]["conv_v"] == ("layers", "heads", None)
    assert llama.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    n = llama.param_count(cfg) - 256 * 64
    assert llama.flops_per_token(cfg, SEQ) == (
        6.0 * n + 6 * 3 * 32 * SEQ)
    assert llama._rope_tables_of(cfg, SEQ) == (None, None)


def by_hand(cfg, p, x):
    """One Gated DeltaNet layer's branch on the stream ``x`` in plain
    lines: the convolutions as sums over shifted copies, the
    recurrence a position at a time, the norm and then ``silu``."""
    heads, eps = cfg.linear_num_value_heads, cfg.norm_eps
    b, s, _ = x.shape

    def conv_silu(a, w):
        out = jnp.zeros_like(a)
        for j in range(w.shape[1]):
            back = w.shape[1] - 1 - j
            out = out + w[:, j] * jnp.pad(
                a, ((0, 0), (back, 0), (0, 0)))[:, :s]
        return jax.nn.silu(out)

    def l2(a):
        a = a.reshape(b, s, heads, -1)
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q = l2(conv_silu(x @ p["wq"], p["conv_q"]))
    k = l2(conv_silu(x @ p["wk"], p["conv_k"]))
    v = conv_silu(x @ p["wv"], p["conv_v"]).reshape(b, s, heads, -1)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(x @ p["w_a"] + p["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(x @ p["w_beta"])

    def step(state, t):
        q_t, k_t, v_t, g_t, beta_t = t
        state = jnp.exp(g_t)[..., None, None] * state
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", beta_t[..., None] * k_t, v_t - held)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, q.shape[-1], v.shape[-1])),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1) / jnp.sqrt(q.shape[-1])
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["o_norm"]
    return (o.reshape(b, s, -1) * jax.nn.silu(x @ p["wg"])) @ p["wo"]


def test_a_layer_through_the_operator_is_the_recurrence():
    """The first layer's block, leaf for leaf: ``x + RMSNorm(mixer(
    x))``, then ``x + RMSNorm(MLP(x))``, no norm ahead of either."""
    cfg = gdn_tiny(embed_init_std=1.0)
    params = llama.init_params(jax.random.key(0), cfg)
    p = jax.tree.map(lambda a: a[0], params["period"][0])
    p["o_norm"] = p["o_norm"] * jnp.linspace(0.5, 1.5, 40)
    x = params["embed"][batch(cfg)[0]]
    kind = cfg.layer_plan()[1][0]
    got, _, _ = llama._block(
        cfg, x, p, None, None, llama._operator_of(cfg, None, kind),
        kind=kind)
    mid = x + llama.rms_norm(by_hand(cfg, p, x), p["post_attn_norm"], 1e-6)
    mlp = (jax.nn.silu(mid @ p["w_gate"]) * (mid @ p["w_up"])) @ p["w_down"]
    want = mid + llama.rms_norm(mlp, p["post_mlp_norm"], 1e-6)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(want - x).max()) > 0.1


@pytest.mark.parametrize("post_norms,leaves", [
    (False, {"attn_norm", "mlp_norm"}),
    (True, {"attn_norm", "mlp_norm", "post_attn_norm", "post_mlp_norm"}),
    ("alone", {"post_attn_norm", "post_mlp_norm"}),
])
def test_where_a_blocks_norms_stand_is_one_field(post_norms, leaves):
    """``post_norms``: ahead of the branches, on both sides, or on the
    results alone: the leaves each place owns, and three different
    functions of the same stream."""
    cfg = llama.llama_tiny(post_norms=post_norms, dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    norms = {n for n in params["blocks"] if n.endswith("_norm")}
    assert norms == leaves
    assert llama.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    loss = float(llama.next_token_loss(params, batch(cfg), cfg))
    others = []
    for other in {False, True, "alone"} - {post_norms}:
        ocfg = llama.llama_tiny(post_norms=other, dtype=jnp.float32)
        oparams = llama.init_params(jax.random.key(0), ocfg)
        others.append(float(llama.next_token_loss(oparams, batch(cfg), ocfg)))
    assert all(abs(loss - o) > 1e-4 for o in others), (loss, others)
    with pytest.raises(ValueError, match="post_norms"):
        llama.llama_tiny(post_norms="before")


def test_the_blocks_norms_ahead_of_the_branches_are_another_model():
    """The same leaves read with the norms ahead of the branches (an
    ``attn_norm`` and an ``mlp_norm`` in the two results' place) give
    another loss: the place is held."""
    cfg = gdn_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    ahead = gdn_tiny(post_norms=False)
    moved = dict(params, period=[
        {{"post_attn_norm": "attn_norm", "post_mlp_norm": "mlp_norm"}.get(
            name, name): leaf for name, leaf in layer.items()}
        for layer in params["period"]])
    a = float(llama.next_token_loss(params, batch(cfg), cfg))
    b = float(llama.next_token_loss(moved, batch(cfg), ahead))
    assert abs(a - b) > 1e-3


@pytest.mark.parametrize("remat", ["off", "dots", "dots_attn_out", "minimal"])
def test_the_scopes_name_every_stage_under_every_remat_policy(remat):
    cfg = gdn_tiny(remat=remat)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    tok = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    text = jax.jit(jax.grad(
        lambda p, t: llama.next_token_loss(p, (t, t), cfg))
    ).lower(params, tok).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    assert "kda." not in text


def test_remat_changes_neither_the_loss_nor_a_gradient():
    losses, grads = [], []
    for remat in ("off", "minimal"):
        cfg = gdn_tiny(remat=remat)
        params = llama.init_params(jax.random.key(0), cfg)
        loss, grad = jax.jit(jax.value_and_grad(
            lambda p: llama.next_token_loss(p, batch(cfg), cfg)))(params)
        losses.append(float(loss))
        grads.append(grad)
    assert abs(losses[0] - losses[1]) < 1e-6
    for a, b in zip(*map(jax.tree.leaves, grads)):
        # two programs' float32 sums through decays down to exp(-40)
        assert float(jnp.abs(a - b).max()) < 3e-4 * float(jnp.abs(a).max())


def _calls():
    from dlrover_tpu.telemetry.registry import counter

    return tuple(
        counter(f"delta_rule_{handed}_calls", "", scan_kernels.CALL_LABELS)
        .labels(decay="head", head="24x40").value
        for handed in ("rows", "folded"))


def test_the_steps_scans_are_handed_rows_and_a_decay_a_head(monkeypatch):
    """Where a TPU process takes the kernels (interpret mode here) the
    three layers' scans are built on rows, none folded, under the
    labels of one decay a head and the head's two widths; the log
    decay the operator is handed is ``[batch, seq, heads]``; the loss
    and every gradient are the plain path's."""
    cfg = gdn_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    want, want_g = jax.value_and_grad(
        lambda p: llama.next_token_loss(p, batch(cfg), cfg))(params)
    seen = []
    entry = delta_rule.gated_delta_rule_rows
    monkeypatch.setattr(
        llama, "gated_delta_rule_rows",
        lambda q, k, v, g, beta, heads: seen.append(
            (q.shape, v.shape, g.shape, g.dtype)) or entry(
                q, k, v, g, beta, heads))
    monkeypatch.setattr(
        delta_rule, "_use_pallas_a_head", lambda q, v, heads: True)
    before = _calls()
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: llama.next_token_loss(p, batch(cfg), cfg)))(params)
    assert seen == [((2, SEQ, 72), (2, SEQ, 120), (2, SEQ, 3),
                     jnp.float32)] * 3
    # a layer: the call as it is traced, the forward that keeps the
    # entry states in its place, and the backward
    assert _calls() == (before[0] + 9, before[1])
    assert abs(float(got) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert float(jnp.abs(a - b).max()) < 3e-4 * float(jnp.abs(b).max())


def test_the_decay_is_not_floored_and_its_gauge_reads_it():
    """``decay_min`` reads a Gated DeltaNet layer's least ``alpha`` a
    head; with the rates drawn up to 16 and an un-normed stream it
    passes ``exp(-10)``, which this operator's path leaves as it is."""
    cfg = gdn_tiny(embed_init_std=1.0)
    params = llama.init_params(jax.random.key(0), cfg)
    least = llama.decay_min(params, batch(cfg)[0], cfg)
    assert least.shape == (4,) and float(least[3]) == 1.0
    assert float(least[:3].min()) < float(np.exp(delta_rule.G_FLOOR))
    loss = float(llama.next_token_loss(params, batch(cfg), cfg))
    assert np.isfinite(loss)


@pytest.mark.parametrize("change,says", [
    (dict(linear_num_key_heads=1), "linear_num_key_heads 1"),
    (dict(linear_num_key_heads=0, linear_num_value_heads=0),
     "linear_num_value_heads 0"),
    (dict(linear_value_head_dim=0), "linear_value_head_dim 0"),
    (dict(layer_types=("gated_delta_net", "linear_attention",
                       "full_attention", "full_attention"),
          linear_num_heads=2), "one stack holds one form"),
    (dict(num_experts=4), "experts"),
    (dict(layer_types=("gated_delta_net",) * 3 + ("delta_net",)),
     "'gated_delta_net'"),
])
def test_what_is_not_built_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        gdn_tiny(**change)


def test_the_trainer_refuses_a_mesh_and_sets_the_operators_gauge():
    from jax.sharding import Mesh

    from dlrover_tpu.telemetry.registry import gauge
    from dlrover_tpu.trainer.sharded import make_trainer_for_llama

    cfg = gdn_tiny()
    assert "gated_delta_net" in llama.ONE_DEVICE_OPERATORS
    make_trainer_for_llama(cfg, Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "fsdp")))
    layers = gauge("dlrover_model_operator_layers", "", ("operator",))
    assert layers.labels(operator="gated_delta_net").value == 3
    assert layers.labels(operator="full_attention").value == 1
    assert layers.labels(operator="linear_attention").value == 0
    if len(jax.devices()) > 1:
        for axes in (("data", "fsdp"), ("data", "seq")):
            with pytest.raises(
                    ValueError, match=r"gated_delta_net.*whole sequences"):
                make_trainer_for_llama(cfg, Mesh(
                    np.array(jax.devices()[:2]).reshape(1, 2), axes))
