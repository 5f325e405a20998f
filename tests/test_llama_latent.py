"""What models/llama.py, parallel/moe.py and the attention ops gained
for latent attention with a second prediction head: two low-rank
projections with a norm between, q and k wider than v with one rotated
key for every head, rotation in neighbouring pairs, a factor on the
routing weights and the source's own 1e-20, a shared expert, and a
prediction module past the stack whose loss joins the step's; and
that a config with none of it keeps the parameter tree and the loss it
always had."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops.attention import mha_reference, whole_q_and_k
from dlrover_tpu.parallel import moe
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

REMATS = ("off", "dots", "dots_attn_out", "minimal")
SCOPES = ("mla.q_down", "mla.kv_down", "mla.up", "attn.latent",
          "moe.shared", "mtp.merge", "mtp.block", "mtp.head")


def _latent(**kw):
    kw = {**dict(
        vocab_size=128, intermediate_size=96, max_seq_len=32,
        dtype=jnp.float32, remat="off", moe_capacity_factor=0.0,
        router_z_loss_coef=0.0, moe_experts_held=4, embed_init_std=0.1,
    ), **kw}
    return llama.llama_latent_tiny(**kw)


def _init(cfg, seed=0):
    return llama.init_params(jax.random.key(seed), cfg)


def _batch(cfg, seed=1, shape=(2, 32)):
    tokens = jax.random.randint(
        jax.random.key(seed), shape, 0, cfg.vocab_size)
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
    return tokens, targets


def test_the_tree_has_the_latent_leaves_and_the_module():
    cfg = _latent()
    params = _init(cfg)
    assert set(params) == {
        "embed", "final_norm", "lead", "period", "lm_head", "mtp"}
    layer = params["period"][0]
    assert layer["wq_a"].shape == (2, 64, 48)
    assert layer["wq_b"].shape == (2, 48, 4 * 24)
    assert layer["wkv_a"].shape == (2, 64, 32 + 8)
    assert layer["wkv_b"].shape == (2, 32, 4 * 32)
    assert layer["wo"].shape == (2, 4 * 16, 64)
    assert layer["q_a_norm"].shape == (2, 48)
    assert layer["kv_a_norm"].shape == (2, 32)
    assert layer["ws_gate"].shape == (2, 64, 32)
    assert layer["w_gate"].shape == (2, 4, 64, 32)  # 4 of 8 held
    assert not {"wq", "wk", "wv"} & set(layer)
    assert "router" not in params["lead"][0]
    (module,) = params["mtp"]
    assert set(module) == {"embed_norm", "hidden_norm", "eh_proj",
                           "block", "final_norm"}
    assert module["eh_proj"].shape == (128, 64)
    assert set(module["block"]) == set(layer)
    assert module["block"]["wq_a"].shape == (64, 48)
    assert llama.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    axes = llama.param_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for leaf, names in zip(
            jax.tree.leaves(params),
            jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))):
        assert leaf.ndim == len(names)
    frozen = llama.frozen_params(cfg)
    assert frozen["mtp"][0]["block"]["expert_bias"] is True
    assert frozen["mtp"][0]["eh_proj"] is False
    assert sum(jax.tree.leaves(frozen)) == 2


def test_a_config_without_the_keys_keeps_its_tree_and_its_draws():
    """The eight draws a layer had are what they were: the new leaves
    draw from keys of their own."""
    plain = llama.llama_moe_tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), plain)
    assert set(params) == {"embed", "final_norm", "blocks", "lm_head"}
    shared = dataclasses.replace(plain, moe_shared_experts=1)
    more = llama.init_params(jax.random.key(0), shared)
    for name, leaf in params["blocks"].items():
        np.testing.assert_array_equal(leaf, more["blocks"][name])
    assert set(more["blocks"]) - set(params["blocks"]) == {
        "ws_gate", "ws_up", "ws_down"}


def _latent_attention_by_hand(cfg, y, p):
    """The equations, a head at a time."""
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    s = y.shape[1]
    c_q = llama.rms_norm(y @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
    down = y @ p["wkv_a"]
    c_kv = llama.rms_norm(
        down[..., :cfg.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps)
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, rope, 2) / rope)
    angles = jnp.arange(s)[:, None] * inv[None, :]

    def turn(x):  # [b, s, rope]: columns (2i, 2i + 1) by angle i
        even, odd = x[..., 0::2], x[..., 1::2]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        return jnp.stack(
            [even * cos - odd * sin, odd * cos + even * sin], axis=-1
        ).reshape(x.shape)

    k_rope = turn(down[..., cfg.kv_lora_rank:])
    q_all = (c_q @ p["wq_b"]).reshape(*y.shape[:2], cfg.num_heads, -1)
    kv_all = (c_kv @ p["wkv_b"]).reshape(*y.shape[:2], cfg.num_heads, -1)
    mask = jnp.tril(jnp.ones((s, s), bool))
    heads = []
    for n in range(cfg.num_heads):
        q, kv = q_all[:, :, n], kv_all[:, :, n]
        scores = (
            jnp.einsum("bqd,bkd->bqk", q[..., :nope], kv[..., :nope])
            + jnp.einsum("bqd,bkd->bqk", turn(q[..., nope:]), k_rope)
        ) * (nope + rope) ** -0.5
        weights = jax.nn.softmax(
            jnp.where(mask, scores, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bqk,bkd->bqd", weights, kv[..., nope:]))
        assert heads[-1].shape[-1] == vd
    return jnp.concatenate(heads, axis=-1) @ p["wo"]


def test_latent_attention_is_the_equations():
    cfg = _latent()
    p = _init(cfg)["lead"][0]
    keys = jax.random.split(jax.random.key(4), 3)
    p = dict(
        p,
        q_a_norm=jax.random.uniform(keys[0], p["q_a_norm"].shape, minval=0.5,
                                    maxval=1.5),
        kv_a_norm=jax.random.uniform(keys[1], p["kv_a_norm"].shape,
                                     minval=0.5, maxval=1.5),
    )
    y = jax.random.normal(keys[2], (2, 32, 64))
    cos, sin = llama.rope_tables(32, cfg.rope_dim, cfg.rope_theta)
    q, k, v, q_rope, k_rope = llama._latent_qkv(cfg, y, p, cos, sin)
    assert q.shape == k.shape == v.shape == (2, 32, 4, 16)
    # one rotated key for every head
    assert q_rope.shape == (2, 32, 4, 8) and k_rope.shape == (2, 32, 1, 8)
    out = llama._operator_out(
        y, mha_reference(q, k, v, q_rope=q_rope, k_rope=k_rope), p,
        cfg.layer_plan()[0][0])
    np.testing.assert_allclose(
        out, _latent_attention_by_hand(cfg, y, p), rtol=1e-4, atol=1e-5)


def _whole_qkv(cfg, y, p, cos, sin, constrain=None, rotate=True):
    """``_latent_qkv`` as it was while attention took a head's q and k
    whole: two products, the activations split, rotated in
    neighbouring pairs, the rotated key copied to every head, and
    concatenated. Handed on with no rotated parts."""
    assert rotate  # every layer of these configs rotates
    b, s, _ = y.shape
    nh, nope, rope = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim)
    c_q = llama.rms_norm(y @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
    c_kv, k_rope = jnp.split(y @ p["wkv_a"], [cfg.kv_lora_rank], axis=-1)
    c_kv = llama.rms_norm(c_kv, p["kv_a_norm"], cfg.norm_eps)
    q = (c_q @ p["wq_b"]).reshape(b, s, nh, -1)
    kv = (c_kv @ p["wkv_b"]).reshape(b, s, nh, -1)
    q_nope, q_rope = jnp.split(q, [nope], axis=-1)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    q_rope = llama.apply_rope(q_rope, cos, sin, cfg.rope_interleave)
    k_rope = llama.apply_rope(
        k_rope[:, :, None, :], cos, sin, cfg.rope_interleave)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, nh, rope))], axis=-1)
    return q, k, v, None, None


@pytest.mark.parametrize("interleave", [True, False])
@pytest.mark.parametrize("what", ["q", "k", "v"])
def test_the_parts_side_by_side_are_the_whole_q_and_k(what, interleave):
    """Rotated in neighbouring pairs, the whole q and k left their
    rotated columns in (evens, odds) order: the parts' are in the same
    one, taken on the weights."""
    cfg = _latent(rope_interleave=interleave)
    p = _init(cfg)["lead"][0]
    y = jax.random.normal(jax.random.key(5), (2, 32, 64))
    cos, sin = llama.rope_tables(32, cfg.rope_dim, cfg.rope_theta)
    q, k, v, q_rope, k_rope = llama._latent_qkv(cfg, y, p, cos, sin)
    got = dict(zip("qk", whole_q_and_k(q, k, q_rope, k_rope)), v=v)
    want = dict(zip("qkv", _whole_qkv(cfg, y, p, cos, sin)))
    assert got[what].shape == want[what].shape
    np.testing.assert_allclose(got[what], want[what], rtol=1e-6, atol=1e-6)
    if interleave and what != "v":
        # and not the order of the halves' form on the same weights
        other = dict(zip("qkv", _whole_qkv(
            dataclasses.replace(cfg, rope_interleave=False), y, p, cos, sin)))
        assert float(jnp.abs(other[what] - want[what]).max()) > 0.01


@pytest.mark.parametrize("remat", ["off", "minimal"])
def test_the_loss_and_every_gradient_are_the_whole_q_and_ks(
        remat, monkeypatch):
    """Every leaf's gradient in the leaf's own shape and column order:
    the slices' and the (evens, odds) order's transposes put each
    column's back where the leaf keeps it."""
    cfg = _latent(remat=remat)
    params, batch = _init(cfg), _batch(cfg)

    def loss_and_grads():
        # a function of its own a call: traced anew, with what is
        # patched by then
        return jax.jit(lambda params, batch: jax.value_and_grad(
            llama.next_token_loss)(params, batch, cfg))(params, batch)

    got, got_g = loss_and_grads()
    monkeypatch.setattr(llama, "_latent_qkv", _whole_qkv)
    want, want_g = loss_and_grads()
    assert abs(float(got) - float(want)) < 1e-6
    flat, _ = jax.tree_util.tree_flatten_with_path(got_g)
    for (path, a), b in zip(flat, jax.tree.leaves(want_g)):
        assert a.shape == b.shape, path
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-6, err_msg=str(path))
    layer = got_g["period"][0]
    for name in ("wq_b", "wkv_b", "wkv_a"):
        assert layer[name].shape == params["period"][0][name].shape
        assert float(jnp.abs(layer[name]).max()) > 0


def test_the_routing_weights_take_the_factor_and_the_sources_eps():
    logits = jax.random.normal(jax.random.key(2), (24, 8))
    plain, experts, aux = moe.route_logits(
        logits, 3, True, z_coef=0.0, gate="sigmoid")
    scaled, same, aux2 = moe.route_logits(
        logits, 3, True, z_coef=0.0, gate="sigmoid", scaling=2.5,
        norm_eps=1e-20)
    np.testing.assert_array_equal(experts, same)
    assert float(aux) == float(aux2)  # the balance reads the scores
    picked = jnp.take_along_axis(jax.nn.sigmoid(logits), experts, axis=-1)
    np.testing.assert_allclose(
        plain, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(
        scaled, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(scaled.sum(-1), 2.5, rtol=1e-5)
    # a softmax gate has no eps of its own and takes one given
    soft, _, _ = moe.route_logits(logits, 3, True, z_coef=0.0)
    np.testing.assert_allclose(soft.sum(-1), 1.0, rtol=1e-6)
    # not renormalised: the factor alone
    raw, _, _ = moe.route_logits(
        logits, 3, False, z_coef=0.0, gate="sigmoid", scaling=2.5)
    np.testing.assert_allclose(raw, 2.5 * picked, rtol=1e-6)


def test_the_shared_expert_is_every_tokens_and_unweighted():
    cfg = _latent(moe_experts_held=8)
    p = jax.tree.map(lambda a: a[0], _init(cfg)["period"][0])
    x = jax.random.normal(jax.random.key(6), (2, 16, 64))
    kw = dict(k=2, z_coef=0.0, gate="sigmoid", scaling=2.5)
    experts = (p["router"], p["w_gate"], p["w_up"], p["w_down"])
    routed, aux = moe.dropless_moe_mlp(x, *experts, **kw)
    both, aux2 = moe.dropless_moe_mlp(
        x, *experts, shared=(p["ws_gate"], p["ws_up"], p["ws_down"]), **kw)
    want = (jax.nn.silu(x @ p["ws_gate"]) * (x @ p["ws_up"])) @ p["ws_down"]
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(both - routed, want, rtol=1e-4, atol=1e-5)
    assert float(aux) == float(aux2)
    # with a share of the routed experts the shared one is still whole
    held = (p["router"], *(p[n][2:6] for n in ("w_gate", "w_up", "w_down")))
    part, _ = moe.dropless_moe_mlp(x, *held, first_held=2, **kw)
    part_both, _ = moe.dropless_moe_mlp(
        x, *held, first_held=2,
        shared=(p["ws_gate"], p["ws_up"], p["ws_down"]), **kw)
    np.testing.assert_allclose(
        part_both - part, want, rtol=1e-4, atol=1e-5)


def _loss_by_hand(params, batch, cfg):
    """The stack as a loop over layers, the module written out."""
    tokens, targets = batch
    cos, sin = llama.rope_tables(
        tokens.shape[1], cfg.rope_dim, cfg.rope_theta)
    lead, (kind,) = cfg.layer_plan()
    x, aux_sum = params["embed"][tokens], 0.0
    layers = [(lead[0], params["lead"][0])] + [
        (kind, jax.tree.map(lambda a: a[i], params["period"][0]))
        for i in range(cfg.num_layers - 1)]
    for layer_kind, p in layers:
        x, aux, _ = llama._block(
            cfg, x, p, cos, sin,
            llama._operator_of(cfg, mha_reference, layer_kind),
            kind=layer_kind)
        aux_sum = aux_sum + aux
    head = params["lm_head"]

    def ce(x, scale, targets):
        nll, count = llama._masked_nll(
            (llama.rms_norm(x, scale, cfg.norm_eps) @ head).astype(
                jnp.float32), targets)
        return nll / count

    main = ce(x, params["final_norm"], targets)
    (m,) = params["mtp"]
    ahead = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    merged = jnp.concatenate([
        llama.rms_norm(params["embed"][ahead], m["embed_norm"], cfg.norm_eps),
        llama.rms_norm(x, m["hidden_norm"], cfg.norm_eps),
    ], axis=-1) @ m["eh_proj"]
    y, aux, _ = llama._block(
        cfg, merged, m["block"], cos, sin,
        llama._operator_of(cfg, mha_reference, kind), kind=kind)
    further = jnp.concatenate(
        [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], axis=1)
    return (main + cfg.mtp_loss_weight * ce(y, m["final_norm"], further)
            + aux_sum + aux)


@pytest.mark.parametrize("remat", REMATS)
def test_the_loss_against_a_loop_over_layers_and_the_module(remat):
    cfg = _latent(remat=remat)
    params = _init(cfg)
    batch = _batch(cfg)
    want, want_g = jax.jit(
        jax.value_and_grad(_loss_by_hand), static_argnums=2
    )(params, batch, dataclasses.replace(cfg, remat="off"))
    got, got_g = jax.jit(
        jax.value_and_grad(llama.next_token_loss), static_argnums=2
    )(params, batch, cfg)
    assert abs(float(got) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(got_g)
    for (path, a), b in zip(flat, jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-6, err_msg=str(path))
        if path[-1].key == "expert_bias":
            assert float(jnp.abs(a).max()) == 0.0
        else:  # every other leaf learns, the module's among them
            assert float(jnp.abs(a).max()) > 0.0, path


def test_the_module_is_scored_one_token_further_on():
    """Two positions a sequence have no target for it; the last,
    which the roll hands the sequence's first token, weighs on no
    other."""
    cfg = _latent()
    params = _init(cfg)
    tokens, targets = _batch(cfg, shape=(1, 32))
    ce, mtp, _ = llama._losses(params, (tokens, targets), cfg)
    assert float(mtp) == float(llama.mtp_loss(params, (tokens, targets), cfg))
    # a module fed another token at the last position (which reads
    # the first's embedding) scores the same on every counted one:
    # attention is causal and the routing dropless
    (module,) = params["mtp"]
    x, _, layer_of, _ = llama._run_stack(params, tokens, cfg)
    ahead = jnp.roll(tokens, -1, axis=1)
    states = [
        llama._mtp_states(cfg, params, module, x, fed, layer_of)[0]
        for fed in (ahead, ahead.at[0, -1].add(1))]
    np.testing.assert_array_equal(states[0][:, :-1], states[1][:, :-1])
    assert float(jnp.abs(states[0][:, -1] - states[1][:, -1]).max()) > 0
    # chunked, the module's head too
    chunked = dataclasses.replace(cfg, loss_chunk=16)
    ce2, mtp2, _ = llama._losses(params, (tokens, targets), chunked)
    assert float(ce2) == pytest.approx(float(ce), abs=1e-5)
    assert float(mtp2) == pytest.approx(float(mtp), abs=1e-5)
    # a weight of zero leaves the trunk's loss and the aux terms
    none = dataclasses.replace(cfg, mtp_loss_weight=0.0)
    whole = float(llama.next_token_loss(params, (tokens, targets), cfg))
    assert whole - float(llama.next_token_loss(
        params, (tokens, targets), none)) == pytest.approx(
            0.3 * float(mtp), abs=1e-5)
    # masking one more target takes one position out of each mean
    fewer = targets.at[0, 10].set(-1)
    ce3, mtp3, _ = llama._losses(params, (tokens, fewer), cfg)
    assert float(ce3) != float(ce) and float(mtp3) != float(mtp)


def test_every_new_op_carries_its_scope():
    cfg = _latent()
    text = jax.jit(llama.next_token_loss, static_argnums=2).lower(
        _init(cfg), _batch(cfg), cfg).as_text(debug_info=True)
    for scope in SCOPES + ("moe.route",):
        assert scope in text, scope
    plain = llama.llama_moe_tiny(dtype=jnp.float32)
    text = jax.jit(llama.next_token_loss, static_argnums=2).lower(
        llama.init_params(jax.random.key(0), plain),
        _batch(plain), plain).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope not in text, scope


def test_the_trainer_steps_and_leaves_both_biases_bit_equal():
    cfg = _latent()
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
    (module,), (was,) = params["mtp"], before["mtp"]
    np.testing.assert_array_equal(
        module["block"]["expert_bias"], was["block"]["expert_bias"])
    np.testing.assert_array_equal(
        params["period"][0]["expert_bias"],
        before["period"][0]["expert_bias"])
    assert float(jnp.abs(module["eh_proj"] - was["eh_proj"]).max()) > 0
    assert float(jnp.abs(
        params["period"][0]["ws_up"] - before["period"][0]["ws_up"]
    ).max()) > 0


def test_flops_per_token_counts_both_widths_and_the_module():
    cfg = _latent()
    short, long = (llama.flops_per_token(cfg, s) for s in (32, 64))
    # four blocks of attention (three layers and the module's), each
    # head 24 wide in its scores and 16 in its values
    assert long - short == 6 * 4 * (24 + 16) * 4 * 32
    without = dataclasses.replace(cfg, mtp_layers=0)
    assert llama.flops_per_token(cfg, 32) > llama.flops_per_token(
        without, 32) + 6 * 128 * 64  # a block and the head again


@pytest.mark.parametrize("change,sentence", [
    # q_lora_rank None is q by one matrix since PR 60
    # (tests/test_llama_latent_pattern.py); a window still is refused
    (dict(sliding_window_size=16, sliding_window_layout=(1, 0, 0)),
     "latent attention"),
    (dict(num_kv_heads=2), "latent attention"),
    (dict(v_head_dim=0), "latent attention"),
    (dict(qk_norm=True), "latent attention"),
    (dict(mtp_layers=2), "mtp_layers 2"),
])
def test_the_config_refuses_what_it_cannot_run(change, sentence):
    with pytest.raises(ValueError, match=sentence):
        _latent(**change)


def test_an_expert_axis_refuses_the_factor_and_the_shared_expert():
    base = dict(
        moe_experts_held=8, moe_gate="softmax", use_expert_bias=False,
        moe_topk_norm_eps=None, moe_routed_scaling=1.0,
        moe_shared_experts=0, moe_capacity_factor=1.25)
    llama._expert_mlp(_latent(**base), True)  # nothing of the dropless path
    for change in (dict(moe_routed_scaling=2.5),
                   dict(moe_shared_experts=1),
                   dict(moe_topk_norm_eps=1e-20)):
        with pytest.raises(ValueError, match="shared expert"):
            llama._expert_mlp(_latent(**{**base, **change}), True)
