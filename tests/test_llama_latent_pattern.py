"""What models/llama.py gained for latent attention inside a layer
pattern: ``layer_types`` may name ``"latent_attention"`` beside
``"linear_attention"``, such a layer takes its rotation from
``rope_layout`` (0: the further columns of q and the one key enter the
scores as their products made them, and no table is built when no
layer rotates), ``q_lora_rank`` None makes q one matrix's product
under the scope ``mla.q``, a delta-rule layer may lead the stack with
a dense MLP; what is not built stays refused by name; and a config
with a q latent in every layer (joyai's) keeps the leaves and the
lowered program it had."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.telemetry.registry import gauge
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

REMATS = ("off", "dots", "dots_attn_out", "minimal")
KDA, MLA = "linear_attention", "latent_attention"


def _pattern(**kw):
    """A leading dense delta-rule layer, then ``[KDA, KDA, MLA, KDA]``
    with experts: latent attention without positions, q by one
    matrix."""
    kw = {**dict(
        vocab_size=128, intermediate_size=96, max_seq_len=32,
        num_layers=5, num_dense_layers=1, num_kv_heads=4,
        layer_types=(KDA, KDA, KDA, MLA, KDA), rope_layout=(0,) * 5,
        linear_num_heads=4, linear_head_dim=16, linear_gate_rank=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, moe_top_k=2,
        moe_intermediate_size=32, moe_gate="sigmoid",
        use_expert_bias=True, moe_topk_norm_eps=1e-20,
        moe_routed_scaling=2.446, moe_shared_experts=1,
        moe_capacity_factor=0.0, router_z_loss_coef=0.0,
        dtype=jnp.float32, remat="off", embed_init_std=0.1,
    ), **kw}
    return llama.llama_tiny(**kw)


def _init(cfg, seed=0):
    return llama.init_params(jax.random.key(seed), cfg)


def _batch(cfg, seed=1, shape=(2, 32)):
    tokens = jax.random.randint(
        jax.random.key(seed), shape, 0, cfg.vocab_size)
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], axis=1)
    return tokens, targets


def test_the_plan_leads_with_the_delta_rule_and_scans_the_period():
    cfg = _pattern()
    lead, period = cfg.layer_plan()
    assert lead == (llama.LayerKind(KDA, None, False, "dense"),)
    assert period == (
        llama.LayerKind(KDA, None, False, "experts"),) * 2 + (
        llama.LayerKind(MLA, None, False, "experts"),
        llama.LayerKind(KDA, None, False, "experts"))
    assert cfg.latent and cfg.by_position
    assert llama.operator_layers(cfg) == {KDA: 4, MLA: 1}
    two = _pattern(num_layers=9, layer_types=(KDA,) + (KDA, KDA, MLA, KDA) * 2,
                   rope_layout=(0,) * 9)
    assert two.layer_plan()[1] == period
    assert llama.operator_layers(two) == {KDA: 7, MLA: 2}


def test_the_latent_layer_has_one_q_matrix_and_no_q_latent():
    cfg = _pattern()
    params = _init(cfg)
    assert set(params) == {
        "embed", "final_norm", "lead", "period", "lm_head"}
    latent = params["period"][2]
    assert latent["wq"].shape == (1, 64, 4 * 24)
    assert latent["wkv_a"].shape == (1, 64, 32 + 8)
    assert latent["wkv_b"].shape == (1, 32, 4 * 32)
    assert latent["wo"].shape == (1, 4 * 16, 64)
    assert latent["kv_a_norm"].shape == (1, 32)
    assert not {"wq_a", "wq_b", "q_a_norm", "wk", "wv"} & set(latent)
    lead = params["lead"][0]
    assert lead["w_gate"].shape == (64, 96) and "router" not in lead
    assert lead["f_b"].shape == (16, 64) and "wkv_a" not in lead
    assert params["period"][0]["w_gate"].shape == (1, 8, 64, 32)
    axes = llama.param_axes(cfg)
    assert axes["period"][2]["wq"] == ("layers", "embed", "heads")
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(
        a, tuple)) == jax.tree.structure(params)
    assert llama.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))


def _layer_by_hand(cfg, y, p, rotate):
    """Latent attention's equations on whole q and k, by one matrix,
    with the rotation of the program's own tables or without."""
    b, s, _ = y.shape
    nh, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (y @ p["wq"]).reshape(b, s, nh, nope + rope)
    down = y @ p["wkv_a"]
    c = llama.rms_norm(down[..., :32], p["kv_a_norm"], cfg.norm_eps)
    k_r = down[..., 32:][:, :, None, :]
    kv = (c @ p["wkv_b"]).reshape(b, s, nh, nope + cfg.v_head_dim)
    q_r = q[..., nope:]
    if rotate:
        cos, sin = llama.rope_tables(s, rope, cfg.rope_theta)
        q_r = llama.apply_rope(q_r, cos, sin)
        k_r = llama.apply_rope(k_r, cos, sin)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, nh, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    return mha_reference(q, k, kv[..., nope:], causal=True)


@pytest.mark.parametrize("rotate", [False, True], ids=["nope", "rope"])
def test_a_latent_layer_takes_its_rotation_from_the_layout(rotate):
    """``_pre_attn`` and the operator's call on a latent layer of the
    pattern against the equations on whole q and k: with
    ``rope_layout`` 0 nothing is turned, with 1 the further columns
    are, and the two differ."""
    layout = (0, 0, 0, int(rotate), 0)
    cfg = _pattern(rope_layout=layout)
    kind = cfg.layer_plan()[1][2]
    assert kind.operator == MLA and kind.rope is rotate
    p = jax.tree.map(lambda a: a[0], _init(cfg)["period"][2])
    x = jax.random.normal(jax.random.key(5), (2, 32, 64))
    cos, sin = llama._rope_tables_of(cfg, 32)
    assert (cos is None) is not rotate
    operands, logits = llama._pre_attn(cfg, x, p, cos, sin, kind=kind)
    assert logits is None and len(operands) == 5
    assert operands[3].shape == (2, 32, 4, 8)   # a head's further q
    assert operands[4].shape == (2, 32, 1, 8)   # the one key
    got = llama._operator_of(
        cfg, lambda q, k, v, **parts: mha_reference(
            q, k, v, causal=True, **parts), kind)(*operands)
    y = llama.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    want = _layer_by_hand(cfg, y, p, rotate)
    assert float(jnp.abs(got - want).max()) < 1e-5
    other = _layer_by_hand(cfg, y, p, not rotate)
    assert float(jnp.abs(got - other).max()) > 1e-3


def test_no_table_is_built_when_no_layer_rotates():
    cfg = _pattern()
    assert llama._rope_tables_of(cfg, 32) == (None, None)
    jaxpr = str(jax.make_jaxpr(
        lambda p, b: llama.next_token_loss(p, b, cfg))(
            _init(cfg), _batch(cfg)))
    assert "= cos " not in jaxpr and "= sin " not in jaxpr  # primitives
    rotating = _pattern(rope_layout=(0, 0, 0, 1, 0))
    cos, sin = llama._rope_tables_of(rotating, 32)
    assert cos.shape == sin.shape == (32, 4)  # qk_rope_head_dim / 2
    # a stack of plain layers rotates as it did
    assert llama._rope_tables_of(llama.llama_tiny(), 16)[0].shape == (16, 8)


def _loop_over_layers(cfg, params, batch):
    """The loss with the layers one by one in Python: no scan, no
    remat."""
    tokens, targets = batch
    cos, sin = llama._rope_tables_of(cfg, tokens.shape[1])
    lead, period = cfg.layer_plan()
    x = params["embed"][tokens]
    aux = 0.0
    layers = [(kind, p) for kind, p in zip(lead, params["lead"])]
    periods = (cfg.num_layers - len(lead)) // len(period)
    for i in range(periods):
        for kind, stack in zip(period, params["period"]):
            layers.append((kind, jax.tree.map(lambda a: a[i], stack)))
    for kind, p in layers:
        operate = llama._operator_of(
            cfg, lambda q, k, v, **parts: mha_reference(
                q, k, v, causal=True, **parts), kind)
        x, layer_aux, _ = llama._block(
            cfg, x, p, cos, sin, operate, kind=kind)
        aux = aux + layer_aux
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    keep = targets >= 0
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(keep, nll, 0.0)) / jnp.sum(keep) + aux


@pytest.mark.parametrize("remat", REMATS)
def test_the_scanned_period_against_a_loop_over_layers(remat):
    cfg = _pattern(num_layers=9, remat=remat,
                   layer_types=(KDA,) + (KDA, KDA, MLA, KDA) * 2,
                   rope_layout=(0,) * 9)
    params, batch = _init(cfg), _batch(cfg)
    want = float(_loop_over_layers(cfg, params, batch))
    got = float(jax.jit(
        lambda p, b: llama.next_token_loss(p, b, cfg))(params, batch))
    assert got == pytest.approx(want, abs=2e-5)


@pytest.mark.parametrize("remat", REMATS)
def test_q_by_one_matrix_carries_its_scope_under_every_policy(remat):
    cfg = _pattern(remat=remat)
    text = jax.jit(jax.grad(
        lambda p, b: llama.next_token_loss(p, b, cfg))).lower(
            _init(cfg), _batch(cfg)).as_text(debug_info=True)
    for scope in ("mla.q/", "mla.kv_down", "mla.up", "attn.latent",
                  "kda.proj", "kda.conv", "kda.scan", "kda.out",
                  "moe.route", "moe.shared"):
        assert scope in text, scope
    assert "mla.q_down" not in text


def test_trace_by_scope_tells_q_by_one_matrix_from_a_q_latent():
    """``benchmarks/trace_by_scope.py`` takes the first of its scopes
    that an ``op_name`` holds, and "mla.q_down" holds "mla.q"."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_by_scope", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "trace_by_scope.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def scope_of(op_name):
        return next(s for s in tool.SCOPES if s in op_name)

    step = "jit(step)/loss/jvp()/while/body/closed_call/"
    assert scope_of(step + "mla.q/dot_general") == "mla.q"
    assert scope_of(step + "mla.q_down/dot_general") == "mla.q_down"
    assert scope_of(step + "mla.up/dot_general") == "mla.up"
    assert scope_of(step + "attn.latent/jit(flash_attention)") == (
        "attn.latent")


def test_a_q_latent_keeps_its_scopes_and_gains_none():
    cfg = llama.llama_latent_tiny(dtype=jnp.float32)
    text = jax.jit(llama.next_token_loss, static_argnums=2).lower(
        _init(cfg), _batch(cfg), cfg).as_text(debug_info=True)
    assert "mla.q_down" in text and "mla.up" in text
    assert "mla.q/" not in text


#: sha256 (first 16 hex digits) of the lowered gradient of the loss
#: and of the leaves' paths, shapes and dtypes, of two configurations
#: with a q latent in every layer, as the tree before PR 60 lowered
#: them on this installation (jax 0.4's text carries no source
#: location). A PR that changes what such a model runs reads them
#: again and writes its own here (the test prints them). Since PR 63
#: with the plain cross entropy in the place of the head's own rule
#: (``llama._head_nll``, which tests/test_head_loss.py holds to it).
UNCHANGED = {
    "llama_latent_tiny": ("8c3e8cbd7313d421", "d30d326c4af70b01"),
    "tiny-joyai": ("4e1e20df068fc51b", "cc6bdb7949b0cb3d"),
}


@pytest.mark.parametrize("name", list(UNCHANGED))
def test_joyais_leaves_and_lowered_program_are_unchanged(
        name, monkeypatch):
    from yardstick import cells, worker

    monkeypatch.setattr(
        llama, "_head_nll", lambda x, head, targets: llama._position_nll(
            (x @ head).astype(jnp.float32), targets))

    if name == "llama_latent_tiny":
        cfg = llama.llama_latent_tiny()
    else:
        with open(os.path.join(
                cells.HERE, "configs", name + ".json")) as f:
            cfg = worker.program_config(json.load(f), {
                "seq": 128, "remat": "minimal", "loss_chunk": 0})
    assert cfg.q_lora_rank and cfg.layer_types is None
    assert all(kind.rope for kind in sum(cfg.layer_plan(), ()))
    shapes = jax.eval_shape(lambda: _init(cfg))
    tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = jax.jit(jax.grad(
        lambda p, b: llama.next_token_loss(p, b, cfg))).lower(
            shapes, (tok, tok)).as_text()
    leaves = sorted(
        (jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(shapes))
    got = tuple(hashlib.sha256(x.encode()).hexdigest()[:16]
                for x in (text, repr(leaves)))
    print(name, got)
    assert got == UNCHANGED[name]


def test_the_trainer_steps_sets_the_gauge_and_every_operator_moves():
    cfg = _pattern()
    mesh = create_mesh([("data", 4), ("fsdp", 2)])
    trainer = make_trainer_for_llama(
        cfg, mesh, strategy="fsdp", optimizer=optax.adamw(1e-2))
    layers = gauge("dlrover_model_operator_layers", "", ("operator",))
    # by name, wherever an operator stands in the list
    assert {o: layers.labels(operator=o).value
            for o in llama.OPERATORS} == {
        **dict.fromkeys(llama.OPERATORS, 0),
        "latent_attention": 1, "linear_attention": 4}
    params, opt_state = trainer.init(jax.random.key(0))
    before = jax.tree.map(np.asarray, params)
    tokens, targets = _batch(cfg, shape=(8, 32))
    mb = trainer.microbatch((np.asarray(tokens), np.asarray(targets)))
    losses = []
    for _ in range(3):
        params, opt_state, loss = trainer.train_step(params, opt_state, mb)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    for where, leaf in ((params["period"][2], "wq"),
                        (params["period"][2], "wkv_b"),
                        (params["lead"][0], "w_beta"),
                        (params["lead"][0], "w_down"),
                        (params["period"][3], "A_log")):
        was = before["lead"][0] if where is params["lead"][0] else (
            before["period"][2] if where is params["period"][2]
            else before["period"][3])
        assert float(jnp.abs(where[leaf] - was[leaf]).max()) > 0, leaf
    np.testing.assert_array_equal(
        params["period"][2]["expert_bias"],
        before["period"][2]["expert_bias"])
    # the next trainer's model has none of either operator
    make_trainer_for_llama(
        llama.llama_tiny(), mesh, strategy="fsdp",
        optimizer=optax.adamw(1e-2))
    assert {o: layers.labels(operator=o).value
            for o in llama.OPERATORS} == {
        **dict.fromkeys(llama.OPERATORS, 0), "full_attention": 2}


def test_flops_per_token_counts_the_latent_layers_scores_alone():
    cfg = _pattern()
    short, long = (llama.flops_per_token(cfg, s) for s in (32, 64))
    # one latent layer: each head 24 wide in its scores, 16 in its values
    assert long - short == 6 * 4 * (24 + 16) * 1 * 32
    with_latent = dataclasses.replace(cfg, q_lora_rank=48)
    # q's one matrix 64 x 96 against 64 x 48 + 48 x 96 and the norm
    assert llama.param_count(with_latent) - llama.param_count(cfg) == (
        64 * 48 + 48 * 96 + 48 - 64 * 96)


@pytest.mark.parametrize("change,sentence", [
    # a latent layer that layer_types names and kv_lora_rank does not size
    (dict(kv_lora_rank=None), "kv_lora_rank sizes it"),
    # attention of whole q, k and v beside latent layers
    (dict(layer_types=(KDA, KDA, "full_attention", MLA, KDA)),
     "no 'full_attention' beside it"),
    # kv_lora_rank and a pattern that names no latent layer
    (dict(layer_types=(KDA,) * 5), "kv_lora_rank sizes it"),
    (dict(layer_types=(KDA, KDA, KDA, "retention", KDA)), "retention"),
    (dict(sliding_window_size=16, sliding_window_layout=(0, 0, 0, 1, 0)),
     "a window"),
    (dict(qk_norm=True), "a norm of whole q and k"),
    (dict(qk_head_norm=True), "a norm of whole q and k"),
    (dict(attn_out_gate=True), "a gate on its result"),
    (dict(num_kv_heads=2), "as many kv heads as heads"),
    (dict(v_head_dim=0), "latent attention"),
    (dict(rope_layout=(0,) * 4), "rope_layout has 4 entries"),
    (dict(linear_num_heads=0), "gives it no head"),
])
def test_the_config_refuses_what_is_not_built(change, sentence):
    with pytest.raises(ValueError, match=sentence):
        _pattern(**change)


def test_the_refusal_says_what_is_taken():
    with pytest.raises(ValueError) as refused:
        _pattern(qk_norm=True)
    said = str(refused.value)
    for taken in ("q_lora_rank", "one matrix", "layer_types",
                  "rope_layout", "'linear_attention'"):
        assert taken in said, taken
