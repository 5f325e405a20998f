"""models/llama.py's ``sparse_attention`` and ``lightning_attention``
operators and the three scalar factors: a lightning layer through
``ssd_scan`` against the recurrence walked a position at a time, the
scopes in every op's name under every remat policy, what the config
refuses, and what the trainer says of the model."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops.pallas import ssd as scan_kernels

SEQ = 128


def sala_tiny(**kw):
    """One selected-attention layer and three lightning layers, the
    selection's sizes an eighth of the published ones."""
    return llama.llama_tiny(**{**dict(
        num_layers=4, layer_types=("sparse_attention",)
        + ("lightning_attention",) * 3, rope_layout=(0, 1, 1, 1),
        num_heads=4, num_kv_heads=2, head_dim=16, qk_head_norm=True,
        attn_out_gate=True, lightning_num_heads=4, lightning_head_dim=16,
        scale_emb=12.0, scale_depth=1.4, scale_depth_layers=32,
        dim_model_base=4, sparse_block_size=8, sparse_kernel_size=4,
        sparse_kernel_stride=2, sparse_topk=6, sparse_window_size=16,
        sparse_init_blocks=1, sparse_dense_len=64, dtype=jnp.float32,
        remat="off",
    ), **kw})


def batch(cfg, sequences=2, seq=SEQ):
    tokens = jax.random.randint(
        jax.random.key(1), (sequences, seq), 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def test_the_plan_the_leaves_and_the_counts():
    cfg = sala_tiny()
    lead, period = cfg.layer_plan()
    assert lead == ()
    assert [(k.operator, k.rope, k.window, k.ffn) for k in period] == [
        ("sparse_attention", False, None, "dense"),
        ("lightning_attention", True, None, "dense"),
        ("lightning_attention", True, None, "dense"),
        ("lightning_attention", True, None, "dense")]
    assert cfg.by_position
    assert llama.operator_layers(cfg) == {
        "sparse_attention": 1, "lightning_attention": 3}
    assert {"sparse_attention", "lightning_attention"} <= set(llama.OPERATORS)
    params = llama.init_params(jax.random.key(0), cfg)
    sparse, lightning = params["period"][0], params["period"][1]
    assert set(sparse) == {
        "attn_norm", "mlp_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wg",
        "wo", "w_gate", "w_up", "w_down"}
    assert set(lightning) == set(sparse) | {"o_norm"}
    assert sparse["wk"].shape == (1, 64, 32)
    assert lightning["wk"].shape == lightning["wg"].shape == (1, 64, 64)
    assert lightning["o_norm"].shape == sparse["q_norm"].shape == (1, 16)
    assert llama.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    assert cfg.branch_scale == 1.4 / math.sqrt(32)
    assert np.allclose(
        np.asarray(cfg.lightning_decay()), 2.0 ** (-2.0 * np.arange(1, 5)))
    # 6N and, of attention, the keys of a query's 6 blocks of 8
    n = llama.param_count(cfg) - 256 * 64
    assert llama.flops_per_token(cfg, SEQ) == 6.0 * n + 6 * 4 * 32 * 48
    # within dense_len every earlier key
    assert llama.flops_per_token(cfg, 64) == 6.0 * n + 6 * 4 * 32 * 64


def by_hand(cfg, params, tokens):
    """The stack as the operators' docstrings have it, a lightning
    layer's state a position at a time."""
    eps, f = cfg.norm_eps, cfg.branch_scale
    x = params["embed"][tokens] * cfg.scale_emb
    b, s, _ = x.shape
    cos, sin = llama.rope_tables(s, cfg.head_dim, cfg.rope_theta)
    for i, kind in enumerate(cfg.layer_plan()[1]):
        p = jax.tree.map(lambda a: a[0], params["period"][i])
        y = llama.rms_norm(x, p["attn_norm"], eps)
        if kind.operator == "lightning_attention":
            heads, d = cfg.lightning_num_heads, cfg.lightning_head_dim
            q, k, v = ((y @ p[w]).reshape(b, s, heads, d)
                       for w in ("wq", "wk", "wv"))
            q = llama.apply_rope(llama.rms_norm(q, p["q_norm"], eps), cos, sin)
            k = llama.apply_rope(llama.rms_norm(k, p["k_norm"], eps), cos, sin)
            keep = jnp.exp(-cfg.lightning_decay())[None, :, None, None]

            def step(state, at):
                q_t, k_t, v_t = at
                state = keep * state + jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
                return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

            _, o = jax.lax.scan(
                step, jnp.zeros((b, heads, d, d)),
                tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
            o = jnp.moveaxis(o, 0, 1) * d ** -0.5
            o = llama.rms_norm(o, p["o_norm"], eps).reshape(b, s, -1)
            x = x + f * ((o * jax.nn.sigmoid(y @ p["wg"])) @ p["wo"])
        else:
            operate = llama._operator_of(cfg, None, kind)
            operands, _ = llama._pre_attn(cfg, x, p, cos, sin, kind=kind)
            out, gate = operate(*operands)
            x = x + f * (
                (out.reshape(b, s, -1) * jax.nn.sigmoid(gate)) @ p["wo"])
        y = llama.rms_norm(x, p["mlp_norm"], eps)
        x = x + f * ((jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"]))
                     @ p["w_down"])
    x = llama.rms_norm(x, params["final_norm"], eps) * (
        cfg.dim_model_base / cfg.hidden_size)
    return x


def test_a_lightning_layer_through_the_scan_is_the_recurrence():
    cfg = sala_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    tokens, _ = batch(cfg)
    got, _ = llama.hidden_states(params, tokens, cfg)
    want = by_hand(cfg, params, tokens)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(want).max()) > 0.01


@pytest.mark.parametrize("remat", ["off", "dots", "dots_attn_out", "minimal"])
def test_the_scopes_name_every_stage_under_every_remat_policy(remat):
    cfg = sala_tiny(remat=remat)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    tok = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    text = jax.jit(jax.grad(
        lambda p, t: llama.next_token_loss(p, (t, t), cfg))
    ).lower(params, tok).as_text(debug_info=True)
    for scope in ("sparse.compress", "sparse.select", "sparse.attn",
                  "lightning.proj", "lightning.scan", "lightning.out",
                  "embed.scale", "branch.scale", "head.scale", "attn.gate"):
        assert scope in text, scope


def test_remat_changes_neither_the_loss_nor_a_gradient():
    losses, grads = [], []
    for remat in ("off", "minimal"):
        cfg = sala_tiny(remat=remat)
        params = llama.init_params(jax.random.key(0), cfg)
        loss, grad = jax.value_and_grad(
            lambda p: llama.next_token_loss(p, batch(cfg), cfg))(params)
        losses.append(float(loss))
        grads.append(grad)
    assert losses[0] == losses[1]
    for a, b in zip(*map(jax.tree.leaves, grads)):
        assert float(jnp.abs(a - b).max()) < 1e-7


def test_a_sequence_within_dense_len_takes_full_attention():
    cfg = sala_tiny()
    full = sala_tiny(layer_types=("full_attention",)
                     + ("lightning_attention",) * 3)
    params = llama.init_params(jax.random.key(0), cfg)
    short = batch(cfg, seq=64)
    assert float(llama.next_token_loss(params, short, cfg)) == float(
        llama.next_token_loss(params, short, full))
    long = batch(cfg)
    assert abs(float(llama.next_token_loss(params, long, cfg)) - float(
        llama.next_token_loss(params, long, full))) > 1e-6


@pytest.mark.parametrize("field,value,says", [
    ("num_experts", 4, "experts"),
    ("post_norms", True, "experts"),
    ("sparse_block_size", 12, "a power of two"),
    ("sparse_topk", 2, "forced within the 2 selected"),
    ("sparse_window_size", 20, "whole blocks"),
    ("lightning_num_heads", 0, "lightning_num_heads 0"),
    ("lightning_head_dim", 32, "one table of angles"),
    ("total_ut_steps", 2, "looped stack"),
    ("layer_types", ("sparse_attention", "retention", "conv", "conv"),
     "layer_types names"),
])
def test_what_is_not_built_is_refused(field, value, says):
    with pytest.raises(ValueError, match=says):
        sala_tiny(**{field: value})


def test_the_factors_alone_are_refused_beside_a_loop_and_a_module():
    with pytest.raises(ValueError, match="dim_model_base"):
        llama.llama_tiny(dim_model_base=4, total_ut_steps=2, post_norms=True)
    with pytest.raises(ValueError, match="scale_depth_layers 32"):
        llama.llama_tiny(scale_depth_layers=32)
    # the factors on a plain stack: no new leaf, another loss
    plain, scaled = llama.llama_tiny(), llama.llama_tiny(
        scale_emb=12.0, scale_depth=1.4, dim_model_base=16)
    assert scaled.branch_scale == 1.4 / math.sqrt(2)  # its own depth
    params = llama.init_params(jax.random.key(0), plain)
    assert jax.tree.structure(params) == jax.tree.structure(
        llama.init_params(jax.random.key(0), scaled))
    tokens = batch(plain)
    assert abs(float(llama.next_token_loss(params, tokens, plain)) - float(
        llama.next_token_loss(params, tokens, scaled))) > 1e-3


def test_the_trainer_refuses_a_mesh_and_sets_the_operators_gauge():
    from jax.sharding import Mesh

    from dlrover_tpu.telemetry.registry import gauge
    from dlrover_tpu.trainer.sharded import make_trainer_for_llama

    cfg = sala_tiny()
    make_trainer_for_llama(cfg, Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "fsdp")))
    layers = gauge("dlrover_model_operator_layers", "", ("operator",))
    assert layers.labels(operator="sparse_attention").value == 1
    assert layers.labels(operator="lightning_attention").value == 3
    assert layers.labels(operator="full_attention").value == 0
    if len(jax.devices()) > 1:
        with pytest.raises(ValueError, match="whole sequences"):
            make_trainer_for_llama(cfg, Mesh(
                np.array(jax.devices()[:2]).reshape(1, 2), ("data", "fsdp")))


def test_the_scans_kernels_take_the_lightning_regime():
    """One head a group, 128 values and 128 states a head, as the
    cell's 32 heads of 128 at 16,384 positions."""
    assert scan_kernels.tiles_the_kernel(
        (1, 16384, 4096), (1, 16384, 4096), 32, 32)
    assert scan_kernels.heads_a_step(32, 32) == 1
    # a head narrower than a lane tile alone in its group is not taken
    assert not scan_kernels.tiles_the_kernel(
        (1, 256, 4 * 64), (1, 256, 4 * 128), 4, 4)
