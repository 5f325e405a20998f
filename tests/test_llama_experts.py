"""What models/llama.py gained for OLMoE: q/k norms over the whole
projections, the dropless expert path chosen by the mesh, the remat
policies' say on a grouped matmul, ``routing_stats``; and that a
dense config traces none of it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.trainer.sharded import make_trainer_for_llama


def _tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    return llama.llama_tiny(**kw)


def _batch(cfg, seed=1, shape=(2, 32)):
    tokens = jax.random.randint(
        jax.random.key(seed), shape, 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


@pytest.mark.parametrize("qk_norm", [False, True])
def test_qk_norm_params_and_count(qk_norm):
    cfg = _tiny(qk_norm=qk_norm)
    params = llama.init_params(jax.random.key(0), cfg)
    blocks = params["blocks"]
    assert ("q_norm" in blocks) == ("k_norm" in blocks) == qk_norm
    assert set(llama.param_axes(cfg)["blocks"]) == set(blocks)
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        llama.param_count(cfg))
    if qk_norm:
        L = cfg.num_layers
        assert blocks["q_norm"].shape == (
            L, cfg.num_heads * cfg.head_dim)
        assert blocks["k_norm"].shape == (
            L, cfg.num_kv_heads * cfg.head_dim)


def test_qk_norm_normalises_the_whole_projection():
    """With unit scales the scores no longer depend on the size of wq;
    without the norm they do. And the scales get gradient."""
    on, off = _tiny(qk_norm=True), _tiny()
    batch = _batch(on)

    def losses(cfg):
        params = llama.init_params(jax.random.key(0), cfg)
        grown = jax.tree.map(lambda x: x, params)
        grown["blocks"]["wq"] = params["blocks"]["wq"] * 3.0
        return (llama.next_token_loss(params, batch, cfg),
                llama.next_token_loss(grown, batch, cfg), params)

    a, b, params = losses(on)
    assert abs(float(a) - float(b)) < 1e-5
    a, b, _ = losses(off)
    assert abs(float(a) - float(b)) > 1e-4
    g = jax.grad(llama.next_token_loss)(params, batch, on)
    assert float(jnp.abs(g["blocks"]["q_norm"]).sum()) > 0
    assert float(jnp.abs(g["blocks"]["k_norm"]).sum()) > 0


def test_routing_stats_sums():
    cfg = _tiny(num_experts=8, moe_top_k=2, norm_topk_prob=False)
    params = llama.init_params(jax.random.key(0), cfg)
    tokens, _ = _batch(cfg)
    counts = np.asarray(
        jax.jit(llama.routing_stats, static_argnums=2)(
            params, tokens, cfg))
    assert counts.shape == (cfg.num_layers, cfg.num_experts)
    assert (counts.sum(axis=1) == tokens.size * cfg.moe_top_k).all()
    assert (counts >= 0).all()
    with pytest.raises(ValueError):
        llama.routing_stats(params, tokens, _tiny())


def test_dense_config_traces_no_expert_or_qk_norm_op():
    """A dense config's loss holds no sort, no top-k, no grouped
    matmul, and no norm beyond the two a block has (plus the final
    one): the new branches are decided by the config, in Python."""
    cfg = _tiny(remat="off")
    params = llama.init_params(jax.random.key(0), cfg)
    text = str(jax.make_jaxpr(
        lambda p, b: llama.next_token_loss(p, b, cfg)
    )(params, _batch(cfg)))
    for op in ("sort", "top_k", "ragged_dot_general", "cumsum"):
        assert not re.search(rf"\b{op}\b", text), op
    assert text.count("rsqrt") == 3
    sparse = _tiny(remat="off", num_experts=4, qk_norm=True)
    text = str(jax.make_jaxpr(
        lambda p, b: llama.next_token_loss(p, b, sparse)
    )(llama.init_params(jax.random.key(0), sparse), _batch(sparse)))
    for op in ("sort", "top_k", "ragged_dot_general"):
        assert re.search(rf"\b{op}\b", text), op
    assert text.count("rsqrt") == 5


@pytest.mark.parametrize("remat", ["dots", "dots_attn_out", "minimal"])
def test_remat_policies_agree_on_an_expert_config(remat):
    kw = dict(num_experts=8, moe_top_k=2, qk_norm=True,
              norm_topk_prob=False)
    off, cfg = _tiny(remat="off", **kw), _tiny(remat=remat, **kw)
    params = llama.init_params(jax.random.key(0), off)
    batch = _batch(off)
    want, want_g = jax.value_and_grad(llama.next_token_loss)(
        params, batch, off)
    got, got_g = jax.value_and_grad(llama.next_token_loss)(
        params, batch, cfg)
    assert abs(float(want) - float(got)) < 1e-6
    for a, b in zip(jax.tree.leaves(want_g), jax.tree.leaves(got_g)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_dots_policy_keeps_the_gate_and_up_products():
    """Under ``dots`` the backward pass of an expert block runs the
    three grouped matmuls' six backward products and recomputes none
    of the forward ones but the cheap down input: 3 forward + 6
    backward ragged_dots in the gradient's jaxpr, not 3 + 2 + 6."""
    kw = dict(num_experts=8, moe_top_k=2, num_layers=1)
    counts = {}
    for remat in ("off", "dots", "minimal"):
        cfg = _tiny(remat=remat, **kw)
        params = llama.init_params(jax.random.key(0), cfg)
        text = str(jax.make_jaxpr(jax.grad(
            lambda p, b: llama.next_token_loss(p, b, cfg)
        ))(params, _batch(cfg)))
        counts[remat] = len(re.findall(r"\bragged_dot_general\[", text))
    assert counts["off"] == 9
    assert counts["dots"] == counts["off"]
    assert counts["minimal"] > counts["dots"]


def test_the_mesh_chooses_the_path():
    """Every expert on the device: dropless. Over an ``expert`` axis:
    the capacity path, and a config that states dropless routing
    (capacity factor 0) is refused, not run with drops."""
    cfg = llama.llama_moe_tiny()
    trainer = make_trainer_for_llama(
        cfg, create_mesh([("data", 1), ("fsdp", 1)],
                         devices=jax.devices()[:1]),
        optimizer=optax.adam(1e-2))
    params, opt_state = trainer.init(jax.random.key(0))
    tokens = np.asarray(_batch(cfg, shape=(4, 16))[0])
    batch = trainer.shard_batch(trainer.microbatch((tokens, tokens)))
    assert "ragged_dot" in str(
        jax.make_jaxpr(trainer._loss_fn)(params, (tokens, tokens)))
    losses = []
    for _ in range(8):
        params, opt_state, loss = trainer.train_step(
            params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses

    mesh = create_mesh([("data", 2), ("expert", 4)])
    sharded = make_trainer_for_llama(cfg, mesh, strategy="tp_fsdp")
    p2, _ = sharded.init(jax.random.key(0))
    more = np.tile(tokens, (2, 1))
    assert "ragged_dot" not in str(
        jax.make_jaxpr(sharded._loss_fn)(p2, (more, more)))
    with pytest.raises(ValueError, match="dropless"):
        make_trainer_for_llama(
            llama.llama_moe_tiny(moe_capacity_factor=0.0), mesh,
            strategy="tp_fsdp")


def test_expert_load_gauges_come_from_routing_stats():
    from dlrover_tpu.parallel.moe import set_expert_load_gauges
    from dlrover_tpu.telemetry.registry import default_registry

    most, least = set_expert_load_gauges(
        np.array([[4, 4, 4, 4], [10, 2, 4, 0]]))
    assert (most, least) == (2.5, 0.0)
    cfg = _tiny(num_experts=8, moe_top_k=2)
    params = llama.init_params(jax.random.key(0), cfg)
    most, least = set_expert_load_gauges(
        llama.routing_stats(params, _batch(cfg)[0], cfg))
    assert most >= 1.0 >= least >= 0.0
    text = default_registry().to_prometheus_text()
    for name, value in (("moe_expert_load_max_over_mean", most),
                        ("moe_expert_load_min_over_mean", least)):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(name + " "))
        assert float(line.split()[1]) == value
