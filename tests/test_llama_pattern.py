"""What models/llama.py gained for SmallThinker: a head width of its
own, a layer pattern scanned a period at a time (window or full
attention, RoPE or none, by layer), a router on the block's input,
ReLU-gated experts, and a share of the experts held on this device;
and that a config with none of it traces the jaxpr it always did."""

import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops.attention import mha_reference
from dlrover_tpu.parallel import moe
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.trainer.sharded import make_trainer_for_llama

REMATS = ("off", "dots", "dots_attn_out", "minimal")


def _patterned(**kw):
    """7 query heads of 16 on one kv head beside a hidden size of 64,
    pattern [0, 1, 1, 1] twice, window 8, 8 experts of which 4 held."""
    kw = {**dict(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        num_layers=8, num_heads=7, num_kv_heads=1, head_dim=16,
        max_seq_len=32, dtype=jnp.float32, remat="off",
        num_experts=8, moe_top_k=3, moe_capacity_factor=0.0,
        router_z_loss_coef=0.0, sliding_window_size=8,
        sliding_window_layout=(0, 1, 1, 1) * 2,
        rope_layout=(0, 1, 1, 1) * 2,
        moe_router_input="block_input", moe_expert_act="relu",
        moe_first_expert_held=0, moe_experts_held=4,
    ), **kw}
    return llama.LlamaConfig(**kw)


def _batch(cfg, seed=1, shape=(2, 32)):
    tokens = jax.random.randint(
        jax.random.key(seed), shape, 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


# -- the head's width ----------------------------------------------------

def test_head_dim_is_a_key_of_its_own():
    cfg = _patterned()
    assert cfg.head_dim == 16 != cfg.hidden_size // cfg.num_heads
    assert llama.llama_tiny().head_dim == 64 // 4  # None: derived
    params = llama.init_params(jax.random.key(0), cfg)
    blocks = params["blocks"]
    assert blocks["wq"].shape == (8, 64, 7 * 16)
    assert blocks["wk"].shape == blocks["wv"].shape == (8, 64, 16)
    assert blocks["wo"].shape == (8, 7 * 16, 64)
    assert blocks["router"].shape == (8, 64, 8)  # the router's width
    assert blocks["w_gate"].shape == (8, 4, 64, 32)  # the held
    axes = llama.param_axes(cfg)
    assert set(axes["blocks"]) == set(blocks)
    for name, leaf in blocks.items():
        assert len(axes["blocks"][name]) == leaf.ndim, name
    assert sum(x.size for x in jax.tree.leaves(params)) == (
        llama.param_count(cfg))
    cos, _ = llama.rope_tables(32, cfg.head_dim, cfg.rope_theta)
    assert cos.shape == (32, 8)


def test_the_embeddings_deviation_is_a_field():
    """At its default the draws are the ones they always were."""
    plain = llama.init_params(jax.random.key(0), llama.llama_tiny())
    scaled = llama.init_params(
        jax.random.key(0), llama.llama_tiny(embed_init_std=0.7))
    assert float(jnp.std(plain["embed"].astype(jnp.float32))) == (
        pytest.approx(0.02, rel=0.05))
    np.testing.assert_allclose(
        scaled["embed"].astype(jnp.float32),
        35 * plain["embed"].astype(jnp.float32), rtol=1e-2, atol=1e-6)
    for name, leaf in plain["blocks"].items():
        np.testing.assert_array_equal(scaled["blocks"][name], leaf)


def test_flops_per_token_counts_head_dim_and_the_window():
    """The conventional count (no causality) takes ``num_heads x
    head_dim`` and, in a windowed layer, the window's keys; a config
    without a pattern reads what it always read."""
    plain = llama.llama_tiny()
    dense = 6.0 * (llama.param_count(plain)
                   - plain.vocab_size * plain.hidden_size)
    assert llama.flops_per_token(plain, 64) == (
        dense + 12 * plain.num_layers * plain.hidden_size * 64)
    cfg = _patterned()  # 8 layers of [0, 1, 1, 1], window 8, 7 x 16
    wide = 7 * 16
    assert cfg.hidden_size != wide

    def attention(seq):
        without = dataclasses.replace(
            cfg, sliding_window_layout=None, rope_layout=None)
        return (llama.flops_per_token(cfg, seq)
                - llama.flops_per_token(without, seq)
                + 12 * 8 * wide * seq)

    assert attention(32) == 12 * wide * 2 * (32 + 3 * 8)
    assert attention(4) == 12 * wide * 8 * 4  # shorter than the window


# -- the layer pattern ---------------------------------------------------

@pytest.mark.parametrize("layouts,kinds", [
    (None, ((None, True),)),
    (((0, 1, 1, 1) * 2, (0, 1, 1, 1) * 2),
     ((None, False), (8, True), (8, True), (8, True))),
    (((1,) * 8, (1,) * 8), ((8, True),)),
    (((0, 1) * 4, (1, 1, 1, 0) * 2),
     ((None, True), (8, True), (None, True), (8, False))),
    (((0,) * 7 + (1,), (1,) * 8),
     ((None, True),) * 7 + ((8, True),)),
])
def test_the_period_is_the_shortest_repeat_of_the_layouts(layouts, kinds):
    windows, ropes = layouts or (None, None)
    cfg = _patterned(sliding_window_layout=windows, rope_layout=ropes)
    assert cfg.layer_kinds() == kinds


def test_layouts_are_checked():
    with pytest.raises(ValueError, match="8 layers"):
        _patterned(rope_layout=(0, 1, 1, 1))
    with pytest.raises(ValueError, match="sliding_window_size"):
        _patterned(sliding_window_size=None)
    with pytest.raises(ValueError, match="experts 6..10 of 8"):
        _patterned(moe_first_expert_held=6)
    with pytest.raises(ValueError, match="moe_router_input"):
        _patterned(moe_router_input="after")


def _loop_over_layers(params, batch, cfg):
    """The loss with the layers walked in a Python loop, each by its
    own layout entries: no scan, no period."""
    tokens, targets = batch
    cos, sin = llama.rope_tables(
        tokens.shape[1], cfg.head_dim, cfg.rope_theta)
    x, aux_sum = params["embed"][tokens], 0.0
    for i in range(cfg.num_layers):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        window = (cfg.sliding_window_size
                  if cfg.sliding_window_layout[i] else None)
        x, aux, _ = llama._block(
            cfg, x, p, cos, sin,
            lambda q, k, v: mha_reference(q, k, v, window=window),
            kind=llama.LayerKind(
                rope=bool(cfg.rope_layout[i]), ffn="experts"),
        )
        aux_sum = aux_sum + aux
    x = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    nll, count = llama._masked_nll(
        (x @ params["lm_head"]).astype(jnp.float32), targets)
    return nll / count + aux_sum


@pytest.mark.parametrize("remat", REMATS)
def test_period_scan_against_a_loop_over_layers(remat):
    cfg = _patterned(remat=remat)
    params = llama.init_params(jax.random.key(0), cfg)
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
    # the router's logits, taken before attention, reached the experts
    assert float(jnp.abs(got_g["blocks"]["router"]).sum()) > 0
    # one scan over the two periods, four blocks in its body: two
    # kinds of attention call, the first without the rotary embedding
    text = str(jax.make_jaxpr(
        lambda p: llama.next_token_loss(p, batch, cfg))(params))
    assert len(re.findall(r"length=2\b", text)) == 1
    assert "length=8" not in text


def test_window_and_rope_by_layer_show():
    """Each layout decides something: a changed entry moves the loss."""
    cfg = _patterned()
    params = llama.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg)
    base = float(llama.next_token_loss(params, batch, cfg))
    for change in (
        dict(sliding_window_layout=(0,) * 8),
        dict(rope_layout=(1,) * 8),
        dict(rope_layout=(0,) * 8),
        dict(sliding_window_size=16),
    ):
        other = float(llama.next_token_loss(
            params, batch, dataclasses.replace(cfg, **change)))
        assert abs(other - base) > 1e-5, change


def test_a_windowed_layer_hands_attn_fn_its_window():
    cfg = _patterned()
    params = llama.init_params(jax.random.key(0), cfg)
    seen = []

    def attn_fn(q, k, v, window=None):
        seen.append(window)
        return mha_reference(q, k, v, window=window)

    llama.next_token_loss(params, _batch(cfg), cfg, attn_fn=attn_fn)
    assert seen == [None, 8, 8, 8]  # one period is traced
    with pytest.raises(TypeError, match="window"):
        llama.next_token_loss(
            params, _batch(cfg), cfg,
            attn_fn=lambda q, k, v: mha_reference(q, k, v))


def test_a_config_without_a_pattern_traces_the_parents_jaxpr(monkeypatch):
    """The hashes are of ``str(make_jaxpr(value_and_grad(
    next_token_loss)))`` at the commit before the layer pattern
    (025500f), addresses struck: ``llama_tiny`` dense and with
    experts, under each remat policy. A change that means to alter
    these programs regenerates the file. The head's cross entropy
    has had a backward rule of its own since PR 63
    (tests/test_head_loss.py holds it to the plain rule): with the
    plain rule in its place the programs are 025500f's still."""
    monkeypatch.setattr(
        llama, "_head_nll", lambda x, head, targets: llama._position_nll(
            (x @ head).astype(jnp.float32), targets))
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "llama_jaxprs_025500f.json")) as f:
        want = json.load(f)
    kinds = {"dense": {}, "experts": dict(
        num_experts=4, moe_capacity_factor=0.0, qk_norm=True)}
    got = {}
    for name, kw in kinds.items():
        for remat in REMATS:
            cfg = llama.llama_tiny(remat=remat, **kw)
            params = jax.eval_shape(
                lambda: llama.init_params(jax.random.key(0), cfg))
            tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
            text = str(jax.make_jaxpr(jax.value_and_grad(
                lambda p, b: llama.next_token_loss(p, b, cfg)
            ))(params, (tok, tok)))
            got[f"{name}.{remat}"] = hashlib.sha256(
                re.sub(r" at 0x[0-9a-f]+", "", text).encode()
            ).hexdigest()
    assert got == want


# -- the router on the block's input --------------------------------------

def test_router_reads_the_blocks_input():
    """The first layer's routing follows the embedding alone: it is
    what ``routing_stats`` counts from ``x Wr``, whatever attention
    does, and another choice of input routes otherwise. (That the
    logits reach the experts past attention under every remat policy,
    and give the router its gradient, is the loop test above: its
    loop reads the block's input too.)"""
    cfg = _patterned(
        num_layers=4, sliding_window_layout=(0, 1) * 2,
        rope_layout=(0, 1) * 2)
    params = llama.init_params(jax.random.key(0), cfg)
    tokens, _ = batch = _batch(cfg)
    stats = jax.jit(llama.routing_stats, static_argnums=2)
    counts = stats(params, tokens, cfg)
    assert counts.shape == (4, 8)
    assert (np.asarray(counts.sum(-1)) == 2 * 32 * 3).all()
    first = moe.tokens_per_expert(
        params["embed"][tokens], params["blocks"]["router"][0], 3)
    np.testing.assert_array_equal(counts[0], first)
    after = dataclasses.replace(cfg, moe_router_input="post_attn_norm")
    assert not np.array_equal(stats(params, tokens, after)[0], first)
    run = jax.jit(llama.next_token_loss, static_argnums=2)
    assert abs(float(run(params, batch, cfg))
               - float(run(params, batch, after))) > 1e-5


def test_relu_is_not_silu():
    cfg = _patterned()
    params = llama.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg)
    a = float(llama.next_token_loss(params, batch, cfg))
    b = float(llama.next_token_loss(
        params, batch, dataclasses.replace(cfg, moe_expert_act="silu")))
    assert abs(a - b) > 1e-5


# -- the share of the experts ----------------------------------------------

def _one_layer(seed, router_bias=None, n=(2, 16), h=32, m=16, e=8):
    keys = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(keys[0], (*n, h))
    logits = jax.random.normal(keys[1], (*n, e))
    if router_bias is not None:
        logits = logits + jnp.asarray(router_bias, jnp.float32)
    gate_w = jax.random.normal(keys[2], (h, e))  # unused: logits given
    w_gate, w_up = (
        jax.random.normal(k, (e, h, m)) * h ** -0.5 for k in keys[3:5])
    w_down = jax.random.normal(keys[5], (e, m, h)) * m ** -0.5
    return x, gate_w, w_gate, w_up, w_down, logits


@pytest.mark.parametrize("case,bias", [
    ("even", None),
    # expert 1 in every token's top-3 and three experts in none: one
    # share's buffer takes a row of every token, another share's none
    ("one expert takes every token, several none",
     [0, 50, 0, 0, -50, -50, -50, 0]),
    # every token's three experts on one share: its N x k rows are all
    # held (the worst case), the other shares hold none
    ("every assignment on one share", [50, 50, 50, 50, 0, 0, 0, 0]),
])
@pytest.mark.parametrize("shares", [2, 4])
@pytest.mark.parametrize("shared", [False, True], ids=[
    "routed alone", "a shared expert and 2.5 on the weights"])
def test_the_shares_parts_add_up_to_the_uncut_layer(
        case, bias, shares, shared):
    """The guide's share test: the parts of one layer's result that
    all the shares give add up to what the layer that holds every
    expert gives, forward and in the gradient of its input; routing,
    weights and aux are each share's own copy of the same numbers.
    With ``shared``: every share computes the shared expert's term
    alike, for its own tokens, so over the shares it counts once; the
    routed weights carry the factor, the shared term does not."""
    x, gate_w, w_gate, w_up, w_down, logits = _one_layer(3, bias)
    kw = dict(k=3, norm_topk_prob=True, z_coef=0.0, act="relu",
              logits=logits)
    alike = lambda x: 0.0  # noqa: E731: what every share computes alike
    if shared:
        keys = jax.random.split(jax.random.key(13), 3)
        ws_gate, ws_up = (
            jax.random.normal(k, (32, 24)) * 32 ** -0.5 for k in keys[:2])
        ws_down = jax.random.normal(keys[2], (24, 32)) * 24 ** -0.5
        kw.update(gate="sigmoid", norm_eps=1e-20, scaling=2.5,
                  shared=(ws_gate, ws_up, ws_down))
        alike = lambda x: (  # noqa: E731
            jax.nn.relu(x @ ws_gate) * (x @ ws_up)) @ ws_down

    def part(x, first, held):
        cut = slice(first, first + held)
        out, aux = moe.dropless_moe_mlp(
            x, gate_w, w_gate[cut], w_up[cut], w_down[cut],
            first_held=first, **kw)
        return out - alike(x), aux  # the share's routed part

    def uncut(x):
        out, aux = part(x, 0, 8)
        return out + alike(x), aux  # the shared term counted once

    whole, aux = uncut(x)
    held = 8 // shares
    parts = [part(x, first, held) for first in range(0, 8, held)]
    np.testing.assert_allclose(
        sum(out for out, _ in parts) + alike(x), whole,
        rtol=1e-5, atol=1e-5)
    if shared:
        assert float(jnp.abs(alike(x)).max()) > 0.1
        # the factor is on the routed weights alone: the same layer
        # at 1 gives the routed parts' sum over 2.5 and the same
        # shared term
        unit = moe.dropless_moe_mlp(
            x, gate_w, w_gate, w_up, w_down, **{**kw, "scaling": 1.0})[0]
        np.testing.assert_allclose(
            2.5 * (unit - alike(x)) + alike(x), whole,
            rtol=1e-5, atol=1e-5)
    for _, share_aux in parts:
        assert float(share_aux) == pytest.approx(float(aux), rel=1e-6)
    counts = np.asarray(moe.logits_per_expert(logits, 3))
    if case.startswith("one expert"):
        assert counts.max() == 32 and (counts == 0).sum() == 3
    elif case.startswith("every"):
        assert counts[:4].sum() == 32 * 3
    if (case == "every assignment on one share" and shares == 2
            and not shared):
        np.testing.assert_allclose(
            parts[0][0], whole, rtol=1e-5, atol=1e-5)
        assert float(jnp.abs(parts[1][0]).max()) == 0.0

    def through(fn):
        return jax.grad(lambda x: jnp.sum(fn(x) ** 2))(x)

    summed = through(lambda x: alike(x) + sum(
        part(x, first, held)[0] for first in range(0, 8, held)))
    np.testing.assert_allclose(
        summed, through(lambda x: uncut(x)[0]), rtol=1e-4, atol=1e-5)


def test_a_share_leaves_no_row_of_an_absent_expert_in_a_group():
    """``group_sizes`` are the held experts' counts, and what the
    absent ones would have added is left out, not approximated."""
    x, gate_w, w_gate, w_up, w_down, logits = _one_layer(5)
    counts = np.asarray(moe.logits_per_expert(logits, 3))
    seen = {}
    from dlrover_tpu.ops import grouped_matmul as gm

    def spy(lhs, rhs, group_sizes, filled=True):
        seen["sizes"], seen["filled"] = np.asarray(group_sizes), filled
        seen["rows"] = lhs.shape[0]
        return real(lhs, rhs, group_sizes, filled)

    real, gm.grouped_matmul = gm.grouped_matmul, spy
    try:
        with jax.disable_jit():
            moe.dropless_moe_mlp(
                x, gate_w, w_gate[2:5], w_up[2:5], w_down[2:5], k=3,
                logits=logits, first_held=2)
    finally:
        gm.grouped_matmul = real
    np.testing.assert_array_equal(seen["sizes"], counts[2:5])
    # one chunk of the walk: the 96 assignments are fewer than
    # ``CHUNK_ROWS`` (chunks and their edges: test_moe_share_walk.py)
    assert seen["filled"] is False and seen["rows"] == 32 * 3


def test_share_gauge_and_refusal_over_an_expert_axis():
    cfg = _patterned()
    params = llama.init_params(jax.random.key(0), cfg)
    counts = llama.routing_stats(params, _batch(cfg)[0], cfg)
    share = moe.set_rows_held_gauge(counts, 0, 4)
    assert share == pytest.approx(
        float(counts[:, :4].sum()) / float(counts.sum()))
    assert 0.2 < share < 0.8
    from dlrover_tpu.telemetry.registry import default_registry

    assert f"moe_rows_held_share {share}" in (
        default_registry().to_prometheus_text())
    assert moe.set_rows_held_gauge(counts, 0, 8) == 1.0
    mesh = create_mesh([("data", 2), ("expert", 4)])
    with pytest.raises(ValueError, match="share of the experts"):
        make_trainer_for_llama(cfg, mesh, strategy="ddp")
