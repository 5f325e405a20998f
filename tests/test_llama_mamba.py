"""models/llama.py's ``mamba`` operator (a Mamba-1 mixer in a
two-branch block beside attention, kept by ``layer_types``): the plan,
the leaves with their axes, dtypes and draws, the stack against the
mixer's equations by hand, the scopes in every op's name under every
remat policy, the counter, what the config refuses and what the trainer
says of a mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.telemetry.registry import counter

SEQ = 64
TYPES = ("mamba", "mamba", "full_attention", "mamba")
SCOPES = ("mamba.in_proj", "mamba.conv", "mamba.x_proj", "mamba.dt",
          "mamba.scan", "mamba.gate", "mamba.out_proj", "attn.full")


def jamba_tiny(**kw):
    """Three mixers of 128 channels of 16 states around one attention
    layer of 3 query heads on 1 key head, no positions, a tied head."""
    return llama.llama_tiny(**{**dict(
        num_layers=4, layer_types=TYPES, rope_layout=(0,) * 4,
        hidden_size=48, num_heads=3, num_kv_heads=1, intermediate_size=64,
        mamba_dt_rank=5, tie_word_embeddings=True, norm_eps=1e-6,
        dtype=jnp.float32, remat="off",
    ), **kw})


def batch(cfg, sequences=2, seq=SEQ):
    tokens = jax.random.randint(
        jax.random.key(1), (sequences, seq), 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def test_the_plan_the_leaves_and_the_counts():
    cfg = jamba_tiny()
    lead, period = cfg.layer_plan()
    assert lead == ()
    assert [(k.operator, k.rope, k.window, k.ffn) for k in period] == [
        (t, False, None, "dense") for t in TYPES]
    assert cfg.by_position and cfg.mamba_widths == (96, 16, 5)
    assert llama.operator_layers(cfg) == {"mamba": 3, "full_attention": 1}
    assert "mamba" in llama.OPERATORS
    assert "mamba" in llama.ONE_DEVICE_OPERATORS
    params = llama.init_params(jax.random.key(0), cfg)
    mixer, attention = params["period"][0], params["period"][2]
    assert set(attention) == {
        "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate", "w_up",
        "w_down"}
    shapes = {name: leaf.shape[1:] for name, leaf in mixer.items()}
    assert shapes == {
        "attn_norm": (48,), "mlp_norm": (48,), "w_gate": (48, 64),
        "w_up": (48, 64), "w_down": (64, 48), "mamba_in": (48, 192),
        "mamba_x": (96, 37), "mamba_dt": (5, 96), "mamba_out": (96, 48),
        "mamba_dt_norm": (5,), "mamba_b_norm": (16,), "mamba_c_norm": (16,),
        "mamba_conv_w": (96, 4), "mamba_conv_b": (96,), "A_log": (96, 16),
        "dt_bias": (96,), "D": (96,)}
    # the decay's leaves, the skip, the convolution's bias and the
    # norms in float32 whatever the config's dtype
    half = llama.init_params(jax.random.key(0), jamba_tiny(
        dtype=jnp.bfloat16))["period"][0]
    for name, leaf in half.items():
        matrix = name in ("mamba_in", "mamba_x", "mamba_dt", "mamba_out",
                          "mamba_conv_w", "w_gate", "w_up", "w_down")
        assert leaf.dtype == (jnp.bfloat16 if matrix else jnp.float32), name
    # the S4D-real start, D at one, the bias at zero, the step in
    # [0.001, 0.1]
    assert np.allclose(np.exp(mixer["A_log"][0]),
                       np.broadcast_to(np.arange(1, 17), (96, 16)))
    assert not mixer["mamba_conv_b"].any() and (mixer["D"] == 1).all()
    step = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 0.1 + 1e-6
    assert "lm_head" not in params  # tied
    axes = llama.param_axes(cfg)["period"][0]
    assert axes["mamba_in"] == ("layers", "embed", "mlp")
    assert axes["mamba_x"] == ("layers", "mlp", None)
    assert axes["mamba_dt"] == ("layers", None, "mlp")
    assert axes["mamba_out"] == ("layers", "mlp", "embed")
    assert axes["A_log"] == ("layers", "mlp", None)
    assert axes["D"] == axes["dt_bias"] == ("layers", "norm")
    for at, layer in zip(llama.param_axes(cfg)["period"], params["period"]):
        assert set(at) == set(layer)
        assert all(len(at[name]) == layer[name].ndim for name in layer)
    assert llama.param_count(cfg) == sum(
        x.size for x in jax.tree.leaves(params))
    # 6N and the one attention layer's every earlier key; the
    # recurrence is not counted
    assert llama.flops_per_token(cfg, SEQ) == (
        6.0 * llama.param_count(cfg) + 6 * 3 * 32 * SEQ)
    # no layer rotates: no table of angles is built
    assert llama._rope_tables_of(cfg, SEQ) == (None, None)


def by_hand(cfg, params, tokens):
    """The stack as the config's comment has a mixer, its state a
    position at a time."""
    eps = cfg.norm_eps
    x = params["embed"][tokens]
    b, s, _ = x.shape
    d, n, rank = cfg.mamba_widths
    for i, kind in enumerate(cfg.layer_plan()[1]):
        p = jax.tree.map(lambda a: a[0], params["period"][i])
        y = llama.rms_norm(x, p["attn_norm"], eps)
        if kind.operator == "mamba":
            u, z = jnp.split(y @ p["mamba_in"], 2, axis=-1)
            taps = p["mamba_conv_w"]
            u = jax.nn.silu(sum(
                taps[:, j] * jnp.pad(u, ((0, 0), (3 - j, 0), (0, 0)))[:, :s]
                for j in range(4)) + p["mamba_conv_b"])
            low = u @ p["mamba_x"]
            dt = llama.rms_norm(low[..., :rank], p["mamba_dt_norm"], eps)
            B = llama.rms_norm(
                low[..., rank:rank + n], p["mamba_b_norm"], eps)
            C = llama.rms_norm(low[..., rank + n:], p["mamba_c_norm"], eps)
            delta = jax.nn.softplus(dt @ p["mamba_dt"] + p["dt_bias"])
            A = -jnp.exp(p["A_log"])

            def step(h, at):
                u_t, delta_t, b_t, c_t = at
                h = jnp.exp(delta_t[..., None] * A) * h + (
                    delta_t * u_t)[..., None] * b_t[:, None]
                return h, jnp.einsum("bdn,bn->bd", h, c_t) + p["D"] * u_t

            _, o = jax.lax.scan(
                step, jnp.zeros((b, d, n)),
                tuple(jnp.moveaxis(a, 1, 0) for a in (u, delta, B, C)))
            o = jnp.moveaxis(o, 0, 1)
            x = x + (o * jax.nn.silu(z)) @ p["mamba_out"]
        else:
            q, k, v = ((y @ p[w]).reshape(b, s, -1, cfg.head_dim)
                       for w in ("wq", "wk", "wv"))
            scores = jnp.einsum("bqhd,bkd->bhqk", q, k[:, :, 0])
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                               scores * cfg.head_dim ** -0.5, -jnp.inf)
            out = jnp.einsum(
                "bhqk,bkd->bqhd", jax.nn.softmax(scores, -1), v[:, :, 0])
            x = x + out.reshape(b, s, -1) @ p["wo"]
        y = llama.rms_norm(x, p["mlp_norm"], eps)
        x = x + (jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])) @ p["w_down"]
    return llama.rms_norm(x, params["final_norm"], eps)


def drawn(params):
    """The leaves the program starts where they change nothing (the
    bias at zero, ``D`` and the norms' scales at one), drawn."""
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name == "mamba_conv_b":
            return 0.3 * jax.random.normal(next(keys), leaf.shape)
        if name in ("D", "mamba_dt_norm", "mamba_b_norm", "mamba_c_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_the_stack_is_the_mixers_equations():
    cfg = jamba_tiny()
    params = drawn(llama.init_params(jax.random.key(0), cfg))
    tokens, _ = batch(cfg)
    from dlrover_tpu.ops.attention import mha_reference
    got, _ = llama.hidden_states(
        params, tokens, cfg, attn_fn=lambda q, k, v: mha_reference(
            q, k, v, causal=True))
    want = by_hand(cfg, params, tokens)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(want).max()) > 0.01


@pytest.mark.parametrize("remat", ["off", "dots", "dots_attn_out", "minimal"])
def test_the_scopes_name_every_stage_under_every_remat_policy(remat):
    cfg = jamba_tiny(remat=remat)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    tok = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    calls = counter("selective_scan_plain_calls", "")
    before = calls.value
    text = jax.jit(jax.grad(
        lambda p, t: llama.next_token_loss(p, (t, t), cfg))
    ).lower(params, tok).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    assert "rope" not in text
    # counted where the entry is traced: once a mixer, and once more
    # where the policy traces the layer's body again for its backward
    assert (calls.value - before) % 3 == 0 and calls.value > before


def test_remat_changes_neither_the_loss_nor_a_gradient():
    losses, grads = [], []
    for remat in ("off", "minimal"):
        cfg = jamba_tiny(remat=remat)
        params = llama.init_params(jax.random.key(0), cfg)
        loss, grad = jax.value_and_grad(
            lambda p: llama.next_token_loss(p, batch(cfg), cfg))(params)
        losses.append(float(loss))
        grads.append(grad)
    assert losses[0] == losses[1]
    for a, b in zip(*map(jax.tree.leaves, grads)):
        assert float(jnp.abs(a - b).max()) < 1e-6
    # every leaf of a mixer is trained
    for name, g in grads[0]["period"][0].items():
        assert float(jnp.abs(g).max()) > 0, name


def test_the_least_decay_is_a_channels_fastest_state():
    cfg = jamba_tiny()
    params = llama.init_params(jax.random.key(0), cfg)
    tokens, _ = batch(cfg)
    least = llama.decay_min(params, tokens, cfg)
    assert least.shape == (4,) and float(least[2]) == 1.0  # attention
    assert all(0.0 <= float(a) < 1.0 for a in least[jnp.array([0, 1, 3])])


@pytest.mark.parametrize("field,value,says", [
    ("num_experts", 4, "experts"),
    ("post_norms", True, "experts"),
    ("mtp_layers", 1, "prediction module"),
    ("total_ut_steps", 2, "kept by position"),
    ("mamba_dt_rank", 0, "mamba_dt_rank 0"),
    ("layer_types", ("mamba", "mamba2", "full_attention", "mamba"),
     "layer_types names"),
])
def test_what_is_not_built_is_refused(field, value, says):
    with pytest.raises(ValueError, match=says):
        jamba_tiny(**{field: value})


def test_the_trainer_refuses_a_mesh_and_sets_the_operators_gauge():
    from jax.sharding import Mesh

    from dlrover_tpu.telemetry.registry import gauge
    from dlrover_tpu.trainer.sharded import make_trainer_for_llama

    cfg = jamba_tiny()
    make_trainer_for_llama(cfg, Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "fsdp")))
    layers = gauge("dlrover_model_operator_layers", "", ("operator",))
    assert layers.labels(operator="mamba").value == 3
    assert layers.labels(operator="full_attention").value == 1
    assert layers.labels(operator="state_space").value == 0
    if len(jax.devices()) > 1:
        two = np.array(jax.devices()[:2])
        for axes, shape in ((("data", "fsdp"), (1, 2)),
                            (("data", "fsdp"), (2, 1)),
                            (("data", "seq"), (1, 2))):
            with pytest.raises(ValueError, match="whole sequences") as e:
                make_trainer_for_llama(
                    cfg, Mesh(two.reshape(shape), axes),
                    strategy="sequence" if "seq" in axes else "fsdp")
            assert "'mamba'" in str(e.value)
            assert axes[shape.index(2)] in str(e.value)
