"""The ``kimi`` family: its program (models/llama.py with Kimi Delta
Attention in three layers of four and in the leading dense layer,
latent attention without positions and with q by one matrix in the
fourth, a sigmoid router that selects by a biased score and scales
its weights, a shared expert and a share of the routed ones) against
``references/kimi.py`` at the tiny size, in the loss and in every
leaf's gradient, each term of the block showing when it is changed;
the shares adding up to the uncut layer; its counts against integers
worked by hand; what the configuration's file states."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import moe
from yardstick import cells, counts, reference, worker
from yardstick.families import kimi

SEQ, SEQUENCES = 128, 4
NAME = "kimi-linear-48b-a3b-ep16"
CELL = NAME + ".steady"
REFERENCE = os.path.join(cells.HERE, "references", "kimi.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-kimi"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with every selection bias drawn at 0.3, the heads'
    norm's scale and the kv latent's at 1 +/- 0.5 and the output
    gate's bias at 0.3 (the program starts them at zero, one and zero,
    where they change nothing), and the head at three times its fan-in
    deviation: over random targets a changed trunk moves the mean loss
    by a sum of mean zero over the positions, whose size goes with the
    logits'."""
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name in ("expert_bias", "g_bias"):
            return 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name in ("o_norm", "kv_a_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 3.0 if name == "lm_head" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config(NAME), {"seq": 16384, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (2304, 9216, 1024)
    assert (cfg.num_heads, cfg.num_kv_heads) == (32, 32)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (
                None, 512, 128, 64, 128)
    assert cfg.latent and not cfg.rope_interleave
    assert (cfg.linear_num_heads, cfg.linear_head_dim,
            cfg.linear_conv_size, cfg.linear_gate_rank) == (32, 128, 4, 128)
    assert cfg.linear_allow_neg_eigval is False  # beta = sigmoid, no 2
    assert (cfg.num_experts, cfg.moe_top_k) == (256, 8)  # the router's
    assert (cfg.moe_first_expert_held, cfg.moe_experts_held) == (0, 16)
    assert cfg.moe_gate == "sigmoid" and cfg.use_expert_bias is True
    assert (cfg.moe_routed_scaling, cfg.moe_topk_norm_eps,
            cfg.moe_shared_experts) == (2.446, 1e-20, 1)
    assert cfg.norm_topk_prob is True
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.0)
    assert cfg.norm_eps == 1e-5 and not cfg.tie_word_embeddings
    assert cfg.mtp_layers == 0 and cfg.num_dense_layers == 1
    assert cfg.rope_layout == (0,) * 5  # mla_use_nope
    lead, period = cfg.layer_plan()
    assert [(k.operator, k.ffn, k.rope) for k in lead] == [
        ("linear_attention", "dense", False)]
    assert [(k.operator, k.ffn, k.rope, k.window) for k in period] == [
        ("linear_attention", "experts", False, None),
        ("linear_attention", "experts", False, None),
        ("latent_attention", "experts", False, None),
        ("linear_attention", "experts", False, None)]
    assert llama.operator_layers(cfg) == {
        "linear_attention": 4, "latent_attention": 1}
    # a layer's operator: the delta rule's four 9.44 M matrices, two
    # low ranks of 0.82 M, the step size's 0.07 M, the taps and the
    # vectors; latent attention's q 14.16 M, the way down 1.33 M, the
    # way up 4.19 M, the output 9.44 M and the latent's norm
    linear = (4 * 2304 * 4096 + 2 * 128 * (2304 + 4096) + 2304 * 32
              + 3 * 4096 * 4 + 2 * 4096 + 32 + 128)
    latent = (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256
              + 4096 * 2304 + 512)
    experts = 2304 * 256 + 256 + 17 * 3 * 2304 * 1024
    dense, norms = 3 * 2304 * 9216, 2 * 2304
    assert (linear, latent, experts, dense) == (
        39_518_368, 29_114_880, 120_914_176, 63_700_992)
    layers = (5 * norms + 4 * linear + latent + dense + 4 * experts)
    assert llama.param_count(cfg) == layers + 2 * 20480 * 2304 + 2304
    assert llama.param_count(cfg) == 828_943_232  # 4.97 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 828_943_232
    assert shapes["lead"][0]["w_gate"].shape == (2304, 9216)
    assert shapes["lead"][0]["f_b"].shape == (128, 4096)
    assert shapes["period"][2]["wq"].shape == (1, 2304, 32 * 192)
    assert shapes["period"][2]["wkv_a"].shape == (1, 2304, 576)
    assert not {"wq_a", "wq_b", "q_a_norm"} & set(shapes["period"][2])
    assert shapes["period"][3]["w_gate"].shape == (1, 16, 2304, 1024)
    assert shapes["period"][0]["router"].shape == (1, 2304, 256)


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


@pytest.mark.parametrize("seed", [3, 5, 6])
def test_bf16_program_is_inside_the_chip_tolerance(seed):
    """At the tiny size the bf16 reading is the noise of flipped
    top-4 choices over a thousand positions (8 of 16 experts held)
    behind 64-wide streams: seeds 1-12 read 0.00008-0.016, as the
    same operators do in ``tiny-solar`` (0.00004-0.015), and these
    three 0.0005, 0.00008 and 0.0011; at the cell's widths the chip's
    runs say what the limit holds (PERF.md section 6). As the program
    starts, the bias at zero; the step jitted, as the worker's is."""
    cfg_file, cfg, params, batch = _case("bfloat16", False, 8, seed=seed)
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(jax.jit(
        lambda p, b: llama.next_token_loss(p, b, cfg))(params, batch))
    assert abs(program - ref) < worker.REFERENCE_TOLERANCE


def test_remat_and_chunked_loss_change_nothing():
    cfg_file, cfg, params, batch = _case("float32")
    want = float(llama.next_token_loss(params, batch, cfg))
    for remat, chunk in (("minimal", 0), ("dots", 256),
                         ("dots_attn_out", 0)):
        other = worker.program_config(
            cfg_file, {"seq": SEQ, "remat": remat, "loss_chunk": chunk})
        got = jax.jit(
            lambda p, b: llama.next_token_loss(p, b, other))(params, batch)
        assert float(got) == pytest.approx(want, abs=2e-5), (remat, chunk)


def test_every_leafs_gradient_is_the_references():
    """Float32, remat ``minimal`` as the cell runs it: every leaf of
    both operators, of the leading dense layer and of the experts
    against ``jax.grad`` of the reference. No gradient reaches the
    selection bias, on either side."""
    cfg_file, _, params, batch = _case("float32", sequences=2)
    want = jax.grad(lambda p: reference.loss(cfg_file, p, *batch))(params)
    cfg = worker.program_config(cfg_file, {**TRAFFIC, "remat": "minimal"})
    got = jax.jit(jax.grad(
        lambda p: llama.next_token_loss(p, batch, cfg)))(params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    seen = set()
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        seen.add(name)
        scale = float(jnp.abs(w).max())
        if name == "expert_bias":
            assert scale == 0 and float(jnp.abs(g).max()) == 0
            continue
        assert scale > 0, path
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale, path
    assert {"wq", "wkv_a", "wkv_b", "kv_a_norm", "f_a", "g_b", "w_beta",
            "A_log", "dt_bias", "conv_k", "o_norm", "g_bias", "router",
            "w_gate", "ws_down", "embed", "lm_head"} <= seen


def edited(name, *pairs):
    """A scratch copy of the reference with ``pairs`` replaced."""
    with open(REFERENCE) as f:
        src = f.read()
    for old, new in pairs:
        assert src.count(old) == 1, old
        src = src.replace(old, new)
    spec = importlib.util.spec_from_loader(f"scratch_ref_{name}", None)
    module = importlib.util.module_from_spec(spec)
    exec(compile(src, name, "exec"), module.__dict__)
    return module


SCORED = (
    "    return attention(\n"
    "        q[..., :nope], q[..., nope:], kv[..., :nope], k_r, "
    "kv[..., nope:]\n"
    "    ) @ p[\"wo\"]\n"
)
#: the controls of ISSUE 60, and a few of the block's other terms, as
#: edits to the reference
CONTROLS = {
    "rotation put back on the latent layer": ((
        SCORED,
        "    from yardstick.reference import rotate\n"
        "    q_r = rotate(q[..., nope:], 10000.0)\n"
        "    k_r = rotate(k_r[:, :, None, :], 10000.0)[:, :, 0]\n"
        "    return attention(\n"
        "        q[..., :nope], q_r, kv[..., :nope], k_r, kv[..., nope:]\n"
        "    ) @ p[\"wo\"]\n"),),
    # head h reads the one key's columns rolled by 2 h: a key of its
    # own made of the same numbers
    "k_r a head's own": ((
        '            + jnp.einsum("bqhd,bkd->bhqk", qr, k_r)\n',
        '            + jnp.einsum("bqhd,bkhd->bhqk", qr, jnp.stack(\n'
        '                [jnp.roll(k_r, 2 * h, -1) for h in range(heads)],'
        ' 2))\n'),),
    "the factor 2 on beta": ((
        '    beta = jax.nn.sigmoid(y @ p["w_beta"])\n',
        '    beta = 2.0 * jax.nn.sigmoid(y @ p["w_beta"])\n'),),
    "the factor 2.446 left out": ((
        "    picked = picked * scaling\n", ""),),
    # the period as solar has it, [MLA, KDA, KDA, KDA]: the same
    # weights, each operator's in its own layers (``exchanged``)
    "latent attention in the first place of the period": ((
        '        "latent_attention" if l in full else "linear_attention"\n',
        '        "latent_attention" if l + 2 in full else '
        '"linear_attention"\n'),),
    "decay left out": ((
        "        state = jnp.exp(g_t)[..., None] * state\n", ""),),
    "q through a norm of its own": ((
        '    q = (y @ p["wq"]).reshape(b, s, heads, -1)\n',
        '    q = (y @ p["wq"]).reshape(b, s, heads, -1)\n'
        '    q = rms_norm(q, jnp.ones(q.shape[-1]), eps)\n'),),
    "no norm on the kv latent": ((
        '    c = rms_norm(down[..., :rank], p["kv_a_norm"], eps)\n',
        '    c = down[..., :rank]\n'),),
    "the scores' scale from the nope columns alone": ((
        "            keep, scores / jnp.sqrt(F32(nope + rope)), -jnp.inf\n",
        "            keep, scores / jnp.sqrt(F32(nope)), -jnp.inf\n"),),
    "no l2norm on q and k": (
        ('    q = l2norm(by_head(conv_silu(y @ p["wq"], p["conv_q"])))\n',
         '    q = by_head(conv_silu(y @ p["wq"], p["conv_q"]))\n'),
        ('    k = l2norm(by_head(conv_silu(y @ p["wk"], p["conv_k"])))\n',
         '    k = by_head(conv_silu(y @ p["wk"], p["conv_k"]))\n')),
    "three taps for four": ((
        "    for j in range(taps):\n", "    for j in range(1, taps):\n"),),
    "no output gate on the delta rule": ((
        '    o = o * jax.nn.sigmoid(y @ p["g_a"] @ p["g_b"] + p["g_bias"])\n',
        ""),),
    "no norm on the heads' result": ((
        '    o = rms_norm(o, p["o_norm"], eps).reshape(b, s, -1)\n',
        "    o = o.reshape(b, s, -1)\n"),),
    "no shared expert": ((
        '    total = total + gated(y, p["ws_gate"], p["ws_up"], '
        'p["ws_down"])\n', ""),),
    "weights not renormalised": (("    if norm_topk:\n",
                                  "    if False:\n"),),
    "top-4 of s without the bias": ((
        'jax.lax.top_k(score + p["expert_bias"], per_token)',
        "jax.lax.top_k(score, per_token)"),),
    "the leading layer's MLP without its gate": ((
        '            return x + gated(y, p["w_gate"], p["w_up"], '
        'p["w_down"]), F32(0.0)\n',
        '            return x + (y @ p["w_up"]) @ p["w_down"], F32(0.0)\n'),),
}
#: the reference in the nearest precision below the program's
#: bfloat16: every matrix and the two normed streams a layer rounded
#: to float8 (e4m3, a scale a tensor), the sums in float32
FLOAT8 = (
    ('EXPERTS = ("w_gate", "w_up", "w_down")\n',
     'EXPERTS = ("w_gate", "w_up", "w_down")\n\n\n'
     'def q8(a):\n'
     '    s = jnp.max(jnp.abs(a)) / 448.0\n'
     '    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s\n'),
    ('        y = rms_norm(x, p["attn_norm"], eps)\n',
     '        p = {k: q8(v) if v.ndim > 1 else v for k, v in p.items()}\n'
     '        y = q8(rms_norm(x, p["attn_norm"], eps))\n'),
    ('        y = rms_norm(x, p["mlp_norm"], eps)\n',
     '        y = q8(rms_norm(x, p["mlp_norm"], eps))\n'),
    ('    return one_layer[e].astype(F32)\n',
     '    return q8(one_layer[e].astype(F32))\n'),
)


def exchanged(params, term=""):
    """``params`` as a reference whose period is ``[MLA, KDA, KDA,
    KDA]`` reads them: the latent layer's stack first, the delta-rule
    layers' behind it in their order (``benchmarks/controls.py``
    hands a control with "place" in its name what this returns)."""
    a, b, latent, c = params["period"]
    return {**params, "period": [latent, a, b, c]}


@pytest.fixture(scope="module")
def float32_cases():
    """Two batches on the same weights, each with the program's
    loss: a changed term's reading is a sum of mean zero over the
    positions, and on one batch in ten it lands inside the
    tolerance."""
    cases = [_case("float32", seed=seed) for seed in (7, 8)]
    return [
        (case, float(llama.next_token_loss(case[2], case[3], case[1])))
        for case in cases
    ]


def most_off(changed, cases, term=""):
    """The larger |program - changed reference| of the batches."""
    return max(
        abs(program - float(changed.loss(
            cfg_file, exchanged(params) if "place" in term else params,
            *batch)))
        for (cfg_file, _, params, batch), program in cases
    )


@pytest.mark.parametrize("term", list(CONTROLS))
def test_a_changed_term_shows(term, float32_cases):
    """A reference with one term of the block altered is off by more
    than the chip's tolerance, in float32, where the unchanged pair
    agrees to 2e-5 (the biases, the norms' scales and the head
    drawn: ``drawn``)."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases, term)
    # off by more than the tolerance, or no number at all (without
    # the norm on k the recurrence's update is no contraction)
    assert not difference <= worker.REFERENCE_TOLERANCE, (term, difference)


def test_the_exchanged_weights_change_nothing_but_the_order():
    """What the order control is compared with: the reference's
    ``operators`` names the program's layers (``layer_types`` of the
    family), and the exchanged period is the same four stacks."""
    c = config("tiny-kimi")
    ref = edited("same")
    assert ref.operators(c) == kimi.layer_types(c) == (
        "linear_attention",) * 3 + ("latent_attention", "linear_attention")
    shifted = edited("order", *CONTROLS[
        "latent attention in the first place of the period"])
    assert shifted.operators(c) == (
        "linear_attention", "latent_attention") + ("linear_attention",) * 3
    cfg_file, _, params, _ = _case("float32", sequences=1)
    moved = exchanged(params)
    assert "wkv_a" in moved["period"][0] and "f_a" in moved["period"][2]


def test_the_reference_in_float8_shows(float32_cases):
    assert most_off(
        edited("float8", *FLOAT8), float32_cases
    ) > worker.REFERENCE_TOLERANCE


def test_reference_refuses_more_positions_than_the_source_declares():
    cfg_file, _, params, batch = _case("float32")
    with pytest.raises(ValueError):
        reference.loss({**cfg_file, "model_max_length": 64}, params, *batch)


def test_reference_imports_no_line_of_the_program():
    with open(REFERENCE) as f:
        src = f.read()
    imports = [ln for ln in src.splitlines(True)
               if ln.startswith(("import ", "from "))]
    assert imports == [
        "import functools\n", "import jax\n", "import jax.numpy as jnp\n",
        "from yardstick.reference import (\n",
    ]
    body = src.split('"""', 2)[2]
    assert "dlrover_tpu" not in body and "delta_rule" not in body
    assert "lax.scan" in body and "cumsum" not in body  # token by token
    assert "rotate" not in body and "HIGHEST" in body
    with open(os.path.join(cells.HERE, "families", "kimi.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_recurrence_is_the_programs_scan():
    """The reference's token-by-token walk against the program's
    chunked entry (ops/delta_rule.py) on operands of their own, the
    step size in (0, 1) as this family's is."""
    from dlrover_tpu.ops.delta_rule import gated_delta_rule

    ref = edited("recurrence")
    keys = jax.random.split(jax.random.key(3), 5)
    q, k, v = (jax.random.normal(key, (2, 96, 3, 16)) for key in keys[:3])
    g = -jax.random.uniform(keys[3], (2, 96, 3, 16), maxval=3.0)
    beta = jax.random.uniform(keys[4], (2, 96, 3))
    want = ref.recurrence(q, k, v, g, beta)
    got = gated_delta_rule(q, k, v, g, beta)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_reference_attention_walks_rows_in_blocks():
    """In blocks of query rows, and against the program's plain
    attention on the parts (``mha_reference``, as the program calls it
    off the TPU)."""
    ref = edited("rows")
    keys = jax.random.split(jax.random.key(3), 5)
    q, k, v = (jax.random.normal(key, (1, 64, 4, 16)) for key in keys[:3])
    q_r = jax.random.normal(keys[3], (1, 64, 4, 8))
    k_r = jax.random.normal(keys[4], (1, 64, 8))
    whole = ref.attention(q, q_r, k, k_r, v, rows=64)
    parts = ref.attention(q, q_r, k, k_r, v, rows=8)
    assert float(jnp.abs(whole - parts).max()) < 1e-5
    from dlrover_tpu.ops.attention import mha_reference

    want = mha_reference(
        q, k, v, causal=True, q_rope=q_r, k_rope=k_r[:, :, None, :]
    ).reshape(1, 64, -1)
    assert float(jnp.abs(whole - want).max()) < 1e-5


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-kimi")
    for key, other in (
            ("q_lora_rank", 48), ("mla_use_nope", False),
            ("num_nextn_predict_layers", 1), ("num_expert_group", 2),
            ("topk_group", 2), ("tie_word_embeddings", True),
            ("moe_router_activation_func", "softmax"),
            ("num_key_value_heads", 2)):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**tiny, key: other}, TRAFFIC)
    raw = worker.program_config({**tiny, "moe_renormalize": False}, TRAFFIC)
    assert raw.norm_topk_prob is False
    assert kimi.layer_types({**tiny, "num_hidden_layers": 8}) == (
        ("linear_attention",) * 3 + ("latent_attention",)) * 2
    with pytest.raises(ValueError, match="neither"):
        kimi.layer_types({**tiny, "num_hidden_layers": 9})
    both = {**tiny["linear_attn_config"], "kda_layers": [1, 2, 3, 4]}
    with pytest.raises(ValueError, match="kda_layers and in"):
        kimi.layer_types({**tiny, "linear_attn_config": both})


# -- the share ---------------------------------------------------------------

def test_the_16_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's number: 16 shares of
    one expert each, of a router 16 wide. The routed parts that the
    shares give, with the shared expert's term (which every share
    computes alike, for its own tokens) counted once, add up to what
    the layer that holds all 16 gives: in the reference, and in the
    program's layer; the weights times 2.446 in both."""
    ref = edited("share")
    h, m, width, k, scaling = 32, 16, 16, 4, 2.446
    keys = jax.random.split(jax.random.key(11), 8)
    y = jax.random.normal(keys[0], (2, 24, h))
    p = {
        "router": jax.random.normal(keys[1], (h, width)) * h ** -0.5,
        "expert_bias": 0.3 * jax.random.normal(keys[2], (width,)),
        "ws_gate": jax.random.normal(keys[3], (h, m)) * h ** -0.5,
        "ws_up": jax.random.normal(keys[4], (h, m)) * h ** -0.5,
        "ws_down": jax.random.normal(keys[5], (m, h)) * m ** -0.5,
    }
    whole = {
        "w_gate": jax.random.normal(keys[6], (1, width, h, m)) * h ** -0.5,
        "w_up": jax.random.normal(keys[7], (1, width, h, m)) * h ** -0.5,
        "w_down": jax.random.normal(keys[0], (1, width, m, h)) * m ** -0.5,
    }
    shared = ref.gated(y, p["ws_gate"], p["ws_up"], p["ws_down"])
    with reference.HIGHEST():
        uncut, balance = ref.experts(
            y, whole, p, 0, k, 0, True, 1e-20, scaling)
        parts = []
        for rank in range(width):
            one = {n: w[:, rank:rank + 1] for n, w in whole.items()}
            part, same = ref.experts(
                y, one, p, 0, k, rank, True, 1e-20, scaling)
            assert float(same) == float(balance)  # over all 16, held or not
            parts.append(part - shared)
        unscaled, _ = ref.experts(y, whole, p, 0, k, 0, True, 1e-20, 1.0)
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(parts)).max()) > 0.1
    assert float(jnp.abs(
        scaling * (unscaled - shared) - (uncut - shared)).max()) < 1e-5
    # a token's four experts are on four of the 16 shares
    live = sum(float(jnp.abs(part[0, 0]).max()) > 0 for part in parts)
    assert live == k

    def program(first, held):
        out, _ = moe.dropless_moe_mlp(
            y, p["router"], *(whole[n][0, first:first + held]
                              for n in ("w_gate", "w_up", "w_down")),
            k=k, norm_topk_prob=True, z_coef=0.0, first_held=first,
            shared=(p["ws_gate"], p["ws_up"], p["ws_down"]),
            gate="sigmoid", bias=p["expert_bias"], norm_eps=1e-20,
            scaling=scaling)
        return out

    mine = sum(program(rank, 1) - shared for rank in range(width)) + shared
    assert float(jnp.abs(mine - uncut).max()) < 1e-4
    assert float(jnp.abs(program(0, width) - uncut).max()) < 1e-4


# -- the counts --------------------------------------------------------------

def test_kimi_counts_by_hand():
    c = config(NAME)
    s = kimi.shape(c)
    assert (s["layers"], s["dense_layers"], s["attention_layers"],
            s["linear_layers"]) == (5, 1, 1, 4)
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["shared_experts"], s["ffn"], s["dense_ffn"]) == (
                256, 16, 8, 1, 1024, 9216)
    assert (s["heads"], s["kv_heads"], s["head_dim"], s["nope_dim"],
            s["rope_dim"], s["v_head_dim"], s["kv_rank"]) == (
                32, 32, 192, 128, 64, 128, 512)
    assert (s["linear_heads"], s["linear_head_dim"], s["taps"],
            s["gate_rank"]) == (32, 128, 4, 128)
    # in millions of weights met a token: a delta-rule layer's
    # projections 39.5, the latent layer's 29.1, the leading MLP 63.7,
    # the router 0.6, the shared expert 7.1 and half a held expert in
    # expectation, the head 47.2
    linear = 4 * 2304 * 4096 + 2 * 128 * (2304 + 4096) + 2304 * 32
    latent = (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256
              + 4096 * 2304)
    router, expert, head = 2304 * 256, 3 * 2304 * 1024, 2304 * 20480
    dense = 3 * 2304 * 9216
    assert (linear, latent, router, expert, dense, head) == (
        39_460_864, 29_114_368, 589_824, 7_077_888, 63_700_992,
        47_185_920)
    want = (4 * linear + latent + dense
            + 4 * (router + 1.5 * expert) + head)
    assert kimi.matmul_params(c) == counts.matmul_params(c) == want
    assert want == 342_671_360
    # scores at 192 columns and weighted values at 128, at 16,384, in
    # the one latent layer
    attn = counts.attention_forward_flops_per_token(c, 16384)
    assert attn == 32 * (192 + 128) * 16384 == 167_772_160
    flops = counts.train_flops_per_token(c, 16384)
    assert flops == 3 * (2 * want + attn) == 2_559_344_640
    forward = flops / 3
    assert 2 * 4 * linear / forward == pytest.approx(0.370, abs=2e-3)
    assert attn / forward == pytest.approx(0.197, abs=2e-3)
    assert 2 * latent / forward == pytest.approx(0.068, abs=2e-3)
    assert 2 * dense / forward == pytest.approx(0.149, abs=2e-3)
    assert 2 * head / forward == pytest.approx(0.111, abs=2e-3)
    assert 2 * 4 * expert / forward == pytest.approx(0.066, abs=2e-3)
    assert 2 * 4 * 0.5 * expert / forward == pytest.approx(0.033, abs=2e-3)
    # the attention kernels: seven causal products, four at 192 columns
    # and three at 128, one layer
    kernel_flops, nbytes = counts.attention_kernel_step(c, 1, 16384)
    assert kernel_flops == 32 * 16384 * 16384 * (4 * 192 + 3 * 128)
    assert nbytes == 16384 * 32 * 2 * (6 * 192 + 6 * 128)
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.050232, rel=1e-3)
    # the grouped matmuls: 16,384 x 8 x 16 / 256 = 8,192 rows a layer
    # on the 16 held experts, 512 an expert, over four layers
    flops, nbytes = kimi.expert_matmul_step(c, 16384)
    rows = 16384 * 8 * 16 // 256
    assert rows == 8192 and rows // 16 == 512
    assert flops == 4 * 3 * 2 * rows * 3 * 2304 * 1024
    weights = 3 * 16 * 3 * 2304 * 1024
    per_row = 2 * ((2304 + 1024) + (1024 + 2 * 2304)) + (
        (1024 + 2304) + (2304 + 2 * 1024))
    assert nbytes == 4 * 2 * (weights + rows * per_row)
    # the recurrence: 21 x 128 x 128 operations a token and head over
    # the four delta-rule layers
    flops, nbytes = kimi.delta_rule_step(c, 16384)
    assert flops == 4 * 21 * 16384 * 32 * 128 * 128
    column, betas = 16384 * 32 * 128, 16384 * 32 * 4
    assert nbytes == 4 * (
        (8 + 4) * column + betas + (8 + 4) * column + betas
        + (6 + 4) * column + betas)
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory"
    assert seconds == pytest.approx(0.011175, rel=1e-3)


def test_the_share_of_a_roofline_stays_under_100_at_the_kernels_least():
    """``delta_rule_roofline_pct`` in this cell with the kernels at
    the least time they could take: what the kernels move is more than
    the count's least bytes (the backward's entry states, 64 KB a chunk
    and head written and read, and the forward run twice under
    ``minimal`` are the implementation's), so the share is under 100
    there, and a reading above it is a wrong count."""
    from yardstick.layer_metrics import delta_rule_roofline_pct as share

    c = config(NAME)
    cell, _, traffic = cells.load_cell(CELL)
    peak = cells.peak_of("TPU v5 lite")
    flops, nbytes = kimi.delta_rule_step(c, 16384)
    column, chunks = 16384 * 32 * 128, 16384 // 64
    forward = (8 + 4) * column
    states = 4 * 2 * chunks * 32 * 128 * 128 * 4
    moved = nbytes + 4 * forward + states  # a second forward, the states
    least = moved / peak["hbm_bytes_per_s"]
    run = {"trace": {"steps": 4, "ops": [["delta_rule.7", 4 * least, 16]]},
           "peak": peak, "config": c, "traffic": traffic, "cell": cell}
    got = share.read(run)
    assert 50 < got < 100, got
    assert share.read({**run, "trace": None}) is None


def test_every_published_number_is_run_but_the_cut():
    c = config(NAME)
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 16, 20480)
    for key, value in (
            ("hidden_size", 2304), ("num_attention_heads", 32),
            ("num_key_value_heads", 32), ("head_dim", 72),
            ("kv_lora_rank", 512), ("q_lora_rank", None),
            ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
            ("v_head_dim", 128), ("intermediate_size", 9216),
            ("moe_intermediate_size", 1024), ("num_experts_per_token", 8),
            ("num_shared_experts", 1), ("routed_scaling_factor", 2.446),
            ("first_k_dense_replace", 1), ("mla_use_nope", True)):
        assert c[key] == c["published"][key] == value, key
    linear = c["linear_attn_config"]
    assert linear == c["published"]["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(linear["kda_layers"]) == 20
    assert kimi.layer_types(c) == ("linear_attention",) * 3 + (
        "latent_attention", "linear_attention")
    # every number of the catalog's row, under its key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
        assert c["published"] == row["config"]
        assert c["source"] == row["source_url"]
    share = c["share"]
    assert share["router_width"] == c["published"]["num_experts"] == 256
    assert (share["chips_sharing_a_layer"], share["rank"],
            share["first_expert_held"]) == (16, 0, 0)
    assert 8 * c["vocab_size"] == c["published"]["vocab_size"]
    assert 16 * c["num_experts"] == share["router_width"]
    assert c["depth"]["found"] == 5
    for key in ("attention", "kda_gate_rank", "kda_decay", "kda_beta",
                "kda_conv", "kda_out", "routing", "expert_bias",
                "router_aux_loss_coef", "embed_init_std", "topk_norm_eps",
                "layers", "max_seq_len", "optimizer_state"):
        assert key in c["assumed"], key
    for key in ("accepted_peak_memory_in_bytes", "refused", "how"):
        assert key in c["depth"], key
    assert len(c["deployment"]) > 200
    bench = cells.benchmark()
    (entry,) = [e for e in bench["configs"] if e["name"] == NAME]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "steady-1x16384", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for metric in ("delta_rule_ms", "delta_rule_roofline_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert CELL in m["workloads"], metric
    # not the experts' readers: 512 rows an expert are calls near the
    # 200 operation names a reduced trace keeps (PERF.md section 7),
    # and a list that names a cell obliges it; nor the convolution's
    for metric in ("moe_expert_ms", "moe_expert_roofline_pct",
                   "short_conv_ms", "short_conv_roofline_pct",
                   "ssd_ms", "ssd_roofline_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert CELL not in m["workloads"], metric
