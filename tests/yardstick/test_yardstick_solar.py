"""The ``solar`` family: its program (models/llama.py with the gated
delta rule behind four-tap convolutions in three layers of four, a
gated full attention without positions in the fourth, a sigmoid router
that selects by a biased score, a shared expert and a share of the
routed ones) against ``references/solar.py`` at the tiny size, each
term of the block showing when it is changed; the shares adding up to
the uncut layer; its counts against integers worked by hand; what the
configuration's file states."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import moe
from yardstick import cells, counts, reference, worker
from yardstick.families import solar

SEQ, SEQUENCES = 128, 4
CELL = "solar-open2-250b-ep32.steady"
REFERENCE = os.path.join(cells.HERE, "references", "solar.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-solar"), dtype=dtype)
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
    norm's scale at 1 +/- 0.5 and the output gate's bias at 0.3 (the
    program starts them at zero, one and zero, where they change
    nothing), and the head at three times its fan-in deviation: over
    random targets a changed trunk moves the mean loss by a sum of
    mean zero over the positions, whose size goes with the logits'."""
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name in ("expert_bias", "g_bias"):
            return 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name == "o_norm":
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 3.0 if name == "lm_head" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config("solar-open2-250b-ep32"),
        {"seq": 8192, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.moe_intermediate_size) == (4096, 1280)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.linear_num_heads, cfg.linear_head_dim,
            cfg.linear_conv_size, cfg.linear_gate_rank) == (64, 128, 4, 128)
    assert cfg.linear_allow_neg_eigval is True and cfg.attn_out_gate is True
    assert (cfg.num_experts, cfg.moe_top_k) == (320, 8)  # the router's
    assert (cfg.moe_first_expert_held, cfg.moe_experts_held) == (0, 10)
    assert cfg.moe_gate == "sigmoid" and cfg.use_expert_bias is True
    assert (cfg.moe_routed_scaling, cfg.moe_topk_norm_eps,
            cfg.moe_shared_experts) == (1.0, 1e-20, 1)
    assert cfg.norm_topk_prob is True
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.0)
    assert cfg.norm_eps == 1e-5 and not cfg.tie_word_embeddings
    assert cfg.rope_layout == (0, 0, 0, 0)  # use_rope false
    lead, period = cfg.layer_plan()
    assert lead == ()
    assert [(k.operator, k.ffn, k.rope) for k in period] == [
        ("full_attention", "experts", False)] + 3 * [
        ("linear_attention", "experts", False)]
    # a layer's operator: the delta rule's four 33.55 M matrices, two
    # low ranks of 1.57 M, the step size's 0.26 M, the taps and the
    # vectors; attention's q, gate and output 33.55 M each, k and v
    # 4.19 M each
    linear = (4 * 4096 * 8192 + 2 * 128 * (4096 + 8192) + 4096 * 64
              + 3 * 8192 * 4 + 2 * 8192 + 64 + 128)
    attention = 3 * 4096 * 8192 + 2 * 4096 * 1024
    experts = (4096 * 320 + 320 + 11 * 3 * 4096 * 1280 + 2 * 4096)
    assert (linear, attention, experts) == (
        137_740_480, 109_051_904, 174_334_272)
    layers = 3 * linear + attention + 4 * experts
    assert layers == 1_219_610_432
    assert llama.param_count(cfg) == layers + 2 * 24576 * 4096 + 4096
    assert llama.param_count(cfg) == 1_420_941_120  # 8.53 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_420_941_120
    assert shapes["period"][1]["f_b"].shape == (1, 128, 8192)
    assert shapes["period"][0]["wg"].shape == (1, 4096, 8192)
    assert shapes["period"][3]["w_gate"].shape == (1, 10, 4096, 1280)


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_bf16_program_is_inside_the_chip_tolerance():
    """At the tiny size the bf16 reading is the noise of flipped
    top-4 choices over a thousand positions (8 of 16 experts held)
    behind 64-wide streams: nine seeds read 0.00004-0.015, twice what
    the same model with attention in every layer reads, and this one
    0.0008. As the program starts, the bias at zero."""
    cfg_file, cfg, params, batch = _case("bfloat16", False, 8, seed=5)
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
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


FULL = "            x = x + full_attention(y, p, heads, kv_heads)\n"
LINEAR = "            x = x + linear_attention(y, p, linear_heads, eps)\n"
#: the controls of ISSUE 44, as edits to the reference
CONTROLS = {
    "decay left out": ((
        "        state = jnp.exp(g_t)[..., None] * state\n", ""),),
    "beta without its factor 2": ((
        '    beta = 2.0 * jax.nn.sigmoid(y @ p["w_beta"])\n',
        '    beta = jax.nn.sigmoid(y @ p["w_beta"])\n'),),
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
    "no gate on attention": ((
        '    a = jax.nn.sigmoid(y @ p["wg"]) * a\n', ""),),
    # each operator in the other's place, on the leaves they share
    # (wq, wk, wv, wo): a layer with both sets is handed both
    "the delta rule in attention's place": ((FULL, LINEAR),),
    "attention in the delta rule's place": ((LINEAR, FULL),),
    "no shared expert": ((
        '    total = total + gated(y, p["ws_gate"], p["ws_up"], '
        'p["ws_down"])\n', ""),),
    "weights not renormalised": (("    if norm_topk:\n",
                                  "    if False:\n"),),
    "no norm on the heads' result": ((
        '    o = rms_norm(o, p["o_norm"], eps).reshape(b, s, -1)\n',
        "    o = o.reshape(b, s, -1)\n"),),
    "top-4 of s without the bias": ((
        'jax.lax.top_k(score + p["expert_bias"], per_token)',
        "jax.lax.top_k(score, per_token)"),),
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


#: with attention in the delta rule's place the exchanged layers are
#: given k and v projections of the kv heads' width (``exchanged``)
KV_OF_THEIR_OWN = ((
    '    k = (y @ p["wk"]).reshape(b, s, kv_heads, -1)\n'
    '    v = (y @ p["wv"]).reshape(b, s, kv_heads, -1)\n',
    '    k = (y @ p.get("wk_attn", p["wk"])).reshape('
    'b, s, kv_heads, -1)\n'
    '    v = (y @ p.get("wv_attn", p["wv"])).reshape('
    'b, s, kv_heads, -1)\n'),)
OPERATOR = ("wq", "wk", "wv", "wo", "f_a", "f_b", "g_a", "g_b", "g_bias",
            "w_beta", "A_log", "dt_bias", "conv_q", "conv_k", "conv_v",
            "o_norm")


def exchanged(params, term):
    """``params`` as a reference with the operators exchanged reads
    them (the program reads its own kind's alone). With the delta rule
    in attention's place the period's first position is given the
    second's operator; with attention in the delta rule's place the
    others are given the first's gate and, under names of their own,
    its k and v projections (a linear layer's are a head's each, not a
    kv head's)."""
    full, linear = params["period"][0], params["period"][1]
    if term.startswith("the delta rule"):
        period = [{**full, **{k: linear[k] for k in OPERATOR}}
                  ] + params["period"][1:]
    else:
        period = [full] + [
            {**stack, "wg": full["wg"],
             "wk_attn": full["wk"], "wv_attn": full["wv"]}
            for stack in params["period"][1:]
        ]
    return {**params, "period": period}


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
            cfg_file, exchanged(params, term) if "place" in term
            else params, *batch)))
        for (cfg_file, _, params, batch), program in cases
    )


@pytest.mark.parametrize("term", list(CONTROLS))
def test_a_changed_term_shows(term, float32_cases):
    """A reference with one term of the block altered is off by more
    than the chip's tolerance, in float32, where the unchanged pair
    agrees to 2e-5 (the biases, the heads' norm's scale and the head
    drawn: ``drawn``)."""
    edits = CONTROLS[term]
    if term.startswith("attention in"):
        edits = edits + KV_OF_THEIR_OWN
    difference = most_off(
        edited(term.split()[0], *edits), float32_cases, term)
    # off by more than the tolerance, or no number at all (without
    # the norm on k the recurrence's update is no contraction)
    assert not difference <= worker.REFERENCE_TOLERANCE, (term, difference)


def test_the_unchanged_reference_takes_both_operators_leaves(float32_cases):
    """What the exchange controls are compared with: extra leaves at a
    position change nothing."""
    assert most_off(
        edited("same"), float32_cases, "attention in its place") < 2e-5


def test_the_reference_in_float8_shows(float32_cases):
    assert most_off(
        edited("float8", *FLOAT8), float32_cases
    ) > worker.REFERENCE_TOLERANCE


def test_reference_refuses_more_positions_than_the_source_declares():
    cfg_file, _, params, batch = _case("float32")
    with pytest.raises(ValueError):
        reference.loss({**cfg_file, "max_position_embeddings": 64},
                       params, *batch)


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
    with open(os.path.join(cells.HERE, "families", "solar.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_recurrence_is_the_programs_scan():
    """The reference's token-by-token walk against the program's
    chunked entry (ops/delta_rule.py) on operands of their own."""
    from dlrover_tpu.ops.delta_rule import gated_delta_rule

    ref = edited("recurrence")
    keys = jax.random.split(jax.random.key(3), 5)
    q, k, v = (jax.random.normal(key, (2, 96, 3, 16)) for key in keys[:3])
    g = -jax.random.uniform(keys[3], (2, 96, 3, 16), maxval=3.0)
    beta = 2 * jax.random.uniform(keys[4], (2, 96, 3))
    want = ref.recurrence(q, k, v, g, beta)
    got = gated_delta_rule(q, k, v, g, beta)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_reference_attention_walks_rows_in_blocks():
    ref = edited("rows")
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (1, 64, 4, 16))
    k, v = (jax.random.normal(key, (1, 64, 2, 16)) for key in keys[1:])
    whole = ref.attention(q, k, v, rows=64)
    parts = ref.attention(q, k, v, rows=8)
    assert float(jnp.abs(whole - parts).max()) < 1e-5
    from dlrover_tpu.ops.attention import mha_reference

    want = mha_reference(q, k, v, causal=True).reshape(1, 64, -1)
    assert float(jnp.abs(whole - want).max()) < 1e-5


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-solar")
    for key, other in (
            ("use_rope", True), ("kda_use_full_proj", True),
            ("first_k_dense_replace", 1), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**tiny, key: other}, TRAFFIC)
    with pytest.raises(ValueError, match="num_kv_heads"):
        worker.program_config({**tiny, "linear_attn_config": {
            **tiny["linear_attn_config"], "num_kv_heads": 2}}, TRAFFIC)
    ungated = worker.program_config({**tiny, "use_gqa_gate": False}, TRAFFIC)
    assert ungated.attn_out_gate is False
    raw = worker.program_config({**tiny, "norm_topk_prob": False}, TRAFFIC)
    assert raw.norm_topk_prob is False
    assert solar.layer_types({**tiny, "num_hidden_layers": 9}) == (
        ("full_attention",) + ("linear_attention",) * 3) * 2 + (
            "full_attention",)


# -- the share ---------------------------------------------------------------

def test_the_32_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's number: 32 shares of
    one expert each, of a router 32 wide. The routed parts that the
    shares give, with the shared expert's term (which every share
    computes alike, for its own tokens) counted once, add up to what
    the layer that holds all 32 gives: in the reference, and in the
    program's layer."""
    ref = edited("share")
    h, m, width, k = 32, 16, 32, 4
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
        uncut, balance = ref.experts(y, whole, p, 0, k, 0, True, 1e-20)
        parts = []
        for rank in range(32):
            one = {n: w[:, rank:rank + 1] for n, w in whole.items()}
            part, same = ref.experts(y, one, p, 0, k, rank, True, 1e-20)
            assert float(same) == float(balance)  # over all 32, held or not
            parts.append(part - shared)
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(parts)).max()) > 0.1
    # a token's four experts are on four of the 32 shares
    live = sum(float(jnp.abs(part[0, 0]).max()) > 0 for part in parts)
    assert live == k

    def program(first, held):
        out, _ = moe.dropless_moe_mlp(
            y, p["router"], *(whole[n][0, first:first + held]
                              for n in ("w_gate", "w_up", "w_down")),
            k=k, norm_topk_prob=True, z_coef=0.0, first_held=first,
            shared=(p["ws_gate"], p["ws_up"], p["ws_down"]),
            gate="sigmoid", bias=p["expert_bias"], norm_eps=1e-20)
        return out

    mine = sum(program(rank, 1) - shared for rank in range(32)) + shared
    assert float(jnp.abs(mine - uncut).max()) < 1e-4
    assert float(jnp.abs(program(0, 32) - uncut).max()) < 1e-4


# -- the counts --------------------------------------------------------------

def test_solar_counts_by_hand():
    c = config("solar-open2-250b-ep32")
    s = solar.shape(c)
    assert (s["layers"], s["attention_layers"], s["linear_layers"]) == (
        4, 1, 3)
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["shared_experts"], s["ffn"]) == (320, 10, 8, 1, 1280)
    assert (s["heads"], s["kv_heads"], s["head_dim"]) == (64, 8, 128)
    assert (s["linear_heads"], s["linear_head_dim"], s["taps"],
            s["gate_rank"]) == (64, 128, 4, 128)
    # in millions of weights met a token: a delta-rule layer's
    # projections 137.6, the attention layer's 109.1, the router 1.3,
    # the shared expert 15.7 and a quarter of a held expert in
    # expectation, the head 100.7
    linear = 4 * 4096 * 8192 + 2 * 128 * (4096 + 8192) + 4096 * 64
    attention = 3 * 4096 * 8192 + 2 * 4096 * 1024
    router, expert, head = 4096 * 320, 3 * 4096 * 1280, 4096 * 24576
    assert (linear, attention, router, expert, head) == (
        137_625_600, 109_051_904, 1_310_720, 15_728_640, 100_663_296)
    want = (3 * linear + attention
            + 4 * (router + 1.25 * expert) + head)
    assert solar.matmul_params(c) == counts.matmul_params(c) == want
    assert want == 706_478_080
    # scores and weighted values at 8,192 in the one attention layer
    attn = counts.attention_forward_flops_per_token(c, 8192)
    assert attn == 2 * 64 * 128 * 8192 == 134_217_728
    flops = counts.train_flops_per_token(c, 8192)
    assert flops == 3 * (2 * want + attn) == 4_641_521_664
    forward = flops / 3
    assert 2 * 3 * linear / forward == pytest.approx(0.534, abs=2e-3)
    assert (2 * attention + attn) / forward == pytest.approx(
        0.228, abs=2e-3)
    assert 2 * head / forward == pytest.approx(0.130, abs=2e-3)
    assert 2 * 4 * expert / forward == pytest.approx(0.081, abs=2e-3)
    assert 2 * 4 * 0.25 * expert / forward == pytest.approx(
        0.020, abs=2e-3)
    # the attention kernels: seven causal products, one layer
    kernel_flops, nbytes = counts.attention_kernel_step(c, 1, 8192)
    assert kernel_flops == 7 * 64 * 8192 * 8192 * 128
    assert nbytes == 6 * 8192 * (64 + 8) * 128 * 2
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.019534, rel=1e-3)
    # the grouped matmuls: 8,192 x 8 x 10 / 320 = 2,048 rows a layer on
    # the 10 held experts, 205 an expert, over four layers
    flops, nbytes = solar.expert_matmul_step(c, 8192)
    rows = 8192 * 8 * 10 // 320
    assert rows == 2048 and rows // 10 == 204
    assert flops == 4 * 3 * 2 * rows * 3 * 4096 * 1280
    weights = 3 * 10 * 3 * 4096 * 1280
    per_row = 2 * ((4096 + 1280) + (1280 + 2 * 4096)) + (
        (1280 + 4096) + (4096 + 2 * 1280))
    assert nbytes == 4 * 2 * (weights + rows * per_row)
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory"  # 205 rows an expert: the matrices' bytes
    assert seconds == pytest.approx(0.005444, rel=1e-3)
    # the recurrence: 21 x 128 x 128 operations a token and head; q,
    # k, v, o in bf16 and g in float32 forward, four bf16 and g read
    # and three bf16 and g's gradient written backward
    flops, nbytes = solar.delta_rule_step(c, 8192)
    assert flops == 3 * 21 * 8192 * 64 * 128 * 128
    column, betas = 8192 * 64 * 128, 8192 * 64 * 4
    assert nbytes == 3 * (
        (8 + 4) * column + betas + (8 + 4) * column + betas
        + (6 + 4) * column + betas)
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory"
    assert seconds == pytest.approx(0.008381, rel=1e-3)


def test_the_share_of_a_roofline_stays_under_100_at_the_kernels_least():
    """``delta_rule_roofline_pct`` with the kernels at the least time
    they could take: what the kernels move is more than the count's
    least bytes (the backward's entry states, 64 KB a chunk and head
    written and read, and the forward run twice under ``minimal`` are
    the implementation's), so the share is under 100 there, and a
    reading above it is a wrong count."""
    from yardstick.layer_metrics import delta_rule_roofline_pct as share

    c = config("solar-open2-250b-ep32")
    cell, _, traffic = cells.load_cell(CELL)
    peak = cells.peak_of("TPU v5 lite")
    flops, nbytes = solar.delta_rule_step(c, 8192)
    column, chunks = 8192 * 64 * 128, 8192 // 64
    forward = (8 + 4) * column
    states = 3 * 2 * chunks * 64 * 128 * 128 * 4
    moved = nbytes + 3 * forward + states  # a second forward, the states
    least = moved / peak["hbm_bytes_per_s"]
    run = {"trace": {"steps": 4, "ops": [["delta_rule.7", 4 * least, 12]]},
           "peak": peak, "config": c, "traffic": traffic, "cell": cell}
    got = share.read(run)
    assert 50 < got < 100, got
    assert share.read({**run, "trace": None}) is None
    no_kernel = {"steps": 4, "ops": [["fusion.1", 1.0, 3]]}
    assert share.read({**run, "trace": no_kernel}) is None


def test_every_published_number_is_run_but_the_cut():
    c = config("solar-open2-250b-ep32")
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (4, 10, 24576)
    for key, value in (
            ("hidden_size", 4096), ("num_attention_heads", 64),
            ("num_key_value_heads", 8), ("head_dim", 128),
            ("moe_intermediate_size", 1280), ("num_experts_per_tok", 8),
            ("n_shared_experts", 1)):
        assert c[key] == c["published"][key] == value, key
    assert c["linear_attn_config"] == c["published"][
        "linear_attn_config"] == {
            "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
            "num_kv_heads": None}
    assert solar.layer_types(c) == (
        "full_attention",) + ("linear_attention",) * 3
    share = c["share"]
    assert share["router_width"] == c["published"]["n_routed_experts"] == 320
    assert (share["chips_sharing_a_layer"], share["rank"],
            share["first_expert_held"]) == (32, 0, 0)
    assert 8 * c["vocab_size"] == c["published"]["vocab_size"]
    assert 32 * c["n_routed_experts"] == share["router_width"]
    assert c["depth"]["found"] == 4
    for key in ("attention_gate", "kda_gate_rank", "kda_decay", "kda_beta",
                "kda_conv", "kda_out", "routing", "expert_bias",
                "router_aux_loss_coef", "embed_init_std", "topk_norm_eps"):
        assert key in c["assumed"], key
    bench = cells.benchmark()
    (entry,) = [e for e in bench["configs"]
                if e["name"] == "solar-open2-250b-ep32"]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    for metric in ("delta_rule_ms", "delta_rule_roofline_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert m["workloads"] == [CELL], metric
    # not the experts' readers: a traced run keeps its 200 largest
    # operation names (yardstick/reduce.py), the 200th at 0.82 ms a
    # step in this cell, and every grouped matmul of 205 rows an
    # expert is under it (0.38-0.77 ms: PERF.md section 7), so the
    # readers find nothing and a cell they list must report them; nor
    # the convolution's, whose taps are plain fusions here
    for metric in ("moe_expert_ms", "moe_expert_roofline_pct",
                   "short_conv_ms", "short_conv_roofline_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert CELL not in m["workloads"], metric
