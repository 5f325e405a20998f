"""The ``trinity`` family: its program (models/llama.py with four norms
a block, the embedding times sqrt(hidden), gated windowed attention
with the rotary embedding and gated full attention without positions,
the heads' norms on q and k, a leading dense layer, a sigmoid router
that selects by a biased score and scales its weights, a shared expert
and a share of the routed ones) against ``references/trinity.py`` at
the tiny size, each term of the block showing when it is changed; the
shares adding up to the uncut layer; its counts against integers worked
by hand; what the configuration's file states. The rule that moves the
bias, and the static path of the families that were there, are held in
``tests/test_moe_bias_rule.py``."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import moe
from yardstick import cells, counts, reference, worker
from yardstick.families import trinity

SEQ, SEQUENCES = 128, 4
CELL = "trinity-mini-ep8.steady"
REFERENCE = os.path.join(cells.HERE, "references", "trinity.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-trinity"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with every selection bias drawn at 0.3 and the
    heads' norms' and the two result norms' scales at 1 +/- 0.5 (the
    program starts them at zero and one, where the bias changes
    nothing and a norm of a unit-deviation q or k next to nothing),
    and the head at three times its fan-in deviation: over random
    targets a changed trunk moves the mean loss by a sum of mean zero
    over the positions, whose size goes with the logits'."""
    keys = iter(jax.random.split(jax.random.key(3), 128))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name == "expert_bias":
            return 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name in ("q_norm", "k_norm", "post_attn_norm", "post_mlp_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 3.0 if name == "lm_head" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config("trinity-mini-ep8"),
        {"seq": 16384, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (2048, 6144, 1024)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert cfg.num_heads * cfg.head_dim == 4096 != cfg.hidden_size
    assert (cfg.num_layers, cfg.num_dense_layers) == (9, 1)
    assert (cfg.num_experts, cfg.moe_top_k) == (128, 8)  # the router's
    assert (cfg.moe_first_expert_held, cfg.moe_experts_held) == (0, 16)
    assert cfg.moe_gate == "sigmoid" and cfg.use_expert_bias is True
    assert (cfg.moe_routed_scaling, cfg.moe_topk_norm_eps,
            cfg.moe_shared_experts) == (2.826, 1e-20, 1)
    assert cfg.norm_topk_prob is True
    assert cfg.moe_bias_update_rate == 0.001  # load_balance_coeff
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.0, 0.0)
    assert (cfg.rope_theta, cfg.norm_eps) == (10000.0, 1e-5)
    assert cfg.post_norms and cfg.mup_enabled and cfg.attn_out_gate
    assert cfg.qk_head_norm and not cfg.qk_norm
    assert not cfg.tie_word_embeddings and cfg.embed_init_std == 0.5
    # twice the fan-in deviation: logits of deviation 2
    assert cfg.head_init_std == pytest.approx(2 * 2048 ** -0.5, rel=1e-3)
    assert cfg.sliding_window_size == 2048
    assert cfg.sliding_window_layout == cfg.rope_layout == (
        1,) + (1, 0, 1, 1) * 2
    lead, period = cfg.layer_plan()
    assert [(k.window, k.rope, k.ffn) for k in lead] == [
        (2048, True, "dense")]
    # the period of what follows starts mid-pattern
    assert [(k.window, k.rope, k.ffn) for k in period] == [
        (2048, True, "experts"), (None, False, "experts"),
        (2048, True, "experts"), (2048, True, "experts")]
    # attention's five matrices 27.26 M, the heads' two scales, four
    # norms; a dense MLP 37.75 M; the router, its bias, the shared
    # expert and 16 held experts of 6.29 M each
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 4 * 2048
    dense = 3 * 2048 * 6144
    experts = 2048 * 128 + 128 + 17 * 3 * 2048 * 1024
    assert (attention, dense, experts) == (
        27_271_424, 37_748_736, 107_217_024)
    layers = 9 * attention + dense + 8 * experts
    assert llama.param_count(cfg) == layers + 2 * 25024 * 2048 + 2048
    assert llama.param_count(cfg) == 1_243_428_096  # 7.46 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_243_428_096
    assert shapes["lead"][0]["w_gate"].shape == (2048, 6144)
    assert shapes["period"][1]["wg"].shape == (2, 2048, 4096)
    assert shapes["period"][3]["w_gate"].shape == (2, 16, 2048, 1024)
    assert shapes["period"][0]["expert_bias"].shape == (2, 128)
    assert shapes["period"][2]["post_mlp_norm"].shape == (2, 2048)


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_bf16_program_is_inside_the_chip_tolerance():
    """On the fresh parameters, as the cell's check has them, over
    three seeds of tokens."""
    for seed in (7, 8, 9):
        cfg_file, cfg, params, batch = _case(
            "bfloat16", draw=False, seed=seed)
        ref = float(reference.loss(cfg_file, params, *batch))
        program = float(llama.next_token_loss(params, batch, cfg))
        assert abs(program - ref) < worker.REFERENCE_TOLERANCE, seed


@pytest.mark.parametrize("remat", ["minimal", "dots", "dots_attn_out"])
def test_remat_and_chunked_loss_change_nothing(remat):
    cfg_file, cfg, params, batch = _case("float32", sequences=2)
    want = float(llama.next_token_loss(params, batch, cfg))
    other = worker.program_config(
        cfg_file, {"seq": SEQ, "remat": remat, "loss_chunk": 64})
    got, counted = jax.jit(
        lambda p, b: llama.loss_and_expert_counts(p, b, other)
    )(params, batch)
    assert float(got) == pytest.approx(want, abs=2e-5)
    # the counts of the one forward pass, whatever is made again
    assert (counted["stack"] == llama.routing_stats(
        params, batch[0], cfg)).all()


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


ROPE = "            rope=sliding,\n"
#: the controls of ISSUE 49, as edits to the reference
CONTROLS = {
    "no post-attention norm": ((
        '        x = x + rms_norm(a, p["post_attn_norm"], eps)\n',
        "        x = x + a\n"),),
    "no post-MLP norm": ((
        '        return x + rms_norm(m, p["post_mlp_norm"], eps), counts\n',
        "        return x + m, counts\n"),),
    "no muP factor": ((
        '    if config["mup_enabled"]:\n', "    if False:\n"),),
    "RoPE on a full layer": ((ROPE, "            rope=True,\n"),),
    "no RoPE on a sliding layer": ((ROPE, "            rope=False,\n"),),
    "a window twice as long (4096 for 2048)": ((
        'window=config["sliding_window"] if sliding',
        'window=2 * config["sliding_window"] if sliding'),),
    "no output gate": ((
        '    return (jax.nn.sigmoid(y @ p["wg"]) * a) @ p["wo"]\n',
        '    return a @ p["wo"]\n'),),
    "no q/k norms": ((
        '    q, k = rms_norm(q, p["q_norm"], eps), '
        'rms_norm(k, p["k_norm"], eps)\n', ""),),
    "softmax for sigmoid": ((
        '    score = jax.nn.sigmoid(y @ p["router"])',
        '    score = jax.nn.softmax(y @ p["router"], axis=-1)'),),
    "weights not renormalised": (("    if norm_topk:\n",
                                  "    if False:\n"),),
    "no factor 2.826": ((
        '        scaling=float(config["route_scale"]),\n',
        "        scaling=1.0,\n"),),
    "no shared expert": ((
        '    total = total + gated(y, p["ws_gate"], p["ws_up"], '
        'p["ws_down"])\n', ""),),
    "top-k of s without the bias": ((
        '    _, chosen = jax.lax.top_k(score + p["expert_bias"], '
        'per_token)\n    picked',
        "    _, chosen = jax.lax.top_k(score, per_token)\n    picked"),),
    # the first expert layer's router and experts on the leading
    # layer's leaves (``exchanged``)
    "experts in the leading dense MLP's place": ((
        "            x, stack, i, dense=l < lead,\n",
        "            x, stack, i, dense=False,\n"),),
}
#: the reference in the nearest precision below the program's
#: bfloat16: whatever the program keeps in bfloat16 rounded to float8
#: (e4m3, a scale a tensor), the sums in float32. That is every
#: matrix, the embedding's rows, the stream after each residual sum,
#: the two normed streams a layer, the final normed stream and the head
FLOAT8 = (
    ('EXPERTS = ("w_gate", "w_up", "w_down")\n',
     'EXPERTS = ("w_gate", "w_up", "w_down")\n\n\n'
     'def q8(a):\n'
     '    s = jnp.max(jnp.abs(a)) / 448.0\n'
     '    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s\n'),
    ('        y = rms_norm(x, p["attn_norm"], eps)\n',
     '        p = {k: q8(v) if v.ndim > 1 else v for k, v in p.items()}\n'
     '        y = q8(rms_norm(x, p["attn_norm"], eps))\n'),
    ('        x = x + rms_norm(a, p["post_attn_norm"], eps)\n',
     '        x = q8(x + rms_norm(a, p["post_attn_norm"], eps))\n'),
    ('        y = rms_norm(x, p["mlp_norm"], eps)\n',
     '        y = q8(rms_norm(x, p["mlp_norm"], eps))\n'),
    ('        return x + rms_norm(m, p["post_mlp_norm"], eps), counts\n',
     '        return q8(x + rms_norm(m, p["post_mlp_norm"], eps)), counts\n'),
    ('    return one_layer[e].astype(F32)\n',
     '    return q8(one_layer[e].astype(F32))\n'),
    ('    x = embed(params["embed"], tokens)\n',
     '    x = q8(embed(params["embed"], tokens))\n'),
    ('    x = final_rms(x, params["final_norm"], '
     'float(config["rms_norm_eps"]))\n'
     '    return mean_nll(x, params["lm_head"], targets)\n',
     '    x = q8(final_rms(x, params["final_norm"], '
     'float(config["rms_norm_eps"])))\n'
     '    return mean_nll(x, q8(params["lm_head"].astype(F32)), targets)\n'),
)
FFN = ("router", "expert_bias", "w_gate", "w_up", "w_down", "ws_gate",
       "ws_up", "ws_down")


def exchanged(params, term):
    """``params`` as the reference that runs experts in the leading
    layer reads them: the leading layer with the feed-forward leaves
    of the first expert layer (the program reads its dense MLP's)."""
    first = jax.tree.map(lambda a: a[0], params["period"][0])
    lead = {**params["lead"][0], **{k: first[k] for k in FFN}}
    return {**params, "lead": [lead] + params["lead"][1:]}


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
    agrees to 2e-5 (the biases and the norms' scales drawn:
    ``drawn``)."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases, term)
    assert difference > worker.REFERENCE_TOLERANCE, (term, difference)


def test_the_unchanged_reference_reads_no_leaf_it_is_handed_beside(
        float32_cases):
    """What the exchange control is compared with: a leading layer
    that keeps its own leaves is the program's."""
    assert most_off(edited("same"), float32_cases) < 2e-5


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
    assert "dlrover_tpu" not in src.split('"""', 2)[2]
    with open(os.path.join(cells.HERE, "families", "trinity.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_attention_walks_rows_in_blocks():
    """Whatever the block of query rows, the same band, at the cell's
    group of eight."""
    ref = edited("rows")
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (1, 64, 8, 8))
    k, v = (jax.random.normal(key, (1, 64, 1, 8)) for key in keys[1:])
    for window in (None, 16):
        whole = ref.banded_attention(q, k, v, window, rows=64)
        blocks = ref.banded_attention(q, k, v, window, rows=8)
        assert float(jnp.abs(whole - blocks).max()) < 1e-5
    from dlrover_tpu.ops.attention import mha_reference

    want = mha_reference(q, k, v, window=16).reshape(1, 64, -1)
    assert float(jnp.abs(ref.banded_attention(
        q, k, v, 16, rows=16) - want).max()) < 1e-5


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-trinity")
    for key, other in (
            ("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4),
            ("num_limited_groups", 2), ("score_func", "softmax"),
            ("rope_scaling", {"type": "yarn", "factor": 4.0}),
            ("hidden_act", "gelu"), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**tiny, key: other}, TRAFFIC)
    with pytest.raises(ValueError, match="layer_types"):
        worker.program_config({**tiny, "num_hidden_layers": 8}, TRAFFIC)
    with pytest.raises(ValueError, match="layer_types"):
        worker.program_config({**tiny, "layer_types": ["conv"] * 9}, TRAFFIC)
    plain = worker.program_config({**tiny, "mup_enabled": False}, TRAFFIC)
    assert plain.mup_enabled is False
    raw = worker.program_config({**tiny, "route_norm": False}, TRAFFIC)
    assert raw.norm_topk_prob is False
    still = worker.program_config({**tiny, "load_balance_coeff": 0}, TRAFFIC)
    assert still.moe_bias_update_rate == 0


# -- the share ---------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the deployment's number: eight shares
    of two experts each, of a router 16 wide. The routed parts that
    the shares give, with the shared expert's term (which every share
    computes alike, for its own tokens) counted once, add up to what
    the layer that holds all 16 gives: in the reference, and in the
    program's layer; and every share counts the same assignments, over
    all 16."""
    ref = edited("share")
    h, m, width, k = 32, 16, 16, 4
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
    routing = (k, True, 1e-20, 2.826)
    shared = ref.gated(y, p["ws_gate"], p["ws_up"], p["ws_down"])
    with reference.HIGHEST():
        uncut, counted = ref.experts(y, whole, p, 0, k, 0, *routing[1:])
        assert int(counted.sum()) == 2 * 24 * k
        parts = []
        for rank in range(8):
            two = {n: w[:, 2 * rank:2 * rank + 2] for n, w in whole.items()}
            part, same = ref.experts(
                y, two, p, 0, k, 2 * rank, *routing[1:])
            assert (same == counted).all()  # over all 16, held or not
            parts.append(part - shared)
    assert float(jnp.abs(sum(parts) + shared - uncut).max()) < 1e-5
    assert float(jnp.abs(sum(parts)).max()) > 0.1

    def program(first, held):
        out, _, mine = moe.dropless_moe_mlp(
            y, p["router"], *(whole[n][0, first:first + held]
                              for n in ("w_gate", "w_up", "w_down")),
            k=k, norm_topk_prob=True, balance_coef=0.0, z_coef=0.0,
            first_held=first, count=True,
            shared=(p["ws_gate"], p["ws_up"], p["ws_down"]),
            gate="sigmoid", bias=p["expert_bias"], norm_eps=1e-20,
            scaling=2.826)
        assert (mine == counted).all()
        return out

    mine = sum(program(2 * rank, 2) - shared for rank in range(8)) + shared
    assert float(jnp.abs(mine - uncut).max()) < 1e-4
    assert float(jnp.abs(program(0, 16) - uncut).max()) < 1e-4


# -- the counts --------------------------------------------------------------

def test_trinity_counts_by_hand():
    c = config("trinity-mini-ep8")
    s = trinity.shape(c)
    assert (s["layers"], s["dense_layers"], s["window"]) == (9, 1, 2048)
    assert s["windowed"] == (True,) + (True, False, True, True) * 2
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["shared_experts"], s["ffn"], s["dense_ffn"]) == (
        128, 16, 8, 1, 1024, 6144)
    # in millions of weights met a token: attention's five matrices
    # 27.3, the dense MLP 37.7, the router 0.3, the shared expert 6.3
    # and 8 x 16 / 128 = one held expert in expectation, the head 51.2
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    dense, router, expert = 3 * 2048 * 6144, 2048 * 128, 3 * 2048 * 1024
    head = 2048 * 25024
    assert (attention, dense, router, expert, head) == (
        27_262_976, 37_748_736, 262_144, 6_291_456, 51_249_152)
    want = 9 * attention + dense + 8 * (router + 2 * expert) + head
    assert trinity.matmul_params(c) == counts.matmul_params(c) == want
    assert want == 437_125_120
    # live pairs a head and sequence at 16,384: the whole triangle in
    # a full layer, the band in a windowed one (23% of it)
    full, windowed = 16384 * 16384 // 2, 2048 * 2048 // 2 + 14336 * 2048
    assert (full, windowed) == (134_217_728, 31_457_280)
    assert trinity.live_pairs(c, 16384) == 2 * full + 7 * windowed
    assert trinity.live_pairs(c, 2048) == 9 * 2048 * 2048 // 2
    # a token, forward: 134.2 MFLOP in a full layer, 31.5 in a
    # windowed one
    per_pair = 2 * 2 * 128 * 32
    assert per_pair * full // 16384 == 134_217_728
    assert per_pair * windowed // 16384 == 31_457_280
    attn = counts.attention_forward_flops_per_token(c, 16384)
    assert attn == 2 * 134_217_728 + 7 * 31_457_280 == 488_636_416
    flops = counts.train_flops_per_token(c, 16384)
    assert flops == 3 * (2 * want + attn) == 4_088_659_968
    assert flops * 16384 == pytest.approx(66.99e12, rel=1e-3)  # a step
    # of the counted operations: attention's scores 36%, its five
    # projections 36%, the expert layers 15%, the dense MLP 5.5%, the
    # head 7.5%
    forward = flops / 3
    assert attn / forward == pytest.approx(0.359, abs=2e-3)
    assert 2 * 9 * attention / forward == pytest.approx(0.360, abs=2e-3)
    assert 2 * 8 * (router + 2 * expert) / forward == pytest.approx(
        0.151, abs=2e-3)
    assert 2 * dense / forward == pytest.approx(0.055, abs=2e-3)
    assert 2 * head / forward == pytest.approx(0.075, abs=2e-3)
    # the kernels: seven products over the live pairs, 28.0 TFLOP a
    # step, 142 ms at 197 TFLOP/s
    kernel_flops, nbytes = counts.attention_kernel_step(c, 1, 16384)
    assert kernel_flops == 7 * 2 * 128 * 32 * (2 * full + 7 * windowed)
    assert kernel_flops == 28_020_366_639_104
    q_like, kv_like = 16384 * 32 * 128 * 2, 16384 * 4 * 128 * 2
    assert nbytes == 9 * (6 * q_like + 6 * kv_like)
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.142235, rel=1e-3)
    # no count of the grouped matmuls yet: the cell is in neither
    # `moe_expert_*` list (their calls lie at the reader's 200-name
    # cut, ROADMAP B12m), and a count nothing reads is not kept
    assert not hasattr(trinity, "expert_matmul_step")


def test_every_published_number_is_run_but_the_cut():
    c = config("trinity-mini-ep8")
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (9, 1, 16, 25024)
    # the source's entries 1-9
    assert c["layer_types"] == c["published"]["layer_types"][1:10]
    for key, value in (
            ("hidden_size", 2048), ("intermediate_size", 6144),
            ("moe_intermediate_size", 1024), ("num_attention_heads", 32),
            ("num_key_value_heads", 4), ("head_dim", 128),
            ("num_experts_per_tok", 8), ("num_shared_experts", 1),
            ("sliding_window", 2048), ("route_scale", 2.826),
            ("load_balance_coeff", 0.001), ("mup_enabled", True)):
        assert c[key] == c["published"][key] == value, key
    share = c["share"]
    assert share["router_width"] == c["published"]["num_experts"] == 128
    assert (share["chips_sharing_a_layer"], share["rank"],
            share["first_expert_held"]) == (8, 0, 0)
    assert 8 * c["vocab_size"] == c["published"]["vocab_size"]
    assert 8 * c["num_experts"] == share["router_width"]
    assert c["depth"]["found"] == 9
    assert max(
        c["depth"]["accepted_peak_memory_in_bytes"].values()) < 16.91e9
    for key in ("attention_gate", "norms", "embedding", "rope", "routing",
                "topk_norm_eps", "expert_bias", "bias_rule",
                "embed_init_std", "head_init_std", "init_origin"):
        assert key in c["assumed"], key
    bench = cells.benchmark()
    (entry,) = [e for e in bench["configs"]
                if e["name"] == "trinity-mini-ep8"]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini-ep8", "steady-1x16384", 1)
