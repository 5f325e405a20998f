"""The ``joyai`` family: its program (models/llama.py with latent
attention, a leading dense layer, a sigmoid router that selects by a
biased score and scales its weights, a shared expert, a share of the
routed ones and a multi-token prediction module in the loss) against
``references/joyai.py`` at the tiny size, each term of the block
showing when it is changed; its counts against integers worked by
hand; what the configuration's file states."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from yardstick import cells, counts, reference, worker
from yardstick.families import joyai

SEQ, SEQUENCES = 128, 4
CELL = "joyai-llm-flash-ep8.steady"
REFERENCE = os.path.join(cells.HERE, "references", "joyai.py")
TRAFFIC = {"seq": SEQ, "remat": "off", "loss_chunk": 0}


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, draw=True, sequences=SEQUENCES, seed=7):
    cfg_file = dict(config("tiny-joyai"), dtype=dtype)
    cfg = worker.program_config(cfg_file, TRAFFIC)
    params = llama.init_params(jax.random.key(2), cfg)
    if draw:
        params = drawn(params)
    tokens, targets = worker.SeededTokens(
        seed, SEQ, cfg_file["vocab_size"])(0, sequences)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def drawn(params):
    """``params`` with every selection bias drawn at 0.3 and every
    scale of the two latent norms at 1 +/- 0.5: the program starts the
    first at zero and the others at one, where the bias changes no
    choice and a latent norm is nearly the identity (its input comes
    off a fan-in matrix at unit size), so their controls would be the
    unchanged pair. And the head at three times its fan-in deviation:
    over random targets a changed trunk moves the mean loss by a sum
    of mean zero over the positions, whose size goes with the
    logits', and five hundred positions at unit logits leave three of
    the controls inside the tolerance on this seed."""
    keys = iter(jax.random.split(jax.random.key(3), 64))

    def draw(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else None
        if name == "expert_bias":
            return 0.3 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if name in ("q_a_norm", "kv_a_norm"):
            return leaf * jax.random.uniform(
                next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
        return leaf * 3.0 if name == "lm_head" else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config("joyai-llm-flash-ep8"),
        {"seq": 8192, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (2048, 7168, 768)
    assert (cfg.num_heads, cfg.num_kv_heads) == (32, 32)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rope_dim) == (128, 64, 128, 64)
    assert cfg.latent and cfg.rope_interleave is True
    assert (cfg.num_experts, cfg.moe_top_k) == (256, 8)  # the router's
    assert (cfg.moe_first_expert_held, cfg.moe_experts_held) == (0, 32)
    assert cfg.moe_gate == "sigmoid" and cfg.use_expert_bias is True
    assert (cfg.moe_routed_scaling, cfg.moe_topk_norm_eps,
            cfg.moe_shared_experts) == (2.5, 1e-20, 1)
    assert cfg.norm_topk_prob is True
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.0)
    assert (cfg.rope_theta, cfg.norm_eps) == (3.2e7, 1e-6)
    assert (cfg.mtp_layers, cfg.mtp_loss_weight) == (1, 0.3)
    assert not cfg.tie_word_embeddings
    lead, period = cfg.layer_plan()
    assert [(k.operator, k.ffn) for k in lead] == [
        ("latent_attention", "dense")]
    assert [(k.operator, k.ffn) for k in period] == [
        ("latent_attention", "experts")]
    # a layer: latent attention 26.35 M and four norms; the router, its
    # bias, the shared expert and 32 x 4.72 M of experts, or 44.04 M
    # of dense MLP
    attention = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576
                 + 512 * 32 * 256 + 4096 * 2048 + 1536 + 512 + 2 * 2048)
    experts = 2048 * 256 + 256 + 33 * 3 * 2048 * 768
    assert (attention, experts) == (26_351_616, 156_238_080)
    module = attention + experts + 2 * 2048 * 2048 + 3 * 2048
    layers = 6 * attention + 5 * experts + 3 * 2048 * 7168 + module
    assert llama.param_count(cfg) == layers + 2 * 16160 * 2048 + 2048
    assert llama.param_count(cfg) == 1_240_518_144  # 7.44 GB at 6 bytes
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 1_240_518_144
    (module,) = shapes["mtp"]
    assert module["eh_proj"].shape == (4096, 2048)
    assert module["block"]["w_gate"].shape == (32, 2048, 768)
    assert shapes["period"][0]["wkv_a"].shape == (5, 2048, 576)


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_bf16_program_is_inside_the_chip_tolerance():
    """At the tiny size the bf16 reading is the noise of flipped
    top-4 choices over a thousand positions (8 of 16 experts held,
    each choice a quarter of the routed sum at 2.5): from seed to
    seed it is about the tolerance itself, and this seed's is a tenth
    of it. As the program starts, the bias at zero."""
    cfg_file, cfg, params, batch = _case("bfloat16", False, 8)
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


def test_mtp_loss_is_the_term_the_loss_adds():
    cfg_file, cfg, params, batch = _case("float32", draw=False)

    whole = float(llama.next_token_loss(params, batch, cfg))
    term = float(llama.mtp_loss(params, batch, cfg))
    assert 4.0 < term < 8.0  # about ln 256 + 1/2
    ce, mtp, aux = (float(x) for x in llama._losses(params, batch, cfg))
    assert mtp == term
    assert whole == pytest.approx(ce + 0.3 * term + aux, abs=1e-6)
    # without the module the trunk's own loss is the same trunk's
    plain = dataclasses.replace(cfg, mtp_layers=0)
    trunk = {k: v for k, v in params.items() if k != "mtp"}
    ce0, mtp0, _ = (
        float(x) for x in llama._losses(trunk, batch, plain))
    assert (ce0, mtp0) == (pytest.approx(ce, abs=1e-6), 0.0)


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


ROTATION = ("    q_rope, k_rope = rotate_pairs(q_rope, theta), "
            "rotate_pairs(k_rope, theta)\n")
#: the controls of ISSUE 42, as edits to the reference
CONTROLS = {
    "no shared expert": ((
        '    total = total + gated(y, p["ws_gate"], p["ws_up"], '
        'p["ws_down"])\n', ""),),
    "factor 1 for 2.5": (("    picked = picked * scaling\n", ""),),
    "the rotary key a head's own": ((
        '            + jnp.einsum("bqhd,bkd->bhqk", qr, one_key)\n',
        '            + jnp.einsum("bqhd,bkhd->bhqk", qr, jnp.stack([\n'
        '                jnp.roll(one_key, 2 * n, axis=-1)\n'
        '                for n in range(heads)], axis=2))\n'),),
    "rotation over all the columns": ((
        ROTATION,
        "    q_nope, q_rope = jnp.split(\n"
        "        rotate_pairs(q, theta), [nope], axis=-1)\n"
        "    k_nope, k_rope = jnp.split(rotate_pairs(jnp.concatenate([\n"
        "        k_nope, jnp.broadcast_to(k_rope, (b, s, heads, rope))\n"
        "    ], axis=-1), theta), [nope], axis=-1)\n"
        "    k_rope = k_rope[:, :, :1]\n"),),
    "rotation in halves, not in pairs": ((
        "    even, odd = x[..., 0::2], x[..., 1::2]\n"
        "    return jnp.stack(\n"
        "        [even * cos - odd * sin, odd * cos + even * sin], axis=-1\n"
        "    ).reshape(x.shape)\n",
        "    even, odd = x[..., : d // 2], x[..., d // 2:]\n"
        "    return jnp.concatenate(\n"
        "        [even * cos - odd * sin, odd * cos + even * sin], axis=-1\n"
        "    )\n"),),
    "no rotation": ((ROTATION, ""),),
    "no norm on c_q": ((
        '    c_q = rms_norm(y @ p["wq_a"], p["q_a_norm"], eps)\n',
        '    c_q = y @ p["wq_a"]\n'),),
    "no norm on c_kv": ((
        '    c_kv = rms_norm(down[..., :rank], p["kv_a_norm"], eps)\n',
        "    c_kv = down[..., :rank]\n"),),
    "scores scaled by the un-rotated width alone": ((
        "keep, scores / jnp.sqrt(F32(nope + rope)), -jnp.inf",
        "keep, scores / jnp.sqrt(F32(nope)), -jnp.inf"),),
    "the MTP term dropped": ((
        '        main + assumed["mtp_loss_weight"] * mtp\n',
        "        main\n"),),
    "MTP's target t_{i+1}": ((
        "    further = jnp.concatenate(\n"
        "        [targets[:, 1:], jnp.full_like(targets[:, :1], -1)], "
        "axis=1\n    )  # t_{i+2}\n",
        "    further = targets\n"),),
    "MTP with a head of its own": ((
        'module["final_norm"], eps), head, further)',
        'module["final_norm"], eps), head[:, ::-1], further)'),),
    "MTP's halves the other way round": ((
        "        return jnp.concatenate([e, h], axis=-1)",
        "        return jnp.concatenate([h, e], axis=-1)"),),
    "top-4 of s without the bias": ((
        'jax.lax.top_k(score + p["expert_bias"], per_token)',
        "jax.lax.top_k(score, per_token)"),),
    "weights taken from s + b": ((
        "jnp.take_along_axis(score, chosen, axis=-1)",
        'jnp.take_along_axis(score + p["expert_bias"], chosen, axis=-1)'),),
    "weights not renormalised": (("    if norm_topk:\n",
                                  "    if False:\n"),),
    "the leading layer without its MLP": ((
        '            return x + gated(y, p["w_gate"], p["w_up"], '
        'p["w_down"]), F32(0.0)\n',
        "            return x, F32(0.0)\n"),),
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


def most_off(changed, cases):
    """The larger |program - changed reference| of the batches."""
    return max(
        abs(program - float(changed.loss(cfg_file, params, *batch)))
        for (cfg_file, _, params, batch), program in cases
    )


@pytest.mark.parametrize("term", list(CONTROLS))
def test_a_changed_term_shows(term, float32_cases):
    """A reference with one term of the block altered is off by more
    than the chip's tolerance, in float32, where the unchanged pair
    agrees to 2e-5 (the bias, the latent norms' scales and the head
    drawn: ``drawn``)."""
    difference = most_off(
        edited(term.split()[0], *CONTROLS[term]), float32_cases)
    assert difference > worker.REFERENCE_TOLERANCE, (term, difference)


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
    with open(os.path.join(cells.HERE, "families", "joyai.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_attention_walks_rows_in_blocks():
    ref = edited("rows")
    keys = jax.random.split(jax.random.key(3), 5)
    qn, kn = (jax.random.normal(k, (1, 64, 4, 16)) for k in keys[:2])
    qr = jax.random.normal(keys[2], (1, 64, 4, 8))
    kr = jax.random.normal(keys[3], (1, 64, 1, 8))
    v = jax.random.normal(keys[4], (1, 64, 4, 12))
    whole = ref.attention(qn, qr, kn, kr, v, rows=64)
    parts = ref.attention(qn, qr, kn, kr, v, rows=8)
    assert float(jnp.abs(whole - parts).max()) < 1e-5
    from dlrover_tpu.ops.attention import mha_reference

    want = mha_reference(
        jnp.concatenate([qn, qr], axis=-1),
        jnp.concatenate([kn, jnp.broadcast_to(kr, qr.shape)], axis=-1), v,
    ).reshape(1, 64, -1)
    assert float(jnp.abs(whole - want).max()) < 1e-5


def test_the_programs_rotation_scores_as_the_pairs_do():
    """The program leaves the rotated columns in the order (evens,
    odds), q and k alike: every score is the reference's."""
    ref = edited("pairs")
    keys = jax.random.split(jax.random.key(5), 2)
    q, k = (jax.random.normal(key, (2, 16, 3, 8)) for key in keys)
    cos, sin = llama.rope_tables(16, 8, 3.2e7)
    mine = jnp.einsum(
        "bqhd,bkhd->bhqk", llama.apply_rope(q, cos, sin, True),
        llama.apply_rope(k, cos, sin, True))
    want = jnp.einsum(
        "bqhd,bkhd->bhqk", ref.rotate_pairs(q, 3.2e7),
        ref.rotate_pairs(k, 3.2e7))
    assert float(jnp.abs(mine - want).max()) < 1e-5
    halves = jnp.einsum(
        "bqhd,bkhd->bhqk", llama.apply_rope(q, cos, sin),
        llama.apply_rope(k, cos, sin))
    assert float(jnp.abs(halves - want).max()) > 0.1


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-joyai")
    for key, other in (
            ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
            ("topk_method", "greedy"), ("rope_scaling", {"type": "yarn"}),
            ("attention_bias", True), ("moe_layer_freq", 2),
            ("tie_word_embeddings", True), ("qk_head_dim", 32),
            ("num_key_value_heads", 2), ("num_nextn_predict_layers", 2),
            ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            worker.program_config({**tiny, key: other}, TRAFFIC)
    raw = worker.program_config({**tiny, "norm_topk_prob": False}, TRAFFIC)
    assert raw.norm_topk_prob is False


# -- the counts --------------------------------------------------------------

def test_joyai_counts_by_hand():
    c = config("joyai-llm-flash-ep8")
    s = joyai.shape(c)
    assert (s["layers"], s["dense_layers"], s["mtp_layers"]) == (6, 1, 1)
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["shared_experts"], s["ffn"], s["dense_ffn"]) == (
                256, 32, 8, 1, 768, 7168)
    assert (s["head_dim"], s["v_head_dim"], s["heads"]) == (192, 128, 32)
    # in millions of weights met a token: the latent projections 26.3,
    # the router 0.5, the shared expert and one held expert in
    # expectation 4.7 each, the dense MLP 44.0, the merge 8.4, the
    # head 33.1, twice
    attention = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                 + 4096 * 2048)
    router, expert = 2048 * 256, 3 * 2048 * 768
    dense, merge, head = 3 * 2048 * 7168, 2 * 2048 * 2048, 2048 * 16160
    assert (attention, router, expert, dense, merge, head) == (
        26_345_472, 524_288, 4_718_592, 44_040_192, 8_388_608, 33_095_680)
    want = (7 * attention + 6 * (router + 2 * expert) + dense + merge
            + 2 * head)
    assert joyai.matmul_params(c) == counts.matmul_params(c) == want
    assert want == 362_807_296
    # scores and weighted values at 8,192: 32 x (192 + 128) x 8192 a
    # block
    attn = counts.attention_forward_flops_per_token(c, 8192)
    assert attn == 7 * 32 * 320 * 8192 == 587_202_560
    flops = counts.train_flops_per_token(c, 8192)
    assert flops == 3 * (2 * want + attn) == 3_938_451_456
    forward = flops / 3
    assert attn / forward == pytest.approx(0.447, abs=2e-3)
    assert 2 * 7 * attention / forward == pytest.approx(0.281, abs=2e-3)
    assert 2 * 2 * head / forward == pytest.approx(0.101, abs=2e-3)
    assert 2 * dense / forward == pytest.approx(0.067, abs=2e-3)
    assert 2 * 6 * expert / forward == pytest.approx(0.043, abs=2e-3)
    # the kernels: four causal products 192 wide and three 128 wide,
    # over six layers and the module's block
    kernel_flops, nbytes = counts.attention_kernel_step(c, 4, 8192)
    assert kernel_flops == 7 * 4 * 32 * 8192 * 8192 * (4 * 192 + 3 * 128)
    assert nbytes == 7 * 4 * 8192 * 32 * 2 * 6 * (192 + 128)
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.351621, rel=1e-3)
    # the grouped matmuls: 32,768 rows a block on the 32 held experts,
    # 1,024 an expert, over five layers and the module's block; the
    # shared expert is not in it
    flops, nbytes = joyai.expert_matmul_step(c, 32768)
    rows = 32768 * 8 * 32 // 256
    assert rows == 32_768 and rows // 32 == 1024
    assert flops == 6 * 3 * 2 * rows * 3 * 2048 * 768
    weights = 3 * 32 * 3 * 2048 * 768
    per_row = 2 * ((2048 + 768) + (768 + 2 * 2048)) + (
        (768 + 2048) + (2048 + 2 * 768))
    assert nbytes == 6 * 2 * (weights + rows * per_row)
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.028255, rel=1e-3)


def test_every_published_number_is_run_but_the_cut():
    c = config("joyai-llm-flash-ep8")
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (6, 32, 16160)
    share = c["share"]
    assert share["router_width"] == c["published"]["n_routed_experts"] == 256
    assert (share["chips_sharing_a_layer"], share["rank"],
            share["first_expert_held"]) == (8, 0, 0)
    assert 8 * c["vocab_size"] == c["published"]["vocab_size"]
    assert 8 * c["n_routed_experts"] == share["router_width"]
    assert c["depth"]["found"] == 6
    assert min(c["depth"]["accepted_peak_memory_in_bytes"].values()) >= 10e9
    assert c["depth"]["refused"]
    for key in ("mtp_loss_weight", "topk_norm_eps", "embed_init_std",
                "router_aux_loss_coef", "expert_bias", "mtp"):
        assert key in c["assumed"], key
    bench = cells.benchmark()
    (entry,) = [e for e in bench["configs"]
                if e["name"] == "joyai-llm-flash-ep8"]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    for metric in ("moe_expert_ms", "moe_expert_roofline_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert CELL in m["workloads"], metric
