"""The ``lfm2`` family: its program (models/llama.py with two kinds of
operator, a leading dense layer, a sigmoid router that selects by a
biased score, a tied head and a share of the experts) against
``references/lfm2.py`` at the tiny size, each term of the block
showing when it is changed; the four shares' parts of an expert layer
against the uncut layer; its counts against integers worked by hand;
the readers on a step the chip recorded."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from yardstick import cells, counts, reduce, reference, worker
from yardstick.families import lfm2
from yardstick.layer_metrics import (
    attn_kernel_ms, attn_roofline_pct, moe_expert_ms,
    moe_expert_roofline_pct, short_conv_ms, short_conv_roofline_pct,
)

SEQ, SEQUENCES = 128, 4
CELL = "lfm2-8b-a1b-ep4.steady"
REFERENCE = os.path.join(cells.HERE, "references", "lfm2.py")


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype, **assumed):
    cfg_file = dict(config("tiny-lfm2"), dtype=dtype)
    cfg_file["assumed"] = {**cfg_file["assumed"], **assumed}
    cfg = worker.program_config(
        cfg_file, {"seq": SEQ, "remat": "off", "loss_chunk": 0})
    params = with_bias(llama.init_params(jax.random.key(1), cfg))
    tokens, targets = worker.SeededTokens(
        5, SEQ, cfg_file["vocab_size"])(0, SEQUENCES)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def with_bias(params, std=0.3):
    """``params`` with every layer's selection bias drawn at ``std``:
    the program starts the buffer at zero, where the two bias
    controls would be the unchanged pair."""
    drawn = iter(jax.random.split(jax.random.key(3), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: std * jax.random.normal(
            next(drawn), leaf.shape, leaf.dtype
        ) if path[-1].key == "expert_bias" else leaf,
        params,
    )


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config("lfm2-8b-a1b-ep4"),
        {"seq": 8192, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.head_dim) == (2048, 7168, 1792, 64)
    assert (cfg.num_heads, cfg.num_kv_heads) == (32, 8)
    assert (cfg.num_experts, cfg.moe_top_k) == (32, 4)  # the router's
    assert (cfg.moe_first_expert_held, cfg.moe_experts_held) == (0, 8)
    assert cfg.moe_gate == "sigmoid" and cfg.use_expert_bias is True
    assert cfg.norm_topk_prob is True
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.0)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.conv_L_cache) == (1e6, 1e-5, 3)
    assert cfg.qk_head_norm and not cfg.qk_norm
    assert cfg.tie_word_embeddings and cfg.moe_expert_act == "silu"
    assert cfg.moe_router_input == "post_attn_norm"
    lead, period = cfg.layer_plan()
    assert [k.operator for k in lead] == ["conv"]
    assert [k.ffn for k in lead] == ["dense"]
    assert [k.operator for k in period] == [
        "full_attention", "conv", "conv", "conv"]
    assert {k.ffn for k in period} == {"experts"}
    # a layer: the operator, two norms, and the router, its bias and
    # 8 x 11.01 M of experts, or 44.04 M of dense MLP
    conv = 4 * 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 2048 * 32 + 32 + 8 * 3 * 2048 * 1792
    assert (conv, attention, experts) == (16_783_360, 10_485_888,
                                          88_145_952)
    layers = (10 * conv + 3 * attention + 12 * experts
              + 3 * 2048 * 7168 + 13 * 2 * 2048)
    assert llama.param_count(cfg) == layers + 16384 * 2048 + 2048
    assert llama.param_count(cfg) == 1_334_692_608  # 8.01 GB at 6 bytes


def test_float32_program_agrees_with_the_reference():
    cfg_file, cfg, params, batch = _case("float32")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_bf16_program_is_inside_the_chip_tolerance():
    cfg_file, cfg, params, batch = _case("bfloat16")
    ref = float(reference.loss(cfg_file, params, *batch))
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < worker.REFERENCE_TOLERANCE


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


#: the controls of PERF.md section 6, as edits to the reference
CONTROLS = {
    "no B gate": (("    v = B * u\n    taps", "    v = u\n    taps"),),
    "no C gate": (("    mixed = C * c\n", "    mixed = c\n"),),
    "taps reversed": ((
        '        c = c + p["conv_w"][:, j] * earlier\n',
        '        c = c + p["conv_w"][:, taps - 1 - j] * earlier\n'),),
    "attention in a conv layer's place: C, B, u as q, k, v": ((
        "    mixed = C * c\n",
        "    heads = lambda a: a.reshape(*a.shape[:2], -1, 16)\n"
        "    mixed = attention(heads(C), heads(B), heads(u))\n"),),
    "top-4 of s without the bias": ((
        'jax.lax.top_k(score + p["expert_bias"], per_token)',
        "jax.lax.top_k(score, per_token)"),),
    "weights taken from s + b": ((
        "jnp.take_along_axis(score, chosen, axis=-1)",
        'jnp.take_along_axis(score + p["expert_bias"], chosen, axis=-1)'),),
    "softmax for sigmoid": ((
        "score = jax.nn.sigmoid(logits)",
        "score = jax.nn.softmax(logits, axis=-1)"),),
    "weights not renormalised": (("    if norm_topk:\n",
                                  "    if False:\n"),),
    "q/k norms off": ((
        '    q, k = rms_norm(q, p["q_norm"], eps), '
        'rms_norm(k, p["k_norm"], eps)\n', ""),),
    "the leading layer as four experts at even weights": ((
        "            return x + out, F32(0.0)\n",
        "            return x + out / per_token, F32(0.0)\n"),),
}
#: the reference in the nearest precision below the program's
#: bfloat16: every matrix, the taps and the two normed streams a layer
#: rounded to float8 (e4m3, a scale a tensor), the sums in float32
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
#: and as a program that got the term wrong, on its own parameters
PROGRAM_CONTROLS = {
    "an untied head": dict(tie_word_embeddings=False),
}


def control_difference(term, cfg_file, cfg, params, batch):
    """|program - reference| with ``term`` changed on one side."""
    if term in PROGRAM_CONTROLS:
        wrong = dataclasses.replace(cfg, **PROGRAM_CONTROLS[term])
        params = llama.init_params(jax.random.key(1), wrong)
        program = llama.next_token_loss(params, batch, wrong)
        return abs(float(program) - float(
            reference.loss(cfg_file, params, *batch)))
    program = float(llama.next_token_loss(params, batch, cfg))
    changed = edited(term.split()[0], *CONTROLS[term]).loss(
        cfg_file, params, *batch)
    return abs(program - float(changed))


@pytest.mark.parametrize("term", [*CONTROLS, *PROGRAM_CONTROLS])
def test_a_changed_term_shows(term):
    """A reference with one term of the block altered (or a program
    with it altered, where the reference has no such parameter) is
    off by more than the chip's tolerance, in float32, where the
    unchanged pair agrees to 2e-5. With the embedding drawn at 0.25:
    the head is tied, so its deviation is the logits' (2 here, 0.4
    at the file's 0.05, where 512 positions in bf16 stay inside the
    tolerance and half of these would too)."""
    difference = control_difference(
        term, *_case("float32", embed_init_std=0.25))
    assert difference > worker.REFERENCE_TOLERANCE, (term, difference)


def test_the_reference_in_float8_shows():
    """The nearest precision below the program's: at this size and
    deviation it is off by ten times the tolerance. (At the cell's
    size it reads as any changed trunk does, within the positions'
    noise: PERF.md section 7.)"""
    cfg_file, cfg, params, batch = _case("float32", embed_init_std=0.25)
    program = float(llama.next_token_loss(params, batch, cfg))
    lower = edited("float8", *FLOAT8).loss(cfg_file, params, *batch)
    assert abs(program - float(lower)) > worker.REFERENCE_TOLERANCE


def test_reference_refuses_more_positions_than_the_source_declares():
    cfg_file, cfg, params, batch = _case("float32")
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
    assert "conv_general_dilated" not in src
    with open(os.path.join(cells.HERE, "families", "lfm2.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_attention_walks_rows_in_blocks():
    ref = edited("rows")
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (1, 64, 4, 8))
    k, v = (jax.random.normal(key, (1, 64, 2, 8)) for key in keys[1:])
    whole = ref.attention(q, k, v, rows=64)
    assert float(jnp.abs(whole - ref.attention(q, k, v, rows=8)).max()) < 1e-5
    from dlrover_tpu.ops.attention import mha_reference

    want = mha_reference(q, k, v).reshape(1, 64, -1)
    assert float(jnp.abs(whole - want).max()) < 1e-5


def test_program_config_refuses_what_it_does_not_pass_on():
    tiny = config("tiny-lfm2")
    traffic = {"seq": SEQ, "remat": "off", "loss_chunk": 0}
    with pytest.raises(ValueError, match="norm_topk_prob"):
        worker.program_config(
            {**tiny, "norm_topk_prob": False, "num_experts_per_tok": 1},
            traffic)
    with pytest.raises(ValueError, match="layer_types"):
        worker.program_config({**tiny, "num_hidden_layers": 5}, traffic)
    with pytest.raises(ValueError, match="conv_bias"):
        worker.program_config({**tiny, "conv_bias": True}, traffic)
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        worker.program_config(
            {**tiny, "routed_scaling_factor": 2.5}, traffic)
    raw = worker.program_config({**tiny, "norm_topk_prob": False}, traffic)
    assert raw.norm_topk_prob is False


# -- the share ---------------------------------------------------------------

def test_the_four_shares_parts_add_up_to_the_uncut_layer():
    """One expert layer of the reference at a small size, 16 experts:
    four chips' parts (experts 0-3, 4-7, 8-11, 12-15, each ranking
    all 16 and giving its own experts' terms) add up to the layer
    with every expert held; and the program's layer, given each
    share, gives the same parts."""
    from dlrover_tpu.parallel import moe

    ref = edited("shares")
    keys = jax.random.split(jax.random.key(7), 6)
    h, m, e = 32, 16, 16
    y = jax.random.normal(keys[0], (2, 24, h))
    p = {"router": jax.random.normal(keys[1], (h, e)),
         "expert_bias": 0.3 * jax.random.normal(keys[2], (e,))}
    blocks = {
        "w_gate": jax.random.normal(keys[3], (1, e, h, m)) * h ** -0.5,
        "w_up": jax.random.normal(keys[4], (1, e, h, m)) * h ** -0.5,
        "w_down": jax.random.normal(keys[5], (1, e, m, h)) * m ** -0.5,
    }
    with reference.HIGHEST():
        whole, balance = ref.experts(y, blocks, p, 0, 4, 0, True)
        parts = []
        for first in range(0, e, 4):
            held = {k: v[:, first:first + 4] for k, v in blocks.items()}
            part, again = ref.experts(y, held, p, 0, 4, first, True)
            assert float(again) == pytest.approx(float(balance), rel=1e-6)
            mine, aux = moe.dropless_moe_mlp(
                y, p["router"], *(held[k][0] for k in (
                    "w_gate", "w_up", "w_down")),
                k=4, norm_topk_prob=True, z_coef=0.0, first_held=first,
                gate="sigmoid", bias=p["expert_bias"],
            )
            np.testing.assert_allclose(mine, part, rtol=1e-4, atol=1e-5)
            assert float(aux) == pytest.approx(
                moe.BALANCE_LOSS_COEF * float(balance), rel=1e-5)
            parts.append(part)
    assert float(jnp.abs(parts[0]).max()) > 0.01
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-5)


# -- the counts --------------------------------------------------------------

def test_lfm2_counts_by_hand():
    c = config("lfm2-8b-a1b-ep4")
    s = lfm2.shape(c)
    assert (s["layers"], s["dense_layers"], s["attention_layers"],
            s["conv_layers"]) == (13, 1, 3, 10)
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["ffn"], s["dense_ffn"], s["taps"]) == (32, 8, 4, 1792, 7168, 3)
    # in millions of weights met a token: the conv operator 16.8, the
    # attention projections 10.5, one held expert in expectation 11.0
    # and the router, the dense MLP 44.0, the head 33.6
    conv, attention = 4 * 2048 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512
    sparse = 2048 * 32 + 3 * 2048 * 1792
    dense, head = 3 * 2048 * 7168, 2048 * 16384
    assert (conv, attention, sparse, dense, head) == (
        16_777_216, 10_485_760, 11_075_584, 44_040_192, 33_554_432)
    want = 10 * conv + 3 * attention + 12 * sparse + dense + head
    assert lfm2.matmul_params(c) == counts.matmul_params(c) == want
    assert want == 409_731_072
    # attention scores at 8,192: 2 x 32 x 64 x 8192 a layer and product
    attn = counts.attention_forward_flops_per_token(c, 8192)
    assert attn == 3 * 2 * 32 * 64 * 8192 == 100_663_296
    flops = counts.train_flops_per_token(c, 8192)
    assert flops == 3 * (2 * want + attn) == 2_760_376_320
    forward = flops / 3
    assert 2 * 10 * conv / forward == pytest.approx(0.365, abs=2e-3)
    assert 2 * 12 * 3 * 2048 * 1792 / forward == pytest.approx(0.287, abs=2e-3)
    assert attn / forward == pytest.approx(0.109, abs=2e-3)
    assert 2 * dense / forward == pytest.approx(0.096, abs=2e-3)
    assert 2 * head / forward == pytest.approx(0.073, abs=2e-3)
    # the kernels: seven causal products over the three attention layers
    kernel_flops, nbytes = counts.attention_kernel_step(c, 4, 8192)
    assert kernel_flops == 7 * 3 * 4 * 32 * 8192 * 8192 * 64
    q_like, kv_like = 4 * 8192 * 32 * 64 * 2, 4 * 8192 * 8 * 64 * 2
    assert nbytes == 3 * (6 * q_like + 6 * kv_like)
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(kernel_flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.058599, rel=1e-3)


def test_expert_and_convolution_steps_by_hand():
    c = config("lfm2-8b-a1b-ep4")
    flops, nbytes = lfm2.expert_matmul_step(c, 32768)
    rows = 32768 * 4 * 8 // 32
    assert rows == 32_768 and rows // 8 == 4096  # rows an expert
    a_layer = 3 * 2 * rows * 3 * 2048 * 1792
    assert flops == 12 * a_layer == 25_975_962_206_208  # 26.0 TFLOP
    weights = 3 * 8 * 3 * 2048 * 1792
    per_row = 2 * ((2048 + 1792) + (1792 + 2 * 2048)) + (
        (1792 + 2048) + (2048 + 2 * 1792))
    assert nbytes == 12 * 2 * (weights + rows * per_row)
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.131858, rel=1e-3)
    # the convolution: 8 + 14 bytes a token, channel and layer
    flops, nbytes = lfm2.short_conv_step(c, 32768)
    assert nbytes == 10 * 32768 * 2048 * 22 == 14_763_950_080
    assert flops == 10 * 32768 * 2048 * (8 + 23)
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "memory"
    assert seconds == pytest.approx(0.018027, rel=1e-3)


def test_every_published_number_is_run_but_the_cut():
    c = config("lfm2-8b-a1b-ep4")
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_dense_layers"],
            c["num_experts"], c["vocab_size"]) == (13, 1, 8, 16384)
    assert c["layer_types"] == c["published"]["layer_types"][1:14]
    assert c["share"]["router_width"] == c["published"]["num_experts"] == 32
    assert 4 * c["vocab_size"] == c["published"]["vocab_size"]
    assert c["depth"]["found"] == 13
    assert min(c["depth"]["accepted_peak_memory_in_bytes"].values()) >= 10e9
    bench = cells.benchmark()
    (entry,) = [e for e in bench["configs"] if e["name"] == "lfm2-8b-a1b-ep4"]
    assert entry["source"] == c["source"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    for metric in ("moe_expert_ms", "moe_expert_roofline_pct",
                   "short_conv_ms", "short_conv_roofline_pct"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == metric]
        assert CELL in m["workloads"], metric


# -- the readers on a step the chip recorded -------------------------------

def _recorded(name):
    path = os.path.join(os.path.dirname(__file__), "data", name)
    with open(path) as f:
        return reduce.reduce(json.load(f), steps=1)


def test_readers_on_the_recorded_step():
    """One step of the cell as the chip recorded it (PR 36), remat
    ``minimal``: a conv layer's forward kernel twice and its backward
    kernel once, all ``short_conv.<n>``; the attention layers' three
    kernels; the walk's grouped matmuls."""
    trace = _recorded("trace_excerpt_lfm2_v5e.json")
    conv = [row for row in trace["ops"]
            if short_conv_ms.KERNEL.search(row[0])]
    assert conv and all(name.startswith("short_conv") for name, _, _ in conv)
    assert not any(attn_kernel_ms.KERNEL.search(n) for n, _, _ in conv)
    assert not any(moe_expert_ms.KERNEL.search(n) for n, _, _ in conv)
    _, cfg_file, traffic = cells.load_cell(CELL)
    run = {"trace": trace, "config": cfg_file, "traffic": traffic,
           "cell": {"chips": 1}, "values": {}, "events": {},
           "peak": cells.peak_of("TPU v5 lite")}
    took = short_conv_ms.read(run)
    assert took == pytest.approx(
        1e3 * sum(t for _, t, _ in conv), rel=1e-9)
    least, bound = short_conv_roofline_pct.least_seconds(run)
    assert bound == "memory"
    assert least == pytest.approx(0.018027, rel=1e-3)
    share = short_conv_roofline_pct.read(run)
    assert share == pytest.approx(100 * least * 1e3 / took)
    assert 20 < share <= 100
    assert 30 < attn_kernel_ms.read(run) < 400
    least, bound = attn_roofline_pct.least_seconds(run)
    assert bound == "compute"
    assert least == pytest.approx(0.058599, rel=1e-3)
    assert 10 < attn_roofline_pct.read(run) < 100
    assert 80 < moe_expert_ms.read(run) < 400
    assert 20 < moe_expert_roofline_pct.read(run) < 100
    # a cell without the kernel: the readers have nothing to read
    _, other, mix = cells.load_cell("olmoe-1b-7b-1chip.steady")
    quiet = {**run, "config": other, "traffic": mix,
             "trace": {**trace, "ops": [
                 row for row in trace["ops"] if row not in conv]}}
    assert short_conv_ms.read(quiet) is None
    assert short_conv_roofline_pct.read(quiet) is None
    assert short_conv_roofline_pct.read({**run, "trace": None}) is None
