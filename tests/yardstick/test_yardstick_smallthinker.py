"""The ``smallthinker`` family: its program (models/llama.py with a
layer pattern, a router on the block's input and a share of ReLU-gated
experts) against ``references/smallthinker.py`` at the tiny size, each
term of the block showing when the reference is changed; its counts
against integers worked by hand; its cell's rehearsal; the readers on
a step the chip recorded."""

import dataclasses
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from yardstick import cells, counts, reduce, reference, worker
from yardstick.families import smallthinker
from yardstick.layer_metrics import (
    attn_kernel_ms, attn_roofline_pct, moe_expert_ms,
    moe_expert_roofline_pct,
)

SEQ = 128
CELL = "smallthinker-21b-a3b-ep4.steady"
REFERENCE = os.path.join(cells.HERE, "references", "smallthinker.py")


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype):
    cfg_file = dict(config("tiny-smallthinker"), dtype=dtype)
    cfg = worker.program_config(
        cfg_file, {"seq": SEQ, "remat": "off", "loss_chunk": 0})
    params = llama.init_params(jax.random.key(1), cfg)
    tokens, targets = worker.SeededTokens(
        5, SEQ, cfg_file["vocab_size"])(0, 2)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    return cfg_file, cfg, params, batch


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config("smallthinker-21b-a3b-ep4"),
        {"seq": 16384, "remat": "minimal", "loss_chunk": 0})
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.head_dim) == (
        2560, 768, 128)
    assert (cfg.num_heads, cfg.num_kv_heads) == (28, 4)
    assert cfg.num_heads * cfg.head_dim == 3584 != cfg.hidden_size
    assert (cfg.num_experts, cfg.moe_top_k) == (64, 6)  # the router's
    assert (cfg.moe_first_expert_held, cfg.moe_experts_held) == (0, 16)
    assert cfg.moe_router_input == "block_input"
    assert cfg.moe_expert_act == "relu" and cfg.norm_topk_prob is True
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.0)
    assert (cfg.rope_theta, cfg.norm_eps) == (1.5e6, 1e-6)
    # the embedding near the layers' own output (the file's
    # assumed.init): the first five layers route evenly, the last
    # three do not, and the balance term then answers a changed layer
    assert cfg.embed_init_std == 0.7
    assert cfg.sliding_window_size == 4096
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) * 2
    assert cfg.layer_kinds() == (
        (None, False), (4096, True), (4096, True), (4096, True))
    # 20.97 M of attention, 0.16 M of router, 16 x 5.898 M of experts
    # and two norms a layer; embedding and head over 37,984 ids
    layer = 20_971_520 + 163_840 + 16 * 5_898_240 + 2 * 2560
    assert layer == 115_512_320
    assert llama.param_count(cfg) == 8 * layer + 2 * 2560 * 37984 + 2560
    assert llama.param_count(cfg) == 1_118_579_200  # 6.71 GB at 6 bytes


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


def _edited(name, *pairs):
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
    "no window": ((
        '''            window=(config["sliding_window_size"]
                    if config["sliding_window_layout"][i] else None),''',
        "            window=None,"),),
    "RoPE on the NoPE layers": ((
        'rope=bool(config["rope_layout"][i]),', "rope=True,"),),
    "the router reads h after attention": (
        ('        logits = x @ p["router"]  '
         "# [b, s, width], from the block's input\n", ""),
        ('        y = rms_norm(x, p["mlp_norm"], eps)\n',
         '        y = rms_norm(x, p["mlp_norm"], eps)\n'
         '        logits = y @ p["router"]\n'),
    ),
    "silu for relu": (("jax.nn.relu(", "jax.nn.silu("),),
    "top-k among the held experts: all k parts count": ((
        "        top, chosen = jax.lax.top_k(logits, per_token)"
        "  # ties: lower index\n",
        '        held = blocks["w_gate"].shape[1]\n'
        "        top, chosen = jax.lax.top_k(\n"
        "            logits[..., first_held:first_held + held], per_token)\n"
        "        chosen = chosen + first_held\n"),),
    "weights of the full softmax, not renormalised": ((
        '"bsk,bske->bse", jax.nn.softmax(top, axis=-1), hot',
        '"bsk,bske->bse", jnp.exp(top - jax.nn.logsumexp('
        'logits, axis=-1, keepdims=True)), hot'),),
}


@pytest.mark.parametrize("term", CONTROLS)
def test_a_changed_term_shows(term):
    """A reference with one term of the block altered is off the
    program by far more than the float32 agreement (2e-5): each of
    the six by 3e-3 and more at these weights (1e-3 and more at five
    other seeds of them)."""
    cfg_file, cfg, params, batch = _case("float32")
    program = float(llama.next_token_loss(params, batch, cfg))
    changed = float(_edited(term.split()[0], *CONTROLS[term]).loss(
        cfg_file, params, *batch))
    assert abs(program - changed) > 1e-3, (term, program, changed)


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
    with open(os.path.join(cells.HERE, "families", "smallthinker.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_reference_attention_walks_rows_in_blocks():
    """Whatever the block of query rows, the same band."""
    ref = _edited("rows")
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (1, 64, 7, 8))
    k, v = (jax.random.normal(key, (1, 64, 1, 8)) for key in keys[1:])
    for window in (None, 16):
        whole = ref.banded_attention(q, k, v, window, rows=64)
        blocks = ref.banded_attention(q, k, v, window, rows=8)
        assert float(jnp.abs(whole - blocks).max()) < 1e-5
    from dlrover_tpu.ops.attention import mha_reference

    want = mha_reference(q, k, v, window=16).reshape(1, 64, -1)
    assert float(jnp.abs(ref.banded_attention(
        q, k, v, 16, rows=16) - want).max()) < 1e-5


def test_smallthinker_counts_by_hand():
    c = config("smallthinker-21b-a3b-ep4")
    s = smallthinker.shape(c)
    assert (s["head_dim"], s["experts"], s["experts_held"],
            s["experts_per_token"], s["window"]) == (128, 64, 16, 6, 4096)
    assert s["sliding_window_layout"] == s["rope_layout"] == (0, 1, 1, 1) * 2
    # q and o 2560 x 3584, k and v 2560 x 512; the router 2560 x 64;
    # 6 x 16 / 64 = 1.5 experts of three 2560 x 768 matrices
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    router, experts = 2560 * 64, 3 * 3 * 2560 * 768 // 2
    assert (attention, router, experts) == (20_971_520, 163_840, 8_847_360)
    head = 2560 * 37984
    assert smallthinker.matmul_params(c) == 8 * (
        attention + router + experts) + head == 337_100_800
    assert counts.matmul_params(c) == 337_100_800
    # live pairs a head and sequence at 16,384: the whole triangle in
    # a full layer, the band in a windowed one (44% of it)
    full, windowed = 16384 * 16384 // 2, 4096 * 4096 // 2 + 12288 * 4096
    assert (full, windowed) == (134_217_728, 58_720_256)
    assert smallthinker.live_pairs(c, 16384) == 2 * (full + 3 * windowed)
    assert smallthinker.live_pairs(c, 2048) == 8 * 2048 * 2048 // 2
    # a token, forward: 117.4 MFLOP in a full layer, 51.4 in a
    # windowed one, 543 over two periods
    per_pair = 2 * 2 * 128 * 28
    assert per_pair * full // 16384 == 117_440_512
    assert per_pair * windowed // 16384 == 51_380_224
    attn = counts.attention_forward_flops_per_token(c, 16384)
    assert attn == 2 * (117_440_512 + 3 * 51_380_224) == 543_162_368
    want = 3 * (2 * 337_100_800 + 543_162_368)
    assert counts.train_flops_per_token(c, 16384) == want == 3_652_091_904
    # attention is 45% of the counted operations, the head 16%
    assert attn / (want / 3) == pytest.approx(0.446, abs=2e-3)
    assert 2 * head / (want / 3) == pytest.approx(0.160, abs=2e-3)
    # the kernels: seven products over the live pairs, 31.1 TFLOP a
    # step, 158 ms at 197 TFLOP/s
    flops, nbytes = counts.attention_kernel_step(c, 1, 16384)
    assert flops == 7 * 2 * 128 * 28 * 2 * (full + 3 * windowed)
    assert flops == 31_147_102_830_592
    q_like, kv_like = 16384 * 28 * 128 * 2, 16384 * 4 * 128 * 2
    assert nbytes == 8 * (6 * q_like + 6 * kv_like)
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.15811, rel=1e-3)


def test_expert_matmul_step_by_hand():
    c = config("smallthinker-21b-a3b-ep4")
    flops, nbytes = smallthinker.expert_matmul_step(c, 16384)
    rows = 16384 * 6 * 16 // 64
    assert rows == 24_576 and rows // 16 == 1536  # rows an expert
    a_layer = 3 * 2 * rows * 3 * 2560 * 768
    assert a_layer == 869_730_877_440
    assert flops == 8 * a_layer  # 6.96 TFLOP
    weights = 3 * 16 * 3 * 2560 * 768  # read, read again, gradient
    per_row = 2 * ((2560 + 768) + (768 + 2 * 2560)) + (
        (768 + 2560) + (2560 + 2 * 768))
    assert per_row == 25_856
    assert nbytes == 8 * 2 * (weights + rows * per_row)
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(0.035319, rel=1e-3)


def test_every_published_number_is_run_but_the_cut():
    c = config("smallthinker-21b-a3b-ep4")
    differs = [k for k, v in c["published"].items() if c[k] != v]
    assert sorted(differs) == sorted(c["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert (c["num_hidden_layers"], c["moe_num_primary_experts"],
            c["vocab_size"]) == (8, 16, 37984)
    assert c["share"]["router_width"] == (
        c["published"]["moe_num_primary_experts"]) == 64
    assert 4 * c["vocab_size"] == c["published"]["vocab_size"]
    assert c["depth"]["found"] == 8
    assert min(c["depth"]["accepted_peak_memory_in_bytes"].values()) >= 10e9


# -- the readers on a step the chip recorded -------------------------------

def _recorded(name):
    path = os.path.join(os.path.dirname(__file__), "data", name)
    with open(path) as f:
        return reduce.reduce(json.load(f), steps=1)


def test_readers_on_the_recorded_step():
    """One step of the cell as the chip recorded it (PR 34), remat
    ``minimal``: a layer's forward kernel twice and its two backward
    kernels, in two shapes of result; a layer's grouped matmuls over
    buffers of all 98,304 tokens x k rows, of which they compute the
    held experts'."""
    trace = _recorded("trace_excerpt_smallthinker_v5e.json")
    attn = [row for row in trace["ops"]
            if attn_kernel_ms.KERNEL.search(row[0])]
    # a name a layer of the period and kernel, met in both periods
    assert sorted(n for _, _, n in attn) == [2] * 16
    full = sorted(t for _, t, _ in attn)[-4:]  # the full layer's four
    rest = sorted(t for _, t, _ in attn)[:-4]
    assert sum(rest) / 3 < 0.62 * sum(full)  # a windowed layer's
    experts = [row for row in trace["ops"]
               if moe_expert_ms.KERNEL.search(row[0])]
    assert all("98304" in name or name.startswith("tgmm")
               for name, _, _ in experts)
    kinds = [re.split(r"[. ]", name)[0] for name, _, _ in experts]
    # 9 and 3 a layer of the period (the table keeps the 200 longest)
    assert kinds.count("tgmm") == 12 and 28 <= kinds.count("gmm") <= 36
    _, cfg_file, traffic = cells.load_cell(CELL)
    run = {"trace": trace, "config": cfg_file, "traffic": traffic,
           "cell": {"chips": 1}, "values": {}, "events": {},
           "peak": cells.peak_of("TPU v5 lite")}
    took = attn_kernel_ms.read(run)
    assert took == pytest.approx(426.495636, abs=1e-5)
    least, bound = attn_roofline_pct.least_seconds(run)
    assert bound == "compute"
    assert least == pytest.approx(0.158107, rel=1e-4)
    assert attn_roofline_pct.read(run) == pytest.approx(
        100 * least * 1e3 / took)
    assert 30 < attn_roofline_pct.read(run) < 100
    took = moe_expert_ms.read(run)
    assert took == pytest.approx(64.485994, abs=1e-5)
    least, bound = moe_expert_roofline_pct.least_seconds(run)
    assert bound == "compute"
    assert least == pytest.approx(0.0353190, rel=1e-4)
    assert 40 < moe_expert_roofline_pct.read(run) < 100
