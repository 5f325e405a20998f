"""The ``olmoe`` family: its program (models/llama.py with q/k norms
and the dropless experts) against ``references/olmoe.py`` at the tiny
size, each term of the block showing when it is changed; its counts
against integers worked by hand; its cell's rehearsal."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.parallel import moe
from yardstick import cells, counts, reduce, reference, worker
from yardstick.families import olmoe
from yardstick.layer_metrics import (
    attn_kernel_ms, attn_roofline_pct, moe_expert_ms,
    moe_expert_roofline_pct,
)

from .test_yardstick_rehearse_steady import rehearse

SEQ = 64
CELL = "olmoe-1b-7b-1chip.steady"


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def _case(dtype):
    cfg_file = dict(config("tiny-olmoe"), dtype=dtype)
    cfg = worker.program_config(
        cfg_file, {"seq": SEQ, "remat": "off", "loss_chunk": 0})
    params = llama.init_params(jax.random.key(7), cfg)
    # norm scales of one would hide a norm that is not applied
    keys = jax.random.split(jax.random.key(8), 2)
    for name, key in zip(("q_norm", "k_norm"), keys):
        scale = params["blocks"][name]
        params["blocks"][name] = scale * jax.random.uniform(
            key, scale.shape, minval=0.5, maxval=1.5)
    tokens, targets = worker.SeededTokens(
        5, SEQ, cfg_file["vocab_size"])(0, 3)
    batch = (jnp.asarray(tokens), jnp.asarray(targets))
    ref = float(reference.loss(cfg_file, params, tokens, targets))
    return cfg, params, batch, ref


def test_program_config_takes_the_sources_keys():
    cfg = worker.program_config(
        config("olmoe-1b-7b-1chip"),
        {"seq": 4096, "remat": "dots_attn_out", "loss_chunk": 0})
    assert (cfg.num_experts, cfg.moe_top_k) == (64, 8)
    assert cfg.norm_topk_prob is False and cfg.qk_norm is True
    assert cfg.moe_capacity_factor == 0.0  # dropless, stated
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (
        0.01, 0.001)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.head_dim) == (
        2048, 1024, 128)
    assert llama.param_count(cfg) == 3 * 419_569_664 + 206_047_232


def test_float32_program_agrees_with_the_reference():
    cfg, params, batch, ref = _case("float32")
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_bf16_program_is_inside_the_chip_tolerance():
    cfg, params, batch, ref = _case("bfloat16")
    program = float(llama.next_token_loss(params, batch, cfg))
    assert abs(program - ref) < worker.REFERENCE_TOLERANCE


@pytest.mark.parametrize("term,change", [
    ("no q/k norm", dict(qk_norm=False)),
    ("renormalised weights", dict(norm_topk_prob=True)),
    ("a dropped z-loss", dict(router_z_loss_coef=0.0)),
    ("a dropped balance loss", dict(router_aux_loss_coef=0.0)),
    ("top-1 f_e", "top1"),
    ("capacity drops", "capacity"),
])
def test_a_changed_term_shows(term, change, monkeypatch):
    """A program that leaves out or alters one term of the block is
    off by far more than the float32 agreement."""
    cfg, params, batch, ref = _case("float32")
    kw = {}
    if change == "top1":
        whole = moe.balance_loss
        monkeypatch.setattr(
            moe, "balance_loss",
            lambda probs, experts: whole(probs, experts[:, :1]))
    elif change == "capacity":
        # the einsum path with room for three quarters of the rows
        cfg = dataclasses.replace(cfg, moe_capacity_factor=0.75)
        kw["expert_parallel"] = True
    else:
        cfg = dataclasses.replace(cfg, **change)
    program = float(llama.next_token_loss(params, batch, cfg, **kw))
    assert abs(program - ref) > 1e-4, (term, program, ref)


def test_the_capacity_path_with_room_agrees_too():
    """The control of the case above: the einsum path differs from
    the reference by its drops, not by anything else."""
    cfg, params, batch, ref = _case("float32")
    roomy = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    program = float(llama.next_token_loss(
        params, batch, roomy, expert_parallel=True))
    assert abs(program - ref) < 2e-5, (program, ref)


def test_reference_refuses_more_positions_than_the_source_declares():
    cfg_file = config("tiny-olmoe")
    cfg, params, batch, _ = _case("float32")
    with pytest.raises(ValueError):
        reference.loss({**cfg_file, "max_position_embeddings": 32},
                       params, *batch)


def test_reference_imports_no_line_of_the_program():
    path = os.path.join(cells.HERE, "references", "olmoe.py")
    with open(path) as f:
        imports = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert imports == [
        "import functools\n", "import jax\n", "import jax.numpy as jnp\n",
        "from yardstick.reference import (\n",
    ]
    with open(os.path.join(cells.HERE, "families", "olmoe.py")) as f:
        top = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert top == []  # no JAX, nothing of the program, at import


def test_olmoe_counts_by_hand():
    c = config("olmoe-1b-7b-1chip")
    # q, k, v, o: 2048 x 2048 each; router 2048 x 64; 8 experts of
    # gate, up, down 2048 x 1024 each
    attention, router = 4 * 2048 * 2048, 2048 * 64
    experts = 8 * 3 * 2048 * 1024
    assert (attention, router, experts) == (
        16_777_216, 131_072, 50_331_648)
    layer = attention + router + experts
    assert layer == 67_239_936
    assert olmoe.matmul_params(c) == 3 * layer + 2048 * 50304
    assert counts.matmul_params(c) == 304_742_400
    attn = 2 * 3 * 16 * 128 * 4096  # causal, forward, a token
    want = 3 * (2 * 304_742_400 + attn)
    assert counts.train_flops_per_token(c, 4096) == want
    assert want == pytest.approx(1.9794e9, rel=1e-4)
    # the head's share of the counted operations, 3 layers and 16
    head = 2 * 2048 * 50304
    assert head / (want / 3) == pytest.approx(0.312, abs=2e-3)
    whole = 2 * (16 * layer + 2048 * 50304) + 2 * 16 * 16 * 128 * 4096
    assert head / whole == pytest.approx(0.078, abs=2e-3)
    # the attention kernels see 16 ungrouped heads of 128
    flops, _ = counts.attention_kernel_step(c, 3, 4096)
    assert flops == 7 * 3 * 3 * 16 * 4096 * 4096 * 128


def test_expert_matmul_step_by_hand():
    c = config("olmoe-1b-7b-1chip")
    flops, nbytes = olmoe.expert_matmul_step(c, 12288)
    rows = 12288 * 8
    assert rows == 98_304 and rows // 64 == 1536  # rows an expert
    a_layer = 3 * 2 * rows * 3 * 2048 * 1024
    assert a_layer == 3_710_851_743_744  # 3.71 TFLOP
    assert flops == 3 * a_layer
    weights = 3 * 64 * 3 * 2048 * 1024  # read, read again, gradient
    per_row = 2 * ((2048 + 1024) + (1024 + 2 * 2048)) + (
        (1024 + 2048) + (2048 + 2 * 1024))
    assert per_row == 23_552
    assert nbytes == 3 * 2 * (weights + rows * per_row)
    # 18.8 ms of operations a layer against 8.6 ms of bytes on a v5e:
    # bound by compute
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "compute"
    assert seconds / 3 == pytest.approx(0.018837, rel=1e-3)
    assert nbytes / 3 / peak["hbm_bytes_per_s"] == pytest.approx(
        0.008603, rel=1e-3)


def test_depth_scales_only_the_layers():
    c = config("olmoe-1b-7b-1chip")
    head = 2048 * 50304
    deep = dict(c, num_hidden_layers=16)
    assert (olmoe.matmul_params(deep) - head) * 3 == (
        olmoe.matmul_params(c) - head) * 16
    assert olmoe.expert_matmul_step(deep, 512)[0] * 3 == (
        olmoe.expert_matmul_step(c, 512)[0] * 16)


def test_every_published_number_is_run_but_the_depth():
    c = config("olmoe-1b-7b-1chip")
    differs = [k for k, v in c["published"].items()
               if k != "architectures" and c[k] != v]
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["depth"]["found"] == c["num_hidden_layers"] == 3
    assert min(c["depth"]["accepted_peak_memory_in_bytes"][k]
               for k in c["depth"]["accepted_peak_memory_in_bytes"]
               if k.startswith("3 layers")) >= 12e9


def test_olmoe_cell_rehearsal_is_whole_and_not_correct():
    line, out = rehearse(CELL, "tiny-olmoe", trace=0)
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "a rehearsal with tiny-olmoe" in out
    ref = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("reference:")
    ).split(": ", 1)[1])
    assert ref["ok"] is True  # the objective with its aux terms


# -- the expert layer's readers on a step the chip recorded ---------------

def _recorded(name):
    path = os.path.join(os.path.dirname(__file__), "data", name)
    with open(path) as f:
        return reduce.reduce(json.load(f), steps=1)


def _run(trace, cell):
    _, cfg_file, traffic = cells.load_cell(cell)
    return {"trace": trace, "config": cfg_file, "traffic": traffic,
            "cell": {"chips": 1}, "values": {}, "events": {},
            "peak": cells.peak_of("TPU v5 lite")}


def test_expert_readers_on_the_recorded_step():
    """One step of the cell as the chip recorded it (PR 29): nine
    grouped matmuls a layer (gate, up, down; forward, the rows'
    gradient, the matrices'), three layers."""
    trace = _recorded("trace_excerpt_olmoe_v5e.json")
    kernels = [row for row in trace["ops"]
               if moe_expert_ms.KERNEL.search(row[0])]
    assert sorted(n for _, _, n in kernels) == [3] * 9
    assert sorted(name.split(".")[0] for name, _, _ in kernels) == (
        ["gmm"] * 6 + ["tgmm"] * 3)
    run = _run(trace, CELL)
    took = moe_expert_ms.read(run)
    assert took == pytest.approx(81.961012, abs=1e-5)
    # least time: 11.13 TFLOP over 197 TFLOP/s = 56.51 ms, by compute
    least, bound = moe_expert_roofline_pct.least_seconds(run)
    assert bound == "compute"
    assert least == pytest.approx(0.0565104, rel=1e-4)
    share = moe_expert_roofline_pct.read(run)
    assert share == pytest.approx(100 * least * 1e3 / took)
    assert 60 < share < 100
    # the attention readers see the same step's three kernels
    assert attn_kernel_ms.read(run) == pytest.approx(22.788081, abs=1e-5)
    assert 40 < attn_roofline_pct.read(run) < 60


def test_expert_readers_match_no_op_of_a_dense_step():
    """On the recorded Mistral step, and for a family without experts,
    they find nothing and say nothing (as on the parent, whose program
    has no such kernel)."""
    trace = _recorded("trace_excerpt_v5e.json")
    assert not [row for row in trace["ops"]
                if moe_expert_ms.KERNEL.search(row[0])]
    for cell in (CELL, "mistral-7b-l4.steady"):
        run = _run(trace, cell)
        assert moe_expert_ms.read(run) is None
        assert moe_expert_roofline_pct.read(run) is None
    olmoe_step = _recorded("trace_excerpt_olmoe_v5e.json")
    assert moe_expert_roofline_pct.read(
        _run(olmoe_step, "mistral-7b-l4.steady")) is None
    for reader in (moe_expert_ms, moe_expert_roofline_pct):
        assert reader.read({**_run(None, CELL)}) is None


@pytest.mark.parametrize("name,hit", [
    ("gmm.33 bf16[98304,1024]", True),
    ("gmm bf16[98304,2048]", True),
    ("tgmm.13 bf16[64,1024,2048]", True),
    ("ragged-dot-none.2 bf16[98304,2048]", True),
    ("ragged-dot-none bf16[98304,1024]", True),
    ("ragged-dot-metadata.1 (s32[65]", False),
    ("fusion.819 bf16[98304,2048]", False),
    ("add_any.385 bf16[98304,2048]", False),
    ("flash_attention.47 (bf16[48,4096,128]", False),
    ("gmm_fusion.2 bf16[98304,1024]", False),
    ("custom-call.9 s32[1024]", False),
])
def test_expert_kernel_pattern(name, hit):
    assert bool(moe_expert_ms.KERNEL.search(name)) is hit
