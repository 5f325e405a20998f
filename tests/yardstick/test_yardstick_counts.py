"""The yardstick's operation and byte counts against numbers worked
by hand from the published sizes."""

import importlib
import json
import os

import pytest

from yardstick import cells, counts


def config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_layer_and_head_by_hand():
    c = config("mistral-7b-l4")
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each;
    # gate, up, down: 4096 x 14336 each
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert counts.matmul_params(c) == 4 * layer + 4096 * 32000
    # attention, causal, forward, a token: 2 products x 4 layers x
    # 32 heads x 128 x 4096 keys / 2 seen x 2 ops = 2 * L * h * d * s
    attn = 2 * 4 * 32 * 128 * 4096
    assert counts.attention_forward_flops_per_token(c, 4096) == attn
    want = 3 * (2 * (4 * layer + 4096 * 32000) + attn)
    assert counts.train_flops_per_token(c, 4096) == want
    assert want == pytest.approx(6.4236e9, rel=1e-4)


def test_depth_scales_only_the_layers():
    l4, l16 = config("mistral-7b-l4"), config("mistral-7b-l16")
    head = 4096 * 32000
    assert (counts.matmul_params(l16) - head
            == 4 * (counts.matmul_params(l4) - head))


def test_gpt2_xl_by_hand():
    c = config("gpt2-xl")
    # q, k, v, o: 1600 x 1600 each; fc and proj: 1600 x 6400 each
    layer = 4 * 1600 * 1600 + 2 * 1600 * 6400
    assert layer == 30_720_000
    # the tied head is still a matrix multiplication
    assert counts.matmul_params(c) == 48 * layer + 1600 * 50257
    attn = 2 * 48 * 25 * 64 * 1024
    want = 3 * (2 * (48 * layer + 1600 * 50257) + attn)
    assert counts.train_flops_per_token(c, 1024) == want
    assert want == pytest.approx(9.8017e9, rel=1e-4)


def test_attention_kernel_ops_and_bytes_by_hand():
    c = config("mistral-7b-l4")
    flops, nbytes = counts.attention_kernel_step(c, 3, 4096)
    # 7 causal products (2 forward, 5 backward) of s * s * d a head
    assert flops == 7 * 4 * 3 * 32 * 4096 * 4096 * 128
    q = 3 * 4096 * 32 * 128 * 2  # bf16
    kv = 3 * 4096 * 8 * 128 * 2
    # fwd: q, k, v in, o out; bwd: q, k, v, o, do in, dq, dk, dv out
    assert nbytes == 4 * ((2 * q + 2 * kv) + (4 * q + 4 * kv))
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(flops, nbytes, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(flops / 197e12)


def test_roofline_names_the_memory_bound():
    peak = cells.peak_of("TPU v5 lite")
    seconds, bound = counts.roofline_seconds(1e9, 819e9, peak)
    assert (seconds, bound) == (pytest.approx(1.0), "memory")


@pytest.mark.parametrize("module,name,rest", [
    ("counts", "shape", ()),
    ("counts", "matmul_params", ()),
    ("counts", "train_flops_per_token", (64,)),
    ("counts", "attention_kernel_step", (1, 64)),
    ("worker", "program_config", ({},)),
    ("reference", "loss", ({}, None, None)),
])
def test_unknown_family_has_no_counts(module, name, rest):
    """No default family: the file that does not exist is named."""
    ask = getattr(importlib.import_module("yardstick." + module), name)
    with pytest.raises(
        cells.UnknownName, match=r"yardstick/(families|references)/"
        r"mamba\.py does not exist",
    ):
        ask({"family": "mamba"}, *rest)


#: taken from the parent commit (9b7561d), where the counts were one
#: chain of branches in counts.py: configuration -> sequence length,
#: sequences a chip, and the four counts at those
PARENT_COUNTS = {
    "mistral-7b-l4.steady": (
        4096, 3.0, 1003487232, 6423576576.0,
        (5772436045824.0, 3019898880.0)),
    "gpt2-xl.steady": (
        1024, 12.0, 1554971200, 9801686400.0,
        (6764573491200.0, 22649241600.0)),
    "mistral-7b-l16.fsdp4": (
        4096, 1.0, 3620732928, 23335010304.0,
        (7696581394432.0, 4026531840.0)),
}


@pytest.mark.parametrize("cell", sorted(PARENT_COUNTS))
def test_the_move_into_family_files_changed_no_count(cell):
    seq, per_chip, params, train, kernel = PARENT_COUNTS[cell]
    entry, c, traffic = cells.load_cell(cell, cells.benchmark(
        os.path.join(cells.CHECKOUT, "BENCHMARK.json")))
    assert traffic["seq"] == seq
    assert traffic["global_batch"] / entry["chips"] == per_chip
    got = counts.matmul_params(c)
    assert (got, type(got)) == (params, int)
    assert counts.train_flops_per_token(c, seq) == train
    assert counts.attention_kernel_step(c, per_chip, seq) == kernel
