"""The harness: what it refuses, that BENCHMARK.json and the files
agree, and that a new family, configuration, traffic mix, job kind
and per-layer metric are new files and new entries only."""

import json
import math
import os
import subprocess
import sys

import pytest

from yardstick import cells

RUN = os.path.join(cells.HERE, "run.py")
BENCH = cells.benchmark(os.path.join(cells.CHECKOUT, "BENCHMARK.json"))


def run(*argv, **env):
    return subprocess.run(
        [sys.executable, RUN, *argv], capture_output=True, text=True,
        env=dict(os.environ, **env), timeout=120,
    )


def test_unknown_cell_is_refused():
    got = run("--workload", "no-such.cell", "--seed", "1",
              "--seconds", "1", "--trace", "0")
    assert got.returncode == 2
    assert "no cell 'no-such.cell'" in got.stderr
    assert "correct" not in got.stdout


def test_unknown_names_raise():
    with pytest.raises(cells.UnknownName, match="per-layer metric"):
        cells.metric_module("no_such_metric")
    with pytest.raises(cells.UnknownName, match="job kind"):
        cells.kind_module({"kind": "no_such_kind"})
    with pytest.raises(cells.UnknownName, match="not in yardstick"):
        cells.peak_of("TPU v9 imaginary")
    with pytest.raises(cells.UnknownName):
        cells.peak_of("source")  # the table's own note is no device
    with pytest.raises(cells.UnknownName, match="configuration"):
        cells.load_cell(BENCH["workloads"][0]["name"], BENCH,
                        rehearse="no-such-config")


def test_peaks_are_the_published_v5e_figures():
    peak = cells.peak_of("TPU v5 lite")
    assert peak == {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


@pytest.mark.parametrize(
    "cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files(cell):
    got, config, traffic = cells.load_cell(cell["name"], BENCH)
    assert got is cell
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert entry["file"] == f"yardstick/configs/{cell['config']}.json"
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    for key, value in config["published"].items():
        if key in config and key not in config["reduced"]:
            assert config[key] == value, key
    assert math.prod(traffic["mesh"].values()) == cell["chips"]
    kind = cells.kind_module(traffic)
    assert callable(kind.work) and callable(kind.summarize)
    names = {m["name"] for m in cells.metrics_of(
        cell["name"], BENCH["end_to_end"])}
    assert "setup_s" in names and len(names) >= 2
    layer = cells.metrics_of(cell["name"], BENCH["per_layer"])
    assert layer
    for m in layer:
        assert m["moves"] in names, (m["name"], m["moves"])


@pytest.mark.parametrize(
    "entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_entry_is_its_module(entry):
    mod = cells.metric_module(entry["name"])
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"],
        entry["source"])
    empty = {"events": {}, "trace": None, "values": {}, "peak": None,
             "cell": BENCH["workloads"][0], "config": {},
             "traffic": {}}
    assert mod.read(empty) is None  # nothing to read: nothing said


#: a throw-away family, as its two files: a decoder of no layer (the
#: embedding, then the head), its counts, and one count of its own
#: (attention over a window of 8 keys) in the dense one's place
FAMILY = """
def program_config(config, traffic):
    return {"width": config["width"], "seq": traffic["seq"]}
def shape(config):
    return {"hidden": config["width"], "ffn": 0, "layers": 1,
            "heads": 2, "kv_heads": 1, "head_dim": 4,
            "vocab": config["vocab_size"]}
def matmul_params(config):
    return config["width"] * config["vocab_size"]
def attention_forward_flops_per_token(config, seq):
    return 2.0 * 2 * 4 * min(seq, 8)
"""
FAMILY_REFERENCE = """
from yardstick.reference import embed, mean_nll
def loss(config, params, tokens, targets):
    return mean_nll(embed(params["embed"], tokens), params["head"],
                    targets)
"""


def _tree():
    """Every file the benchmark has, by its bytes."""
    paths = [os.path.join(cells.CHECKOUT, "BENCHMARK.json")] + [
        os.path.join(top, name)
        for top, _, names in os.walk(cells.HERE)
        if "__pycache__" not in top for name in names]
    found = {}
    for path in paths:
        with open(path, "rb") as f:
            found[path] = f.read()
    return found


def test_additions_are_new_files_and_entries_only():
    """One family, one configuration, one mix, one kind and one
    metric, as throw-away files: the harness finds each by its name
    and no file that was there changes. (Not ``git status``: the
    tests also run on a tree that is not committed yet.)"""
    import numpy as np

    from yardstick import counts, reference, worker
    from yardstick.layer_metrics import attn_roofline_pct, mfu_pct

    tag = f"zz-throwaway-{os.getpid()}"
    mod = tag.replace("-", "_")
    files = {
        f"families/{mod}.py": FAMILY,
        f"references/{mod}.py": FAMILY_REFERENCE,
        f"configs/{tag}.json": json.dumps(
            {"family": mod, "source": "nowhere", "reduced": [],
             "width": 16, "vocab_size": 32}),
        f"traffic/{tag}.json": json.dumps(
            {"kind": mod, "mesh": {"data": 1, "fsdp": 1},
             "global_batch": 2, "seq": 64}),
        f"kinds/{mod}.py":
            "def work(ctx): pass\n"
            "def summarize(events, cell, seconds): return {}\n",
        f"layer_metrics/{mod}.py":
            "NAME = 'x'\n"
            "def read(run): return run['events']['n'][-1]['v'] * 2\n",
    }
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(
        {"name": f"{tag}.cell", "config": tag, "traffic": tag,
         "chips": 1, "why": "a test"})
    before = _tree()
    try:
        for rel, text in files.items():
            with open(os.path.join(cells.HERE, rel), "x") as f:
                f.write(text)
        cell, config, traffic = cells.load_cell(f"{tag}.cell", bench)
        assert config["family"] == mod and traffic["kind"] == mod
        assert cells.kind_module(traffic).summarize({}, cell, 1) == {}
        reader = cells.metric_module(tag)
        assert reader.read({"events": {"n": [{"v": 21}]}}) == 42
        # the family, through every name that asks its files
        assert worker.program_config(config, traffic) == {
            "width": 16, "seq": 64}
        flops = 3 * (2 * 16 * 32 + 2 * 2 * 4 * 8)  # its own window
        assert counts.train_flops_per_token(config, 64) == flops
        kernel = counts.attention_kernel_step(config, 2, 64)
        assert kernel == (7.0 * 2 * 2 * 64 * 64 * 4,
                          6.0 * (2 * 64 * 2 * 4 * 2 + 2 * 64 * 4 * 2))
        run = {"values": {"tokens_per_s": 1e6}, "cell": cell,
               "config": config, "traffic": traffic,
               "peak": cells.peak_of("TPU v5 lite"),
               "trace": {"steps": 2, "ops": [
                   ("flash_attention.3", 1e-3, 2)]}}
        assert mfu_pct.read(run) == pytest.approx(
            100 * flops * 1e6 / 197e12)
        assert attn_roofline_pct.read(run) == pytest.approx(
            100 * max(kernel[0] / 197e12, kernel[1] / 819e9) / 0.5e-3)
        rng = np.random.default_rng(0)
        params = {"embed": rng.standard_normal((32, 16), "float32"),
                  "head": np.zeros((16, 32), "float32")}
        tokens = rng.integers(0, 32, (2, 64), dtype=np.int32)
        assert float(reference.loss(config, params, tokens, tokens)
                     ) == pytest.approx(np.log(32), rel=1e-6)
        after = _tree()
        assert {k: after[k] for k in before} == before
        assert sorted(set(after) - set(before)) == sorted(
            os.path.join(cells.HERE, rel) for rel in files)
    finally:
        for rel in files:
            path = os.path.join(cells.HERE, rel)
            if os.path.exists(path):
                os.remove(path)


#: files that must find a family by the configuration's name for it
NAMELESS = ["run.py", "worker.py", "cells.py", "counts.py",
            "reference.py", "kinds", "layer_metrics"]


@pytest.mark.parametrize("where", NAMELESS)
def test_no_file_of_the_harness_names_a_family(where):
    """A family exists when ``families/<family>.py`` and
    ``references/<family>.py`` do: nothing else holds its name."""
    families = sorted(
        name[:-3] for name in os.listdir(
            os.path.join(cells.HERE, "families"))
        if name.endswith(".py") and name != "__init__.py")
    assert {"llama", "gpt"} <= set(families)
    path = os.path.join(cells.HERE, where)
    paths = [path] if where.endswith(".py") else [
        os.path.join(path, n) for n in sorted(os.listdir(path))
        if n.endswith(".py")]
    assert paths
    for path in paths:
        with open(path) as f:
            text = f.read()
        for family in families:
            for quote in "\"'":
                assert quote + family + quote not in text, (
                    path, family)


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "yardstick/run.py"]
    four = [c for c in BENCH["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["workloads"]:
        assert len(c["why"]) <= 200 and "\n" not in c["why"]
