"""The harness: what it refuses, that BENCHMARK.json and the files
agree, and that a new configuration, traffic mix, job kind and
per-layer metric are new files and new entries only."""

import json
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
    assert traffic["mesh"]["data"] * traffic["mesh"]["fsdp"] == (
        cell["chips"])
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


def test_additions_are_new_files_and_entries_only(tmp_path):
    """One configuration, one mix, one kind and one metric, as
    throw-away files: the harness finds each by its name and no file
    that was there changes."""
    tag = f"zz-throwaway-{os.getpid()}"
    mod = tag.replace("-", "_")
    files = {
        f"configs/{tag}.json": json.dumps(
            {"family": "gpt", "source": "nowhere", "reduced": []}),
        f"traffic/{tag}.json": json.dumps(
            {"kind": mod, "mesh": {"data": 1, "fsdp": 1}}),
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
    try:
        for rel, text in files.items():
            with open(os.path.join(cells.HERE, rel), "x") as f:
                f.write(text)
        cell, config, traffic = cells.load_cell(f"{tag}.cell", bench)
        assert config["family"] == "gpt" and traffic["kind"] == mod
        assert cells.kind_module(traffic).summarize({}, cell, 1) == {}
        reader = cells.metric_module(tag)
        assert reader.read({"events": {"n": [{"v": 21}]}}) == 42
    finally:
        for rel in files:
            path = os.path.join(cells.HERE, rel)
            if os.path.exists(path):
                os.remove(path)


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
