"""A CPU rehearsal of the ``steady`` kind at a tiny size: the whole
path (launcher, agent, worker, coworkers, report, last line) runs,
and for want of a chip the run is not ``correct``."""

import json
import os
import subprocess

from yardstick import cells

from . import on_two_cores

BENCH = cells.benchmark(os.path.join(cells.CHECKOUT, "BENCHMARK.json"))


def rehearse(cell, tiny, trace, seconds="2"):
    got = subprocess.run(
        on_two_cores(
            os.path.join(cells.HERE, "run.py"),
            "--workload", cell, "--seed", str(2 ** 31 + 11),
            "--seconds", seconds, "--trace", str(trace),
            "--rehearse", tiny),
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert got.returncode == 0, got.stderr[-3000:]
    return json.loads(got.stdout.splitlines()[-1]), got.stdout


def steady_cell():
    for cell in BENCH["workloads"]:
        _, _, traffic = cells.load_cell(cell["name"], BENCH)
        if traffic["kind"] == "steady" and cell["chips"] == 1:
            return cell["name"]


def test_steady_rehearsal_is_whole_and_not_correct():
    line, out = rehearse(steady_cell(), "tiny-llama", trace=0)
    assert line["correct"] is False
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
    assert "a rehearsal with tiny-llama" in out
    # what the earlier lines say
    for word in ("compile_cache:", "device:", "step_program:",
                 "reference:", "warmup:", "window:"):
        assert word in out, word
    window = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("window:")
    ).split(": ", 1)[1])
    assert window["compile_requests"] == 0


def test_traced_rehearsal_reports_no_device_metric():
    """No device plane in a CPU trace: the readers of the device
    trace find nothing and say nothing."""
    line, _ = rehearse(steady_cell(), "tiny-gpt", trace=1)
    assert line["correct"] is False
    assert "data_wait_ms" in line["metrics"]
    for name in ("attn_kernel_ms", "attn_roofline_pct",
                 "device_idle_pct", "mfu_pct"):
        assert name not in line["metrics"]
    assert "busy_s" not in line["device"]
    assert "breakdown" not in line
