"""The new cell's control flow at the tiny size on the CPU: launcher,
agent, worker, coworkers, the reference check (the recurrence walked
position by position against the program's chunked scan), warm-up,
window."""

import json

from .test_yardstick_rehearse_steady import rehearse

CELL = "nemotron-3-super-120b-a12b-ep64.steady"


def test_nemotron_cell_rehearsal_is_whole_and_not_correct():
    # four seconds: a step walks five scans and six expert layers
    line, out = rehearse(CELL, "tiny-nemotron", trace=0, seconds="4")
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "a rehearsal with tiny-nemotron" in out
    ref = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("reference:")
    ).split(": ", 1)[1])
    assert abs(ref["difference"]) < 0.02  # bf16 at 64-wide streams
    built = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("step_program:")
    ).split(": ", 1)[1])
    assert built["kernel_in_step"] is False  # the plain path off the TPU


def test_a_traced_rehearsal_reports_neither_of_the_scans_metrics():
    """No device plane in a CPU trace: ``ssd_ms`` and
    ``ssd_roofline_pct`` find nothing and say nothing, and the line is
    whole without them."""
    line, _ = rehearse(CELL, "tiny-nemotron", trace=1, seconds="3")
    assert line["correct"] is False and line["failed"] == 0
    assert "data_wait_ms" in line["metrics"]
    for name in ("ssd_ms", "ssd_roofline_pct", "mfu_pct",
                 "attn_kernel_ms"):
        assert name not in line["metrics"]
