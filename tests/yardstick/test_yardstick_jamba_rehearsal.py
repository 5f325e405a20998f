"""The new cell's control flow at the tiny size on the CPU: launcher,
agent, worker, coworkers, the reference check (the recurrence walked
position by position against the program's chunked scan), warm-up,
window. What the scan's two readers say of a trace without their
kernels is in ``test_yardstick_jamba.py``."""

import json

from .test_yardstick_rehearse_steady import rehearse

CELL = "jamba2-3b-l14.steady"


def test_jamba_cell_rehearsal_is_whole_and_not_correct():
    # four seconds: a step walks thirteen scans
    line, out = rehearse(CELL, "tiny-jamba", trace=0, seconds="4")
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "a rehearsal with tiny-jamba" in out
    ref = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("reference:")
    ).split(": ", 1)[1])
    assert abs(ref["difference"]) < 0.02  # bf16 at 96-wide streams
    built = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("step_program:")
    ).split(": ", 1)[1])
    assert built["kernel_in_step"] is False  # the plain path off the TPU
