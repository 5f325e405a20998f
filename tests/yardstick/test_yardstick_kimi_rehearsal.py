"""The new cell's control flow at the tiny size on the CPU: launcher,
agent, worker, coworkers, the reference check (the recurrence walked
token by token and latent attention in blocks of rows against the
program's chunked scan and its attention on parts), warm-up, window."""

import json

from .test_yardstick_rehearse_steady import rehearse

CELL = "kimi-linear-48b-a3b-ep16.steady"


def test_kimi_cell_rehearsal_is_whole_and_not_correct():
    # six seconds: a step walks four scans and reads 15 in four
    # seconds alone, a fifth of that beside five busy workers
    line, out = rehearse(CELL, "tiny-kimi", trace=0, seconds="6")
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "a rehearsal with tiny-kimi" in out
    ref = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("reference:")
    ).split(": ", 1)[1])
    assert abs(ref["difference"]) < 0.02  # bf16 at 64-wide streams
    built = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("step_program:")
    ).split(": ", 1)[1])
    assert built["kernel_in_step"] is False  # the plain path off the TPU
