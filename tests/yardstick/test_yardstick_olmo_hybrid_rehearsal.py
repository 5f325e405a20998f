"""The new cell's control flow at the tiny size on the CPU: launcher,
agent, worker, coworkers, the reference check (the recurrence walked
token by token with a state of two widths and attention in blocks of
rows, against the program's chunked scan with one decay a head),
warm-up, window."""

import json

from .test_yardstick_rehearse_steady import rehearse

CELL = "olmo-hybrid-7b-vp8.steady"


def test_olmo_hybrid_cell_rehearsal_is_whole_and_not_correct():
    line, out = rehearse(CELL, "tiny-olmo_hybrid", trace=0, seconds="4")
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "a rehearsal with tiny-olmo_hybrid" in out
    ref = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("reference:")
    ).split(": ", 1)[1])
    assert abs(ref["difference"]) < 0.02  # bf16 at 96-wide streams
    built = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("step_program:")
    ).split(": ", 1)[1])
    assert built["kernel_in_step"] is False  # the plain paths off the TPU
