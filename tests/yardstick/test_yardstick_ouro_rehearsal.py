"""The new cell's control flow at the tiny size on the CPU: launcher,
agent, worker, coworkers, the reference check (four passes of three
layers against the program's scan over passes), warm-up, window."""

import json

from .test_yardstick_rehearse_steady import rehearse

CELL = "ouro-2.6b-1chip.steady"


def test_ouro_cell_rehearsal_is_whole_and_not_correct():
    line, out = rehearse(CELL, "tiny-ouro", trace=0)
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "a rehearsal with tiny-ouro" in out
    # every line the kind writes ahead of the last
    for word in ("compile_cache:", "device:", "step_program:",
                 "reference:", "warmup:", "window:"):
        assert word in out, word
    ref = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("reference:")
    ).split(": ", 1)[1])
    assert ref["ok"] is True
    window = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("window:")
    ).split(": ", 1)[1])
    assert window["compile_requests"] == 0
    assert all(loss == loss for loss in window["losses"])  # finite
