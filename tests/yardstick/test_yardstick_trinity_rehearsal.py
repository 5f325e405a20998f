"""The new cell's control flow at the tiny size on the CPU: launcher,
agent, worker, coworkers, the reference check, warm-up, window; the
trainer moves the router's selection bias in every step of it."""

import json

from .test_yardstick_rehearse_steady import rehearse

CELL = "trinity-mini-ep8.steady"


def test_trinity_cell_rehearsal_is_whole_and_not_correct():
    line, out = rehearse(CELL, "tiny-trinity", trace=0)
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 3 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert "a rehearsal with tiny-trinity" in out
    ref = json.loads(next(
        ln for ln in out.splitlines() if ln.startswith("reference:")
    ).split(": ", 1)[1])
    assert ref["ok"] is True
