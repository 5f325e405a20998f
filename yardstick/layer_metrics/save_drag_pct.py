"""What the lanes' background work costs the loop while it runs."""

import statistics

from yardstick import program_spans
from yardstick.layer_metrics import save_stall_ms

NAME, UNIT = "save_drag_pct", "%"
LAYER = "checkpoint"
MOVES, SOURCE = "tokens_per_s", "host_clock"


def read(run):
    """A step is the time from one completion to the next in the
    report's window rows. Dragged steps lie wholly inside a
    ``ckpt.serialize`` and after that save's ``ckpt.wait_staged`` (or
    its ``ckpt.stage``, where the loop did not have to wait): the loop
    runs, and the serializer lane encodes, writes and hashes on a
    thread of the same interpreter. Clean steps lie wholly outside
    every ``ckpt.*`` span of the worker. The median of the dragged
    over the median of the clean, less one, in percent. None where
    either kind is missing."""
    found = save_stall_ms.saves_in_window(run)
    if found is None:
        return None
    records, saves = found
    rows = run["events"]["window"][-1]["rows"]
    steps = [(a["done"], b["done"]) for a, b in zip(rows, rows[1:])]
    busy = [(r["ts"], r["ts"] + r["dur"])
            for r in program_spans.of(records, "ckpt.")]
    background = []
    for save in saves:
        held = [save] + save_stall_ms.of_step(
            records, "ckpt.wait_staged", save)
        for lane in save_stall_ms.of_step(
                records, "ckpt.serialize", save):
            background.append((
                max(r["ts"] + r["dur"] for r in held),
                lane["ts"] + lane["dur"],
            ))
    dragged = [hi - lo for lo, hi in steps
               if any(a <= lo and hi <= b for a, b in background)]
    clean = [hi - lo for lo, hi in steps
             if not any(lo < b and a < hi for a, b in busy)]
    if not dragged or not clean:
        return None
    return 100.0 * (
        statistics.median(dragged) / statistics.median(clean) - 1.0
    )
