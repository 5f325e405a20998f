"""What Python's collector held the step loop for, a step."""

from yardstick import program_spans

NAME, UNIT = "gc_pause_ms", "ms"
LAYER = "trainer step"
MOVES, SOURCE = "tokens_per_s", "host_clock"

program_spans.arm()


def read(run):
    """Summed length of the first worker's ``gc.collect`` spans on its
    main thread that start inside the window (every collection of
    generation 1 or 2, and any of generation 0 from 1 ms on), over the
    steps that completed inside it. 0.0 where the collector's hook ran
    (the worker wrote such a span at any time) and caught nothing in
    the window; None where the program has no hook."""
    pid = program_spans.worker_pid(run["events"])
    window = program_spans.window_of(run["events"])
    if pid is None or window is None:
        return None
    pauses = program_spans.of(
        program_spans.spans(run), "gc.collect", pid=pid)
    counted = sum(
        1 for r in run["events"]["window"][-1].get("rows", ())
        if r["done"] <= window[1]
    )
    if not pauses or not counted:
        return None
    return 1e3 * sum(
        r["dur"] for r in pauses
        if r.get("thread") == "MainThread"
        and window[0] <= r["ts"] < window[1]
    ) / counted
