"""How long the step loop waited for its next batch."""

import statistics

NAME, UNIT = "data_wait_ms", "ms"
LAYER = "data plane"
MOVES, SOURCE = "tokens_per_s", "host_clock"


def read(run):
    """Median over the window's steps of the host-clock time around
    ``next(loader)``: the shm ring, the coworkers and the prefetch to
    the device all hide behind it or show in it."""
    window = run["events"].get("window")
    if not window:
        return None
    return 1e3 * statistics.median(
        r["data_wait"] for r in window[-1]["rows"]
    )
