"""The share of the traced steps in which no operation ran."""

NAME, UNIT = "device_idle_pct", "%"
LAYER = "device"
MOVES, SOURCE = "tokens_per_s", "device_trace"


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
