"""The selective scan kernels' share of their roofline."""

from yardstick import cells, counts
from yardstick.layer_metrics import selective_scan_ms

NAME, UNIT = "selective_scan_roofline_pct", "%"
LAYER = "selective scan"
MOVES, SOURCE = "tokens_per_s", "device_trace"


def least_seconds(run):
    """``(seconds, bound)``: the least time one chip could take for
    its tokens' scans in a step (the family's ``selective_scan_step``:
    forward once and backward once, the recurrence's own operations and
    the least bytes), and whether operations or bytes set it; None for
    a family without the operator."""
    count = getattr(
        cells.family_module(run["config"]), "selective_scan_step", None)
    if count is None:
        return None
    traffic = run["traffic"]
    tokens = (
        traffic["global_batch"] * traffic["seq"] // run["cell"]["chips"]
    )
    return counts.roofline_seconds(
        *count(run["config"], tokens), run["peak"]
    )


def read(run):
    if run["trace"] is None or run["peak"] is None:
        return None
    took = selective_scan_ms.kernel_seconds_per_step(run["trace"])
    least = least_seconds(run)
    if took is None or least is None:
        return None
    return 100.0 * least[0] / took
