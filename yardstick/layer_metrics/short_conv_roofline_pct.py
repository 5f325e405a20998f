"""The gated short convolution kernels' share of their roofline."""

from yardstick import cells, counts
from yardstick.layer_metrics import short_conv_ms

NAME, UNIT = "short_conv_roofline_pct", "%"
LAYER = "short convolution"
MOVES, SOURCE = "tokens_per_s", "device_trace"


def least_seconds(run):
    """``(seconds, bound)``: the least time one chip could take for
    its tokens' convolutions in a step (the family's
    ``short_conv_step``: forward once and backward once, the least
    bytes), and whether operations or bytes set it; None for a family
    without the operator."""
    count = getattr(
        cells.family_module(run["config"]), "short_conv_step", None
    )
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
    took = short_conv_ms.kernel_seconds_per_step(run["trace"])
    least = least_seconds(run)
    if took is None or least is None:
        return None
    return 100.0 * least[0] / took
