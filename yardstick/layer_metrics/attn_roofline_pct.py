"""The attention kernels' share of their roofline."""

from yardstick import counts
from yardstick.layer_metrics import attn_kernel_ms

NAME, UNIT = "attn_roofline_pct", "%"
LAYER = "attention kernel"
MOVES, SOURCE = "tokens_per_s", "device_trace"


def least_seconds(run):
    """``(seconds, bound)``: the least time one chip could take for
    its sequences' attention in a step, and whether operations or
    bytes set it."""
    traffic = run["traffic"]
    flops, nbytes = counts.attention_kernel_step(
        run["config"],
        traffic["global_batch"] / run["cell"]["chips"],
        traffic["seq"],
    )
    return counts.roofline_seconds(flops, nbytes, run["peak"])


def read(run):
    if run["trace"] is None or run["peak"] is None:
        return None
    took = attn_kernel_ms.kernel_seconds_per_step(run["trace"])
    if took is None:
        return None
    return 100.0 * least_seconds(run)[0] / took
