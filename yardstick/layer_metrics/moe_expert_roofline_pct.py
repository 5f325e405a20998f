"""The grouped expert matmuls' share of their roofline."""

from yardstick import cells, counts
from yardstick.layer_metrics import moe_expert_ms

NAME, UNIT = "moe_expert_roofline_pct", "%"
LAYER = "expert layer"
MOVES, SOURCE = "tokens_per_s", "device_trace"


def least_seconds(run):
    """``(seconds, bound)``: the least time one chip could take for
    its tokens' expert matmuls in a step (the family's
    ``expert_matmul_step``), and whether operations or bytes set it;
    None for a family without experts."""
    count = getattr(
        cells.family_module(run["config"]), "expert_matmul_step", None
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
    took = moe_expert_ms.kernel_seconds_per_step(run["trace"])
    least = least_seconds(run)
    if took is None or least is None:
        return None
    return 100.0 * least[0] / took
