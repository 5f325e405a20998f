"""Model FLOP/s utilisation of the cell's chips."""

from yardstick import counts

NAME, UNIT = "mfu_pct", "%"
LAYER = "trainer step"
MOVES, SOURCE = "tokens_per_s", "host_clock"


def read(run):
    """The operations forward and backward require per token
    (``counts.train_flops_per_token``: causal attention, no
    recomputation) x this run's tokens per second, over the chips'
    published peak."""
    rate = run["values"].get("tokens_per_s")
    if rate is None or run["peak"] is None:
        return None
    flops = counts.train_flops_per_token(
        run["config"], run["traffic"]["seq"]
    )
    return 100.0 * flops * rate / (
        run["cell"]["chips"] * run["peak"]["bf16_flops_per_s"]
    )
