"""How long the prefetch thread waited for the coworkers a batch."""

from yardstick import program_spans

NAME, UNIT = "data_ring_wait_ms", "ms"
LAYER = "data plane"
MOVES, SOURCE = "tokens_per_s", "host_clock"

program_spans.arm()


def read(run):
    """Median of the ``data.fetch`` spans that start inside the
    window: the fill thread blocked on the shm ring. The inside twin
    of ``data_wait_ms``, which times the step loop's ``next``."""
    return program_spans.window_median_ms(run, "data.fetch")
