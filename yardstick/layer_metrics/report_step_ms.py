"""What elastic supervision costs the loop a step."""

from yardstick import program_spans

NAME, UNIT = "report_step_ms", "ms"
LAYER = "launcher, master, agent"
MOVES, SOURCE = "tokens_per_s", "host_clock"

program_spans.arm()


def read(run):
    """Median of the ``train.report_step`` spans that start inside
    the window: hang detection, fault injection, the polls of the
    master's rollback and transition orders, the step count sent to
    it every few steps."""
    return program_spans.window_median_ms(run, "train.report_step")
