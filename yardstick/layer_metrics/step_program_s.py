"""Tracing, lowering, compiling and cache reads during set-up."""

from yardstick import program_spans

NAME, UNIT = "step_program_s", "s"
LAYER = "process bootstrap"
MOVES, SOURCE = "setup_s", "host_clock"

program_spans.arm()


def read(run):
    """Seconds of set-up the first worker spent inside jax's own
    compile events (the ``xla.*`` spans that end before the window
    starts), for every program set-up runs: state init, reference
    check, the step, warm-up. A cache read lies inside its backend
    compile's event, so overlaps count once."""
    pid = program_spans.worker_pid(run["events"])
    window = program_spans.window_of(run["events"])
    if pid is None or window is None:
        return None
    made = program_spans.of(
        program_spans.spans(run), "xla.", pid=pid, before=window[0]
    )
    return program_spans.covered(made) if made else None
