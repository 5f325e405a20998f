"""What the first worker waits for its devices."""

from yardstick import program_spans

NAME, UNIT = "chip_open_s", "s"
LAYER = "process bootstrap"
MOVES, SOURCE = "setup_s", "host_clock"

program_spans.arm()


def read(run):
    """``boot.distributed_init`` (none in a one-process world) plus
    ``boot.backend_open`` of the first incarnation: joining the
    processes, then the first ``jax.devices()``."""
    pid = program_spans.worker_pid(run["events"])
    if pid is None:
        return None
    records = program_spans.spans(run)
    opened = program_spans.of(records, "boot.backend_open", pid=pid)
    if not opened:
        return None
    joined = program_spans.of(records, "boot.distributed_init", pid=pid)
    return sum(r["dur"] for r in opened + joined)
