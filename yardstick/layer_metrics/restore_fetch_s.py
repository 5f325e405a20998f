"""Reading the saved members inside a restore."""

from yardstick import program_spans

NAME, UNIT = "restore_fetch_s", "s"
LAYER = "checkpoint"
MOVES, SOURCE = "resume_s", "host_clock"

program_spans.arm()


def read(run):
    """Summed ``ckpt.restore.fetch`` spans inside the successor's
    ``ckpt.restore``: the reads of the archive's members (from tmpfs,
    a peer or the store)."""
    return program_spans.inside_restore(run, "ckpt.restore.fetch")
