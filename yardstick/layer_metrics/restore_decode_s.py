"""Decoding the saved members inside a restore."""

from yardstick import program_spans

NAME, UNIT = "restore_decode_s", "s"
LAYER = "checkpoint"
MOVES, SOURCE = "resume_s", "host_clock"

program_spans.arm()


def read(run):
    """Summed ``ckpt.restore.decode`` spans inside the successor's
    ``ckpt.restore``: turning members back into arrays of the saved
    dtype (a copy for every extension dtype, bfloat16 among them)."""
    return program_spans.inside_restore(run, "ckpt.restore.decode")
