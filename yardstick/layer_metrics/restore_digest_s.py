"""Verifying the saved members inside a restore."""

from yardstick import program_spans

NAME, UNIT = "restore_digest_s", "s"
LAYER = "checkpoint"
MOVES, SOURCE = "resume_s", "host_clock"

program_spans.arm()


def read(run):
    """Summed ``ckpt.restore.digest`` spans inside the successor's
    ``ckpt.restore``: the sha256 passes over the members (in the RAM
    tier's reader a pass of its own, the read and the zip's crc32
    with it)."""
    return program_spans.inside_restore(run, "ckpt.restore.digest")
