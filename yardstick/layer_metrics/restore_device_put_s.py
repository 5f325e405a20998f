"""Enqueueing the copies to the device inside a restore."""

from yardstick import program_spans

NAME, UNIT = "restore_device_put_s", "s"
LAYER = "checkpoint"
MOVES, SOURCE = "resume_s", "host_clock"

program_spans.arm()


def read(run):
    """Summed ``ckpt.restore.device_put`` spans inside the
    successor's ``ckpt.restore``: the ``jax.device_put`` calls, which
    is the enqueue and not the copies' completion; that is what is
    left of ``restore_s``."""
    return program_spans.inside_restore(run, "ckpt.restore.device_put")
