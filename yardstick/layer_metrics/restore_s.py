"""Reading the saved state back onto the device."""

NAME, UNIT = "restore_s", "s"
LAYER = "checkpoint"
MOVES, SOURCE = "resume_s", "host_clock"


def read(run):
    """Host clock around ``ckpt.restore(...)`` and
    ``block_until_ready`` on what it returned, in the successor."""
    restored = run["events"].get("restored")
    return restored[-1]["restore_secs"] if restored else None
