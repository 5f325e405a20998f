"""What a restarted process needs before it can step, restore apart."""

from yardstick.layer_metrics import restore_s

NAME, UNIT = "boot_s", "s"
LAYER = "process bootstrap"
MOVES, SOURCE = "resume_s", "host_clock"


def read(run):
    """The successor's process start to its first retired step, less
    ``restore_s``: imports, opening the chip, rendezvous with the
    master, building trainer and data plane, and the step program
    (a read from the compile cache, or a compile)."""
    first = run["events"].get("first_step")
    starts = [s for s in run["events"].get("start", [])
              if s["restart_count"] > 0]
    restore = restore_s.read(run)
    if not first or not starts or restore is None:
        return None
    return (first[-1]["done"] - starts[-1]["t_process_start"]
            - restore)
