"""From a worker's death to its successor's process start."""

NAME, UNIT = "respawn_s", "s"
LAYER = "launcher, master, agent"
MOVES, SOURCE = "resume_s", "host_clock"


def read(run):
    """The dead worker's last timestamp (written just before the call
    at which it dies) to the successor's own first timestamp: the
    agent noticing the exit, waiting out the process group, reporting
    to the master and starting the next process."""
    dying = run["events"].get("dying")
    starts = [s for s in run["events"].get("start", [])
              if s["restart_count"] > 0]
    if not dying or not starts:
        return None
    return starts[-1]["t_process_start"] - dying[-1]["t_death"]
