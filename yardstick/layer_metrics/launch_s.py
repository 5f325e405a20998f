"""From the launcher's first line of work to the worker's spawn."""

from yardstick import program_spans

NAME, UNIT = "launch_s", "s"
LAYER = "launcher, master, agent"
MOVES, SOURCE = "setup_s", "host_clock"

program_spans.arm()


def read(run):
    """Start of ``launch.run`` to the end of the first ``agent.spawn``
    (the one whose child is the first worker): the local master's
    start, the agent's heartbeat and rendezvous, the fork. The
    launcher's own imports come before it and are not in it."""
    pid = program_spans.worker_pid(run["events"])
    if pid is None:
        return None
    records = program_spans.spans(run)
    spawned = [r for r in program_spans.of(records, "agent.spawn")
               if (r.get("attrs") or {}).get("pid") == pid]
    if not spawned:
        return None
    launched = program_spans.of(
        records, "launch.run", pid=spawned[0]["pid"]
    )
    if not launched:
        return None
    return spawned[0]["ts"] + spawned[0]["dur"] - launched[0]["ts"]
