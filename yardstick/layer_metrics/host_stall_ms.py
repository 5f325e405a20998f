"""How late the window's late steps came, together."""

from yardstick import program_spans

NAME, UNIT = "host_stall_ms", "ms"
LAYER = "launcher, master, agent"
MOVES, SOURCE = "tokens_per_s", "host_clock"

program_spans.arm()


def read(run):
    """Summed ``late_s`` of the first worker's ``train.stall`` spans
    that were due inside the window: the record the hang detector's
    watchdog closes for a step that came later than 1.5 times the
    median cadence (and the median plus 0.2 s). 0.0 where the window
    has steps (``train.report_step``) and no stall; None where it has
    none."""
    pid = program_spans.worker_pid(run["events"])
    window = program_spans.window_of(run["events"])
    if pid is None or window is None:
        return None
    records = program_spans.spans(run)
    steps = [r for r in program_spans.of(
        records, "train.report_step", pid=pid, after=window[0])
        if r["ts"] < window[1]]
    if not steps:
        return None
    return 1e3 * sum(
        r["attrs"]["late_s"]
        for r in program_spans.of(records, "train.stall", pid=pid)
        if window[0] <= (r.get("attrs") or {}).get("due_ts", 0.0)
        < window[1]
    )
