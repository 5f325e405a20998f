"""Time training is stalled by a save."""

from yardstick import program_spans

NAME, UNIT = "save_stall_ms", "ms"
LAYER = "checkpoint"
MOVES, SOURCE = "tokens_per_s", "host_clock"

program_spans.arm()


def saves_in_window(run):
    """``(records, saves)``: the first worker's spans, and of them the
    ``ckpt.stage`` spans on its main thread that start inside the
    window, one a save begun there. None where the run has no worker,
    no window or no such save."""
    pid = program_spans.worker_pid(run["events"])
    window = program_spans.window_of(run["events"])
    if pid is None or window is None:
        return None
    records = [r for r in program_spans.spans(run)
               if r.get("pid") == pid]
    saves = [r for r in program_spans.of(
        records, "ckpt.stage", after=window[0])
        if r["ts"] < window[1] and r.get("thread") == "MainThread"]
    return (records, saves) if saves else None


def of_step(records, name, save):
    """The spans called ``name`` of the save that ``save`` (its
    ``ckpt.stage``) began: the same step, from its start on."""
    step = save["attrs"]["step"]
    return [r for r in program_spans.of(records, name, after=save["ts"])
            if (r.get("attrs") or {}).get("step") == step]


def read(run):
    """The first worker's main-thread time inside ``ckpt.stage`` (the
    dispatch of the device-to-host copies, and ``ckpt.submit_wait``
    where the lane was full) plus ``ckpt.wait_staged`` (the loop
    standing until the copies are on the host, before its next
    dispatch may donate the buffers), over the saves begun inside the
    window. None where there is none, or where the program writes no
    ``ckpt.wait_staged`` (it then gives ``ckpt.stage`` no ``bytes``
    either): the dispatch alone is not the stall."""
    found = saves_in_window(run)
    if found is None:
        return None
    records, saves = found
    if any("bytes" not in s["attrs"] for s in saves):
        return None
    stalled = sum(
        save["dur"] + sum(
            r["dur"] for r in of_step(records, "ckpt.wait_staged", save)
            if r.get("thread") == "MainThread")
        for save in saves
    )
    return 1e3 * stalled / len(saves)
