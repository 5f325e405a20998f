"""Job kind ``steady``: a fault-free run, measured for step speed.

Worker side (``work``): fresh state from the seed, the reference
check, the step compiled or read from the cache, warm-up, then the
window. Parent side (``summarize``): the report's lines into
``correct``, ``attempted``, ``failed`` and ``tokens_per_s``.

The rate is taken over whole steps completed inside the window: their
tokens, over the time from the first one's start (the device idle,
warm-up retired) to the last one's completion. A step the window's
end cuts costs nothing; every step that completed inside counts.
"""

import math
import time


def work(ctx):
    import jax

    state = ctx.init_state()
    ctx.check_reference(state[0])
    batches = iter(ctx.loader)
    # the first batch only shapes the ahead-of-time compile
    mb = ctx.trainer.microbatch(next(batches))
    ctx.compile_step(*state, mb)
    state, rows = ctx.drive(
        state, batches, 1, steps=ctx.traffic["warmup_steps"]
    )
    jax.block_until_ready(state)
    ctx.report("warmup", rows=rows)
    with ctx.compilations() as compiled:
        t_start = time.time()
        state, rows = ctx.drive(
            state, batches, rows[-1]["step"] + 1,
            until=t_start + ctx.args.seconds,
        )
    ctx.report(
        "window", t_window_start=t_start, seconds=ctx.args.seconds,
        rows=rows, compile_requests=compiled["requests"],
    )
    if ctx.args.trace:
        ctx.trace_steps(state, batches, rows[-1]["step"] + 1)


def summarize(events, cell, seconds):
    """``events``: the report's lines by event name (lists)."""
    problems = []
    window = events.get("window", [None])[-1]
    reference = events.get("reference", [None])[-1]
    if window is None:
        return {"problems": ["the worker reported no window"]}
    rows = window["rows"]
    end = window["t_window_start"] + window["seconds"]
    counted = [r for r in rows if r["done"] <= end]
    failed = [
        r for r in rows
        if r["loss"] is None or not math.isfinite(r["loss"])
    ]
    if not counted:
        problems.append("no step completed inside the window")
    if failed:
        problems.append(f"{len(failed)} step(s) without a finite loss")
    if window["compile_requests"]:
        problems.append(
            f"{window['compile_requests']} compilation(s) inside the "
            "window"
        )
    if reference is None:
        problems.append("no comparison with the reference")
    elif not reference["ok"]:
        problems.append(
            f"loss {reference['program_loss']} against the "
            f"reference's {reference['reference_loss']}: off by more "
            f"than {reference['tolerance']}"
        )
    out = {
        "problems": problems, "attempted": len(rows),
        "failed": len(failed),
        "t_window_start": window["t_window_start"], "values": {},
    }
    if counted:
        tokens = len(counted) * events["built"][-1]["tokens_per_step"]
        span = counted[-1]["done"] - window["t_window_start"]
        out["values"]["tokens_per_s"] = tokens / span
        out["counted_steps"] = len(counted)
    return out
