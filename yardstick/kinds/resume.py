"""Job kind ``resume``: one crash, one restart in place, measured for
what the failure costs.

Set-up is the first incarnation: fresh state from the seed, steps up
to ``save_at``, one durable save to the RAM tier with the state's
checksum reported, steps on to ``die_at``, where the documented
injector (``DLROVER_FAULT_INJECT=crash@<die_at>`` in the mix's
``env``) ends the process with ``os._exit``. The worker stamps the
report just before that call: there the window opens. The agent
restarts the worker in place; the successor restores, reports the
checksum of what it got, and steps until the window ends.

``resume_s`` is the death to the successor's first retired optimizer
step, both ``time.time()`` on one host.
"""

import math
import time

#: a restored state that replays the dead worker's samples gives its
#: losses again: the same program on the same chip, the same bits.
#: The tolerance is for a restart that compiled the step anew.
REPLAY_TOLERANCE = 1e-3
#: where the data plane delivers other samples, the successor's losses
#: stay in the band of the dead worker's, this far beyond its ends
BAND_MARGIN = 0.25


def _target(ctx):
    import jax

    params, opt_state = ctx.trainer.abstract_state()
    return {"params": params, "opt_state": opt_state,
            "step": jax.ShapeDtypeStruct((), jax.numpy.int32)}


def _one_step(ctx, state, batches, step):
    """A step retired before the next is dispatched: set-up and the
    steps around the failure are not a speed measurement."""
    import numpy as np

    batch = next(batches)
    # which samples these were: the first tokens of the first sequence
    data_id = np.asarray(batch[0][0, :4]).tolist()
    params, opt_state, loss = ctx.trainer.train_step(
        *state, ctx.trainer.microbatch(batch)
    )
    row = {"step": step, "loss": float(loss), "data_id": data_id,
           "done": time.time()}
    return (params, opt_state), row


def work(ctx):
    import jax

    save_at, die_at = ctx.traffic["save_at"], ctx.traffic["die_at"]
    t0 = time.time()
    restored, _ = ctx.ckpt.restore(target=_target(ctx))
    jax.block_until_ready(restored)
    restore_secs = time.time() - t0
    batches = iter(ctx.loader)
    if restored is None:
        state = ctx.init_state()
        rows = []
        for step in range(1, die_at + 1):
            state, row = _one_step(ctx, state, batches, step)
            rows.append(row)
            if step == save_at:
                t0 = time.time()
                ctx.ckpt.save(
                    step,
                    {"params": state[0], "opt_state": state[1],
                     "step": jax.numpy.array(step)},
                    durable=True,
                )
                ctx.report(
                    "saved", step=step, save_secs=time.time() - t0,
                    checksum=ctx.checksum(state),
                    state_bytes=sum(
                        x.nbytes for x in jax.tree.leaves(state)
                    ),
                )
            if step == die_at:
                ctx.report("dying", t_death=time.time(), rows=rows)
            ctx.reporter.report_step(step)  # the injector lives here
        raise RuntimeError(
            f"still alive after step {die_at}: the mix's env did not "
            "inject the crash"
        )
    state = (restored["params"], restored["opt_state"])
    step = int(restored["step"])
    ctx.report(
        "restored", start_step=step, restore_secs=restore_secs,
        checksum=ctx.checksum(state),
    )
    end = ctx.events("dying")[-1]["t_death"] + ctx.args.seconds
    with ctx.compilations() as cache:
        state, row = _one_step(ctx, state, batches, step + 1)
    ctx.report("first_step", cache_requests=cache["requests"],
               cache_hits=cache["hits"], **row)
    rows = [row]
    if ctx.args.trace:
        state, traced = ctx.trace_steps(
            state, batches, rows[-1]["step"] + 1
        )
        rows += traced
    while time.time() < end:
        state, row = _one_step(ctx, state, batches, rows[-1]["step"] + 1)
        rows.append(row)
    ctx.report("steps", rows=rows)


def summarize(events, cell, seconds):
    last = {name: evs[-1] for name, evs in events.items()}
    missing = [n for n in ("saved", "dying", "restored", "first_step",
                           "steps") if n not in last]
    if "dying" not in last:
        return {"problems": [f"the report has no {missing}"]}
    t_death = last["dying"]["t_death"]
    out = {"problems": [], "attempted": 1, "failed": 1,
           "t_window_start": t_death, "values": {}}
    problems = out["problems"]
    if missing:
        problems.append(f"the report has no {missing}")
        return out
    out["values"]["resume_s"] = last["first_step"]["done"] - t_death
    if out["values"]["resume_s"] <= seconds:
        out["failed"] = 0
    else:
        problems.append("no step was retired inside the window")
    # the acknowledged save is read back
    if last["restored"]["start_step"] != last["saved"]["step"]:
        problems.append(
            f"restored step {last['restored']['start_step']}, saved "
            f"step {last['saved']['step']}"
        )
    if last["restored"]["checksum"] != last["saved"]["checksum"]:
        problems.append("the restored state's checksum is not the "
                        "saved state's")
    # and training goes on from it
    before = {r["step"]: r for r in last["dying"]["rows"]}
    after = last["steps"]["rows"]
    if not all(r["loss"] is not None and math.isfinite(r["loss"])
               for r in after):
        problems.append("a loss after the restart is not finite")
        return out
    low = min(r["loss"] for r in before.values()) - BAND_MARGIN
    high = max(r["loss"] for r in before.values()) + BAND_MARGIN
    replayed = 0
    for r in after:
        was = before.get(r["step"])
        if was is not None and was.get("data_id") == r.get("data_id"):
            replayed += 1
            if abs(was["loss"] - r["loss"]) > REPLAY_TOLERANCE:
                problems.append(
                    f"step {r['step']} replayed the dead worker's "
                    f"samples and lost {r['loss']}, not {was['loss']}"
                )
        elif not low <= r["loss"] <= high:
            problems.append(
                f"step {r['step']} lost {r['loss']}, outside "
                f"[{low}, {high}]"
            )
    out["replayed_steps"] = replayed
    return out
