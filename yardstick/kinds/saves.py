"""Job kind ``saves``: the ``steady`` job with a restart point kept in
host RAM, measured for what a save costs the step loop.

Worker side (``work``): ``steady``'s sequence (fresh state from the
seed, the reference check, the step compiled ahead, warm-up, the
window, the traced steps), with the window driven in pieces. When its
``save_at_window_step``-th step has been dispatched and retired, and
every ``save_every`` steps from there while the clock is inside the
window, the loop calls ``ckpt.save`` on the whole state (async staging
to the default RAM tier, ``durable=False``) and then
``ckpt.wait_staged()``, as a loop whose step donates its state must
before the next dispatch. The stall is inside the window's time, so
``tokens_per_s`` (``steady.summarize``'s rule) pays for it.

The guarantee is part of ``correct``: at each save, after the wait,
the state's checksum is taken on the device; after the window and the
traced steps the live state is dropped, the newest save restored from
the RAM tier and its checksum compared: the acknowledged save is read
back, leaf for leaf, or the run is not ``correct``.
"""

import statistics
import time

from yardstick.kinds import resume, steady


def _checksum_program():
    """``ctx.checksum``'s sums behind one jitted function: the call
    before the window compiles it and the calls at the saves find it
    (``ctx.checksum`` builds its program anew at every call, a compile
    request each, and the window allows none)."""
    import jax
    import jax.numpy as jnp

    sums = jax.jit(lambda tree: [
        jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)
    ])
    return lambda tree: [float(x) for x in sums(tree)]


def _save(ctx, state, step, checksum):
    """One save as a loop makes it, and what it cost the loop."""
    import jax

    t0 = time.time()
    ctx.ckpt.save(
        step,
        {"params": state[0], "opt_state": state[1],
         "step": jax.numpy.array(step)},
        durable=False,
    )
    t_dispatched = time.time()
    ctx.ckpt.wait_staged()
    t1 = time.time()
    # the live buffers are still valid: nothing was dispatched since
    sums = checksum(state)
    return {"step": step, "t_save": t0,
            "stage_secs": t_dispatched - t0,
            "wait_staged_secs": t1 - t_dispatched,
            # the benchmark's own, inside the window with the stall
            "checksum_secs": time.time() - t1, "checksum": sums}


def _landed(ctx, step):
    """Whether the save of ``step`` is a restart point yet: its
    archive stands in the RAM tier."""
    return ctx.ckpt.shard_provider()(step) is not None


def work(ctx):
    import jax

    state = ctx.init_state()
    ctx.check_reference(state[0])
    batches = iter(ctx.loader)
    # the first batch only shapes the ahead-of-time compile
    mb = ctx.trainer.microbatch(next(batches))
    ctx.compile_step(*state, mb)
    # what the save and its check compile, ahead of the window too
    checksum = _checksum_program()
    checksum(state)
    jax.numpy.array(0)
    state, rows = ctx.drive(
        state, batches, 1, steps=ctx.traffic["warmup_steps"]
    )
    jax.block_until_ready(state)
    ctx.report("warmup", rows=rows)
    first = rows[-1]["step"] + 1
    every = ctx.traffic["save_every"]
    saves = []
    with ctx.compilations() as compiled:
        t_start = time.time()
        end = t_start + ctx.args.seconds
        state, rows = ctx.drive(
            state, batches, first,
            steps=ctx.traffic["save_at_window_step"],
        )
        while time.time() < end:
            saves.append(_save(ctx, state, rows[-1]["step"], checksum))
            step_secs = statistics.median(
                b["done"] - a["done"] for a, b in zip(rows, rows[1:])
            )
            if time.time() + every * step_secs >= end:
                break  # the next save falls outside the window
            state, more = ctx.drive(
                state, batches, rows[-1]["step"] + 1, steps=every
            )
            rows += more
        if time.time() < end:
            state, more = ctx.drive(
                state, batches, rows[-1]["step"] + 1, until=end
            )
            rows += more
        for save in saves:
            save["landed_inside_window"] = _landed(ctx, save["step"])
    ctx.report(
        "window", t_window_start=t_start, seconds=ctx.args.seconds,
        rows=rows, compile_requests=compiled["requests"], saves=saves,
        state_bytes=sum(x.nbytes for x in jax.tree.leaves(state)),
    )
    if ctx.args.trace:
        state, _ = ctx.trace_steps(state, batches, rows[-1]["step"] + 1)
    # the acknowledged save is read back: two states do not fit the
    # chip beside the step's buffers, so the live one goes first
    ctx.ckpt.wait()
    del state
    t0 = time.time()
    restored, _ = ctx.ckpt.restore(target=resume._target(ctx))
    jax.block_until_ready(restored)
    if restored is None:
        ctx.report("read_back", step=None, checksum=None)
        return
    ctx.report(
        "read_back", step=int(restored["step"]),
        restore_secs=time.time() - t0,
        checksum=checksum(
            (restored["params"], restored["opt_state"])
        ),
    )


def summarize(events, cell, seconds):
    """``steady.summarize`` and the guarantee."""
    out = steady.summarize(events, cell, seconds)
    window = events.get("window", [None])[-1]
    if window is None:
        return out
    problems = out["problems"]
    saves = window.get("saves") or []
    read_back = events.get("read_back", [None])[-1]
    out["saves"] = len(saves)
    if not saves:
        problems.append("no save was begun inside the window")
    elif read_back is None or read_back["step"] is None:
        problems.append("the saved state was not read back")
    else:
        last = saves[-1]
        out["landed_inside_window"] = last["landed_inside_window"]
        if read_back["step"] != last["step"]:
            problems.append(
                f"read back step {read_back['step']}, saved step "
                f"{last['step']}"
            )
        if read_back["checksum"] != last["checksum"]:
            problems.append("the checksum of the state read back is "
                            "not the saved state's")
    return out
