"""The yardstick's worker: the training process of every cell.

Started by the elastic launcher (``run.py`` is its parent), as a user
starts ``examples/llama_train.py``, of which this is the benchmark's
own copy: ``init_from_env``, the master client, a trainer from
``models.make_trainer_for`` over the mesh the example builds, the
coworker shm data plane, the flash checkpointer with its default RAM
tier, the elastic reporter (hang detection, fault injection). What it
adds: the model comes from a configuration file (through its
family's ``families/<family>.py``), the job from a traffic file, and
the loop from ``kinds/<kind>.py``; and it appends what it saw, as JSON
lines, to the report that ``run.py`` reads.

It holds nothing that names a cell or a family.
"""

import time

#: the process's own first timestamp, before any heavy import
T_PROCESS_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from yardstick import cells  # noqa: E402

#: exit code of a worker that refuses its device (not 17, the
#: injected crash's; not one the agent retries)
RC_REFUSED = 3
#: |program's loss - reference's loss| on the seeded check batch. The
#: program computes in bf16 with float32 accumulation, the reference
#: in float32 throughout, from the same bf16 parameters; the mean over
#: thousands of positions averages the rounding, and what is left was
#: read on the chip at 0.00001-0.0004 over 60 runs of three cells
#: (PERF.md, PR 25), and at 0.0012 at the tiny sizes of the tests.
#: Eight times the largest reading: a program that drops a term (a
#: norm's eps, the rotary embedding, the scale of the scores) moves
#: the loss by 0.03 and more at these sizes.
REFERENCE_TOLERANCE = 0.003


class SeededTokens:
    """Coworker-side batch function: tokens from the run's seed and
    the sample index (reproducible whoever makes them, in whatever
    order), targets the next token, the last position masked."""

    def __init__(self, seed, seq, vocab):
        self.seed, self.seq, self.vocab = seed, seq, vocab

    def __call__(self, start, end):
        rng = np.random.default_rng([self.seed, start])
        tokens = rng.integers(
            0, self.vocab, (end - start, self.seq), dtype=np.int32
        )
        targets = np.concatenate(
            [tokens[:, 1:],
             np.full((end - start, 1), -1, dtype=np.int32)], axis=1,
        )
        return tokens, targets


def program_config(config, traffic):
    """The program's own config object from the configuration file
    (the source's key names) and the mix's step settings."""
    return cells.family_module(config).program_config(config, traffic)


class Context:
    """What a job kind's ``work`` gets: the built pieces of one
    worker, and the few things every kind does with them."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def report(self, event, **fields):
        """One JSON line per event: a crashed incarnation's lines
        survive it, and the next incarnation adds its own."""
        fields = dict(event=event, t=time.time(),
                      restart_count=self.restart_count, **fields)
        with open(self.report_path, "a") as f:
            f.write(json.dumps(fields) + "\n")
        print("YARDSTICK " + json.dumps(fields)[:2000], flush=True)

    def init_state(self):
        """``(params, opt_state)`` from the seed, in the layout
        ``trainer.abstract_state()`` names: the one a restore gives.

        Not ``trainer.init``: under ``fsdp`` over several chips it
        leaves the Adam moments replicated (its ``out_shardings`` for
        them is None and zeros propagate nothing), 15 GB a chip at 16
        layers, and the first program after it does not load (PERF.md,
        PR 25). On one chip the two are the same program.
        """
        import jax

        seed = self.args.seed  # any whole number: fold the high bits in
        key = jax.random.fold_in(
            jax.random.key(seed & 0x7FFFFFFF), seed >> 31
        )
        trainer = self.trainer

        def fresh(key):
            params = trainer._init_fn(key)
            return params, trainer.optimizer.init(params)

        shardings = jax.tree.map(
            lambda a: a.sharding, trainer.abstract_state()
        )
        with self.mesh:
            return jax.jit(fresh, out_shardings=shardings)(key)

    def check_reference(self, params):
        """The program's loss against the plain reference's, on one
        seeded sequence a chip, with these parameters."""
        import jax

        from yardstick import reference

        n = self.mesh.size
        start = 2 ** 40  # a sample index no dataset reaches
        tokens, targets = self.batch_fn(start, start + n)
        batch = jax.device_put(
            (tokens, targets), self.trainer.batch_sharding
        )
        with self.mesh:
            program = float(
                jax.jit(self.trainer._loss_fn)(params, batch)
            )
            ref = float(reference.loss(self.config, params, *batch))
        diff = abs(program - ref)
        self.report(
            "reference", program_loss=program, reference_loss=ref,
            difference=diff, tolerance=REFERENCE_TOLERANCE,
            sequences=n, seq=self.traffic["seq"],
            ok=bool(math.isfinite(diff)
                    and diff <= REFERENCE_TOLERANCE),
        )

    def compile_step(self, params, opt_state, mb):
        """Compile the train step ahead of its first call and say
        what only this process can know about it (copied from
        examples/llama_train.py)."""
        from dlrover_tpu.ops import tuning

        t0 = time.time()
        with self.compilations() as events:
            compiled = self.trainer.train_step.lower(
                params, opt_state, mb
            ).compile()
        text = compiled.as_text()
        self.report(
            "step_program",
            compile_secs=time.time() - t0,
            cache_requests=events["requests"],
            cache_hits=events["hits"],
            kernel_in_step="tpu_custom_call" in text,
            collectives={
                op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
                for op in ("all-gather", "reduce-scatter",
                           "all-reduce", "all-to-all",
                           "collective-permute")
            },
            tuning=tuning.last_selection(),
        )

    @contextlib.contextmanager
    def compilations(self):
        """Count, from jax's own events, the programs jax asks its
        persistent cache for inside the block, and those it finds
        there (a request that is not a hit compiles). The yardstick's
        own listener: what it counts decides ``correct``."""
        from jax import monitoring

        seen = {"requests": 0, "hits": 0}

        def listener(event, **_):
            if event == (
                "/jax/compilation_cache/compile_requests_use_cache"
            ):
                seen["requests"] += 1
            elif event == "/jax/compilation_cache/cache_hits":
                seen["hits"] += 1

        monitoring.register_event_listener(listener)
        try:
            yield seen
        finally:
            monitoring.unregister_event_listener(listener)

    def drive(self, state, batches, first_step, until=None, steps=None,
              annotate=False):
        """Step until the clock passes ``until`` (at least once), or
        for ``steps`` steps, keeping one step in flight: after step i
        is dispatched, block on the loss of step i-1 and stamp that
        completion. Returns ``(state, rows)``, a row a dispatched
        step: its number, when the loop turned to it, how long
        ``next(batches)`` took, when its loss was back and what it
        was.
        """
        import jax

        def note(name, **kw):
            if not annotate:
                return contextlib.nullcontext()
            if kw:
                return jax.profiler.StepTraceAnnotation(name, **kw)
            return jax.profiler.TraceAnnotation(name)

        def retire(pending):
            row, loss = pending
            with note("yardstick.wait"):
                try:
                    row["loss"] = float(loss)  # blocks on the step
                except Exception as e:  # it raised on the device
                    row["loss"], row["error"] = None, repr(e)
            row["done"] = time.time()

        def more():
            if steps is not None:
                return len(rows) < steps
            return not rows or time.time() < until

        params, opt_state = state
        rows, pending, step = [], None, first_step
        while more():
            with note("yardstick.step", step_num=step):
                row = {"step": step, "start": time.time()}
                with note("yardstick.next_batch"):
                    batch = next(batches)
                row["data_wait"] = time.time() - row["start"]
                with note("yardstick.dispatch"):
                    mb = self.trainer.microbatch(batch)
                    params, opt_state, loss = self.trainer.train_step(
                        params, opt_state, mb
                    )
                # hang detection, fault injection, the master's step
                # count: what the example's loop pays every step
                self.reporter.report_step(step)
                if pending is not None:
                    retire(pending)
            pending = (row, loss)
            rows.append(row)
            step += 1
        retire(pending)
        return (params, opt_state), rows

    def trace_steps(self, state, batches, first_step):
        """A few steady steps under the profiler, reduced to the
        event ``trace`` (left out where the trace shows no device)."""
        import jax

        from yardstick import reduce

        jax.block_until_ready(state)
        trace_dir = os.path.join(self.args.scratch, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            state, rows = self.drive(
                state, batches, first_step,
                steps=self.traffic["traced_steps"], annotate=True,
            )
        finally:
            jax.profiler.stop_trace()
        xplane = reduce.find_xplane(trace_dir)
        describe_to = os.environ.get("YARDSTICK_DESCRIBE_TRACE")
        if describe_to:  # to look at a trace by hand
            with open(describe_to + ".txt", "w") as f:
                f.write(reduce.describe(xplane))
            with open(describe_to + ".planes.json", "w") as f:
                json.dump(reduce.read_planes(xplane), f)
        reduced = reduce.reduce_file(xplane, steps=len(rows))
        if reduced is not None:
            self.report("trace", **reduced)
        return state, rows

    def checksum(self, tree):
        """Float32 sums of every leaf, computed on the device: equal
        before a save and after its restore, or the state that came
        back is not the state that was acknowledged."""
        import jax
        import jax.numpy as jnp

        sums = jax.jit(lambda t: [
            jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(t)
        ])(tree)
        return [float(x) for x in sums]

    def events(self, name):
        """This run's earlier report lines of one event (a restarted
        worker reads what the dead one wrote)."""
        with open(self.report_path) as f:
            lines = [json.loads(line) for line in f if line.strip()]
        return [r for r in lines if r["event"] == name]

    def peak_bytes(self):
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in self.mesh.devices.flat
        ]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--report", required=True)
    ap.add_argument("--scratch", required=True,
                    help="this run's own directory, removed after it")
    ap.add_argument("--rehearse", default=None)
    args = ap.parse_args()
    cell, config, traffic = cells.load_cell(
        args.workload, rehearse=args.rehearse
    )
    kind = cells.kind_module(traffic)

    import jax
    import optax

    from dlrover_tpu.agent.master_client import build_master_client
    from dlrover_tpu.data.elastic_shm import ElasticShmDataLoader
    from dlrover_tpu.models import make_trainer_for
    from dlrover_tpu.parallel.mesh import create_mesh
    from dlrover_tpu.trainer.checkpoint import FlashCheckpointer
    from dlrover_tpu.trainer.distributed import init_from_env
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    env = init_from_env()
    client = build_master_client()
    devices = jax.devices()
    ctx = Context(
        args=args, cell=cell, config=config, traffic=traffic,
        report_path=args.report, restart_count=env.restart_count,
        t_process_start=T_PROCESS_START,
    )
    ctx.report(
        "start", t_process_start=T_PROCESS_START, pid=os.getpid(),
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        device_count=len(devices), chips=cell["chips"],
        rehearse=args.rehearse,
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
    )
    if not args.rehearse:
        if (devices[0].platform != "tpu"
                or len(devices) != cell["chips"]):
            ctx.report(
                "refused", reason=f"{len(devices)} "
                f"{devices[0].platform} device(s) where the cell "
                f"asks for {cell['chips']} TPU chip(s)",
            )
            return RC_REFUSED
        try:
            cells.peak_of(devices[0].device_kind)
        except cells.UnknownName as e:
            ctx.report("refused", reason=str(e))
            return RC_REFUSED

    mesh_axes = list(traffic["mesh"].items())
    n_mesh = math.prod(n for _, n in mesh_axes)
    mesh = create_mesh(mesh_axes, devices=devices[:n_mesh])
    trainer = make_trainer_for(
        program_config(config, traffic), mesh,
        strategy=traffic["strategy"],
        optimizer=optax.adamw(traffic["optimizer"]["learning_rate"]),
    )
    ckpt = FlashCheckpointer(
        # the RAM tier is the default one (ram_dir=None: /dev/shm,
        # named after the persist directory, which is this run's own)
        persist_dir=os.path.join(
            args.scratch, os.path.basename(args.scratch.rstrip("/"))
        ),
        ram_dir=None,
        persist_interval=0, use_orbax=False,
    )
    reporter = ElasticTrainer(
        lambda p, b: 0.0, optax.identity(), max_nodes=1, cur_nodes=1,
        master_client=client, report_interval=5,
    )
    batch_fn = SeededTokens(
        args.seed, traffic["seq"], config["vocab_size"]
    )
    loader = ElasticShmDataLoader(
        batch_fn, dataset_name="yardstick",
        batch_size=traffic["global_batch"],
        dataset_size=4096 * traffic["global_batch"],
        num_epochs=10 ** 6,  # stream until the window ends
        num_workers=traffic["coworkers"], slot_bytes=8 << 20,
        sharding=trainer.batch_sharding,
    )
    ctx.__dict__.update(
        mesh=mesh, trainer=trainer, ckpt=ckpt, reporter=reporter,
        loader=loader, batch_fn=batch_fn,
    )
    ctx.report(
        "built", mesh=dict(mesh.shape), strategy=traffic["strategy"],
        ram_dir=ckpt.ram_dir,
        tokens_per_step=traffic["global_batch"] * traffic["seq"],
    )
    try:
        kind.work(ctx)
    finally:
        loader.shutdown()
    ckpt.close()
    ctx.report("final", peak_bytes_in_use=ctx.peak_bytes())
    return 0


if __name__ == "__main__":
    sys.exit(main())
