"""ElasticTrainer: fixed global batch size under a changing host count.

Parity reference: dlrover/trainer/torch/elastic.py:170 (ElasticTrainer,
GradientState:42, _ElasticOptimizer:78).

TPU-native redesign: the reference wraps the optimizer/scheduler so DDP only
steps on gradient-sync boundaries. Under JAX there is no optimizer object to
hack — gradient accumulation is a ``lax.scan`` *inside* the jitted train
step, so the whole accumulate-then-update loop compiles to one XLA program
per world size (no per-microbatch dispatch overhead, and XLA fuses the
accumulation adds into the backward).

The reference's ``_ElasticLRScheduler`` (elastic.py:139 — step the LR
schedule only on sync boundaries so world changes don't skew it) is
n/a-by-design here: one ``train_step`` call IS one optimizer update at
every world size, and optax schedules key off the update count carried
in ``opt_state`` — which rides the flash checkpoint across world
changes, so the schedule position is exact by construction.
"""

import time
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import fleet, tracing


def compute_accum_steps(max_nodes: int, cur_nodes: int) -> int:
    """gradient_accumulation_steps = ceil(max/cur) keeps the global batch
    fixed when nodes drop out (parity: elastic.py:208)."""
    if cur_nodes <= 0:
        return 1
    return max(1, -(-max_nodes // cur_nodes))


def make_elastic_train_step(
    loss_fn: Callable,
    optimizer,
    accum_steps: int,
    donate_state: bool = True,
):
    """Build a jitted train step running ``accum_steps`` microbatches.

    ``loss_fn(params, batch) -> scalar loss``. ``optimizer`` is an optax
    GradientTransformation. The returned step takes
    ``(params, opt_state, batches)`` where ``batches`` has a leading
    microbatch axis of length ``accum_steps``; it returns
    ``(params, opt_state, mean_loss)``.

    Re-jit per accum_steps (i.e. per world size); callers should cache
    compiled versions keyed by world size (see ElasticTrainer).
    """

    grad_fn = jax.value_and_grad(loss_fn)

    def step(params, opt_state, batches):
        def micro(carry, batch):
            loss_sum, grads_sum = carry
            loss, grads = grad_fn(params, batch)
            grads_sum = jax.tree.map(jnp.add, grads_sum, grads)
            return (loss_sum + loss, grads_sum), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (loss_sum, grads_sum), _ = jax.lax.scan(
            micro, (jnp.zeros(()), zeros), batches
        )
        grads = jax.tree.map(lambda g: g / accum_steps, grads_sum)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(jnp.add, params, updates)
        return params, opt_state, loss_sum / accum_steps

    donate = (0, 1) if donate_state else ()
    return jax.jit(step, donate_argnums=donate)


class ElasticTrainer:
    """Keeps the global batch fixed across elastic world changes.

    Usage::

        trainer = ElasticTrainer(loss_fn, optimizer, max_nodes=4,
                                 cur_nodes=env.node_num)
        step_fn = trainer.train_step  # jitted, cached per accum_steps
        params, opt_state, loss = step_fn(params, opt_state, microbatches)
        trainer.report_step()  # master throughput reporting
    """

    def __init__(
        self,
        loss_fn: Callable,
        optimizer,
        max_nodes: int,
        cur_nodes: int,
        master_client=None,
        report_interval: int = 10,
        hang_detection: Optional[bool] = None,
    ):
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._max_nodes = max_nodes
        self._master_client = master_client
        self._report_interval = report_interval
        self._step_cache = {}
        self._global_step = 0
        self._checkpointer = None
        self._ckpt_interval = 0
        self._hang_detector = None
        self._fault_injector = None
        self._created_ts = time.monotonic()
        self._first_step_seen = False
        self._last_step_mono: Optional[float] = None
        # per-process goodput ledger (telemetry/goodput.py): phase
        # transitions ride on events that already fire; the trainer
        # only marks steps (-> training) and checkpoint stalls
        from dlrover_tpu.telemetry import goodput

        self._goodput = goodput.install()
        self._init_fault_tolerance(hang_detection)
        self.set_world(cur_nodes)

    def _init_fault_tolerance(self, hang_detection: Optional[bool]):
        """Step-progress hang detection (fault_tolerance/hanging_detector
        .py) + the injection drill hook. Both are no-ops without a master
        client; detection defaults on, DLROVER_HANG_DETECTION=0 disables,
        DLROVER_HANG_MIN_TIMEOUT / _MULTIPLIER tune the threshold."""
        import os

        from dlrover_tpu.common.constants import NodeEnv
        from dlrover_tpu.fault_tolerance import (
            FaultInjector,
            HangingDetector,
        )

        self._fault_injector = FaultInjector.from_env(self._master_client)
        # silent-failure sentinel (fault_tolerance/sentinel.py): NaN /
        # SDC detection on the loss scalar the loop already reports;
        # DLROVER_TPU_SENTINEL=0 disables
        from dlrover_tpu.fault_tolerance.sentinel import TrainingSentinel

        self._sentinel = TrainingSentinel.from_env(self._master_client)
        # reshard-in-place (reshard/transition.py): adopts master
        # transition orders exactly-once; the step loop executes them
        # at the next boundary via pending_reshard().
        # DLROVER_TPU_RESHARD=0 disables
        from dlrover_tpu.reshard import MeshTransition

        self._mesh_transition = MeshTransition.from_env(
            self._master_client
        )
        # zero-code timeline capture (DLROVER_TRACE_DIR): see
        # trainer/profiler.py TraceCapture
        from dlrover_tpu.trainer.profiler import TraceCapture

        self._trace_capture = TraceCapture.from_env()
        # graceful drain on SIGTERM (fault_tolerance/drain.py): armed
        # BEFORE the flight recorder so the recorder's hook chains the
        # drain handler (dump first, then drain) instead of
        # re-delivering the signal. Lazy accessors: the checkpointer
        # attaches and steps advance after arming.
        from dlrover_tpu.fault_tolerance.drain import DrainCoordinator

        self._last_state = None
        self._drain = DrainCoordinator(
            master_client_fn=lambda: self._master_client,
            checkpointer_fn=lambda: self._checkpointer,
            state_provider=lambda: (
                (self._global_step, self._last_state)
                if self._last_state is not None else None
            ),
            restart_count=int(
                os.environ.get(NodeEnv.RESTART_COUNT, "0") or 0
            ),
        )
        try:
            self._drain.arm()
        except Exception as e:  # drain is best-effort, never fatal
            logger.warning("drain arming failed: %s", e)
        if self._master_client is None:
            return
        if hang_detection is None:
            hang_detection = (
                os.environ.get("DLROVER_HANG_DETECTION", "1") != "0"
            )
        if not hang_detection:
            return

        def report(elapsed: float):
            from dlrover_tpu.common.constants import (
                TrainingExceptionLevel,
            )

            try:
                self._master_client.report_failure(
                    f"no step progress for {elapsed:.1f}s "
                    f"(last step {self._global_step})",
                    TrainingExceptionLevel.HANG,
                )
            except Exception as e:
                logger.warning("hang report failed: %s", e)

        self._hang_detector = HangingDetector(
            report_fn=report,
            min_timeout=float(
                os.environ.get("DLROVER_HANG_MIN_TIMEOUT", "300")
            ),
            multiplier=float(
                os.environ.get("DLROVER_HANG_MULTIPLIER", "10")
            ),
        ).start()
        # observability wiring around the detector (ISSUE 4): /healthz
        # on any telemetry endpoint in THIS process reports the stall
        # (503 + stalled_for) instead of a bare liveness 200, and a
        # SIGTERM mid-run leaves a flight record (all-thread stacks +
        # last spans) before the process dies
        try:
            from dlrover_tpu.telemetry import flight_recorder, lockwatch
            from dlrover_tpu.telemetry.http import attach_hang_detector

            attach_hang_detector(self._hang_detector)
            flight_recorder.install_signal_hook()
            # runtime lock-order watchdog (no-op unless
            # DLROVER_TPU_LOCKWATCH=1); late is still useful — the
            # trainer's own locks are created after this point
            lockwatch.install()
        except Exception as e:  # telemetry never stops training
            logger.warning("flight-recorder wiring failed: %s", e)

    def set_world(self, cur_nodes: int):
        self._cur_nodes = cur_nodes
        self._accum_steps = compute_accum_steps(self._max_nodes, cur_nodes)
        logger.info(
            "Elastic world: %d/%d nodes -> accum_steps=%d",
            cur_nodes, self._max_nodes, self._accum_steps,
        )

    @property
    def accum_steps(self) -> int:
        return self._accum_steps

    @property
    def train_step(self):
        key = self._accum_steps
        step_fn = self._step_cache.get(key)
        if step_fn is None:
            jitted = make_elastic_train_step(
                self._loss_fn, self._optimizer, key
            )

            def step_fn(params, opt_state, batches):
                # donation-safety contract (docs/CHECKPOINT.md): the
                # jitted step donates (params, opt_state), and an
                # async flash save may still hold un-materialized
                # device handles on them — wait out the staging before
                # the dispatch that invalidates the buffers. No save
                # in flight (or sync staging) makes this a no-op.
                ckpt = self._checkpointer
                if ckpt is not None:
                    wait = getattr(ckpt, "wait_staged", None)
                    if wait is not None:
                        wait()  # where it waits: ``ckpt.wait_staged``
                with tracing.span("train.dispatch"):
                    return jitted(params, opt_state, batches)

            # profiler.profile_step reuses the shared jit cache via
            # .lower — keep it reachable through the wrapper
            step_fn.lower = jitted.lower
            self._step_cache[key] = step_fn
        return step_fn

    def microbatch(self, batch):
        """Split a per-host batch into the accum microbatch layout
        [accum_steps, batch/accum, ...]."""
        return jax.tree.map(
            lambda x: x.reshape(
                (self._accum_steps, x.shape[0] // self._accum_steps)
                + x.shape[1:]
            ),
            batch,
        )

    def report_model_profile(self, params, batch,
                             batch_size: int = 0, seq_len: int = 0):
        """Profile the current train step's compiled program and send
        it to the master's stats pipeline (trainer/profiler.py). Call
        once after the first step; failures never interrupt training."""
        if self._master_client is None:
            return None
        from dlrover_tpu.trainer import profiler

        try:
            # abstract lowering: shapes only, nothing materialized
            abs_params = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
            )
            abs_opt = jax.eval_shape(self._optimizer.init, abs_params)
            abs_batch = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    getattr(x, "shape", ()), getattr(x, "dtype", None)
                ), batch,
            )
            prof = profiler.profile_step(
                self.train_step, abs_params, abs_opt, abs_batch,
                params=params,
            )
        except Exception as e:
            logger.warning("model profiling failed: %s", e)
            return None
        profiler.report_profile(
            self._master_client, prof, batch_size=batch_size,
            seq_len=seq_len,
        )
        return prof

    def report_step(self, step: Optional[int] = None,
                    loss=None, grad_norm=None):
        """Advance the trainer's step bookkeeping. When the loop passes
        its ``loss`` scalar (and optionally the optimizer's global
        ``grad_norm``), the silent-failure sentinel inspects them for
        NaN/SDC anomalies; the (possibly injection-corrupted) effective
        loss is returned so drills observe the same value the sentinel
        saw."""
        # what elastic supervision costs the loop a step: hang
        # detection, fault injection, the polls of the master's
        # rollback and transition orders, its step count
        with tracing.span("train.report_step"):
            self._global_step = step if step is not None else (
                self._global_step + 1
            )
            # spans and flight records carry the step they happened at
            tracing.set_step(self._global_step)
            # step duration feeds the fleet roll-up plane (ISSUE 17): the
            # master answers fleet p99 step time from these sketches with
            # zero agent scrapes
            now_mono = time.monotonic()
            if self._last_step_mono is not None:
                fleet.observe("step", now_mono - self._last_step_mono)
                fleet.incr("steps")
            self._last_step_mono = now_mono
            if not self._first_step_seen:
                # the first completed step carries the compile: classify
                # warm (persistent-cache hit) vs cold for the journal
                self._first_step_seen = True
                try:
                    from dlrover_tpu.trainer.compile_cache import (
                        report_first_compile,
                    )

                    report_first_compile(
                        time.monotonic() - self._created_ts
                    )
                except Exception as e:  # telemetry never stops training
                    logger.warning("compile-cache telemetry failed: %s", e)
            # a completed step is the proof of useful work: it opens the
            # training phase and closes any hang/restart window
            self._goodput.on_step()
            if self._hang_detector is not None:
                self._hang_detector.record_step(self._global_step)
            if self._trace_capture is not None:
                self._trace_capture.step(self._global_step)
            if self._fault_injector is not None:
                self._fault_injector.maybe_inject(self._global_step)
            if loss is not None:
                loss = float(loss)
                if self._fault_injector is not None:
                    # corruption drills (nan@N / sdc@N) poison the scalar
                    # here so the sentinel sees exactly what a corrupting
                    # host would produce
                    loss = self._fault_injector.corrupt_loss(
                        self._global_step, loss
                    )
                if self._sentinel is not None:
                    self._sentinel.check(
                        self._global_step, loss, grad_norm
                    )
            elif self._sentinel is not None:
                # no scalar this step: still poll for rollback orders
                # issued on another rank's anomaly
                self._sentinel.poll_rollback_order()
            if self._mesh_transition is not None:
                # mesh-transition orders are adopted here (exactly-once by
                # order id) and executed by the step loop at the boundary
                # it chooses — see pending_reshard()
                self._mesh_transition.poll_order()
            if (
                self._master_client is not None
                and self._global_step % self._report_interval == 0
            ):
                try:
                    self._master_client.report_global_step(
                        self._global_step, time.time()
                    )
                except Exception as e:
                    logger.warning("report_global_step failed: %s", e)
            return loss

    # ---------------------------------------------------------- checkpoint

    def attach_checkpointer(self, checkpointer,
                            save_interval: int = 10) -> None:
        """Register a :class:`~dlrover_tpu.trainer.checkpoint.
        FlashCheckpointer` on the step cadence. The save path is
        zero-stall (async D2H staging + background serialization), so
        a small ``save_interval`` is cheap — failover loses at most
        ``save_interval`` steps, not a persist interval.

        Donation safety: :attr:`train_step` donates (params,
        opt_state); once a checkpointer is attached it calls
        ``wait_staged()`` before each dispatch, so an async-staged
        save owns its host copies before donation can invalidate the
        source buffers. A step loop driving its OWN donating jit
        function must call ``checkpointer.wait_staged()`` itself (or
        build the checkpointer with ``stage="sync"``) — see
        docs/CHECKPOINT.md."""
        self._checkpointer = checkpointer
        self._ckpt_interval = max(0, int(save_interval))
        if self._sentinel is not None and hasattr(
            checkpointer, "set_clean_fn"
        ):
            # archives saved inside an anomaly window get tagged
            # last_good=False and are skipped by the restore walk-down
            checkpointer.set_clean_fn(self._sentinel.is_clean)

    def maybe_checkpoint(self, state, step: Optional[int] = None,
                         force: bool = False) -> Optional[float]:
        """Save ``state`` when the attached cadence is due (call after
        each step with the post-update state). Returns the train-thread
        stall in ms when a save was issued, else None. Checkpoint
        failures are reported, never raised into the step loop."""
        # the drain coordinator's emergency save reads the freshest
        # state seen here (a pytree reference, not a copy); callers
        # with donating step functions should prefer
        # drain.set_state_provider with an un-donated source
        self._last_state = state
        if self._checkpointer is None:
            return None
        step = self._global_step if step is None else step
        due = force or (
            self._ckpt_interval > 0 and step > 0
            and step % self._ckpt_interval == 0
        )
        if not due:
            return None
        try:
            stall_ms = self._checkpointer.save(
                step, state, force_persist=force
            )
            if self._sentinel is not None:
                self._sentinel.note_checkpoint(step)
            if stall_ms:
                # the measured train-thread stall re-labels the tail
                # of the current training interval as ckpt_stall
                from dlrover_tpu.telemetry.goodput import Phase

                self._goodput.credit(Phase.CKPT_STALL, stall_ms / 1000.0)
            return stall_ms
        except Exception as e:  # checkpointing never stops training
            logger.warning("flash save at step %d failed: %s", step, e)
            return None

    @property
    def global_step(self) -> int:
        return self._global_step

    # ------------------------------------------------------------ reshard

    def pending_reshard(self):
        """The adopted-but-unexecuted :class:`~dlrover_tpu.reshard.
        order.TransitionOrder`, or None. The step loop checks this at
        each step boundary; on a hit it re-forms the collective world,
        migrates state (reshard/migrate.py), calls :meth:`set_world`
        with the new node count (re-jit with ``_step_cache`` reuse),
        and acknowledges through :attr:`mesh_transition`."""
        if self._mesh_transition is None:
            return None
        return self._mesh_transition.pending()

    @property
    def mesh_transition(self):
        """The armed :class:`~dlrover_tpu.reshard.transition.
        MeshTransition` (None when DLROVER_TPU_RESHARD=0)."""
        return self._mesh_transition

    @property
    def sentinel(self):
        """The armed :class:`~dlrover_tpu.fault_tolerance.sentinel.
        TrainingSentinel` (None when DLROVER_TPU_SENTINEL=0)."""
        return self._sentinel

    @property
    def drain(self):
        """The armed :class:`~dlrover_tpu.fault_tolerance.drain.
        DrainCoordinator` (override its state provider when the step
        loop donates buffers)."""
        return self._drain
