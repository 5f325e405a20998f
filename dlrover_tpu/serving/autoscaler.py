"""Serving autoscaler: scale the replica pool on queue depth + p99.

One component, two wirings:

* in the distributed master (master/dist_master.py) it reads the
  in-process :class:`~dlrover_tpu.serving.router.RequestRouter` and
  scales through the SAME scale-plan machinery training uses
  (``JobAutoScaler.manual_scale`` -> ScalePlan -> platform scaler), so
  a serving job's replicas are ordinary elastic nodes;
* in drills / examples it reads ``serve_stats`` over RPC and the
  ``scale_fn`` spawns worker processes directly.

Decisions are deliberately simple and hysteretic: scale UP one replica
when the queue is deeper than ``queue_high`` or p99 exceeds
``p99_high_ms`` (and the cooldown has elapsed), scale DOWN one when the
queue has been empty and latency low. The point is the wiring — queue
depth and measured latency driving the training stack's scale plans —
not a clever controller.
"""

import threading
from typing import Callable, Dict, Optional

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import counter, record

#: queue depth that triggers +1 replica
QUEUE_HIGH = 16
#: p99 latency (ms) that triggers +1 replica (unless model-time-bound)
P99_HIGH_MS = 2000.0
#: seconds between scale decisions
COOLDOWN_S = 5.0
#: goodput-ledger serving-phase share below which the pool counts as
#: idle for scale-down (the p99 window is sticky: a burst an hour ago
#: must not pin an idle pool at max size)
IDLE_SHARE = 0.1


class ServingAutoScaler:
    """Scales a serving pool on router stats.

    ``stats_fn``   -> the router's ``stats()`` dict (in-process or RPC)
    ``scale_fn``   -> callable(target_replicas) executing the change
                      (JobAutoScaler.manual_scale in the master wiring)
    ``replicas_fn``-> current replica count (defaults to the router's
                      ``workers`` stat)
    """

    def __init__(
        self,
        stats_fn: Callable[[], Optional[Dict]],
        scale_fn: Callable[[int], object],
        replicas_fn: Optional[Callable[[], int]] = None,
        min_replicas: int = 1,
        max_replicas: int = 4,
        queue_high: int = QUEUE_HIGH,
        p99_high_ms: float = P99_HIGH_MS,
        interval: float = 1.0,
        cooldown: float = COOLDOWN_S,
        goodput_fn: Optional[Callable[[], Optional[float]]] = None,
    ):
        self._stats_fn = stats_fn
        self._scale_fn = scale_fn
        self._replicas_fn = replicas_fn
        #: ISSUE 20: the goodput ledger's serving-phase share (0..1) —
        #: how much of the pool's wall time was spent answering. None
        #: (no ledger wired) keeps the pre-SLO behavior exactly.
        self._goodput_fn = goodput_fn
        self._min = max(0, min_replicas)
        self._max = max(self._min, max_replicas)
        self._queue_high = int(queue_high)
        self._p99_high_ms = float(p99_high_ms)
        self._interval = max(0.1, interval)
        self._cooldown = float(cooldown)
        self._last_scale: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ------------------------------------------------------------ lifecycle

    def start(self):
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="serve-autoscaler", daemon=True,
        )
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def _loop(self):
        import time

        while not self._stop.wait(self._interval):
            try:
                now = time.monotonic()
                if (self._last_scale is not None
                        and now - self._last_scale < self._cooldown):
                    continue
                if self.evaluate() is not None:
                    self._last_scale = time.monotonic()
            except Exception as e:  # pragma: no cover - defensive
                logger.warning("serving autoscale tick failed: %s", e)

    # -------------------------------------------------------------- descision

    def evaluate(self) -> Optional[int]:
        """One decision tick: returns the new target replica count when
        a scale was issued, None when the pool is left alone. Exposed
        for unit tests (no thread, no clock)."""
        stats = self._stats_fn()
        if not stats or not stats.get("submitted"):
            return None  # inert until the request plane sees traffic
        current = (
            self._replicas_fn() if self._replicas_fn is not None
            else int(stats.get("workers", 0))
        )
        queue_depth = int(stats.get("queue_depth", 0))
        p99_ms = float(stats.get("p99_ms", 0.0))
        # attributed latency (ISSUE 17 / ROADMAP 3b): the router splits
        # the same window into queue wait (submit -> winning lease) and
        # model time (lease -> complete). Stats from an older router
        # lack the keys and read 0.0, keeping the legacy behavior.
        queue_wait_ms = float(stats.get("queue_wait_p99_ms", 0.0))
        model_ms = float(stats.get("model_time_p99_ms", 0.0))
        # SLO feed (ISSUE 20): the goodput ledger's serving-phase share
        serving_share = None
        if self._goodput_fn is not None:
            try:
                serving_share = self._goodput_fn()
            except Exception:  # pragma: no cover - defensive
                serving_share = None
        target = current
        reason = ""
        if stats.get("sealed") and not queue_depth:
            return None  # stream ending: let workers drain out
        # the goodput ledger overrides a stale latency window: nothing
        # queued, nothing in flight, and the pool's wall time shows no
        # serving — the p99 breach is history, not load
        pool_idle = (
            queue_depth == 0 and not stats.get("in_flight")
            and serving_share is not None
            and serving_share < IDLE_SHARE
        )
        if pool_idle and current > self._min:
            target, reason = current - 1, "idle"
        elif queue_depth > self._queue_high and current < self._max:
            target, reason = current + 1, "queue_depth"
        elif p99_ms > self._p99_high_ms and current < self._max:
            if model_ms > self._p99_high_ms and model_ms > queue_wait_ms:
                # the replica ITSELF blew the budget: one more replica
                # cannot shorten a model-time-dominated p99 — hold, and
                # journal the attribution so the operator sees why the
                # pool did not grow
                record(
                    "serve.autoscale_held", cause="model_time",
                    p99_ms=round(p99_ms, 3),
                    model_time_p99_ms=round(model_ms, 3),
                    queue_wait_p99_ms=round(queue_wait_ms, 3),
                    replicas=current,
                    serving_share=-1.0 if serving_share is None
                    else round(serving_share, 4),
                )
                return None
            target, reason = current + 1, "p99_latency"
        elif (queue_depth == 0 and current > self._min
              and not stats.get("in_flight")
              and (p99_ms < self._p99_high_ms / 4
                   or (serving_share is not None
                       and serving_share < IDLE_SHARE))):
            # the latency window is sticky — a burst long past must not
            # pin an idle pool at max size, so a near-zero serving
            # share from the goodput ledger also opens the down path
            target, reason = current - 1, "idle"
        if target == current:
            return None
        record(
            "serve.autoscale", reason=reason, replicas=current,
            target=target, queue_depth=queue_depth,
            p99_ms=round(p99_ms, 3),
            queue_wait_p99_ms=round(queue_wait_ms, 3),
            model_time_p99_ms=round(model_ms, 3),
            serving_share=-1.0 if serving_share is None
            else round(serving_share, 4),
        )
        counter(
            "dlrover_serve_autoscale_total",
            "Serving pool scale decisions", ["reason"],
        ).labels(reason=reason).inc()
        try:
            self._scale_fn(target)
        except Exception as e:
            logger.warning("serving scale to %d failed: %s", target, e)
            return None
        return target
