"""Coalesced delta status reporting — the agent side of ISSUE 12.

At fleet scale the master's binding constraint is control-plane fan-in:
N agents x (heartbeat + global-step + goodput + resource) unary RPCs on
independent cadences is 3-4N calls per interval, each carrying its full
payload every time. This module folds them into ONE
``report_node_status`` rpc per agent per interval with delta semantics:

* the heartbeat timestamp is always present (it IS the liveness signal);
* step / goodput / resource sections ride along only when they changed
  since the last *acked* report (``has_*`` gates on the wire message);
* the first report of an incarnation — and any report after the master
  replies ``resync=True`` (it restarted and lost the delta baseline) —
  is ``full=True`` and resends everything;
* a ``retry_after_s`` load-shed ack is honored with jittered backoff
  and the SAME payload is retried, so overload degrades latency, never
  delivery (zero dropped heartbeats);
* a master that predates the rpc rejects it at the app layer; the
  reporter then degrades to the legacy per-rpc heartbeat for the rest
  of the process (``report.rpc_fallback``), so mixed fleets keep
  working.

The report interval is jittered ±20% (``JITTER``)
so a master restart doesn't get the whole fleet's re-hellos back in
phase — 10k synchronized reports is a self-inflicted thundering herd.
"""

import random
import socket
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from dlrover_tpu.common import comm
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import fleet
from dlrover_tpu.telemetry.journal import current_job_id, record

#: fractional interval jitter (0.2 = ±20%)
JITTER = 0.2
#: resend goodput/resource at least every N intervals even if
#: "unchanged" — bounds how stale a delta'd section can get when the
#: change detector's thresholds hide slow drift
DEFAULT_MAX_SKIP = 8
#: a phase total must advance this much to count as changed
GOODPUT_MIN_DELTA_S = 1.0
CPU_MIN_DELTA_PCT = 5.0
MEM_MIN_DELTA_MB = 64


class DeltaTracker:
    """Composes ``NodeStatusReport`` payloads against the last-acked
    baseline. Pure bookkeeping (no I/O) so the swarm bench can drive
    thousands of instances without threads."""

    def __init__(self, incarnation: int = 0,
                 goodput_min_delta_s: float = GOODPUT_MIN_DELTA_S,
                 max_skip: int = DEFAULT_MAX_SKIP,
                 job_id: str = ""):
        self._incarnation = incarnation
        #: job namespace stamped into every composed report (ISSUE 19);
        #: the sparse wire omits the default, so single-job fleets are
        #: byte-identical to the pre-job format
        self.job_id = job_id or "default"
        self._seq = 0
        self._full_next = True
        self._goodput_min_delta = goodput_min_delta_s
        self._max_skip = max(1, max_skip)
        # last-ACKED baselines — only advanced by commit(), so a shed
        # or failed report never silently drops a delta
        self._acked_step = -1
        self._acked_phases: Dict[str, float] = {}
        self._acked_phase = ""
        self._acked_cpu: Optional[float] = None
        self._acked_mem: Optional[int] = None
        self._acked_served: Optional[int] = None
        self._skipped_goodput = 0
        self._skipped_resource = 0
        self._skipped_serve = 0

    def request_full(self):
        self._full_next = True

    def _goodput_changed(self, fields: Dict) -> bool:
        if fields.get("goodput_phase", "") != self._acked_phase:
            return True
        phases = fields.get("goodput_phases") or {}
        for name, total in phases.items():
            if abs(total - self._acked_phases.get(name, 0.0)) \
                    >= self._goodput_min_delta:
                return True
        return False

    def compose(self, timestamp: float,
                step: Optional[int] = None,
                step_ts: float = 0.0,
                pid: int = 0,
                goodput_fields: Optional[Dict] = None,
                resource: Optional[Tuple[float, int]] = None,
                host: str = "",
                final: bool = False,
                serve_fields: Optional[Dict] = None
                ) -> comm.NodeStatusReport:
        """Build the next report; bumps ``seq``. Retries of a shed
        report reuse the returned object — only an acked seq advances
        the baseline (see :meth:`commit`)."""
        self._seq += 1
        full = self._full_next
        report = comm.NodeStatusReport(
            timestamp=timestamp,
            incarnation=self._incarnation,
            seq=self._seq,
            full=full,
            final=final,
            job_id=self.job_id,
        )
        if full or final:
            # host only travels when someone reads it: the master
            # consumes it solely in the goodput ledger (and below when
            # a goodput section is attached) — steady-state deltas
            # stay host-free
            report.host = host or socket.gethostname()
        if step is not None and (full or step > self._acked_step):
            report.has_step = True
            report.step = step
            report.step_ts = step_ts or timestamp
            report.pid = pid
        if goodput_fields:
            self._skipped_goodput += 1
            if (full or final
                    or self._skipped_goodput >= self._max_skip
                    or self._goodput_changed(goodput_fields)):
                report.has_goodput = True
                report.pid = pid
                report.host = host or socket.gethostname()
                report.goodput_phases = dict(
                    goodput_fields.get("goodput_phases") or {}
                )
                report.goodput_elapsed_s = goodput_fields.get(
                    "goodput_elapsed_s", 0.0
                )
                report.goodput_start_ts = goodput_fields.get(
                    "goodput_start_ts", 0.0
                )
                report.goodput_phase = goodput_fields.get(
                    "goodput_phase", ""
                )
        if resource is not None:
            cpu, mem = resource
            self._skipped_resource += 1
            changed = (
                self._acked_cpu is None
                or abs(cpu - self._acked_cpu) >= CPU_MIN_DELTA_PCT
                or abs(mem - (self._acked_mem or 0)) >= MEM_MIN_DELTA_MB
            )
            if full or changed or self._skipped_resource >= self._max_skip:
                report.has_resource = True
                report.cpu_percent = cpu
                report.memory_mb = mem
        if serve_fields:
            # serving-replica stats (ISSUE 20): 1k-replica pools would
            # melt the master with per-replica serve_stats polling —
            # the counters ride this delta lane instead. Changed =
            # the served count moved (the replica did work).
            self._skipped_serve += 1
            served = int(serve_fields.get("served", 0))
            if (full or final or served != self._acked_served
                    or self._skipped_serve >= self._max_skip):
                report.has_serve = True
                report.serve_served = served
                report.serve_rejected = int(
                    serve_fields.get("rejected", 0)
                )
                report.serve_model_ms = float(
                    serve_fields.get("model_ms", 0.0)
                )
                report.serve_batch_fill = float(
                    serve_fields.get("batch_fill", 0.0)
                )
        return report

    def commit(self, report: comm.NodeStatusReport):
        """Advance the acked baseline to what ``report`` carried."""
        self._full_next = False
        if report.has_step:
            self._acked_step = report.step
        if report.has_goodput:
            self._acked_phases = dict(report.goodput_phases)
            self._acked_phase = report.goodput_phase
            self._skipped_goodput = 0
        if report.has_resource:
            self._acked_cpu = report.cpu_percent
            self._acked_mem = report.memory_mb
            self._skipped_resource = 0
        if report.has_serve:
            self._acked_served = report.serve_served
            self._skipped_serve = 0


class StatusReporter:
    """The agent's reporting loop: one thread, one rpc per interval.

    ``on_action`` receives any pending NodeAction the master piggybacks
    on the ack — the same contract as the legacy heartbeat response, so
    restart/drain/stop directives arrive with zero extra RPCs."""

    def __init__(self, client, interval: float,
                 incarnation: int = 0,
                 on_action: Optional[Callable[[str], None]] = None,
                 resource_fn: Optional[
                     Callable[[], Optional[Tuple[float, int]]]] = None,
                 step_fn: Optional[Callable[[], Optional[int]]] = None,
                 pid: int = 0,
                 serve_fn: Optional[Callable[[], Optional[Dict]]] = None):
        import os

        self._client = client
        self._interval = max(0.1, float(interval))
        self._on_action = on_action
        self._resource_fn = resource_fn
        self._step_fn = step_fn
        self._serve_fn = serve_fn
        self._pid = pid or os.getpid()
        self._tracker = DeltaTracker(
            incarnation=incarnation, job_id=current_job_id()
        )
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        #: None = undecided, True = batched path confirmed, False =
        #: old master, degraded to per-rpc heartbeat for good
        self.batched: Optional[bool] = None
        self.sent = 0
        self.acked = 0
        self.sheds = 0
        self.resyncs = 0

    # ------------------------------------------------------------ lifecycle

    def start(self):
        record(
            "agent.report_interval",
            interval_s=self._interval,
            jitter_pct=int(JITTER * 100),
        )
        self._thread = threading.Thread(
            target=self._run, name="status-reporter", daemon=True
        )
        self._thread.start()

    def stop(self):
        self._stopped.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def _sleep_interval(self):
        lo, hi = 1.0 - JITTER, 1.0 + JITTER
        self._stopped.wait(self._interval * random.uniform(lo, hi))

    def _run(self):
        while not self._stopped.is_set():
            try:
                self.tick_once()
            except Exception as e:
                # connection supervision already retried inside the
                # client; whatever still escapes must not kill the
                # liveness loop
                logger.warning("status report failed: %s", e)
                self._tracker.request_full()
            self._sleep_interval()

    # ----------------------------------------------------------- one report

    def tick_once(self):
        if self.batched is False:
            self._legacy_tick()
            return
        from dlrover_tpu.telemetry import goodput as goodput_mod

        report = self._tracker.compose(
            time.time(),
            step=self._step_fn() if self._step_fn else None,
            pid=self._pid,
            goodput_fields=goodput_mod.report_fields(),
            resource=self._resource_fn() if self._resource_fn else None,
            serve_fields=self._serve_fn() if self._serve_fn else None,
        )
        # fleet roll-up (ISSUE 17): the metric digest rides the same
        # delta contract — compose drains into in-flight, a shed retry
        # reuses this payload, commit() below clears in-flight only
        # once the master acked
        if fleet.digests_enabled():
            digest = fleet.default_collector().compose()
            if digest:
                report.has_metrics = True
                report.metrics = digest
        shed_streak = 0
        while not self._stopped.is_set():
            self.sent += 1
            ack = self._client.report_node_status(report)
            if ack is None:
                # app-level rejection: the master predates the rpc —
                # this report's liveness still lands via the legacy
                # path, and all future ticks skip straight to it
                self.batched = False
                self._legacy_tick()
                return
            self.batched = True
            if ack.accepted:
                self.acked += 1
                self._tracker.commit(report)
                if report.has_metrics:
                    fleet.default_collector().commit()
                if ack.resync:
                    self.resyncs += 1
                    record("report.resync", seq=report.seq)
                    self._tracker.request_full()
                if ack.action and self._on_action:
                    self._on_action(ack.action)
                return
            # load shed: same payload, fresher heartbeat, jittered
            # backoff that grows with the shed streak
            self.sheds += 1
            shed_streak += 1
            if shed_streak == 1:
                record(
                    "report.retry_after",
                    retry_after_s=ack.retry_after_s, seq=report.seq,
                )
            delay = (ack.retry_after_s or 0.5)
            delay *= min(4.0, 2.0 ** (shed_streak - 1))
            delay *= random.uniform(0.5, 1.5)
            self._stopped.wait(delay)
            report.timestamp = time.time()

    def _legacy_tick(self):
        action = self._client.report_heartbeat()
        if action and self._on_action:
            self._on_action(action)
