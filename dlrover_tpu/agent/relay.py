"""Aggregator relay — the middle tier of the hierarchical fan-in
(ISSUE 16 tentpole c).

At 10k agents even a sharded, event-loop master is doing 10k RPC
round-trips per interval. The 100k-GPU HSDP result (PAPERS.md) shows
the scaling move: put an aggregation tier between agents and master so
master load grows with RELAY count, not world size. One relay fronts K
agents (the launcher's ``--relay_fanout``):

* **downstream** it terminates its agents' ``report_node_status``
  deltas with the exact master-side bookkeeping
  (:class:`~dlrover_tpu.master.ingest.ReporterLedger`): ack
  immediately, merge the sections into a per-agent state slot, answer
  ``resync=True`` when the relay lost the agent's baseline (relay
  restart) so the agent resends full — the agent cannot tell a relay
  from a master;
* **upstream** it re-deltas each agent's merged state against its own
  last-acked-by-master baseline via the agent-side
  :class:`~dlrover_tpu.agent.status_reporter.DeltaTracker` — the same
  change detectors, thresholds and full/resync machinery — and
  forwards ONE :class:`~dlrover_tpu.common.comm.RelayBatchReport` per
  interval carrying only the agents that reported since the last
  forward. Sub-reports keep their ORIGINAL reporter identity, so the
  master's per-agent ledger (the exactly-once proof) is tier-agnostic;
* the master's piggybacked actions ride back the same path with one
  interval of latency: each batch-ack entry's ``action`` parks in the
  agent's slot and is delivered on that agent's next report ack;
* when a relay DIES, its agents' ConnectionSupervisors fail over to
  the direct master address after ``DLROVER_TPU_RELAY_FAILOVER_S``
  (master_client.py) and the standard reconnect re-hello resends full
  state — the relay tier degrades to PR 12's direct fan-in, it never
  partitions agents from the master.

The relay only fronts the report lane; every other RPC (rendezvous,
checkpoint consensus, shards) stays agent -> master direct. It answers
``ping`` itself — the agents' supervisors probe RELAY liveness, and a
live relay whose own master link is down rides its upstream
supervisor, invisible to agents.
"""

import argparse
import threading
import time
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common import comm
from dlrover_tpu.common.grpc_utils import GenericRpcServer
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.master.ingest import ReporterLedger
from dlrover_tpu.telemetry import (
    counter, fleet, gauge, histogram, record, tracing,
)
from dlrover_tpu.telemetry.http import start_metrics_server

#: agents per relay — launchers and the swarm bench size the tier as
#: ceil(agents / fanout)
RELAY_FANOUT = 256

#: upstream forward cadence (seconds)
RELAY_INTERVAL_S = 1.0

#: where agents find their relay (set by the launcher); empty = no
#: relay tier, agents report direct (agent/elastic/training.py)
ENV_RELAY_ADDR = "DLROVER_TPU_RELAY_ADDR"


class _AgentSlot:
    """One fronted agent: merged last-known state + the upstream
    delta tracker. Mutated under the relay lock; the tracker is only
    ever driven by the forward thread."""

    __slots__ = (
        "tracker", "timestamp", "step", "step_ts", "pid",
        "goodput_fields", "resource", "host", "final", "fresh",
        "pending_action", "upstream_seq", "trace_ctx", "job_id",
    )

    def __init__(self, tracker):
        self.tracker = tracker
        #: job namespace of the fronted agent (ISSUE 19) — stamped onto
        #: every re-delta'd sub-report so the master attributes it
        self.job_id = "default"
        self.timestamp = 0.0
        self.step: Optional[int] = None
        self.step_ts = 0.0
        self.pid = 0
        self.goodput_fields: Optional[Dict] = None
        self.resource: Optional[Tuple[float, int]] = None
        self.host = ""
        self.final = False
        self.fresh = False
        self.pending_action = ""
        #: last upstream seq the MASTER acked for this agent — the
        #: bench's delivery-chain proof reads it
        self.upstream_seq = -1
        #: trace context carried by the agent's last report — the
        #: forward span adopts one of these so the worker -> relay ->
        #: master chain stays causal (ISSUE 17)
        self.trace_ctx: Optional[Tuple[str, str]] = None


class AggregatorRelay:
    """One relay process/instance fronting up to K agents."""

    def __init__(self, master_addr: str, relay_id: int = 0,
                 port: int = 0, interval: float = RELAY_INTERVAL_S,
                 ledger_cap: Optional[int] = None,
                 rpc_timeout: float = 30.0):
        from dlrover_tpu.agent.master_client import MasterClient

        self.relay_id = relay_id
        self._interval = max(0.05, interval)
        self._lock = threading.Lock()
        self._slots: Dict[Tuple[str, int], _AgentSlot] = {}
        self._ledger = (
            ReporterLedger(cap=ledger_cap) if ledger_cap
            else ReporterLedger()
        )
        self._upstream = MasterClient(
            master_addr, node_id=relay_id, node_type="relay",
            timeout=rpc_timeout,
        )
        #: None = undecided, False = master predates the batch RPC —
        #: forward per-agent report_node_status instead
        self._batch_supported: Optional[bool] = None
        # pre-merged fleet digests (ISSUE 17, per-job since ISSUE 19):
        # agents' per-report metric digests fold into ONE wire dict PER
        # JOB here, so the master sees one summary per (relay, job) per
        # interval regardless of fanout — and jobs sharing a relay
        # never cross-contaminate. Same loss-free contract as the
        # agent's DigestCollector: compose drains pending -> in-flight,
        # a failed forward keeps in-flight for the next compose, only
        # an accepted forward clears it. Both maps (job_id -> wire
        # digest) are guarded by ``self._lock``.
        self._pending_digests: Dict[str, Dict] = {}
        self._inflight_digests: Dict[str, Dict] = {}
        self._stopped = threading.Event()
        self._kick = threading.Event()
        self._flush_on_stop = True
        self._thread: Optional[threading.Thread] = None
        self._server = GenericRpcServer(self.handle, port=port)
        self.port = self._server.port
        self._metrics_server = None
        # observability (read by the bench after stop; single-writer
        # forward thread, so plain ints suffice)
        self.forwarded_batches = 0
        self.forwarded_reports = 0
        self.upstream_sheds = 0
        self.downstream_reports = 0
        # relays were observability blind spots (ISSUE 17): export the
        # tier's own vitals through the standard registry
        self._agents_gauge = gauge(
            "dlrover_relay_agents",
            "agents currently terminated by this relay",
        )
        self._forward_latency = histogram(
            "dlrover_relay_forward_latency_seconds",
            "relay upstream forward latency (compose + RPC + commit)",
        )
        self._forward_failures = counter(
            "dlrover_relay_forward_failures_total",
            "relay upstream forwards that failed (retried next interval)",
        )

    # ------------------------------------------------------------ lifecycle

    def start(self):
        self._server.start()
        # same DLROVER_TPU_METRICS_PORT contract as master/agents
        # ("off" disables; bind failure never takes the relay down)
        self._metrics_server = start_metrics_server()
        self._thread = threading.Thread(
            target=self._run, name=f"relay-forward-{self.relay_id}",
            daemon=True,
        )
        self._thread.start()
        record(
            "relay.started", relay_id=self.relay_id, port=self.port,
            interval_s=self._interval,
        )

    def stop(self, flush: bool = True, grace: float = 0.5):
        """``flush=False`` is the crash drill: drop everything pending
        (agents re-deliver through failover + resync)."""
        self._flush_on_stop = flush
        self._stopped.set()
        self._kick.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        self._server.stop(grace)
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None
        record(
            "relay.stopped", relay_id=self.relay_id, flushed=flush,
            forwarded=self.forwarded_reports,
        )

    def kill(self):
        """Simulate relay death for failover drills: stop serving
        without flushing upstream state."""
        self.stop(flush=False, grace=0.0)

    # ----------------------------------------------------------- downstream

    def handle(self, method: str, message):
        if method == "report_node_status":
            return self._terminate_report(message)
        if method == "report_heartbeat":
            return self._terminate_heartbeat(message)
        if method == "ping":
            # relay liveness: the agents' supervisors probe THIS
            return comm.Response(success=True)
        raise ValueError(
            f"relay does not front RPC {method} — call the master "
            "directly"
        )

    def _slot_for_locked(self, key: Tuple[str, int],
                         incarnation: int) -> _AgentSlot:
        """Lock held by caller (repo convention: ``*_locked``). A new
        incarnation replaces the slot: its delta baselines describe a
        dead process."""
        from dlrover_tpu.agent.status_reporter import DeltaTracker

        slot = self._slots.get(key)
        if slot is None or slot.tracker._incarnation != incarnation:
            slot = _AgentSlot(DeltaTracker(incarnation=incarnation))
            self._slots[key] = slot
        return slot

    def _terminate_report(
        self, req: comm.NodeStatusReport
    ) -> comm.NodeStatusAck:
        key = (req.node_type, req.node_id)
        resync = self._ledger.observe(
            key, req.incarnation, req.seq, req.full, req.timestamp
        )
        with self._lock:
            slot = self._slot_for_locked(key, req.incarnation)
            slot.timestamp = req.timestamp
            if req.has_step:
                slot.step = req.step
                slot.step_ts = req.step_ts
                slot.pid = req.pid
            if req.has_goodput:
                slot.goodput_fields = {
                    "goodput_phases": dict(req.goodput_phases),
                    "goodput_elapsed_s": req.goodput_elapsed_s,
                    "goodput_start_ts": req.goodput_start_ts,
                    "goodput_phase": req.goodput_phase,
                }
                slot.pid = req.pid
            if req.has_resource:
                slot.resource = (req.cpu_percent, req.memory_mb)
            if req.host:
                slot.host = req.host
            if req.final:
                slot.final = True
            slot.fresh = True
            # grpc_utils installed the agent's trace context for this
            # handler; park it so the next forward chains under it
            slot.trace_ctx = tracing.current_context()
            if req.job_id != slot.job_id:
                slot.job_id = req.job_id
                slot.tracker.job_id = req.job_id
            if req.has_metrics and req.metrics:
                fleet.merge_digest(
                    self._pending_digests.setdefault(req.job_id, {}),
                    req.metrics,
                )
            action = slot.pending_action
            slot.pending_action = ""
            self.downstream_reports += 1
        return comm.NodeStatusAck(
            accepted=True, action=action, resync=resync,
            acked_seq=req.seq,
        )

    def _terminate_heartbeat(self, req) -> comm.HeartbeatResponse:
        """Legacy lane for degraded reporters: liveness still flows."""
        key = (req.node_type, req.node_id)
        with self._lock:
            slot = self._slot_for_locked(key, 0)
            slot.timestamp = req.timestamp
            slot.fresh = True
            action = slot.pending_action
            slot.pending_action = ""
            self.downstream_reports += 1
        return comm.HeartbeatResponse(action=action)

    # ------------------------------------------------------------- upstream

    def _run(self):
        while not self._stopped.is_set():
            self._kick.wait(self._interval)
            self._kick.clear()
            if self._stopped.is_set():
                break
            self._forward_once()
        if self._flush_on_stop:
            self._forward_once()

    def _compose_batch(self):
        """Snapshot fresh slots under the lock, compose outside it
        (compose runs change detectors — keep it off the ack path)."""
        with self._lock:
            self._agents_gauge.set(len(self._slots))
            fresh = [
                (key, slot) for key, slot in self._slots.items()
                if slot.fresh
            ]
            for _key, slot in fresh:
                slot.fresh = False
            snapshots = [
                (
                    key, slot, slot.timestamp, slot.step, slot.step_ts,
                    slot.pid, slot.goodput_fields, slot.resource,
                    slot.host, slot.final,
                )
                for key, slot in fresh
            ]
            # drain pending -> in-flight per job; a retried/failed
            # forward's digests are still in-flight and re-merge here
            # losslessly
            for job, pending in self._pending_digests.items():
                fleet.merge_digest(
                    self._inflight_digests.setdefault(job, {}), pending
                )
            self._pending_digests = {}
            digests: Dict[str, Dict] = {}
            for job, inflight in self._inflight_digests.items():
                if inflight:
                    fleet.merge_digest(
                        digests.setdefault(job, {}), inflight
                    )
        reports, slots = [], []
        for (key, slot, ts, step, step_ts, pid, goodput, resource,
             host, final) in snapshots:
            report = slot.tracker.compose(
                ts, step=step, step_ts=step_ts, pid=pid,
                goodput_fields=goodput, resource=resource, host=host,
                final=final,
            )
            # the sub-report travels under the AGENT's identity: the
            # master's ledger must stay keyed by original reporter
            report.node_type, report.node_id = key
            reports.append(report)
            slots.append((key, slot))
        return reports, slots, digests

    def _forward_once(self):
        reports, slots, digests = self._compose_batch()
        if not reports:
            return
        # adopt the freshest carried agent context: the relay's forward
        # span becomes the child of a worker report span and the parent
        # of the master's rpc.report_relay_batch span — the causal
        # chain ISSUE 17's chaos drill asserts
        ctx = None
        for _key, slot in slots:
            if slot.trace_ctx is not None:
                ctx = slot.trace_ctx
        t0 = time.perf_counter()
        try:
            with tracing.trace_context(*(ctx or (None, None))), \
                    tracing.span("relay.forward", {
                        "relay": self.relay_id, "reports": len(reports),
                    }):
                try:
                    if self._batch_supported is False:
                        acks = self._forward_individually(reports)
                    else:
                        acks = self._forward_batch(reports, digests)
                except Exception as e:
                    self._forward_failures.inc()
                    record(
                        "relay.forward_failed", relay_id=self.relay_id,
                        reports=len(reports), error=str(e)[:200],
                    )
                    logger.warning(
                        "relay %d upstream forward failed (%d reports): %s",
                        self.relay_id, len(reports), e,
                    )
                    with self._lock:
                        for _key, slot in slots:
                            slot.fresh = True  # recompose next interval
                    return
                self._commit_acks(slots, reports, acks)
                if digests:
                    # the master applied the in-flight digests (or an
                    # old master that can't consume them acked the
                    # fallback — either way retrying would
                    # double-count)
                    with self._lock:
                        self._inflight_digests = {}
        finally:
            self._forward_latency.observe(time.perf_counter() - t0)

    def _forward_batch(self, reports,
                       digests: Optional[Dict[str, Dict]] = None
                       ) -> List[comm.NodeStatusAck]:
        digests = digests or {}
        if set(digests) <= {"default"}:
            # single-job relay: ride the legacy field so the wire (and
            # an ISSUE 17 master) is byte-identical to the pre-job
            # format
            batch = comm.RelayBatchReport(
                reports=reports, relay_incarnation=0,
                digest=digests.get("default", {}),
            )
        else:
            batch = comm.RelayBatchReport(
                reports=reports, relay_incarnation=0, digests=digests,
            )
        attempts = 0
        while True:
            ack = self._upstream.report_relay_batch(batch)
            if ack is None:
                # master predates the batch RPC: degrade permanently
                self._batch_supported = False
                return self._forward_individually(reports)
            self._batch_supported = True
            if ack.accepted:
                self.forwarded_batches += 1
                self.forwarded_reports += len(reports)
                return ack.acks
            # batch-level shed: same payload, honored retry-after.
            # Bounded: a master that sheds forever is a forward
            # failure — the slots re-mark fresh and next interval
            # recomposes (the trackers never committed).
            self.upstream_sheds += 1
            attempts += 1
            if attempts >= 10:
                raise RuntimeError(
                    f"master shed the relay batch {attempts} times"
                )
            if self._stopped.is_set() and not self._flush_on_stop:
                return []
            time.sleep(ack.retry_after_s or 0.5)

    def _forward_individually(self, reports) -> List[comm.NodeStatusAck]:
        """Mixed-fleet fallback: the coalescing is lost but delivery
        survives against a PR 12 master."""
        acks = []
        for r in reports:
            ack = self._upstream._supervisor.call(
                "report_node_status",
                lambda r=r: self._upstream._client.call(
                    "report_node_status", r
                ),
            )
            acks.append(ack)
            self.forwarded_reports += 1
        return acks

    def _commit_acks(self, slots, reports, acks):
        for (key, slot), report, ack in zip(slots, reports, acks):
            if ack is None or not ack.accepted:
                with self._lock:
                    slot.fresh = True
                continue
            # forward-thread-only state: tracker + upstream_seq
            slot.tracker.commit(report)
            slot.upstream_seq = ack.acked_seq
            if ack.resync:
                # the MASTER lost this agent's baseline (restart):
                # resend full from the relay's merged state next time
                slot.tracker.request_full()
            if ack.action:
                with self._lock:
                    slot.pending_action = ack.action
            if slot.final:
                with self._lock:
                    self._slots.pop(key, None)
                self._ledger.evict(key)

    # -------------------------------------------------------------- views

    def delivery_snapshot(self) -> Dict[Tuple[str, int], Dict[str, int]]:
        """Per-agent delivery chain for the bench's zero-drop proof:
        the seq the relay acked downstream vs the seq the master acked
        upstream."""
        down = self._ledger.snapshot()
        with self._lock:
            return {
                key: {
                    "downstream_seq": down.get(key, (-1, -1))[1],
                    "upstream_seq": slot.upstream_seq,
                }
                for key, slot in self._slots.items()
            }

    def stats(self) -> Dict[str, int]:
        with self._lock:
            agents = len(self._slots)
            downstream = self.downstream_reports
        return {
            "relay_id": self.relay_id,
            "agents": agents,
            "downstream_reports": downstream,
            "forwarded_batches": self.forwarded_batches,
            "forwarded_reports": self.forwarded_reports,
            "upstream_sheds": self.upstream_sheds,
        }


class RelayTier:
    """Launcher-side lifecycle of the relay tier (ISSUE 18).

    ISSUE 16 built the relay; this owns its LIFE: size the tier as
    ``ceil(agents / fanout)``, spawn one relay subprocess per slot,
    monitor them, and restart a dead relay ON ITS ORIGINAL PORT — the
    address handed to agents (``DLROVER_TPU_RELAY_ADDR``) stays valid
    across the restart, so agents that failed over to the direct
    master path drift back to the relay on their supervisor's next
    probe without any re-pointing. Agents map to relays contiguously
    (``rank // fanout``), wrapping for ranks grown past the
    provisioned count.
    """

    def __init__(self, master_addr: str, n_agents: int,
                 fanout: int = RELAY_FANOUT,
                 check_interval: float = 1.0,
                 spawn_timeout: float = 30.0):
        self._master_addr = master_addr
        self._n_agents = max(1, int(n_agents))
        self._fanout = max(1, int(fanout))
        #: tier size: every agent fronted, no relay over fanout
        self.n_relays = -(-self._n_agents // self._fanout)
        self._check_interval = max(0.05, float(check_interval))
        self._spawn_timeout = float(spawn_timeout)
        self._lock = threading.Lock()
        self._procs: Dict[int, "subprocess.Popen"] = {}
        self._ports: Dict[int, int] = {}
        self.restarts = 0
        self._stopped = threading.Event()
        self._monitor: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "RelayTier":
        for rid in range(self.n_relays):
            self._spawn(rid, port=0)
        self._monitor = threading.Thread(
            target=self._watch, name="relay-tier-monitor", daemon=True,
        )
        self._monitor.start()
        record(
            "relay.tier_started", relays=self.n_relays,
            fanout=self._fanout, agents=self._n_agents,
            ports=sorted(self.ports().values()),
        )
        return self

    def stop(self, grace: float = 2.0) -> None:
        self._stopped.set()
        if self._monitor is not None:
            self._monitor.join(timeout=10.0)
        with self._lock:
            procs = list(self._procs.values())
        for p in procs:
            p.terminate()
        deadline = time.monotonic() + grace
        for p in procs:
            try:
                p.wait(timeout=max(0.0, deadline - time.monotonic()))
            except Exception:
                p.kill()
        record(
            "relay.tier_stopped", relays=self.n_relays,
            restarts=self.restarts,
        )

    # ------------------------------------------------------------ addressing

    def addr_for(self, node_rank: int) -> str:
        """The relay address for one agent — what the launcher exports
        as ``DLROVER_TPU_RELAY_ADDR`` into the agent's env."""
        rid = (int(node_rank) // self._fanout) % self.n_relays
        with self._lock:
            return f"localhost:{self._ports[rid]}"

    def ports(self) -> Dict[int, int]:
        with self._lock:
            return dict(self._ports)

    # ------------------------------------------------------------ internals

    def _spawn(self, rid: int, port: int) -> None:
        import re
        import subprocess
        import sys

        proc = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.agent.relay",
                "--master_addr", self._master_addr,
                "--relay_id", str(rid), "--port", str(port),
            ],
            stdout=subprocess.PIPE, text=True,
        )
        got = None
        deadline = time.monotonic() + self._spawn_timeout
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            m = re.match(r"PORT (\d+)", line or "")
            if m:
                got = int(m.group(1))
                break
            if proc.poll() is not None:
                break
        if got is None:
            proc.kill()
            raise RuntimeError(
                f"relay {rid} did not report its port in "
                f"{self._spawn_timeout}s"
            )
        with self._lock:
            self._procs[rid] = proc
            self._ports[rid] = got

    def _watch(self) -> None:
        """Restart dead relays on their original port. Agents ride
        their supervisor's failover to the direct master while the
        slot is down; the restart makes the advertised address serve
        again."""
        while not self._stopped.wait(self._check_interval):
            with self._lock:
                dead = [
                    (rid, p, self._ports[rid])
                    for rid, p in self._procs.items()
                    if p.poll() is not None
                ]
            for rid, p, port in dead:
                if self._stopped.is_set():
                    return
                logger.warning(
                    "relay %d died rc=%s; restarting on port %d",
                    rid, p.poll(), port,
                )
                try:
                    self._spawn(rid, port=port)
                except Exception as e:
                    # the port can linger in TIME_WAIT right after a
                    # crash: leave the slot dead and retry next tick
                    logger.warning(
                        "relay %d restart failed (%s); retrying", rid, e
                    )
                    continue
                self.restarts += 1
                record(
                    "relay.restarted", relay_id=rid, port=port,
                    exit_rc=p.poll(),
                )


def main():
    parser = argparse.ArgumentParser(
        description="dlrover-tpu aggregator relay (ISSUE 16)"
    )
    parser.add_argument("--master_addr", required=True)
    parser.add_argument("--relay_id", type=int, default=0)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--interval", type=float,
                        default=RELAY_INTERVAL_S)
    ns = parser.parse_args()
    relay = AggregatorRelay(
        ns.master_addr, relay_id=ns.relay_id, port=ns.port,
        interval=ns.interval,
    )
    relay.start()
    print(f"PORT {relay.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        relay.stop()


if __name__ == "__main__":
    main()
