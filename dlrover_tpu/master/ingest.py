"""Sharded ingest plane — the master side of the event-loop fan-in
(ISSUE 16 tentpole b).

PR 12 proved the delta-report wire format; what still serialized the
hot path was the master's ingest state: ONE ``_reporters`` dict under
ONE lock, touched by every ``report_node_status`` in flight. At 10k
agents that lock is the whole control plane. Here reporter state is
sharded by node id into N independent :class:`IngestShard`\\ s:

* each shard OWNS its slice of the acked-seq ledger / delta baselines
  (:class:`ReporterLedger`) and its slice of the admission budget —
  there are no cross-shard locks, and nothing here ever holds two
  locks at once;
* on the event-loop front end (``AsyncRpcServer``), each shard applies
  reports on its own single-thread executor (``ingest-shard-<i>``), so
  per-shard application is SERIAL — the shard lock is only contended
  by stats readers and the threaded fallback lane;
* applied sections drain into the same shared consumers as before
  (job manager striped locks, speed monitor, goodput aggregator, the
  group-commit journal lane) — the exactly-once and commit-before-
  reply contracts from PR 12 survive verbatim because the ledger
  update and the section application happen, in that order, before
  the ack is composed.

The ledger is also the master's per-reporter MEMORY — and before this
PR it grew forever (satellite bugfix). Now it is bounded by
``LEDGER_CAP`` entries with the SpeedMonitor stale-first
pattern: a ``final=True`` report (process exit) evicts its entry
immediately, and at the cap the stalest incumbent is evicted to admit
a newcomer. An evicted-but-alive reporter is not harmed: its next
delta report finds no baseline and is acked ``resync=True``, exactly
the master-restart path the agent already handles.

The relay (``agent/relay.py``) terminates its agents' reports with the
same :class:`ReporterLedger` semantics — one implementation of the
exactly-once bookkeeping, two tiers of the fan-in tree.
"""

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

from dlrover_tpu.common import comm
from dlrover_tpu.telemetry import counter, record

#: ingest shard count; each shard is an independent ledger slice +
#: admission slice + (event-loop mode) single-thread apply executor
INGEST_SHARDS = 4

#: per-reporter ledger entries the master retains across all shards;
#: at the cap the stalest entry is evicted (resync heals a live one)
LEDGER_CAP = 16384


def _shed_counter():
    return counter(
        "dlrover_report_shed_total",
        "batched reports shed with retry-after",
    )


def _evict_counter():
    return counter(
        "dlrover_report_ledger_evicted_total",
        "per-reporter ledger entries evicted (final report, or "
        "stale-first at the cap)",
    )


def _entry_staleness(item):
    (_key, (_inc, _seq, ts)) = item
    return ts


class ReporterLedger:
    """One slice of per-reporter delta state: ``(node_type, node_id)``
    -> ``(incarnation, seq, last_seen_ts)``. Bounded; stale-first
    eviction at the cap (SpeedMonitor pattern, ISSUE 12); ``final``
    reports evict immediately. Thread-safe; shared by the master's
    ingest shards and the relay's downstream termination."""

    def __init__(self, cap: int = LEDGER_CAP):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, int], Tuple[int, int, float]] = {}
        self._cap = max(2, cap)
        self.evictions = 0

    def observe(self, key: Tuple[str, int], incarnation: int, seq: int,
                full: bool, timestamp: float) -> bool:
        """Fold one report into the ledger; returns ``resync`` — True
        when the reporter is unknown (restart lost the baseline, or it
        was evicted) or switched incarnation without a full report."""
        with self._lock:
            last = self._entries.get(key)
            resync = not full and (
                last is None or last[0] != incarnation
            )
            if last is None and len(self._entries) >= self._cap:
                # cap reached: evict the stalest incumbent to admit the
                # newcomer — liveness must always land, and the evicted
                # reporter (if alive) self-heals through resync
                stalest = min(
                    self._entries.items(), key=_entry_staleness
                )[0]
                del self._entries[stalest]
                self.evictions += 1
                _evict_counter().inc()
            self._entries[key] = (incarnation, seq, timestamp)
            return resync

    def evict(self, key: Tuple[str, int]) -> bool:
        """Drop one reporter (its process exited); True if present."""
        with self._lock:
            if key not in self._entries:
                return False
            del self._entries[key]
            self.evictions += 1
        _evict_counter().inc()
        return True

    def get(self, key: Tuple[str, int]) -> Optional[Tuple[int, int]]:
        with self._lock:
            e = self._entries.get(key)
            return (e[0], e[1]) if e is not None else None

    def snapshot(self) -> Dict[Tuple[str, int], Tuple[int, int]]:
        with self._lock:
            return {
                k: (inc, seq)
                for k, (inc, seq, _ts) in self._entries.items()
            }

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class IngestShard:
    """One slice of the ingest plane: a ledger, an admission budget,
    and (event-loop mode) a single-thread apply executor."""

    def __init__(self, index: int, inflight_limit: int,
                 ledger_cap: int):
        self.index = index
        self.ledger = ReporterLedger(cap=ledger_cap)
        self._lock = threading.Lock()
        self._inflight = 0
        self._inflight_limit = max(1, inflight_limit)
        self._last_shed_log = 0.0
        self._executor: Optional[ThreadPoolExecutor] = None

    # ---------------------------------------------------------- admission

    def try_admit(self) -> bool:
        with self._lock:
            if self._inflight >= self._inflight_limit:
                return False
            self._inflight += 1
            return True

    def release(self):
        with self._lock:
            self._inflight -= 1

    def set_inflight_limit(self, limit: int):
        with self._lock:
            self._inflight_limit = limit

    def note_shed(self, retry_after_s: float):
        """Shed accounting + the rate-limited journal event."""
        _shed_counter().inc()
        now = time.monotonic()
        with self._lock:
            should_log = now - self._last_shed_log > 1.0
            if should_log:
                self._last_shed_log = now
            inflight = self._inflight
            limit = self._inflight_limit
        if should_log:
            record(
                "control.load_shed",
                shard=self.index,
                inflight=inflight,
                limit=limit,
                retry_after_s=retry_after_s,
            )

    # ----------------------------------------------------------- executor

    @property
    def executor(self) -> ThreadPoolExecutor:
        """Lazily created single-thread apply lane: per-shard serial
        execution is what makes the shard state effectively lock-free
        under the event-loop front end."""
        with self._lock:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"ingest-shard-{self.index}",
                )
            return self._executor

    def close(self):
        with self._lock:
            ex = self._executor
            self._executor = None
        if ex is not None:
            ex.shutdown(wait=False)


class IngestPlane:
    """N independent ingest shards keyed by node id.

    ``apply_fn(report) -> action`` is the servicer's section
    application (heartbeat/step/goodput/resource into the shared
    managers); the plane owns everything per-reporter around it:
    admission, the acked-seq ledger, resync, and eviction."""

    def __init__(self, shards: int = INGEST_SHARDS,
                 inflight_limit: Optional[int] = None,
                 retry_after: Optional[float] = None,
                 ledger_cap: int = LEDGER_CAP):
        shards = max(1, shards)
        if inflight_limit is None:
            inflight_limit = int(
                os.environ.get("DLROVER_TPU_REPORT_INFLIGHT_LIMIT", "48")
            )
        if retry_after is None:
            retry_after = float(
                os.environ.get("DLROVER_TPU_REPORT_RETRY_AFTER", "0.5")
            )
        self.retry_after = retry_after
        self._inflight_limit = max(1, inflight_limit)
        # the admission budget splits across shards (no cross-shard
        # coordination); per-shard ledger caps split the same way so
        # the global bound holds whatever the id distribution
        per_shard_limit = max(1, self._inflight_limit // shards)
        per_shard_cap = max(2, ledger_cap // shards)
        self.shards: List[IngestShard] = [
            IngestShard(i, per_shard_limit, per_shard_cap)
            for i in range(shards)
        ]

    # ------------------------------------------------------------ routing

    def shard_of(self, node_type: str, node_id: int) -> IngestShard:
        if len(self.shards) == 1:
            return self.shards[0]
        return self.shards[(hash(node_type) ^ node_id) % len(self.shards)]

    # ------------------------------------------------------------- report

    def shed_ack(self, shard: IngestShard) -> comm.NodeStatusAck:
        shard.note_shed(self.retry_after)
        return comm.NodeStatusAck(
            accepted=False, retry_after_s=self.retry_after,
        )

    def apply(self, req: comm.NodeStatusReport,
              apply_fn: Callable[[comm.NodeStatusReport], str],
              shard: Optional[IngestShard] = None,
              ) -> comm.NodeStatusAck:
        """Ledger-then-sections application (admission already done).
        Runs on a shard executor (event-loop lane) or the RPC thread
        (threaded lane) — the shard's own state is safe either way."""
        if shard is None:
            shard = self.shard_of(req.node_type, req.node_id)
        key = (req.node_type, req.node_id)
        resync = shard.ledger.observe(
            key, req.incarnation, req.seq, req.full, req.timestamp
        )
        action = apply_fn(req) or ""
        if req.final:
            # process exit closes the incarnation: its baseline can
            # never be consulted again — drop it now, not at the cap
            shard.ledger.evict(key)
        return comm.NodeStatusAck(
            accepted=True, action=action, resync=resync,
            acked_seq=req.seq,
        )

    def report(self, req: comm.NodeStatusReport,
               apply_fn: Callable[[comm.NodeStatusReport], str],
               ) -> comm.NodeStatusAck:
        """The threaded (legacy / cold-servicer) entry: admission +
        apply inline on the calling thread."""
        shard = self.shard_of(req.node_type, req.node_id)
        if not shard.try_admit():
            return self.shed_ack(shard)
        try:
            return self.apply(req, apply_fn, shard=shard)
        finally:
            shard.release()

    # -------------------------------------------------------------- views

    @property
    def inflight_limit(self) -> int:
        return self._inflight_limit

    @inflight_limit.setter
    def inflight_limit(self, limit: int):
        """Reconfigure the admission budget (tests, ops). ``0`` sheds
        everything."""
        limit = max(0, int(limit))
        self._inflight_limit = limit
        per_shard = max(1, limit // len(self.shards)) if limit else 0
        for s in self.shards:
            s.set_inflight_limit(per_shard)

    def reporters(self) -> Dict[Tuple[str, int], Tuple[int, int]]:
        """Merged (incarnation, seq) view across shards — the bench's
        delivery proof and the tests' ledger assertions read this."""
        out: Dict[Tuple[str, int], Tuple[int, int]] = {}
        for s in self.shards:
            out.update(s.ledger.snapshot())
        return out

    def evictions(self) -> int:
        return sum(s.ledger.evictions for s in self.shards)

    def stats(self) -> Dict[str, int]:
        return {
            "shards": len(self.shards),
            "reporters": sum(len(s.ledger) for s in self.shards),
            "evictions": self.evictions(),
        }

    def close(self):
        for s in self.shards:
            s.close()
