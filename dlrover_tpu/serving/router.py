"""Master-side request plane: hash-partitioned router shards with
per-tenant fair queuing.

PR 11 built the serving twin of the shard ledger — exactly-once request
leasing with redelivery — behind ONE ``threading.Lock`` and one deque.
That is correct but it is a single serialization point between
"millions of users" and the replica pool, and two of its costs grow
with the stream: ``finished()`` scanned the entire done-store under the
lock after EVERY complete/poll, and the done-store itself never shrank.
This module shards the plane (ISSUE 20):

* **hash partitioning** — :class:`RequestRouter` is now a facade over N
  independent :class:`RouterShard` instances
  (``DLROVER_TPU_SERVE_ROUTER_SHARDS``), keyed by
  ``crc32(req_id) % N``. Each shard owns its lock, admission queues,
  lease table, and done-store partition, so the exactly-once argument
  (done-store first-complete-wins + three redelivery paths) holds
  per-shard with ZERO cross-shard coordination on the hot path: a
  request's submit, lease record, completion, and poll all live on the
  one shard its id hashes to.
* **round-robin leasing** — replicas drain shards in rotated order with
  *non-blocking* lock acquisition: a contended shard is skipped, not
  waited on, so a partial batch rides immediately (continuous
  batching's "return what is queued NOW" now also means "on the shards
  you can reach NOW").
* **per-tenant fair queuing** — each shard's admission queue is a set
  of per-(priority, tenant) deques drained by deficit round-robin
  (``DRR_QUANTUM`` requests per tenant per visit).
  Priority classes are strict (a higher class drains first); tenants
  within a class share by DRR, so one chatty tenant cannot starve the
  rest — a newly-arrived tenant is served within one drain cycle.
  ``tenant=`` / ``priority=`` ride ``serve_submit``; the default tenant
  keeps the old global-FIFO behavior exactly.
* **done-store GC** — delivered responses older than
  ``DONE_TTL_S`` seconds are evicted by the watchdog
  (``dlrover_serve_done_evicted_total``); undelivered responses are
  kept forever (a poller may still come). Duplicate rejection holds for
  any retry inside the TTL; ``finished()`` is O(1) per shard via
  completed/undelivered counters instead of a full scan.
* **live resharding** — ``resize_shards(n)`` re-partitions the plane
  under a full freeze (all shard locks held), preserving in-flight
  leases, queued order (by global submit seq), and the done-store, so
  an operator can grow the router mid-stream (the soak drill changes
  the shard count with leases outstanding).

Incarnation bookkeeping is the one deliberately plane-level table: a
lease from a newer incarnation must reclaim the dead predecessor's
leases on EVERY shard, not just the ones the new lease happens to
visit — reclaim is a cold path (once per replica restart), so it takes
the shard locks in turn.

The plane lives in the master process, is served over the same
proto-less gRPC envelope (servicer ``rpc_serve_*`` methods), and drives
the serving autoscaler (serving/autoscaler.py) off its ``stats()``.
"""

import itertools
import os
import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import counter, gauge, histogram, record

#: redelivery watchdog: a leased-but-unacked request older than this is
#: requeued (its worker is presumed dead). Serving leases are seconds,
#: not the minutes of a training shard — default accordingly.
ENV_LEASE_TIMEOUT = "DLROVER_TPU_SERVE_LEASE_TIMEOUT"
DEFAULT_LEASE_TIMEOUT = 5.0

#: bounded admission: submits past this TOTAL depth (split across
#: shards) are rejected
MAX_QUEUE = 1024

#: router shard count: independent locks/queues/done-partitions
ENV_ROUTER_SHARDS = "DLROVER_TPU_SERVE_ROUTER_SHARDS"
DEFAULT_ROUTER_SHARDS = 1

#: delivered done-store entries older than this are GC'd (seconds);
#: undelivered entries are kept until polled
DONE_TTL_S = 300.0

#: deficit-round-robin quantum: requests granted per tenant per visit
DRR_QUANTUM = 4

#: sub-ms cache hits up to multi-second cold batches
_LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0,
)

#: recent completed-request latencies kept for p50/p99 (per shard)
_LATENCY_WINDOW = 4096

#: replica stats older than this are dropped from stats() aggregation
_REPLICA_STATS_TTL = 30.0

#: cardinality guard on the distinct-tenant stat
_TENANT_SET_CAP = 4096

DEFAULT_TENANT = ""
DEFAULT_PRIORITY = 0


def shard_for(req_id: str, n: int) -> int:
    """The partition function: stable, Python-hash-free (crc32, the
    same choice as the checkpoint plane's owner election)."""
    if n <= 1:
        return 0
    return zlib.crc32(req_id.encode("utf-8", "replace")) % n


class _Pending:
    """One in-flight request record."""

    __slots__ = ("req_id", "payload", "tenant", "priority", "seq",
                 "submit_ts", "worker", "incarnation", "lease_ts",
                 "redeliveries")

    def __init__(self, req_id: str, payload: bytes, tenant: str,
                 priority: int, seq: int):
        self.req_id = req_id
        self.payload = payload
        self.tenant = tenant
        self.priority = priority
        #: plane-global admission order — what "front of the queue"
        #: and reshard queue rebuilds sort by
        self.seq = seq
        self.submit_ts = time.time()
        self.worker: Optional[Tuple[str, int]] = None
        self.incarnation = -1
        self.lease_ts = 0.0
        self.redeliveries = 0


class _Done:
    """A completed request: the stored exactly-once response."""

    __slots__ = ("payload", "worker", "latency_s", "delivered",
                 "done_ts")

    def __init__(self, payload: bytes, worker: Tuple[str, int],
                 latency_s: float):
        self.payload = payload
        self.worker = worker
        self.latency_s = latency_s
        self.delivered = False
        self.done_ts = time.time()


class RouterShard:
    """One partition: its own lock, per-tenant admission deques, lease
    table, and done-store. All cross-request invariants (exactly-once,
    front-requeue order, duplicate rejection) are per-shard — the plane
    guarantees a request id always routes to the same shard."""

    def __init__(self, index: int, max_queue: int,
                 drr_quantum: int = DRR_QUANTUM):
        self.index = index
        self._max_queue = max(1, max_queue)
        self._quantum = max(1, drr_quantum)
        self._lock = threading.Lock()
        #: set under the plane's full freeze during resize_shards():
        #: an op that raced the swap re-checks this under the lock and
        #: re-routes through the new shard list
        self.detached = False
        #: (priority, tenant) -> deque of req ids awaiting a lease
        self._tq: Dict[Tuple[int, str], deque] = {}
        #: priority -> round-robin ring of tenants with queued work
        self._rings: Dict[int, List[str]] = {}
        self._ring_pos: Dict[int, int] = {}
        self._deficit: Dict[Tuple[int, str], int] = {}
        self._queued = 0
        #: req_id -> _Pending, for every submitted-but-not-done request
        self._pending: Dict[str, _Pending] = {}
        #: req_id -> _Done, exactly-once response store (GC'd: delivered
        #: entries past the TTL are evicted, undelivered kept forever)
        self._done: Dict[str, _Done] = {}
        self._latencies: deque = deque(maxlen=_LATENCY_WINDOW)
        # attributed split of the same window (ISSUE 17): queue wait
        # (submit -> winning lease) vs model time (lease -> complete).
        # The SLO evaluator reads it to say WHICH side blew the p99 —
        # capacity (scale out) or the model itself (scaling won't help)
        self._queue_waits: deque = deque(maxlen=_LATENCY_WINDOW)
        self._model_times: deque = deque(maxlen=_LATENCY_WINDOW)
        self._submitted = 0
        #: monotonic completion count — len(_done) shrinks under GC
        self._completed = 0
        #: completed-but-not-yet-polled count: the O(1) replacement for
        #: the old all(d.delivered ...) full scan on every complete
        self._undelivered = 0
        self._rejected = 0
        self._duplicates = 0
        self._redelivered = 0
        self._evicted = 0

    # ------------------------------------------------------ queue plumbing

    def _enqueue_locked(self, pending: _Pending, front: bool = False):
        key = (pending.priority, pending.tenant)
        q = self._tq.get(key)
        if q is None:
            q = self._tq[key] = deque()
        if front:
            q.appendleft(pending.req_id)
        else:
            q.append(pending.req_id)
        if len(q) == 1:
            ring = self._rings.setdefault(pending.priority, [])
            if pending.tenant not in ring:
                ring.append(pending.tenant)
        self._queued += 1

    def _drop_tenant_locked(self, priority: int, tenant: str):
        """The tenant's deque drained: leave the ring and clear its
        deficit so a returning tenant starts a fresh DRR cycle."""
        self._tq.pop((priority, tenant), None)
        self._deficit.pop((priority, tenant), None)
        ring = self._rings.get(priority)
        if ring and tenant in ring:
            pos = ring.index(tenant)
            ring.remove(tenant)
            # keep the rotation anchored: removals before the cursor
            # must not skip the next tenant
            if pos < self._ring_pos.get(priority, 0):
                self._ring_pos[priority] -= 1
            if not ring:
                self._rings.pop(priority, None)
                self._ring_pos.pop(priority, None)

    def _pop_batch_locked(self, n: int, now: float,
                          worker: Tuple[str, int],
                          incarnation: int) -> List[Tuple[str, bytes]]:
        """Deficit round-robin drain: strict priority between classes,
        DRR across tenants within a class (quantum requests per tenant
        per visit) — a starved tenant is served within one cycle."""
        batch: List[Tuple[str, bytes]] = []
        while self._queued and len(batch) < n:
            priority = max(self._rings)
            ring = self._rings[priority]
            pos = self._ring_pos.get(priority, 0) % len(ring)
            tenant = ring[pos]
            key = (priority, tenant)
            q = self._tq.get(key)
            if not q:
                self._drop_tenant_locked(priority, tenant)
                continue
            budget = self._deficit.get(key, 0) + self._quantum
            while q and budget > 0 and len(batch) < n:
                req_id = q.popleft()
                self._queued -= 1
                budget -= 1
                pending = self._pending.get(req_id)
                if pending is None:
                    continue
                pending.worker = worker
                pending.incarnation = incarnation
                pending.lease_ts = now
                batch.append((req_id, pending.payload))
            if not q:
                self._drop_tenant_locked(priority, tenant)
            elif budget <= 0:
                # quantum spent, queue non-empty: next tenant's turn
                self._deficit[key] = 0
                self._ring_pos[priority] = (pos + 1) % len(ring)
            else:
                # batch filled mid-quantum: bank the remainder so the
                # next visit resumes this tenant's share
                self._deficit[key] = budget
        return batch

    # -------------------------------------------------------------- ops
    # Each takes the shard lock itself and returns plain data; metric
    # emission happens in the plane, outside any shard lock.

    def submit(self, pending: _Pending, sealed: bool
               ) -> Tuple[bool, str, int]:
        """Returns (accepted, reason, queue_depth)."""
        with self._lock:
            if self.detached:
                return False, "detached", 0
            if sealed:
                return False, "sealed", self._queued
            req_id = pending.req_id
            if req_id in self._pending or req_id in self._done:
                self._duplicates += 1
                return False, "duplicate", self._queued
            if self._queued >= self._max_queue:
                self._rejected += 1
                return False, "backpressure", self._queued
            self._submitted += 1
            self._pending[req_id] = pending
            self._enqueue_locked(pending)
            return True, "", self._queued

    def try_lease(self, n: int, now: float, worker: Tuple[str, int],
                  incarnation: int
                  ) -> Optional[Tuple[List[Tuple[str, bytes]], int]]:
        """Non-blocking drain: None when the shard lock is contended
        (the plane skips it — a partial batch never waits), else
        (batch, queue_depth)."""
        if not self._lock.acquire(blocking=False):
            return None
        try:
            if self.detached:
                return [], 0
            return (
                self._pop_batch_locked(n, now, worker, incarnation),
                self._queued,
            )
        finally:
            self._lock.release()

    def complete(self, worker: Tuple[str, int], req_id: str,
                 payload: bytes) -> Tuple[bool, float, float, float]:
        """Returns (accepted, latency, queue_wait, model_time);
        rejected completions return (False, 0, 0, 0)."""
        with self._lock:
            if self.detached:
                return False, -1.0, 0.0, 0.0
            if req_id in self._done:
                self._duplicates += 1
                return False, 0.0, 0.0, 0.0
            pending = self._pending.get(req_id)
            if pending is None:
                self._duplicates += 1
                return False, 0.0, 0.0, 0.0
            now = time.time()
            latency = max(0.0, now - pending.submit_ts)
            del self._pending[req_id]
            self._done[req_id] = _Done(payload, worker, latency)
            self._completed += 1
            self._undelivered += 1
            self._latencies.append(latency)
            wait = model = 0.0
            # the WINNING lease's timestamps: a redelivered request
            # attributes its wait up to the lease that answered
            if pending.lease_ts:
                wait = max(0.0, pending.lease_ts - pending.submit_ts)
                model = max(0.0, now - pending.lease_ts)
                self._queue_waits.append(wait)
                self._model_times.append(model)
            return True, latency, wait, model

    def poll(self, req_id: str) -> Tuple[bool, bytes, int, float]:
        with self._lock:
            if self.detached:
                return False, b"", -2, 0.0
            done = self._done.get(req_id)
            if done is None:
                return False, b"", -1, 0.0
            if not done.delivered:
                done.delivered = True
                self._undelivered -= 1
            return True, done.payload, done.worker[1], done.latency_s

    def requeue_expired(self, now: float, lease_timeout: float
                        ) -> List[str]:
        """Watchdog body. The scan runs on a snapshot OUTSIDE the lock
        (the PR 12 _monitor_heartbeats pattern — a full lease-table
        scan must not stall the admission hot path); the requeue
        re-checks each candidate under the lock, so a completion or
        re-lease that raced the scan wins."""
        with self._lock:
            snapshot = list(self._pending.values())
        expired = [
            p.req_id for p in sorted(snapshot, key=lambda p: -p.seq)
            if p.worker is not None
            and now - p.lease_ts > lease_timeout
        ]
        if not expired:
            return []
        requeued: List[str] = []
        with self._lock:
            # newest-first appendleft: the batch lands at each tenant
            # queue's front in its original submit order
            for req_id in expired:
                pending = self._pending.get(req_id)
                if pending is None or pending.worker is None:
                    continue  # completed / already requeued: stale scan
                if now - pending.lease_ts <= lease_timeout:
                    continue  # re-leased since the snapshot
                self._requeue_locked(pending)
                requeued.append(req_id)
        return requeued

    def requeue_worker(self, worker: Tuple[str, int],
                       max_incarnation: Optional[int] = None
                       ) -> List[str]:
        """Relinquish / incarnation reclaim: requeue this worker's
        leases, oldest first (front of their tenant queues)."""
        with self._lock:
            victims = [
                p for p in self._pending.values()
                if p.worker == worker
                and (max_incarnation is None
                     or p.incarnation <= max_incarnation)
            ]
            # front-requeue newest-first so each tenant queue ends up
            # in original submit order
            for pending in sorted(victims, key=lambda p: -p.seq):
                self._requeue_locked(pending)
        return [p.req_id for p in victims]

    def _requeue_locked(self, pending: _Pending):
        pending.worker = None
        pending.incarnation = -1
        pending.lease_ts = 0.0
        pending.redeliveries += 1
        self._redelivered += 1
        # front of its tenant queue: a redelivered request is that
        # tenant's oldest outstanding work, and its latency clock has
        # been running all along
        self._enqueue_locked(pending, front=True)

    def gc_done(self, now: float, ttl: float) -> int:
        """Evict DELIVERED responses older than the TTL (undelivered
        ones are kept — their poller may still come). Runs on the
        watchdog cadence; the duplicate-reject guarantee holds for any
        retry inside the TTL because the entry is still present."""
        with self._lock:
            snapshot = list(self._done.items())
        stale = [
            req_id for req_id, done in snapshot
            if done.delivered and now - done.done_ts > ttl
        ]
        if not stale:
            return 0
        evicted = 0
        with self._lock:
            for req_id in stale:
                done = self._done.get(req_id)
                if done is None or not done.delivered:
                    continue
                del self._done[req_id]
                evicted += 1
            self._evicted += evicted
        return evicted

    def snapshot(self) -> Dict:
        """One consistent read for stats(): cheap copies under the
        lock, all derived math (percentiles, leased counts) outside."""
        with self._lock:
            return {
                "queue_depth": self._queued,
                "pending": list(self._pending.values()),
                "latencies": list(self._latencies),
                "queue_waits": list(self._queue_waits),
                "model_times": list(self._model_times),
                "submitted": self._submitted,
                "completed": self._completed,
                "rejected": self._rejected,
                "duplicates": self._duplicates,
                "redelivered": self._redelivered,
                "evicted": self._evicted,
                "undelivered": self._undelivered,
            }

    def quiesced(self) -> bool:
        """O(1): nothing queued, nothing leased, every stored response
        delivered. The plane's finished() ANDs this across shards."""
        with self._lock:
            return (
                not self._queued
                and not self._pending
                and self._undelivered == 0
            )


class _ShardsRef:
    """Lock-free publication cell for the live shard list (the
    atomic-reference idiom). Rebinding ``current`` is a single
    GIL-atomic reference store; hot-path readers snapshot it once and
    work on the copy — a reader that raced ``resize_shards`` onto the
    retired list finds every shard ``detached`` and retries, so stale
    snapshots are safe by construction and the per-request path never
    touches a plane-wide lock."""

    __slots__ = ("current",)

    def __init__(self, shards: List[RouterShard]):
        self.current = shards


class RequestRouter:
    """Hash-partitioned, fair-queued, lease-with-redelivery request
    plane. The facade keeps PR 11's public surface — submit / lease /
    complete / poll / seal / relinquish / stats / finished — while the
    state lives in N independent shards."""

    def __init__(self, max_queue: int = MAX_QUEUE,
                 lease_timeout: Optional[float] = None,
                 shards: Optional[int] = None,
                 done_ttl: float = DONE_TTL_S,
                 drr_quantum: int = DRR_QUANTUM):
        if lease_timeout is None:
            lease_timeout = float(
                os.getenv(ENV_LEASE_TIMEOUT, "") or DEFAULT_LEASE_TIMEOUT
            )
        if shards is None:
            shards = int(
                os.getenv(ENV_ROUTER_SHARDS, "")
                or DEFAULT_ROUTER_SHARDS
            )
        self._max_queue = max(1, max_queue)
        self._lease_timeout = max(0.1, lease_timeout)
        self._done_ttl = max(0.05, done_ttl)
        self._quantum = max(1, drr_quantum)
        self._shards = _ShardsRef(self._build_shards(max(1, shards)))
        #: plane-level concerns: req-id minting, submit ordering, the
        #: incarnation table (reclaim must span shards), resize, and
        #: replica-reported stats. None of these sit on the per-request
        #: hot path's shard critical sections.
        self._admin_lock = threading.Lock()
        self._id_counter = itertools.count(1)
        self._seq_counter = itertools.count(1)
        self._lease_rr = itertools.count()
        #: (node_type, node_id) -> newest incarnation seen leasing
        self._incarnations: Dict[Tuple[str, int], int] = {}
        #: distinct tenants observed (capped; stats surface only)
        self._tenants: set = set()
        #: (node_type, node_id) -> replica-reported serve section off
        #: the delta-report plane (agent/status_reporter.py) — the
        #: 1k-replica answer to per-replica serve_stats polling
        self._replica_stats: Dict[Tuple[str, int], Dict] = {}
        #: counters carried over from shards retired by resize_shards
        self._carry: Dict[str, int] = {}
        self._sealed = threading.Event()
        self._drained_recorded = False
        self._watchdog: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def _build_shards(self, n: int) -> List[RouterShard]:
        per_shard = max(1, (self._max_queue + n - 1) // n)
        return [
            RouterShard(i, per_shard, drr_quantum=self._quantum)
            for i in range(n)
        ]

    @property
    def shard_count(self) -> int:
        return len(self._shards.current)

    def _route(self, req_id: str) -> RouterShard:
        shards = self._shards.current
        return shards[shard_for(req_id, len(shards))]

    # ------------------------------------------------------------ lifecycle

    def start(self):
        if self._watchdog is not None:
            return
        self._stop.clear()
        self._watchdog = threading.Thread(
            target=self._watchdog_loop, name="serve-lease-watchdog",
            daemon=True,
        )
        self._watchdog.start()

    def stop(self):
        self._stop.set()
        if self._watchdog is not None:
            self._watchdog.join(timeout=2.0)
            self._watchdog = None

    def _watchdog_loop(self):
        while not self._stop.wait(0.5):
            try:
                self.check_timeouts()
                self.gc_done()
            except Exception as e:  # pragma: no cover - defensive
                logger.warning("serve lease watchdog failed: %s", e)

    # ------------------------------------------------------------ admission

    def submit(self, payload: bytes, req_id: str = "",
               tenant: str = DEFAULT_TENANT,
               priority: int = DEFAULT_PRIORITY
               ) -> Tuple[bool, str, str]:
        """Admit one request; returns (accepted, req_id, reason).

        Rejections are explicit backpressure (reason "backpressure" /
        "sealed") or an id collision (reason "duplicate") — the caller
        decides whether to retry, never the router. ``tenant`` buys
        fair queuing against the other tenants of its priority class;
        ``priority`` picks the class (higher drains first)."""
        if not req_id:
            req_id = f"req-{next(self._id_counter)}"
        pending = _Pending(
            req_id, payload, tenant or DEFAULT_TENANT, int(priority),
            next(self._seq_counter),
        )
        while True:
            shard = self._route(req_id)
            accepted, reason, depth = shard.submit(
                pending, self._sealed.is_set()
            )
            if reason != "detached":
                break
        if tenant:
            with self._admin_lock:
                if len(self._tenants) < _TENANT_SET_CAP:
                    self._tenants.add(tenant)
        if not accepted:
            if reason == "backpressure":
                counter(
                    "dlrover_serve_rejected_total",
                    "Serve requests rejected by queue backpressure",
                ).inc()
            return False, req_id if reason != "duplicate" else req_id, \
                reason
        counter(
            "dlrover_serve_requests_total",
            "Serve requests admitted by the router",
        ).inc()
        gauge(
            "dlrover_serve_shard_queue_depth",
            "Serve requests queued awaiting a lease, per router shard",
            ["shard"],
        ).labels(shard=str(shard.index)).set(depth)
        return True, req_id, ""

    def seal(self):
        """No more submissions: the stream is ending. Workers observe
        the seal on their next lease and exit once the queue drains."""
        if self._sealed.is_set():
            return
        self._sealed.set()
        queued = sum(s.snapshot()["queue_depth"] for s in self._shards.current)
        record("serve.sealed", queued=queued)
        # a seal AFTER the last response was delivered is what drains
        # an idle stream — check here too, not just on complete/poll
        self._maybe_drained()

    # --------------------------------------------------------------- leases

    def lease(self, node_type: str, node_id: int, max_requests: int = 1,
              incarnation: int = -1
              ) -> Tuple[List[Tuple[str, bytes]], bool]:
        """Hand out up to ``max_requests`` queued requests to a worker.

        Continuous batching over shards: one rotated pass with
        non-blocking shard locks — whatever the reachable shards hold
        NOW rides, a contended shard is simply skipped (its work goes
        to whichever replica reaches it next). Returns
        ``(batch, sealed)``; an empty batch with sealed=True is the
        worker's signal to exit."""
        worker = (node_type, int(node_id))
        self._note_incarnation(worker, incarnation)
        want = max(1, max_requests)
        now = time.time()
        batch: List[Tuple[str, bytes]] = []
        shards = self._shards.current
        offset = next(self._lease_rr)
        for i in range(len(shards)):
            shard = shards[(offset + i) % len(shards)]
            got = shard.try_lease(
                want - len(batch), now, worker, incarnation
            )
            if got is None:
                continue  # contended: a partial batch never waits
            part, depth = got
            batch.extend(part)
            if part:
                gauge(
                    "dlrover_serve_shard_queue_depth",
                    "Serve requests queued awaiting a lease, per"
                    " router shard",
                    ["shard"],
                ).labels(shard=str(shard.index)).set(depth)
            if len(batch) >= want:
                break
        return batch, self._sealed.is_set()

    def _note_incarnation(self, worker: Tuple[str, int],
                          incarnation: int):
        """Plane-level incarnation table: a newer incarnation proves
        the older process dead — reclaim its leases on EVERY shard
        (cold path: once per replica restart)."""
        if incarnation < 0:
            return
        with self._admin_lock:
            prev = self._incarnations.get(worker, -1)
            if incarnation <= prev:
                return
            self._incarnations[worker] = incarnation
        if prev < 0:
            return
        reclaimed: List[str] = []
        for shard in self._shards.current:
            reclaimed.extend(shard.requeue_worker(
                worker, max_incarnation=incarnation - 1
            ))
        if reclaimed:
            self._note_redelivered(reclaimed, cause="incarnation",
                                   worker=worker)

    def complete(self, node_type: str, node_id: int, req_id: str,
                 payload: bytes) -> bool:
        """Store the response for ``req_id``; exactly-once: the first
        completion wins, duplicates and late ghosts (the request was
        redelivered to someone else after this worker's lease timed
        out, then THAT worker completed it) are rejected."""
        worker = (node_type, int(node_id))
        while True:
            accepted, latency, _wait, _model = self._route(
                req_id
            ).complete(worker, req_id, payload)
            if latency >= 0.0:
                break  # -1.0 marks a detached shard: re-route
        if not accepted:
            counter(
                "dlrover_serve_duplicates_total",
                "Duplicate serve completions rejected",
            ).inc()
            return False
        counter(
            "dlrover_serve_responses_total",
            "Serve responses stored (exactly-once completions)",
        ).inc()
        histogram(
            "dlrover_serve_latency_seconds",
            "Submit-to-response latency per request",
            buckets=_LATENCY_BUCKETS,
        ).observe(latency)
        self._maybe_drained()
        return True

    def poll(self, req_id: str) -> Tuple[bool, bytes, int, float]:
        """Response retrieval: (done, payload, worker_id, latency_s)."""
        while True:
            done, payload, worker_id, latency = self._route(
                req_id
            ).poll(req_id)
            if worker_id != -2:  # -2 marks a detached shard: re-route
                break
        if done:
            self._maybe_drained()
        return done, payload, worker_id, latency

    # ----------------------------------------------------------- redelivery

    def check_timeouts(self) -> int:
        """Watchdog body: requeue leases older than the timeout (their
        worker is presumed dead — SIGKILL leaves no goodbye). The scan
        runs per shard on an outside-the-lock snapshot."""
        now = time.time()
        expired: List[str] = []
        for shard in self._shards.current:
            expired.extend(
                shard.requeue_expired(now, self._lease_timeout)
            )
        if expired:
            self._note_redelivered(expired, cause="lease_timeout")
        return len(expired)

    def gc_done(self) -> int:
        """Evict delivered done-store entries past the TTL (the PR 11
        leak: _done grew for the life of the stream)."""
        now = time.time()
        evicted = 0
        for shard in self._shards.current:
            evicted += shard.gc_done(now, self._done_ttl)
        if evicted:
            counter(
                "dlrover_serve_done_evicted_total",
                "Delivered done-store entries GC'd after the TTL",
            ).inc(evicted)
        return evicted

    def relinquish(self, node_type: str, node_id: int) -> int:
        """Drain handoff: a rotating worker returns its unprocessed
        leases NOW instead of waiting out the watchdog (the serving
        analog of relinquish_shards) — across every shard it leased
        from."""
        worker = (node_type, int(node_id))
        requeued: List[str] = []
        for shard in self._shards.current:
            requeued.extend(shard.requeue_worker(worker))
        record(
            "serve.relinquished", node_type=node_type, node_id=node_id,
            requeued=len(requeued),
        )
        if requeued:
            self._note_redelivered(requeued, cause="relinquish",
                                   worker=worker)
        return len(requeued)

    def _note_redelivered(self, req_ids: List[str], cause: str,
                          worker: Optional[Tuple[str, int]] = None):
        counter(
            "dlrover_serve_redeliveries_total",
            "Serve requests requeued after a lease loss", ["cause"],
        ).labels(cause=cause).inc(len(req_ids))
        record(
            "serve.request_redelivered", cause=cause,
            count=len(req_ids), req_ids=sorted(req_ids)[:16],
            node_type=worker[0] if worker else "",
            node_id=worker[1] if worker else -1,
        )

    # ------------------------------------------------------------ resharding

    def resize_shards(self, n: int) -> int:
        """Re-partition the plane to ``n`` shards, live. The whole
        plane freezes for the move (every old shard lock held), then
        every record re-routes by the new hash: in-flight leases keep
        their worker/incarnation/lease-clock, queued requests keep
        their global submit order, the done-store keeps its exactly-
        once history. An op that raced the swap finds its old shard
        ``detached`` and retries against the new list."""
        n = max(1, int(n))
        with self._admin_lock:
            old = self._shards.current
            if n == len(old):
                return n
            for shard in old:
                shard._lock.acquire()
            try:
                new = self._build_shards(n)
                moved_pending = moved_done = 0
                queued: List[_Pending] = []
                for shard in old:
                    shard.detached = True
                    for req_id, pending in shard._pending.items():
                        target = new[shard_for(req_id, n)]
                        target._pending[req_id] = pending
                        if pending.worker is None:
                            queued.append(pending)
                        moved_pending += 1
                    for req_id, done in shard._done.items():
                        target = new[shard_for(req_id, n)]
                        target._done[req_id] = done
                        if not done.delivered:
                            target._undelivered += 1
                        moved_done += 1
                    # latency windows redistribute round-robin: the
                    # merged percentile view in stats() is unchanged
                    for i, v in enumerate(shard._latencies):
                        new[i % n]._latencies.append(v)
                    for i, v in enumerate(shard._queue_waits):
                        new[i % n]._queue_waits.append(v)
                    for i, v in enumerate(shard._model_times):
                        new[i % n]._model_times.append(v)
                    # lifetime counters outlive their shard
                    self._carry["submitted"] = (
                        self._carry.get("submitted", 0)
                        + shard._submitted
                    )
                    self._carry["completed"] = (
                        self._carry.get("completed", 0)
                        + shard._completed
                    )
                    self._carry["rejected"] = (
                        self._carry.get("rejected", 0)
                        + shard._rejected
                    )
                    self._carry["duplicates"] = (
                        self._carry.get("duplicates", 0)
                        + shard._duplicates
                    )
                    self._carry["redelivered"] = (
                        self._carry.get("redelivered", 0)
                        + shard._redelivered
                    )
                    self._carry["evicted"] = (
                        self._carry.get("evicted", 0) + shard._evicted
                    )
                # queued work re-enqueues in global submit order, so
                # FIFO-within-tenant (and the front-requeue contract)
                # survive the move
                for pending in sorted(queued, key=lambda p: p.seq):
                    target = new[shard_for(pending.req_id, n)]
                    target._enqueue_locked(pending)
                self._shards.current = new
            finally:
                for shard in old:
                    shard._lock.release()
        record(
            "serve.shards_resized", old=len(old), new=n,
            moved_pending=moved_pending, moved_done=moved_done,
        )
        return n

    # ----------------------------------------------------- replica stats

    def note_replica_stats(self, node_type: str, node_id: int,
                           incarnation: int, fields: Dict):
        """A replica's serve section off the delta-report plane
        (``report_node_status`` — agent/status_reporter.py). At 1k
        replicas this replaces per-replica stats polling: the master
        already holds every replica's served/model-time numbers when
        stats() is read."""
        with self._admin_lock:
            self._replica_stats[(node_type, int(node_id))] = {
                "incarnation": incarnation,
                "ts": time.time(),
                **fields,
            }

    # -------------------------------------------------------------- reading

    @staticmethod
    def _percentile(values: List[float], q: float) -> float:
        if not values:
            return 0.0
        values = sorted(values)
        idx = min(len(values) - 1, int(round(q * (len(values) - 1))))
        return values[idx]

    def stats(self) -> Dict:
        shards = self._shards.current
        snaps = [s.snapshot() for s in shards]
        lat: List[float] = []
        waits: List[float] = []
        model: List[float] = []
        per_shard: Dict = {}
        with self._admin_lock:
            totals = dict(self._carry)
        depth = leased = 0
        for shard, snap in zip(shards, snaps):
            lat.extend(snap["latencies"])
            waits.extend(snap["queue_waits"])
            model.extend(snap["model_times"])
            depth += snap["queue_depth"]
            shard_leased = sum(
                1 for p in snap["pending"] if p.worker is not None
            )
            leased += shard_leased
            for key in ("submitted", "completed", "rejected",
                        "duplicates", "redelivered", "evicted"):
                totals[key] = totals.get(key, 0) + snap[key]
            per_shard[str(shard.index)] = {
                "queue_depth": snap["queue_depth"],
                "in_flight": shard_leased,
                "completed": snap["completed"],
            }
        now = time.time()
        with self._admin_lock:
            workers = len(self._incarnations)
            tenants = len(self._tenants)
            replicas = [
                r for r in self._replica_stats.values()
                if now - r["ts"] <= _REPLICA_STATS_TTL
            ]
        gauge(
            "dlrover_serve_queue_depth",
            "Serve requests queued awaiting a worker lease",
        ).set(depth)
        out = {
            "queue_depth": depth,
            "in_flight": leased,
            "submitted": totals.get("submitted", 0),
            "completed": totals.get("completed", 0),
            "rejected": totals.get("rejected", 0),
            "duplicates": totals.get("duplicates", 0),
            "redelivered": totals.get("redelivered", 0),
            "done_evicted": totals.get("evicted", 0),
            "workers": workers,
            "shards": len(shards),
            "tenants": tenants,
            "replicas_reporting": len(replicas),
            "replica_served": sum(
                int(r.get("served", 0)) for r in replicas
            ),
            "sealed": self._sealed.is_set(),
            "per_shard": per_shard,
        }
        out["p50_ms"] = round(self._percentile(lat, 0.50) * 1000.0, 3)
        out["p99_ms"] = round(self._percentile(lat, 0.99) * 1000.0, 3)
        out["queue_wait_p99_ms"] = round(
            self._percentile(waits, 0.99) * 1000.0, 3
        )
        out["model_time_p99_ms"] = round(
            self._percentile(model, 0.99) * 1000.0, 3
        )
        out["drained"] = self.finished()
        return out

    def finished(self) -> bool:
        """True once the stream is over: sealed, every admitted request
        answered, and every response delivered to a poller — the master
        run loop's serving-job termination condition. O(shards), not
        O(requests): each shard keeps queued/pending/undelivered
        counters instead of scanning its done-store."""
        if not self._sealed.is_set():
            return False
        return all(s.quiesced() for s in self._shards.current)

    def _maybe_drained(self):
        if self._drained_recorded or not self.finished():
            return
        with self._admin_lock:
            if self._drained_recorded:
                return
            self._drained_recorded = True
            totals = dict(self._carry)
        for snap in (s.snapshot() for s in self._shards.current):
            for key in ("completed", "redelivered"):
                totals[key] = totals.get(key, 0) + snap[key]
        record(
            "serve.drained", completed=totals.get("completed", 0),
            redelivered=totals.get("redelivered", 0),
        )
