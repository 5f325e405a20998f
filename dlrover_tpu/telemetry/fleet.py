"""Fleet observability plane: relay-carried metric roll-ups and the
in-master time-series store (ISSUE 17 tentpole, layers 2–3).

At 10k agents "what is fleet p99 step time right now" used to mean
scraping 10k per-process ``/metrics`` endpoints — the telemetry
aggregation wall every fleet-scale training system hits (the 100k-GPU
HSDP report, PAPERS.md). This module makes metrics ride the control
plane the reports already use:

* **HistogramSketch** — a mergeable log-bucketed histogram. Fixed
  bucket boundaries (powers of ``2**(1/8)``, ~9% relative resolution)
  mean merging two sketches is a sparse dict sum: associative,
  commutative, order-independent — exactly what a relay tier needs to
  pre-merge K agents' digests without losing quantile fidelity.
* **DigestCollector** — the process-local accumulation point. Hot
  sites call :func:`observe` / :func:`incr`; the StatusReporter folds
  :meth:`DigestCollector.compose` into its delta report under the
  PR 12 contract (compose-then-commit; a shed retry reuses the same
  payload; a failed forward re-merges into the next interval — no
  sample is ever dropped or double-counted).
* **merge_digest** — pure wire-dict merge the relay uses to pre-merge
  its K agents' digests into ONE summary per interval
  (``RelayBatchReport.digest``).
* **TimeSeriesStore** — bounded downsampling ring store in the master:
  raw per-ingest-interval points fold into 10 s buckets fold into 1 m
  buckets, all three tiers capped (``FLEET_MEM_MB``), so a
  week-long job cannot grow master memory.
* **FleetAggregator** — hangs off the ingest plane: folds every relay
  digest (or direct per-agent digest) into the store, keeps per-host
  step breakdowns from the report sections it already sees, answers
  ``/fleet`` + ``/fleet.json`` (fleet quantiles, per-host breakdown,
  top-k stragglers) with ZERO agent scrapes.
* **SLOEvaluator** — declarative objectives
  (``DLROVER_TPU_SLO="step_p99_ms<=500;goodput_percent>=95"``)
  evaluated on the ingest cadence; journals ``slo.violated`` /
  ``slo.recovered`` with attributed cause (goodput ledger badput for
  training, queue-wait vs model-time for serving) and feeds the
  ServingAutoScaler the attributed-latency signal (ROADMAP 3b).
"""

import math
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from dlrover_tpu.telemetry.journal import record

__all__ = [
    "HistogramSketch",
    "DigestCollector",
    "TimeSeriesStore",
    "FleetAggregator",
    "SLOEvaluator",
    "merge_digest",
    "observe",
    "incr",
    "default_collector",
    "set_default_collector",
    "ENV_FLEET_DIGEST",
    "ENV_FLEET_TOPK",
    "ENV_SLO",
]

#: digest folding on agents/relays; "0"/"off" turns the roll-up plane
#: off and reports travel exactly as PR 12 shipped them
ENV_FLEET_DIGEST = "DLROVER_TPU_FLEET_DIGEST"

#: hard cap (MiB) on the master's time-series store across all tiers
FLEET_MEM_MB = 16

#: declarative SLOs, ";"-separated ``name<=value`` / ``name>=value``
ENV_SLO = "DLROVER_TPU_SLO"

#: cap on the ``/fleet`` per-host breakdown: the top-k hosts by the
#: sort metric (furthest behind the fleet-max step, then stalest)
#: travel; the rest fold into an ``omitted_hosts`` count so a
#: 10k-agent fleet cannot emit a multi-MB response (ISSUE 19 satellite)
ENV_FLEET_TOPK = "DLROVER_TPU_FLEET_TOPK"
DEFAULT_FLEET_TOPK = 16


def fleet_topk() -> int:
    try:
        return int(
            os.environ.get(ENV_FLEET_TOPK, "") or DEFAULT_FLEET_TOPK
        )
    except ValueError:
        return DEFAULT_FLEET_TOPK


def digests_enabled() -> bool:
    return os.environ.get(ENV_FLEET_DIGEST, "1").lower() not in (
        "0", "off", "false",
    )


# ------------------------------------------------------------------ sketch

#: bucket base: 2**(1/8) per bucket => worst-case quantile error ~4.4%
#: (half a bucket in log space) — ample for SLO evaluation, and 8
#: buckets per octave keeps a step-time distribution to a few dozen
#: sparse entries
_LOG_BASE = math.log(2.0) / 8.0
#: index clamp: covers ~2**-32 .. 2**32 seconds — anything outside is
#: measurement garbage, parked in the edge bucket
_IDX_MIN = -256
_IDX_MAX = 256


def _bucket_of(value: float) -> int:
    if value <= 0.0:
        return _IDX_MIN
    idx = int(math.floor(math.log(value) / _LOG_BASE))
    return max(_IDX_MIN, min(_IDX_MAX, idx))


def _bucket_upper(idx: int) -> float:
    """Upper edge of bucket ``idx`` — the quantile estimate (an upper
    bound, so an SLO can never pass on an underestimate)."""
    if idx <= _IDX_MIN:
        return 0.0
    return math.exp((idx + 1) * _LOG_BASE)


class HistogramSketch:
    """Sparse fixed-bucket log histogram; merge = dict sum.

    Not thread-safe by itself — the DigestCollector serializes access;
    master-side merges happen under the FleetAggregator lock."""

    __slots__ = ("buckets", "count", "sum", "min", "max")

    def __init__(self):
        self.buckets: Dict[int, int] = {}
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, value: float):
        value = float(value)
        idx = _bucket_of(value)
        self.buckets[idx] = self.buckets.get(idx, 0) + 1
        self.count += 1
        self.sum += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def merge(self, other: "HistogramSketch"):
        for idx, n in other.buckets.items():
            self.buckets[idx] = self.buckets.get(idx, 0) + n
        self.count += other.count
        self.sum += other.sum
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)

    def quantile(self, q: float) -> float:
        """Upper-edge estimate of quantile ``q`` in [0, 1]; exact min
        and max at the extremes (they are tracked exactly)."""
        if self.count <= 0:
            return 0.0
        if q <= 0.0:
            return self.min
        if q >= 1.0:
            return self.max
        rank = q * self.count
        seen = 0
        for idx in sorted(self.buckets):
            seen += self.buckets[idx]
            if seen >= rank:
                return min(_bucket_upper(idx), self.max)
        return self.max

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    # -------------------------------------------------------------- wire

    def to_wire(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "b": {str(i): n for i, n in self.buckets.items()},
            "n": self.count,
            "s": round(self.sum, 9),
        }
        if self.count:
            out["mn"] = round(self.min, 9)
            out["mx"] = round(self.max, 9)
        return out

    @classmethod
    def from_wire(cls, doc: Dict[str, Any]) -> "HistogramSketch":
        sk = cls()
        if not isinstance(doc, dict):
            return sk
        for key, n in (doc.get("b") or {}).items():
            try:
                sk.buckets[int(key)] = int(n)
            except (ValueError, TypeError):
                continue
        sk.count = int(doc.get("n", 0) or 0)
        sk.sum = float(doc.get("s", 0.0) or 0.0)
        sk.min = float(doc.get("mn", math.inf))
        sk.max = float(doc.get("mx", -math.inf))
        return sk

    def approx_bytes(self) -> int:
        # ~12 bytes per sparse bucket entry + fixed header; the store's
        # memory cap sums these
        return 48 + 12 * len(self.buckets)


def merge_digest(into: Dict, add: Dict) -> Dict:
    """Merge wire digest ``add`` into wire digest ``into`` (mutates and
    returns ``into``). Pure dict arithmetic so relays pre-merge without
    building sketch objects; associative and commutative by
    construction. Malformed entries are dropped, never raised on — a
    bad digest from one agent must not poison the relay's interval."""
    if not isinstance(add, dict):
        return into
    counters = into.setdefault("c", {})
    for name, delta in (add.get("c") or {}).items():
        try:
            counters[name] = counters.get(name, 0) + int(delta)
        except (ValueError, TypeError):
            continue
    hists = into.setdefault("h", {})
    for name, doc in (add.get("h") or {}).items():
        if not isinstance(doc, dict):
            continue
        cur = hists.get(name)
        if cur is None:
            merged = HistogramSketch.from_wire(doc)
        else:
            merged = HistogramSketch.from_wire(cur)
            merged.merge(HistogramSketch.from_wire(doc))
        hists[name] = merged.to_wire()
    return into


# --------------------------------------------------------------- collector


class DigestCollector:
    """Process-local digest accumulation under the PR 12
    compose/commit contract.

    ``observe``/``incr`` fold into the open accumulation. ``compose``
    drains it into the in-flight buffer and returns the in-flight wire
    form — composing again before ``commit`` (relay forward failed,
    recompose next interval) RE-INCLUDES the in-flight samples plus
    anything new, so nothing is lost; a shed retry reuses the same
    payload so nothing is double-counted. ``commit`` clears in-flight
    once the upstream acked."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._sketches: Dict[str, HistogramSketch] = {}
        self._inflight: Dict[str, Any] = {}

    def observe(self, series: str, value: float):
        with self._lock:
            sk = self._sketches.get(series)
            if sk is None:
                sk = self._sketches[series] = HistogramSketch()
            sk.observe(value)

    def incr(self, name: str, delta: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(delta)

    def dirty(self) -> bool:
        with self._lock:
            return bool(
                self._counters or self._sketches or self._inflight
            )

    def compose(self) -> Dict[str, Any]:
        """Drain the open accumulation into in-flight; return the
        in-flight digest's wire form ({} when empty)."""
        with self._lock:
            pending: Dict[str, Any] = {}
            if self._counters:
                pending["c"] = dict(self._counters)
                self._counters.clear()
            if self._sketches:
                pending["h"] = {
                    name: sk.to_wire()
                    for name, sk in self._sketches.items()
                }
                self._sketches.clear()
            if pending:
                merge_digest(self._inflight, pending)
            # deep-ish copy: the caller's payload must not alias state
            # a later observe() could mutate
            return {
                "c": dict(self._inflight.get("c") or {}),
                "h": {
                    k: {
                        "b": dict(v.get("b") or {}),
                        **{f: v[f] for f in ("n", "s", "mn", "mx")
                           if f in v},
                    }
                    for k, v in (self._inflight.get("h") or {}).items()
                },
            } if self._inflight else {}

    def commit(self):
        """Upstream acked the composed digest: drop in-flight."""
        with self._lock:
            self._inflight = {}


_default_collector: Optional[DigestCollector] = None
_collector_lock = threading.Lock()


def default_collector() -> DigestCollector:
    global _default_collector
    with _collector_lock:
        if _default_collector is None:
            _default_collector = DigestCollector()
        return _default_collector


def set_default_collector(collector: Optional[DigestCollector]):
    global _default_collector
    with _collector_lock:
        _default_collector = collector


def observe(series: str, value: float):
    """Hot-site hook: fold one sample into the process digest. Cheap
    (one dict upsert under a process lock) and gated off entirely when
    roll-ups are disabled."""
    if digests_enabled():
        default_collector().observe(series, value)


def incr(name: str, delta: int = 1):
    if digests_enabled():
        default_collector().incr(name, delta)


# ------------------------------------------------------------------- store


#: downsampling tiers: (bucket seconds, default ring length). Raw
#: points arrive on the ingest cadence (~1 s); 1 min of raw, 1 h of
#: 10 s, 24 h of 1 m by default — all shrink under the memory cap.
_TIERS: Tuple[Tuple[str, int, int], ...] = (
    ("raw", 1, 120),
    ("10s", 10, 360),
    ("1m", 60, 1440),
)


class _SeriesTier:
    __slots__ = ("bucket_s", "ring", "open_ts", "open_sketch")

    def __init__(self, bucket_s: int, maxlen: int):
        self.bucket_s = bucket_s
        self.ring: deque = deque(maxlen=maxlen)
        self.open_ts: Optional[int] = None
        self.open_sketch: Optional[HistogramSketch] = None


class TimeSeriesStore:
    """Bounded downsampling ring store, one named series per sketch
    stream. Raw points merge into the open bucket of each tier; a
    bucket that closes rolls into the ring; rings are bounded and the
    WHOLE store honors a hard byte cap by evicting oldest-coarsest
    last (raw first — recent coarse history outlives old raw detail).
    Thread-safe."""

    def __init__(self, max_mb: float = FLEET_MEM_MB):
        self._max_bytes = int(max_mb * 1024 * 1024)
        self._lock = threading.Lock()
        self._series: Dict[str, Dict[str, _SeriesTier]] = {}

    def add(self, series: str, ts: float, sketch: HistogramSketch):
        with self._lock:
            tiers = self._series.get(series)
            if tiers is None:
                tiers = self._series[series] = {
                    name: _SeriesTier(bucket_s, maxlen)
                    for name, bucket_s, maxlen in _TIERS
                }
            for tier in tiers.values():
                bucket_ts = int(ts) - int(ts) % tier.bucket_s
                if tier.open_ts is None or bucket_ts > tier.open_ts:
                    if tier.open_sketch is not None:
                        tier.ring.append(
                            (tier.open_ts, tier.open_sketch)
                        )
                    tier.open_ts = bucket_ts
                    tier.open_sketch = HistogramSketch()
                if tier.open_sketch is not None:
                    tier.open_sketch.merge(sketch)
            self._enforce_cap_locked()

    def _enforce_cap_locked(self):
        size = self._bytes_locked()
        if size <= self._max_bytes:
            return
        # raw detail goes first, then 10s, then 1m — and round-robin
        # across series so one noisy series cannot evict the others
        for tier_name, _bucket, _maxlen in _TIERS:
            while size > self._max_bytes:
                evicted = False
                for tiers in self._series.values():
                    tier = tiers.get(tier_name)
                    if tier is not None and tier.ring:
                        _ts, sk = tier.ring.popleft()
                        size -= sk.approx_bytes() + 16
                        evicted = True
                        if size <= self._max_bytes:
                            return
                if not evicted:
                    break

    def _bytes_locked(self) -> int:
        total = 0
        for tiers in self._series.values():
            for tier in tiers.values():
                for _ts, sk in tier.ring:
                    total += sk.approx_bytes() + 16
                if tier.open_sketch is not None:
                    total += tier.open_sketch.approx_bytes() + 16
        return total

    def memory_bytes(self) -> int:
        with self._lock:
            return self._bytes_locked()

    def current(self, series: str) -> Optional[HistogramSketch]:
        """The open raw bucket's sketch merged with the last closed one
        — "now" for SLO evaluation without a full-window wait."""
        with self._lock:
            tiers = self._series.get(series)
            if tiers is None:
                return None
            raw = tiers["raw"]
            merged = HistogramSketch()
            if raw.ring:
                merged.merge(raw.ring[-1][1])
            if raw.open_sketch is not None:
                merged.merge(raw.open_sketch)
            return merged if merged.count else None

    def window(self, series: str, tier: str = "raw",
               points: int = 0) -> List[Tuple[int, HistogramSketch]]:
        with self._lock:
            tiers = self._series.get(series)
            if tiers is None or tier not in tiers:
                return []
            t = tiers[tier]
            out = list(t.ring)
            if t.open_sketch is not None:
                out.append((t.open_ts, t.open_sketch))
            return out[-points:] if points else out

    def series_names(self) -> List[str]:
        with self._lock:
            return sorted(self._series)


# -------------------------------------------------------------- aggregator


def _series_summary(sk: HistogramSketch) -> Dict[str, Any]:
    return {
        "count": sk.count,
        "mean_ms": round(sk.mean * 1e3, 3),
        "p50_ms": round(sk.quantile(0.5) * 1e3, 3),
        "p90_ms": round(sk.quantile(0.9) * 1e3, 3),
        "p99_ms": round(sk.quantile(0.99) * 1e3, 3),
        "max_ms": round((sk.max if sk.count else 0.0) * 1e3, 3),
    }


class _JobView:
    """One job's slice of the fleet plane: its own store, counters,
    host breakdown and source set. Created lazily on the first digest
    or report stamped with a non-default ``job_id`` — single-job
    deployments never allocate one. Guarded by the owning aggregator's
    lock (the store has its own)."""

    __slots__ = ("store", "counters", "sources", "hosts", "digests")

    def __init__(self):
        self.store = TimeSeriesStore()
        self.counters: Dict[str, int] = {}
        self.sources: Dict[str, float] = {}
        self.hosts: Dict[str, Dict[str, Any]] = {}
        self.digests = 0


def _capped_hosts(hosts: Dict[str, Dict[str, Any]]
                  ) -> Tuple[List[Dict[str, Any]], int]:
    """Top-k per-host breakdown (ISSUE 19 satellite): when the fleet
    exceeds ``DLROVER_TPU_FLEET_TOPK`` hosts, keep the ones furthest
    behind the fleet-max step (the ones an operator is looking for),
    stalest-first on ties, and report the rest as a count."""
    entries = [dict(h) for h in hosts.values()]
    topk = fleet_topk()
    omitted = 0
    if topk > 0 and len(entries) > topk:
        lead = max(
            (h["step"] for h in entries if h["step"] >= 0), default=-1
        )
        entries.sort(
            key=lambda h: (
                -(lead - h["step"]) if h["step"] >= 0 else 1,
                h["last_seen"], h["host"],
            )
        )
        omitted = len(entries) - topk
        entries = entries[:topk]
    entries.sort(key=lambda h: h["host"])
    return entries, omitted


class FleetAggregator:
    """Master-side consumer of the digest roll-ups.

    ``observe_digest`` folds one relay (or direct-agent) digest into
    the store; ``observe_report`` keeps the per-host breakdown from
    report sections the ingest plane already applies. Both are called
    on ingest shard executors — everything here takes the aggregator
    lock briefly and never calls out while holding it (lock-discipline:
    journal/SLO work happens after the merge, outside the lock).

    Since ISSUE 19 both entry points take a ``job`` namespace: the
    fleet-wide store/counters/hosts stay the merge across ALL jobs
    (every pre-job view and SLO built-in reads them unchanged), and a
    non-default job additionally folds into its own :class:`_JobView`
    so ``snapshot(job=...)``, per-job SLO evaluation and the Brain
    advisor attribute per job."""

    def __init__(self, store: Optional[TimeSeriesStore] = None,
                 slo: Optional["SLOEvaluator"] = None):
        self.store = store or TimeSeriesStore()
        self.slo = slo
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._sources: Dict[str, float] = {}
        self._hosts: Dict[str, Dict[str, Any]] = {}
        self._digests = 0
        self._jobs: Dict[str, _JobView] = {}

    # ---------------------------------------------------------- ingestion

    def _job_view_locked(self, job: str) -> Optional[_JobView]:
        if not job or job == "default":
            return None
        view = self._jobs.get(job)
        if view is None:
            view = self._jobs[job] = _JobView()
        return view

    def observe_digest(self, digest: Dict, source: str = "",
                       ts: Optional[float] = None,
                       job: str = "default"):
        if not digest or not isinstance(digest, dict):
            return
        now = ts if ts is not None else time.time()
        sketches = []
        for name, doc in (digest.get("h") or {}).items():
            if isinstance(name, str) and isinstance(doc, dict):
                sketches.append((name, HistogramSketch.from_wire(doc)))
        with self._lock:
            self._digests += 1
            if source:
                self._sources[source] = now
            for name, delta in (digest.get("c") or {}).items():
                try:
                    self._counters[name] = (
                        self._counters.get(name, 0) + int(delta)
                    )
                except (ValueError, TypeError):
                    continue
            view = self._job_view_locked(job)
            if view is not None:
                view.digests += 1
                if source:
                    view.sources[source] = now
                for name, delta in (digest.get("c") or {}).items():
                    try:
                        view.counters[name] = (
                            view.counters.get(name, 0) + int(delta)
                        )
                    except (ValueError, TypeError):
                        continue
        # stores have their own locks; never nest them under ours
        for name, sk in sketches:
            if sk.count:
                self.store.add(name, now, sk)
                if view is not None:
                    view.store.add(name, now, sk)
        if self.slo is not None:
            self.slo.evaluate(self)
            if view is not None:
                self.slo.evaluate(self, job=job)

    def observe_report(self, report):
        """Per-host breakdown from sections the report already carries
        (no extra wire cost): step progress and resource stats."""
        host = getattr(report, "host", "") or ""
        if not host:
            return
        job = getattr(report, "job_id", "default") or "default"
        with self._lock:
            view = self._job_view_locked(job)
            tables = [self._hosts]
            if view is not None:
                tables.append(view.hosts)
            for table in tables:
                entry = table.get(host)
                if entry is None:
                    entry = table[host] = {
                        "host": host, "step": -1, "step_ts": 0.0,
                        "cpu_percent": 0.0, "memory_mb": 0,
                        "last_seen": 0.0,
                    }
                entry["last_seen"] = float(
                    getattr(report, "timestamp", 0.0) or time.time()
                )
                if getattr(report, "has_step", False):
                    entry["step"] = int(report.step)
                    entry["step_ts"] = float(report.step_ts)
                if getattr(report, "has_resource", False):
                    entry["cpu_percent"] = float(report.cpu_percent)
                    entry["memory_mb"] = int(report.memory_mb)
                if getattr(report, "final", False):
                    table.pop(host, None)

    # ------------------------------------------------------------- views

    def jobs(self) -> List[str]:
        """Job namespaces with their own view (non-default only)."""
        with self._lock:
            return sorted(self._jobs)

    def store_for(self, job: Optional[str]) -> TimeSeriesStore:
        """The fleet-wide store, or one job's slice of it (an empty
        fresh store for an unknown job — absence reads as no data, not
        an error)."""
        if not job or job == "default":
            return self.store
        with self._lock:
            view = self._jobs.get(job)
        return view.store if view is not None else TimeSeriesStore()

    def stragglers(self, k: int = 5,
                   job: Optional[str] = None) -> List[Dict[str, Any]]:
        """Top-k hosts furthest behind the fleet-max step — the
        straggler view a 10k-agent job reads FIRST. ``job`` scopes the
        lead and the candidates to one job's hosts."""
        with self._lock:
            if job and job != "default":
                view = self._jobs.get(job)
                table = view.hosts if view is not None else {}
            else:
                table = self._hosts
            hosts = [dict(h) for h in table.values()
                     if h["step"] >= 0]
        if not hosts:
            return []
        lead = max(h["step"] for h in hosts)
        behind = sorted(
            hosts, key=lambda h: (h["step"], -h["step_ts"])
        )
        out = []
        for h in behind[:k]:
            h["behind"] = lead - h["step"]
            out.append(h)
        return out

    def snapshot(self, job: Optional[str] = None) -> Dict[str, Any]:
        """The ``/fleet.json`` document: quantiles per series, top-k
        per-host breakdown, stragglers, counters, SLO state.
        ``job=None`` is the fleet-wide merge across all jobs;
        ``job="a"`` scopes every section to that job's view."""
        if job and job != "default":
            return self._job_snapshot(job)
        series: Dict[str, Any] = {}
        for name in self.store.series_names():
            sk = self.store.current(name)
            if sk is not None:
                series[name] = _series_summary(sk)
        with self._lock:
            counters = dict(self._counters)
            hosts, omitted = _capped_hosts(self._hosts)
            sources = len(self._sources)
            digests = self._digests
            jobs = sorted(self._jobs)
        doc = {
            "series": series,
            "counters": counters,
            "hosts": hosts,
            "omitted_hosts": omitted,
            "stragglers": self.stragglers(),
            "sources": sources,
            "digests": digests,
            "store_bytes": self.store.memory_bytes(),
        }
        if jobs:
            doc["jobs"] = jobs
        if self.slo is not None:
            doc["slo"] = self.slo.status()
        return doc

    def _job_snapshot(self, job: str) -> Dict[str, Any]:
        with self._lock:
            view = self._jobs.get(job)
            if view is None:
                hosts: List[Dict[str, Any]] = []
                omitted = 0
                counters: Dict[str, int] = {}
                sources = 0
                digests = 0
            else:
                counters = dict(view.counters)
                hosts, omitted = _capped_hosts(view.hosts)
                sources = len(view.sources)
                digests = view.digests
        series: Dict[str, Any] = {}
        if view is not None:
            for name in view.store.series_names():
                sk = view.store.current(name)
                if sk is not None:
                    series[name] = _series_summary(sk)
        doc = {
            "job": job,
            "series": series,
            "counters": counters,
            "hosts": hosts,
            "omitted_hosts": omitted,
            "stragglers": self.stragglers(job=job),
            "sources": sources,
            "digests": digests,
            "store_bytes": (
                view.store.memory_bytes() if view is not None else 0
            ),
        }
        if self.slo is not None:
            doc["slo"] = self.slo.status(job=job)
        return doc


# --------------------------------------------------------------------- SLO


def _parse_objectives(spec: str) -> List[Tuple[str, str, float]]:
    """``"step_p99_ms<=500;goodput_percent>=95"`` ->
    ``[("step_p99_ms", "<=", 500.0), ...]``; malformed clauses are
    skipped (a typo'd objective must not take the master down)."""
    out = []
    for clause in (spec or "").split(";"):
        clause = clause.strip()
        if not clause:
            continue
        for op in ("<=", ">="):
            if op in clause:
                name, _, value = clause.partition(op)
                try:
                    out.append((name.strip(), op, float(value)))
                except ValueError:
                    pass
                break
    return out


class SLOEvaluator:
    """Declarative objective evaluation over the fleet plane.

    Signals are pluggable callables (the dist master registers
    goodput %, serve p99, and attribution providers); ``step_p99_ms``
    reads the aggregator's store directly. Each objective is a tiny
    state machine: crossing into violation journals ``slo.violated``
    (once) with the attributed cause; crossing back journals
    ``slo.recovered`` with the violation's duration. ``min_count``
    gates quantile objectives so a 3-sample blip cannot page anyone.

    Objective state is keyed per ``(job, objective)`` since ISSUE 19:
    ``evaluate(agg)`` drives the fleet-wide machines exactly as before,
    ``evaluate(agg, job="a")`` drives job "a"'s own machines against
    its :class:`_JobView` store — one job's violation never masks or
    clears another's. Signals registered with a ``job``-accepting
    callable serve both scopes; zero-arg signals stay fleet-only."""

    def __init__(self, spec: Optional[str] = None, min_count: int = 20):
        if spec is None:
            spec = os.environ.get(ENV_SLO, "")
        self.objectives = _parse_objectives(spec)
        self._min_count = min_count
        self._lock = threading.Lock()
        self._signals: Dict[str, Callable[..., Optional[float]]] = {}
        self._attribution: Dict[
            str, Callable[..., Dict[str, Any]]
        ] = {}
        #: signal/attribution callables that accept a ``job`` kwarg
        self._job_aware: Dict[str, bool] = {}
        #: (job-scoped) objective key -> violated_since_ts
        #: (absent = healthy)
        self._violated: Dict[str, float] = {}
        self._last_values: Dict[str, float] = {}

    @staticmethod
    def _accepts_job(fn) -> bool:
        import inspect

        try:
            sig = inspect.signature(fn)
        except (TypeError, ValueError):
            return False
        for p in sig.parameters.values():
            if p.kind is inspect.Parameter.VAR_KEYWORD:
                return True
            if p.name == "job":
                return True
        return False

    @staticmethod
    def _key(name: str, job: Optional[str]) -> str:
        return name if not job else f"{job}:{name}"

    def register_signal(self, name: str,
                        fn: Optional[
                            Callable[..., Optional[float]]
                        ] = None,
                        attribution: Optional[
                            Callable[..., Dict[str, Any]]
                        ] = None):
        """``fn=None`` keeps the built-in quantile value and attaches
        only the attribution provider (e.g. ``step_p99_ms`` reads the
        store but blames the goodput ledger). A callable accepting a
        ``job`` keyword serves per-job evaluation too."""
        with self._lock:
            if fn is not None:
                self._signals[name] = fn
                self._job_aware[f"s:{name}"] = self._accepts_job(fn)
            if attribution is not None:
                self._attribution[name] = attribution
                self._job_aware[f"a:{name}"] = self._accepts_job(
                    attribution
                )

    # ---------------------------------------------------------- evaluate

    def _value_of(self, name: str, aggregator: "FleetAggregator",
                  job: Optional[str] = None) -> Optional[float]:
        with self._lock:
            fn = self._signals.get(name)
            job_aware = self._job_aware.get(f"s:{name}", False)
        if fn is not None:
            if job and not job_aware:
                # fleet-only signal: this objective has no per-job
                # meaning — skip it in job scope rather than evaluate
                # the fleet value under a job's name
                return None
            try:
                return fn(job=job) if job_aware else fn()
            except Exception:
                return None
        # built-in: <series>_p99_ms / _p50_ms / _mean_ms over the
        # aggregator's current window (series name is seconds-valued)
        for suffix, q in (("_p99_ms", 0.99), ("_p90_ms", 0.9),
                          ("_p50_ms", 0.5)):
            if name.endswith(suffix):
                store = (
                    aggregator.store_for(job) if job
                    else aggregator.store
                )
                sk = store.current(name[: -len(suffix)])
                if sk is None or sk.count < self._min_count:
                    return None
                return sk.quantile(q) * 1e3
        return None

    def _attribute(self, name: str,
                   job: Optional[str] = None) -> Dict[str, Any]:
        with self._lock:
            fn = self._attribution.get(name)
            job_aware = self._job_aware.get(f"a:{name}", False)
        if fn is None:
            return {}
        try:
            out = fn(job=job) if (job and job_aware) else fn()
            return out if isinstance(out, dict) else {}
        except Exception:
            return {}

    def evaluate(self, aggregator: "FleetAggregator",
                 job: Optional[str] = None):
        now = time.time()
        for name, op, target in self.objectives:
            value = self._value_of(name, aggregator, job=job)
            if value is None:
                continue
            violated = (
                value > target if op == "<=" else value < target
            )
            key = self._key(name, job)
            with self._lock:
                self._last_values[key] = value
                was_since = self._violated.get(key)
                if violated and was_since is None:
                    self._violated[key] = now
                elif not violated and was_since is not None:
                    del self._violated[key]
            scope = {"job": job} if job else {}
            if violated and was_since is None:
                record(
                    "slo.violated", objective=name, op=op,
                    target=target, value=round(value, 3),
                    **scope, **self._attribute(name, job=job),
                )
            elif not violated and was_since is not None:
                record(
                    "slo.recovered", objective=name, target=target,
                    value=round(value, 3),
                    violated_s=round(now - was_since, 3),
                    **scope,
                )

    def violated(self, name: str, job: Optional[str] = None) -> bool:
        with self._lock:
            return self._key(name, job) in self._violated

    def status(self, job: Optional[str] = None) -> Dict[str, Any]:
        with self._lock:
            return {
                name: {
                    "op": op,
                    "target": target,
                    "value": self._last_values.get(
                        self._key(name, job)
                    ),
                    "violated": self._key(name, job) in self._violated,
                    "violated_since": self._violated.get(
                        self._key(name, job)
                    ),
                }
                for name, op, target in self.objectives
            }
