"""Explainable resource advisor: per-job telemetry -> journaled plans.

The Brain archive (brain/client.py) learns across RUNS; this module
closes the loop WITHIN a run. The advisor is a master-side observer
over the job-scoped telemetry plane (ISSUE 19): each job's goodput
account (telemetry/goodput.py), its fleet view — straggler scores,
HBM/CPU digest series, SLO state (telemetry/fleet.py) — and the
quarantine verdicts. On a cadence it evaluates three rules and
journals every conclusion as an *evidence chain*, so a human reading
``dump --kind brain`` can replay exactly why a plan was (or was not)
proposed:

  ``shrink_badput``      a job burning more than ``BADPUT_PCT``
                         percent of its wall clock in ckpt_stall +
                         rendezvous is
                         over-provisioned for its I/O — fewer hosts
                         stall less; propose shrink by one node unit.
  ``grow_scaling``       a job at/above ``GROW_PCT`` percent
                         goodput, straggler-free, whose per-worker
                         step rate has not degraded as workers joined
                         (the step-time curve still scales) earns one
                         more node unit.
  ``reclaim_quarantine`` a quarantined host still reporting telemetry
                         holds capacity the job can no longer trust;
                         propose reclaiming its node.

Every ``brain.plan_proposed`` event carries the rule fired, the metric
values it read, the observation window, and the expected goodput
delta. The advisor is SHADOW by default (``DLROVER_TPU_BRAIN=observe``
— propose and journal, touch nothing). ``advise`` additionally feeds
grow/shrink plans for the master's own job into
``JobAutoScaler.manual_scale``, which applies the existing validity
guards (node-unit alignment, min/max clamps) before any real scale
plan executes; the outcome lands as ``brain.plan_adopted`` or
``brain.plan_rejected`` with the reason. ``off`` disables the cadence
entirely.

The advisor owns no thread: the master's run loop calls
``maybe_step()`` each beat and the advisor rate-limits itself to
``INTERVAL_S`` seconds, with a per-(job, action) cooldown
(``COOLDOWN_S``) so a persistent condition journals one proposal,
not one per beat.
"""

import os
import time
from typing import Any, Callable, Dict, List, Optional

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import record
from dlrover_tpu.telemetry.goodput import Phase

ENV_BRAIN = "DLROVER_TPU_BRAIN"

#: seconds between evaluation passes
INTERVAL_S = 30.0
#: stall % of wall clock that triggers ``shrink_badput``
BADPUT_PCT = 25.0
#: goodput % floor for ``grow_scaling``
GROW_PCT = 90.0
#: seconds between proposals per (job, action)
COOLDOWN_S = 120.0

MODE_OFF = "off"
MODE_OBSERVE = "observe"
MODE_ADVISE = "advise"

#: a grow proposal requires the latest per-worker step rate to retain
#: at least this fraction of the best observed — below it the curve
#: has flattened and another unit buys mostly rendezvous time
_SCALING_RETENTION = 0.9


def advisor_mode() -> str:
    """``DLROVER_TPU_BRAIN`` -> off | observe | advise (default
    observe: shadow proposals are free and make incidents legible)."""
    raw = os.getenv(ENV_BRAIN, MODE_OBSERVE).strip().lower()
    if raw in ("", MODE_OBSERVE, "shadow"):
        return MODE_OBSERVE
    if raw in (MODE_ADVISE, "act", "active"):
        return MODE_ADVISE
    return MODE_OFF


class ResourceAdvisor:
    """Cadenced per-job rule evaluation over the fleet/goodput planes.

    Collaborators are duck-typed so tests drive the advisor with
    synthetic aggregators: ``fleet`` needs ``jobs()/stragglers(job=)/
    snapshot(job=)``, ``goodput`` needs ``jobs()/summary(job=)``,
    ``speed_monitors_fn`` returns ``{job: SpeedMonitor}``,
    ``quarantine`` needs ``quarantined_hosts()``, ``scale_fn`` is
    ``JobAutoScaler.manual_scale`` (advise mode only).
    """

    def __init__(self, fleet=None, goodput=None,
                 speed_monitors_fn: Optional[Callable] = None,
                 quarantine=None,
                 scale_fn: Optional[Callable[[int], bool]] = None,
                 local_job: str = "default", node_unit: int = 1,
                 mode: Optional[str] = None,
                 interval: Optional[float] = None,
                 now_fn: Callable[[], float] = time.time):
        self._fleet = fleet
        self._goodput = goodput
        self._speed_monitors_fn = speed_monitors_fn
        self._quarantine = quarantine
        self._scale_fn = scale_fn
        self._local_job = local_job or "default"
        self._node_unit = max(1, int(node_unit or 1))
        self.mode = mode if mode is not None else advisor_mode()
        self.interval = (
            float(interval) if interval is not None else INTERVAL_S
        )
        self._now = now_fn
        self._last_step = 0.0
        self._last_proposed: Dict[Any, float] = {}  # (job, action) -> ts
        # (ts, workers, per-worker step rate) per job: the grow rule's
        # scaling-curve memory
        self._speed_hist: Dict[str, List] = {}
        self._history: List[Dict[str, Any]] = []
        self._started = False

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        if self._started or self.mode == MODE_OFF:
            return
        self._started = True
        record(
            "brain.advisor_started",
            mode=self.mode, interval_s=self.interval,
            badput_pct=BADPUT_PCT, grow_pct=GROW_PCT,
            node_unit=self._node_unit, job=self._local_job,
        )

    def maybe_step(self, now: Optional[float] = None) -> None:
        """Run-loop hook: evaluates at most once per interval."""
        if self.mode == MODE_OFF:
            return
        now = self._now() if now is None else now
        if now - self._last_step < self.interval:
            return
        self._last_step = now
        try:
            self.step(now=now)
        except Exception as e:
            # advisory plane: a rule crash must never take the master
            # down with it
            logger.warning("brain advisor step failed: %s", e)

    def history(self) -> List[Dict[str, Any]]:
        return list(self._history)

    # ---------------------------------------------------------- evaluation

    def _jobs(self) -> List[str]:
        jobs = {self._local_job}
        if self._goodput is not None:
            jobs.update(self._goodput.jobs())
        if self._fleet is not None:
            jobs.update(self._fleet.jobs())
        return sorted(jobs)

    def step(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        """One full evaluation pass; returns the proposals it made."""
        now = self._now() if now is None else now
        proposals: List[Dict[str, Any]] = []
        monitors = (
            self._speed_monitors_fn() if self._speed_monitors_fn else {}
        )
        for job in self._jobs():
            self._observe_speed(job, monitors.get(job), now)
            summary = (
                self._goodput.summary(job=job).get("job") or {}
                if self._goodput is not None else {}
            )
            for plan in (
                self._rule_shrink_badput(job, summary, monitors, now),
                self._rule_grow_scaling(job, summary, now),
            ):
                if plan is not None:
                    proposals.append(plan)
        proposals.extend(self._rule_reclaim_quarantine(now))
        for plan in proposals:
            self._propose(plan, now)
        return proposals

    def _observe_speed(self, job: str, monitor, now: float) -> None:
        if monitor is None:
            return
        try:
            workers = len(monitor.running_workers) \
                or monitor._target_worker_num
            speed = float(monitor.running_speed())
        except Exception:
            return
        if workers <= 0 or speed <= 0:
            return
        hist = self._speed_hist.setdefault(job, [])
        hist.append((now, workers, speed / workers))
        del hist[:-16]  # a bounded curve is all the rule reads

    # ------------------------------------------------------------- rules

    def _rule_shrink_badput(self, job: str, summary: Dict,
                            monitors: Dict, now: float):
        wall = float(summary.get("wall_s") or 0.0)
        if not summary.get("procs") or wall <= 0:
            return None
        badput = summary.get("badput_s") or {}
        ckpt_stall = float(badput.get(Phase.CKPT_STALL, 0.0))
        rendezvous = float(badput.get(Phase.RENDEZVOUS, 0.0))
        stall_pct = 100.0 * (ckpt_stall + rendezvous) / wall
        if stall_pct <= BADPUT_PCT:
            return None
        workers = self._workers_of(job, monitors, summary)
        return {
            "job": job,
            "action": "shrink",
            "rule": "shrink_badput",
            "target_nodes": max(workers - self._node_unit, 0),
            "node_unit": self._node_unit,
            # reclaiming the stalled fraction is the ceiling on the
            # goodput this shrink can win back
            "expected_goodput_delta": round(stall_pct, 2),
            "evidence": {
                "window_s": round(wall, 3),
                "ckpt_stall_s": round(ckpt_stall, 3),
                "rendezvous_s": round(rendezvous, 3),
                "stall_pct": round(stall_pct, 2),
                "threshold_pct": BADPUT_PCT,
                "goodput_percent": summary.get("goodput_percent"),
                "workers": workers,
            },
        }

    def _rule_grow_scaling(self, job: str, summary: Dict, now: float):
        goodput_pct = float(summary.get("goodput_percent") or 0.0)
        if not summary.get("procs") or goodput_pct < GROW_PCT:
            return None
        # the fleet's straggler view lists every host (the lead reads
        # behind=0) — only hosts actually trailing the lead park a grow
        stragglers = [
            s for s in (
                self._fleet.stragglers(job=job)
                if self._fleet is not None else []
            )
            if (s.get("behind") or 0) > 0
        ]
        if stragglers:
            return None
        hist = self._speed_hist.get(job) or []
        if len(hist) < 2:
            return None  # no curve yet: nothing to extrapolate from
        best_rate = max(r for _, _, r in hist[:-1])
        _, workers, last_rate = hist[-1]
        if best_rate <= 0 or last_rate < _SCALING_RETENTION * best_rate:
            return None
        retention = last_rate / best_rate
        return {
            "job": job,
            "action": "grow",
            "rule": "grow_scaling",
            "target_nodes": workers + self._node_unit,
            "node_unit": self._node_unit,
            # the new unit trains at the observed per-worker rate
            # discounted by the curve's retention: expressed as the
            # job-level goodput-seconds gained per wall second, in %
            "expected_goodput_delta": round(
                goodput_pct * retention * self._node_unit
                / max(workers, 1), 2
            ),
            "evidence": {
                "window_s": round(
                    hist[-1][0] - hist[0][0], 3
                ),
                "goodput_percent": goodput_pct,
                "threshold_pct": GROW_PCT,
                "per_worker_rate": round(last_rate, 6),
                "best_per_worker_rate": round(best_rate, 6),
                "scaling_retention": round(retention, 4),
                "stragglers": 0,
                "workers": workers,
            },
        }

    def _rule_reclaim_quarantine(self, now: float) -> List[Dict]:
        if self._quarantine is None or self._fleet is None:
            return []
        quarantined = set(self._quarantine.quarantined_hosts())
        if not quarantined:
            return []
        out = []
        for job in self._jobs():
            doc = self._fleet.snapshot(job=job) or {}
            summary = (
                self._goodput.summary(job=job).get("job") or {}
                if self._goodput is not None else {}
            )
            wall = float(summary.get("wall_s") or 0.0)
            restart_s = float(
                (summary.get("badput_s") or {}).get(Phase.RESTART, 0.0)
            )
            for entry in doc.get("hosts") or []:
                host = entry.get("host")
                if host not in quarantined:
                    continue
                out.append({
                    "job": job,
                    "action": "reclaim",
                    "rule": "reclaim_quarantine",
                    "host": host,
                    "node_unit": self._node_unit,
                    # the restart badput this job already paid is the
                    # measured cost of keeping untrusted capacity
                    "expected_goodput_delta": round(
                        100.0 * restart_s / wall, 2
                    ) if wall > 0 else 0.0,
                    "evidence": {
                        "window_s": round(wall, 3),
                        "quarantined": True,
                        "still_reporting": True,
                        "last_seen": entry.get("last_seen"),
                        "restart_badput_s": round(restart_s, 3),
                        "faults": summary.get("faults"),
                    },
                })
        return out

    def _workers_of(self, job: str, monitors: Dict,
                    summary: Dict) -> int:
        monitor = monitors.get(job)
        if monitor is not None:
            try:
                n = len(monitor.running_workers) \
                    or monitor._target_worker_num
                if n:
                    return int(n)
            except Exception:
                pass
        return int(summary.get("nodes") or 0)

    # ----------------------------------------------------------- proposal

    def _propose(self, plan: Dict[str, Any], now: float) -> None:
        key = (plan["job"], plan["action"])
        last = self._last_proposed.get(key, 0.0)
        if now - last < COOLDOWN_S:
            return
        self._last_proposed[key] = now
        self._history.append(plan)
        del self._history[:-64]
        record(
            "brain.plan_proposed",
            job=plan["job"], action=plan["action"], rule=plan["rule"],
            mode=self.mode,
            expected_goodput_delta=plan["expected_goodput_delta"],
            target_nodes=plan.get("target_nodes"),
            host=plan.get("host"),
            **{f"evidence_{k}": v
               for k, v in plan["evidence"].items()},
        )
        if self.mode != MODE_ADVISE:
            return
        self._actuate(plan)

    def _actuate(self, plan: Dict[str, Any]) -> None:
        """advise mode: feed grow/shrink for OUR job into the scaler's
        guarded path; everything else is journaled as rejected with
        the reason, so the advise-mode audit trail is complete."""
        job, action = plan["job"], plan["action"]
        if action not in ("grow", "shrink"):
            record(
                "brain.plan_rejected", job=job, action=action,
                rule=plan["rule"], reason="no_actuator",
            )
            return
        if job != self._local_job:
            # this master only owns its own job's scale plans; a
            # sibling job's proposal is advice for ITS master
            record(
                "brain.plan_rejected", job=job, action=action,
                rule=plan["rule"], reason="job_not_local",
            )
            return
        if self._scale_fn is None:
            record(
                "brain.plan_rejected", job=job, action=action,
                rule=plan["rule"], reason="no_scaler",
            )
            return
        target = int(plan.get("target_nodes") or 0)
        try:
            ok = bool(self._scale_fn(target))
        except Exception as e:
            logger.warning("brain plan actuation failed: %s", e)
            ok = False
        if ok:
            record(
                "brain.plan_adopted", job=job, action=action,
                rule=plan["rule"], target_nodes=target,
            )
        else:
            record(
                "brain.plan_rejected", job=job, action=action,
                rule=plan["rule"], reason="scaler_declined",
            )


__all__ = [
    "ResourceAdvisor",
    "advisor_mode",
    "ENV_BRAIN",
    "MODE_OFF",
    "MODE_OBSERVE",
    "MODE_ADVISE",
]
