"""Worker-side step-progress hang detection.

Parity reference: atorch/atorch/fault_tolerance/hanging_detector.py:86
(HangingDetector judges "hung" from relative step time vs the history it
has seen) and dlrover/python/master/node/dist_job_manager.py:662 (the
master-side resource-stagnation signal).

TPU shape: the detector is a daemon thread inside the training process,
fed by ``ElasticTrainer.report_step``. The hang threshold adapts to the
observed cadence: ``max(min_timeout, multiplier * median(recent step
durations))`` — so a job whose steps take 0.1 s is flagged in seconds
while a job with 60 s steps is given minutes, with no per-model tuning.
It arms only after the first completed step, so the (minutes-long on a
cold cache) XLA compile of step 0 can never trip it.

On detection it calls ``report_fn(elapsed_seconds)`` once per stall; the
standard wiring reports a HANG-level failure to the master, which answers
the supervising agent's next heartbeat with a ``restart`` action — the
process is replaced without the node ever leaving RUNNING (the agent and
its heartbeat survive; only the training process is recycled).

Far below that threshold the same watchdog records every step that
comes *late*: one whose gap passes ``1.5 x median`` and the median plus
0.2 s, judged once five durations are known. While a step is late the
watchdog samples, each tick, where the main thread stands and what the
process did (CPU seconds, involuntary context switches, major faults,
the collector's full collections, its own lateness); when the step
arrives it closes one record: the span ``train.stall`` with tracing
on, and always the journal event ``step.stall``, a warning line and
``dlrover_step_stalls_total``. The loop's own thread pays one float
comparison a step for it; nothing is sampled until a step is late.
"""

import gc
import math
import re
import resource
import threading
import time
from collections import Counter, deque
from statistics import median
from typing import Callable, Dict, List, Optional, Tuple

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import counter, record, tracing

#: a step is late once its gap passes LATE_FACTOR x the median cadence
#: and the median plus LATE_MARGIN_S, judged from LATE_MIN_STEPS
#: durations on (warm-up feeds them)
LATE_FACTOR = 1.5
LATE_MARGIN_S = 0.2
LATE_MIN_STEPS = 5
#: the watchdog wakes every quarter of the median cadence, between
#: MIN_TICK_S and ``check_interval``: a stall of a few steps' length is
#: sampled several times
MIN_TICK_S = 0.05
#: stack samples kept of one stall, and frames of each (innermost)
MAX_STALL_SAMPLES = 8
STACK_FRAMES = 10

#: the packages whose frames say where the loop stood; a sample's
#: ``where`` is its innermost frame under one of them
_OWN = ("dlrover_tpu/", "yardstick/", "jax/", "grpc/")
_FRAME = re.compile(r'File "(.*)", line (\d+), in (.*)')


def _read_process() -> Tuple[float, int, int, int]:
    """(CPU seconds, involuntary context switches, major page faults,
    full collections) of this process so far."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    return (time.process_time(), usage.ru_nivcsw, usage.ru_majflt,
            gc.get_stats()[2]["collections"])


def _main_thread_frames() -> List[str]:
    """The main thread's innermost frames as ``file:line function``,
    outermost first, a path cut to start at its package."""
    from dlrover_tpu.telemetry import flight_recorder

    out = []
    for stack in flight_recorder.thread_stacks(
            main_only=True, limit=STACK_FRAMES):
        for line in stack["stack"]:
            m = _FRAME.search(line)
            if m is None:
                continue
            path = m.group(1)
            for root in _OWN:
                at = path.rfind("/" + root)
                if at >= 0:
                    path = path[at + 1:]
                    break
            else:
                path = "/".join(path.split("/")[-2:])
            out.append(f"{path}:{m.group(2)} {m.group(3)}")
    return out


def _where(frames: List[str]) -> Optional[str]:
    """The innermost frame under one of ``_OWN``, else the innermost."""
    for frame in reversed(frames):
        if frame.startswith(_OWN):
            return frame
    return frames[-1] if frames else None


class _Stall:
    """One late step as the watchdog saw it: where the main thread
    stood at each tick, and the process's readings at the first and
    the latest one. ``since`` is the ``record_step`` stamp the step is
    late after; one that had arrived before any tick saw it late has
    no sample and no reading."""

    def __init__(self, since: float, median_s: float, span=None,
                 attrs: Optional[Dict] = None):
        self.since = since
        self.median_s = median_s
        self.span, self.attrs = span, attrs
        self.stacks: List[List[str]] = []
        self.tick_late_s = 0.0
        self.first = self.last = None  # (clock, *_read_process())

    def sample(self, now: float, tick_late: float,
               arrived: bool = False) -> None:
        """One tick's readings, and while the step is still due (and
        fewer than MAX_STALL_SAMPLES are kept) the main thread's
        frames."""
        self.tick_late_s += tick_late
        self.last = (now,) + _read_process()
        if self.first is None:
            self.first = self.last
        if not arrived and len(self.stacks) < MAX_STALL_SAMPLES:
            frames = _main_thread_frames()
            if frames:
                self.stacks.append(frames)

    def fields(self) -> Dict:
        """What the samples say, under the record's names."""
        where, stack = None, []
        if self.stacks:
            where = Counter(
                _where(frames) for frames in self.stacks
            ).most_common(1)[0][0]
            stack = next(f for f in self.stacks if _where(f) == where)
        out = {"samples": len(self.stacks), "where": where,
               "stack": stack, "tick_late_s": round(self.tick_late_s, 4)}
        if self.first is not None:
            spent = [b - a for a, b in zip(self.first, self.last)]
            out.update(
                watched_s=round(spent[0], 4), cpu_s=round(spent[1], 4),
                nivcsw=spent[2], majflt=spent[3],
                gen2_collections=spent[4],
            )
        return out


class HangingDetector:
    """Flags a stalled training loop from the absence of step progress,
    and records every step that comes late."""

    def __init__(
        self,
        report_fn: Optional[Callable[[float], None]] = None,
        min_timeout: float = 300.0,
        multiplier: float = 10.0,
        check_interval: float = 1.0,
        history: int = 50,
        clock: Callable[[], float] = time.monotonic,
    ):
        if multiplier <= 1.0:
            raise ValueError(f"multiplier must be > 1, got {multiplier}")
        self._report_fn = report_fn
        self._min_timeout = min_timeout
        self._multiplier = multiplier
        self._check_interval = check_interval
        self._clock = clock
        self._durations = deque(maxlen=history)
        self._last_step_time: float = 0.0  # 0 = not armed yet
        self._last_step: int = -1
        self._lock = threading.Lock()
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._reported_stall = False
        # late steps: the gap past which a step is late (the watchdog
        # keeps it, ``record_step`` compares against it), the late
        # steps that arrived since the last tick as (step, the stamp
        # before, its own stamp), and what only the watchdog touches:
        # the median that gap came from and the stall it is sampling
        self._late_after = math.inf
        self._late_arrivals: deque = deque(maxlen=16)
        self._median = 0.0
        self._stall: Optional[_Stall] = None

    # -- feeding -----------------------------------------------------------

    def record_step(self, step: int) -> None:
        """Called after every completed optimizer step."""
        now = self._clock()
        with self._lock:
            if self._last_step_time > 0:
                duration = now - self._last_step_time
                if duration > self._late_after:
                    self._late_arrivals.append(
                        (step, self._last_step_time, now)
                    )
                threshold = (
                    max(
                        self._min_timeout,
                        self._multiplier * median(self._durations),
                    )
                    if self._durations else self._min_timeout
                )
                # a gap beyond the hang threshold was a stall (recovered
                # or transient), not training cadence — recording it
                # would inflate the threshold and mask the next hang
                if duration <= threshold:
                    self._durations.append(duration)
            self._last_step_time = now
            self._last_step = step
            self._reported_stall = False

    # -- threshold ---------------------------------------------------------

    def timeout(self) -> float:
        """Current adaptive hang threshold in seconds."""
        with self._lock:
            if not self._durations:
                return self._min_timeout
            return max(
                self._min_timeout,
                self._multiplier * median(self._durations),
            )

    def stalled_for(self) -> float:
        """Seconds since the last completed step (0 if not armed)."""
        with self._lock:
            if self._last_step_time <= 0:
                return 0.0
            return self._clock() - self._last_step_time

    @property
    def last_step(self) -> int:
        """The last completed step (-1 before the first one) — the
        ``/healthz`` degraded payload and flight records carry it."""
        with self._lock:
            return self._last_step

    def is_hanged(self) -> bool:
        elapsed = self.stalled_for()
        return elapsed > 0 and elapsed > self.timeout()

    # -- monitor thread ----------------------------------------------------

    def start(self) -> "HangingDetector":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="hang-detector"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stopped.set()

    def _tick(self) -> float:
        """Seconds to the next check: a quarter of the cadence once it
        is known, between MIN_TICK_S and ``check_interval``."""
        if not self._median:
            return self._check_interval
        return min(self._check_interval,
                   max(MIN_TICK_S, self._median / 4))

    def _run(self) -> None:
        asked, before = self._tick(), self._clock()
        while not self._stopped.wait(asked):
            # how much later than asked the wait returned: the process,
            # or the GIL, was held, not just the main thread
            tick_late = max(0.0, self._clock() - before - asked)
            try:
                self._check_once(tick_late)
            except Exception as e:  # never kill the monitor
                logger.warning("hang check failed: %s", e)
            asked, before = self._tick(), self._clock()

    def _check_once(self, tick_late: float = 0.0) -> None:
        self._check_hang()
        self._check_late(tick_late)

    def _check_hang(self) -> None:
        if not self.is_hanged():
            return
        with self._lock:
            if self._reported_stall:
                return
            self._reported_stall = True
            elapsed = self._clock() - self._last_step_time
            step = self._last_step
        logger.error(
            "Training hang: no step since step %d for %.1fs "
            "(threshold %.1fs)", step, elapsed, self.timeout(),
        )
        counter(
            "dlrover_hang_stalls_total",
            "Stalls the step-progress hang detector flagged",
        ).inc()
        # flight record FIRST: the report_fn path can end in the master
        # restarting this process — the stacks must be on disk by then
        dump_path = None
        try:
            from dlrover_tpu.telemetry import flight_recorder

            dump_path = flight_recorder.dump_on_hang(
                stalled_for=elapsed, step=step,
                threshold=self.timeout(),
            )
        except Exception as e:  # diagnosis never blocks the report
            logger.warning("hang flight record failed: %s", e)
        record(
            "hang.detected", step=step,
            stalled_for=round(elapsed, 1),
            stalled_s=round(elapsed, 1),
            threshold_s=round(self.timeout(), 1),
            flight_record=dump_path,
        )
        if self._report_fn is not None:
            self._report_fn(elapsed)

    # -- late steps ----------------------------------------------------------

    def _check_late(self, tick_late: float) -> None:
        """One tick of the late-step record: close what arrived, then
        open a stall if the step now due is late, or sample the open
        one."""
        now = self._clock()
        cadence = self._median
        with self._lock:
            since = self._last_step_time
            if len(self._durations) >= LATE_MIN_STEPS:
                cadence = median(self._durations)
                self._late_after = max(
                    LATE_FACTOR * cadence, cadence + LATE_MARGIN_S
                )
            late_after = self._late_after
            arrivals = list(self._late_arrivals)
            self._late_arrivals.clear()
        self._median = cadence
        for step, before, arrived in arrivals:
            stall = self._stall
            if stall is not None and stall.since == before:
                self._stall = None
                stall.sample(now, tick_late, arrived=True)
            else:  # came and went between two ticks: nothing sampled
                stall = _Stall(before, self._median)
                stall.tick_late_s = tick_late
            self._close(stall, step, arrived)
        if self._stall is not None:
            self._stall.sample(now, tick_late)
        elif since > 0 and now - since > late_after:
            attrs: Dict = {}
            span = tracing.span("train.stall", attrs)
            span.__enter__()  # live until the tick after the step
            self._stall = _Stall(since, self._median, span, attrs)
            self._stall.sample(now, tick_late)

    def _close(self, stall: _Stall, step: int, arrived: float) -> None:
        """The one record of a stall, once its step has arrived."""
        period = arrived - stall.since
        late = period - stall.median_s
        # the span's clock is the wall's; the stamps are this clock's
        due_ts = time.time() - (self._clock() - stall.since) + (
            stall.median_s
        )
        fields = dict(
            step=step, due_ts=round(due_ts, 6), late_s=round(late, 6),
            period_s=round(period, 6), median_s=round(stall.median_s, 6),
            **stall.fields(),
        )
        if stall.span is not None:
            stall.attrs.update(fields)
            stall.span.__exit__(None, None, None)
        else:
            tracing.add_span("train.stall", due_ts, late, fields)
        logger.warning(
            "Step %d came %.3fs late (%.3fs after the step before, "
            "median %.3fs) at %s: cpu %ss of %ss watched, watchdog "
            "late %.3fs, full collections %s, %d sample(s)",
            step, late, period, stall.median_s, fields["where"],
            fields.get("cpu_s"), fields.get("watched_s"),
            fields["tick_late_s"], fields.get("gen2_collections"),
            fields["samples"],
        )
        counter(
            "dlrover_step_stalls_total",
            "Late steps the hang detector's watchdog recorded",
        ).inc()
        record("step.stall", **fields)
