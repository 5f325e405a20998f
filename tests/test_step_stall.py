"""ISSUE 38: a step that comes late says why. The hang detector's
watchdog records every stall of the step loop, and the collector's
pauses are spans. Deterministic: an injected clock and direct
``_check_once`` calls."""

import gc
import logging
import math
import threading
import time

import pytest

from dlrover_tpu import telemetry as T
from dlrover_tpu.fault_tolerance import hanging_detector as hd
from dlrover_tpu.telemetry import flight_recorder, tracing
from dlrover_tpu.telemetry.journal import EventJournal


@pytest.fixture(autouse=True)
def fresh_state():
    tracing.disable()
    tracing.clear()
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))
    yield
    tracing.disable()
    tracing.clear()
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))


class Clock:
    def __init__(self, now=1000.0):
        self.now = now

    def __call__(self):
        return self.now


def stepping(median_s, steps=8, **kw):
    """A detector that has seen ``steps`` steps ``median_s`` apart and
    one watchdog tick since."""
    clock = Clock()
    det = hd.HangingDetector(clock=clock, **kw)
    for step in range(steps):
        det.record_step(step)
        clock.now += median_s
    clock.now -= median_s  # stands at the last step's stamp
    det._check_once()
    return det, clock


def stalls():
    return [e["data"] for e in T.default_journal().events("step.stall")]


@pytest.fixture
def readings(monkeypatch):
    """Scripted process readings and main-thread frames; the calls
    made are counted."""
    state = {"calls": 0, "process": (0.0, 0, 0, 0),
             "frames": ["yardstick/worker.py:241 drive",
                        "dlrover_tpu/trainer/elastic.py:366 report_step",
                        "dlrover_tpu/fault_tolerance/injection.py:381 "
                        "maybe_inject"]}

    def read_process():
        state["calls"] += 1
        return state["process"]

    def frames():
        state["calls"] += 1
        return list(state["frames"])

    monkeypatch.setattr(hd, "_read_process", read_process)
    monkeypatch.setattr(hd, "_main_thread_frames", frames)
    return state


# ------------------------------------------------------------ thresholds


@pytest.mark.parametrize("median_s, elapsed, late, hanged", [
    (0.34, 0.50, False, False),   # under the median plus 0.2 s
    (0.34, 0.55, True, False),    # past it (and past 1.5 x 0.34)
    (2.0, 2.5, False, False),     # past median + 0.2, under 1.5 x
    (2.0, 3.1, True, False),
    (2.0, 19.0, True, False),     # the hang threshold is 10 x 2.0
    (2.0, 21.0, True, True),
    (0.01, 0.15, False, False),
    (0.01, 0.25, True, False),    # short steps: the 0.2 s floor
], ids=lambda v: str(v))
def test_late_threshold_beside_hang_threshold(
        readings, median_s, elapsed, late, hanged):
    reports = []
    det, clock = stepping(
        median_s, report_fn=reports.append, min_timeout=5.0)
    clock.now += elapsed
    det._check_once()
    assert (det._stall is not None) == late
    assert bool(reports) == hanged
    assert det.is_hanged() == hanged
    assert det.timeout() == pytest.approx(max(5.0, 10 * median_s))
    # the step arrives: a late one leaves one record, others none
    det.record_step(99)
    det._check_once()
    assert len(stalls()) == (1 if late else 0)
    assert det._stall is None


@pytest.mark.parametrize("steps, armed", [
    (1, False), (5, False), (6, True), (30, True),
], ids=lambda v: str(v))
def test_not_armed_before_five_durations(readings, steps, armed):
    """``steps`` steps are ``steps - 1`` durations; warm-up feeds them
    and the first step's compile can never be called late."""
    det, clock = stepping(0.1, steps=steps)
    assert (det._late_after < math.inf) == armed
    clock.now += 60.0
    det._check_once()
    det.record_step(steps)
    det._check_once()
    assert len(stalls()) == (1 if armed else 0)
    assert (readings["calls"] > 0) == armed


@pytest.mark.parametrize("median_s, check_interval, tick", [
    (0.34, 1.0, 0.085), (1.4, 1.0, 0.35), (0.1, 1.0, 0.05),
    (8.0, 1.0, 1.0), (0.34, 0.01, 0.01),
], ids=lambda v: str(v))
def test_tick_follows_the_cadence(median_s, check_interval, tick):
    det = hd.HangingDetector(check_interval=check_interval)
    assert det._tick() == check_interval  # no cadence known yet
    det, _ = stepping(median_s, check_interval=check_interval)
    assert det._tick() == pytest.approx(tick)


# ---------------------------------------------------------- one record


@pytest.mark.parametrize("ticks", [1, 3, 8, 40])
def test_one_record_a_stall_however_many_ticks(readings, ticks):
    det, clock = stepping(0.4)
    clock.now += 0.7
    for _ in range(ticks):
        det._check_once()
        clock.now += 0.1
    assert stalls() == []  # nothing is said before the step arrives
    det.record_step(8)
    clock.now += 0.1
    det._check_once()
    det._check_once()
    (rec,) = stalls()
    assert rec["samples"] == min(ticks, hd.MAX_STALL_SAMPLES)
    assert rec["step"] == 8
    assert rec["period_s"] == pytest.approx(0.7 + 0.1 * ticks)
    assert rec["median_s"] == pytest.approx(0.4)
    assert rec["late_s"] == pytest.approx(0.3 + 0.1 * ticks)
    assert rec["where"] == (
        "dlrover_tpu/fault_tolerance/injection.py:381 maybe_inject")
    assert rec["stack"] == readings["frames"]
    assert T.default_registry().get(
        "dlrover_step_stalls_total").value == 1
    # the next steps are on time again: nothing more
    for step in range(9, 12):
        clock.now += 0.4
        det.record_step(step)
        det._check_once()
    assert len(stalls()) == 1


def test_due_ts_is_on_the_wall_clock(readings):
    det, clock = stepping(0.4)
    stamp = clock.now
    clock.now += 1.4
    det._check_once()
    det.record_step(8)
    clock.now += 0.05
    wall = time.time()
    det._check_once()
    (rec,) = stalls()
    # due one median after the stamp, 1.05 s of this clock ago
    assert rec["due_ts"] == pytest.approx(
        wall - (clock.now - stamp) + 0.4, abs=0.2)
    assert rec["late_s"] == pytest.approx(1.0)


def test_cpu_and_tick_lateness_from_faked_readings(readings):
    det, clock = stepping(0.4)
    clock.now += 0.7
    readings["process"] = (10.0, 100, 7, 3)
    det._check_once(tick_late=0.02)        # finds it late
    clock.now += 2.0
    readings["process"] = (10.1, 104, 7, 3)
    det._check_once(tick_late=1.9)         # the process was held
    det.record_step(8)
    clock.now += 0.1
    readings["process"] = (10.15, 105, 9, 4)
    det._check_once(tick_late=0.01)        # the tick after the step
    (rec,) = stalls()
    assert rec["tick_late_s"] == pytest.approx(1.93)
    assert rec["cpu_s"] == pytest.approx(0.15)
    assert rec["watched_s"] == pytest.approx(2.1)
    assert (rec["nivcsw"], rec["majflt"], rec["gen2_collections"]) == (
        5, 2, 1)
    assert rec["samples"] == 2  # no frames once the step is in


def test_a_step_that_came_and_went_between_two_ticks(readings):
    """The whole process stood still: the watchdog's wait returned
    late and the step (and a quick one behind it) was in by then."""
    det, clock = stepping(0.4)
    clock.now += 5.0
    det.record_step(8)
    clock.now += 0.01
    det.record_step(9)
    det._check_once(tick_late=4.6)
    (rec,) = stalls()
    assert rec["step"] == 8 and rec["samples"] == 0
    assert rec["late_s"] == pytest.approx(4.6)
    assert rec["tick_late_s"] == pytest.approx(4.6)
    assert rec["where"] is None and "cpu_s" not in rec
    assert readings["calls"] == 0


# -------------------------------------------------------- off and on


def test_off_nothing_is_sampled_until_late_then_journal_and_log(
        readings):
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logging.getLogger("dlrover_tpu").addHandler(handler)
    try:
        det, clock = stepping(0.4, steps=30)
        assert gc.callbacks.count(tracing._gc_hook) == 0
        assert readings["calls"] == 0 and not det._late_arrivals
        clock.now += 1.5
        det._check_once()
        det.record_step(30)
        det._check_once()
    finally:
        logging.getLogger("dlrover_tpu").removeHandler(handler)
    (rec,) = stalls()
    assert rec["late_s"] == pytest.approx(1.1)
    (line,) = [r.getMessage() for r in records
               if r.levelno == logging.WARNING]
    assert "Step 30 came 1.100s late" in line
    assert "injection.py:381 maybe_inject" in line
    assert tracing.tail() == []  # and no span: tracing is off


def test_on_the_stall_is_one_live_span_on_the_watchdogs_thread(
        readings):
    tracing.enable()
    det, clock = stepping(0.4)
    clock.now += 0.7
    seen = {}

    def watchdog():
        det._check_once()
        seen["context"] = tracing.current_context()
        time.sleep(0.05)
        det.record_step(8)
        det._check_once()

    t = threading.Thread(target=watchdog, name="hang-detector")
    t.start()
    t.join()
    (span,) = [r for r in tracing.tail() if r["name"] == "train.stall"]
    assert span["thread"] == "hang-detector"
    assert span["span"] == seen["context"][1]  # live while it was late
    assert span["dur"] >= 0.05
    assert span["attrs"] == stalls()[0]
    assert set(span["attrs"]) == {
        "step", "due_ts", "late_s", "period_s", "median_s", "samples",
        "where", "stack", "cpu_s", "watched_s", "tick_late_s",
        "gen2_collections", "nivcsw", "majflt"}


def test_a_late_step_nobody_saw_is_a_retroactive_span():
    tracing.enable()
    det, clock = stepping(0.4)
    clock.now += 3.0
    det.record_step(8)
    det._check_once(tick_late=2.5)
    (span,) = [r for r in tracing.tail() if r["name"] == "train.stall"]
    assert span["ts"] == pytest.approx(span["attrs"]["due_ts"])
    assert span["dur"] == pytest.approx(2.6)


def test_the_watchdog_thread_records_a_real_stall():
    """The thread itself, on the real clock: 20 ms steps, one of
    0.6 s."""
    det = hd.HangingDetector(check_interval=0.02).start()
    try:
        for step in range(8):
            det.record_step(step)
            time.sleep(0.02)
        for _ in range(4):  # this test's late step
            time.sleep(0.15)
        det.record_step(8)
        time.sleep(0.1)
    finally:
        det.stop()
    # (a loaded machine may make another step late too)
    rec = max(stalls(), key=lambda r: r["late_s"])
    assert rec["step"] == 8 and rec["late_s"] > 0.4
    assert rec["samples"] >= 1
    assert "test_step_stall.py" in rec["where"]
    assert rec["stack"][-1] == rec["where"]


# ----------------------------------------------------- frames, where


@pytest.mark.parametrize("frames, where", [
    (["yardstick/worker.py:228 retire",
      "jax/_src/array.py:305 __float__",
      "jax/_src/array.py:640 _value"], "jax/_src/array.py:640 _value"),
    (["dlrover_tpu/agent/master_client.py:90 report",
      "grpc/_channel.py:1178 __call__",
      "threading.py:355 wait"], "grpc/_channel.py:1178 __call__"),
    (["examples/train.py:10 main", "lib/queue.py:171 get"],
     "lib/queue.py:171 get"),
    ([], None),
], ids=["device", "grpc", "neither", "none"])
def test_where_is_the_innermost_frame_of_a_known_package(frames, where):
    assert hd._where(frames) == where


def test_where_is_what_most_samples_share():
    stall = hd._Stall(0.0, 0.4)
    stall.stacks = [["a.py:1 f", "jax/x.py:2 g"],
                    ["a.py:1 f", "grpc/y.py:3 h"],
                    ["a.py:9 k", "grpc/y.py:3 h"]]
    fields = stall.fields()
    assert fields["where"] == "grpc/y.py:3 h"
    assert fields["stack"] == ["a.py:1 f", "grpc/y.py:3 h"]
    assert fields["samples"] == 3


def test_main_thread_frames_are_short_and_innermost_last():
    def ping(n):  # two lines by turns: equal frames are folded
        return pong(n - 1) if n else hd._main_thread_frames()

    def pong(n):
        return ping(n - 1) if n else hd._main_thread_frames()

    frames = ping(15)
    assert len(frames) == hd.STACK_FRAMES
    assert frames[-1].startswith(
        "dlrover_tpu/telemetry/flight_recorder.py:")
    assert frames[-1].endswith(" thread_stacks")
    assert frames[-2].startswith(
        "dlrover_tpu/fault_tolerance/hanging_detector.py:")
    assert frames[0].startswith("tests/test_step_stall.py:")


@pytest.mark.parametrize("main_only, limit", [
    (False, None), (True, None), (True, 3), (False, 2),
])
def test_thread_stacks_main_only_and_limit(main_only, limit):
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="parked", daemon=True)
    t.start()
    try:
        stacks = flight_recorder.thread_stacks(
            main_only=main_only, limit=limit)
    finally:
        stop.set()
        t.join()
    names = [s["name"] for s in stacks]
    assert names[0] == "MainThread"
    assert ("parked" in names) == (not main_only)
    if limit:
        assert all(len(s["stack"]) <= limit for s in stacks)
        assert "thread_stacks" in stacks[0]["stack"][-1]


# ------------------------------------------------------- the collector


def test_enable_and_disable_add_and_remove_the_gc_hook():
    assert tracing._gc_hook not in gc.callbacks
    tracing.enable()
    tracing.enable()  # twice: still one hook
    assert gc.callbacks.count(tracing._gc_hook) == 1
    tracing.disable()
    assert tracing._gc_hook not in gc.callbacks


def test_a_forced_full_collection_leaves_one_span_under_its_parent():
    tracing.enable()
    with tracing.span("train.report_step") as parent:
        gc.collect(2)
    spans = [r for r in tracing.tail() if r["name"] == "gc.collect"]
    full = [r for r in spans if r["attrs"]["generation"] == 2]
    assert len(full) == 1
    assert full[0]["parent"] == parent.span_id
    assert full[0]["thread"] == "MainThread"
    assert set(full[0]["attrs"]) == {"generation", "collected"}
    assert 0 < full[0]["dur"] < 5


@pytest.mark.parametrize("generation, took, kept", [
    (0, 0.0, False), (0, 0.0015, True), (1, 0.0, True), (2, 0.0, True),
])
def test_which_collections_are_spans(monkeypatch, generation, took,
                                     kept):
    tracing.enable()
    gc.disable()  # none of its own between the two phases
    try:
        tracing._gc_hook("start", {"generation": generation})
        if took:
            time.sleep(took)
        tracing._gc_hook("stop", {"generation": generation,
                                  "collected": 4})
    finally:
        gc.enable()
    spans = [r for r in tracing.tail() if r["name"] == "gc.collect"]
    assert len(spans) == (1 if kept else 0)
    if kept:
        assert spans[0]["attrs"] == {
            "generation": generation, "collected": 4}
        assert spans[0]["dur"] >= took
    # a stop whose start this hook did not see says nothing
    tracing.clear()
    tracing._gc_hook("stop", {"generation": 2, "collected": 0})
    assert tracing.tail() == []


def test_a_collection_inside_a_spans_own_record_does_not_deadlock():
    """The hook runs inside whatever allocation the collector
    interrupts: also one made while ``_finish`` holds the process
    index's lock (found by benchmarks/trace_overhead.py hanging)."""
    from dlrover_tpu.common import log

    tracing.enable()
    done = threading.Event()

    def collect_under_the_lock():
        with log._proc_lock:
            gc.collect(2)
        done.set()

    threading.Thread(target=collect_under_the_lock, daemon=True).start()
    assert done.wait(5.0), "gc.collect span deadlocked on _proc_lock"
    assert [r for r in tracing.tail() if r["name"] == "gc.collect"]
