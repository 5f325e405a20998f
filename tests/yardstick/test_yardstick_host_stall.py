"""``host_stall_ms`` and ``gc_pause_ms``: the two readers on hand-made
spans, their entries, and one traced rehearsal end to end with a step
held for a second by the program's own fault injection."""

import json
import os
import subprocess

import pytest

from yardstick import cells, program_spans
from yardstick.layer_metrics import gc_pause_ms, host_stall_ms

from . import on_two_cores

BENCH = cells.benchmark(os.path.join(cells.CHECKOUT, "BENCHMARK.json"))


def span(name, of_pid, ts, dur, thread="MainThread", **attrs):
    rec = {"name": name, "pid": of_pid, "ts": ts, "dur": dur,
           "thread": thread}
    if attrs:
        rec["attrs"] = attrs
    return rec


def stall(of_pid, due_ts, late_s):
    """As the watchdog writes it: live from when it found the step
    late to the tick after the step, the exact figures in attrs."""
    return span("train.stall", of_pid, due_ts + 0.3, late_s - 0.2,
                thread="hang-detector", step=7, due_ts=due_ts,
                late_s=late_s, where="jax/_src/array.py:640 _value")


#: worker 20, window 100..140, ten steps of which eight end inside
EVENTS = {
    "start": [{"restart_count": 0, "pid": 20}],
    "window": [{"t_window_start": 100.0, "seconds": 40.0,
                "rows": [{"done": 100.0 + 5 * i} for i in range(1, 11)]}],
}
STEPS = [span("train.report_step", 20, 95.0, 0.004),
         span("train.report_step", 20, 101.0, 0.004),
         span("train.report_step", 20, 139.0, 0.004)]
SETUP_GC = [span("gc.collect", 20, 50.0, 0.080, generation=2,
                 collected=9)]


@pytest.mark.parametrize("spans, value", [
    (STEPS, 0.0),
    (STEPS + [stall(20, 120.0, 2.4)], 2400.0),
    (STEPS + [stall(20, 120.0, 2.4), stall(20, 130.0, 0.5)], 2900.0),
    # due before the window opened, after it closed, another process's
    (STEPS + [stall(20, 99.0, 3.0), stall(20, 140.5, 1.0),
              stall(21, 120.0, 7.0)], 0.0),
    # due inside and arrived after the end: the window lost that step
    (STEPS + [stall(20, 139.5, 4.0)], 4000.0),
    # no step reported inside the window: nothing to say
    (STEPS[:1] + [stall(20, 120.0, 2.4)], None),
    ([], None),
], ids=["none", "inside", "two", "outside", "over-the-end", "no-steps",
        "empty"])
def test_host_stall_ms_on_hand_made_spans(spans, value):
    got = host_stall_ms.read({"events": EVENTS, "spans": spans})
    assert got == (None if value is None else pytest.approx(value))


@pytest.mark.parametrize("spans, value", [
    # the hook ran (set-up's collection) and caught nothing inside
    (SETUP_GC, 0.0),
    (SETUP_GC + [
        span("gc.collect", 20, 110.0, 0.016, generation=1, collected=0),
        span("gc.collect", 20, 120.0, 0.064, generation=2, collected=3),
    ], 1e3 * 0.080 / 8),
    # the fill thread's, another process's, after the window's end
    (SETUP_GC + [
        span("gc.collect", 20, 110.0, 0.5, thread="shm-fill",
             generation=2, collected=0),
        span("gc.collect", 21, 110.0, 0.5, generation=2, collected=0),
        span("gc.collect", 20, 140.0, 0.5, generation=2, collected=0),
    ], 0.0),
    # a program without the hook (the parent commit)
    (STEPS, None),
    ([], None),
], ids=["quiet", "two-pauses", "not-the-loop's", "no-hook", "empty"])
def test_gc_pause_ms_on_hand_made_spans(spans, value):
    got = gc_pause_ms.read({"events": EVENTS, "spans": spans})
    assert got == (None if value is None else pytest.approx(value))


@pytest.mark.parametrize("reader", [host_stall_ms, gc_pause_ms],
                         ids=lambda r: r.NAME)
def test_reader_says_nothing_without_a_worker_or_a_window(reader):
    spans = STEPS + SETUP_GC + [stall(20, 120.0, 2.4)]
    for events in ({}, {"start": EVENTS["start"]},
                   {"window": EVENTS["window"]}):
        assert reader.read({"events": events, "spans": spans}) is None
    assert (reader.MOVES, reader.SOURCE, reader.UNIT) == (
        "tokens_per_s", "host_clock", "ms")


def test_the_two_metrics_are_the_last_entries_of_every_cell():
    last = BENCH["per_layer"][-2:]
    assert [m["name"] for m in last] == ["host_stall_ms", "gc_pause_ms"]
    assert [m["layer"] for m in last] == [
        "launcher, master, agent", "trainer step"]
    for m in last:
        assert "workloads" not in m and m["better"] == "lower"


# ------------------------------------------------------- end to end


def rehearse(tmp_path, fault):
    cell = next(c["name"] for c in BENCH["workloads"]
                if c["chips"] == 1)
    kept = tmp_path / "spans"
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    # a directory set beforehand is kept: arm() leaves it alone
    env[program_spans.ENV_TRACE_DIR] = str(kept)
    env.pop("DLROVER_FAULT_INJECT", None)
    if fault:
        env["DLROVER_FAULT_INJECT"] = fault
    got = subprocess.run(
        on_two_cores(
            program_spans.RUN_PY, "--workload", cell,
            "--seed", str(2 ** 31 + 38), "--seconds", "2",
            "--trace", "1", "--rehearse", "tiny-llama",
            "--keep", str(tmp_path / "keep")),
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.splitlines()[-1])
    stalls = program_spans.of(
        program_spans.load(str(kept)), "train.stall")
    (log,) = [n for n in os.listdir(tmp_path / "keep")
              if n.endswith(".log")]
    return line["metrics"], stalls, (tmp_path / "keep" / log).read_text()


def test_a_held_step_shows_in_a_traced_rehearsal(tmp_path):
    metrics, stalls, log = rehearse(tmp_path, "hang@12:1.0")
    assert metrics["host_stall_ms"]["unit"] == "ms"
    assert 500 < metrics["host_stall_ms"]["value"] < 1500
    assert metrics["gc_pause_ms"]["value"] >= 0.0
    held = max(stalls, key=lambda r: r["attrs"]["late_s"])
    assert abs(held["attrs"]["late_s"] - 1.0) < 0.5
    assert "fault_tolerance/injection.py" in held["attrs"]["where"]
    assert held["thread"] == "hang-detector"
    assert held["attrs"]["samples"] >= 1
    assert held["attrs"]["watched_s"] < held["attrs"]["period_s"]
    # and the line an untraced run's --keep log would hold
    assert "INJECTED HANG at step 12" in log
    assert "Step 13 came" in log and "injection.py" in log


def test_a_rehearsal_without_the_fault_shows_none(tmp_path):
    """0.0 on a quiet machine. Beside other tests a 9 ms step of a
    dozen processes on two cores can itself come 0.2 s late, and that
    is a stall the record should hold: only the injector's may not be
    there."""
    metrics, stalls, log = rehearse(tmp_path, None)
    assert 0.0 <= metrics["host_stall_ms"]["value"] < 500
    assert metrics["gc_pause_ms"]["value"] >= 0.0
    assert not [r for r in stalls
                if "injection.py" in (r["attrs"]["where"] or "")]
    assert "INJECTED" not in log
