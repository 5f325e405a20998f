"""ISSUE 4: span tracing, Chrome merge, flight recorder, degraded
/healthz, straggler scorer, and the journal event-name lint."""

import gc
import json
import os
import pathlib
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from dlrover_tpu import telemetry as T
from dlrover_tpu.telemetry import flight_recorder, tracing
from dlrover_tpu.telemetry import http as thttp
from dlrover_tpu.telemetry.journal import EventJournal

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def fresh_state():
    """Isolate the process-wide tracing/health/registry/journal state."""
    tracing.disable()
    tracing.clear()
    tracing.set_step(-1)
    thttp.set_health_check(None)
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))
    yield
    tracing.disable()
    tracing.enable(capacity=4096)  # restore the default ring size
    tracing.disable()
    tracing.clear()
    tracing.set_step(-1)
    thttp.set_health_check(None)
    flight_recorder.uninstall_signal_hook()
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))


def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.read().decode()


# ------------------------------------------------------------ span basics


def test_disabled_span_is_shared_noop_and_allocation_free():
    assert not tracing.enabled()
    # the disabled path hands back ONE shared object — nothing is
    # constructed per call site
    assert tracing.span("a") is tracing.span("b")

    def run(n):
        span = tracing.span
        for _ in range(n):
            with span("x"):
                pass

    run(100)  # warm caches/freelists
    # allocation-free: a couple of blocks of slack for interpreter
    # noise, nothing proportional to the 2000 calls. Noise from
    # unrelated threads is strictly additive, so best-of-3 keeps the
    # invariant sharp (a real per-call allocation taints every trial)
    # without failing on a stray background wakeup mid-measurement.
    deltas = []
    for _ in range(3):
        gc.collect()
        before = sys.getallocatedblocks()
        run(2000)
        gc.collect()
        deltas.append(sys.getallocatedblocks() - before)
        if min(deltas) <= 4:
            break
    assert min(deltas) <= 4, deltas
    assert len(tracing.tail(10)) == 0  # and nothing was recorded


def test_span_records_carry_journal_envelope():
    tracing.enable()
    tracing.set_step(41)
    with tracing.span("data_load", {"batch": 7}):
        time.sleep(0.002)
    recs = tracing.tail(5)
    assert len(recs) == 1
    rec = recs[0]
    assert rec["name"] == "data_load"
    assert rec["pid"] == os.getpid()
    assert {"host", "proc", "tid", "thread", "ts", "dur"} <= set(rec)
    assert rec["step"] == 41
    assert rec["attrs"] == {"batch": 7}
    assert rec["dur"] >= 0.002


def test_span_marks_errors_and_propagates():
    tracing.enable()
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError("x")
    assert tracing.tail(1)[0]["error"] is True


def test_ring_wraparound_keeps_newest():
    tracing.enable(capacity=8)
    for i in range(20):
        tracing.add_span(f"s{i}", 100.0 + i, 0.001)
    recs = tracing.tail(100)
    assert len(recs) == 8
    assert [r["name"] for r in recs] == [f"s{i}" for i in range(12, 20)]


def test_add_span_retroactive_and_disabled_noop():
    tracing.add_span("off", 1.0, 1.0)  # disabled: dropped
    assert tracing.tail(5) == []
    tracing.enable()
    tracing.add_span("rdzv.training", 1000.0, 2.5, {"round": 3})
    rec = tracing.tail(1)[0]
    assert rec["ts"] == 1000.0 and rec["dur"] == 2.5
    assert rec["attrs"]["round"] == 3


def _burn(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_cpu_s_is_an_opt_in_of_the_site():
    """``cpu=True``: the thread's own CPU time over the block, near
    ``dur`` where it held a core and near zero where it waited."""
    tracing.enable()
    with tracing.span("plain"):
        _burn(0.01)
    with tracing.span("held", cpu=True):
        _burn(0.1)
    with tracing.span("waited", {"bytes": 7}, cpu=True):
        time.sleep(0.1)
    plain, held, waited = tracing.tail(3)
    assert "attrs" not in plain
    assert set(held["attrs"]) == {"cpu_s"}
    assert 0.05 < held["attrs"]["cpu_s"] <= held["dur"] + 0.02
    assert waited["attrs"]["bytes"] == 7
    assert waited["attrs"]["cpu_s"] < 0.05 <= waited["dur"]


def test_cpu_s_leaves_the_sites_own_attrs_alone():
    """A site hands one dict to several spans (the checkpoint's
    ``size``): each record gets a ``cpu_s`` of its own."""
    tracing.enable()
    size = {"bytes": 3}
    with tracing.span("first", size, cpu=True):
        _burn(0.02)
    with tracing.span("second", size, cpu=True):
        size["late"] = True  # filled in inside the block, as sites do
    first, second = tracing.tail(2)
    assert size == {"bytes": 3, "late": True}
    assert first["attrs"]["cpu_s"] >= 0.01 > second["attrs"]["cpu_s"]
    assert second["attrs"]["late"] is True and "late" not in first["attrs"]


@pytest.mark.parametrize("on, cpu, reads", [
    (False, True, 0), (True, False, 0), (True, True, 2),
], ids=["off", "on-not-asked", "on-asked"])
def test_thread_clock_is_read_only_where_asked_and_on(
        monkeypatch, on, cpu, reads):
    calls = []
    monkeypatch.setattr(
        time, "thread_time", lambda: calls.append(1) or 0.0
    )
    if on:
        tracing.enable()
    with tracing.span("x", None, cpu=cpu):
        pass
    assert len(calls) == reads
    assert len(tracing.tail(5)) == (1 if on else 0)


def test_cpu_s_reaches_the_chrome_args_pane():
    tracing.enable()
    with tracing.span("ckpt.serialize", {"step": 4}, cpu=True):
        pass
    (event,) = [e for e in tracing.chrome_trace()["traceEvents"]
                if e["ph"] == "X"]
    assert event["args"]["step"] == 4 and "cpu_s" in event["args"]


@pytest.mark.parametrize("where", ["dlrover_tpu", "docs", "examples",
                                   "benchmarks"])
def test_the_wait_for_staging_has_one_name(where):
    """``ckpt.wait_staged``, inside ``FlashCheckpointer.wait_staged``
    wherever it is called from; the wrapper span
    ``train.wait_staged`` is gone from code and documents."""
    hits = [
        str(p) for p in (REPO_ROOT / where).rglob("*")
        if p.suffix in (".py", ".md") and "train.wait_staged"
        in p.read_text(errors="replace")
    ]
    assert hits == []


def test_summarize_aggregates_by_name():
    tracing.enable()
    for ms in (10, 20, 30):
        tracing.add_span("data", 100.0, ms / 1e3)
    tracing.add_span("dispatch", 100.0, 0.005)
    agg = tracing.summarize(("data",))
    assert set(agg) == {"data"}
    assert agg["data"]["count"] == 3
    assert agg["data"]["mean_ms"] == pytest.approx(20.0)
    assert agg["data"]["max_ms"] == pytest.approx(30.0)


# -------------------------------------------------- chrome export + merge


def test_write_through_and_chrome_merge(tmp_path):
    d = str(tmp_path / "trace")
    tracing.enable(trace_dir=d)
    tracing.set_step(3)
    with tracing.span("step", {"k": "v"}):
        pass
    tracing.disable()
    files = os.listdir(d)
    assert len(files) == 1 and files[0].startswith("spans-")
    trace = tracing.merge_trace_dir(d)
    evts = trace["traceEvents"]
    xs = [e for e in evts if e["ph"] == "X"]
    assert len(xs) == 1
    assert xs[0]["name"] == "step"
    args = xs[0]["args"]
    assert args["k"] == "v" and args["step"] == 3
    # root span: carries its trace/span ids but no parent edge
    assert args["trace"] and args["span"] and "parent" not in args
    assert xs[0]["pid"] == os.getpid()
    # process/thread metadata present for the trace viewer
    metas = {e["name"] for e in evts if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= metas


_CHILD = """
import sys
from dlrover_tpu.telemetry import tracing
tracing.enable(trace_dir=sys.argv[1])
tracing.set_step(int(sys.argv[2]))
with tracing.span("work", {"proc": sys.argv[2]}):
    pass
tracing.add_span("phase", 1000.0 + float(sys.argv[2]), 0.25)
tracing.disable()
"""


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        str(REPO_ROOT) + os.pathsep + env.get("PYTHONPATH", "")
    )
    env.pop("DLROVER_TPU_TRACE", None)
    env.pop("DLROVER_TPU_TRACE_DIR", None)
    return env


def test_cross_process_merge_two_pids_deterministic(tmp_path):
    """Acceptance: a 2-process drill yields ONE merged Chrome trace
    with spans from both pids, and the merge is deterministic."""
    d = str(tmp_path / "trace")
    for idx in ("1", "2"):
        subprocess.run(
            [sys.executable, "-c", _CHILD, d, idx],
            check=True, env=_child_env(), timeout=60,
        )
    merged = tracing.merge_trace_dir(d)
    xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    pids = {e["pid"] for e in xs}
    assert len(pids) == 2
    assert sorted(e["name"] for e in xs) == [
        "phase", "phase", "work", "work",
    ]
    # determinism: merging the same files twice is byte-identical
    a = json.dumps(merged, sort_keys=True)
    b = json.dumps(tracing.merge_trace_dir(d), sort_keys=True)
    assert a == b
    # events are globally time-ordered across processes
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)


def test_dump_cli_trace_mode(tmp_path, capsys):
    from dlrover_tpu.telemetry import dump

    d = str(tmp_path / "trace")
    tracing.enable(trace_dir=d)
    with tracing.span("alpha"):
        pass
    tracing.disable()
    out_file = str(tmp_path / "merged.json")
    assert dump.main([d, "--trace", "-o", out_file]) == 0
    err = capsys.readouterr().err
    assert "1 spans from 1 process(es)" in err
    with open(out_file) as f:
        trace = json.load(f)
    assert any(
        e["name"] == "alpha" for e in trace["traceEvents"]
        if e["ph"] == "X"
    )
    # stdout mode
    assert dump.main([d, "--trace"]) == 0
    assert "alpha" in capsys.readouterr().out
    # missing dir is a clean error, not a traceback
    assert dump.main([str(tmp_path / "nope"), "--trace"]) == 2


FIXTURE_TRACE = os.path.join(
    os.path.dirname(__file__), "fixtures", "trace"
)


def _merged(tmp_path, capsys, *flags):
    """Run dump --trace over the committed 2-process fixture with the
    given filter flags; return (trace dict, stderr)."""
    from dlrover_tpu.telemetry import dump

    out = str(tmp_path / "t.json")
    assert dump.main([FIXTURE_TRACE, "--trace", "-o", out, *flags]) == 0
    err = capsys.readouterr().err
    with open(out) as f:
        return json.load(f), err


def test_dump_trace_fixture_full_causal_chain(tmp_path, capsys):
    """The committed fixture is a frozen 2-process causal chain
    (worker report -> relay.forward -> rpc.report_relay_batch): the
    merged trace carries both pids and the cross-process flow arrows."""
    trace, err = _merged(tmp_path, capsys)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {101, 202}
    assert "10 spans from 2 process(es)" in err
    by_name = {e["name"]: e for e in xs}
    assert by_name["relay.forward"]["args"]["parent"] \
        == by_name["report_node_status"]["args"]["span"]
    assert by_name["rpc.report_relay_batch"]["args"]["parent"] \
        == by_name["relay.forward"]["args"]["span"]
    # one flow arrow per cross-pid parent/child hop
    starts = [e for e in trace["traceEvents"] if e["ph"] == "s"]
    finishes = [e for e in trace["traceEvents"] if e["ph"] == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["pid"] == 101 and finishes[0]["pid"] == 202


def test_dump_trace_step_filter(tmp_path, capsys):
    """--step keeps the asked-for training steps and drops unstamped
    setup spans (they are noise on a step-range query)."""
    trace, err = _merged(tmp_path, capsys, "--step", "4..6")
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["args"]["step"] for e in xs) == [4, 5, 6]
    assert all(e["name"] == "train_step" for e in xs)
    assert "kept 3/10 spans" in err
    # open-ended range + single-step form
    trace, _ = _merged(tmp_path, capsys, "--step", "8..")
    assert sorted(e["name"] for e in trace["traceEvents"]
                  if e["ph"] == "X") == [
        "report_node_status", "train_step",
    ]
    trace, _ = _merged(tmp_path, capsys, "--step", "3")
    assert [e["args"]["step"] for e in trace["traceEvents"]
            if e["ph"] == "X"] == [3]


def test_dump_trace_proc_filter_recomputes_flows(tmp_path, capsys):
    """--proc matches the elastic proc index OR the OS pid; flow
    arrows are recomputed AFTER filtering so a dropped parent never
    leaves a dangling arrow."""
    # proc index 1 = the worker side only
    trace, _ = _merged(tmp_path, capsys, "--proc", "1")
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {101}
    assert not [e for e in trace["traceEvents"] if e["ph"] in "sf"]
    # OS pid 202 = the relay/master side; its parents are filtered
    # out, so again: spans survive, dangling flows do not
    trace, _ = _merged(tmp_path, capsys, "--proc", "202")
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["name"] for e in xs) == [
        "relay.forward", "rpc.report_relay_batch",
    ]
    assert not [e for e in trace["traceEvents"] if e["ph"] in "sf"]


def test_dump_trace_since_filter_and_bad_value(tmp_path, capsys):
    from dlrover_tpu.telemetry import dump

    trace, err = _merged(tmp_path, capsys, "--since", "120.0")
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["name"] for e in xs) == [
        "relay.forward", "report_node_status", "rpc.report_relay_batch",
    ]
    # both ends of each hop survive -> the flow arrows do too
    assert len([e for e in trace["traceEvents"] if e["ph"] == "s"]) == 1
    assert "kept 3/10 spans" in err
    # a bad --since is a clean rc-2 error, not a traceback
    assert dump.main(
        [FIXTURE_TRACE, "--trace", "--since", "yesterdayish"]
    ) == 2
    assert "--since" in capsys.readouterr().err


def test_torn_span_lines_skipped(tmp_path):
    d = tmp_path / "trace"
    d.mkdir()
    good = json.dumps({"name": "ok", "ts": 1.0, "dur": 0.1, "pid": 9,
                       "tid": 1, "host": "h", "proc": 0})
    (d / "spans-h-9.jsonl").write_text(good + "\n{torn wri\n")
    xs = [
        e for e in tracing.merge_trace_dir(str(d))["traceEvents"]
        if e["ph"] == "X"
    ]
    assert [e["name"] for e in xs] == ["ok"]


# --------------------------------------------------------- flight recorder


def test_flight_record_dump_contents(tmp_path, monkeypatch):
    monkeypatch.setenv(
        flight_recorder.ENV_CRASH_DIR, str(tmp_path / "crash")
    )
    tracing.enable()
    tracing.set_step(12)
    with tracing.span("last_op"):
        pass
    T.record("checkpoint.save", step=12, tier="ram")
    out = flight_recorder.dump_flight_record("unit test")
    assert out and os.path.isdir(out)
    assert os.path.dirname(out) == str(tmp_path / "crash")
    with open(os.path.join(out, "record.json")) as f:
        rec = json.load(f)
    assert rec["reason"] == "unit test"
    assert rec["step"] == 12
    names = [t["name"] for t in rec["threads"]]
    assert "MainThread" in names
    assert any(s["name"] == "last_op" for s in rec["spans"])
    assert any(
        e["kind"] == "checkpoint.save" for e in rec["journal"]
    )
    assert "dlrover_flight_dumps_total" in rec["metrics"]
    stacks = open(os.path.join(out, "stacks.txt")).read()
    assert 'Thread "MainThread"' in stacks
    # the dump itself lands on the journal for the incident timeline
    evs = T.default_journal().events("flight.dumped")
    assert len(evs) == 1 and evs[0]["data"]["path"] == out


def test_flight_record_on_simulated_hang(tmp_path, monkeypatch):
    """Acceptance: a forced hang produces a flight-recorder dump with
    all-thread stacks + last spans, and the hang event links it."""
    from dlrover_tpu.fault_tolerance.hanging_detector import (
        HangingDetector,
    )

    monkeypatch.setenv(flight_recorder.ENV_FLIGHT_RECORDER, "1")
    monkeypatch.setenv(
        flight_recorder.ENV_CRASH_DIR, str(tmp_path / "crash")
    )
    tracing.enable()
    with tracing.span("pre_hang"):
        pass
    reports = []
    det = HangingDetector(
        report_fn=reports.append, min_timeout=0.05, multiplier=2.0
    )
    det.record_step(7)
    time.sleep(0.12)
    det._check_once()
    assert len(reports) == 1
    evs = T.default_journal().events("hang.detected")
    assert len(evs) == 1
    data = evs[0]["data"]
    assert data["step"] == 7 and data["stalled_for"] >= 0.1
    dump_dir = data["flight_record"]
    assert dump_dir and os.path.isdir(dump_dir)
    with open(os.path.join(dump_dir, "record.json")) as f:
        rec = json.load(f)
    assert any(s["name"] == "pre_hang" for s in rec["spans"])
    assert any(t["name"] == "MainThread" for t in rec["threads"])


def test_flight_record_disabled_by_default_in_tests(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv(flight_recorder.ENV_FLIGHT_RECORDER, "0")
    assert flight_recorder.dump_on_hang(1.0, 1, 1.0) is None
    assert flight_recorder.install_signal_hook() is False


def test_signal_hook_install_and_uninstall(monkeypatch, tmp_path):
    monkeypatch.setenv(flight_recorder.ENV_FLIGHT_RECORDER, "1")
    prev = signal.getsignal(signal.SIGTERM)
    try:
        assert flight_recorder.install_signal_hook() is True
        assert signal.getsignal(signal.SIGTERM) is (
            flight_recorder._on_signal
        )
        # idempotent: re-install keeps ONE hook, not a chain of hooks
        assert flight_recorder.install_signal_hook() is True
    finally:
        flight_recorder.uninstall_signal_hook()
    assert signal.getsignal(signal.SIGTERM) is prev


_SIGTERM_CHILD = """
import os, signal, sys
os.environ["DLROVER_TPU_FLIGHT_RECORDER"] = "1"
os.environ["DLROVER_TPU_CRASH_DIR"] = sys.argv[1]
from dlrover_tpu.telemetry import flight_recorder, tracing
tracing.enable()
with tracing.span("pre_signal"):
    pass
assert flight_recorder.install_signal_hook()
os.kill(os.getpid(), signal.SIGTERM)
import time
time.sleep(30)  # never reached: the chained default disposition kills
"""


def test_sigterm_dumps_flight_record_then_dies(tmp_path):
    crash = str(tmp_path / "crash")
    p = subprocess.run(
        [sys.executable, "-c", _SIGTERM_CHILD, crash],
        env=_child_env(), timeout=60,
    )
    # the hook dumps, then re-delivers SIGTERM with the default
    # disposition restored: the process still dies of SIGTERM
    assert p.returncode == -signal.SIGTERM
    dumps = os.listdir(crash)
    assert len(dumps) == 1 and dumps[0].startswith("flight-")
    with open(os.path.join(crash, dumps[0], "record.json")) as f:
        rec = json.load(f)
    assert rec["reason"] == "signal-SIGTERM"
    assert any(s["name"] == "pre_signal" for s in rec["spans"])


# ------------------------------------------- /healthz + /debug endpoints


class _FakeDetector:
    def __init__(self):
        self.hanged = False
        self.last_step = 7

    def is_hanged(self):
        return self.hanged

    def stalled_for(self):
        return 12.3

    def timeout(self):
        return 5.0


def test_healthz_degraded_on_stall():
    det = _FakeDetector()
    thttp.attach_hang_detector(det)
    srv = thttp.MetricsServer(host="127.0.0.1").start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        assert _get(f"{base}/healthz").strip() == "ok"
        det.hanged = True
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(f"{base}/healthz")
        assert exc.value.code == 503
        body = json.loads(exc.value.read().decode())
        assert body["status"] == "degraded"
        assert body["stalled_for"] == 12.3
        assert body["last_step"] == 7
        det.hanged = False
        assert _get(f"{base}/healthz").strip() == "ok"
    finally:
        srv.stop()


def test_debug_stacks_and_trace_endpoints():
    tracing.enable()
    with tracing.span("served_span"):
        pass
    srv = thttp.MetricsServer(host="127.0.0.1").start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        stacks = _get(f"{base}/debug/stacks")
        assert 'Thread "MainThread"' in stacks
        trace = json.loads(_get(f"{base}/debug/trace?n=10"))
        xs = [
            e for e in trace["traceEvents"] if e["ph"] == "X"
        ]
        assert any(e["name"] == "served_span" for e in xs)
    finally:
        srv.stop()


def test_rpc_handling_emits_spans():
    from dlrover_tpu.common import comm
    from dlrover_tpu.master.servicer import MasterServicer

    tracing.enable()
    MasterServicer().handle("ping", comm.BaseRequest())
    names = [r["name"] for r in tracing.tail(10)]
    assert "rpc.ping" in names


# -------------------------------------------------- straggler diagnosis


def _feed(sm, node_id, step, ts):
    sm.collect_global_step(step, ts, node_id=node_id)


def test_straggler_scorer_flags_and_recovers():
    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

    sm = SpeedMonitor(straggler_ratio=1.5, straggler_window=2)
    t = 1000.0
    # hosts 0/1 run 0.1 s/step; host 2 runs 0.3 s/step (3x the median)
    for k in range(1, 6):
        _feed(sm, 0, 10 * k, t + k * 1.0)
        _feed(sm, 1, 10 * k, t + k * 1.0)
        _feed(sm, 2, 10 * k, t + k * 3.0)
    assert sm.straggler_ranks() == [2]
    evs = T.default_journal().events("straggler.detected")
    assert len(evs) == 1
    data = evs[0]["data"]
    assert data["node"] == 2
    assert data["ratio"] > 1.5
    assert data["fleet_median_s"] == pytest.approx(0.1, rel=0.01)
    reg = T.default_registry()
    assert reg.get("dlrover_straggler_hosts").value == 1
    assert reg.get("dlrover_stragglers_detected_total").value == 1
    assert reg.get("dlrover_host_step_duration_seconds").labels(
        node="2"
    ).count >= 2
    # recovery: host 2 speeds back up; rolling median falls under the
    # threshold and the verdict clears with a journal event
    t2 = t + 5 * 3.0
    for k in range(1, 12):
        _feed(sm, 0, 50 + 10 * k, t2 + k * 1.0)
        _feed(sm, 1, 50 + 10 * k, t2 + k * 1.0)
        _feed(sm, 2, 50 + 10 * k, t2 + k * 1.0)
    assert sm.straggler_ranks() == []
    assert len(T.default_journal().events("straggler.recovered")) == 1
    assert reg.get("dlrover_straggler_hosts").value == 0


def test_straggler_scorer_needs_two_hosts_and_ignores_replays():
    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

    sm = SpeedMonitor(straggler_ratio=1.5, straggler_window=1)
    t = 1000.0
    for k in range(1, 8):
        _feed(sm, 0, 10 * k, t + k * 5.0)  # slow but ALONE: no verdict
    assert sm.straggler_ranks() == []
    # duplicate/replayed reports (restart) carry no duration signal
    _feed(sm, 1, 10, t + 1.0)
    _feed(sm, 1, 10, t + 1.0)
    _feed(sm, 1, 5, t + 0.5)  # step went backwards: restart replay
    assert sm.host_step_durations().get(1) is None


def test_straggler_state_cleared_on_worker_removal():
    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

    sm = SpeedMonitor(straggler_ratio=1.5, straggler_window=1)
    t = 1000.0
    for k in range(1, 5):
        _feed(sm, 0, 10 * k, t + k * 1.0)
        _feed(sm, 1, 10 * k, t + k * 1.0)
        _feed(sm, 2, 10 * k, t + k * 4.0)
    assert sm.straggler_ranks() == [2]
    sm.remove_running_worker("worker", 2)
    assert sm.straggler_ranks() == []
    assert 2 not in sm.host_step_durations()
    assert T.default_registry().get(
        "dlrover_straggler_hosts"
    ).value == 0


def test_scale_down_prunes_all_per_host_straggler_state():
    """Scale-down pruning (PR 7): evicting several hosts at once drops
    their duration windows, strike counters AND last-report anchors —
    a later re-add of the same node id must start a fresh window, not
    inherit the dead incarnation's cadence."""
    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

    sm = SpeedMonitor(straggler_ratio=1.5, straggler_window=1)
    for i in range(4):
        sm.add_running_worker("worker", i)
    t = 1000.0
    # hosts 0/1 healthy; hosts 2/3 at 4x the median: both flagged
    for k in range(1, 5):
        _feed(sm, 0, 10 * k, t + k * 1.0)
        _feed(sm, 1, 10 * k, t + k * 1.0)
        _feed(sm, 2, 10 * k, t + k * 4.0)
        _feed(sm, 3, 10 * k, t + k * 4.0)
    assert sorted(sm.straggler_ranks()) == [2, 3]
    # the scaler shrinks the job by evicting both stragglers
    sm.remove_running_worker("worker", 2)
    sm.remove_running_worker("worker", 3)
    assert sm.straggler_ranks() == []
    assert set(sm.host_step_durations()) <= {0, 1}
    assert sm.running_workers == {("worker", 0), ("worker", 1)}
    reg = T.default_registry()
    assert reg.get("dlrover_straggler_hosts").value == 0
    assert reg.get("dlrover_training_workers").value == 2
    # node id 2 comes back (a replacement host reusing the rank): its
    # first report must carry NO duration signal — pairing it with the
    # dead incarnation's last report would fabricate a huge step time
    # and instantly re-flag the fresh host
    sm.add_running_worker("worker", 2)
    _feed(sm, 2, 100, t + 100.0)
    assert sm.host_step_durations().get(2) is None
    assert sm.straggler_ranks() == []
    # and from its SECOND report on it scores like everyone else
    _feed(sm, 2, 110, t + 101.0)
    assert sm.host_step_durations().get(2) == pytest.approx(0.1)
    assert sm.straggler_ranks() == []


def test_autoscaler_unions_speed_hint():
    """The cadence scorer's verdicts reach the shrink path alongside
    the network-check list (the `straggler.hint` journal event marks
    the union)."""
    from dlrover_tpu.master.node.job_auto_scaler import (
        AllreduceTrainingAutoScaler,
    )

    captured = {}

    class _Node:
        def __init__(self, rank):
            self.rank_index = rank
            self.type = "worker"
            self.id = rank
            self.is_released = False
            self.relaunchable = True
            self.host_name = f"h{rank}"
            self.name = f"w{rank}"

    class _Mgr:
        def unfinished_nodes(self):
            return [_Node(r) for r in range(4)]

    class _JobMgr:
        _node_managers = {"worker": _Mgr()}

    class _Monitor:
        completed_global_step = 100

        def straggler_ranks(self):
            return [2]

    class _Optimizer:
        _speed_monitor = _Monitor()

        def generate_straggler_shrink_plan(self, stragglers, live,
                                           min_nodes=0):
            captured["stragglers"] = list(stragglers)
            return None  # stop before any scaling machinery

    scaler = AllreduceTrainingAutoScaler(
        _JobMgr(), _Optimizer(), scaler=None,
        straggler_fn=lambda: [3],
    )
    scaler._maybe_shrink_stragglers()
    assert captured["stragglers"] == [2, 3]
    evs = T.default_journal().events("straggler.hint")
    assert len(evs) == 1 and evs[0]["data"]["nodes"] == [2]


# ----------------------------------------------- journal event-name lint
#
# These tests used to carry ~8 hand-rolled ast.walk loops and seven
# near-identical closed-vocabulary sets. ISSUE 15 moved the machinery
# and the vocabularies into tools/dlint (rules/events.py, rules/
# phases.py) — the single source of truth the CLI gate, CI and these
# tests all share. The test NAMES survive because docs/TELEMETRY.md
# and past PR discussions reference them; each is now a thin shim that
# asserts its slice of the dlint run is clean.


import functools

from tools.dlint.core import lint_repo
from tools.dlint.rules import (
    EventNameRule,
    EventVocabularyRule,
    GoodputPhaseRule,
    SpanNameRule,
)
from tools.dlint.rules.events import VOCABULARY


@functools.lru_cache(maxsize=None)
def _lint_findings():
    """One shared whole-repo run for every shim below (single parse +
    walk per file; the whole batch costs well under a second)."""
    res = lint_repo(rules=[EventNameRule, EventVocabularyRule,
                           SpanNameRule, GoodputPhaseRule])
    return tuple(res.findings)


def _assert_clean(findings):
    assert not findings, "\n".join(
        f"{f.location()}: {f.rule}: {f.message}" for f in findings
    )


def _assert_vocabulary_clean(group):
    """The group's namespace is a closed set: no unexpected emission,
    no documented-but-ghost event (see EventVocabularyRule)."""
    prefixes, canonical = VOCABULARY[group]
    assert canonical, f"vocabulary group {group!r} is empty"
    _assert_clean([
        f for f in _lint_findings()
        if f.rule == "event-vocabulary"
        and any(
            f.anchor.startswith(f"unexpected:{p}.")
            or f.anchor.startswith(f"ghost:{p}.")
            for p in prefixes
        )
    ])


def test_journal_event_names_are_snake_case_dotted():
    """Tier-1 typo guard (ISSUE 4): every journal event name used in
    dlrover_tpu/ is a lowercase snake-case dotted constant — a
    misspelled or free-form kind fails HERE, not in a dashboard weeks
    later. (Enforced by dlint's event-names rule; this shim keeps the
    historical entry point.)"""
    _assert_clean([
        f for f in _lint_findings() if f.rule == "event-names"
    ])


def test_preempt_event_names_are_the_canonical_set():
    """The preempt.* journal vocabulary is closed: every record() of a
    preempt event uses exactly one of the documented names, and every
    documented name is actually emitted somewhere. The canonical set
    lives in tools/dlint/rules/events.py (VOCABULARY['preempt'])."""
    _assert_vocabulary_clean("preempt")


def test_sentinel_event_names_are_the_canonical_set():
    """The anomaly.* / rollback.* / quarantine.* vocabulary is closed
    (VOCABULARY['sentinel'])."""
    _assert_vocabulary_clean("sentinel")


def test_serve_event_names_are_the_canonical_set():
    """The serve.* vocabulary is closed (VOCABULARY['serve'])."""
    _assert_vocabulary_clean("serve")


def test_reshard_event_names_are_the_canonical_set():
    """The reshard.* vocabulary is closed (VOCABULARY['reshard'])."""
    _assert_vocabulary_clean("reshard")


def test_spare_event_names_are_the_canonical_set():
    """The spare.* vocabulary is closed (VOCABULARY['spare'], new in
    ISSUE 18 with hot-spare promotion)."""
    _assert_vocabulary_clean("spare")


def test_relay_event_names_are_the_canonical_set():
    """The relay.* vocabulary is closed (VOCABULARY['relay'];
    tier_*/restarted joined in ISSUE 18 with the launcher-owned relay
    lifecycle)."""
    _assert_vocabulary_clean("relay")


def test_control_event_names_are_the_canonical_set():
    """The control.* vocabulary is closed (VOCABULARY['control'])."""
    _assert_vocabulary_clean("control")


def test_report_event_names_are_the_canonical_set():
    """The report.* vocabulary is closed (VOCABULARY['report'])."""
    _assert_vocabulary_clean("report")


def test_ckpt_event_names_are_the_canonical_set():
    """The ckpt.* vocabulary is closed (VOCABULARY['ckpt'])."""
    _assert_vocabulary_clean("ckpt")


def test_lockwatch_event_names_are_the_canonical_set():
    """The lockwatch.* vocabulary is closed (VOCABULARY['lockwatch'],
    new in ISSUE 15 with the runtime lock-order watchdog)."""
    _assert_vocabulary_clean("lockwatch")


def test_brain_event_names_are_the_canonical_set():
    """The brain.* vocabulary is closed (VOCABULARY['brain'], new in
    ISSUE 19 with the explainable resource advisor): plan_proposed /
    plan_adopted / plan_rejected / advisor_started, each with a live
    emitter in brain/advisor.py."""
    _assert_vocabulary_clean("brain")


def test_span_names_are_canonical():
    """ISSUE 8 companion to the event-name lint: every tracing span
    name is a lowercase snake-case (optionally dotted) constant —
    summarize()/dashboards match spans by exact name. (dlint's
    span-names rule.)"""
    _assert_clean([
        f for f in _lint_findings() if f.rule == "span-names"
    ])


def test_goodput_phase_labels_are_canonical():
    """Companion lint (PR 7): a phase label the ledger would reject at
    runtime (ValueError in transition/credit) or a typo'd ``Phase.X``
    member fails here, at lint speed, not mid-drill. (dlint's
    goodput-phases rule.)"""
    _assert_clean([
        f for f in _lint_findings() if f.rule == "goodput-phases"
    ])
