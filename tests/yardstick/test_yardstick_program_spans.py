"""The channel from a traced run to the program's spans, and the nine
readers on hand-made spans; one traced rehearsal end to end."""

import json
import os
import subprocess
import sys

import pytest

from yardstick import cells, program_spans
from yardstick.layer_metrics import (
    chip_open_s,
    data_ring_wait_ms,
    launch_s,
    report_step_ms,
    restore_decode_s,
    restore_device_put_s,
    restore_digest_s,
    restore_fetch_s,
    step_program_s,
)

from . import on_two_cores

BENCH = cells.benchmark(os.path.join(cells.CHECKOUT, "BENCHMARK.json"))
NEW = ("launch_s", "chip_open_s", "step_program_s",
       "data_ring_wait_ms", "report_step_ms")


# ------------------------------------------------------------------ arm


def _arm_as(monkeypatch, main_file, argv):
    monkeypatch.setattr(sys.modules["__main__"], "__file__", main_file,
                        raising=False)
    monkeypatch.setattr(sys, "argv", ["run.py"] + argv)
    return program_spans.arm()


@pytest.mark.parametrize("main_file, argv", [
    (program_spans.RUN_PY, ["--trace", "0"]),
    (program_spans.RUN_PY, ["--seed", "1"]),
    (program_spans.RUN_PY, ["--seconds", "1", "--trace"]),
    ("/somewhere/pytest/__main__.py", ["--trace", "1"]),
], ids=["trace-0", "no-trace", "trace-last", "not-run-py"])
def test_arm_sets_nothing_without_a_traced_run(
        monkeypatch, main_file, argv):
    monkeypatch.delenv(program_spans.ENV_TRACE_DIR, raising=False)
    assert _arm_as(monkeypatch, main_file, argv) is None
    assert program_spans.ENV_TRACE_DIR not in os.environ


def test_arm_does_not_override_a_set_variable(monkeypatch, tmp_path):
    monkeypatch.setenv(program_spans.ENV_TRACE_DIR, str(tmp_path))
    assert _arm_as(
        monkeypatch, program_spans.RUN_PY, ["--trace", "1"]) is None
    assert os.environ[program_spans.ENV_TRACE_DIR] == str(tmp_path)


def test_arm_gives_a_traced_run_a_directory(monkeypatch, tmp_path):
    monkeypatch.delenv(program_spans.ENV_TRACE_DIR, raising=False)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    removed = []
    monkeypatch.setattr(
        program_spans.atexit, "register",
        lambda fn, *a, **kw: removed.append((fn, a, kw)),
    )
    try:
        path = _arm_as(
            monkeypatch, program_spans.RUN_PY, ["--trace", "1"])
        assert os.environ[program_spans.ENV_TRACE_DIR] == path
        assert os.path.dirname(path) == str(tmp_path)
        assert os.path.basename(path).startswith("yardstick_spans_")
        ((fn, args, kw),) = removed  # and its removal at exit
        fn(*args, **kw)
        assert not os.path.exists(path)
    finally:
        os.environ.pop(program_spans.ENV_TRACE_DIR, None)


def test_load_skips_torn_lines_and_of_picks(tmp_path):
    (tmp_path / "spans-h-1.jsonl").write_text(
        json.dumps({"name": "a.x", "pid": 1, "ts": 5.0, "dur": 1.0})
        + "\n" + '{"name": "a.y", "pid": 1, "ts"'  # torn by a kill
    )
    (tmp_path / "spans-h-2.jsonl").write_text(
        json.dumps({"name": "a.y", "pid": 2, "ts": 2.0, "dur": 1.0})
        + "\n\n" + json.dumps([1, 2]) + "\n"
    )
    (tmp_path / "journal.jsonl").write_text('{"name": "not.a.span"}\n')
    recs = program_spans.load(str(tmp_path))
    assert [r["name"] for r in recs] == ["a.x", "a.y"]
    assert program_spans.load(str(tmp_path / "nowhere")) == []
    of = program_spans.of
    assert [r["pid"] for r in of(recs, "a.")] == [2, 1]  # by start
    assert of(recs, "a") == []
    assert [r["pid"] for r in of(recs, "a.x")] == [1]
    assert of(recs, "a.", pid=2, after=2.5) == []
    assert [r["pid"] for r in of(recs, "a.", before=3.0)] == [2]
    assert program_spans.covered(recs) == 2.0
    assert program_spans.covered(recs + recs) == 2.0


# -------------------------------------------------------------- readers


def span(name, of_pid, ts, dur, **attrs):
    rec = {"name": name, "pid": of_pid, "ts": ts, "dur": dur}
    if attrs:
        rec["attrs"] = attrs
    return rec


#: a steady run: launcher 10, worker 20, window 100..140
STEADY_EVENTS = {
    "start": [{"restart_count": 0, "pid": 20}],
    "window": [{"t_window_start": 100.0, "seconds": 40.0}],
}
STEADY_SPANS = [
    span("launch.run", 10, 1.0, 150.0),
    span("launch.master_start", 10, 1.1, 0.4),
    span("agent.rendezvous", 10, 2.0, 0.5, round=1, world=1),
    span("agent.spawn", 10, 2.6, 0.1, restart_count=0, pid=20),
    span("launch.run", 11, 0.5, 9.0),  # another job's launcher
    span("boot.compile_cache_setup", 20, 5.0, 0.01),
    span("boot.backend_open", 20, 5.1, 12.5, platform="tpu"),
    span("boot.backend_open", 21, 5.1, 99.0),  # a coworker's
    span("xla.trace", 20, 20.0, 1.0),
    span("xla.lower", 20, 21.0, 2.0),
    span("xla.backend_compile", 20, 23.0, 10.0),
    span("xla.cache_read", 20, 23.5, 4.0),  # inside the compile
    span("xla.backend_compile", 20, 99.5, 1.0),  # ends in the window
    span("xla.trace", 21, 30.0, 50.0),
    span("data.fetch", 20, 90.0, 7.0),  # before the window
    span("data.fetch", 20, 101.0, 0.002),
    span("data.fetch", 20, 102.0, 0.004),
    span("data.fetch", 20, 103.0, 0.300),
    span("data.fetch", 20, 141.0, 5.0),  # after it
    span("train.report_step", 20, 95.0, 1.0),
    span("train.report_step", 20, 101.5, 0.0001),
    span("train.report_step", 20, 102.5, 0.0003),
]

#: a resume run: worker 20 dies, worker 30 restores
RESUME_EVENTS = {
    "start": [{"restart_count": 0, "pid": 20},
              {"restart_count": 1, "pid": 30}],
}
RESUME_SPANS = [
    span("ckpt.restore", 20, 10.0, 0.1),  # nothing to restore yet
    span("ckpt.restore.select", 20, 10.0, 0.1),
    span("ckpt.restore.fetch", 20, 10.05, 0.01, bytes=1),
    span("ckpt.restore", 30, 200.0, 39.0, step=4, tier="ram"),
    span("ckpt.restore.select", 30, 200.0, 0.5),
    span("ckpt.restore.digest", 30, 201.0, 9.0, bytes=50),
    span("ckpt.restore.digest", 30, 210.0, 8.0, bytes=50),
    span("ckpt.restore.fetch", 30, 218.0, 6.0, bytes=100),
    span("ckpt.restore.decode", 30, 224.0, 5.0, bytes=100),
    span("ckpt.restore.device_put", 30, 229.0, 1.5, bytes=60),
    span("ckpt.restore.device_put", 30, 231.0, 2.5, bytes=40),
    span("ckpt.restore.fetch", 30, 300.0, 77.0),  # a later read
]


@pytest.mark.parametrize("reader, events, spans, value", [
    (launch_s, STEADY_EVENTS, STEADY_SPANS, 2.7 - 1.0),
    (chip_open_s, STEADY_EVENTS, STEADY_SPANS, 12.5),
    (chip_open_s, STEADY_EVENTS, STEADY_SPANS + [
        span("boot.distributed_init", 20, 5.0, 0.5)], 13.0),
    (step_program_s, STEADY_EVENTS, STEADY_SPANS, 13.0),
    (data_ring_wait_ms, STEADY_EVENTS, STEADY_SPANS, 4.0),
    (report_step_ms, STEADY_EVENTS, STEADY_SPANS, 0.2),
    (restore_fetch_s, RESUME_EVENTS, RESUME_SPANS, 6.0),
    (restore_digest_s, RESUME_EVENTS, RESUME_SPANS, 17.0),
    (restore_decode_s, RESUME_EVENTS, RESUME_SPANS, 5.0),
    (restore_device_put_s, RESUME_EVENTS, RESUME_SPANS, 4.0),
], ids=lambda x: getattr(x, "NAME", None))
def test_reader_on_hand_made_spans(reader, events, spans, value):
    assert reader.read({"events": events, "spans": spans}) == (
        pytest.approx(value))


@pytest.mark.parametrize("reader", [
    launch_s, chip_open_s, step_program_s, data_ring_wait_ms,
    report_step_ms, restore_fetch_s, restore_digest_s,
    restore_decode_s, restore_device_put_s,
], ids=lambda r: r.NAME)
def test_reader_says_nothing_where_its_spans_are_missing(reader):
    """As on a parent commit whose program has no such span."""
    other = [span("rpc.get_task", 20, 1.0, 1.0),
             span("rpc.get_task", 30, 1.0, 1.0)]
    for events in ({}, STEADY_EVENTS, RESUME_EVENTS):
        for spans in ([], other):
            assert reader.read(
                {"events": events, "spans": spans}) is None
    assert reader.MOVES in ("setup_s", "tokens_per_s", "resume_s")
    assert reader.SOURCE == "host_clock"


@pytest.mark.parametrize("reader", [
    launch_s, chip_open_s, step_program_s, data_ring_wait_ms,
    report_step_ms, restore_fetch_s,
], ids=lambda r: r.NAME)
def test_reader_speaks_up_in_a_traced_run_that_was_not_armed(
        monkeypatch, reader):
    """A run with a worker and no span directory (``run.py`` started
    some way ``arm()`` does not know) must not just lose the metrics."""
    monkeypatch.delenv(program_spans.ENV_TRACE_DIR, raising=False)
    events = (RESUME_EVENTS if reader.MOVES == "resume_s"
              else STEADY_EVENTS)
    with pytest.raises(RuntimeError, match="did not recognise"):
        reader.read({"events": events})
    assert reader.read({"events": {}}) is None  # no worker: not asked


def test_the_five_new_metrics_are_entries_of_every_cell():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert "workloads" not in entries[name]
        assert entries[name]["better"] == "lower"
    assert [m["name"] for m in BENCH["per_layer"]
            if m["name"] in NEW] == list(NEW)


# ------------------------------------------------------- end to end


def test_traced_rehearsal_carries_the_five_new_metrics(tmp_path):
    cell = next(c["name"] for c in BENCH["workloads"]
                if c["chips"] == 1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    env.pop(program_spans.ENV_TRACE_DIR, None)
    got = subprocess.run(
        on_two_cores(
            program_spans.RUN_PY, "--workload", cell,
            "--seed", str(2 ** 31 + 26), "--seconds", "2",
            "--trace", "1", "--rehearse", "tiny-llama"),
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.splitlines()[-1])
    metrics = line["metrics"]
    assert set(NEW) <= set(metrics), sorted(metrics)
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    for name in NEW:
        assert metrics[name]["unit"] == units[name]
        assert metrics[name]["value"] > 0
    # the run took its span directory away with it
    assert not [n for n in os.listdir(tmp_path)
                if n.startswith("yardstick_spans_")]
