"""The ``saves`` kind and its three readers: ``save_stall_ms``,
``save_land_s`` and ``save_drag_pct`` on hand-made spans and rows, the
guarantee in ``saves.summarize``, the entries the cell would have, and
one CPU rehearsal of the job end to end."""

import json
import os
import subprocess

import pytest

from yardstick import cells, program_spans
from yardstick.kinds import saves, steady
from yardstick.layer_metrics import (
    save_drag_pct,
    save_land_s,
    save_stall_ms,
)

from . import on_two_cores

CELL = "mistral-7b-l4.saves"
READERS = (save_stall_ms, save_land_s, save_drag_pct)


def bench_with_the_saves_cell():
    """BENCHMARK.json as it is where it has the cell, else with the
    entries a ``benchmark`` PR would append for it (PERF.md section 7
    says why PR 56 did not: the device-to-host copy's own time swings
    the cell's ``tokens_per_s`` by several percent from run to run)."""
    bench = cells.benchmark(os.path.join(cells.CHECKOUT, "BENCHMARK.json"))
    if all(c["name"] != CELL for c in bench["workloads"]):
        bench["workloads"].append(
            {"name": CELL, "config": "mistral-7b-l4",
             "traffic": "saves-3x4096", "chips": 1, "why": "a test"})
        bench["per_layer"] += [
            {"name": r.NAME, "unit": r.UNIT, "better": "lower",
             "source": r.SOURCE, "layer": r.LAYER, "moves": r.MOVES,
             "workloads": [CELL]} for r in READERS]
    return bench


BENCH = bench_with_the_saves_cell()


def span(name, ts, dur, thread="MainThread", pid=20, **attrs):
    rec = {"name": name, "pid": pid, "ts": ts, "dur": dur,
           "thread": thread}
    if attrs:
        rec["attrs"] = attrs
    return rec


def save(step, ts, wait=7.0, lands=27.0, submit_wait=0.0, pid=20,
         new=True):
    """One save as the program writes it: the loop's ``ckpt.stage``
    (11 ms of dispatch and, under back-pressure, ``ckpt.submit_wait``
    inside it) and ``ckpt.wait_staged``; the lane's ``ckpt.serialize``
    over its passes. ``new=False``: a program from before the wait
    had a span (no ``bytes`` on ``ckpt.stage`` either)."""
    stage = 0.011 + submit_wait
    size = {"bytes": 6_808_000_000, "shards": 111} if new else {}
    out = [
        span("ckpt.stage", ts, stage, pid=pid, step=step, **size),
        span("ckpt.write.materialize", ts + stage, wait,
             thread="ckpt-serialize", pid=pid, cpu_s=0.1),
        span("ckpt.write.io", ts + stage + wait, 1.0,
             thread="ckpt-serialize", pid=pid, cpu_s=0.9),
        span("ckpt.serialize", ts + stage, lands - stage,
             thread="ckpt-serialize", pid=pid, step=step, cpu_s=19.0),
    ]
    if submit_wait:
        out.append(span("ckpt.submit_wait", ts + 0.011, submit_wait,
                        pid=pid, step=step, behind=step - 1))
    if new and wait:
        out.append(span("ckpt.wait_staged", ts + stage, wait, pid=pid,
                        step=step))
    return out


def rows(t0, clean, n_before, stall, dragged, n_dragged, n_after):
    """Window rows whose completions are ``clean`` seconds apart,
    then one step held by ``stall``, ``n_dragged`` steps of
    ``dragged`` seconds, and clean ones again."""
    done, out = t0, []
    for length in ([clean] * n_before + [clean + stall]
                   + [dragged] * n_dragged + [clean] * n_after):
        done += length
        out.append({"step": len(out) + 4, "done": done, "loss": 2.0})
    return out


def events(window_rows, t0=100.0, seconds=40.0):
    return {
        "start": [{"restart_count": 0, "pid": 20}],
        "window": [{"t_window_start": t0, "seconds": seconds,
                    "rows": window_rows}],
    }


#: window 100..140; steps of 0.5 s; the 8th retires at 104, there the
#: save: 7.011 s of stall, then 39 steps of 0.51 s while the lane
#: works (it lands at 131), then clean ones
ROWS = rows(100.0, 0.5, 8, 7.011, 0.51, 39, 20)
ONE = save(11, 104.0)


@pytest.mark.parametrize("spans, stall, land, drag", [
    (ONE, 7011.0, 27.0, 2.0),
    # a save begun before the window (warm-up's) and one after its
    # end, another process's, the fill thread's: not the window's
    (ONE + save(3, 60.0, wait=3.0, lands=9.0)
     + save(99, 140.5, wait=1.0, lands=2.0)
     + save(11, 104.0, wait=9.0, pid=21)
     + [span("ckpt.stage", 110.0, 5.0, thread="shm-fill", step=12,
             bytes=1, shards=1)],
     7011.0, 27.0, 2.0),
    # two saves inside, the second behind the first in the lane
    (save(11, 104.0, lands=10.0)
     + save(12, 112.0, wait=4.0, lands=12.0, submit_wait=2.0),
     (7011.0 + 6011.0) / 2, (10.0 + 12.0) / 2, 2.0),
    # the copies were on the host before the loop asked: no wait
    (save(11, 104.0, wait=0.0), 11.0, 27.0, 2.0),
    # a program without ``ckpt.wait_staged``: the dispatch alone is
    # not the stall; the two that need no new span still read
    (save(11, 104.0, new=False), None, 27.0, 2.0),
    # a save that never landed (the lane died)
    ([s for s in ONE if s["name"] != "ckpt.serialize"],
     7011.0, None, None),
    # no save inside the window; no span at all
    (save(3, 60.0), None, None, None),
    ([], None, None, None),
], ids=["one", "others-beside", "two-with-back-pressure", "no-wait",
        "older-program", "never-landed", "outside", "empty"])
def test_the_three_readers_on_hand_made_spans(spans, stall, land, drag):
    run = {"events": events(ROWS), "spans": spans}
    for reader, want in zip(READERS, (stall, land, drag)):
        got = reader.read(run)
        assert got == (None if want is None
                       else pytest.approx(want, rel=1e-6)), reader.NAME


@pytest.mark.parametrize("window_rows, drag", [
    # a fifth slower while the lane works
    (rows(100.0, 0.5, 8, 7.011, 0.6, 33, 20), 20.0),
    # no slower at all
    (rows(100.0, 0.5, 8, 7.011, 0.5, 39, 20), 0.0),
    # the save landed after the last step: no clean step after it,
    # the eight before it are the clean ones
    (rows(100.0, 0.5, 8, 7.011, 0.55, 30, 0), 10.0),
    # no step wholly inside the lane's work
    (rows(100.0, 0.5, 8, 27.5, 0.5, 0, 10), None),
], ids=["a-fifth", "none", "clean-before-only", "no-dragged-step"])
def test_save_drag_pct_is_the_dragged_over_the_clean(window_rows, drag):
    got = save_drag_pct.read(
        {"events": events(window_rows), "spans": ONE})
    assert got == (None if drag is None else pytest.approx(drag))


def test_the_step_the_save_held_is_in_neither_class():
    """The step that spans the stall is 7.5 s long: in the dragged
    class it would be the median of few, in the clean one a lie."""
    few = rows(100.0, 0.5, 8, 7.011, 0.5, 2, 2)
    got = save_drag_pct.read(
        {"events": events(few), "spans": save(11, 104.0, lands=8.5)})
    assert got == pytest.approx(0.0)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.NAME)
def test_reader_says_nothing_without_a_worker_or_a_window(reader):
    whole = events(ROWS)
    for ev in ({}, {"start": whole["start"]},
               {"window": whole["window"]}):
        assert reader.read({"events": ev, "spans": ONE}) is None
    assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (
        "checkpoint", "tokens_per_s", "host_clock")


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.NAME)
def test_a_reader_is_what_its_entry_would_say(reader):
    """``test_every_per_layer_entry_is_its_module``, for readers whose
    entries a later PR adds: found by name, and silent on nothing."""
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == reader.NAME]
    assert cells.metric_module(m["name"]) is reader
    assert m["workloads"] == [CELL] and m["better"] == "lower"
    empty = {"events": {}, "trace": None, "values": {}, "peak": None,
             "cell": BENCH["workloads"][0], "config": {}, "traffic": {}}
    assert reader.read(empty) is None


def test_the_cell_reports_every_metric_without_a_list():
    names = {m["name"] for m in cells.metrics_of(CELL, BENCH["per_layer"])}
    assert {"mfu_pct", "attn_roofline_pct", "device_idle_pct",
            "host_stall_ms"} | {r.NAME for r in READERS} <= names


def test_the_mix_is_the_steady_one_with_two_keys_of_its_own():
    _, _, mix = cells.load_cell(CELL, BENCH)
    _, _, steady_mix = cells.load_cell("mistral-7b-l4.steady", BENCH)
    own = {"save_at_window_step": 8, "save_every": 64}
    assert {k: v for k, v in mix.items() if k in own} == own
    for key, value in steady_mix.items():
        if key not in ("kind", "why"):
            assert mix[key] == value, key
    assert set(mix) == set(steady_mix) | set(own)
    assert cells.kind_module(mix) is saves


# ------------------------------------------------------ the guarantee

SUMS = [1.5, -2.25, 1024.0]


def report(saved=SUMS, read=SUMS, saved_step=11, read_step=11,
           landed=True, n_saves=1):
    ev = events(ROWS)
    ev["built"] = [{"tokens_per_step": 12288}]
    ev["reference"] = [{"ok": True}]
    ev["window"][0].update(compile_requests=0, saves=[
        {"step": saved_step - 64 * (n_saves - 1 - i), "t_save": 104.0,
         "checksum": saved if i == n_saves - 1 else [0.0],
         "landed_inside_window": landed}
        for i in range(n_saves)])
    if read_step is not None or read is not None:
        ev["read_back"] = [{"step": read_step, "checksum": read}]
    return ev


def test_summarize_is_steadys_with_the_guarantee_kept():
    ev = report()
    got = saves.summarize(ev, {"name": CELL}, 40.0)
    assert got["problems"] == []
    assert got["saves"] == 1 and got["landed_inside_window"] is True
    want = steady.summarize(ev, {"name": CELL}, 40.0)
    assert got["values"] == want["values"]
    assert (got["attempted"], got["failed"], got["t_window_start"]) == (
        want["attempted"], want["failed"], want["t_window_start"])
    # the stall is inside the time: 67 counted steps over 40 s
    assert got["values"]["tokens_per_s"] < 0.85 * 12288 / 0.5


@pytest.mark.parametrize("kw, problem", [
    ({"read": None, "read_step": None}, "not read back"),
    ({"read": SUMS[:2] + [1024.5]}, "checksum"),
    ({"read": SUMS[:2]}, "checksum"),
    ({"read_step": 10}, "read back step 10, saved step 11"),
    ({"n_saves": 0}, "no save was begun"),
    # of several saves the last one is the one read back
    ({"n_saves": 3, "saved_step": 139, "read_step": 75},
     "read back step 75, saved step 139"),
], ids=["missing", "one-leaf-off", "a-leaf-short", "another-step",
        "no-save", "not-the-last"])
def test_summarize_refuses_a_missing_or_unequal_read_back(kw, problem):
    got = saves.summarize(report(**kw), {"name": CELL}, 40.0)
    assert len(got["problems"]) == 1 and problem in got["problems"][0]


def test_summarize_takes_the_last_of_several_saves():
    got = saves.summarize(
        report(n_saves=3, saved_step=139, read_step=139),
        {"name": CELL}, 40.0)
    assert got["problems"] == [] and got["saves"] == 3


def test_summarize_keeps_steadys_own_problems():
    ev = report()
    ev["window"][0]["compile_requests"] = 2
    ev["reference"] = [{"ok": False, "program_loss": 1.0,
                        "reference_loss": 2.0, "tolerance": 0.003}]
    got = saves.summarize(ev, {"name": CELL}, 40.0)
    assert len(got["problems"]) == 2
    assert saves.summarize({}, {"name": CELL}, 40.0)["problems"] == [
        "the worker reported no window"]


# ------------------------------------------------------- end to end


def test_saves_rehearsal_saves_reads_back_and_reports(tmp_path):
    """The cell's control flow at a size the CPU holds, traced: tiny
    steps take milliseconds, so the 8th falls inside a 2 s window and
    several saves follow it, 64 steps apart."""
    kept = tmp_path / "spans"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               YARDSTICK_BENCHMARK=str(tmp_path / "BENCHMARK.json"))
    env[program_spans.ENV_TRACE_DIR] = str(kept)
    env.pop("DLROVER_FAULT_INJECT", None)
    got = subprocess.run(
        on_two_cores(
            program_spans.RUN_PY, "--workload", CELL,
            "--seed", str(2 ** 31 + 56), "--seconds", "2",
            "--trace", "1", "--rehearse", "tiny-llama",
            "--keep", str(tmp_path / "keep")),
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.splitlines()[-1])
    problems = [json.loads(ln.split(": ", 1)[1])["what"]
                for ln in got.stdout.splitlines()
                if ln.startswith("problem:")]
    # not correct for want of a chip, and for nothing else: no
    # compilation inside the window, the save read back equal
    assert line["correct"] is False
    assert len(problems) == 3 and sorted(problems)[::2] == [
        "a rehearsal with tiny-llama",
        "the traced run brought no device trace"]
    assert " cpu device(s)" in sorted(problems)[1]
    assert line["failed"] == 0 and line["attempted"] > 8
    for name, unit in (("save_stall_ms", "ms"), ("save_land_s", "s")):
        assert line["metrics"][name]["unit"] == unit
        assert line["metrics"][name]["value"] > 0.0
    (name,) = [n for n in os.listdir(tmp_path / "keep")
               if n.endswith(".report.jsonl")]
    report_lines = [json.loads(ln) for ln in
                    (tmp_path / "keep" / name).read_text().splitlines()]
    (window,) = [r for r in report_lines if r["event"] == "window"]
    (read_back,) = [r for r in report_lines
                    if r["event"] == "read_back"]
    made = window["saves"]
    first = window["rows"][0]["step"]
    assert [s["step"] for s in made] == [
        first + 7 + 64 * i for i in range(len(made))]
    assert made and read_back["step"] == made[-1]["step"]
    assert read_back["checksum"] == made[-1]["checksum"]
    assert window["compile_requests"] == 0
    # every save is a ckpt.stage of the worker's main thread, inside
    # the window, with the size it staged
    records = program_spans.load(str(kept))
    stages = [r for r in program_spans.of(records, "ckpt.stage")
              if r["thread"] == "MainThread"]
    assert [r["attrs"]["step"] for r in stages] == [
        s["step"] for s in made]
    assert all(r["attrs"]["bytes"] == window["state_bytes"] + 4
               for r in stages)
    lanes = program_spans.of(records, "ckpt.serialize")
    assert len(lanes) == len(made)
    assert all("cpu_s" in r["attrs"] for r in lanes)
