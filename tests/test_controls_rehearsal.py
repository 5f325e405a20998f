"""``benchmarks/controls.py`` at the tiny sizes on the CPU: the one
script reads a cell's family from its configuration, imports that
family's controls by name and runs them; a rehearsal's rows are marked
and written nowhere. The runs are started together and each case
waits for its own, and the compiler is asked for the least: most of a
run is one process tracing and compiling a tiny model, and the module
has sixty seconds."""

import json
import os
import runpy
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "benchmarks", "controls.py")
#: two thirds of a run's CPU seconds, and the same readings
CHEAP_COMPILES = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_backend_optimization_level=0",
}
JOYAI, SOLAR = "joyai-llm-flash-ep8.steady", "solar-open2-250b-ep32.steady"
NEMOTRON = "nemotron-3-super-120b-a12b-ep64.steady"
FLOAT8 = "the reference in float8"
#: what the float32 reference and the bfloat16 program differ by at
#: these widths and seeds, at most (the solar rehearsal's bound)
AGREES = 0.02
# a seed each at which the unchanged pair is well inside ``AGREES``:
# at 128 positions a reading's noise is of a control's own size
CASES = {
    "tiny-joyai": (JOYAI, 1, ("no shared expert", "no rotation")),
    # the first is given the other operator's leaves (``exchanged``)
    "tiny-solar": (SOLAR, 2, (
        "attention in the delta rule's place", "no shared expert")),
}
RUNS = {
    **{tiny: ("--cell", cell, "--rehearse", tiny, "--seeds", str(seed),
              "--only", "unchanged", FLOAT8, *controls)
       for tiny, (cell, seed, controls) in CASES.items()},
    "probe": ("--cell", SOLAR, "--rehearse", "tiny-solar",
              "--seeds", "2", "--probe", "2"),
    "probe-nemotron": ("--cell", NEMOTRON, "--rehearse", "tiny-nemotron",
                       "--seeds", "2", "--probe", "2"),
}


@pytest.fixture(scope="module")
def finished(tmp_path_factory):
    """``finished(name)``: the exit code, the rows and the errors of
    the run ``name`` of ``RUNS``, which ran in a directory of its own
    and left nothing there."""
    dirs = {name: tmp_path_factory.mktemp("controls") for name in RUNS}
    started = {
        name: subprocess.Popen(
            [sys.executable, SCRIPT, *argv],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=dirs[name], env={**os.environ, **CHEAP_COMPILES})
        for name, argv in RUNS.items()
    }

    def wait(name):
        out, err = started[name].communicate(timeout=240)
        assert os.listdir(dirs[name]) == [], err[-2000:]
        return started[name].returncode, [
            json.loads(ln) for ln in out.splitlines()], err

    yield wait
    for proc in started.values():
        proc.kill()
        proc.wait()


@pytest.mark.parametrize("tiny", list(CASES))
def test_rehearsal_runs_the_familys_controls(tiny, finished):
    _, seed, controls = CASES[tiny]
    rc, rows, err = finished(tiny)
    assert rc == 0, err[-2000:]
    assert [r["control"] for r in rows] == [
        "unchanged", *controls, FLOAT8]
    assert all(r["rehearse"] == tiny and r["platform"] == "cpu"
               for r in rows)
    off = {r["control"]: r["readings"][str(seed)]["difference"]
           for r in rows}
    assert off["unchanged"] < AGREES, off
    for control in controls:
        assert off[control] > AGREES, off


@pytest.mark.parametrize("run,tiny,expert_layers,decays", [
    ("probe", "tiny-solar", 4, 4), ("probe-nemotron", "tiny-nemotron", 5, 11),
])
def test_rehearsal_probes_the_routers_and_the_decay(
        run, tiny, expert_layers, decays, finished):
    rc, rows, err = finished(run)
    assert rc == 0, err[-2000:]
    (row,) = rows
    assert (row["probe"], row["rehearse"]) == (2, tiny)
    assert [r["step"] for r in row["rows"]] == [0, 1]
    for r in row["rows"]:
        # a row an expert layer (of a stack of one-branch blocks the
        # expert layers alone); a delta-rule layer's alpha, or a
        # state-space layer's a, under 1 and every other layer's at 1
        assert len(r["held_rows"]) == len(r["max_over_mean"]) == (
            expert_layers)
        assert min(r["max_over_mean"]) >= 1.0
        assert len(r["decay_min"]) == decays
        assert 0.0 < min(r["decay_min"]) < 1.0
        assert len(r.get("bias_abs_max", [0] * expert_layers)) == (
            expert_layers)
        assert r["loss"] > 0 and r["seconds"] > 0


def test_the_chip_is_asked_for_without_a_rehearsal(
        monkeypatch, tmp_path):
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(
        sys, "argv", [SCRIPT, "--cell", JOYAI, "--seeds", "1"])
    with pytest.raises(SystemExit, match="no TPU"):
        runpy.run_path(SCRIPT, run_name="__main__")
    assert os.listdir(tmp_path) == []
