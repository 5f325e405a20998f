"""A CPU rehearsal of the ``resume`` kind at a tiny size: a durable
save, the injected crash, the agent's restart in place, the restore
and the steps after it; for want of a chip not ``correct``."""

import json
import os
import subprocess

from yardstick import cells
from yardstick.kinds import resume

from . import on_two_cores

BENCH = cells.benchmark(os.path.join(cells.CHECKOUT, "BENCHMARK.json"))
LAYER = {"respawn_s": "launcher, master, agent",
         "boot_s": "process bootstrap", "restore_s": "checkpoint"}


def bench_with_a_resume_cell(tmp_path):
    """BENCHMARK.json as it is where it has a resume cell, else with
    one added, as a later PR would add it."""
    bench = json.loads(json.dumps(BENCH))
    for cell in bench["workloads"]:
        if cells.load_cell(cell["name"], bench)[2]["kind"] == "resume":
            name = cell["name"]
            break
    else:
        name = "mistral-7b-l4.resume"
        bench["workloads"].append(
            {"name": name, "config": "mistral-7b-l4",
             "traffic": "resume-3x4096", "chips": 1, "why": "a test"})
        bench["end_to_end"].append(
            {"name": "resume_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": [name]})
        for m in bench["end_to_end"] + bench["per_layer"]:
            m.setdefault("workloads", [
                c["name"] for c in BENCH["workloads"]])
        bench["per_layer"] += [
            {"name": n, "unit": "s", "better": "lower",
             "source": "host_clock", "layer": layer,
             "moves": "resume_s", "workloads": [name]}
            for n, layer in LAYER.items()]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return name, str(path)


def test_resume_rehearsal_restores_what_it_saved(tmp_path):
    name, bench = bench_with_a_resume_cell(tmp_path)
    got = subprocess.run(
        on_two_cores(
            os.path.join(cells.HERE, "run.py"),
            "--workload", name, "--seed", "12345", "--seconds", "15",
            "--trace", "1", "--rehearse", "tiny-llama",
            "--keep", str(tmp_path)),
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 YARDSTICK_BENCHMARK=bench),
    )
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.splitlines()[-1])
    assert line["correct"] is False
    assert (line["attempted"], line["failed"]) == (1, 0)
    assert set(line["metrics"]) == set(LAYER)
    problems = [ln for ln in got.stdout.splitlines()
                if ln.startswith("problem:")]
    # nothing is wrong but the device: the save was read back, the
    # losses went on
    assert len(problems) == 3, problems
    with open(tmp_path / f"{name}.12345.1.report.jsonl") as f:
        events = {}
        for ln in f:
            ev = json.loads(ln)
            events.setdefault(ev["event"], []).append(ev)
    assert [s["restart_count"] for s in events["start"]] == [0, 1]
    assert events["restored"][-1]["start_step"] == (
        events["saved"][-1]["step"])
    assert events["restored"][-1]["checksum"] == (
        events["saved"][-1]["checksum"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    resume_s = (events["first_step"][-1]["done"]
                - events["dying"][-1]["t_death"])
    assert abs(m["respawn_s"] + m["boot_s"] + m["restore_s"]
               - resume_s) < 1e-6
    # nothing of the run is left behind
    assert not os.path.exists(events["built"][-1]["ram_dir"])


def summary(**over):
    """A report as the kind's two incarnations write it."""
    events = {
        "saved": [{"step": 4, "checksum": [1.0, 2.0]}],
        "dying": [{"t_death": 100.0, "rows": [
            {"step": s, "loss": 10.9, "data_id": [s]}
            for s in range(1, 7)]}],
        "restored": [{"start_step": 4, "checksum": [1.0, 2.0]}],
        "first_step": [{"done": 130.0}],
        "steps": [{"rows": [
            {"step": 5, "loss": 10.9, "data_id": [5]},
            {"step": 6, "loss": 10.8, "data_id": [99]}]}],
    }
    events.update(over)
    return resume.summarize(events, {}, 40.0)


def test_summary_of_a_good_resume():
    out = summary()
    assert out["problems"] == [] and out["failed"] == 0
    assert out["values"] == {"resume_s": 30.0}
    assert out["t_window_start"] == 100.0
    assert out["replayed_steps"] == 1


def test_summary_refuses_a_state_that_is_not_the_saved_one():
    out = summary(restored=[{"start_step": 4, "checksum": [1.0, 2.5]}])
    assert any("checksum" in p for p in out["problems"])
    out = summary(restored=[{"start_step": 3, "checksum": [1.0, 2.0]}])
    assert any("restored step 3" in p for p in out["problems"])


def test_summary_refuses_losses_that_do_not_go_on():
    rows = [{"step": 5, "loss": 10.7, "data_id": [5]}]  # a replay
    assert any("replayed" in p for p in summary(
        steps=[{"rows": rows}])["problems"])
    rows = [{"step": 5, "loss": 12.0, "data_id": [77]}]
    assert any("outside" in p for p in summary(
        steps=[{"rows": rows}])["problems"])
    rows = [{"step": 5, "loss": float("nan"), "data_id": [5]}]
    assert any("finite" in p for p in summary(
        steps=[{"rows": rows}])["problems"])


def test_summary_counts_a_resume_past_the_window_as_failed():
    out = summary(first_step=[{"done": 141.0}])
    assert out["failed"] == 1 and out["problems"]
