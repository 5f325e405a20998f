#!/usr/bin/env python3
"""One run of one cell of the yardstick.

    python yardstick/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Starts the elastic launcher on ``yardstick/worker.py``, as a user
starts a job, waits for it, reads the report the worker wrote, and
prints as its last line the one JSON object the benchmark's contract
fixes. Lines before it say what the run saw (README.md).

This script never initialises JAX: the worker under the launcher
needs the chips, and a chip belongs to one process. What the device
was, it learns from the worker's report. It exits 1 and prints no
result when the worker found no TPU, or not the cell's number of
chips, or a device kind without published peaks; ``--rehearse
<tiny configuration>`` runs the cell's control flow at a size a CPU
holds and ends ``"correct": false``.
"""

import time

T_RUN_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import uuid  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, CHECKOUT)

from yardstick import cells  # noqa: E402

#: every process this run starts carries it, so that every one can be
#: found again and stopped, whoever its parent has become
MARK = ("YARDSTICK_RUN", uuid.uuid4().hex)
#: a run that has not ended by then is stopped (the contract allows a
#: cell's first run in a checkout 1200 s)
HARD_LIMIT_S = 1150


def say(line, **fields):
    print(f"{line}: {json.dumps(fields)}", flush=True)


def child_env(extra):
    env = dict(os.environ, **extra)
    env[MARK[0]] = MARK[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [CHECKOUT] + [p for p in [env.get("PYTHONPATH")] if p]
    )
    return env


def stop_everything_started():
    """SIGKILL whatever still carries this run's mark."""
    needle = f"{MARK[0]}={MARK[1]}".encode()
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/environ", "rb") as f:
                if needle in f.read().split(b"\0"):
                    os.kill(int(name), signal.SIGKILL)
        except (OSError, ValueError):
            continue


def read_reports(path):
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except OSError:
        return []


def tail(path, n=80, width=400):
    try:
        with open(path, errors="replace") as f:
            return "".join(
                ln if len(ln) <= width else ln[:width] + "...\n"
                for ln in f.readlines()[-n:]
            )
    except OSError as e:
        return f"<{path}: {e}>"


def cache_dir():
    """Where the program keeps its caches: JAX's variable as given,
    else the fixed directory in the checkout. Not created here."""
    from dlrover_tpu.common.cachedir import default_cache_dir

    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or default_cache_dir())


def launch(args, traffic, scratch):
    """The launcher a user runs, as a module of this tree, one worker
    process driving all of the cell's chips."""
    log = os.path.join(scratch, "launcher.log")
    report = os.path.join(scratch, "report.jsonl")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
        "--standalone", "--nnodes", "1:1",
        "--max_restarts", str(traffic.get("max_restarts", 0)),
        "--monitor_interval", "1",
        os.path.join(HERE, "worker.py"), "--",
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", str(args.trace),
        "--report", report, "--scratch", scratch,
    ]
    if args.rehearse:
        cmd += ["--rehearse", args.rehearse]
    with open(log, "w") as f:
        proc = subprocess.Popen(
            cmd, cwd=CHECKOUT, env=child_env(traffic.get("env", {})),
            stdout=f, stderr=subprocess.STDOUT,
        )
        try:
            rc = proc.wait(timeout=HARD_LIMIT_S)
        except subprocess.TimeoutExpired:
            rc = f"no end after {HARD_LIMIT_S}s"
        finally:
            stop_everything_started()
            proc.wait()
    return rc, read_reports(report), log


def by_event(reports):
    events = {}
    for r in reports:
        events.setdefault(r["event"], []).append(r)
    return events


def say_what_was_seen(events):
    for start in events.get("start", []):
        say("device", **{k: start[k] for k in (
            "restart_count", "platform", "device_kind",
            "device_count", "compile_cache_dir")})
    for prog in events.get("step_program", []):
        tuning = prog.get("tuning") or {}
        say("step_program",
            restart_count=prog["restart_count"],
            blocks=[tuning.get("block_q"), tuning.get("block_k")],
            blocks_source=tuning.get("source"),
            cache_hits=prog["cache_hits"],
            cache_requests=prog["cache_requests"],
            compile_secs=prog["compile_secs"],
            kernel_in_step=prog["kernel_in_step"],
            collectives=prog["collectives"])
    for ref in events.get("reference", []):
        say("reference", **{k: ref[k] for k in (
            "program_loss", "reference_loss", "difference",
            "tolerance", "ok")})
    for name in ("warmup", "window", "steps"):
        for ev in events.get(name, []):
            rows = ev["rows"]
            say(name, restart_count=ev["restart_count"],
                steps=[r["step"] for r in rows],
                step_secs=[round(b["done"] - a["done"], 4)
                           for a, b in zip(rows, rows[1:])],
                losses=[r["loss"] and round(r["loss"], 4) for r in rows],
                compile_requests=ev.get("compile_requests"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", default=None,
                    help="a tiny configuration to run the cell's "
                         "control flow with; never correct")
    ap.add_argument("--keep", default=None,
                    help="copy the launcher's log and the worker's "
                         "report into this directory")
    args = ap.parse_args()
    try:
        bench = cells.benchmark()
        cell, config, traffic = cells.load_cell(
            args.workload, bench, rehearse=args.rehearse
        )
        kind = cells.kind_module(traffic)
        end_to_end = cells.metrics_of(cell["name"], bench["end_to_end"])
        readers = [
            (m, cells.metric_module(m["name"]))
            for m in cells.metrics_of(cell["name"], bench["per_layer"])
        ]
    except cells.UnknownName as e:
        print(f"yardstick: {e}", file=sys.stderr)
        return 2

    try:
        cache = cache_dir()
    except ImportError as e:
        print(f"yardstick: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    try:
        entries = len([n for n in os.listdir(cache)
                       if not n.startswith(".")])
    except OSError:
        entries = 0
    say("compile_cache", dir=cache, entries_at_start=entries,
        t_run_start=T_RUN_START)
    scratch = tempfile.mkdtemp(prefix="yardstick_")
    ram_dirs = set()
    try:
        rc, reports, log = launch(args, traffic, scratch)
        events = by_event(reports)
        ram_dirs = {e["ram_dir"] for e in events.get("built", [])}
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            stem = f"{cell['name']}.{args.seed}.{args.trace}"
            for src, ext in ((log, "log"),
                             (os.path.join(scratch, "report.jsonl"),
                              "report.jsonl")):
                if os.path.exists(src):
                    shutil.copy(src, os.path.join(
                        args.keep, f"{stem}.{ext}"))
        say_what_was_seen(events)
        starts = events.get("start", [])
        refused = events.get("refused", [])
        if rc != 0 or not starts or refused:
            print(f"----- last lines of the launcher's log (rc {rc})",
                  file=sys.stderr)
            print(tail(log), file=sys.stderr)
            for r in refused:
                print(f"yardstick: refused: {r['reason']}",
                      file=sys.stderr)
            return 1
    finally:
        stop_everything_started()
        shutil.rmtree(scratch, ignore_errors=True)
        for d in ram_dirs:
            # the RAM tier's own default place, named after this run
            if os.path.basename(scratch) in d:
                shutil.rmtree(d, ignore_errors=True)

    device = {
        "platform": starts[-1]["platform"],
        "kind": starts[-1]["device_kind"],
        "count": starts[-1]["device_count"],
        "memory_peak_bytes":
            events["final"][-1]["peak_bytes_in_use"],
    }
    summary = kind.summarize(events, cell, args.seconds)
    problems = list(summary["problems"])
    if args.rehearse:
        problems.append(f"a rehearsal with {args.rehearse}")
    if (device["platform"], device["count"]) != ("tpu", cell["chips"]):
        problems.append(f"ran on {device['count']} "
                        f"{device['platform']} device(s)")
    values = dict(summary.get("values", {}))
    if "t_window_start" in summary:
        values["setup_s"] = summary["t_window_start"] - T_RUN_START
    trace = events.get("trace", [None])[-1]
    metrics = {}
    if args.trace:
        run = {
            "events": events, "cell": cell, "config": config,
            "traffic": traffic, "values": values, "trace": trace,
            "peak": (None if args.rehearse
                     else cells.peak_of(device["kind"])),
        }
        for m, reader in readers:
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {
                    "value": value, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        else:
            problems.append("the traced run brought no device trace")
    else:
        for m in end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
            else:
                problems.append(f"no {m['name']}")
    for p in problems:
        say("problem", what=p)
    line = {
        "correct": not problems,
        "attempted": summary.get("attempted", 0),
        "failed": summary.get("failed", 0),
        "metrics": metrics, "device": device,
    }
    if args.trace and trace is not None:
        line["breakdown"] = {
            "device_ops": [[n, t] for n, t, _ in trace["ops"][:10]],
            "idle_gaps": trace["idle_gaps"],
        }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
