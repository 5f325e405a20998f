"""The program's own spans, for the readers of a traced run. No JAX.

The program (``dlrover_tpu/telemetry/tracing.py``) writes a span at
every layer boundary of set-up and of a restart, one JSON line each,
into ``spans-<host>-<pid>.jsonl`` under the directory that
``DLROVER_TPU_TRACE_DIR`` names; every process arms itself from that
variable when it imports the module, and does nothing without it.

How a traced run reaches them, with no edit to ``run.py``: it imports
every reader of the cell (``run.py:194-197``) before it launches
(``:218``), and the launcher's environment starts from its own
(``child_env``, ``:53-59``). So a reader that needs the spans calls
``arm()`` when it is imported: in ``run.py``'s process, under
``--trace 1``, that makes a directory and sets the variable, and
launcher, master, agent, worker and coworkers inherit it. A ``--trace
0`` run arms nothing: the end-to-end metrics are measured with
tracing off. A process that merely imports a reader (a test) arms
nothing either. ``run.py`` calls the readers before it exits, and the
directory goes when it does.
"""

import atexit
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile

ENV_TRACE_DIR = "DLROVER_TPU_TRACE_DIR"
RUN_PY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "run.py")


def _traced_run():
    """Whether this process is ``yardstick/run.py --trace 1``."""
    main = getattr(sys.modules.get("__main__"), "__file__", None)
    if main is None or os.path.realpath(main) != os.path.realpath(RUN_PY):
        return False
    argv = sys.argv
    return "--trace=1" in argv or any(
        a == "--trace" and b == "1" for a, b in zip(argv, argv[1:])
    )


def arm():
    """Give the run a directory for the program's spans, unless this
    is no traced run or the variable is already set."""
    if os.environ.get(ENV_TRACE_DIR) or not _traced_run():
        return None
    path = tempfile.mkdtemp(prefix="yardstick_spans_")
    os.environ[ENV_TRACE_DIR] = path
    atexit.register(shutil.rmtree, path, ignore_errors=True)
    return path


def load(path=None):
    """Every span record under the directory (the variable's, unless
    given): one dict a JSON line of every ``spans-*.jsonl``; a line a
    killed writer tore is skipped."""
    path = path or os.environ.get(ENV_TRACE_DIR)
    records = []
    if not path:
        return records
    for name in sorted(glob.glob(os.path.join(path, "spans-*.jsonl"))):
        with open(name, errors="replace") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "name" in rec:
                    records.append(rec)
    return records


def spans(run):
    """The records a reader reads: the run's directory (or, in a
    test, what the run was handed as ``spans``). A reader asks only
    in a traced run that had a worker: if the variable is not set
    then, ``arm()`` did not know the run for one (``run.py`` started
    another way, ``--trace`` spelt another way), and the metrics
    would go missing from the line with nothing said. Say it."""
    if "spans" in run:
        return run["spans"]
    path = os.environ.get(ENV_TRACE_DIR)
    if not path:
        raise RuntimeError(
            f"a traced run with {ENV_TRACE_DIR} not set: "
            "yardstick.program_spans.arm() did not recognise "
            f"{sys.argv!r} as run.py --trace 1, so the program "
            "wrote no spans"
        )
    return load(path)


def of(records, name, pid=None, before=None, after=None):
    """The spans called ``name`` (or, where it ends in ``.``, whose
    name starts with it), of one process if ``pid`` is given, that
    end by ``before`` and start at or after ``after``, by start."""
    picked = [
        r for r in records
        if (r["name"].startswith(name) if name.endswith(".")
            else r["name"] == name)
        and (pid is None or r.get("pid") == pid)
        and (before is None or r["ts"] + r["dur"] <= before)
        and (after is None or r["ts"] >= after)
    ]
    return sorted(picked, key=lambda r: r["ts"])


def worker_pid(events, restarted=False):
    """The pid of the first worker, or of the last restarted one,
    from the report's ``start`` lines; None where there is none."""
    pids = [
        s.get("pid") for s in events.get("start", [])
        if (s["restart_count"] > 0) == restarted
    ]
    return pids[-1 if restarted else 0] if pids else None


def window_of(events):
    """``(start, end)`` of the measured window, or None."""
    window = events.get("window")
    if not window:
        return None
    start = window[-1]["t_window_start"]
    return start, start + window[-1]["seconds"]


def covered(spans):
    """Seconds the spans cover together: overlaps count once."""
    total, reach = 0.0, float("-inf")
    for r in sorted(spans, key=lambda r: r["ts"]):
        lo, hi = max(r["ts"], reach), r["ts"] + r["dur"]
        if hi > lo:
            total += hi - lo
            reach = hi
    return total


def window_median_ms(run, name):
    """Median, in milliseconds, of the first worker's spans called
    ``name`` that start inside the measured window; None where there
    is no window or no such span."""
    pid = worker_pid(run["events"])
    window = window_of(run["events"])
    if pid is None or window is None:
        return None
    took = [r["dur"]
            for r in of(spans(run), name, pid=pid, after=window[0])
            if r["ts"] < window[1]]
    return 1e3 * statistics.median(took) if took else None


def inside_restore(run, name):
    """Summed seconds of the spans called ``name`` inside the
    restarted worker's ``ckpt.restore``; None where it made none."""
    pid = worker_pid(run["events"], restarted=True)
    if pid is None:
        return None
    records = spans(run)
    restores = [r for r in of(records, "ckpt.restore", pid=pid)
                if (r.get("attrs") or {}).get("step") is not None]
    if not restores:
        return None
    whole = restores[-1]
    parts = of(records, name, pid=pid, after=whole["ts"],
               before=whole["ts"] + whole["dur"])
    return sum(r["dur"] for r in parts)
