"""Measure the span-tracing primitive's cost (ISSUE 4 acceptance).

Prints ONE JSON line::

    {"metric": "trace_overhead_disabled_ns", "value": N, "unit": "ns",
     "disabled_ns": N, "enabled_ring_ns": N, "enabled_file_ns": N,
     "gc_hook_ns": N, "gc_span_file_ns": N, "watchdog_tick_ns": N,
     "wait_staged_idle_ns": N, "pass_lt_1us_disabled": true, ...}

The budget that matters is the DISABLED path: span sites stay wired
into the train step, RPC handler, and checkpoint lanes permanently, so
``with span(...)`` with tracing off must cost well under 1 µs (it is a
module-global check plus a shared no-op context manager — no
allocation). The enabled numbers size what turning tracing on costs
per span: ring-only (a dict build + deque append) and write-through
(one ``os.write`` of a JSON line). Beside them, what ISSUE 38 added:
``gc_hook_ns``, what the collector's hook adds to one collection that
leaves no span (a quick one of the youngest generation: two calls, two
clock readings, a profiler annotation where jax is imported: not
here); ``gc_span_file_ns``, what it adds to one that does (generation
1, written through); and ``watchdog_tick_ns``, one check of the hang
detector's watchdog while no step is late (it wakes one to four times
a step, on its own thread, whether tracing is on or off). And what
ISSUE 56 added: ``wait_staged_idle_ns``, one
``FlashCheckpointer.wait_staged()`` with nothing in flight (the last
save's copies long on the host), which a loop whose step donates its
state calls before every dispatch: no span, no histogram, tracing on
or off.

Pure-Python overhead: jax is imported only for that last figure (the
checkpointer's module needs it), after the others are taken.
"""

import gc
import json
import os
import shutil
import sys
import tempfile
import time

from dlrover_tpu.fault_tolerance.hanging_detector import HangingDetector
from dlrover_tpu.telemetry import tracing


def _per_call_ns(n: int, fn) -> float:
    t0 = time.perf_counter()
    fn(n)
    return (time.perf_counter() - t0) / n * 1e9


def _spin_disabled(n: int):
    span = tracing.span
    for _ in range(n):
        with span("bench.disabled"):
            pass


def _spin_enabled(n: int):
    span = tracing.span
    for _ in range(n):
        with span("bench.enabled"):
            pass


def _collect(generation: int):
    def spin(n: int):
        collect = gc.collect
        for _ in range(n):
            collect(generation)
    return spin


def _gc_hook_ns(generation: int, n: int, trace_dir=None) -> float:
    """What the hook adds to one ``gc.collect(generation)``: on less
    off, the collector's own automatic runs held off meanwhile."""
    gc.collect()
    gc.disable()
    try:
        tracing.disable()
        _collect(generation)(1_000)
        off = _per_call_ns(n, _collect(generation))
        tracing.enable(trace_dir=trace_dir, capacity=4096)
        _collect(generation)(1_000)
        on = _per_call_ns(n, _collect(generation))
        tracing.disable()
    finally:
        gc.enable()
    return on - off


def _watchdog_tick_ns(n: int) -> float:
    """One ``_check_once`` of a detector that knows its cadence (a
    full history of 50 durations) while the next step is not late."""
    now = [1000.0]
    det = HangingDetector(clock=lambda: now[0])
    for step in range(51):
        det.record_step(step)
        now[0] += 0.34

    def spin(k: int):
        check = det._check_once
        for _ in range(k):
            check()

    now[0] -= 0.2
    spin(1_000)
    return _per_call_ns(n, spin)


def _wait_staged_idle_ns(n: int) -> float:
    """One ``wait_staged()`` after a save whose snapshot is on the
    host: the site a donating loop crosses every step."""
    import numpy as np

    from dlrover_tpu.trainer.checkpoint import FlashCheckpointer

    tmp = tempfile.mkdtemp(prefix="trace_overhead_ckpt_")
    ckpt = FlashCheckpointer(
        os.path.join(tmp, "persist"), ram_dir=os.path.join(tmp, "ram"),
        persist_interval=0, use_orbax=False,
    )
    try:
        ckpt.save(1, {"w": np.zeros(8, np.float32)})
        ckpt.wait()

        def spin(k: int):
            wait = ckpt.wait_staged
            for _ in range(k):
                wait()

        spin(10_000)
        return _per_call_ns(n, spin)
    finally:
        ckpt.close()
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    # warm the function paths before any measurement
    tracing.disable()
    _spin_disabled(10_000)
    disabled_ns = _per_call_ns(1_000_000, _spin_disabled)

    tracing.clear()
    tracing.enable(capacity=4096)
    _spin_enabled(10_000)
    ring_ns = _per_call_ns(200_000, _spin_enabled)
    tracing.disable()

    tmp = tempfile.mkdtemp(prefix="trace_overhead_")
    try:
        tracing.clear()
        tracing.enable(trace_dir=tmp, capacity=4096)
        _spin_enabled(1_000)
        file_ns = _per_call_ns(50_000, _spin_enabled)
        tracing.disable()
        span_files = [
            f for f in os.listdir(tmp) if f.startswith("spans-")
        ]
        gc_span_file_ns = _gc_hook_ns(1, 20_000, trace_dir=tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    gc_hook_ns = _gc_hook_ns(0, 100_000)
    watchdog_tick_ns = _watchdog_tick_ns(100_000)
    # last: it imports jax, and the figures above are without it
    wait_staged_idle_ns = _wait_staged_idle_ns(1_000_000)

    print(json.dumps({
        "metric": "trace_overhead_disabled_ns",
        "value": round(disabled_ns, 1),
        "unit": "ns",
        "disabled_ns": round(disabled_ns, 1),
        "enabled_ring_ns": round(ring_ns, 1),
        "enabled_file_ns": round(file_ns, 1),
        "gc_hook_ns": round(gc_hook_ns, 1),
        "gc_span_file_ns": round(gc_span_file_ns, 1),
        "watchdog_tick_ns": round(watchdog_tick_ns, 1),
        "wait_staged_idle_ns": round(wait_staged_idle_ns, 1),
        "pass_lt_1us_disabled": disabled_ns < 1000.0,
        "span_files_written": len(span_files),
        "python": sys.version.split()[0],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
