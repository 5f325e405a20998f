"""Persistent XLA compilation cache: make warm restarts cheap.

Why this exists (SURVEY §7 hard part #1): the reference's failover
design restarts training processes in place precisely to avoid paying
re-setup costs (dlrover/python/elastic_agent/torch/training.py:441
_restart_workers). On TPU the dominant re-setup cost is neither the
process fork nor the rendezvous — it is XLA re-compiling the training
step (tens of seconds at 1B scale, minutes at 7B). A restarted process
traces the same program over the same mesh, so the compile is 100%
redundant; JAX's persistent compilation cache turns it into a
disk read.

Deployment shape: every worker an agent spawns shares one host-local
directory that OUTLIVES the worker process — a restarted worker hits
the executables its predecessor compiled. Where it lives is
common/cachedir.py's one rule: ``JAX_COMPILATION_CACHE_DIR`` as given,
else a fixed directory in the checkout. The cache key covers the HLO,
the compile options, and the device topology, so a world-size change
after elasticity simply misses the cache and compiles fresh (correct,
just cold); a same-topology restart — the common failover case:
process crash, hang recovery, preemption resume on the same hosts —
hits it.

Cold vs warm restart→first-new-step: an earlier chip run, not
reproduced; ``chip_smoke.py``'s resume phase prints both.
"""

import contextlib
import os
import time
from typing import Dict, Iterator, Optional

from dlrover_tpu.common.cachedir import (
    ENV_JAX_CACHE_DIR,
    resolve_cache_dir,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import counter, gauge, record, tracing

#: compiles faster than this are not cached (jax's default 1s floor
#: would skip small-but-many programs whose SUM is the restart tax)
ENV_MIN_COMPILE_SECS = "DLROVER_TPU_COMPILE_CACHE_MIN_SECS"


def setup_compilation_cache() -> Optional[str]:
    """Enable jax's persistent compilation cache; returns the directory
    or None when the default cannot be trusted.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as given — jax
    read it at import and this function names no other directory.
    Unset, the cache goes to the fixed directory in the checkout,
    after the ownership check (entries are executables this process
    will LOAD: train cold instead of trusting a loose dir). Must run
    before the first ``jit`` executes — ``init_from_env`` calls it, so
    agent-launched workers get it for free; standalone scripts can
    call it directly.
    """
    import jax

    if tracing.enabled():
        trace_compiles()
    cache_dir = resolve_cache_dir()
    if cache_dir is None:
        logger.error("compilation cache disabled (untrusted dir)")
        return None
    if not os.environ.get(ENV_JAX_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.getenv(ENV_MIN_COMPILE_SECS, "0.1")),
    )
    # size floor off: the restart path re-runs EVERY program, small
    # ones included (jax_compilation_cache_max_size stays at its
    # default, bounding growth)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    logger.info("persistent compilation cache at %s", cache_dir)
    global _armed_dir, _armed_entries
    _armed_dir = cache_dir
    _armed_entries = cache_entries(cache_dir)
    gauge(
        "dlrover_compile_cache_entries",
        "Executables in the persistent compilation cache",
    ).set(_armed_entries)
    record(
        "compile_cache.armed", dir=cache_dir, entries=_armed_entries,
    )
    return cache_dir


#: jax's own duration events -> the span each becomes. The backend
#: compile's event wraps the persistent cache's lookup, so on a hit
#: ``xla.cache_read`` lies inside ``xla.backend_compile``.
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    "/jax/core/compile/backend_compile_duration": "xla.backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "xla.cache_read",
}
_compiles_traced = False


def _compile_span(event: str, duration: float, **kw) -> None:
    name = _COMPILE_SPANS.get(event)
    if name is not None:
        attrs = {"event": event}
        if "fun_name" in kw:
            attrs["fun_name"] = kw["fun_name"]
        # jax reports when the work ends: it began ``duration`` ago
        tracing.add_span(name, time.time() - duration, duration, attrs)


def trace_compiles() -> None:
    """Turn every trace, lowering, backend compile and persistent
    cache read jax makes in this process into a span, tagged with the
    step ``tracing.set_step`` last named: which step recompiled, from
    inside the program. One listener a process, however often called;
    a no-op site by site while tracing is off."""
    global _compiles_traced
    if not _compiles_traced:
        from jax import monitoring

        _compiles_traced = True
        monitoring.register_event_duration_secs_listener(_compile_span)


@contextlib.contextmanager
def cache_events() -> Iterator[Dict[str, int]]:
    """Count jax's own persistent-cache events inside the block:
    ``{"requests": n, "hits": m}`` for the programs compiled there
    (a request that is not a hit compiled). Wrap
    exactly the compile that matters (the train step's) — a restored
    worker also compiles small programs its predecessor never did,
    so a directory-wide count would read its warm step as a miss."""
    from jax import monitoring

    seen = {"requests": 0, "hits": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            seen["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    monitoring.register_event_listener(listener)
    try:
        yield seen
    finally:
        monitoring.unregister_event_listener(listener)


def cache_entries(cache_dir: str) -> int:
    """Number of cached executables (drill/observability helper)."""
    try:
        return sum(
            1 for n in os.listdir(cache_dir)
            if not n.startswith(".")
        )
    except FileNotFoundError:
        return 0


# -- hit/miss telemetry ------------------------------------------------
# jax gives no per-program cache-hit callback, but the restart question
# the telemetry must answer is coarser: did THIS incarnation's first
# jit come from the warm pool (entry count unchanged) or compile fresh
# (new entries persisted)? setup_compilation_cache snapshots the armed
# entry count; report_first_compile classifies the delta after the
# first step and journals it — the e2e warm-restart drill reads the
# hit/miss straight off the timeline.

_armed_dir: Optional[str] = None
_armed_entries: int = 0


def report_first_compile(
    first_step_s: Optional[float] = None,
) -> Optional[str]:
    """Classify this process's first-jit outcome against the armed
    cache; returns "hit"/"miss" (None when the cache is not armed).
    Call once after the first jitted step has completed."""
    if _armed_dir is None:
        return None
    entries = cache_entries(_armed_dir)
    new = max(0, entries - _armed_entries)
    outcome = "miss" if new > 0 else "hit"
    counter(
        "dlrover_compile_cache_events_total",
        "First-jit persistent-cache outcomes", ["outcome"],
    ).labels(outcome=outcome).inc()
    gauge(
        "dlrover_compile_cache_entries",
        "Executables in the persistent compilation cache",
    ).set(entries)
    record(
        f"compile_cache.{outcome}", dir=_armed_dir, entries=entries,
        new_entries=new,
        first_step_s=(
            round(first_step_s, 3) if first_step_s is not None else None
        ),
    )
    return outcome
