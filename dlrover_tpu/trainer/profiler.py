"""Model/step profiler: XLA cost analysis -> stats pipeline.

Parity reference: atorch/atorch/utils/prof.py:41 (AProfiler: per-module
flops/memory walk of a torch model) and the TF profile extractor the
reference feeds into report_model_metric. The TPU shape gets the same
numbers from the compiler instead of a module walk: ``jit(fn).lower(...)
.compile()`` exposes the whole-program flops and HBM bytes XLA actually
scheduled (including remat recompute — hardware flops, the HFU
numerator), and ``memory_analysis()`` the buffer footprint.

``ElasticTrainer`` reports the profile to the master over the
``report_model_info`` RPC -> JobMetricCollector -> LocalStatsReporter
(master/stats), closing the loop for the resource optimizer.
"""

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from dlrover_tpu.common.log import default_logger as logger


@dataclass
class StepProfile:
    """Whole-train-step profile from the compiled XLA program."""

    flops: float = 0.0  # hardware flops per step (incl. remat recompute)
    hbm_bytes: float = 0.0  # bytes accessed per step
    peak_memory_bytes: float = 0.0  # args + temps resident
    generated_code_bytes: float = 0.0
    param_count: int = 0
    variable_count: int = 0
    max_variable_size: int = 0
    extra: Dict[str, float] = field(default_factory=dict)

    def to_model_info_kwargs(self, batch_size: int = 0,
                             seq_len: int = 0) -> Dict[str, Any]:
        """kwargs for MasterClient.report_model_info."""
        return dict(
            param_count=self.param_count,
            flops_per_step=self.flops,
            batch_size=batch_size,
            seq_len=seq_len,
            extra={
                "hbm_bytes": self.hbm_bytes,
                "peak_memory_bytes": self.peak_memory_bytes,
                "variable_count": self.variable_count,
                "max_variable_size": self.max_variable_size,
                **self.extra,
            },
        )


def _tensor_stats(params) -> Tuple[int, int, int]:
    leaves = jax.tree.leaves(params)
    sizes = [x.size for x in leaves]
    return (len(sizes), int(sum(sizes)), int(max(sizes, default=0)))


def profile_compiled(compiled) -> StepProfile:
    """Extract flops/bytes from an already-compiled XLA executable."""
    prof = StepProfile()
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
        prof.flops = float(ca.get("flops", 0.0))
        prof.hbm_bytes = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # some backends lack cost analysis
        logger.warning("cost_analysis unavailable: %s", e)
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            prof.peak_memory_bytes = float(
                getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                - getattr(ma, "alias_size_in_bytes", 0)
            )
            prof.generated_code_bytes = float(
                getattr(ma, "generated_code_size_in_bytes", 0)
            )
    except Exception as e:
        logger.warning("memory_analysis unavailable: %s", e)
    return prof


def profile_step(step_fn: Callable, *args,
                 params: Any = None, **kwargs) -> StepProfile:
    """Lower+compile ``step_fn(*args, **kwargs)`` and profile it.

    ``step_fn`` may already be a jitted function (its cache is shared, so
    profiling costs one lowering, not a second compile at run time).
    Args may be real arrays or ``jax.ShapeDtypeStruct`` pytrees — the
    abstract form (the reference's meta-model dryrun, atorch
    utils/meta_model_utils.py role) compiles without materializing
    anything. ``params`` (any pytree with .size leaves) fills the tensor
    statistics.
    """
    fn = step_fn if hasattr(step_fn, "lower") else jax.jit(step_fn)
    compiled = fn.lower(*args, **kwargs).compile()
    prof = profile_compiled(compiled)
    if params is not None:
        (prof.variable_count, prof.param_count,
         prof.max_variable_size) = _tensor_stats(params)
    return prof


def report_profile(master_client, prof: StepProfile,
                   batch_size: int = 0, seq_len: int = 0) -> bool:
    """Send the profile to the master's stats pipeline; False on error
    (profiling must never take training down)."""
    try:
        master_client.report_model_info(
            **prof.to_model_info_kwargs(batch_size, seq_len)
        )
        return True
    except Exception as e:
        logger.warning("report_model_info failed: %s", e)
        return False


class TraceCapture:
    """Timeline capture around training steps (parity role: AProfiler's
    timeline export, atorch/atorch/utils/prof.py, and the reference's
    torch-profiler trace dumps): wraps ``jax.profiler`` so a window of
    steps lands in a TensorBoard-loadable trace directory.

    Usage::

        with TraceCapture("/tmp/trace", start_step=10, num_steps=3) as tc:
            for step in range(100):
                run_step()
                tc.step(step)

    Or drive it manually with start()/stop(). Env trigger for zero-code
    capture: DLROVER_TRACE_DIR [+ DLROVER_TRACE_START/_STEPS].
    """

    def __init__(self, trace_dir: str, start_step: int = 1,
                 num_steps: int = 3):
        self._dir = trace_dir
        self._start = start_step
        self._stop_after = start_step + num_steps
        self._active = False
        self._atexit_registered = False

    @classmethod
    def from_env(cls) -> "TraceCapture | None":
        import os

        trace_dir = os.environ.get("DLROVER_TRACE_DIR", "")
        if not trace_dir:
            return None
        return cls(
            trace_dir,
            start_step=int(os.environ.get("DLROVER_TRACE_START", "1")),
            num_steps=int(os.environ.get("DLROVER_TRACE_STEPS", "3")),
        )

    def start(self):
        if not self._active:
            jax.profiler.start_trace(self._dir)
            self._active = True
            # a window still open when the process ends (short run,
            # restart action mid-window) must still flush the trace.
            # Registered ONCE per capture object: stop() is idempotent,
            # and re-registering on every window open would grow the
            # atexit stack by one callback per window for the life of
            # the process
            if not self._atexit_registered:
                import atexit

                atexit.register(self.stop)
                self._atexit_registered = True
            logger.info("Trace capture started -> %s", self._dir)

    def stop(self):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            logger.info("Trace capture written to %s", self._dir)

    def step(self, step: int):
        """Call once per completed step; starts/stops the window."""
        if step >= self._start and not self._active and (
                step < self._stop_after):
            self.start()
        elif step >= self._stop_after and self._active:
            self.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
