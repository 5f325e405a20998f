"""Time a step spends in the expert layer's grouped matmuls."""

import re

NAME, UNIT = "moe_expert_ms", "ms"
LAYER = "expert layer"
MOVES, SOURCE = "tokens_per_s", "device_trace"

#: what the grouped matmuls of ops/grouped_matmul.py (gate, up, down,
#: and the two backward products of each) are called in a device
#: trace (PR 29, by hand from YARDSTICK_DESCRIBE_TRACE on the chip):
#: custom calls ``gmm.<n>`` (the forward product, and the rows'
#: gradient against the transposed matrices) and ``tgmm.<n>`` (the
#: matrices' gradient), after the jitted functions of
#: ``jax.experimental.pallas.ops.tpu.megablox`` that hold the Pallas
#: calls; and ``ragged-dot-none[.<n>]``, the chip compiler's own
#: kernel for ``jax.lax.ragged_dot``, where a shape does not tile and
#: the entry takes that route. ``ragged-dot-metadata`` is bookkeeping,
#: not a matmul, and is left out.
KERNEL = re.compile(r"^(t?gmm|ragged-dot-none)(\.\d+)?( |$)")


def kernel_seconds_per_step(trace):
    hits = [t for name, t, _ in trace["ops"] if KERNEL.search(name)]
    if not hits:
        return None
    return sum(hits) / trace["steps"]


def read(run):
    """Summed device durations of the kernels' events over the traced
    steps, forward and backward, a step and chip. None where the
    trace holds no such kernel (a program without the dropless
    path)."""
    if run["trace"] is None:
        return None
    seconds = kernel_seconds_per_step(run["trace"])
    return None if seconds is None else 1e3 * seconds
