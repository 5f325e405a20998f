"""Time a step spends in the Pallas attention kernels."""

import re

NAME, UNIT = "attn_kernel_ms", "ms"
LAYER = "attention kernel"
MOVES, SOURCE = "tokens_per_s", "device_trace"

#: what the three kernels (forward, dq, dkv) of
#: ops/pallas/flash_attention.py are called in a device trace today
#: (PR 25, by hand): custom calls ``flash_attention.<n>``, one name a
#: kernel and layer loop, told apart only by their result shapes
KERNEL = re.compile(r"^flash_attention(\.\d+)?( |$)")


def kernel_seconds_per_step(trace):
    hits = [t for name, t, _ in trace["ops"] if KERNEL.search(name)]
    if not hits:
        return None
    return sum(hits) / trace["steps"]


def read(run):
    """Summed device durations of the kernels' events over the traced
    steps, a step and chip."""
    if run["trace"] is None:
        return None
    seconds = kernel_seconds_per_step(run["trace"])
    return None if seconds is None else 1e3 * seconds
