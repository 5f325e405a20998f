"""Time a step spends in the selective scan's kernels."""

import re

NAME, UNIT = "selective_scan_ms", "ms"
LAYER = "selective scan"
MOVES, SOURCE = "tokens_per_s", "device_trace"

#: what the Pallas kernels of ops/pallas/selective_scan.py (the
#: forward; the forward that keeps the chunks' entry states and the
#: backward over them) are called in a device trace: custom calls
#: ``selective_scan.<n>``, after the one jitted function that holds the
#: calls (tests/test_chip_compile.py holds the name in the compiled
#: step's text, whose instruction names are a trace's)
KERNEL = re.compile(r"^selective_scan(\.\d+)?( |$)")


def kernel_seconds_per_step(trace):
    hits = [t for name, t, _ in trace["ops"] if KERNEL.search(name)]
    if not hits:
        return None
    return sum(hits) / trace["steps"]


def read(run):
    """Summed device durations of the kernels' events over the traced
    steps, forward (again where the remat policy runs it twice) and
    backward, a step and chip. None where the trace holds no such
    kernel (a program without the operator, or one that runs it as
    plain fusions)."""
    if run["trace"] is None:
        return None
    seconds = kernel_seconds_per_step(run["trace"])
    return None if seconds is None else 1e3 * seconds
