"""Collective time that no compute hides."""

NAME, UNIT = "collective_exposed_ms", "ms"
LAYER = "trainer step across chips"
MOVES, SOURCE = "tokens_per_s", "device_trace"


def read(run):
    """Time a step in which a collective runs on a chip and no
    compute does, mean over the chips."""
    trace = run["trace"]
    if trace is None:
        return None
    return 1e3 * trace["collective_exposed_s"] / trace["steps"]
