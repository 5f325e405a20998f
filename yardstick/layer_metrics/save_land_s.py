"""How long a save that was asked for is not yet a restart point."""

from yardstick.layer_metrics import save_stall_ms

NAME, UNIT = "save_land_s", "s"
LAYER = "checkpoint"
MOVES, SOURCE = "tokens_per_s", "host_clock"


def read(run):
    """From the start of ``ckpt.stage`` to the end of the same step's
    ``ckpt.serialize`` (device to host, encode, the write to the RAM
    tier with its digest), over the saves begun inside the window
    whose ``ckpt.serialize`` ended. None where there is none."""
    found = save_stall_ms.saves_in_window(run)
    if found is None:
        return None
    records, saves = found
    took = []
    for save in saves:
        lanes = save_stall_ms.of_step(records, "ckpt.serialize", save)
        if lanes:
            took.append(lanes[0]["ts"] + lanes[0]["dur"] - save["ts"])
    return sum(took) / len(took) if took else None
