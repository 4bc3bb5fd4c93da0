"""Share of the traced window in which no operation ran on the device,
over the traced stream updates.  Layer: device.  Moves: update_p95_ms."""

LAYER = "device"
MOVES = "update_p95_ms"


def read(summary, ctx):
    if not summary["window_s"] > 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
