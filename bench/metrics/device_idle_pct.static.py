"""Share of the traced window in which no operation ran on the device,
over one whole detection.  Layer: device.  Moves: detect_edges_per_s."""

LAYER = "device"
MOVES = "detect_edges_per_s"


def read(summary, ctx):
    if not summary["window_s"] > 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
