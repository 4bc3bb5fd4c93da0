"""Share of the HBM roofline reached by the Pallas coarsen kernel
(``kernels/aggregate/coarsen.py``), which the default aggregation runs on
a TPU: the least time its bytes need at the chip's peak bandwidth
(``bench/peaks.py``), over the kernel's device time.  The bytes are
``bench/kernels.coarsen_bytes`` of each aggregation's slot count.  Layer:
kernels.  Moves: detect_edges_per_s."""

from bench import kernels, peaks

LAYER = "kernels"
MOVES = "detect_edges_per_s"
# The pallas_call is named after the function that makes it.
KERNEL = "coarsen_groups_pallas"


def read(summary, ctx):
    seconds = sum(v for k, v in summary["ops"].items()
                  if k.rsplit("/", 1)[-1].startswith(KERNEL))
    caps = ctx.get("aggregations_e_cap", [])
    if not seconds > 0 or not caps:
        return None
    moved = sum(kernels.coarsen_bytes(c) for c in caps)
    floor_s = moved / peaks.peak(ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / seconds
