"""Device milliseconds per detection in aggregation and the capacity
ladder: ``core/louvain._aggregate_phase`` (with the Pallas coarsen kernel
on a TPU), ``_renumber_and_fold`` and ``core/graph.rebucket_capacity``.
Layer: phases.  Moves: detect_edges_per_s."""

LAYER = "phases"
MOVES = "detect_edges_per_s"
MODULES = ("jit__aggregate_phase", "jit__renumber_and_fold",
           "jit_rebucket_capacity")


def read(summary, ctx):
    runs = ctx.get("detections", 0)
    found = [summary["modules"][m]["seconds"] for m in MODULES
             if m in summary["modules"]]
    if not runs or not found:
        return None
    return 1e3 * sum(found) / runs
