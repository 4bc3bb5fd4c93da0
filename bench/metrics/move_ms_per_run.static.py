"""Device milliseconds per detection in the move phase: the jitted
``core/louvain._move_phase`` (the sort-reduce engine of
``core/local_move``) and the ``singleton_init`` that seeds each pass.
Layer: phases.  Moves: detect_edges_per_s."""

LAYER = "phases"
MOVES = "detect_edges_per_s"
MODULES = ("jit__move_phase", "jit_singleton_init")


def read(summary, ctx):
    runs = ctx.get("detections", 0)
    found = [summary["modules"][m]["seconds"] for m in MODULES
             if m in summary["modules"]]
    if not runs or not found:
        return None
    return 1e3 * sum(found) / runs
