"""Device milliseconds per stream update in the screened warm move
(``core/louvain._move_phase``, full or frontier-compacted, and the
``warm_init`` / ``affected_frontier`` that seed it).  Layer: phases.
Moves: update_p95_ms."""

LAYER = "phases"
MOVES = "update_p95_ms"
MODULES = ("jit__move_phase", "jit_warm_init", "jit_affected_frontier",
           "jit_singleton_init")


def read(summary, ctx):
    runs = ctx.get("batches", 0)
    found = [summary["modules"][m]["seconds"] for m in MODULES
             if m in summary["modules"]]
    if not runs or not found:
        return None
    return 1e3 * sum(found) / runs
