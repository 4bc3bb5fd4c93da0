"""Device milliseconds per stream update in the batch apply
(``core/delta._apply_edge_batch``, the sort-reduce over e_cap + 2 b_cap
slots).  Layer: phases.  Moves: update_p95_ms."""

LAYER = "phases"
MOVES = "update_p95_ms"
MODULES = ("jit__apply_edge_batch",)


def read(summary, ctx):
    runs = ctx.get("batches", 0)
    found = [summary["modules"][m]["seconds"] for m in MODULES
             if m in summary["modules"]]
    if not runs or not found:
        return None
    return 1e3 * sum(found) / runs
