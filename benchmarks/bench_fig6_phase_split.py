"""Figure 6 analogue: pass split (first pass vs rest) per graph, per
aggregation backend and per capacity-ladder setting, with per-pass timings
as the committed machine-readable artifact.

``BENCH_phase_split.json`` carries one row per (graph, agg_backend, ladder,
pass) with the pass's seconds and the capacities it ran at, plus summary
rows with the coarse-pass (pass >= 1) totals and the ladder's coarse-pass
speedup.  The split of a pass into its phases is in a profiler trace: the
pass loop's ``gve.*`` host spans (``repro.core.spans``).
"""

from __future__ import annotations

import time

from benchmarks.common import emit_csv, emit_json, graph_suite
from repro.core.louvain import LouvainConfig, louvain


def _timed_run(g, cfg, repeats: int):
    """Warm every tier's compiled phases, then best-of-N by total pass time
    (per-pass timings are taken from the best run, so compiles never
    pollute the phase split)."""
    louvain(g, cfg)
    best = None
    for _ in range(max(repeats, 1)):
        res = louvain(g, cfg)
        tot = sum(p.seconds for p in res.passes)
        if best is None or tot < best[0]:
            best = (tot, res)
    return best[1]


def run(small: bool = True, repeats: int = 2):
    graphs = graph_suite(small=small)
    pass_rows, summary = [], []
    t0 = time.perf_counter()
    for gname, g in graphs.items():
        coarse_by_cfg = {}
        for backend in ("sort", "pallas"):
            for ladder in (False, True):
                cfg = LouvainConfig(use_ladder=ladder, agg_backend=backend)
                res = _timed_run(g, cfg, repeats)
                all_p = max(sum(p.seconds for p in res.passes), 1e-12)
                coarse = sum(p.seconds for p in res.passes[1:])
                coarse_by_cfg[(backend, ladder)] = coarse
                for i, p in enumerate(res.passes):
                    pass_rows.append({
                        "graph": gname, "agg_backend": backend,
                        "ladder": ladder, "pass": i,
                        "seconds": round(p.seconds, 6),
                        "n_cap": p.n_cap, "e_cap": p.e_cap,
                        "n_vertices": p.n_vertices,
                        "n_communities": p.n_communities,
                    })
                summary.append({
                    "graph": gname, "agg_backend": backend, "ladder": ladder,
                    "passes": res.n_passes,
                    "first_pass_frac": round(res.passes[0].seconds / all_p, 3),
                    "coarse_pass_s": round(coarse, 6),
                })
        for backend in ("sort", "pallas"):
            off = coarse_by_cfg[(backend, False)]
            on = coarse_by_cfg[(backend, True)]
            for row in summary:
                if (row["graph"] == gname and row["agg_backend"] == backend
                        and row["ladder"]):
                    row["coarse_speedup_vs_no_ladder"] = round(
                        off / max(on, 1e-12), 2)
    emit_csv(summary, ["graph", "agg_backend", "ladder", "passes",
                       "first_pass_frac", "coarse_pass_s",
                       "coarse_speedup_vs_no_ladder"])
    emit_json("phase_split", pass_rows,
              seconds=time.perf_counter() - t0, small=small, summary=summary)
    return summary


if __name__ == "__main__":
    run(small=False)
