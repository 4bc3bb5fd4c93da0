"""Shared benchmark infrastructure: the graph suite (the paper's dataset
*families* at laptop scale — SuiteSparse itself is not available offline),
timing helpers, and CSV/JSON emission.

Every benchmark section also lands as a machine-readable ``BENCH_<name>.json``
(rows + wall time + environment), so the perf trajectory is diffable across
PRs — see ``benchmarks/run.py``.  ``BENCH_OUT_DIR`` overrides the output
directory (default: the current working directory)."""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.graph import CSRGraph
from repro.data import powerlaw_cluster, rmat_graph, sbm_graph


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> None:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself); without it, at the fixed ``<repo>/.jax_cache``,
    since the cache directory is part of what a later run must find again."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO_ROOT, ".jax_cache"))


def graph_suite(small: bool = False) -> Dict[str, CSRGraph]:
    """Five graphs mirroring Table 1's families: web (R-MAT power-law),
    social (powerlaw-cluster), community-structured (SBM), road (2D grid),
    k-mer (low-degree chains)."""
    import networkx as nx
    from repro.core.graph import from_networkx

    scale = 9 if small else 11
    n_grid = 24 if small else 48
    n_sbm = (8, 24) if small else (16, 48)

    web = rmat_graph(scale, edge_factor=8, seed=0)
    social, _ = powerlaw_cluster(300 if small else 1500, 6, 0.5, seed=1)
    sbm, _ = sbm_graph(*n_sbm, p_in=0.25, p_out=0.004, seed=2)
    road = from_networkx(nx.grid_2d_graph(n_grid, n_grid))
    # k-mer-like: union of long paths (avg degree ~2)
    kmer_nx = nx.Graph()
    rng = np.random.default_rng(3)
    base = 0
    for _ in range(20 if small else 60):
        ln = int(rng.integers(20, 60))
        kmer_nx.add_edges_from((base + i, base + i + 1) for i in range(ln))
        base += ln + 1
    kmer = from_networkx(kmer_nx)
    return {"rmat_web": web, "powerlaw_social": social, "sbm": sbm,
            "grid_road": road, "kmer_paths": kmer}


def time_fn(fn: Callable, *args, repeats: int = 3, **kw):
    """(best_seconds, last_result) — best-of-N like the paper's 5-run mean."""
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return best, out


def emit_csv(rows: List[dict], header: List[str]) -> None:
    print(",".join(header))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in header))


def _git_sha() -> Optional[str]:
    """Short commit hash of the tree the artifact was produced from, or
    None outside a git checkout — ties each BENCH json to a revision."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None if out.returncode == 0 else None
    except OSError:
        return None


def emit_json(name: str, rows: Optional[List[dict]],
              seconds: Optional[float] = None, **extra) -> str:
    """Write ``BENCH_<name>.json`` — the machine-readable perf artifact.

    ``rows`` is whatever the section measured (each bench keeps its own
    schema: wall times, edges/s / updates/s, modularity where applicable);
    ``seconds`` the section's wall time; ``extra`` free-form metadata.
    Every payload carries the producing tree's ``git_sha``.
    Returns the path written.
    """
    import jax

    payload = {
        "bench": name,
        "seconds": None if seconds is None else round(float(seconds), 3),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "git_sha": _git_sha(),
        "rows": rows if rows is not None else [],
    }
    payload.update(extra)
    out_dir = os.environ.get("BENCH_OUT_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, default=str)
        fh.write("\n")
    print(f"[bench] wrote {path}")
    return path


def geomean(xs) -> float:
    xs = np.asarray(list(xs), dtype=float)
    return float(np.exp(np.log(np.maximum(xs, 1e-12)).mean()))
