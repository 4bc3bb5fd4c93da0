"""Figure 8 analogue: strong scaling.

The paper scales OpenMP threads 1..64 on one node.  Here the distributed
implementation's *structural* scaling is reported: per-shard work (edge
slots) as the device count doubles, with wall time and modularity —
demonstrating quality is scale-invariant.  On a TPU host the sweep runs
in-process over sub-meshes of the real chips (one process holds them all);
on the CPU each device count runs in a subprocess that forces that many
host devices (jax locks the host device count at first init), and its wall
time is time-shared, indicative only."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import emit_csv

_CHILD = r"""
import os, sys
n = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
import json
import jax
from benchmarks.bench_fig8_scaling import measure
print(json.dumps(measure(n, jax.devices())))
"""


def measure(n: int, devices) -> dict:
    """One sweep point: distributed Louvain over the first ``n`` devices."""
    import jax.numpy as jnp

    from repro.compat import make_mesh
    from repro.core.distributed import (distributed_louvain,
                                        partition_graph_host)
    from repro.core.modularity import modularity
    from repro.data import rmat_graph

    g = rmat_graph(10, edge_factor=8, seed=0)
    mesh = make_mesh((n,), ("data",), devices=devices[:n])
    _, _, _, spec = partition_graph_host(g, n)
    t0 = time.perf_counter()
    mem, ncomm, stats = distributed_louvain(g, mesh, ("data",))
    dt = time.perf_counter() - t0
    comm = jnp.concatenate([jnp.asarray(mem, jnp.int32),
                            jnp.full((g.n_cap + 1 - len(mem),), g.n_cap,
                                     jnp.int32)])
    return {"devices": n, "wall_s": dt, "edges_per_shard": spec.e_per_shard,
            "q": float(modularity(g, comm)), "n_comms": ncomm,
            "passes": len(stats)}


def run(max_devices: int = 8):
    import jax

    rows = []
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        max_devices = min(max_devices, jax.device_count())
    n = 1
    while n <= max_devices:
        if on_tpu:
            rec = measure(n, jax.devices())
        else:
            proc = subprocess.run([sys.executable, "-c", _CHILD, str(n)],
                                  env=env, capture_output=True, text=True,
                                  timeout=1200, cwd=root)
            if proc.returncode != 0:
                raise RuntimeError(proc.stderr[-2000:])
            rec = json.loads(proc.stdout.strip().splitlines()[-1])
        rec["work_reduction_vs_1dev"] = None
        rows.append(rec)
        n *= 2
    base = rows[0]["edges_per_shard"]
    for r in rows:
        r["work_reduction_vs_1dev"] = round(base / r["edges_per_shard"], 2)
        r["wall_s"] = round(r["wall_s"], 3)
        r["q"] = round(r["q"], 4)
    emit_csv(rows, ["devices", "edges_per_shard", "work_reduction_vs_1dev",
                    "wall_s", "q", "n_comms", "passes"])
    return rows


if __name__ == "__main__":
    run()
