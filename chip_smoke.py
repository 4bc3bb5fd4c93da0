"""Smoke run of the Louvain main path on TPU chips, through the user entry points.

    python chip_smoke.py [--seed N] [--scale S]     # one chip
    python chip_smoke.py --chips 4 [--scale S]      # the sharded paths only

One chip (the default) runs three phases in one process:

  a. goldens   ``louvain`` (default config and fused ELL kernel) on the small
               golden corpora, and ``louvain_dynamic`` on the golden stream
               with the Pallas batch apply, against
               ``tests/golden/engine_memberships.npz`` (bit-identity is
               reported; Q, recomputed on the host in float64, must agree to
               1e-3, and the fused ELL run's Q to the default run's);
  b. static    a Graph500-parameter R-MAT graph (A/B/C = 0.57/0.19/0.19, edge
               factor 16, 2^scale vertices) through ``louvain`` with the
               default config (Pallas aggregation on a TPU); the first
               pass's aggregation is then run with both backends, whose
               coarse graphs must be identical; the first pass's move phase
               is run again with the fused ELL kernel, whose level-0 Q must
               be within 1e-3 of the default run's;
  c. stream    insert/delete batches of about 1e-4 |E| each through
               ``louvain_dynamic`` with the Pallas batch apply; the batches
               are then applied with both apply backends, whose graphs and
               touched sets must be identical after every batch.

A backend pair differs in one jitted phase only, so b and c compare that
phase's output on the real inputs: identical outputs give identical
memberships, since all else is the same deterministic program.  At scale
21 the default run aggregates once (its second pass converges), so the
first pass's aggregation is the only one.  A second scale-21 pass loop per
backend would cost minutes of chip time each and push the run past the
1200 s a smoke run may take.

``--chips 4`` runs ``distributed_louvain`` over a 4-chip mesh with the
"gather" and "delta" exchanges (memberships must be identical), a few
batches of ``louvain_dynamic_sharded``, and a one-chip ``louvain`` on the
same graph for Q, in four worker threads of the one process so that one
run compiles while another holds the chips.  On one chip the phases run
one after another (concurrent scale-21 runs exhaust the chip's 16 GB);
the host's R-MAT set-up overlaps the goldens, and the check phases'
programs compile while the default run holds the chip.

Each phase prints one line with the device kind, its wall seconds and the
seconds its thread spent in XLA compilation (smoke timings, not benchmark
numbers), Q, passes, communities and the chip's peak bytes in use.  The
last line is the JSON result.  A host without a TPU exits non-zero before
any phase; any failed check raises, so no result line is printed.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
Q_TOL = 1e-3
WORKERS = 4
_COMPILE_S = collections.defaultdict(float)   # thread id -> XLA compile s


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _count_compile(event: str, seconds: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[threading.get_ident()] += seconds


def timed(fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` with its wall and XLA compile seconds."""
    me = threading.get_ident()
    c0, t0 = _COMPILE_S[me], time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0, _COMPILE_S[me] - c0


def report(phase: str, seconds: float, compile_s: float, **fields) -> None:
    import jax

    dev = jax.devices()[0]
    parts = [f"phase={phase}", f"kind={dev.device_kind!r}",
             f"smoke_wall_s={seconds:.2f}", f"smoke_compile_s={compile_s:.2f}"]
    parts += [f"{k}={v}" for k, v in fields.items()]
    parts.append(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")
    print(" ".join(parts), flush=True)


def host_q(graph, membership) -> float:
    """Q of ``membership`` on ``graph``, in float64 on the host."""
    import numpy as np
    from _oracle import modularity_np

    e = int(graph.e_valid)
    return modularity_np(np.asarray(graph.src)[:e],
                         np.asarray(graph.indices)[:e],
                         np.asarray(graph.weights)[:e], membership)


def check_kernels_lower() -> None:
    """Each Pallas kernel of the main path lowers to a TPU custom call."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.aggregate import coarsen_groups_pallas
    from repro.kernels.batch_apply import resolve_groups_pallas
    from repro.kernels.louvain_scan import ops

    n = 4096
    i1, f1 = jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32)
    r, d = 64, 16
    i2, f2 = jnp.zeros((r, d), jnp.int32), jnp.zeros((r, d), jnp.float32)
    ic, fc = jnp.zeros((r, 1), jnp.int32), jnp.ones((r, 1), jnp.float32)
    m, rnd = jnp.float32(1.0), jnp.int32(0)
    lowered = {
        "coarsen": jax.jit(lambda a, b, w: coarsen_groups_pallas(
            a, b, w, sent=n)).lower(i1, i1, f1),
        "resolve": jax.jit(lambda a, b, w, f: resolve_groups_pallas(
            a, b, w, f, sent=n)).lower(i1, i1, f1, i1 > 0),
        "fused_ell": jax.jit(lambda *a: ops.louvain_fused(
            *a, gate_fraction=2, sentinel=n)).lower(
                i2, f2, f2, i2, fc, ic, fc, ic, ic, ic, m, rnd),
        "scan_ell": jax.jit(ops.louvain_scan).lower(i2, f2, f2, fc, ic, fc, m),
    }
    for name, low in lowered.items():
        check("tpu_custom_call" in low.as_text(),
              f"kernel {name} did not lower to a tpu_custom_call")
    print(f"kernels lowered to tpu_custom_call: {', '.join(lowered)}",
          flush=True)


def phase_goldens() -> None:
    import numpy as np

    from golden.capture_engine_golden import corpora, dynamic_stream
    from repro.core.dynamic import louvain_dynamic
    from repro.core.louvain import LouvainConfig, louvain

    want = np.load(os.path.join(ROOT, "tests", "golden",
                                "engine_memberships.npz"))
    configs = (("single", LouvainConfig()),
               ("ell", LouvainConfig(use_ell_kernel=True)))
    for name, g in corpora().items():
        q_run = {}
        for kind, cfg in configs:
            res, dt, ct = timed(louvain, g, cfg)
            gold = want[f"{kind}__{name}"]
            q, q_gold = host_q(g, res.membership), host_q(g, gold)
            check(abs(q - q_gold) <= Q_TOL,
                  f"golden {kind} {name}: Q {q} vs golden {q_gold}")
            q_run[kind] = q
            report(f"a.golden.{kind}.{name}", dt, ct, Q=f"{q:.6f}",
                   passes=res.n_passes, communities=res.n_communities,
                   bit_identical=bool(np.array_equal(res.membership, gold)))
        check(abs(q_run["ell"] - q_run["single"]) <= Q_TOL,
              f"golden {name}: fused ELL Q {q_run['ell']} vs default "
              f"{q_run['single']}")
    init, batches = dynamic_stream()
    res, dt, ct = timed(louvain_dynamic, init, batches, apply_backend="pallas")
    gold = want["dynamic__sbm_stream"]
    q, q_gold = host_q(res.graph, res.membership), host_q(res.graph, gold)
    check(abs(q - q_gold) <= Q_TOL,
          f"golden stream: Q {q} vs golden {q_gold}")
    report("a.golden.sbm_stream_pallas_apply", dt, ct, Q=f"{q:.6f}",
           batches=len(batches), communities=res.n_communities,
           bit_identical=bool(np.array_equal(res.membership, gold)))


def same_arrays(a, b) -> bool:
    """Two pytrees of arrays are equal bit for bit."""
    import jax
    import numpy as np

    return all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True))


def stream_batches(graph, seed: int, n_batches: int = 3):
    """Insert-heavy batches (4 inserts : 1 delete) of ~1e-4 |E| edges each."""
    import numpy as np

    from repro.core.delta import make_edge_batch

    e = int(graph.e_valid)
    src = np.asarray(graph.src)[:e]
    dst = np.asarray(graph.indices)[:e]
    und = np.flatnonzero(src < dst)
    n = int(graph.n_valid)
    size = max(5, round(1e-4 * len(und)))
    n_del = size // 5
    rng = np.random.default_rng(seed + 1)
    batches = []
    for _ in range(n_batches):
        gone = rng.choice(und, n_del, replace=False)
        u = rng.integers(0, n, size - n_del)
        v = (u + rng.integers(1, n, size - n_del)) % n     # never a self loop
        batches.append(make_edge_batch(
            np.concatenate([src[gone], u]), np.concatenate([dst[gone], v]),
            np.concatenate([np.zeros(n_del), np.ones(size - n_del)]),
            graph.n_cap, b_cap=size))
    return batches


def rmat_with_stream(scale: int, seed: int):
    """The Graph500-parameter R-MAT graph (edge factor 16) and its stream.

    The graph gets headroom for every insert of the stream, so the static
    runs and the stream share one capacity (and one set of programs).
    """
    from repro.core.graph import rebucket_graph
    from repro.data import rmat_graph

    g = rmat_graph(scale, edge_factor=16, seed=seed)
    batches = stream_batches(g, seed)
    slack = 2 * sum(int(b.b_valid) for b in batches)
    return rebucket_graph(g, g.n_cap, int(g.e_valid) + slack), batches


def precompile_checks(g, batches, cfg) -> None:
    """Compile the programs the check phases add to the default run's.

    A jitted function lowered and compiled for some arguments is the
    program a later call with arguments of the same shapes runs, so the
    checks then compile nothing.  The arguments mirror those that
    ``_aggregate_phase``, ``apply_edge_batch`` and ``move_phase_ell`` get.
    """
    import jax.numpy as jnp

    from repro import kernels
    from repro.core import ell_move
    from repro.core.delta import _apply_edge_batch
    from repro.core.graph import to_ell_blocks
    from repro.core.louvain import _aggregate_phase

    cap = g.n_cap + 1
    _aggregate_phase.lower(g, jnp.zeros((cap,), jnp.int32), jnp.int32(0),
                           backend="sort").compile()
    _apply_edge_batch.lower(g, batches[0], backend="xla").compile()
    blocks, leftover = to_ell_blocks(g, cfg.ell_widths)
    run = ell_move._ell_runner(len(blocks), True, kernels.interpret_mode(),
                               cfg.max_iterations, cfg.use_pruning,
                               cfg.gate_fraction, True, False)
    k = g.vertex_weights()
    run.lower(g, tuple(blocks), jnp.asarray(leftover), k, g.total_weight(),
              jnp.arange(cap, dtype=jnp.int32), k, jnp.arange(cap) < g.n_valid,
              jnp.float32(cfg.initial_tolerance)).compile()


def fused_ell_level0(g, cfg):
    """The first pass's move phase with the fused ELL kernel, renumbered as
    ``louvain`` renumbers it: (level-0 membership, rounds)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ell_move
    from repro.core.louvain import _renumber_and_fold

    comm, iters, _ = ell_move.move_phase_ell(
        g, jnp.float32(cfg.initial_tolerance),
        max_iterations=cfg.max_iterations, use_pruning=cfg.use_pruning,
        gate_fraction=cfg.gate_fraction, widths=cfg.ell_widths, fused=True)
    _, _, folded = _renumber_and_fold(comm, g.n_valid, jnp.int32(g.n_cap),
                                      jnp.arange(g.n_cap, dtype=jnp.int32))
    return np.asarray(folded[:int(g.n_valid)]), int(iters)


def phase_static_and_stream(pool, setup) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.louvain_arch import resolve_agg_backend
    from repro.core.delta import apply_edge_batch
    from repro.core.dynamic import louvain_dynamic
    from repro.core.louvain import (LouvainConfig, _aggregate_phase, louvain,
                                    louvain_modularity, pad_membership)

    (g, batches), dt, ct = setup.result()
    report("b.setup", dt, ct, vertices=int(g.n_valid), slots=int(g.e_valid),
           e_cap=g.e_cap)

    check(resolve_agg_backend("auto") == "pallas",
          "agg_backend='auto' did not resolve to the Pallas kernel")
    cfg = LouvainConfig()
    warm = pool.submit(timed, precompile_checks, g, batches, cfg)
    base, dt, ct = timed(louvain, g, cfg)
    q = host_q(g, base.membership)
    q_dev = louvain_modularity(g, base)
    check(abs(q - q_dev) <= Q_TOL, f"device Q {q_dev} vs host Q {q}")
    report("b.static.default_pallas_agg", dt, ct, Q=f"{q:.6f}",
           Q_device=f"{q_dev:.6f}", passes=base.n_passes,
           rounds="/".join(str(p.iterations) for p in base.passes),
           communities=base.n_communities)
    _, dt, ct = warm.result()
    report("b.precompile_checks", dt, ct)

    # The first pass's aggregation, as ``louvain`` ran it: the renumbered
    # first-level partition of the input graph.
    comm = jnp.asarray(pad_membership(base.levels[0], g.n_cap))
    n_comms = jnp.int32(base.passes[0].n_communities)
    coarse, dt, ct = timed(lambda: {
        backend: _aggregate_phase(g, comm, n_comms, backend=backend)
        for backend in ("pallas", "sort")})
    check(same_arrays(coarse["pallas"], coarse["sort"]),
          "Pallas and sort aggregation give different coarse graphs")
    report("b.static.aggregate_pallas_vs_sort", dt, ct,
           communities=int(n_comms),
           coarse_slots=int(coarse["sort"].e_valid), bit_identical=True)
    del coarse

    (level0, iters), dt, ct = timed(fused_ell_level0, g, cfg)
    q_ell, q_base = host_q(g, level0), host_q(g, base.levels[0])
    check(abs(q_ell - q_base) <= Q_TOL,
          f"fused ELL level-0 Q {q_ell} vs default {q_base}")
    report("b.static.fused_ell_level0", dt, ct, Q=f"{q_ell:.6f}",
           Q_default=f"{q_base:.6f}", rounds=iters,
           rounds_default=base.passes[0].iterations,
           communities=len(np.unique(level0)),
           bit_identical=bool(np.array_equal(level0, base.levels[0])))
    del level0

    res, dt, ct = timed(louvain_dynamic, g, batches, prev=base.membership,
                        apply_backend="pallas")
    report("c.stream.pallas_apply", dt, ct,
           Q=f"{host_q(res.graph, res.membership):.6f}",
           batches=len(batches), batch_edges=int(batches[0].b_valid),
           communities=res.n_communities)
    del res

    def apply_both():
        graphs = {"pallas": g, "xla": g}
        for batch in batches:
            out = {backend: apply_edge_batch(graphs[backend], batch,
                                             backend=backend)
                   for backend in graphs}
            check(same_arrays(out["pallas"], out["xla"]),
                  "Pallas and XLA batch apply give different graphs")
            graphs = {backend: out[backend][0] for backend in out}
        return graphs["xla"]

    final, dt, ct = timed(apply_both)
    report("c.stream.apply_pallas_vs_xla", dt, ct, batches=len(batches),
           slots=int(final.e_valid), bit_identical=True)


def phase_sharded(pool, setup) -> None:
    import jax
    import numpy as np

    from repro.compat import make_mesh
    from repro.core.distributed import distributed_louvain
    from repro.core.distributed_dynamic import louvain_dynamic_sharded
    from repro.core.louvain import louvain

    (g, batches), dt, ct = setup.result()
    mesh = make_mesh((4,), ("shard",))
    axes = ("shard",)
    report("d.setup", dt, ct, vertices=int(g.n_valid), slots=int(g.e_valid),
           mesh=dict(mesh.shape))

    sharded = {backend: pool.submit(timed, distributed_louvain, g, mesh, axes,
                                    comm_backend=backend)
               for backend in ("gather", "delta")}
    single = pool.submit(timed, louvain, g)
    (mem, n_comms, stats), dt, ct = sharded["gather"].result()
    stream = pool.submit(timed, louvain_dynamic_sharded, g, mesh, axes,
                         batches, prev=mem, track_modularity=True)

    mems = {}
    for backend, job in sharded.items():
        (mem, n_comms, stats), dt, ct = job.result()
        mems[backend] = mem
        report(f"d.sharded.{backend}", dt, ct, Q=f"{host_q(g, mem):.6f}",
               passes=len(stats), communities=n_comms)
    check(np.array_equal(mems["gather"], mems["delta"]),
          "gather and delta exchanges give different memberships")

    res, dt, ct = stream.result()
    report("d.sharded.stream", dt, ct,
           Q_device=f"{res.batch_stats[-1].modularity:.6f}",
           batches=len(batches), communities=res.n_communities,
           regrows=res.n_regrows)

    res, dt, ct = single.result()
    q1, q4 = host_q(g, res.membership), host_q(g, mems["gather"])
    check(abs(q1 - q4) <= Q_TOL, f"4-chip Q {q4} vs one-chip Q {q1}")
    report("d.one_chip_reference", dt, ct, Q=f"{q1:.6f}", Q_4chip=f"{q4:.6f}",
           passes=res.n_passes, communities=res.n_communities,
           bit_identical=bool(np.array_equal(res.membership,
                                             mems["gather"])))

    # Each chip held at least its quarter of the (src, dst, w) edge slots.
    shard_bytes = 12 * int(g.e_valid) // 4
    for d in jax.devices()[:4]:
        s = d.memory_stats()
        print(f"device {d.id}: bytes_in_use={s['bytes_in_use']} "
              f"peak_bytes_in_use={s['peak_bytes_in_use']}", flush=True)
        check(s["peak_bytes_in_use"] >= shard_bytes,
              f"device {d.id} never held a shard of {shard_bytes} bytes")


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=None,
                    help="log2 of the R-MAT vertex count (default: 21 on "
                         "one chip, 11 on four)")
    args = ap.parse_args()
    scale = args.scale or (21 if args.chips == 1 else 11)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(1)
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"found {len(devices)}", file=sys.stderr)
        sys.exit(1)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT,
                    os.path.join(ROOT, "tests")]
    from benchmarks.common import enable_compile_cache

    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(WORKERS) as pool:
        setup = pool.submit(timed, rmat_with_stream, scale, args.seed)
        if args.chips == 4:
            phase_sharded(pool, setup)
        else:
            check_kernels_lower()
            phase_goldens()
            phase_static_and_stream(pool, setup)
    print(f"smoke total wall seconds: {time.perf_counter() - t0:.2f}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
