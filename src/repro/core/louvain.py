"""GVE-Louvain main loop (Algorithm 1) — passes of local-moving + aggregation.

The pass loop runs on the host (graph capacities are static, so every phase is
jit-compiled exactly once and reused across passes — the JAX realization of
the paper's preallocated ping-pong buffers).  Its stretches of work are host
spans and its device reads named syncs (``repro.core.spans``): ``gve.pass``
holds ``gve.move``, ``gve.refine``, ``gve.renumber`` and ``gve.aggregate``,
and a ``gve.pass.counts`` counter follows each pass.  All paper parameters
are exposed with the paper's defaults:

    MAX_PASSES=10, MAX_ITERATIONS=20, initial tolerance 0.01,
    TOLERANCE_DROP=10, aggregation tolerance 0.8, vertex pruning on.

The move phase accepts an arbitrary initial membership + community-weight
snapshot (plus an optional seed frontier), which is what the dynamic
warm-start driver in ``repro.core.dynamic`` builds on: ``louvain()`` with
``init_membership=`` resumes from a previous partition instead of the
singleton start, and ``init_frontier=`` restricts the first pass to a
delta-screened vertex set.  All jit signatures stay static — warm and cold
starts share one compiled ``_move_phase``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.louvain_arch import (COMPACT_WORK_FRAC, compact_work_cap,
                                        resolve_agg_backend,
                                        resolve_coarse_capacity,
                                        resolve_scan_backend)
from repro.core import spans
from repro.core.aggregate import aggregate_graph, renumber_communities
from repro.core.engine import affected_frontier
from repro.core.graph import CSRGraph, count_trace, rebucket_capacity
from repro.core.local_move import louvain_move
from repro.core.modularity import community_weights, modularity


@dataclasses.dataclass(frozen=True)
class LouvainConfig:
    """Paper §4.1 parameter set (defaults = paper's chosen values)."""

    max_passes: int = 10
    max_iterations: int = 20          # opt. 4.1.2
    initial_tolerance: float = 0.01   # opt. 4.1.4
    tolerance_drop: float = 10.0      # opt. 4.1.3 (threshold scaling)
    aggregation_tolerance: float = 0.8  # opt. 4.1.5
    use_pruning: bool = True          # opt. 4.1.6
    gate_fraction: int = 2            # stochastic round gating (see local_move)
    use_ell_kernel: bool = False      # Pallas scan kernel for the move phase
    ell_widths: tuple = (16, 64, 256)
    track_modularity: bool = False    # record Q after every pass (debugging)
    #: Scanner backend for the move phase (configs.louvain_arch policy):
    #: "auto" (frontier-compacted sort-reduce when a small seed frontier is
    #: active; the fused kernel on the ELL family), "full", "compact",
    #: "ell", "ell_fused".  All backends are bit-identical in results —
    #: this knob trades work, never memberships.
    scan_backend: str = "auto"
    #: Compact work-buffer capacity as a fraction of e_cap (default: the
    #: configs.louvain_arch.COMPACT_WORK_FRAC policy — ONE home).
    compact_cap_frac: float = COMPACT_WORK_FRAC
    #: Aggregation backend ("sort" | "pallas" | "auto"): the XLA
    #: lexsort -> segment_sum -> scatter chain, or the fused Pallas
    #: group-detect + accumulate + emit kernel (repro.kernels.aggregate).
    #: Bit-identical memberships across backends — policy in
    #: configs.louvain_arch.resolve_agg_backend.
    agg_backend: str = "auto"
    #: Coarse-pass capacity ladder: after aggregation, re-bucket the coarse
    #: graph down to the smallest power-of-two tier fitting (n_comms,
    #: e_valid), so later passes' scans/renumbers/sorts run at coarse
    #: capacity instead of the original e_cap.  Memberships are invariant
    #: to capacity, so this trades work, never results (pinned bit-for-bit
    #: in tests/test_engine_equiv.py).  Tier policy:
    #: configs.louvain_arch.resolve_coarse_capacity.
    use_ladder: bool = True
    #: Sharded per-round exchange backend ("gather" | "delta" | "auto"):
    #: dense Vite-style all_gather/psum of the whole replicated state, or
    #: compacted bit-packed owned CHANGES with a measured-overflow dense
    #: fallback (repro.core.distributed.DeltaShardedScanner).  "auto"
    #: resolves per mesh (delta on multi-shard meshes).  Single-device
    #: drivers ignore it; memberships are invariant to it (pinned
    #: bit-for-bit in tests/test_engine_equiv.py).  Policy + caps:
    #: configs.louvain_arch.resolve_comm_backend / delta_move_cap.
    comm_backend: str = "auto"
    #: Leiden-style refinement ("none" | "leiden"): after each local-moving
    #: phase, re-seed vertices as singletons and run a CONSTRAINED engine
    #: sweep (moves only within the outer community, singleton movers only
    #: — ``engine.ConstrainedScanner``), then aggregate the REFINED
    #: partition while the reported membership / warm start stay at the
    #: outer partition.  Fixes Louvain's badly-connected-community
    #: pathology: every refined community is connected by construction,
    #: so aggregation never glues disconnected pieces into one coarse
    #: vertex.  All scanner/agg/comm backends inherit the constrained
    #: sweep through the one wrapper — pinned bit-for-bit in
    #: tests/test_engine_equiv.py.
    refine: str = "none"
    #: Skew-aware coarse re-sharding on the sharded paths ("none" |
    #: "auto"): after each aggregation, measure per-coarse-vertex edge
    #: load and, past configs.louvain_arch.RESHARD_IMBALANCE_THRESHOLD,
    #: relabel the coarse ids onto contiguous load-balanced owner ranges
    #: instead of inheriting the seed owner map (policy:
    #: configs.louvain_arch.plan_reshard).  A no-op on one shard and on
    #: balanced graphs; single-device drivers ignore it.  Default "none"
    #: keeps every committed golden's layout history bit-for-bit.
    reshard: str = "none"
    #: Pipeline the sharded pass loop's host convergence fetch: dispatch
    #: the next aggregation speculatively before reading this pass's
    #: convergence scalars, overlapping device work with host control.
    #: Dispatch order only — memberships are identical (pinned in
    #: tests/test_engine_equiv.py); single-device drivers ignore it.
    pipeline_fetch: bool = False
    #: Sharded working-state placement ("replicated" | "hybrid" | "auto"):
    #: replicated keeps the full (n_pad + 1,) membership/Sigma/sizes on
    #: every shard; hybrid keeps per-vertex state OWNER-PARTITIONED and
    #: exchanges only boundary-mover labels + touched-community deltas
    #: per round (repro.core.distributed.HybridShardedScanner), with one
    #: membership resync per phase.  "auto" measures the partitioned
    #: layout's boundary fraction and engages hybrid below the
    #: configs.louvain_arch.HYBRID_BOUNDARY_FRAC_MAX threshold on
    #: multi-shard meshes.  Single-device drivers ignore it; memberships
    #: are invariant to it (pinned bit-for-bit in
    #: tests/test_engine_equiv.py).  Default "replicated" keeps every
    #: committed golden/bench artifact's comm history bit-for-bit.
    #: Policy: configs.louvain_arch.resolve_state_layout.
    state_layout: str = "replicated"


@dataclasses.dataclass
class PassStats:
    iterations: int
    n_communities: int
    n_vertices: int
    dq_sum: float
    seconds: float
    modularity: Optional[float] = None
    frontier_size: Optional[int] = None  # seed-frontier size (delta screening)
    n_cap: Optional[int] = None          # capacities the pass ran at
    e_cap: Optional[int] = None          # (ladder tier when use_ladder)
    refine_iterations: Optional[int] = None  # constrained-sweep iterations
    n_refined: Optional[int] = None      # refined (aggregation) communities
    #: Screening granularity the step actually ran with ("community" |
    #: "vertex" | "auto" | None) — batched/fleet drivers resolve "auto"
    #: host-side and record the concrete choice here.
    screening: Optional[str] = None
    #: Scanner backend the step actually ran with ("full" | "compact" |
    #: "sharded") — the batched driver cannot honor scan_backend="auto"
    #: under vmap and records the resolved backend here.
    scan_backend: Optional[str] = None
    #: True when a requested "auto" knob could not be honored as such and
    #: was downgraded to a safe concrete choice (the explicit record the
    #: batched drivers emit instead of silently staying on the full path).
    downgraded: Optional[bool] = None


@dataclasses.dataclass
class LouvainResult:
    membership: np.ndarray       # (n,) community id per original vertex
    n_communities: int
    passes: List[PassStats]
    total_seconds: float
    #: Per-level memberships of the dendrogram: ``levels[p]`` is the (n,)
    #: membership of the ORIGINAL vertices after pass p (the fold of every
    #: renumbered pass partition up to p); ``levels[-1] == membership``.
    #: With ``refine="none"`` each level is a coarsening of the previous
    #: one (nested dendrogram); with ``refine="leiden"`` the levels hold
    #: the OUTER partitions (what the pass reports) while aggregation
    #: follows the refined chain, so consecutive levels need not nest —
    #: only the refined fold chain does.
    levels: List[np.ndarray] = dataclasses.field(default_factory=list)

    @property
    def n_passes(self) -> int:
        return len(self.passes)


def pad_membership(mem, n_cap: int) -> np.ndarray:
    """Pad a flat (n,) membership to the (n_cap + 1,) sentinel layout shared
    by the warm-start paths (single-device and sharded)."""
    out = np.full(n_cap + 1, n_cap, np.int32)
    mem = np.asarray(mem, np.int32)
    out[: len(mem)] = mem
    return out


def screened_frontier(touched: jax.Array, membership: jax.Array,
                      n_valid: jax.Array, mode: str = "community") -> jax.Array:
    """Delta-screened seed frontier from a touched-vertex mask.

    (cap + 1,) bool; works for both the single-device capacity layout
    (cap = n_cap) and the replicated sharded layout (cap = n_pad).  Thin
    alias of the engine-level ``repro.core.engine.affected_frontier`` —
    ``mode="community"`` (default) expands to whole affected communities,
    ``mode="vertex"`` is the DF-Louvain-style per-vertex flag set.
    """
    return affected_frontier(touched, membership, n_valid, mode)


@jax.jit
def singleton_init(graph: CSRGraph):
    """(comm0, sigma0, frontier0) of the cold singleton start."""
    n_cap = graph.n_cap
    comm0 = jnp.arange(n_cap + 1, dtype=jnp.int32)
    sigma0 = graph.vertex_weights()   # every vertex its own community
    frontier0 = jnp.arange(n_cap + 1) < graph.n_valid
    return comm0, sigma0, frontier0


@jax.jit
def warm_init(graph: CSRGraph, membership: jax.Array,
              frontier: jax.Array | None = None):
    """(comm0, sigma0, frontier0) resuming from ``membership``.

    ``membership`` is (n_cap,) or (n_cap + 1,) int32 community ids in vertex-id
    space (what ``LouvainResult.membership`` holds, padded to capacity);
    invalid vertex slots are remapped to the sentinel, and valid vertices
    WITHOUT a previous assignment (id >= n_cap — e.g. vertices that entered
    via an edge insert) fall back to their own singleton.  ``sigma0`` is
    recomputed from the CURRENT graph weights, so a warm start stays exact
    after edge-batch updates.  ``frontier`` optionally seeds delta screening.
    """
    n_cap = graph.n_cap
    idx = jnp.arange(n_cap + 1)
    valid = idx < graph.n_valid
    mem = jnp.concatenate([
        membership[:n_cap].astype(jnp.int32),
        jnp.full((1,), n_cap, jnp.int32),
    ])
    assigned = jnp.where(mem < n_cap, mem, idx.astype(jnp.int32))
    comm0 = jnp.where(valid, assigned, n_cap)
    sigma0 = community_weights(graph, comm0)
    frontier0 = valid if frontier is None else (frontier[: n_cap + 1] & valid)
    return comm0, sigma0, frontier0


@functools.partial(jax.jit, static_argnames=("max_iterations", "use_pruning",
                                             "gate_fraction", "work_cap"))
def _move_phase(graph: CSRGraph, comm0, sigma0, frontier0, tolerance, *,
                max_iterations: int, use_pruning: bool,
                gate_fraction: int = 2, work_cap: int = 0):
    """One local-moving phase from an arbitrary (C, Sigma, frontier) start.

    ``work_cap > 0`` runs the frontier-compacted scanner with that static
    work-buffer capacity (bit-identical results, frontier-proportional
    work); 0 is the full e_cap scan.
    """
    count_trace("move_phase")
    k = graph.vertex_weights()
    m = graph.total_weight()
    st = louvain_move(
        graph, comm0, sigma0, k, m,
        tolerance=tolerance, max_iterations=max_iterations,
        use_pruning=use_pruning, gate_fraction=gate_fraction,
        frontier0=frontier0, work_cap=work_cap,
    )
    return st.comm, st.iters, st.dq_sum


@functools.partial(jax.jit, static_argnames=("max_iterations", "use_pruning",
                                             "gate_fraction"))
def _refine_phase(graph: CSRGraph, outer, tolerance, *,
                  max_iterations: int, use_pruning: bool,
                  gate_fraction: int = 2):
    """Leiden refinement sweep: singletons under the outer-community constraint.

    Re-seeds every vertex as its own community and runs the CONSTRAINED
    engine sweep (``local_move.louvain_move(refine_outer=...)``): cross-outer
    edges are masked out of the candidate topology and only still-singleton
    vertices may move, so the result is a partition that (a) refines
    ``outer`` and (b) contains only CONNECTED communities.  ``k``/``m`` are
    the full graph's — the constraint restricts candidates, not the
    objective.
    """
    count_trace("refine_phase")
    k = graph.vertex_weights()
    m = graph.total_weight()
    n_cap = graph.n_cap
    comm0 = jnp.arange(n_cap + 1, dtype=jnp.int32)
    frontier0 = jnp.arange(n_cap + 1) < graph.n_valid
    st = louvain_move(
        graph, comm0, k, k, m,
        tolerance=tolerance, max_iterations=max_iterations,
        use_pruning=use_pruning, gate_fraction=gate_fraction,
        frontier0=frontier0, refine_outer=outer,
    )
    return st.comm, st.iters, st.dq_sum


@jax.jit
def _leiden_warm_membership(comm_ren, outer_ren, n_valid, n_agg):
    """Next-pass warm start after aggregating the REFINED partition.

    The coarse graph's vertices are the refined communities; the next pass
    must start from the OUTER partition expressed on them (Leiden's pass
    semantics — Q of the warm start equals Q of the reported outer
    partition).  For each live coarse vertex r (< ``n_agg``) the outer
    label is constant over its members, so a scatter of ``outer_ren``
    through ``comm_ren`` is well defined; the returned membership labels
    each coarse vertex with the SMALLEST coarse id sharing its outer
    community (labels must live in coarse vertex-id space).

    ``n_valid`` is the scalar live count for dense-prefix layouts or a
    ``(cap + 1,)`` bool live mask for gappy (skew-resharded) sharded
    layouts.
    """
    cap = comm_ren.shape[0] - 1
    idx = jnp.arange(cap + 1, dtype=jnp.int32)
    nv = jnp.asarray(n_valid)
    valid = (nv & (idx < cap)) if nv.ndim else (idx < nv)
    tgt = jnp.where(valid, jnp.minimum(comm_ren, cap), cap)
    oc = jnp.full((cap + 1,), cap, jnp.int32).at[tgt].set(
        jnp.where(valid, outer_ren.astype(jnp.int32), cap))
    live = idx < n_agg
    oc = jnp.where(live, jnp.minimum(oc, cap), cap)
    rep = jax.ops.segment_min(jnp.where(live, idx, cap), oc,
                              num_segments=cap + 1)
    rep = jnp.minimum(rep, cap)
    return jnp.where(live, rep[oc], cap).astype(jnp.int32)


@jax.jit
def _renumber_and_fold(comm, n_valid, n_cap_arr, global_comm):
    """Renumber pass-level communities and fold into the dendrogram lookup.

    ``comm`` may live at a laddered (shrunk) capacity while ``global_comm``
    stays at the ORIGINAL vertex capacity; invalid original slots carry
    stale sentinel values that clamp on the gather — they are sliced off
    before the membership is returned.
    """
    n_cap = global_comm.shape[0]  # == original n_cap (static via shape)
    del n_cap_arr
    count_trace("renumber_and_fold")
    comm_new, n_comms = renumber_communities(comm, n_valid, comm.shape[0] - 1)
    folded = comm_new[global_comm]
    return comm_new, n_comms, folded


@functools.partial(jax.jit, static_argnames=("backend",))
def _aggregate_phase(graph: CSRGraph, comm_renumbered, n_comms,
                     backend: str = "sort"):
    count_trace("aggregate_phase")
    return aggregate_graph(graph, comm_renumbered, n_comms, backend=backend)


def louvain(
    graph: CSRGraph,
    config: LouvainConfig = LouvainConfig(),
    *,
    init_membership: Optional[np.ndarray] = None,
    init_frontier: Optional[np.ndarray] = None,
) -> LouvainResult:
    """Run GVE-Louvain; returns the flat membership for the original vertices.

    ``init_membership`` warm-starts the FIRST pass from a previous partition
    ((n,), (n_cap,) or (n_cap + 1,) community ids) instead of singletons;
    ``init_frontier`` restricts that pass's seed frontier to a boolean
    vertex mask (delta screening — see ``repro.core.dynamic``), with or
    without a warm membership.  Later passes (after aggregation) always
    restart from singletons on the coarse graph, as in static Louvain.

    ``config.scan_backend`` picks the move-phase scanner per pass
    (``configs.louvain_arch.resolve_scan_backend``): with an active seed
    frontier the compacted sort-reduce scanner makes scan work proportional
    to |F| instead of e_cap; on the ELL family the fused Pallas kernel makes
    the whole round one kernel trip.  Memberships are bit-identical across
    backends.

    With ``config.use_ladder`` (the default), every aggregation is followed
    by a capacity re-bucket down to the smallest power-of-two tier that
    fits the coarse graph (``resolve_coarse_capacity``), so later passes'
    scans, renumbering and sorts run at coarse capacity; per-tier phases
    are jit-cached by shape, bounding recompiles at log2(e_cap) per phase.
    ``config.agg_backend`` picks the aggregation implementation (the XLA
    sort-reduce chain or the fused Pallas kernel) — memberships are
    bit-identical across ladder tiers and aggregation backends.
    """
    with spans.span("louvain"):
        return _louvain(graph, config, init_membership, init_frontier)


def _louvain(graph, config, init_membership, init_frontier) -> LouvainResult:
    t_start = time.perf_counter()
    n_cap = graph.n_cap
    n = int(spans.fetch("n_vertices", graph.n_valid))
    global_comm = jnp.arange(n_cap, dtype=jnp.int32)

    g = graph
    tol = float(config.initial_tolerance)
    passes: List[PassStats] = []
    agg_backend = resolve_agg_backend(config.agg_backend)
    if config.refine not in ("none", "leiden"):
        raise ValueError(f"refine must be 'none' or 'leiden', "
                         f"got {config.refine!r}")
    refine_on = config.refine == "leiden"
    levels: List[np.ndarray] = []
    leiden_warm = None   # outer-on-coarse membership for the next pass

    ell_family = (config.use_ell_kernel
                  or config.scan_backend in ("ell", "ell_fused"))
    if ell_family:
        from repro.core import ell_move  # lazy: pulls in Pallas

    warm_comm0 = warm_sigma0 = warm_frontier0 = None
    frontier_size0 = None
    fr = None
    if init_frontier is not None:
        # jnp-native: device-resident frontiers (delta screening) stay on
        # device — no host round-trip between batch apply and warm start.
        fr = jnp.asarray(init_frontier).astype(bool)
        if fr.shape[0] < n_cap + 1:
            fr = jnp.concatenate(
                [fr, jnp.zeros(n_cap + 1 - fr.shape[0], bool)])
    if init_membership is not None or fr is not None:
        with spans.span("warm_start"):
            if init_membership is not None:
                mem = np.asarray(init_membership, dtype=np.int32)
                if len(mem) < n_cap + 1:   # pad (n,) / (n_cap,) to capacity
                    mem = np.concatenate(
                        [mem, np.full(n_cap + 1 - len(mem), n_cap, np.int32)])
                warm_comm0, warm_sigma0, warm_frontier0 = warm_init(
                    g, jnp.asarray(mem), fr)
            else:
                # Screened frontier over a cold singleton start: still honored.
                warm_comm0, warm_sigma0, frontier0_all = singleton_init(g)
                warm_frontier0 = fr & frontier0_all
            frontier_size0 = int(spans.fetch("frontier",
                                             jnp.sum(warm_frontier0)))

    for p in range(config.max_passes):
        t0 = time.perf_counter()
        # A *screened* frontier is active only on pass 0 with init_frontier;
        # warm-only starts re-scan all vertices, so compaction buys nothing.
        frontier_frac = (frontier_size0 / max(n, 1)
                         if p == 0 and fr is not None else None)
        backend = resolve_scan_backend(
            config.scan_backend, use_ell_kernel=config.use_ell_kernel,
            frontier_frac=frontier_frac)
        work_cap = (compact_work_cap(g.e_cap, config.compact_cap_frac)
                    if backend == "compact" else 0)
        pass_caps = (g.n_cap, g.e_cap)
        with spans.span("pass", **{"pass": p}, n_cap=g.n_cap,
                        e_cap=g.e_cap, backend=backend):
            with spans.span("move"):
                if p == 0 and warm_comm0 is not None:
                    comm0, sigma0, frontier0 = (warm_comm0, warm_sigma0,
                                                warm_frontier0)
                    pass_frontier = frontier_size0
                elif leiden_warm is not None:
                    # Leiden pass semantics: the coarse graph's vertices are
                    # the REFINED communities, so the next pass resumes from
                    # the outer partition expressed on them (Q matches the
                    # reported outer Q).
                    comm0, sigma0, frontier0 = warm_init(
                        g, jnp.asarray(leiden_warm))
                    pass_frontier = None
                else:
                    comm0, sigma0, frontier0 = singleton_init(g)
                    pass_frontier = None
                if ell_family:
                    comm, iters, dq_sum = ell_move.move_phase_ell(
                        g, jnp.float32(tol),
                        max_iterations=config.max_iterations,
                        use_pruning=config.use_pruning,
                        gate_fraction=config.gate_fraction,
                        widths=config.ell_widths,
                        comm0=comm0, sigma0=sigma0, frontier0=frontier0,
                        fused=backend == "ell_fused")
                else:
                    comm, iters, dq_sum = _move_phase(
                        g, comm0, sigma0, frontier0, jnp.float32(tol),
                        max_iterations=config.max_iterations,
                        use_pruning=config.use_pruning,
                        gate_fraction=config.gate_fraction,
                        work_cap=work_cap)
                iters = int(spans.fetch("iters", iters))

            refine_iters = None
            outer_ren = None
            if refine_on:
                with spans.span("refine"):
                    if ell_family:
                        refined, r_it, _r_dq = ell_move.move_phase_ell(
                            g, jnp.float32(tol),
                            max_iterations=config.max_iterations,
                            use_pruning=config.use_pruning,
                            gate_fraction=config.gate_fraction,
                            widths=config.ell_widths,
                            fused=backend == "ell_fused", refine_outer=comm)
                    else:
                        refined, r_it, _r_dq = _refine_phase(
                            g, comm, jnp.float32(tol),
                            max_iterations=config.max_iterations,
                            use_pruning=config.use_pruning,
                            gate_fraction=config.gate_fraction)
                    refine_iters = int(spans.fetch("refine_iters", r_it))

            with spans.span("renumber"):
                if refine_on:
                    # Two folds off the SAME pre-pass global_comm: the outer
                    # fold is what this pass reports, the refined fold is
                    # what aggregation (and the dendrogram chain) follows.
                    outer_ren, n_outer, outer_fold = _renumber_and_fold(
                        comm, g.n_valid, jnp.int32(g.n_cap), global_comm)
                    comm_ren, n_comms, folded = _renumber_and_fold(
                        refined, g.n_valid, jnp.int32(g.n_cap), global_comm)
                    level = outer_fold
                    n_report = int(spans.fetch("n_outer", n_outer))
                    # aggregation granularity (refined)
                    n_comms_i = int(spans.fetch("n_comms", n_comms))
                else:
                    comm_ren, n_comms, folded = _renumber_and_fold(
                        comm, g.n_valid, jnp.int32(g.n_cap), global_comm)
                    level = folded
                    n_report = n_comms_i = int(spans.fetch("n_comms",
                                                           n_comms))
                global_comm = folded
                n_verts_i = int(spans.fetch("n_vertices", g.n_valid))
                levels.append(spans.fetch("level", level[:n]))

            q_now = float(spans.fetch("modularity", modularity(
                graph, jnp.concatenate(
                    [level, jnp.asarray([n_cap], jnp.int32)])))) \
                if config.track_modularity else None

            converged = iters <= 1                       # Alg. 1 line 7
            low_shrink = (n_report / max(n_verts_i, 1)
                          > config.aggregation_tolerance)    # line 9

            if not (converged or low_shrink or p == config.max_passes - 1):
                with spans.span("aggregate"):
                    g = _aggregate_phase(g, comm_ren, n_comms,
                                         backend=agg_backend)
                    if config.use_ladder:
                        # Ladder: re-bucket the coarse graph down to the
                        # smallest power-of-two tier that fits it, so the
                        # NEXT pass's phases run (and jit-cache) at coarse
                        # capacity.
                        n_cap_new, e_cap_new = resolve_coarse_capacity(
                            n_comms_i, int(spans.fetch("e_valid", g.e_valid)),
                            g.n_cap, g.e_cap)
                        if (n_cap_new, e_cap_new) != (g.n_cap, g.e_cap):
                            g = rebucket_capacity(g, n_cap_new=n_cap_new,
                                                  e_cap_new=e_cap_new)
                    if refine_on:
                        warm_flat = spans.fetch(
                            "leiden_warm", _leiden_warm_membership(
                                comm_ren, outer_ren, jnp.int32(n_verts_i),
                                n_comms))[:n_comms_i]
                        leiden_warm = pad_membership(warm_flat, g.n_cap)

            stats = PassStats(
                iterations=iters, n_communities=n_report,
                n_vertices=n_verts_i,
                dq_sum=float(spans.fetch("dq_sum", dq_sum)),
                seconds=time.perf_counter() - t0,
                modularity=q_now,
                frontier_size=pass_frontier if pass_frontier is not None
                else n_verts_i,
                n_cap=pass_caps[0], e_cap=pass_caps[1],
                refine_iterations=refine_iters,
                n_refined=n_comms_i if refine_on else None,
            )
        passes.append(stats)
        spans.mark("pass.counts", **{"pass": p}, sweeps=iters,
                   slots=work_cap or pass_caps[1],
                   n_vertices=n_verts_i, n_communities=n_report,
                   frontier=stats.frontier_size)
        if converged or low_shrink:
            break
        tol = tol / config.tolerance_drop            # line 13 threshold scaling

    # With refinement the dendrogram chain (global_comm) follows the REFINED
    # partitions; the reported membership is the last pass's OUTER level.
    membership = (levels[-1] if levels
                  else spans.fetch("level", global_comm[:n]))
    return LouvainResult(
        membership=membership,
        n_communities=int(len(np.unique(membership))),
        passes=passes,
        total_seconds=time.perf_counter() - t_start,
        levels=levels,
    )


def membership_modularity(graph: CSRGraph, membership) -> float:
    """Q of a flat (n,) membership array on ``graph`` (sentinel-padded)."""
    membership = np.asarray(membership)
    comm = jnp.concatenate([
        jnp.asarray(membership, jnp.int32),
        jnp.full((graph.n_cap + 1 - len(membership),), graph.n_cap,
                 jnp.int32),
    ])
    return float(spans.fetch("modularity", modularity(graph, comm)))


def louvain_modularity(graph: CSRGraph, result: LouvainResult) -> float:
    """Q of a result on the original graph."""
    return membership_modularity(graph, result.membership)
