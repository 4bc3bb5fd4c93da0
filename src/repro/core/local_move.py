"""Sort-reduce scanner backend + single-device local-moving adapter.

The paper's asynchronous per-thread moves (OpenMP atomics) have no efficient
analogue in a bulk-synchronous XLA program, so GVE-Louvain's local-moving is
recast as rounds: every frontier vertex computes its best move against the
*same* snapshot of (C, Sigma), then all moves are applied at once (cf. the GPU
adaptations the paper cites, Naim et al. / Cheong et al.).

The round/sweep loop itself lives in ``repro.core.engine.MoveEngine`` — this
module contributes only the **scanner**: the per-thread collision-free Far-KV
hashtable of scanCommunities() becomes a sort-reduce, grouping edges by
(src, C[dst]) with a lexicographic sort and segment-summing the per-community
weights K_{i->c}.  A Pallas ELL kernel implementing the same scan as a dense
pairwise compare lives in ``repro.core.ell_move`` / ``repro.kernels``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.engine import (ConstrainedScanner, EngineConfig, MoveEngine,
                               MoveState, ReplicatedScannerBase,
                               mask_cross_outer_slots, sanitize_outer)
from repro.core.graph import CSRGraph, count_trace
from repro.core.modularity import delta_modularity

_NEG_INF = -jnp.inf

__all__ = ["CompactSortReduceScanner", "MoveState", "SortReduceScanner",
           "best_moves", "best_moves_slots", "compact_best_moves",
           "gather_frontier_slots", "louvain_move", "slot_counts",
           "spread_to_slots"]


def _scan_communities_slots(src, dst, w, comm):
    """Group directed slots by (src, C[dst]); K_{i->c} per slot.

    Returns (s_src, s_c, s_w, k_i_to_c) in sorted slot order.  Self-loop
    slots carry weight 0 (K_{i->c} excludes self edges).  The sort carries
    the weights as a payload and is stable, so each group adds its weights
    in slot order.
    """
    cdst = comm[dst]
    w0 = jnp.where(src == dst, 0.0, w)
    # primary: src, secondary: community
    s_src, s_c, s_w = jax.lax.sort((src, cdst, w0), num_keys=2,
                                   is_stable=True)

    prev_src = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_src[:-1]])
    prev_c = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_c[:-1]])
    new_group = (s_src != prev_src) | (s_c != prev_c)
    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    group_w = jax.ops.segment_sum(s_w, gid, num_segments=src.shape[0])
    return s_src, s_c, s_w, group_w[gid]


def slot_counts(graph: CSRGraph, keep: jax.Array | None = None,
                e: int | None = None) -> jax.Array:
    """(n_cap + 1,) int32 slots per source vertex of a CSR-ordered slot list.

    The degrees of the vertices in ``keep`` ((n_cap + 1,) bool; all by
    default), with the sentinel row holding the rest of the list's ``e``
    slots (default ``e_cap``): the graph's own list, or its compaction by
    ``gather_frontier_slots`` when nothing overflowed.
    """
    deg = graph.degrees()
    if keep is not None:
        deg = jnp.where(keep[:-1], deg, 0)
    e = graph.e_cap if e is None else e
    return jnp.concatenate([deg, e - jnp.sum(deg, keepdims=True)])


def spread_to_slots(counts: jax.Array, e: int,
                    *values: jax.Array) -> Tuple[jax.Array, ...]:
    """Per-vertex values at every slot of a source-sorted slot list.

    ``counts`` (n_cap + 1,) holds each vertex's number of slots, summing to
    the list's length ``e`` (the sentinel row holds the pads); each value
    is (n_cap + 1,) float32, int32 or bool.  Returns one (e,) array per
    value, equal bit for bit to ``value[s_src]`` for the list's
    non-decreasing source column ``s_src``.  A difference array places each
    run: the value's int32 bits are added at the run's head and subtracted
    at its end, and one wrapping int32 cumulative sum spreads them over the
    run.  That is a scatter of 2 (n_cap + 1) updates and one pass over e,
    not an e-sized gather.  Empty runs add nothing: their index is e,
    which is dropped.
    """
    bits = jnp.stack([
        jax.lax.bitcast_convert_type(v, jnp.int32)
        if v.dtype == jnp.float32 else v.astype(jnp.int32) for v in values])
    head = jnp.cumsum(counts) - counts
    live = counts > 0
    at = jnp.concatenate([jnp.where(live, head, e),
                          jnp.where(live, head + counts, e)])
    diff = jnp.zeros((bits.shape[0], e), jnp.int32).at[:, at].add(
        jnp.concatenate([bits, -bits], axis=1), mode="drop")
    out = jnp.cumsum(diff, axis=1)
    return tuple(
        jax.lax.bitcast_convert_type(o, jnp.float32)
        if v.dtype == jnp.float32 else o.astype(v.dtype)
        for v, o in zip(values, out))


def best_moves_slots(
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    comm: jax.Array,
    sigma: jax.Array,
    k: jax.Array,
    frontier: jax.Array,
    m: jax.Array,
    n_cap: int,
    counts: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-vertex (best community, best dQ) from a directed-slot list.

    The slot arrays may be the graph's full ``e_cap`` layout or any
    compacted subset of it (dead slots hold the sentinel ``n_cap``); a
    vertex whose live slots are ALL present gets exactly the full-scan
    answer — compaction preserves slot order, the sort is declared stable,
    and the per-group reductions therefore add the same weights in the
    same order, so the result is bit-identical, not just numerically close.

    ``counts`` (n_cap + 1,) is the number of slots per source vertex (the
    sentinel row counting the dead slots); the per-vertex values reach the
    sorted slots through it (``spread_to_slots``).  Callers derive it from
    their own slot list; without it, it is counted from ``src``.
    """
    e = src.shape[0]
    if counts is None:
        count_trace("scan.counts_bincount")
        counts = jnp.zeros((n_cap + 1,), jnp.int32).at[src].add(1)

    s_src, s_c, s_w, k_i_to_c = _scan_communities_slots(src, dst, w, comm)
    c_own, k_s, sigma_own, front_s = spread_to_slots(
        counts, e, comm, k, sigma[comm], frontier)
    # K_{i -> own community}: the own group's slots, self loops at 0, in
    # the same stable relative order as the unsorted list.
    k_to_own = jax.ops.segment_sum(
        jnp.where(s_c == c_own, s_w, 0.0), s_src, num_segments=n_cap + 1)
    (k_to_own_s,) = spread_to_slots(counts, e, k_to_own)
    dq = delta_modularity(k_i_to_c, k_to_own_s, k_s, sigma[s_c], sigma_own, m)
    valid = (s_c != c_own) & (s_src != n_cap) & (s_c != n_cap) & front_s
    dq = jnp.where(valid, dq, _NEG_INF)

    best_dq = jax.ops.segment_max(dq, s_src, num_segments=n_cap + 1)
    best_dq = jnp.where(jnp.isfinite(best_dq), best_dq, _NEG_INF)
    (best_dq_s,) = spread_to_slots(counts, e, best_dq)
    is_best = (dq == best_dq_s) & valid
    best_c = jax.ops.segment_min(
        jnp.where(is_best, s_c, n_cap), s_src, num_segments=n_cap + 1
    )
    # Empty segments yield iinfo.max — clamp into the sentinel slot.
    best_c = jnp.minimum(best_c, n_cap)
    return best_c, best_dq


def best_moves(
    graph: CSRGraph,
    comm: jax.Array,
    sigma: jax.Array,
    k: jax.Array,
    frontier: jax.Array,
    m: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Per-vertex (best community, best dQ) from one snapshot (sort-reduce path)."""
    return best_moves_slots(graph.src, graph.indices, graph.weights, comm,
                            sigma, k, frontier, m, graph.n_cap,
                            slot_counts(graph))


def gather_frontier_slots(
    graph: CSRGraph, frontier: jax.Array, work_cap: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Compact the frontier vertices' edge slots into a (work_cap,) buffer.

    Order-preserving: slot i of the output is the i-th edge slot (in CSR
    order) whose src is in the frontier, so downstream sort-reduce results
    are bit-identical to the full scan.  Slots past ``work_cap`` are dropped
    — ``overflow`` reports whether any were, in which case the caller must
    fall back to the full scan (the compact result would be missing edges).

    Returns (src, dst, w, overflow) with dead slots = (n_cap, n_cap, 0).
    """
    n_cap = graph.n_cap
    src, dst, w = graph.src, graph.indices, graph.weights
    in_f = frontier[src]                       # pad slots: frontier[n_cap]=F
    rank = jnp.cumsum(in_f.astype(jnp.int32)) - 1
    keep = in_f & (rank < work_cap)
    slot = jnp.where(keep, rank, work_cap)
    out_src = jnp.full((work_cap + 1,), n_cap, jnp.int32).at[slot].set(
        jnp.where(keep, src, n_cap))[:work_cap]
    out_dst = jnp.full((work_cap + 1,), n_cap, jnp.int32).at[slot].set(
        jnp.where(keep, dst, n_cap))[:work_cap]
    out_w = jnp.zeros((work_cap + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, w, 0.0))[:work_cap]
    overflow = jnp.sum(in_f.astype(jnp.int32)) > work_cap
    return out_src, out_dst, out_w, overflow


def compact_best_moves(
    graph: CSRGraph,
    comm: jax.Array,
    sigma: jax.Array,
    k: jax.Array,
    frontier: jax.Array,
    m: jax.Array,
    work_cap: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Frontier-proportional best-move scan with measured-overflow fallback.

    Gathers only the frontier vertices' edge slots into a static
    ``(work_cap,)`` buffer and scans that, so per-round scan cost is
    O(work_cap log work_cap) instead of O(e_cap log e_cap) — the
    DF-Louvain-style payoff when |F| << n.  When the frontier's slots
    exceed the cap, ``lax.cond`` dispatches the full e_cap scan instead
    (shapes stay static; one compiled program handles both regimes).

    Returns (best_c, best_dq, overflowed); the first two are bit-identical
    to ``best_moves`` either way.
    """
    c_src, c_dst, c_w, overflow = gather_frontier_slots(graph, frontier,
                                                        work_cap)

    def full_scan(_):
        return best_moves(graph, comm, sigma, k, frontier, m)

    def compact_scan(_):
        return best_moves_slots(c_src, c_dst, c_w, comm, sigma, k, frontier,
                                m, graph.n_cap,
                                slot_counts(graph, frontier, work_cap))

    best_c, best_dq = jax.lax.cond(overflow, full_scan, compact_scan,
                                   operand=None)
    return best_c, best_dq, overflow


class SortReduceScanner(ReplicatedScannerBase):
    """Engine backend: CSR sort-reduce scan on a single device.

    Local layout == replicated layout ((n_cap + 1,) with the sentinel slot);
    all topology hooks are the identities from ``ReplicatedScannerBase``.
    """

    def __init__(self, graph: CSRGraph, k: jax.Array, m: jax.Array):
        super().__init__(graph.n_cap, graph.n_valid, k)
        self.graph = graph
        self.m = m

    def scan(self, comm, sigma, frontier):
        return best_moves(self.graph, comm, sigma, self.k_local, frontier,
                          self.m)

    def mark_neighbors(self, moved: jax.Array) -> jax.Array:
        g = self.graph
        (moved_s,) = spread_to_slots(slot_counts(g), g.e_cap,
                                     moved.astype(jnp.int32))
        marked = jax.ops.segment_max(moved_s, g.indices,
                                     num_segments=g.n_cap + 1)
        return marked > 0


class CompactSortReduceScanner(SortReduceScanner):
    """Engine backend: frontier-compacted CSR sort-reduce scan.

    Same topology surface as ``SortReduceScanner`` — only the scan differs:
    per round it gathers the CURRENT frontier's edge slots into a static
    ``(work_cap,)`` buffer and sort-reduces that, falling back to the full
    ``e_cap`` scan inside the same compiled program when the frontier's
    slots overflow the cap.  Results are bit-identical to the full scan;
    only the work is frontier-proportional (ROADMAP "Unified move engine ->
    Next": scan ONLY frontier vertices' edge slots).
    """

    def __init__(self, graph: CSRGraph, k: jax.Array, m: jax.Array,
                 work_cap: int):
        super().__init__(graph, k, m)
        if not 0 < work_cap:
            raise ValueError(f"work_cap must be positive, got {work_cap}")
        self.work_cap = int(min(work_cap, graph.e_cap))

    def scan(self, comm, sigma, frontier):
        best_c, best_dq, _ = compact_best_moves(
            self.graph, comm, sigma, self.k_local, frontier, self.m,
            self.work_cap)
        return best_c, best_dq


def louvain_move(
    graph: CSRGraph,
    comm: jax.Array,
    sigma: jax.Array,
    k: jax.Array,
    m: jax.Array,
    *,
    tolerance: jax.Array,
    max_iterations: int = 20,
    use_pruning: bool = True,
    gate_fraction: int = 2,
    frontier0: jax.Array | None = None,
    work_cap: int = 0,
    refine_outer: jax.Array | None = None,
) -> MoveState:
    """Algorithm 2 on the sort-reduce backend — a thin engine adapter.

    ``comm``/``sigma`` may be ANY consistent membership + community-weight
    snapshot, not just the singleton start — warm starts (dynamic Louvain)
    pass the previous membership here.  ``frontier0`` optionally restricts
    the first round to a seed set (delta screening); ``None`` means all
    valid vertices.  ``work_cap > 0`` selects the frontier-compacted
    scanner with that (static) work-buffer capacity; 0 keeps the full-scan
    backend.  Sweep/tolerance/gating semantics are the engine's — see
    ``repro.core.engine.MoveEngine``.

    ``refine_outer`` switches the sweep into the Leiden-style CONSTRAINED
    mode: cross-outer edge slots are masked (dst -> sentinel, w -> 0) so a
    vertex only ever sees candidates inside its outer community, and the
    scanner is wrapped in ``engine.ConstrainedScanner`` (intra-outer target
    + singleton-only movers).  ``k``/``m``/``sigma`` stay the FULL graph's
    quantities — only the candidate topology is restricted.
    """
    valid = jnp.arange(graph.n_cap + 1) < graph.n_valid
    frontier0 = valid if frontier0 is None else (frontier0 & valid)
    if refine_outer is not None:
        outer = sanitize_outer(refine_outer, graph.n_valid, graph.n_cap)
        dst, w = mask_cross_outer_slots(graph.src, graph.indices,
                                        graph.weights, outer, graph.n_cap)
        graph = graph._replace(indices=dst, weights=w)
    scanner = (CompactSortReduceScanner(graph, k, m, work_cap) if work_cap
               else SortReduceScanner(graph, k, m))
    if refine_outer is not None:
        scanner = ConstrainedScanner(scanner, outer, graph.n_valid,
                                     gate_fraction=gate_fraction)
    engine = MoveEngine(
        scanner,
        EngineConfig(max_iterations=max_iterations, use_pruning=use_pruning,
                     gate_fraction=gate_fraction))
    return engine.run(comm, sigma, frontier0, tolerance)
