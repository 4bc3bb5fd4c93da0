"""Plain NumPy reference of the Louvain semantics that the benchmark checks.

It imports nothing of the program.  It follows the GVE-Louvain pass loop
(Sahu, arXiv:2312.04876, Algorithms 1-3) in the bulk-synchronous form that
the system under test states for its move phase:

* a sweep is ``GATE_FRACTION`` rounds; in round ``r`` only the vertices that
  a Weyl hash of ``(vertex, r)`` selects may move, and every selected
  frontier vertex picks its best community against the same snapshot of
  memberships and community weights;
* the best community maximises the modularity gain (Eq. 2), ties to the
  lowest community id; a move needs a gain above 0, and two singletons may
  only merge towards the lower id;
* vertex pruning: a processed vertex leaves the frontier and the neighbours
  of movers join it;
* a pass sweeps until a sweep's total gain is at most the pass tolerance or
  ``MAX_ITERATIONS`` sweeps ran; passes stop when a pass took at most one
  sweep or kept more than ``AGGREGATION_TOLERANCE`` of its vertices; the
  tolerance drops by ``TOLERANCE_DROP`` each pass;
* communities are renumbered densely in the order of their old ids and the
  graph is coarsened by summing the weights of parallel slots.

Graphs are live directed slots in CSR order (sorted by source, then
destination): an undirected edge is two slots, a self loop one.  The value
type ``dtype`` is the precision of every weight, degree, community weight
and gain: float32 is the precision the system states, and a lower one makes
the control of the check.  Sums are accumulated in float64 and rounded to
``dtype``; they are exact for the integer weights the benchmark's graphs
carry.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# The paper's parameters (GVE-Louvain Section 4.1), as the system states them.
MAX_PASSES = 10
MAX_ITERATIONS = 20
INITIAL_TOLERANCE = 0.01
TOLERANCE_DROP = 10.0
AGGREGATION_TOLERANCE = 0.8
GATE_FRACTION = 2

# Weyl gate hash: Knuth's 2654435761 as int32, and an odd increment.
GATE_MUL = np.int32(-1640531535)
GATE_INC = np.int32(40503)


@dataclasses.dataclass
class Slots:
    """Live directed slots in CSR order, and the vertex count."""

    src: np.ndarray   # (e,) int32
    dst: np.ndarray   # (e,) int32
    w: np.ndarray     # (e,) dtype
    n: int


def round_gate(n: int, round_ix: int) -> np.ndarray:
    """(n,) bool: the vertices that may move in round ``round_ix``."""
    ids = np.arange(n, dtype=np.int32)
    with np.errstate(over="ignore"):
        h = ids * GATE_MUL + np.array([round_ix], np.int32) * GATE_INC
    return np.abs(h >> 13) % GATE_FRACTION == 0


def _sum_by(index, values, size, dtype):
    """Per-index sums of ``values``, accumulated in float64."""
    return np.bincount(index, weights=values.astype(np.float64),
                       minlength=size).astype(dtype)


def _group_sums(key, values, dtype):
    """(sorted distinct keys, per-key sums of ``values``)."""
    order = np.argsort(key, kind="stable")
    key = key[order]
    starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    sums = np.add.reduceat(values[order].astype(np.float64), starts)
    return key[starts], sums.astype(dtype)


def vertex_weights(g: Slots, dtype) -> np.ndarray:
    return _sum_by(g.src, g.w, g.n, dtype)


def _best_moves(g, active, comm, sigma, k, m, dtype):
    """(best community, best gain) of every ``active`` vertex.

    Inactive vertices get (n, -inf); only active ones can move, so the
    scan covers just their slots.
    """
    n = g.n
    best_c = np.full(n, n, np.int32)
    best_dq = np.full(n, -np.inf, dtype)
    sel = active[g.src]
    s, d, w = g.src[sel], g.dst[sel], g.w[sel]
    c = comm[d]
    own_c = comm[s]
    own = (c == own_c) & (d != s)
    k_to_own = _sum_by(s[own], w[own], n, dtype)
    cand = c != own_c
    s, c, w = s[cand], c[cand], w[cand]
    if len(s) == 0:
        return best_c, best_dq
    ukey, k_to_c = _group_sums(s.astype(np.int64) * (n + 1) + c, w, dtype)
    gs = (ukey // (n + 1)).astype(np.int32)
    gc = (ukey % (n + 1)).astype(np.int32)
    dq = delta_modularity(k_to_c, k_to_own[gs], k[gs], sigma[gc],
                          sigma[comm[gs]], m, dtype)
    # Groups are sorted by (vertex, community): a vertex's first group of
    # the best gain holds the lowest community id among the best.
    starts = np.flatnonzero(np.r_[True, gs[1:] != gs[:-1]])
    vmax = np.maximum.reduceat(dq, starts)
    hit = np.flatnonzero(dq == np.repeat(vmax, np.diff(np.r_[starts,
                                                             len(gs)])))
    first = hit[np.r_[True, gs[hit[1:]] != gs[hit[:-1]]]]
    best_dq[gs[starts]] = vmax
    best_c[gs[first]] = gc[first]
    return best_c, best_dq


def delta_modularity(k_to_c, k_to_d, k_i, sigma_c, sigma_d, m, dtype):
    """Eq. 2 in ``dtype``: gain of moving i from d (i inside) to c."""
    two = dtype(2.0)
    return ((k_to_c - k_to_d) / m
            - k_i * ((k_i + sigma_c) - sigma_d) / ((two * m) * m))


def move_phase(g: Slots, comm, sigma, frontier, tolerance, dtype):
    """One local-moving phase; returns (membership, sweeps)."""
    n = g.n
    k = vertex_weights(g, dtype)
    m = dtype(np.sum(g.w, dtype=np.float64) * 0.5)
    if not m > 0:
        return comm, 1
    comm = comm.astype(np.int32)
    sigma = sigma.astype(dtype).copy()
    frontier = frontier.copy()
    tol = np.float32(tolerance)
    iters, dq_sweep = 0, np.float32(np.inf)
    while iters < MAX_ITERATIONS and dq_sweep > tol:
        dq_sweep = np.float32(0.0)
        for r in range(GATE_FRACTION):
            gate = round_gate(n, iters * GATE_FRACTION + r)
            active = frontier & gate
            sizes = np.bincount(comm, minlength=n + 1)
            best_c, best_dq = _best_moves(g, active, comm, sigma, k, m,
                                          dtype)
            swap_blocked = ((sizes[comm] == 1) & (sizes[best_c] == 1)
                            & (best_c > comm))
            move = (active & (best_dq > 0) & (best_c != comm) & (best_c < n)
                    & ~swap_blocked)
            dq_sweep = np.float32(dq_sweep + np.float32(
                np.sum(best_dq[move], dtype=np.float64)))
            moved_k = k[move]
            sigma = ((sigma + _sum_by(best_c[move], moved_k, n, dtype))
                     - _sum_by(comm[move], moved_k, n, dtype))
            comm = np.where(move, best_c, comm)
            neighbours = np.zeros(n, bool)
            neighbours[g.dst[move[g.src]]] = True
            frontier = neighbours | (frontier & ~gate)
        iters += 1
    return comm, iters


def aggregate(g: Slots, comm_dense, n_comms, dtype) -> Slots:
    """Coarse graph: communities become vertices, parallel slots merge."""
    ukey, w = _group_sums(
        comm_dense[g.src].astype(np.int64) * n_comms + comm_dense[g.dst],
        g.w, dtype)
    return Slots((ukey // n_comms).astype(np.int32),
                 (ukey % n_comms).astype(np.int32), w, int(n_comms))


def louvain(g: Slots, dtype=np.float32, init_membership=None,
            init_frontier=None) -> np.ndarray:
    """Flat (n,) membership of the pass loop, cold or warm.

    ``init_membership`` resumes the first pass from a partition (community
    weights recomputed from ``g``); ``init_frontier`` limits that pass's
    first frontier to a vertex mask.
    """
    g = Slots(g.src, g.dst, g.w.astype(dtype), g.n)
    n = g.n
    fold = np.arange(n)
    tol = INITIAL_TOLERANCE
    membership = fold
    for p in range(MAX_PASSES):
        k = vertex_weights(g, dtype)
        if p == 0 and init_membership is not None:
            comm0 = np.asarray(init_membership, np.int64)
            sigma0 = _sum_by(comm0, k, g.n, dtype)
        else:
            comm0, sigma0 = np.arange(g.n), k
        frontier0 = np.ones(g.n, bool)
        if p == 0 and init_frontier is not None:
            frontier0 = np.asarray(init_frontier, bool).copy()
        comm, iters = move_phase(g, comm0, sigma0, frontier0, tol, dtype)
        uniq, dense = np.unique(comm, return_inverse=True)
        n_comms = len(uniq)
        fold = dense[fold]
        membership = fold
        converged = iters <= 1
        low_shrink = n_comms / max(g.n, 1) > AGGREGATION_TOLERANCE
        if converged or low_shrink or p == MAX_PASSES - 1:
            break
        g = aggregate(g, dense, n_comms, dtype)
        tol = tol / TOLERANCE_DROP
    return membership.astype(np.int32)


def modularity(src, dst, w, membership) -> float:
    """Q (Eq. 1) in float64 over directed slots."""
    w = np.asarray(w, np.float64)
    membership = np.asarray(membership)
    two_m = w.sum()
    if two_m <= 0:
        return 0.0
    internal = w[membership[src] == membership[dst]].sum()
    k = np.bincount(src, weights=w, minlength=len(membership))
    sigma = np.bincount(membership, weights=k)
    return float(internal / two_m - np.sum((sigma / two_m) ** 2))


def _misplaced(a, b) -> int:
    """Vertices of ``a``'s communities outside their largest overlap with
    one community of ``b``."""
    width = int(b.max()) + 1
    pair, counts = np.unique(a * width + b, return_counts=True)
    best = np.zeros(int(a.max()) + 1, np.int64)
    np.maximum.at(best, pair // width, counts)
    return len(a) - int(best.sum())


def mismatch(a, b) -> float:
    """Share of vertices placed differently by two partitions.

    0 exactly when ``a`` and ``b`` are the same partition under any
    labelling.  Counted both ways (a split and a merge both show), the
    larger of the two shares.
    """
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    if a.shape != b.shape or a.ndim != 1:
        return 1.0
    if len(a) == 0:
        return 0.0
    return max(_misplaced(a, b), _misplaced(b, a)) / len(a)
