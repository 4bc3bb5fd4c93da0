"""Pure-NumPy sequential Louvain oracle — no JAX anywhere.

An independent reference implementation of classic sequential Louvain
(Blondel et al.), used by the golden tests to pin the quality of every
execution path in the repo (single-device sort-reduce, ELL kernel, sharded
static, sharded dynamic).  It deliberately shares NO code with ``src/``:
adjacency is a plain dict-of-dicts, the move phase is the textbook
sequential sweep (vertices in id order, best community by modularity gain,
lowest-id tie-break), and aggregation rebuilds the coarse slot list with
``np.add.at``.

Slot conventions match the repo's CSR (see the ``repro.core.graph`` module
docstring): an undirected edge
{i, j}, i != j, appears as two directed slots; a self loop as one.  So
``modularity_np`` on the same slot list is directly comparable with
``repro.core.modularity.modularity``.
"""

from __future__ import annotations

import numpy as np


def modularity_np(src, dst, w, membership) -> float:
    """Q over directed slot lists (undirected edges as two slots)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    w = np.asarray(w, dtype=np.float64)
    membership = np.asarray(membership)
    m = w.sum() / 2.0
    if m <= 0:
        return 0.0
    internal = w[membership[src] == membership[dst]].sum()
    # bincount sums each bin in index order in float64, as np.add.at does.
    k = np.bincount(src, weights=w, minlength=len(membership))
    sigma = np.bincount(membership, weights=k)
    return float(internal / (2 * m) - np.sum((sigma / (2 * m)) ** 2))


def _move_phase(adj, n, m, max_sweeps=100):
    """Sequential local-moving: sweep vertices in id order until no vertex
    moves.  ``adj`` is {u: {v: w}}; returns the membership array."""
    comm = np.arange(n)
    k = np.zeros(n, np.float64)
    for u, nbrs in adj.items():
        k[u] = sum(nbrs.values())
    sigma = k.copy()

    for _ in range(max_sweeps):
        moved = False
        for u in range(n):
            nbrs = adj.get(u, {})
            # K_{u -> c} over neighbor communities (self loops excluded).
            k_to = {}
            for v, wv in nbrs.items():
                if v == u:
                    continue
                c = int(comm[v])
                k_to[c] = k_to.get(c, 0.0) + wv
            d = int(comm[u])
            sigma[d] -= k[u]  # remove u from its community
            # Best community by gain: k_uc - k_u * sigma_c / (2m); staying
            # in d scores its own gain too.  Lowest id breaks ties.
            best_c, best_gain = d, k_to.get(d, 0.0) - k[u] * sigma[d] / (2 * m)
            for c in sorted(k_to):
                gain = k_to[c] - k[u] * sigma[c] / (2 * m)
                if gain > best_gain + 1e-12:
                    best_c, best_gain = c, gain
            sigma[best_c] += k[u]
            if best_c != d:
                comm[u] = best_c
                moved = True
        if not moved:
            break
    return comm


def _aggregate(src, dst, w, comm_dense, n_comms):
    """Coarse directed slot list: communities become vertices, parallel
    slots merge by weight sum (self loops collapse community-internal
    weight, appearing once per (c, c) key as in the repo's aggregation)."""
    cs, cd = comm_dense[src], comm_dense[dst]
    key = cs.astype(np.int64) * n_comms + cd
    order = np.argsort(key, kind="stable")
    key, cs, cd, w = key[order], cs[order], cd[order], np.asarray(w)[order]
    first = np.ones(len(key), bool)
    first[1:] = key[1:] != key[:-1]
    gid = np.cumsum(first) - 1
    wsum = np.zeros(int(gid[-1]) + 1, np.float64)
    np.add.at(wsum, gid, w)
    return cs[first], cd[first], wsum


# Public alias: the coarsening oracle is also pinned directly against
# ``repro.core.aggregate.aggregate_graph`` (tests/test_aggregate.py), not
# just through the end-to-end Louvain goldens.
aggregate_oracle = _aggregate


def louvain_oracle(src, dst, w, n, *, max_passes=10):
    """Full sequential Louvain; returns the flat (n,) membership.

    ``src``/``dst``/``w`` are directed slot lists in the repo convention.
    Deterministic: in-order sweeps, lowest-id tie-break, aggregation keyed
    by dense community ids.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float64)
    m = w.sum() / 2.0
    flat = np.arange(n)
    cur_src, cur_dst, cur_w, cur_n = src, dst, w, n
    for _ in range(max_passes):
        adj = {}
        for s, d, x in zip(cur_src, cur_dst, cur_w):
            adj.setdefault(int(s), {})
            adj[int(s)][int(d)] = adj[int(s)].get(int(d), 0.0) + x
        comm = _move_phase(adj, cur_n, m)
        uniq, comm_dense = np.unique(comm, return_inverse=True)
        flat = comm_dense[flat]
        if len(uniq) == cur_n:  # no compression -> converged
            break
        cur_src, cur_dst, cur_w = _aggregate(
            cur_src, cur_dst, cur_w, comm_dense, len(uniq))
        cur_n = len(uniq)
    return flat


def oracle_graph_slots(graph):
    """Live directed slot lists (np arrays) of a repro ``CSRGraph``."""
    e = int(graph.e_valid)
    return (np.asarray(graph.src)[:e], np.asarray(graph.indices)[:e],
            np.asarray(graph.weights)[:e], int(graph.n_valid))


def refine_oracle(src, dst, w, n, outer, *, max_sweeps=100):
    """Sequential Leiden-style refinement: the NumPy reference of the
    constrained sweep (``repro.core.louvain._refine_phase``).

    Every vertex re-seeds as its own singleton community; a sweep in id
    order may merge a STILL-SINGLETON vertex into a neighboring refined
    community, but only one inside its ``outer`` community and only for a
    strictly positive modularity gain.  Because a singleton's gain against
    a community it has no edge to is never positive (the degree term of the
    gain is non-positive when sigma_d == k_u), every refined community is
    connected by construction — the property the auditor below checks.

    Returns the (n,) refined membership (a refinement of ``outer``: each
    refined community lies inside one outer community).
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float64)
    outer = np.asarray(outer)
    m = w.sum() / 2.0
    adj = {}
    for s, d, x in zip(src, dst, w):
        adj.setdefault(int(s), {})
        adj[int(s)][int(d)] = adj[int(s)].get(int(d), 0.0) + x

    comm = np.arange(n)
    k = np.zeros(n, np.float64)
    for u, nbrs in adj.items():
        k[u] = sum(nbrs.values())
    sigma = k.copy()
    size = np.ones(n, np.int64)
    if m <= 0:
        return comm

    for _ in range(max_sweeps):
        moved = False
        for u in range(n):
            if size[int(comm[u])] != 1:    # only still-singleton movers
                continue
            k_to = {}
            for v, wv in adj.get(u, {}).items():
                if v == u or outer[v] != outer[u]:
                    continue               # constrained: intra-outer only
                c = int(comm[v])
                k_to[c] = k_to.get(c, 0.0) + wv
            d = int(comm[u])
            sigma[d] -= k[u]
            best_c = d
            best_gain = k_to.get(d, 0.0) - k[u] * sigma[d] / (2 * m)
            for c in sorted(k_to):
                gain = k_to[c] - k[u] * sigma[c] / (2 * m)
                if gain > best_gain + 1e-12:
                    best_c, best_gain = c, gain
            sigma[best_c] += k[u]
            if best_c != d:
                size[d] -= 1
                size[best_c] += 1
                comm[u] = best_c
                moved = True
        if not moved:
            break
    return comm


def disconnected_communities(src, dst, membership):
    """Community ids whose induced subgraph is NOT connected (BFS audit).

    ``src``/``dst`` are directed slot lists; ``membership`` a flat (n,)
    labeling.  A community is connected when a BFS over its intra-community
    edges from any member reaches every member; singletons are trivially
    connected.  Returns the sorted list of offending community ids.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    membership = np.asarray(membership)
    members = {}
    for v, c in enumerate(membership):
        members.setdefault(int(c), []).append(v)
    intra = membership[src] == membership[dst]
    adj = {}
    for s, d in zip(src[intra], dst[intra]):
        if s != d:
            adj.setdefault(int(s), []).append(int(d))
    bad = []
    for c, vs in members.items():
        if len(vs) <= 1:
            continue
        seen = {vs[0]}
        queue = [vs[0]]
        while queue:
            u = queue.pop()
            for v in adj.get(u, ()):
                if v not in seen:
                    seen.add(v)
                    queue.append(v)
        if len(seen) != len(vs):
            bad.append(c)
    return sorted(bad)
