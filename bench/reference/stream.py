"""Plain NumPy reference of a stream of edge batches over a graph.

A batch is a list of undirected assignments ``{u, v} -> w``: ``w > 0``
inserts or reweights, ``w == 0`` deletes; a later entry of a batch wins over
an earlier one.  The vertices whose incident weights changed are the batch's
touched set.  After each batch the communities are refreshed by a warm
Louvain pass loop (``bench.reference.louvain``) from the previous
membership, with the first frontier screened to the touched vertices and
every member of their communities (delta screening, Zarayeneh et al.).
"""

from __future__ import annotations

import numpy as np

from bench.reference import louvain as ref


class StreamGraph:
    """A graph held as sorted directed slot keys ``src * n + dst``."""

    def __init__(self, src, dst, w, n: int):
        self.n = int(n)
        key = np.asarray(src, np.int64) * self.n + np.asarray(dst, np.int64)
        order = np.argsort(key, kind="stable")
        self.key = key[order]
        self.w = np.asarray(w, np.float32)[order]

    def slots(self) -> ref.Slots:
        return ref.Slots((self.key // self.n).astype(np.int32),
                         (self.key % self.n).astype(np.int32),
                         self.w, self.n)

    def apply(self, u, v, w) -> np.ndarray:
        """Apply one batch (or several, concatenated in order); returns the
        (n,) mask of vertices whose incident weights changed."""
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        w = np.asarray(w, np.float32)
        a, b = np.minimum(u, v), np.maximum(u, v)
        # The last assignment of each undirected pair wins.
        _, last = np.unique((a * self.n + b)[::-1], return_index=True)
        last = len(a) - 1 - last
        a, b, w = a[last], b[last], w[last]
        two = a != b
        key = np.r_[a * self.n + b, (b * self.n + a)[two]]
        new = np.r_[w, w[two]]
        ends = np.r_[a, b[two]], np.r_[b, a[two]]
        at = np.searchsorted(self.key, key)
        present = np.zeros(len(key), bool)
        inside = at < len(self.key)
        present[inside] = self.key[at[inside]] == key[inside]
        old = np.zeros(len(key), np.float32)
        old[present] = self.w[at[present]]
        changed = old != new
        touched = np.zeros(self.n, bool)
        touched[ends[0][changed]] = True
        touched[ends[1][changed]] = True
        keep = np.ones(len(self.key), bool)
        keep[at[present & changed]] = False
        put = changed & (new > 0)
        order = np.argsort(key[put])
        put_key, put_w = key[put][order], new[put][order]
        base_key, base_w = self.key[keep], self.w[keep]
        at = np.searchsorted(base_key, put_key)
        self.key = np.insert(base_key, at, put_key)
        self.w = np.insert(base_w, at, put_w)
        return touched


def screened_frontier(touched, membership) -> np.ndarray:
    """Touched vertices plus every member of their communities."""
    hit = np.zeros(int(membership.max()) + 1, bool)
    hit[membership[touched]] = True
    return touched | hit[membership]


def update(graph: StreamGraph, membership, u, v, w, dtype=np.float32):
    """Apply one batch to ``graph`` and return the refreshed membership."""
    touched = graph.apply(u, v, w)
    return ref.louvain(graph.slots(), dtype,
                       init_membership=membership,
                       init_frontier=screened_frontier(touched, membership))
