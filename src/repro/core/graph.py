"""Static-shape graph containers for JAX Louvain.

The paper (GVE-Louvain §4.1.7/4.1.8) preallocates CSR buffers once and reuses
them across passes; under jit static shapes make this mandatory, so the same
design falls out naturally.  A graph lives in buffers of fixed capacity
(``n_cap`` vertex slots, ``e_cap`` directed edge slots); the *valid* prefix is
tracked with dynamic scalars.  Invalid slots use the sentinel vertex ``n_cap``
(all index arrays are addressable up to ``n_cap`` inclusive, so sentinel
scatters land in a scratch slot).

Conventions (the slot contract every module in ``repro.core`` assumes):
  - undirected edge {i,j}, i != j   -> two directed slots (i,j,w) and (j,i,w)
  - self loop {i,i}                 -> ONE slot (i,i,w)
  - K_i  = sum of slot weights out of i          (row sum of adjacency)
  - m    = (sum of all slot weights) / 2
  - src  = each row id repeated by its degree, in row order, then the
           padding slots (``local_move.slot_counts`` counts slots by it)
These are conserved exactly under community coarsening.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans


class CSRGraph(NamedTuple):
    """Padded CSR graph.  All fields are jax arrays unless noted.

    indptr  : (n_cap + 1,) int32 — offsets; rows >= n_valid are empty.
    indices : (e_cap,) int32 — neighbor ids; padding slots hold ``n_cap``.
    weights : (e_cap,) float32 — edge weights; padding slots hold 0.
    src     : (e_cap,) int32 — row id of each slot (CSR expanded); pad = n_cap.
    n_valid : () int32 — number of valid vertices (dynamic).
    e_valid : () int32 — number of valid edge slots (dynamic).
    """

    indptr: jax.Array
    indices: jax.Array
    weights: jax.Array
    src: jax.Array
    n_valid: jax.Array
    e_valid: jax.Array

    @property
    def n_cap(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def e_cap(self) -> int:
        return self.indices.shape[0]

    def degrees(self) -> jax.Array:
        """(n_cap,) int32 out-degree (slot count) per vertex."""
        return self.indptr[1:] - self.indptr[:-1]

    def vertex_weights(self) -> jax.Array:
        """(n_cap + 1,) float32 — K_i, with a trailing sentinel slot (=0)."""
        k = jax.ops.segment_sum(self.weights, self.src, num_segments=self.n_cap + 1)
        return k.astype(jnp.float32)

    def total_weight(self) -> jax.Array:
        """Scalar m = sum(w)/2 (float32)."""
        return jnp.sum(self.weights) * 0.5


def _np_int32(x) -> np.ndarray:
    return np.asarray(x, dtype=np.int32)


def build_csr(
    src: np.ndarray,
    dst: np.ndarray,
    weight: np.ndarray,
    n: int,
    *,
    n_cap: int | None = None,
    e_cap: int | None = None,
    symmetrize: bool = False,
    dedup: bool = True,
) -> CSRGraph:
    """Host-side CSR builder from a directed slot list.

    ``symmetrize=True`` adds reverse slots for every i != j pair (the paper adds
    reverse edges to directed inputs, Table 1).  ``dedup`` merges parallel slots
    by summing weights.
    """
    src = _np_int32(src)
    dst = _np_int32(dst)
    weight = np.asarray(weight, dtype=np.float32)
    if symmetrize:
        off = src != dst
        src = np.concatenate([src, dst[off]])
        dst = np.concatenate([dst, src[: len(off)][off]])  # original src
        weight = np.concatenate([weight, weight[: len(off)][off]])
    if dedup and len(src):
        key = src.astype(np.int64) * (n + 1) + dst.astype(np.int64)
        order = np.argsort(key, kind="stable")
        key, src, dst, weight = key[order], src[order], dst[order], weight[order]
        first = np.ones(len(key), dtype=bool)
        first[1:] = key[1:] != key[:-1]
        gid = np.cumsum(first) - 1
        # bincount sums each group in slot order in float64, as a
        # sequential np.add.at would, in one vectorised pass.
        wsum = np.bincount(gid, weights=weight)
        # The dedup key is the CSR order, so the groups are already in it.
        src, dst, weight = src[first], dst[first], wsum.astype(np.float32)
    else:
        order = np.argsort(src.astype(np.int64) * (n + 1) + dst,
                           kind="stable")
        src, dst, weight = src[order], dst[order], weight[order]

    e = len(src)
    n_cap = int(n_cap if n_cap is not None else n)
    e_cap = int(e_cap if e_cap is not None else e)
    assert n_cap >= n and e_cap >= e, "capacity below graph size"

    counts = np.zeros(n_cap + 1, dtype=np.int64)
    counts[1:] = np.bincount(src, minlength=n_cap)
    indptr = np.cumsum(counts).astype(np.int32)

    pad_i = np.full(e_cap - e, n_cap, dtype=np.int32)
    pad_w = np.zeros(e_cap - e, dtype=np.float32)
    return CSRGraph(
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(np.concatenate([dst, pad_i])),
        weights=jnp.asarray(np.concatenate([weight, pad_w])),
        src=jnp.asarray(np.concatenate([src, pad_i])),
        n_valid=jnp.asarray(n, dtype=jnp.int32),
        e_valid=jnp.asarray(e, dtype=jnp.int32),
    )


def from_networkx(g, *, n_cap: int | None = None, e_cap: int | None = None) -> CSRGraph:
    """Build from an undirected networkx graph (unit weights by default)."""
    n = g.number_of_nodes()
    nodes = {v: i for i, v in enumerate(g.nodes())}
    src, dst, w = [], [], []
    for u, v, data in g.edges(data=True):
        wt = float(data.get("weight", 1.0))
        iu, iv = nodes[u], nodes[v]
        src.append(iu)
        dst.append(iv)
        w.append(wt)
        if iu != iv:
            src.append(iv)
            dst.append(iu)
            w.append(wt)
    return build_csr(np.array(src or [0][:0]), np.array(dst or [0][:0]),
                     np.array(w or [0.0][:0]), n, n_cap=n_cap, e_cap=e_cap)


# Trace-time side-effect counters: jitted phases bump their key ONCE per
# trace (Python bodies run only while tracing), so tests can assert a
# bounded compile count across ladder tiers without poking jit internals.
TRACE_COUNTS: dict = {}


def count_trace(name: str) -> None:
    """Bump a trace counter (call from inside a jitted function body)."""
    TRACE_COUNTS[name] = TRACE_COUNTS.get(name, 0) + 1


@functools.partial(jax.jit, static_argnames=("n_cap_new", "e_cap_new"))
def rebucket_capacity(graph: CSRGraph, *, n_cap_new: int,
                      e_cap_new: int) -> CSRGraph:
    """Copy a graph into buffers of different capacity (shrink OR grow).

    The capacity-ladder primitive: valid data must fit the target
    (``n_valid <= n_cap_new``, ``e_valid <= e_cap_new``, live edge slots in
    a compact prefix — all true for ``aggregate_graph`` outputs and
    ``build_csr``/``apply_edge_batch`` graphs).  Vertex-id arrays rewrite
    the sentinel (old ``n_cap`` -> new); valid ids are < ``n_valid`` so
    they survive either direction unchanged.  Callers check fit host-side;
    see ``repro.configs.louvain_arch.resolve_coarse_capacity`` for the
    tier policy.
    """
    count_trace("rebucket_capacity")
    n_cap, e_cap = graph.n_cap, graph.e_cap

    def remap(x):
        # Valid ids < n_valid <= n_cap_new; everything >= min(n_cap,
        # n_cap_new) is sentinel/padding in either direction.
        return jnp.where(x >= jnp.int32(min(n_cap, n_cap_new)),
                         jnp.int32(n_cap_new), x)

    def resize_e(x, fill):
        if e_cap_new <= e_cap:
            return x[:e_cap_new]
        return jnp.concatenate(
            [x, jnp.full((e_cap_new - e_cap,), fill, x.dtype)])

    if n_cap_new <= n_cap:
        indptr = graph.indptr[: n_cap_new + 1]
    else:
        indptr = jnp.pad(graph.indptr, (0, n_cap_new - n_cap), mode="edge")
    return CSRGraph(
        indptr=indptr,
        indices=remap(resize_e(graph.indices, jnp.int32(n_cap))),
        weights=resize_e(graph.weights, jnp.float32(0.0)),
        src=remap(resize_e(graph.src, jnp.int32(n_cap))),
        n_valid=graph.n_valid,
        e_valid=graph.e_valid,
    )


def rebucket_graph(graph: CSRGraph, n_cap_new: int,
                   e_cap_new: int) -> CSRGraph:
    """Host-checked wrapper over ``rebucket_capacity``: validates that the
    live data fits the target capacity before re-bucketing (one device
    sync; the ladder hot path calls the jitted core directly with counts
    it already fetched)."""
    n_valid, e_valid = int(graph.n_valid), int(graph.e_valid)
    if n_valid > n_cap_new or e_valid > e_cap_new:
        raise ValueError(
            f"graph does not fit target capacity: n_valid={n_valid} > "
            f"n_cap_new={n_cap_new} or e_valid={e_valid} > "
            f"e_cap_new={e_cap_new}")
    return rebucket_capacity(graph, n_cap_new=int(n_cap_new),
                             e_cap_new=int(e_cap_new))


def empty_like_caps(n_cap: int, e_cap: int) -> CSRGraph:
    """An all-padding graph buffer (used as the coarse-graph target)."""
    return CSRGraph(
        indptr=jnp.zeros(n_cap + 1, dtype=jnp.int32),
        indices=jnp.full((e_cap,), n_cap, dtype=jnp.int32),
        weights=jnp.zeros((e_cap,), dtype=jnp.float32),
        src=jnp.full((e_cap,), n_cap, dtype=jnp.int32),
        n_valid=jnp.asarray(0, dtype=jnp.int32),
        e_valid=jnp.asarray(0, dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# Degree-bucketed ELL view (the TPU tiling of the paper's "dynamic schedule").
# ---------------------------------------------------------------------------

class ELLBlock(NamedTuple):
    """A fixed-width padded adjacency block for vertices of bounded degree.

    rows     : (n_rows,) int32 — vertex id per row (pad rows = n_cap).
    cols     : (n_rows, width) int32 — neighbors (pad = n_cap).
    w        : (n_rows, width) float32 — weights (pad = 0).
    """

    rows: jax.Array
    cols: jax.Array
    w: jax.Array

    @property
    def width(self) -> int:
        return self.cols.shape[1]


def to_ell_blocks(
    graph: CSRGraph,
    widths: Tuple[int, ...] = (16, 64, 256),
) -> Tuple[list, np.ndarray]:
    """Host-side degree bucketing: vertices with degree <= widths[k] (and >
    widths[k-1]) go to block k.  Returns (blocks, leftover_vertex_ids) where
    leftover vertices exceed the largest width (handled by the sorted path).

    Rows are padded to a whole number of the kernel's row tiles
    (``louvain_scan.ops.padded_rows``).
    """
    from repro.kernels.louvain_scan.ops import padded_rows  # lazy: Pallas

    indptr = spans.fetch("ell.indptr", graph.indptr)
    indices = spans.fetch("ell.indices", graph.indices)
    weights = spans.fetch("ell.weights", graph.weights)
    n = int(spans.fetch("ell.n_vertices", graph.n_valid))
    n_cap = graph.n_cap
    deg = indptr[1 : n + 1] - indptr[:n]

    blocks = []
    lo = 0
    assigned = np.zeros(n, dtype=bool)
    for width in widths:
        sel = np.where((deg > lo) & (deg <= width))[0]
        if width == widths[0]:
            sel = np.where(deg <= width)[0]  # include isolated vertices
        lo = width
        n_rows = padded_rows(len(sel), width)
        rows = np.full(n_rows, n_cap, dtype=np.int32)
        cols = np.full((n_rows, width), n_cap, dtype=np.int32)
        wmat = np.zeros((n_rows, width), dtype=np.float32)
        rows[: len(sel)] = sel
        # Row r takes slots indptr[v] .. indptr[v] + deg[v] of v = sel[r],
        # scattered in one pass instead of a loop over rows.
        d = deg[sel]
        row_ix = np.repeat(np.arange(len(sel)), d)
        col_ix = np.arange(int(d.sum())) - np.repeat(np.cumsum(d) - d, d)
        slot = indptr[sel].repeat(d) + col_ix
        cols[row_ix, col_ix] = indices[slot]
        wmat[row_ix, col_ix] = weights[slot]
        assigned[sel] = True
        blocks.append(ELLBlock(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(wmat)))
    leftover = np.where(~assigned)[0].astype(np.int32)
    return blocks, leftover


def connected_total_weight_check(graph: CSRGraph) -> float:
    """Debug helper: host-side 2m."""
    return float(np.asarray(graph.weights).sum())
