"""In-capacity CSR edge-batch updates (the streaming half of dynamic Louvain).

A batch is a set of undirected ``{u, v} -> w`` assignments applied to the
padded ``CSRGraph`` buffers *in place of capacity* (shapes never change, so
every downstream jit — move phase, aggregation, modularity — reuses its
compiled form across the stream):

    w > 0, edge absent   -> insert
    w > 0, edge present  -> reweight (set, not add)
    w == 0               -> delete (no-op if absent)

The update is one sort-reduce over ``e_cap + 2 * b_cap`` slots: existing
directed slots and the batch's directed slots are keyed by
``src * (n_cap + 1) + dst``, lexsorted by (key, rank) with batch slots
outranking existing ones (and later batch entries outranking earlier — last
write wins), then per-key groups resolve to their highest-rank weight and
compact back into CSR order.  Because the key order IS the (src, dst) CSR
order, ``indptr`` rebuilds from a segment-count + cumsum.

Invariants preserved exactly (tested property-style in tests/test_dynamic.py):
  - undirected {i,j}, i != j   -> two directed slots; self loop -> one slot
  - K_i = row sum, m = sum(w)/2, padding slots hold (sentinel, 0)
so ``vertex_weights`` / ``total_weight`` stay consistent by construction.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.graph import CSRGraph


class EdgeBatch(NamedTuple):
    """A padded batch of undirected edge assignments.

    src, dst : (b_cap,) int32 endpoints; padding slots hold ``n_cap``.
    weight   : (b_cap,) float32 new weight (0 = delete); padding slots 0.
    b_valid  : () int32 number of live entries.
    """

    src: jax.Array
    dst: jax.Array
    weight: jax.Array
    b_valid: jax.Array

    @property
    def b_cap(self) -> int:
        return self.src.shape[0]


def make_edge_batch(src, dst, weight, n_cap: int,
                    b_cap: int | None = None) -> EdgeBatch:
    """Host-side batch builder; pads to ``b_cap`` with sentinel entries."""
    with spans.span("make_edge_batch"):
        src = np.asarray(src, dtype=np.int32)
        dst = np.asarray(dst, dtype=np.int32)
        weight = np.asarray(weight, dtype=np.float32)
        b = len(src)
        b_cap = int(b_cap if b_cap is not None else max(b, 1))
        assert b_cap >= b, "batch capacity below batch size"
        pad = np.full(b_cap - b, n_cap, np.int32)
        return EdgeBatch(
            src=jnp.asarray(np.concatenate([src, pad])),
            dst=jnp.asarray(np.concatenate([dst, pad])),
            weight=jnp.asarray(np.concatenate(
                [weight, np.zeros(b_cap - b, np.float32)])),
            b_valid=jnp.asarray(b, dtype=np.int32),
        )


def sort_reduce_apply_slots(all_src, all_dst, all_w, rank, is_batch,
                            sent: int, out_cap: int, backend: str = "xla"):
    """The shared batch-apply sort-reduce over a unified directed-slot list.

    ``all_*`` concatenate the existing slots (rank 0) and the batch's directed
    slots (rank 1 + batch position, so later batch entries win ties); dead
    slots must already carry an endpoint >= ``sent``.  Groups of equal
    (src, dst) resolve to their highest-rank weight and compact back into
    (src, dst)-sorted order in ``out_cap`` slots (overflow rows land in a
    scratch slot and are reported via the uncapped ``e_new``).

    Returns ``(out_src, out_dst, out_w, e_new, chg_src, chg_dst)`` where
    ``chg_src``/``chg_dst`` hold the endpoints of every group whose resolved
    weight actually changed (``sent`` elsewhere) — callers scatter these into
    their own touched-vertex structures.  Used by both the single-device CSR
    apply below and the per-shard apply in ``repro.core.distributed_dynamic``.

    ``backend`` selects the post-sort group-resolve: ``"xla"`` (segment_*
    reductions, the reference) or ``"pallas"`` (the fused carry-chained scan
    kernel in ``repro.kernels.batch_apply`` — interpreted on the CPU).  Both
    produce bit-identical graphs and touched sets; only the internal
    ``chg_*`` encoding differs (all group slots vs one record per group),
    which scatters to the same mask.
    """
    total = all_src.shape[0]
    dead = (all_src >= sent) | (all_dst >= sent)
    k_src = jnp.where(dead, sent, all_src)
    k_dst = jnp.where(dead, sent, all_dst)
    order = jnp.lexsort((rank, k_dst, k_src))
    s_src, s_dst = k_src[order], k_dst[order]
    s_w, s_batch = all_w[order], is_batch[order]

    if backend == "pallas":
        from repro.kernels.batch_apply import resolve_groups_pallas
        keep, pos, f_src, f_dst, f_w, chg = resolve_groups_pallas(
            s_src, s_dst, s_w, s_batch, sent=sent)
        e_new = jnp.sum(keep.astype(jnp.int32))
        pos = jnp.where(keep & (pos < out_cap), pos, out_cap)
        out_src = jnp.full((out_cap + 1,), sent, jnp.int32).at[pos].set(
            jnp.where(keep, f_src, sent))[:out_cap]
        out_dst = jnp.full((out_cap + 1,), sent, jnp.int32).at[pos].set(
            jnp.where(keep, f_dst, sent))[:out_cap]
        out_w = jnp.zeros((out_cap + 1,), jnp.float32).at[pos].set(
            jnp.where(keep, f_w, 0.0))[:out_cap]
        chg_src = jnp.where(chg, f_src, sent)
        chg_dst = jnp.where(chg, f_dst, sent)
        return out_src, out_dst, out_w, e_new, chg_src, chg_dst
    if backend != "xla":
        raise ValueError(f"unknown batch-apply backend: {backend!r}")

    s_sent = s_src == sent
    nxt_same = (s_src[:-1] == s_src[1:]) & (s_dst[:-1] == s_dst[1:])
    is_last = jnp.concatenate([~nxt_same, jnp.ones((1,), bool)])
    is_first = jnp.concatenate([jnp.ones((1,), bool), ~nxt_same])
    gid = jnp.cumsum(is_first.astype(jnp.int32)) - 1

    # Per-group old weight (0 if the first slot is a batch slot, i.e. insert)
    # and new weight (the last slot's weight — batch overrides existing).
    old_w = jax.ops.segment_sum(
        jnp.where(is_first & ~s_batch, s_w, 0.0), gid, num_segments=total)
    new_w = jax.ops.segment_sum(
        jnp.where(is_last, s_w, 0.0), gid, num_segments=total)
    changed_group = jax.ops.segment_max(
        (s_batch & (old_w[gid] != new_w[gid])).astype(jnp.int32),
        gid, num_segments=total)

    # Compact live groups (w > 0, real key) back into sorted slot order.
    keep = is_last & ~s_sent & (new_w[gid] > 0.0)
    e_new = jnp.sum(keep.astype(jnp.int32))
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    pos = jnp.where(keep & (pos < out_cap), pos, out_cap)  # overflow -> scratch
    out_src = jnp.full((out_cap + 1,), sent, jnp.int32).at[pos].set(
        jnp.where(keep, s_src, sent))[:out_cap]
    out_dst = jnp.full((out_cap + 1,), sent, jnp.int32).at[pos].set(
        jnp.where(keep, s_dst, sent))[:out_cap]
    out_w = jnp.zeros((out_cap + 1,), jnp.float32).at[pos].set(
        jnp.where(keep, new_w[gid], 0.0))[:out_cap]

    hit = changed_group[gid] > 0
    chg_src = jnp.where(hit, s_src, sent)
    chg_dst = jnp.where(hit, s_dst, sent)
    return out_src, out_dst, out_w, e_new, chg_src, chg_dst


@functools.partial(jax.jit, static_argnames=("backend",))
def _apply_edge_batch(graph: CSRGraph, batch: EdgeBatch,
                      backend: str = "xla"):
    """Jit core: returns (graph', touched_mask, e_new_uncapped)."""
    n_cap, e_cap = graph.n_cap, graph.e_cap
    b_cap = batch.b_cap

    # Directed batch slots: {u,v} -> (u,v) and (v,u); self loops get ONE slot
    # (the reverse collapses to a sentinel), matching the CSR convention.
    b_idx = jnp.arange(b_cap)
    b_live = (b_idx < batch.b_valid) & (batch.src < n_cap) & (batch.dst < n_cap)
    u = jnp.where(b_live, batch.src, n_cap)
    v = jnp.where(b_live, batch.dst, n_cap)
    rev_live = b_live & (u != v)
    d_src = jnp.concatenate([u, jnp.where(rev_live, v, n_cap)])
    d_dst = jnp.concatenate([v, jnp.where(rev_live, u, n_cap)])
    d_w = jnp.concatenate([batch.weight, jnp.where(rev_live, batch.weight, 0.0)])

    # Unified slot list: existing first (rank 0), batch after (rank = 1 + i so
    # later batch entries win ties — last-write-wins within one batch).
    all_src = jnp.concatenate([graph.src, d_src])
    all_dst = jnp.concatenate([graph.indices, d_dst])
    all_w = jnp.concatenate([graph.weights, d_w]).astype(jnp.float32)
    e_idx = jnp.arange(e_cap)
    exist_live = (e_idx < graph.e_valid) & (graph.src < n_cap)
    slot_live = jnp.concatenate([exist_live,
                                 (d_src < n_cap) | (d_dst < n_cap)])
    is_batch = jnp.concatenate([jnp.zeros(e_cap, bool), jnp.ones(2 * b_cap, bool)])
    rank = jnp.concatenate([
        jnp.zeros(e_cap, jnp.int32),
        1 + (jnp.arange(2 * b_cap, dtype=jnp.int32) % b_cap),
    ])

    # Dead slots collapse to the (n_cap, n_cap) sentinel pair so they sort
    # last; the (src, dst) sort order IS the CSR order — no combined int64
    # key (x64 is usually disabled), the lexsort carries both columns.
    # The group-resolve + compaction itself is the shared sort-reduce core.
    dead = ~(slot_live & (all_src < n_cap) & (all_dst < n_cap))
    out_src, out_dst, out_w, e_new, chg_src, chg_dst = sort_reduce_apply_slots(
        jnp.where(dead, n_cap, all_src), jnp.where(dead, n_cap, all_dst),
        all_w, rank, is_batch, n_cap, e_cap, backend)

    live_rows = out_src < n_cap
    counts = jax.ops.segment_sum(
        jnp.where(live_rows, 1, 0), jnp.where(live_rows, out_src, n_cap),
        num_segments=n_cap + 1)
    indptr = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(counts[:n_cap]).astype(jnp.int32),
    ])

    # Touched vertices: endpoints of groups whose weight actually changed.
    touched = jnp.zeros((n_cap + 1,), bool)
    touched = touched.at[chg_src].set(True)
    touched = touched.at[chg_dst].set(True)
    touched = touched.at[n_cap].set(False)

    # Batch endpoints may extend the valid-vertex prefix (still < n_cap).
    max_end = jnp.max(jnp.where(touched, jnp.arange(n_cap + 1), -1))
    n_valid = jnp.maximum(graph.n_valid, (max_end + 1).astype(jnp.int32))

    out = CSRGraph(
        indptr=indptr, indices=out_dst, weights=out_w, src=out_src,
        n_valid=n_valid, e_valid=jnp.minimum(e_new, e_cap).astype(jnp.int32),
    )
    return out, touched, e_new


def grow_graph_capacity(graph: CSRGraph, e_cap_new: int) -> CSRGraph:
    """Host-side re-bucketing: copy a graph into buffers with more edge slots.

    Vertex capacity (and so every (n_cap + 1,)-shaped consumer) is unchanged;
    only the edge arrays grow, so downstream jits recompile once per growth
    step and are reused for the rest of the stream.
    """
    e_cap_new = int(e_cap_new)
    if e_cap_new < graph.e_cap:
        raise ValueError(f"cannot shrink e_cap {graph.e_cap} -> {e_cap_new}")
    n_cap = graph.n_cap
    e = int(spans.fetch("e_valid", graph.e_valid))
    pad_i = np.full(e_cap_new - e, n_cap, np.int32)
    pad_w = np.zeros(e_cap_new - e, np.float32)
    return CSRGraph(
        indptr=graph.indptr,
        indices=jnp.asarray(np.concatenate(
            [spans.fetch("indices", graph.indices)[:e], pad_i])),
        weights=jnp.asarray(np.concatenate(
            [spans.fetch("weights", graph.weights)[:e], pad_w])),
        src=jnp.asarray(np.concatenate(
            [spans.fetch("src", graph.src)[:e], pad_i])),
        n_valid=graph.n_valid,
        e_valid=graph.e_valid,
    )


def apply_edge_batch(graph: CSRGraph, batch: EdgeBatch, *,
                     grow: bool = False,
                     backend: str = "xla") -> Tuple[CSRGraph, jax.Array]:
    """Apply one edge batch; returns (graph', touched_vertex_mask).

    Raises if the resulting edge count exceeds the preallocated ``e_cap``
    (streaming callers size capacities for the expected insert volume up
    front — growing buffers would retrigger every downstream jit).  With
    ``grow=True`` an overflowing batch instead re-buckets host-side into
    doubled capacity (at least the required count) and re-applies — the
    unbounded-stream policy used by ``louvain_dynamic``.  ``backend``
    selects the group-resolve implementation (see
    ``sort_reduce_apply_slots``).
    """
    with spans.span("apply"):
        out, touched, e_new = _apply_edge_batch(graph, batch,
                                                backend=backend)
        e_new = int(spans.fetch("e_new", e_new))
        if e_new > graph.e_cap:
            if not grow:
                raise ValueError(
                    f"edge batch overflows capacity: {e_new} live directed "
                    f"slots > e_cap={graph.e_cap}")
            with spans.span("apply.grow", e_cap=graph.e_cap):
                grown = grow_graph_capacity(
                    graph, max(2 * graph.e_cap, e_new))
                out, touched, _ = _apply_edge_batch(grown, batch,
                                                    backend=backend)
        return out, touched
