"""Dynamic streaming Louvain: naive-dynamic warm start + delta screening.

Static GVE-Louvain restarts every pass from singleton communities.  Serving
workloads see small edge-batch deltas between queries, so re-running from
scratch wastes nearly all of its work.  This driver implements the two
standard dynamic strategies on top of the (now warm-startable) static
machinery in ``repro.core.louvain``:

  * **Naive-dynamic (ND)**: resume the move phase from the previous
    membership; community weights Sigma are recomputed from the updated
    graph so the warm snapshot is exact.
  * **Delta screening (DS)**: seed the first pass's frontier ONLY with the
    endpoints of changed edges plus every member of the communities those
    endpoints currently belong to (community membership lists come from
    ``community_vertices_csr``-style grouping — realized here as the
    equivalent O(n) mask ``member_of_affected = mark[comm]``).  With vertex
    pruning on, the frontier then grows outward from actual movers, so
    unaffected regions of the graph are never re-scanned.

``louvain_dynamic(graph, batches, prev=...)`` streams a sequence of
``EdgeBatch`` updates, applying each with ``repro.core.delta`` and
re-optimizing incrementally; per-batch ``PassStats.frontier_size`` reports
how many vertices delta screening re-processed (the streaming win is that
this stays a small fraction of n).
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.delta import EdgeBatch, apply_edge_batch
from repro.core.engine import affected_frontier, normalize_screening
from repro.core.graph import CSRGraph
from repro.core.louvain import (LouvainConfig, LouvainResult, louvain,
                                louvain_modularity, pad_membership,
                                screened_frontier)

# The frontier math is shared with the sharded layout — see
# ``repro.core.engine.affected_frontier``; this name is the historical
# single-device entry point.
delta_frontier = screened_frontier


@dataclasses.dataclass
class BatchUpdateStats:
    """One streamed batch: what changed and what it cost."""

    batch_size: int              # live entries in the batch
    n_touched: int               # endpoints whose incident weights changed
    frontier_size: int           # delta-screened seed frontier (|F| <= n)
    n_vertices: int              # n_valid after the update
    n_communities: int
    apply_seconds: float         # CSR edge-batch apply
    update_seconds: float        # warm-started Louvain
    modularity: Optional[float] = None

    @property
    def frontier_fraction(self) -> float:
        return self.frontier_size / max(self.n_vertices, 1)


@dataclasses.dataclass
class DynamicResult:
    graph: CSRGraph              # graph after all batches
    membership: np.ndarray       # (n_valid,) final community per vertex
    n_communities: int
    batch_stats: List[BatchUpdateStats]
    total_seconds: float

    @property
    def updates_per_second(self) -> float:
        edges = sum(s.batch_size for s in self.batch_stats)
        return edges / max(self.total_seconds, 1e-12)


_pad_membership = pad_membership


def louvain_dynamic(
    graph: CSRGraph,
    batches: Sequence[EdgeBatch],
    prev: Optional[np.ndarray] = None,
    config: LouvainConfig = LouvainConfig(),
    *,
    screening=True,
    track_modularity: bool = False,
    grow_capacity: bool = True,
    apply_backend: str = "xla",
) -> DynamicResult:
    """Stream edge batches through warm-started (ND + DS) Louvain.

    ``prev`` is the membership of ``graph`` BEFORE the stream ((n,) ints, as
    in ``LouvainResult.membership``); if ``None``, a cold static run on the
    initial graph produces it.  Each batch is applied in capacity
    (``apply_edge_batch``), then ``louvain`` resumes from the running
    membership with the delta-screened frontier.  ``screening`` picks the
    seed-frontier policy: ``True``/``"community"`` (touched endpoints plus
    their whole communities), ``"vertex"`` (DF-Louvain-style per-vertex
    affected flags — finer; pruning grows the frontier from actual movers),
    ``"auto"`` (per-batch granularity from the touched-set size — vertex
    for small deltas, community for bulky ones; an on-device select, no
    per-batch host sync), or ``False`` (pure naive-dynamic: warm start over
    ALL vertices).  ``config.scan_backend`` additionally routes the move
    phase through the frontier-compacted scanner when the screened frontier
    is small (``"auto"``/``"compact"`` — bit-identical results, scan work
    proportional to |F|).  With
    ``grow_capacity`` (the default) a batch that would overflow ``e_cap``
    re-buckets host-side into doubled capacity instead of raising — one
    recompile per growth step, then the stream continues in capacity.
    ``apply_backend`` selects the batch-apply group-resolve (``"xla"`` or
    the ``"pallas"`` kernel — bit-identical results).

    With ``config.use_ladder`` the warm re-optimizations ride the coarse-
    pass capacity ladder INSIDE each ``louvain`` call; the ladder never
    touches the resident stream graph — ``louvain`` re-buckets only its
    internal coarse graphs, so the next batch always applies at stream
    capacity (the driver is "un-laddered" by construction) and the
    compiled apply/screen programs never change shape across the stream.

    Returns the final graph/membership plus per-batch stats; the acceptance
    property is that modularity tracks a cold recompute while
    ``frontier_size`` stays a small fraction of n.
    """
    with spans.span("louvain_dynamic"):
        return _louvain_dynamic(graph, batches, prev, config, screening,
                                track_modularity, grow_capacity,
                                apply_backend)


def _louvain_dynamic(graph, batches, prev, config, screening,
                     track_modularity, grow_capacity,
                     apply_backend) -> DynamicResult:
    t_start = time.perf_counter()
    n_cap = graph.n_cap
    screen_mode = normalize_screening(screening)

    if prev is None:
        cold = louvain(graph, config)
        prev = cold.membership
    membership = _pad_membership(np.asarray(prev, np.int32), n_cap)

    stats: List[BatchUpdateStats] = []
    # n_touched is a device reduction; materializing it per batch would force
    # a sync inside the stream loop, so collect the lazy scalars and fill the
    # stats in one host transfer after the stream.
    touched_counts: List[jax.Array] = []
    n = int(spans.fetch("n_vertices", graph.n_valid))
    n_comms = int(len(np.unique(membership[:n])))
    for batch in batches:
        t0 = time.perf_counter()
        graph, touched = apply_edge_batch(graph, batch, grow=grow_capacity,
                                          backend=apply_backend)
        t1 = time.perf_counter()

        frontier = None
        if screen_mode is not None:
            with spans.span("screen"):
                frontier = affected_frontier(
                    touched, jnp.asarray(membership), graph.n_valid,
                    screen_mode)
        res: LouvainResult = louvain(
            graph, config, init_membership=membership,
            init_frontier=frontier)
        t2 = time.perf_counter()

        n = int(spans.fetch("n_vertices", graph.n_valid))
        membership = _pad_membership(res.membership, n_cap)
        n_comms = res.n_communities
        touched_counts.append(jnp.sum(touched))
        stats.append(BatchUpdateStats(
            batch_size=int(spans.fetch("b_valid", batch.b_valid)),
            n_touched=-1,  # filled from touched_counts after the stream
            frontier_size=res.passes[0].frontier_size if res.passes else 0,
            n_vertices=n,
            n_communities=n_comms,
            apply_seconds=t1 - t0,
            update_seconds=t2 - t1,
            modularity=louvain_modularity(graph, res)
            if track_modularity else None,
        ))
    for s, cnt in zip(stats, touched_counts):
        s.n_touched = int(spans.fetch("n_touched", cnt))

    return DynamicResult(
        graph=graph,
        membership=membership[:n].copy(),
        n_communities=n_comms,
        batch_stats=stats,
        total_seconds=time.perf_counter() - t_start,
    )
