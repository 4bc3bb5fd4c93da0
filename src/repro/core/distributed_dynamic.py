"""Sharded streaming Louvain: distributed warm-start + delta screening.

The distributed layer (``repro.core.distributed``) ran batch-only: every
stream update meant a fresh partition and a cold singleton start.  This
module turns it into the serving-shaped streaming system of the ROADMAP by
porting the dynamic machinery (``repro.core.dynamic``) to the 1-D vertex
partition, the same way Vite/Ghosh-style distributed Louvain keeps ghost and
community state resident across rounds instead of rebuilding it:

  * **Sharded batch apply** — an ``EdgeBatch`` of undirected ``{u, v} -> w``
    assignments is applied directly to the partitioned per-shard edge arrays
    inside ``shard_map``.  Each shard materializes the batch's directed slots
    it owns (slot (u, v) lives on owner(u)) and resolves them against its
    existing slots with the same key/rank sort-reduce as the single-device
    CSR apply (``repro.core.delta.sort_reduce_apply_slots``) — compiled
    shapes never change across the stream.
  * **Warm start + delta screening** — the move phase resumes from the
    previous replicated membership; the seed frontier is the touched
    endpoints plus their communities' members.  Touched ownership is local
    (every changed directed slot's src is owned), so the global mask is one
    ``all_gather`` of touched-owned slices; the frontier math itself is the
    shared ``repro.core.louvain.screened_frontier``.
  * **Capacity growth** — a batch that would overflow ``e_per_shard``
    re-buckets host-side into doubled capacity (``bucket_slots_host``),
    rebuilds the jit'd phases once, and re-applies, instead of raising —
    unbounded streams keep running.
  * **Skew-aware re-sharding** — coarse-graph ownership skew inside the
    pass loop is no longer absorbed by capacity growth alone: with
    ``config.reshard="auto"`` the pass loop re-balances the coarse owner
    ranges by measured edge load after each aggregation
    (``distributed.sharded_louvain_passes``), so one hot shard stops
    setting the fleet's capacity tier; the one-time relabel traffic is
    priced into the stream's bytes accounting and surfaced as the
    ``reshard_*`` result fields.  Capacity doubling remains the backstop
    for residual skew (e.g. a single dominant coarse vertex).

``louvain_dynamic_sharded`` is the multi-device analogue of
``louvain_dynamic`` and reports the same ``BatchUpdateStats`` per batch.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.delta import EdgeBatch, sort_reduce_apply_slots
from repro.core.distributed import (ShardedGraphSpec,
                                    _rebucket_live_host, _shard_index,
                                    make_distributed_aggregate,
                                    make_distributed_move,
                                    make_tier_phases,
                                    partition_graph_host,
                                    sentinel_forced_membership,
                                    sharded_louvain_passes,
                                    sharded_modularity)
from repro.core.dynamic import BatchUpdateStats
from repro.core.engine import affected_frontier, normalize_screening
from repro.core.graph import CSRGraph
from repro.core.louvain import LouvainConfig, pad_membership


def apply_batch_shard(spec: ShardedGraphSpec, shard_ix,
                      src_l, dst_l, w_l, b_src, b_dst, b_w, b_valid,
                      n_limit: Optional[int] = None,
                      backend: str = "xla"):
    """Per-shard batch apply: resolve the owned directed batch slots against
    this shard's (e_per_shard,) slot arrays via the shared sort-reduce.

    Pure jnp (no collectives), so it is property-testable shard-by-shard
    without a mesh.  An undirected assignment {u, v} -> w materializes as
    slot (u, v) on owner(u) and (v, u) on owner(v); a self loop u == v gets
    one slot on owner(u) — matching the CSR convention, so the union of all
    shards' slots equals the single-device ``apply_edge_batch`` result.
    ``n_limit`` is the logical vertex capacity (the CSR ``n_cap``); entries
    with an endpoint >= n_limit are dropped exactly like the single-device
    apply drops them (n_pad can exceed n_cap when n_cap % n_shards != 0).

    Returns (src', dst', w', touched_own (v_per,), e_new) where ``e_new`` is
    the uncapped owned live-slot count (> e_per_shard signals overflow) and
    ``touched_own`` marks owned vertices whose incident weights changed.
    """
    sent = spec.sentinel
    lim = sent if n_limit is None else n_limit
    v_per, e_per = spec.v_per_shard, spec.e_per_shard
    v0 = shard_ix * v_per
    b_cap = b_src.shape[0]

    b_idx = jnp.arange(b_cap)
    u = b_src.astype(jnp.int32)
    v = b_dst.astype(jnp.int32)
    b_live = (b_idx < b_valid) & (u < lim) & (v < lim)
    own_u = (u >= v0) & (u < v0 + v_per)
    own_v = (v >= v0) & (v < v0 + v_per)
    live_fwd = b_live & own_u
    live_rev = b_live & own_v & (u != v)
    d_src = jnp.concatenate([jnp.where(live_fwd, u, sent),
                             jnp.where(live_rev, v, sent)])
    d_dst = jnp.concatenate([jnp.where(live_fwd, v, sent),
                             jnp.where(live_rev, u, sent)])
    d_w = jnp.concatenate([jnp.where(live_fwd, b_w, 0.0),
                           jnp.where(live_rev, b_w, 0.0)])

    # Unified slot list: existing first (rank 0), batch after (rank = 1 + i
    # so later batch entries win ties — last-write-wins within one batch).
    all_src = jnp.concatenate([src_l, d_src])
    all_dst = jnp.concatenate([dst_l, d_dst])
    all_w = jnp.concatenate([w_l, d_w]).astype(jnp.float32)
    is_batch = jnp.concatenate([jnp.zeros(e_per, bool),
                                jnp.ones(2 * b_cap, bool)])
    rank = jnp.concatenate([
        jnp.zeros(e_per, jnp.int32),
        1 + (jnp.arange(2 * b_cap, dtype=jnp.int32) % b_cap),
    ])
    out_src, out_dst, out_w, e_new, chg_src, _ = sort_reduce_apply_slots(
        all_src, all_dst, all_w, rank, is_batch, sent, e_per, backend)

    # Every changed slot's src is owned here; the mirror shard marks the dst
    # endpoint via its own (v, u) slot — no cross-shard scatter needed.
    loc = jnp.clip(jnp.where(chg_src < sent, chg_src - v0, v_per), 0, v_per)
    touched_own = jnp.zeros((v_per + 1,), bool).at[loc].set(True)[:v_per]
    return out_src, out_dst, out_w, touched_own, e_new


@functools.lru_cache(maxsize=None)
def make_sharded_batch_apply(mesh: Mesh, axes: Tuple[str, ...],
                             spec: ShardedGraphSpec,
                             n_limit: Optional[int] = None,
                             backend: str = "xla",
                             traced_n_limit: bool = False):
    """Build the jit'd sharded batch apply for a fixed mesh/layout.

    Returns fn(src_g, dst_g, w_g, b_src, b_dst, b_w, b_valid, n_valid)
        -> (src_g', dst_g', w_g', touched (n_pad + 1,), e_max, n_valid')
    with edge arrays in the partitioned layout, the touched mask replicated
    (ONE all_gather of touched-owned slices), and ``e_max`` the worst
    shard's uncapped slot count (overflow signal).  ``backend`` picks the
    group-resolve implementation (``"xla"`` / ``"pallas"``).

    With ``traced_n_limit`` the returned fn takes the logical vertex
    capacity as one extra TRACED replicated operand (after ``n_valid``)
    instead of baking it into the compiled body — ``apply_batch_shard``
    only ever compares against it, so the math is identical.  The serving
    fleet uses this to share one compiled apply across tenants whose
    logical ``n_cap`` differ within a capacity bucket (and to vmap the
    apply over tenant lanes with per-lane capacities).
    """
    edge_spec = P(axes)
    rep = P()

    def apply_fn(src_g, dst_g, w_g, b_src, b_dst, b_w, b_valid, n_valid,
                 n_limit_op=None):
        def body(src_l, dst_l, w_l, b_src, b_dst, b_w, b_valid, n_valid,
                 *lim_rest):
            shard_ix = _shard_index(axes)
            lim = lim_rest[0] if lim_rest else n_limit
            src2, dst2, w2, touched_own, e_new = apply_batch_shard(
                spec, shard_ix, src_l, dst_l, w_l, b_src, b_dst, b_w,
                b_valid, lim, backend)
            touched = jax.lax.all_gather(touched_own, axes, tiled=True)
            touched = jnp.concatenate([touched, jnp.zeros((1,), bool)])
            e_max = jax.lax.pmax(e_new, axes)
            # Batch endpoints may extend the valid-vertex prefix.
            mx = jnp.max(jnp.where(touched, jnp.arange(spec.n_pad + 1), -1))
            n_valid_new = jnp.maximum(n_valid, (mx + 1).astype(jnp.int32))
            return src2, dst2, w2, touched, e_max, n_valid_new

        operands = (src_g, dst_g, w_g, b_src, b_dst, b_w, b_valid, n_valid)
        in_specs = (edge_spec, edge_spec, edge_spec, rep, rep, rep, rep, rep)
        if traced_n_limit:
            operands = operands + (n_limit_op,)
            in_specs = in_specs + (rep,)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=in_specs,
            out_specs=(edge_spec, edge_spec, edge_spec, rep, rep, rep),
            check_vma=False,
        )
        return fn(*operands)

    if not traced_n_limit:
        def apply_static(src_g, dst_g, w_g, b_src, b_dst, b_w, b_valid,
                         n_valid):
            return apply_fn(src_g, dst_g, w_g, b_src, b_dst, b_w, b_valid,
                            n_valid)
        return jax.jit(apply_static)
    return apply_fn


def _rebucket_host(src_g, dst_g, w_g, spec: ShardedGraphSpec):
    """Pull live slots to the host and re-bucket into ``spec``'s layout
    (the shared ``distributed._rebucket_live_host`` body; growth callers
    size ``spec`` so the ownership always fits — a layout the slots don't
    fit is a caller bug, not a retry case)."""
    src2, dst2, w2, spec2 = _rebucket_live_host(src_g, dst_g, w_g,
                                                spec.sentinel, spec)
    if spec2 != spec:
        raise ValueError(
            f"slots do not fit the caller-sized layout: needed "
            f"e_per_shard={spec2.e_per_shard} > {spec.e_per_shard}")
    return src2, dst2, w2


def _build_phases(mesh, axes, spec, config: LouvainConfig,
                  n_limit: Optional[int] = None, backend: str = "xla",
                  comm_backend: str = "gather",
                  state_layout: str = "replicated"):
    move = make_distributed_move(
        mesh, axes, spec, max_iterations=config.max_iterations,
        gate_fraction=config.gate_fraction, use_pruning=config.use_pruning,
        comm_backend=comm_backend, state_layout=state_layout)
    agg = make_distributed_aggregate(mesh, axes, spec)
    apply_fn = make_sharded_batch_apply(mesh, axes, spec, n_limit, backend)
    return move, agg, apply_fn


@dataclasses.dataclass
class ShardedDynamicResult:
    membership: np.ndarray       # (n_valid,) final community per vertex
    n_communities: int
    batch_stats: List[BatchUpdateStats]
    total_seconds: float
    n_regrows: int               # capacity-growth re-bucketing events
    spec: ShardedGraphSpec       # final layout (e_per_shard may have grown)
    comm_backend: str = "gather"          # resolved exchange backend
    comm_rounds: int = 0                  # engine rounds across the stream
    comm_fallback_rounds: int = 0         # rounds the delta caps overflowed
    bytes_on_wire: int = 0                # total move-phase exchange bytes
    reshard_passes: int = 0               # skew-aware owner re-shards
    reshard_bytes: int = 0                # one-time relabel bytes (priced)
    #: Worst pre-/post-re-shard shard load fraction observed across the
    #: stream (None when no pass re-sharded).
    max_shard_load_frac_before: Optional[float] = None
    max_shard_load_frac_after: Optional[float] = None
    #: Largest per-shard COARSE edge tier any pass ran at — the capacity
    #: tier the skew check is trying to keep down.
    coarse_e_per_max: int = 0
    #: Resolved working-state layout ("replicated" | "hybrid") and its
    #: accounting: boundary-mover bytes across the stream and the measured
    #: boundary fraction of the fine partition (None under replicated).
    state_layout: str = "replicated"
    halo_bytes: int = 0
    boundary_frac: Optional[float] = None
    #: Summed per-pass wall-clock across every batch's pass loop (the
    #: measured-time signal the reshard="auto" policy is validated
    #: against; aggregation and re-buckets included).
    pass_seconds_total: float = 0.0

    @property
    def updates_per_second(self) -> float:
        edges = sum(s.batch_size for s in self.batch_stats)
        return edges / max(self.total_seconds, 1e-12)

    @property
    def bytes_per_round(self) -> float:
        return self.bytes_on_wire / max(self.comm_rounds, 1)

    @property
    def halo_bytes_per_round(self) -> float:
        return self.halo_bytes / max(self.comm_rounds, 1)


def louvain_dynamic_sharded(
    graph: CSRGraph,
    mesh: Mesh,
    axes: Tuple[str, ...],
    batches: Sequence[EdgeBatch],
    prev: Optional[np.ndarray] = None,
    config: LouvainConfig = LouvainConfig(),
    *,
    screening=True,
    track_modularity: bool = False,
    grow_capacity: bool = True,
    e_per_shard: Optional[int] = None,
    apply_backend: str = "xla",
) -> ShardedDynamicResult:
    """Stream edge batches through warm-started sharded Louvain.

    The distributed counterpart of ``louvain_dynamic``: the graph is
    partitioned ONCE (1-D vertex partition over all ``axes``, with vertex
    capacity ``graph.n_cap`` and edge headroom ``e_per_shard``), then every
    batch is (a) applied in-layout inside ``shard_map``, (b) delta-screened
    into a seed frontier, and (c) re-optimized from the previous replicated
    membership via the shared sharded pass loop.  A batch overflowing
    ``e_per_shard`` triggers host-side re-bucketing into doubled capacity
    (one recompile) when ``grow_capacity`` is set, else raises.

    ``prev`` is the membership of ``graph`` before the stream; ``None`` runs
    one cold sharded pass loop to produce it.  Batches of equal ``b_cap``
    reuse one compiled apply; mixed capacities recompile per distinct size.
    ``screening`` picks the seed-frontier policy (``True``/``"community"``,
    ``"vertex"`` for DF-style per-vertex flags, ``"auto"`` to pick per
    batch from the touched-set size, ``False`` for pure naive-dynamic);
    ``apply_backend`` the batch-apply group-resolve;
    ``config.comm_backend`` the per-round exchange ("gather" | "delta" |
    "auto") — memberships are invariant to it, and the result carries the
    stream's bytes-on-wire accounting (``bytes_per_round``).
    ``config.state_layout`` picks the working-state placement
    ("replicated" | "hybrid" | "auto"; auto measures the fine partition's
    boundary fraction once — memberships are invariant to this too, and
    the result carries ``state_layout`` / ``halo_bytes_per_round`` /
    ``boundary_frac``).
    ``config.refine="leiden"`` runs the constrained refinement sweep inside
    every batch's pass loop (see ``sharded_louvain_passes``).
    ``config.reshard="auto"`` re-balances the coarse owner ranges by
    measured load after each aggregation and ``config.pipeline_fetch``
    overlaps the pass loop's host convergence decision with the next
    aggregation — both change work placement, never memberships.
    """
    from repro.configs.louvain_arch import (resolve_comm_backend,
                                            resolve_state_layout)
    from repro.core.distributed import measure_boundary_frac

    t_start = time.perf_counter()
    screen_mode = normalize_screening(screening)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    cb = resolve_comm_backend(config.comm_backend, n_shards)
    src_g, dst_g, w_g, spec = partition_graph_host(
        graph, n_shards, n_target=graph.n_cap)
    bfrac = (measure_boundary_frac(src_g, dst_g, spec, int(graph.n_valid))
             if n_shards > 1 and config.state_layout != "replicated"
             else None)
    sl = resolve_state_layout(config.state_layout, n_shards, bfrac)
    if e_per_shard is None:
        # Default headroom: 25% slack + room for one worst-case batch (each
        # batch adds at most 2 * b_cap directed slots to a single shard).
        b_max = max((b.b_cap for b in batches), default=1)
        e_per_shard = spec.e_per_shard + spec.e_per_shard // 4 + 2 * b_max
    if int(e_per_shard) > spec.e_per_shard:
        spec = spec._replace(e_per_shard=int(e_per_shard))
        src_g, dst_g, w_g = _rebucket_host(src_g, dst_g, w_g, spec)
    n_limit = graph.n_cap   # logical vertex capacity (n_pad may exceed it)
    move, agg, apply_fn = _build_phases(mesh, axes, spec, config, n_limit,
                                        apply_backend, cb, sl)
    sent = spec.sentinel

    # Coarse-pass ladder phases: one (move, agg) per tier layout, cached so
    # every batch's pass loop reuses the compiled phases.  The ladder only
    # touches the COARSE graphs inside the pass loop — the resident fine
    # arrays stay at stream capacity (the driver "un-ladders" by
    # construction: the next batch applies to ``src_g``/``dst_g``/``w_g``,
    # which the pass loop never mutates).
    phases_for = make_tier_phases(
        mesh, axes, max_iterations=config.max_iterations,
        gate_fraction=config.gate_fraction,
        use_pruning=config.use_pruning, comm_backend=cb,
        state_layout=sl, refine=config.refine)

    pass_kw = dict(
        max_passes=config.max_passes,
        initial_tolerance=config.initial_tolerance,
        tolerance_drop=config.tolerance_drop,
        aggregation_tolerance=config.aggregation_tolerance,
    )
    n_live = int(graph.n_valid)
    stats: List[BatchUpdateStats] = []
    touched_counts: List[jax.Array] = []
    frontier_sizes: List[jax.Array] = []
    n_regrows = 0
    comm_rounds = comm_fb = comm_bytes = halo_bytes = 0
    reshard_passes = reshard_bytes_total = coarse_e_max = 0
    load_frac_before = load_frac_after = None
    pass_seconds = 0.0

    def _grow_to(e_per_new: int):
        """Re-bucket the resident fine arrays into grown capacity and
        rebuild the jit'd phases (one recompile per growth step)."""
        nonlocal spec, src_g, dst_g, w_g, move, agg, apply_fn, n_regrows
        spec = spec._replace(e_per_shard=int(e_per_new))
        src_g, dst_g, w_g = _rebucket_host(src_g, dst_g, w_g, spec)
        move, agg, apply_fn = _build_phases(mesh, axes, spec, config,
                                            n_limit, apply_backend, cb, sl)
        n_regrows += 1

    def _run_passes(n_live_, **kw):
        """Pass loop + comm accounting.  Coarse-edge ownership skew no
        longer raises here: with ``phases_for`` supplied the pass loop
        re-shards the owner map (skew-aware with ``config.reshard="auto"``,
        ladder-tight otherwise) and grows coarse edge capacity pass-
        locally in-flight — the resident fine arrays are untouched."""
        nonlocal comm_rounds, comm_fb, comm_bytes, reshard_passes, \
            reshard_bytes_total, coarse_e_max, load_frac_before, \
            load_frac_after, halo_bytes, pass_seconds
        gc, nc, pstats = sharded_louvain_passes(
            src_g, dst_g, w_g, spec, move, agg, n_live_,
            phases_for=phases_for, use_ladder=config.use_ladder,
            comm_backend=cb, state_layout=sl, refine=config.refine,
            reshard=config.reshard, pipeline_fetch=config.pipeline_fetch,
            **kw, **pass_kw)
        comm_rounds += sum(r["comm_rounds"] for r in pstats)
        comm_fb += sum(r["comm_fallback_rounds"] for r in pstats)
        comm_bytes += sum(r["comm_bytes"] for r in pstats)
        halo_bytes += sum(r.get("halo_bytes", 0) for r in pstats)
        pass_seconds += sum(r.get("seconds", 0.0) for r in pstats)
        for r in pstats[1:]:   # coarse tiers only (row 0 is the fine pass)
            coarse_e_max = max(coarse_e_max, r["e_per_shard"])
        for r in pstats:
            if r.get("reshard"):
                reshard_passes += 1
                reshard_bytes_total += r["reshard_bytes"]
                b, a = (r["max_shard_load_frac_before"],
                        r["max_shard_load_frac_after"])
                load_frac_before = max(load_frac_before or 0.0, b)
                load_frac_after = max(load_frac_after or 0.0, a)
        return gc, nc, pstats

    def _mem_from(global_comm, n_valid):
        """Replicated membership from a pass-loop result (shared with the
        serving fleet — see ``distributed.sentinel_forced_membership``)."""
        return sentinel_forced_membership(global_comm, n_valid, spec.n_pad)

    with mesh:
        if prev is None:
            global_comm, n_comms, _ = _run_passes(n_live)
            mem = _mem_from(global_comm, n_live)
        else:
            mem = jnp.asarray(pad_membership(
                np.asarray(prev, np.int32)[: spec.n_pad], spec.n_pad))
            n_comms = int(len(np.unique(np.asarray(prev)[:n_live])))
        n_valid_dev = jnp.asarray(n_live, jnp.int32)

        for batch in batches:
            t0 = time.perf_counter()
            out = apply_fn(src_g, dst_g, w_g, batch.src, batch.dst,
                           batch.weight, batch.b_valid, n_valid_dev)
            if int(out[4]) > spec.e_per_shard:   # e_max: worst shard count
                if not grow_capacity:
                    raise ValueError(
                        f"sharded edge batch overflows capacity: a shard "
                        f"needs {int(out[4])} slots > e_per_shard="
                        f"{spec.e_per_shard}")
                # Re-bucket the PRE-apply arrays into doubled capacity,
                # rebuild the jit'd phases once, and re-apply the batch.
                _grow_to(max(2 * spec.e_per_shard, int(out[4])))
                out = apply_fn(src_g, dst_g, w_g, batch.src, batch.dst,
                               batch.weight, batch.b_valid, n_valid_dev)
            src_g, dst_g, w_g, touched, _, n_valid_dev = out
            t1 = time.perf_counter()

            frontier = None
            if screen_mode is not None:
                frontier = affected_frontier(touched, mem, n_valid_dev,
                                             screen_mode)
            n_live = int(n_valid_dev)
            global_comm, n_comms, _ = _run_passes(
                n_live, init_membership=mem, init_frontier=frontier)
            mem = _mem_from(global_comm, n_live)
            t2 = time.perf_counter()

            touched_counts.append(jnp.sum(touched))
            frontier_sizes.append(jnp.sum(frontier) if frontier is not None
                                  else jnp.asarray(n_live, jnp.int32))
            stats.append(BatchUpdateStats(
                batch_size=int(batch.b_valid),
                n_touched=-1,      # filled lazily after the stream
                frontier_size=-1,  # filled lazily after the stream
                n_vertices=n_live,
                n_communities=n_comms,
                apply_seconds=t1 - t0,
                update_seconds=t2 - t1,
                modularity=float(sharded_modularity(
                    src_g, dst_g, w_g, mem)) if track_modularity else None,
            ))
        for s, tc, fs in zip(stats, touched_counts, frontier_sizes):
            s.n_touched = int(tc)
            s.frontier_size = int(fs)

    membership = np.asarray(mem[:n_live])
    return ShardedDynamicResult(
        membership=membership,
        n_communities=int(len(np.unique(membership))),
        batch_stats=stats,
        total_seconds=time.perf_counter() - t_start,
        n_regrows=n_regrows,
        spec=spec,
        comm_backend=cb,
        comm_rounds=comm_rounds,
        comm_fallback_rounds=comm_fb,
        bytes_on_wire=comm_bytes,
        reshard_passes=reshard_passes,
        reshard_bytes=reshard_bytes_total,
        max_shard_load_frac_before=load_frac_before,
        max_shard_load_frac_after=load_frac_after,
        coarse_e_per_max=coarse_e_max,
        state_layout=sl,
        halo_bytes=halo_bytes,
        boundary_frac=bfrac,
        pass_seconds_total=pass_seconds,
    )
