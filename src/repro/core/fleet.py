"""Multi-tenant serving fleet: shard every tenant graph AND batch tenants.

The repo's two scaling axes were separate: ``core.multistream`` vmaps many
SMALL streams over one device, ``core.distributed_dynamic`` shard_maps one
BIG stream over many devices.  This layer fuses them into the serving stack
of the ROADMAP's "millions of users" item: each tenant's graph is 1-D
vertex-partitioned across the mesh (every lane is a full sharded layout) and
tenants are *batched per dispatch* with ``jax.vmap`` OVER the shard_map'd
step, so one XLA program advances a whole capacity bucket of tenants by one
stream step — the partition-then-pipeline layout of the parallel-heuristics
literature (Lu et al.; Staudt & Meyerhenke), with JAX collectives instead of
MPI ranks.

Three pieces:

  * **Bucketed capacity fleets** — tenants are admitted into power-of-two
    ``(v_per_shard, e_per_shard, b_cap)`` envelopes via
    ``configs.louvain_arch.plan_fleet``; lanes sharing an envelope share ONE
    compiled fused step.  A whale tenant that overflows its envelope
    *migrates buckets* (``migrate_envelope``) instead of forcing a
    fleet-wide recompile: its pre-apply lane is re-bucketed host-side, the
    overflowing step is replayed solo exactly once, and the lane joins (or
    founds) the bucket of the grown envelope while its old lane is frozen.
  * **Admission/routing** — ``FleetRouter.admit`` partitions a tenant into
    its envelope layout (cold pass loop when no previous membership is
    given) and ``FleetRouter.serve`` routes per-step ``EdgeBatch``es to
    lanes, exposing per-tenant ``PassStats`` (including the host-resolved
    screening mode, see below).
  * **Pipelined stepping** — the serve loop generalizes the pass loop's
    ``pipeline_fetch``: every bucket's step ``t`` is dispatched BEFORE step
    ``t - 1``'s convergence scalars are fetched (one stacked ``device_get``
    across all buckets), so device work overlaps host control.  A lane
    whose deferred scalars violate the fused fast path is repaired and its
    bucket's speculative dispatch is replaced.

**Correctness bar** (pinned in tests + the golden matrix): per-tenant
memberships are bit-for-bit identical to running each tenant alone through
``louvain_dynamic_sharded`` on the same mesh.  The fused step IS the solo
driver's pass 0 (same apply, same screening, same warm start, same move
phase, same renumber fold); a lane is accepted only when solo would have
stopped after pass 0 (converged, low shrink, or ``max_passes == 1``) —
otherwise the full solo pass loop replays that lane from its pre-step
membership, which reproduces the fused pass 0 exactly and continues.

Screening ``"auto"`` is resolved HOST-SIDE per bucket
(``engine.resolve_screening_host``) from the previous validated dispatch's
worst touched fraction: the on-device auto select evaluates both
granularities under vmap, which silently costs the full community-expansion
bill — the downgrade the satellite bugfix makes explicit via
``PassStats.downgraded``.  Because each dispatch's fetch is deferred one
step, the measurement the resolver sees is up to TWO steps stale (step 1
dispatches before step 0 validates); the mode actually run is recorded in
the step's ``PassStats``, and replaying the recorded modes through the solo
driver reproduces the fleet bit-for-bit.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.louvain_arch import (FleetEnvelope, fleet_envelope,
                                        fleet_v_per_shard, migrate_envelope,
                                        resolve_comm_backend,
                                        resolve_state_layout)
from repro.core.delta import EdgeBatch
from repro.core.distributed import (ShardedGraphSpec, _rebucket_live_host,
                                    _vertex_k, _warm_comm_sigma,
                                    make_distributed_move, make_tier_phases,
                                    measure_boundary_frac,
                                    partition_graph_host, replicated_renumber,
                                    sentinel_forced_membership,
                                    sharded_comm_plan, sharded_louvain_passes)
from repro.core.comm import phase_bytes
from repro.core.distributed_dynamic import make_sharded_batch_apply
from repro.core.engine import (affected_frontier, normalize_screening,
                               resolve_screening_host)
from repro.core.graph import CSRGraph
from repro.core.louvain import LouvainConfig, PassStats, pad_membership


def _fleet_spec(env: FleetEnvelope, n_shards: int) -> ShardedGraphSpec:
    return ShardedGraphSpec(n_shards, env.v_per_shard, env.e_per_shard,
                            env.v_per_shard * n_shards)


@functools.lru_cache(maxsize=None)
def _make_fleet_step(mesh: Mesh, axes: Tuple[str, ...],
                     spec: ShardedGraphSpec, b_cap: int,
                     screen_mode: Optional[str], tolerance: float,
                     max_iterations: int, gate_fraction: int,
                     use_pruning: bool, comm_backend: str,
                     apply_backend: str, state_layout: str = "replicated"):
    """Build the fused per-bucket step: ``jit(vmap(`` solo pass 0 ``))``.

    Lane signature (vmapped over axis 0 of every operand)::

        (src_g, dst_g, w_g, mem, n_valid, n_limit,
         b_src, b_dst, b_w, b_valid)
        -> ((src', dst', w', mem', n_valid'), frontier,
            e_max, iters, n_comms, dq_sum, rounds, fallbacks,
            touched_n, frontier_n)

    The body is EXACTLY the solo streaming step's fast path: sharded batch
    apply (traced ``n_limit`` so lanes of different logical ``n_cap`` share
    the program), delta screening at the host-resolved ``screen_mode``,
    warm-started move phase at ``tolerance`` (= pass 0's
    ``initial_tolerance``), replicated renumber, sentinel-forced
    membership.  Lanes with an empty batch (``b_valid == 0``) keep their
    state bit-for-bit via a where-select on every output.  The scalars are
    returned UNFETCHED — the serve loop defers their ``device_get`` one
    dispatch (the ``pipeline_fetch`` generalization).
    """
    n_pad, sent = spec.n_pad, spec.sentinel
    apply_fn = make_sharded_batch_apply(mesh, axes, spec, None,
                                        apply_backend, True)
    move = make_distributed_move(
        mesh, axes, spec, max_iterations=max_iterations,
        gate_fraction=gate_fraction, use_pruning=use_pruning,
        comm_backend=comm_backend, state_layout=state_layout)
    tol = jnp.float32(tolerance)

    def lane(src_g, dst_g, w_g, mem, n_valid, n_limit,
             b_src, b_dst, b_w, b_valid):
        src2, dst2, w2, touched, e_max, nv2 = apply_fn(
            src_g, dst_g, w_g, b_src, b_dst, b_w, b_valid, n_valid,
            n_limit)
        if screen_mode is not None:
            frontier = affected_frontier(touched, mem, nv2, screen_mode)
        else:
            frontier = jnp.ones((n_pad + 1,), bool)
        k = _vertex_k(w2, src2, jnp.zeros((n_pad + 1,), jnp.float32))
        m = jnp.sum(w2) * 0.5
        comm0, sigma0 = _warm_comm_sigma(mem, k, nv2)
        comm, _sigma, iters, dq_sum, rounds, fallbacks = move(
            src2, dst2, w2, comm0, sigma0, k, frontier, m, tol)
        comm_ren, n_comms = replicated_renumber(comm)
        mem2 = sentinel_forced_membership(comm_ren[:n_pad], nv2, n_pad)

        active = b_valid > 0
        sel = lambda new, old: jnp.where(active, new, old)
        state = (sel(src2, src_g), sel(dst2, dst_g), sel(w2, w_g),
                 sel(mem2, mem), sel(nv2, n_valid))
        zero = jnp.int32(0)
        frontier_n = (jnp.sum(frontier.astype(jnp.int32))
                      if screen_mode is not None else nv2)
        scalars = (sel(e_max, zero), sel(iters, zero),
                   sel(n_comms, zero), sel(dq_sum, jnp.float32(0.0)),
                   sel(rounds, zero), sel(fallbacks, zero),
                   sel(jnp.sum(touched.astype(jnp.int32)), zero),
                   sel(frontier_n, zero))
        return state, frontier, scalars

    return jax.jit(jax.vmap(lane))


@dataclasses.dataclass
class _Tenant:
    """Host-side tenant record; device state lives in envelope layout."""
    tid: str
    n_cap: int                  # logical vertex capacity (CSR n_cap)
    env: FleetEnvelope
    src: jax.Array              # (n_shards * e_per_shard,) slot arrays
    dst: jax.Array
    w: jax.Array
    mem: jax.Array              # (n_pad + 1,) replicated membership
    n_valid: int
    stats: List[PassStats] = dataclasses.field(default_factory=list)
    migrations: List[dict] = dataclasses.field(default_factory=list)
    n_fallbacks: int = 0
    #: Boundary fraction of the admitted partition — drives the per-bucket
    #: state_layout="auto" resolution (worst lane wins).
    boundary_frac: Optional[float] = None


class _Bucket:
    """One capacity envelope's stacked lanes during a serve call."""

    def __init__(self, env: FleetEnvelope, spec: ShardedGraphSpec,
                 tenants: List[_Tenant],
                 state_layout: str = "replicated"):
        self.env = env
        self.spec = spec
        self.lanes: List[_Tenant] = list(tenants)
        self.frozen: set = set()     # lane indices migrated away
        self.touched_frac: Optional[float] = None   # last validated max
        self.state_layout = state_layout   # resolved for this bucket
        self.state = (
            jnp.stack([t.src for t in self.lanes]),
            jnp.stack([t.dst for t in self.lanes]),
            jnp.stack([t.w for t in self.lanes]),
            jnp.stack([t.mem for t in self.lanes]),
            jnp.asarray([t.n_valid for t in self.lanes], jnp.int32),
        )
        self.n_lim = jnp.asarray([t.n_cap for t in self.lanes], jnp.int32)

    def append_lane(self, tenant: _Tenant, lane_state):
        """Join a migrated lane: widen every stacked array by one row."""
        self.lanes.append(tenant)
        src, dst, w, mem, nv = self.state
        s2, d2, w2, m2, nv2 = lane_state
        self.state = (
            jnp.concatenate([src, s2[None]]),
            jnp.concatenate([dst, d2[None]]),
            jnp.concatenate([w, w2[None]]),
            jnp.concatenate([mem, m2[None]]),
            jnp.concatenate([nv, jnp.asarray([nv2], jnp.int32)]),
        )
        self.n_lim = jnp.concatenate(
            [self.n_lim, jnp.asarray([tenant.n_cap], jnp.int32)])


@dataclasses.dataclass
class _Pending:
    """One bucket dispatch awaiting its deferred convergence fetch."""
    bucket: _Bucket
    t: int
    pre: tuple                  # stacked state BEFORE the dispatch
    post: tuple                 # stacked state after (speculatively kept)
    frontier: jax.Array         # (T, n_pad + 1) seed frontiers
    scalars: tuple              # (T,) device arrays, unfetched
    batches: tuple              # (bs, bd, bw, bv) np arrays as dispatched
    active: np.ndarray          # (T,) bool, b_valid > 0 at dispatch
    mode: Optional[str]         # screening mode this dispatch ran with
    downgraded: bool
    seconds: float


@dataclasses.dataclass
class FleetResult:
    """Per-tenant results of one ``FleetRouter.serve`` call."""
    membership: Dict[str, np.ndarray]
    n_communities: Dict[str, int]
    pass_stats: Dict[str, List[PassStats]]
    total_seconds: float
    n_dispatches: int = 0
    n_fallbacks: int = 0        # lanes replayed through the solo pass loop
    n_migrations: int = 0       # whale bucket migrations
    bytes_on_wire: int = 0      # plan-priced move-phase exchange bytes
    comm_rounds: int = 0
    comm_backend: str = "gather"
    #: Envelope -> tenant ids, the bucket layout at the END of the serve.
    buckets: Dict[FleetEnvelope, List[str]] = dataclasses.field(
        default_factory=dict)
    #: Per-bucket resolved working-state layout; ``state_layout`` is the
    #: fleet-level summary ("mixed" when buckets disagree under "auto").
    bucket_layouts: Dict[FleetEnvelope, str] = dataclasses.field(
        default_factory=dict)
    state_layout: str = "replicated"
    halo_bytes: int = 0         # boundary-mover share of bytes_on_wire
    #: Worst admitted boundary fraction across the served tenants.
    boundary_frac: Optional[float] = None

    @property
    def bytes_per_dispatch(self) -> float:
        return self.bytes_on_wire / max(self.n_dispatches, 1)

    @property
    def halo_bytes_per_round(self) -> float:
        return self.halo_bytes / max(self.comm_rounds, 1)


class FleetRouter:
    """Admission + routing for the multi-tenant sharded serving fleet.

    ``admit`` places each tenant in its ``plan_fleet`` envelope (one
    compiled fused step per envelope); ``serve`` advances every tenant's
    stream with one vmapped dispatch per bucket per step, deferring each
    dispatch's convergence fetch one step.  See the module docstring for
    the parity contract.

    ``screening`` accepts the usual modes; ``"auto"`` (the default) is
    resolved host-side per bucket and recorded (with its downgrade flag)
    in the per-tenant ``PassStats``.  ``config.refine`` must stay
    ``"none"``: refinement runs inside every solo pass INCLUDING pass 0,
    which the fused fast path does not reproduce.
    """

    def __init__(self, mesh: Mesh, axes: Tuple[str, ...],
                 config: LouvainConfig = LouvainConfig(), *,
                 screening="auto", apply_backend: str = "xla"):
        if config.refine != "none":
            raise ValueError("FleetRouter requires config.refine='none' "
                             "(refinement changes pass 0, which the fused "
                             "fleet step must reproduce bit-for-bit)")
        self.mesh = mesh
        self.axes = tuple(axes)
        self.config = config
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.screen_req = normalize_screening(screening)
        self.comm_backend = resolve_comm_backend(config.comm_backend,
                                                 self.n_shards)
        self.apply_backend = apply_backend
        self.tenants: Dict[str, _Tenant] = {}

        # Tier factories per working-state layout: layouts resolve PER
        # BUCKET (config "auto" + each bucket's worst admitted boundary
        # fraction), and make_tier_phases is cached, so asking for both
        # layouts costs nothing until a bucket actually uses one.
        def _tiers(state_layout: str):
            return make_tier_phases(
                mesh, self.axes, max_iterations=config.max_iterations,
                gate_fraction=config.gate_fraction,
                use_pruning=config.use_pruning,
                comm_backend=self.comm_backend,
                state_layout=state_layout, refine="none")
        self._tiers = _tiers
        self._pass_kw = dict(
            max_passes=config.max_passes,
            initial_tolerance=config.initial_tolerance,
            tolerance_drop=config.tolerance_drop,
            aggregation_tolerance=config.aggregation_tolerance,
        )
        self._buckets: List[_Bucket] = []

    # -- admission ---------------------------------------------------------

    def admit(self, tid: str, graph: CSRGraph,
              prev: Optional[np.ndarray] = None,
              b_cap: int = 1) -> FleetEnvelope:
        """Admit a tenant: partition into its envelope layout and warm up.

        ``b_cap`` is the largest per-step batch capacity the tenant's
        streams will carry (rounded up to the envelope's power of two).
        ``prev=None`` runs one cold solo pass loop to produce the resident
        membership — the same machinery ``louvain_dynamic_sharded`` uses,
        so a later solo run from the same ``prev`` matches bit-for-bit.
        """
        if tid in self.tenants:
            raise ValueError(f"tenant {tid!r} already admitted")
        v_per = fleet_v_per_shard(graph.n_cap, self.n_shards)
        n_pad = v_per * self.n_shards
        # First partition measures the worst owned-edge count; the second
        # lands directly in the envelope's slot layout.
        _, _, _, spec0 = partition_graph_host(graph, self.n_shards,
                                              n_target=n_pad)
        env = fleet_envelope(graph.n_cap, spec0.e_per_shard, b_cap,
                             self.n_shards)
        spec = _fleet_spec(env, self.n_shards)
        src_g, dst_g, w_g, spec2 = partition_graph_host(
            graph, self.n_shards, n_target=n_pad,
            e_per_shard=env.e_per_shard)
        assert spec2 == spec, (spec2, spec)
        n_live = int(graph.n_valid)
        bfrac = measure_boundary_frac(src_g, dst_g, spec, n_live)
        if prev is None:
            with self.mesh:
                mem, _, _ = self._run_solo_passes(
                    spec, src_g, dst_g, w_g, n_live,
                    state_layout=resolve_state_layout(
                        self.config.state_layout, self.n_shards, bfrac))
        else:
            mem = jnp.asarray(pad_membership(
                np.asarray(prev, np.int32)[: spec.n_pad], spec.n_pad))
        self.tenants[tid] = _Tenant(tid=tid, n_cap=graph.n_cap, env=env,
                                    src=src_g, dst=dst_g, w=w_g, mem=mem,
                                    n_valid=n_live, boundary_frac=bfrac)
        return env

    def _run_solo_passes(self, spec, src_g, dst_g, w_g, n_live,
                         init_membership=None, init_frontier=None,
                         state_layout: Optional[str] = None):
        """The solo pass loop at this router's knobs — admission cold
        starts, non-converged-lane fallbacks and migration replays all go
        through here so they are the SAME computation the solo driver
        runs.  ``state_layout`` is the caller's resolved per-bucket (or
        per-admission) layout; memberships are invariant to it."""
        layout = (state_layout if state_layout is not None
                  else resolve_state_layout(self.config.state_layout,
                                            self.n_shards))
        tiers = self._tiers(layout)
        move, agg, _ = tiers(spec)
        gc, nc, pstats = sharded_louvain_passes(
            src_g, dst_g, w_g, spec, move, agg, n_live,
            init_membership=init_membership, init_frontier=init_frontier,
            phases_for=tiers, use_ladder=self.config.use_ladder,
            comm_backend=self.comm_backend, state_layout=layout,
            refine="none", reshard=self.config.reshard,
            pipeline_fetch=self.config.pipeline_fetch, **self._pass_kw)
        return sentinel_forced_membership(gc, n_live, spec.n_pad), nc, pstats

    # -- serving -----------------------------------------------------------

    def serve(self, streams: Dict[str, Sequence[EdgeBatch]]) -> FleetResult:
        """Advance every tenant's stream; one fused dispatch per bucket per
        step, convergence fetches deferred one dispatch."""
        t_start = time.perf_counter()
        for tid in streams:
            if tid not in self.tenants:
                raise ValueError(f"tenant {tid!r} not admitted")
        n_steps = max((len(s) for s in streams.values()), default=0)

        self._n_dispatches = self._n_fallbacks = self._n_migrations = 0
        self._bytes = self._rounds = self._halo = 0
        by_env: Dict[FleetEnvelope, List[_Tenant]] = {}
        for tid in streams:
            ten = self.tenants[tid]
            by_env.setdefault(ten.env, []).append(ten)
        # Layout per bucket: "auto" takes the WORST admitted boundary
        # fraction over the bucket's lanes, so hybrid engages only when
        # every cohabitant tenant is interior-dominated.
        self._buckets = [
            _Bucket(env, _fleet_spec(env, self.n_shards), tenants,
                    resolve_state_layout(
                        self.config.state_layout, self.n_shards,
                        max((t.boundary_frac for t in tenants
                             if t.boundary_frac is not None),
                            default=None)))
            for env, tenants in by_env.items()]

        with self.mesh:
            pending: Dict[int, _Pending] = {}
            for t in range(n_steps):
                fresh = {id(B): self._dispatch(B, t, streams)
                         for B in list(self._buckets)}
                if pending:
                    for B in self._validate(pending):
                        fresh[id(B)] = self._dispatch(B, t, streams)
                pending = fresh
            if pending:
                self._validate(pending)

        # Unstack bucket lanes back into tenant records.
        membership: Dict[str, np.ndarray] = {}
        n_comms: Dict[str, int] = {}
        for B in self._buckets:
            src, dst, w, mem, nv = B.state
            nv_host = np.asarray(nv)
            for i, ten in enumerate(B.lanes):
                if i in B.frozen:
                    continue
                ten.src, ten.dst, ten.w = src[i], dst[i], w[i]
                ten.mem = mem[i]
                ten.n_valid = int(nv_host[i])
                m = np.asarray(ten.mem[: ten.n_valid])
                membership[ten.tid] = m
                n_comms[ten.tid] = int(len(np.unique(m))) if len(m) else 0
        buckets_out = {B.env: [t.tid for i, t in enumerate(B.lanes)
                               if i not in B.frozen]
                       for B in self._buckets}
        layouts_out = {B.env: B.state_layout for B in self._buckets
                       if buckets_out.get(B.env)}
        layout_set = set(layouts_out.values())
        summary_layout = (layout_set.pop() if len(layout_set) == 1
                          else "mixed" if layout_set
                          else resolve_state_layout(
                              self.config.state_layout, self.n_shards))
        fracs = [self.tenants[tid].boundary_frac for tid in streams
                 if self.tenants[tid].boundary_frac is not None]
        self._buckets = []
        return FleetResult(
            membership=membership,
            n_communities=n_comms,
            pass_stats={tid: self.tenants[tid].stats for tid in streams},
            total_seconds=time.perf_counter() - t_start,
            n_dispatches=self._n_dispatches,
            n_fallbacks=self._n_fallbacks,
            n_migrations=self._n_migrations,
            bytes_on_wire=self._bytes,
            comm_rounds=self._rounds,
            comm_backend=self.comm_backend,
            buckets={env: tids for env, tids in buckets_out.items() if tids},
            bucket_layouts=layouts_out,
            state_layout=summary_layout,
            halo_bytes=self._halo,
            boundary_frac=max(fracs) if fracs else None,
        )

    def _dispatch(self, B: _Bucket, t: int, streams) -> _Pending:
        """Dispatch one bucket's step ``t``; returns without any host sync
        on the result (the convergence scalars stay on device)."""
        T = len(B.lanes)
        bc = B.env.b_cap
        sent = B.spec.sentinel
        bs = np.full((T, bc), sent, np.int32)
        bd = np.full((T, bc), sent, np.int32)
        bw = np.zeros((T, bc), np.float32)
        bv = np.zeros((T,), np.int32)
        for i, ten in enumerate(B.lanes):
            if i in B.frozen:
                continue
            st = streams.get(ten.tid, ())
            if t < len(st):
                b = st[t]
                if b.b_cap > bc:
                    raise ValueError(
                        f"tenant {ten.tid!r} batch b_cap={b.b_cap} exceeds "
                        f"its admitted envelope b_cap={bc}")
                bs[i, : b.b_cap] = np.asarray(b.src)
                bd[i, : b.b_cap] = np.asarray(b.dst)
                bw[i, : b.b_cap] = np.asarray(b.weight)
                bv[i] = int(b.b_valid)
        mode, downgraded = resolve_screening_host(self.screen_req,
                                                  B.touched_frac)
        cfg = self.config
        fused = _make_fleet_step(
            self.mesh, self.axes, B.spec, bc, mode,
            float(cfg.initial_tolerance), cfg.max_iterations,
            cfg.gate_fraction, cfg.use_pruning, self.comm_backend,
            self.apply_backend, B.state_layout)
        t0 = time.perf_counter()
        pre = B.state
        state, frontier, scalars = fused(
            *pre, B.n_lim, jnp.asarray(bs), jnp.asarray(bd),
            jnp.asarray(bw), jnp.asarray(bv))
        B.state = state
        self._n_dispatches += 1
        return _Pending(bucket=B, t=t, pre=pre, post=state,
                        frontier=frontier, scalars=scalars,
                        batches=(bs, bd, bw, bv), active=bv > 0, mode=mode,
                        downgraded=downgraded,
                        seconds=time.perf_counter() - t0)

    def _validate(self, pending: Dict[int, _Pending]) -> List[_Bucket]:
        """Fetch + check the deferred scalars of every pending dispatch.

        ONE stacked ``device_get`` across all buckets (the deferred
        convergence fetch).  Returns the buckets whose post-step state
        changed (fallback repairs, migration joins) and therefore need
        their speculative next-step dispatch replaced.
        """
        plist = list(pending.values())
        fetched = jax.device_get([(p.scalars, p.post[4]) for p in plist])
        redo: List[_Bucket] = []
        migrations = []
        for p, (sc, nv_post) in zip(plist, fetched):
            B = p.bucket
            spec = B.spec
            e_max, iters, n_comms, dq_sum, rounds, fallbacks, touched_n, \
                frontier_n = sc
            active = [i for i in range(len(p.active))
                      if p.active[i] and i not in B.frozen]
            if not active:
                continue
            # Comm accounting: the batched collectives ship EVERY lane's
            # payload for the max rounds any lane ran (converged lanes ride
            # along) — price the true wire cost, not the per-lane solo sum.
            plan = sharded_comm_plan(spec, self.comm_backend,
                                     B.state_layout)
            r_exec = max(int(rounds[i]) for i in active)
            fb_exec = max(int(fallbacks[i]) for i in active)
            self._bytes += len(B.lanes) * phase_bytes(plan, r_exec, fb_exec)
            self._halo += len(B.lanes) * plan.halo_round_bytes * r_exec
            self._rounds += r_exec
            # Worst touched fraction over the bucket: drives the NEXT
            # dispatch's host-side "auto" screening resolution.
            B.touched_frac = max(
                int(touched_n[i]) / max(int(nv_post[i]), 1) for i in active)

            patched = None
            agg_tol = self.config.aggregation_tolerance
            max_passes = self.config.max_passes
            for i in active:
                ten = B.lanes[i]
                nv_i = int(nv_post[i])
                overflow = int(e_max[i]) > spec.e_per_shard
                accepted = (not overflow) and (
                    int(iters[i]) <= 1
                    or int(n_comms[i]) / max(nv_i, 1) > agg_tol
                    or max_passes <= 1)
                stat = PassStats(
                    iterations=int(iters[i]),
                    n_communities=int(n_comms[i]),
                    n_vertices=nv_i,
                    dq_sum=float(dq_sum[i]),
                    seconds=p.seconds,
                    frontier_size=int(frontier_n[i]),
                    n_cap=spec.n_pad, e_cap=spec.e_per_shard * spec.n_shards,
                    screening=p.mode, scan_backend="sharded",
                    downgraded=p.downgraded)
                if overflow:
                    migrations.append((p, i, int(e_max[i])))
                    continue
                if accepted:
                    ten.stats.append(stat)
                    continue
                # Fused pass 0 is not where solo stops: replay this lane
                # through the full solo pass loop from its PRE-step
                # membership (it reproduces the fused pass 0 bit-for-bit
                # and continues through aggregation).
                if patched is None:
                    patched = list(p.post)
                frontier_i = (p.frontier[i] if p.mode is not None else None)
                mem_i, nc_i, pstats = self._run_solo_passes(
                    spec, p.post[0][i], p.post[1][i], p.post[2][i], nv_i,
                    init_membership=p.pre[3][i], init_frontier=frontier_i,
                    state_layout=B.state_layout)
                patched[3] = patched[3].at[i].set(mem_i)
                ten.n_fallbacks += 1
                self._n_fallbacks += 1
                self._rounds += sum(r["comm_rounds"] for r in pstats[1:])
                self._bytes += sum(r["comm_bytes"] for r in pstats[1:])
                self._halo += sum(r.get("halo_bytes", 0)
                                  for r in pstats[1:])
                stat = dataclasses.replace(
                    stat, iterations=sum(r["iterations"] for r in pstats),
                    n_communities=nc_i)
                ten.stats.append(stat)
            if patched is not None:
                # B.state currently holds the NEXT step's speculative
                # result — discard it; the caller redispatches from the
                # repaired post-step state.  p.post is updated too so a
                # migration joining this bucket sees the repaired base.
                p.post = tuple(patched)
                B.state = p.post
                redo.append(B)
        for p, i, e_need in migrations:
            dest = self._migrate(p, i, e_need, pending)
            if dest is not None and dest not in redo:
                redo.append(dest)
        return redo

    def _migrate(self, p: _Pending, lane: int, e_need: int,
                 pending) -> Optional[_Bucket]:
        """Whale migration: re-bucket the lane's PRE-apply state into the
        grown envelope, replay the overflowing step solo EXACTLY ONCE, and
        join the destination bucket.  The source lane is frozen (its
        speculative garbage is never read), so cohabitant tenants keep
        their compiled program and their speculative next step.
        """
        B = p.bucket
        ten = B.lanes[lane]
        env = migrate_envelope(ten.env, e_need)
        spec_new = _fleet_spec(env, self.n_shards)
        src, dst, w, spec_got = _rebucket_live_host(
            p.pre[0][lane], p.pre[1][lane], p.pre[2][lane],
            B.spec.sentinel, spec_new)
        if spec_got != spec_new:      # pathological skew grew further
            spec_new = spec_got
            env = env._replace(e_per_shard=spec_got.e_per_shard)
        mem_pre = p.pre[3][lane]
        nv_pre = jnp.asarray(np.asarray(p.pre[4][lane]), jnp.int32)
        bs, bd, bw, bv = p.batches

        apply_fn = make_sharded_batch_apply(self.mesh, self.axes, spec_new,
                                            ten.n_cap, self.apply_backend)
        while True:
            out = apply_fn(src, dst, w, jnp.asarray(bs[lane]),
                           jnp.asarray(bd[lane]), jnp.asarray(bw[lane]),
                           jnp.asarray(bv[lane]), nv_pre)
            if int(out[4]) <= spec_new.e_per_shard:
                break
            env = migrate_envelope(env, int(out[4]))
            spec_new = _fleet_spec(env, self.n_shards)
            src, dst, w, _ = _rebucket_live_host(src, dst, w,
                                                 spec_new.sentinel, spec_new)
            apply_fn = make_sharded_batch_apply(self.mesh, self.axes,
                                                spec_new, ten.n_cap,
                                                self.apply_backend)
        src2, dst2, w2, touched, _, nv2 = out
        frontier = (affected_frontier(touched, mem_pre, nv2, p.mode)
                    if p.mode is not None else None)
        n_live = int(nv2)
        mem2, nc, pstats = self._run_solo_passes(
            spec_new, src2, dst2, w2, n_live,
            init_membership=mem_pre, init_frontier=frontier,
            state_layout=B.state_layout)
        self._rounds += sum(r["comm_rounds"] for r in pstats)
        self._bytes += sum(r["comm_bytes"] for r in pstats)
        self._halo += sum(r.get("halo_bytes", 0) for r in pstats)
        ten.stats.append(PassStats(
            iterations=sum(r["iterations"] for r in pstats),
            n_communities=nc, n_vertices=n_live,
            dq_sum=sum(r["dq_sum"] for r in pstats),
            seconds=0.0,
            frontier_size=int(np.asarray(jnp.sum(frontier)))
            if frontier is not None else n_live,
            n_cap=spec_new.n_pad,
            e_cap=spec_new.e_per_shard * spec_new.n_shards,
            screening=p.mode, scan_backend="sharded",
            downgraded=p.downgraded))
        ten.env = env
        ten.migrations.append(dict(step=p.t, e_need=e_need,
                                   e_per_shard=env.e_per_shard))
        self._n_migrations += 1
        B.frozen.add(lane)

        lane_state = (src2, dst2, w2, mem2, n_live)
        for dest in self._buckets:
            if dest is not B and dest.env == env:
                # Join at the destination's VALIDATED post-step state.  If
                # dest dispatched this step too, its resident state is the
                # NEXT step's speculative result — rewind to its pending
                # entry's post (already repaired if it had fallbacks); the
                # caller redispatches dest with the extra lane.
                dp = pending.get(id(dest))
                if dp is not None:
                    dest.state = dp.post
                dest.append_lane(ten, lane_state)
                return dest
        dest = _Bucket.__new__(_Bucket)
        dest.env = env
        dest.spec = spec_new
        dest.lanes = [ten]
        dest.frozen = set()
        dest.touched_frac = B.touched_frac
        dest.state_layout = B.state_layout
        dest.state = (jnp.stack([src2]), jnp.stack([dst2]),
                      jnp.stack([w2]), jnp.stack([mem2]),
                      jnp.asarray([n_live], jnp.int32))
        dest.n_lim = jnp.asarray([ten.n_cap], jnp.int32)
        self._buckets.append(dest)
        return dest


def serve_fleet(graphs: Dict[str, CSRGraph],
                streams: Dict[str, Sequence[EdgeBatch]],
                mesh: Mesh, axes: Tuple[str, ...],
                prevs: Optional[Dict[str, np.ndarray]] = None,
                config: LouvainConfig = LouvainConfig(), *,
                screening="auto", apply_backend: str = "xla") -> FleetResult:
    """One-shot convenience: admit every tenant, serve every stream.

    ``prevs`` maps tenant id -> previous membership (tenants absent from it
    get a cold solo pass loop at admission).  Batch capacity per tenant is
    taken from the largest batch in its stream.
    """
    router = FleetRouter(mesh, axes, config, screening=screening,
                         apply_backend=apply_backend)
    prevs = prevs or {}
    for tid, graph in graphs.items():
        b_cap = max((b.b_cap for b in streams.get(tid, ())), default=1)
        router.admit(tid, graph, prev=prevs.get(tid), b_cap=b_cap)
    return router.serve(streams)
