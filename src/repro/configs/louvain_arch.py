"""The paper's own workload as dry-run cells: distributed GVE-Louvain
phases lowered at SuiteSparse scale on the production meshes.

Shapes (mirroring Table 1's largest graphs; |E| counts directed slots):
    web_3.8B_move        sk-2005 scale   one local-move round
    web_3.8B_aggregate   sk-2005 scale   aggregation phase
    road_108M_move       europe_osm scale

Variants:
    "a2a"  — aggregation routes partial coarse edges to their owner shard
             with a capacity-bounded all_to_all instead of the gather-based
             baseline (which materializes the FULL edge list per chip —
             45.6 GB at sk-2005 scale, infeasible on v5e; the all_to_all
             variant is the §Perf fix for the paper's own bottleneck phase).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from repro.core.distributed import (ShardedGraphSpec, _best_moves_shard,
                                    _round_body, _shard_index)
from repro.core.engine import round_gate

F32, I32 = jnp.float32, jnp.int32

# ---------------------------------------------------------------------------
# Scanner-backend policy (the ``LouvainConfig.scan_backend`` knob).
# ---------------------------------------------------------------------------

#: Accepted values of ``LouvainConfig.scan_backend``.
SCAN_BACKENDS = ("auto", "full", "compact", "ell", "ell_fused")

#: ``"auto"`` picks the frontier-compacted sort-reduce scanner when the seed
#: frontier covers at most this fraction of the vertices (the measured
#: crossover regime: compact beats the full e_cap scan comfortably at
#: |F|/n <= ~10%, and its overflow fallback makes larger frontiers merely
#: neutral, not wrong).
AUTO_COMPACT_MAX_FRONTIER_FRAC = 0.10

#: Compact work-buffer capacity as a fraction of ``e_cap``.  Frontier edge
#: slots beyond the cap trigger the in-program fallback to the full scan,
#: so this bounds compact-scan memory/compile shape, not correctness.
COMPACT_WORK_FRAC = 0.25

#: Work-buffer floor — tiny graphs keep a sortable minimum.
COMPACT_WORK_MIN = 64


def compact_work_cap(e_cap: int, frac: float = COMPACT_WORK_FRAC) -> int:
    """Static work-buffer capacity for the compacted scanner on ``e_cap``."""
    return max(1, min(int(e_cap), max(COMPACT_WORK_MIN, int(e_cap * frac))))


# ---------------------------------------------------------------------------
# Aggregation-backend policy (the ``LouvainConfig.agg_backend`` knob).
# ---------------------------------------------------------------------------

#: Accepted values of ``LouvainConfig.agg_backend``.
AGG_BACKENDS = ("auto", "sort", "pallas")


def resolve_agg_backend(backend: str) -> str:
    """Map the ``agg_backend`` knob to a concrete aggregation backend.

    ``"sort"`` is the XLA lexsort -> segment_sum -> scatter chain;
    ``"pallas"`` fuses the post-sort group-detect + weight-accumulate +
    emit into one carry-chained kernel sweep (``repro.kernels.aggregate``).
    ``"auto"`` picks the kernel on TPU and the XLA chain elsewhere (the
    interpreter is a correctness tool, not a fast path).
    """
    if backend not in AGG_BACKENDS:
        raise ValueError(f"agg_backend must be one of {AGG_BACKENDS}; "
                         f"got {backend!r}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "sort"
    return backend


# ---------------------------------------------------------------------------
# Communication-backend policy (the ``LouvainConfig.comm_backend`` knob).
#
# The sharded move round has two exchange implementations (both pinned
# bit-for-bit against the committed goldens on one shard):
#   "gather" — the Vite-style dense ghost exchange: all_gather the owned
#              membership slice + moved mask, psum the dense Sigma and
#              community-size arrays (2 x O(n_pad) collectives per round).
#   "delta"  — ship only the movers as bit-packed (index, label) lanes;
#              Sigma and community sizes are reconstructed locally from
#              the replicated vertex weights and membership; a measured-
#              overflow lax.cond falls back to the dense exchange when a
#              round's movers exceed the cap.
# ---------------------------------------------------------------------------

#: Accepted values of ``LouvainConfig.comm_backend``.
COMM_BACKENDS = ("auto", "gather", "delta")

#: Mover-buffer capacity as a fraction of ``v_per_shard``: a round moving
#: more than v_per / DELTA_MOVE_CAP_FRAC owned vertices (early cold rounds)
#: takes the dense fallback; warm/late rounds fit comfortably.
DELTA_MOVE_CAP_FRAC = 4

#: Mover-buffer floor — tiny shards keep a usable buffer.
DELTA_MOVE_CAP_MIN = 8


def delta_move_cap(v_per: int) -> int:
    """Static mover-buffer capacity for a shard owning ``v_per`` vertices.

    The one cap of the delta exchange: movers are all that travels (Sigma
    and community sizes are reconstructed from replicated state), so a
    round overflows exactly when its movers do.
    """
    return max(1, min(int(v_per),
                      max(int(v_per) // DELTA_MOVE_CAP_FRAC,
                          DELTA_MOVE_CAP_MIN)))


def resolve_comm_backend(backend: str, n_shards: int) -> str:
    """Map the ``comm_backend`` knob to a concrete exchange for a mesh.

    ``"auto"`` picks ``"delta"`` on real multi-shard meshes and
    ``"gather"`` on a single shard, where every collective is an identity
    move and the delta path's pack/compact work buys nothing.  Explicit
    values pass through (``"delta"`` on one shard is how the golden matrix
    pins the path bit-for-bit).
    """
    if backend not in COMM_BACKENDS:
        raise ValueError(f"comm_backend must be one of {COMM_BACKENDS}; "
                         f"got {backend!r}")
    if backend == "auto":
        return "delta" if n_shards > 1 else "gather"
    return backend


# ---------------------------------------------------------------------------
# State-layout policy (the ``LouvainConfig.state_layout`` knob).
#
# The sharded move round has two STATE layouts, orthogonal to the comm
# backend (both pinned bit-for-bit against the committed goldens):
#   "replicated" — every shard holds (and keeps fresh) the full replicated
#                  membership / Sigma / sizes / K arrays; reconstruction
#                  and per-lane memory traffic scale with n_pad.
#   "hybrid"     — the P3 hybrid-parallel layout: topology stays sharded,
#                  per-vertex working state is OWNER-PARTITIONED, and only
#                  the boundary/halo labels (owned vertices with a live
#                  remote neighbour, ``comm.boundary_mask``) plus
#                  aggregated touched-community (Sigma, size) deltas are
#                  exchanged per round, so per-round payload scales with
#                  |boundary movers| + |touched communities| instead of n.
#                  One owned-membership all_gather per PHASE re-replicates
#                  the output for the unchanged downstream consumers.
# ---------------------------------------------------------------------------

#: Accepted values of ``LouvainConfig.state_layout``.
STATE_LAYOUTS = ("auto", "replicated", "hybrid")

#: ``"auto"`` engages the hybrid layout only when the measured boundary
#: fraction (boundary vertices / live vertices, measured host-side at
#: partition time) is at most this threshold: a mostly-interior partition
#: is where shipping boundary labels beats shipping dense state.  Above
#: it, nearly every vertex publishes anyway and replicated reconstruction
#: is the simpler bargain.
HYBRID_BOUNDARY_FRAC_MAX = 0.5

#: Touched-community lane capacity as a multiple of the mover cap: each
#: mover touches at most two communities (the one it leaves and the one it
#: joins), so 2x the mover cap never under-provisions a within-cap round.
HYBRID_TOUCHED_CAP_FRAC = 2


def hybrid_touched_cap(v_per: int) -> int:
    """Static touched-community lane capacity for a hybrid-DELTA round.

    Sized off the same mover cap as the delta exchange (every mover touches
    <= 2 communities); a round whose touched set overflows it takes the
    dense resync fallback, exactly like a mover overflow.
    """
    return HYBRID_TOUCHED_CAP_FRAC * delta_move_cap(v_per)


def resolve_state_layout(layout: str, n_shards: int,
                         boundary_frac: Optional[float] = None) -> str:
    """Map the ``state_layout`` knob to a concrete layout for a mesh.

    ``"auto"`` engages ``"hybrid"`` on real multi-shard meshes whose
    MEASURED boundary fraction is at most ``HYBRID_BOUNDARY_FRAC_MAX``
    (``None`` — no measurement available — stays replicated), mirroring
    ``resolve_comm_backend``'s shape.  Explicit values pass through
    (``"hybrid"`` on one shard has an empty boundary and collapses to the
    shard-local arithmetic — that is how the golden matrix pins it).
    """
    if layout not in STATE_LAYOUTS:
        raise ValueError(f"state_layout must be one of {STATE_LAYOUTS}; "
                         f"got {layout!r}")
    if layout == "auto":
        if (n_shards > 1 and boundary_frac is not None
                and boundary_frac <= HYBRID_BOUNDARY_FRAC_MAX):
            return "hybrid"
        return "replicated"
    return layout


# ---------------------------------------------------------------------------
# Coarse-pass capacity ladder (the ``LouvainConfig.use_ladder`` knob).
#
# Aggregation shrinks the live graph 10-100x, but buffers keep their original
# capacity — so every later pass scans, renumbers and sorts e_cap slots that
# are almost all padding.  The ladder re-buckets the coarse graph down to the
# smallest power-of-two tier that fits ``(n_comms, e_valid)`` with slack, so
# pass cost follows |V'|, |E'|.  Power-of-two tiers bound the number of
# distinct compiled shapes at log2(e_cap) per phase (each tier's phases are
# jit-cached by shape, the same reuse trick as the PR 3 ELL runner).
# ---------------------------------------------------------------------------

#: Vertex-capacity floor — below this, shrinking buys dispatch overhead, not
#: scan time, so the ladder stops.
LADDER_MIN_N_CAP = 64

#: Edge-capacity floor (same rationale; keeps the sort non-trivial).
LADDER_MIN_E_CAP = 256

#: Headroom multiplier applied to the live counts before tier rounding, so a
#: tier is never an exact fit (renumber/scatter scratch slots stay cheap).
LADDER_SLACK = 1.25

#: Hysteresis: a pass only re-buckets when the candidate tier is at least
#: this factor below the current capacity.  A < 2x shrink would re-jit every
#: phase to save less than half the scan — not worth the compile.
LADDER_HYSTERESIS = 2


def _pow2_at_least(x: int) -> int:
    """Smallest power of two >= max(x, 1)."""
    return 1 << max(int(x) - 1, 0).bit_length()


def resolve_coarse_capacity(n_comms: int, e_valid: int,
                            n_cap: int, e_cap: int) -> Tuple[int, int]:
    """Ladder tier for the NEXT pass of a coarse graph.

    Returns ``(n_cap_new, e_cap_new)``: each dimension independently drops
    to the smallest power-of-two tier >= ``LADDER_SLACK`` x its live count
    (floored at ``LADDER_MIN_*``), but only when that tier undercuts the
    current capacity by at least ``LADDER_HYSTERESIS`` — otherwise the
    dimension keeps its current capacity (never grows).  ``(n_cap, e_cap)``
    back means "don't re-bucket".
    """
    n_tier = max(_pow2_at_least(int(n_comms * LADDER_SLACK)), LADDER_MIN_N_CAP)
    e_tier = max(_pow2_at_least(int(e_valid * LADDER_SLACK)), LADDER_MIN_E_CAP)
    n_new = n_tier if n_tier * LADDER_HYSTERESIS <= n_cap else n_cap
    e_new = e_tier if e_tier * LADDER_HYSTERESIS <= e_cap else e_cap
    return n_new, e_new


# ---------------------------------------------------------------------------
# Skew-aware coarse re-sharding (the ``LouvainConfig.reshard`` knob).
#
# The sharded pass loop keeps the SEED 1-D owner ranges after every
# aggregation, so community-ownership skew on the coarse graph lands on one
# hot shard and is absorbed by capacity doubling (AggregationOverflow
# retries) instead of being balanced away.  ``plan_reshard`` measures the
# skew host-side (the coarse graph is already on the host for the ladder
# re-bucket) and, when it exceeds ``RESHARD_IMBALANCE_THRESHOLD``, assigns
# contiguous owner ranges by a greedy prefix-sum split that equalizes edge
# slots per shard.  Ranges stay uniform-width on the device: a monotone
# relabel places range ``s`` at block ``[s * v_per, s * v_per + width_s)``,
# so every shard_map body keeps its ``owner = id // v_per`` arithmetic and
# only the id -> block mapping changes.  The ids between ``width_s`` and
# ``v_per`` are GAPS — invalid vertices carrying the sentinel community —
# which is why the pass loop threads a live-vertex mask instead of a dense
# ``idx < n_live`` prefix after a re-shard.
# ---------------------------------------------------------------------------

#: Accepted values of ``LouvainConfig.reshard``.
RESHARD_MODES = ("none", "auto")

#: A coarse pass re-shards only when the worst shard's edge-slot load
#: exceeds this multiple of the mean (max/mean ratio) under the uniform
#: layout — balanced graphs skip the shuffle entirely.
RESHARD_IMBALANCE_THRESHOLD = 1.5

#: Per-shard block-width cap as a multiple of the fair share
#: ceil(n_live / n_shards).  Bounds the replicated-state blowup of the
#: relabelled layout: n_pad_new <= slack * pow2(n_live) instead of one hot
#: range stretching toward n_live.
RESHARD_WIDTH_SLACK = 4


def resolve_reshard(mode: str) -> str:
    """Validate the ``reshard`` knob (``"none"`` | ``"auto"``)."""
    if mode not in RESHARD_MODES:
        raise ValueError(f"reshard must be one of {RESHARD_MODES}; "
                         f"got {mode!r}")
    return mode


class ReshardPlan(NamedTuple):
    """A balanced contiguous owner split of a coarse graph.

    ``bounds`` is ``(n_shards + 1,)``: shard ``s`` owns the dense coarse
    ids ``[bounds[s], bounds[s + 1])``, relabelled onto the uniform block
    ``[s * v_per_shard, ...)``.  ``e_per_shard`` is the power-of-two edge
    tier sized to the worst post-split shard load (with ``LADDER_SLACK``),
    and the ``load_frac_*`` pair records the worst shard's share of all
    edge slots before/after — the ``max_shard_load_frac`` bench columns.
    """

    bounds: np.ndarray
    v_per_shard: int
    e_per_shard: int
    load_frac_before: float
    load_frac_after: float


def owner_load_frac(counts: np.ndarray, v_per: int, n_shards: int) -> float:
    """Worst shard's share of total edge slots under uniform-width ranges.

    ``counts`` holds per-vertex owned edge slots for ids ``[0, n_live)``;
    ownership is ``id // v_per`` (clamped to the last shard).  Returns a
    fraction in ``[1 / n_shards, 1]``; a total of zero reports the
    balanced floor.
    """
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    n_shards = max(int(n_shards), 1)
    if total <= 0 or counts.shape[0] == 0:
        return 1.0 / n_shards
    owner = np.minimum(np.arange(counts.shape[0]) // max(int(v_per), 1),
                       n_shards - 1)
    loads = np.bincount(owner, weights=counts, minlength=n_shards)
    return float(loads.max() / total)


def plan_reshard(counts: np.ndarray, n_shards: int, v_per_uniform: int, *,
                 threshold: float | None = None,
                 width_slack: int | None = None) -> Optional[ReshardPlan]:
    """Plan a skew-aware owner split, or ``None`` when not worth it.

    ``counts`` are per-coarse-vertex edge slots (dense ids, host-side);
    ``v_per_uniform`` is the per-shard width the uniform (non-resharded)
    layout would use for the next pass — the baseline being priced against.
    Returns ``None`` when the mesh is trivial, the measured imbalance
    (max/mean) is at most ``threshold``, or the greedy split cannot beat
    the uniform layout's worst load (e.g. one super-vertex dominates).

    The split is a greedy prefix-sum walk: boundary ``s`` lands where the
    cumulative load first reaches ``s / n_shards`` of the total, clamped so
    no block exceeds ``width_slack`` fair shares (and so the remaining
    shards can still cover the tail).  Deterministic pure numpy — no mesh.
    """
    counts = np.asarray(counts, np.int64)
    n_live = int(counts.shape[0])
    total = int(counts.sum())
    n_shards = int(n_shards)
    if n_shards <= 1 or n_live == 0 or total <= 0:
        return None
    thr = RESHARD_IMBALANCE_THRESHOLD if threshold is None else threshold
    slack = RESHARD_WIDTH_SLACK if width_slack is None else width_slack
    frac_before = owner_load_frac(counts, v_per_uniform, n_shards)
    if frac_before * n_shards <= thr:
        return None

    v_cap = _pow2_at_least(-(-n_live // n_shards) * max(int(slack), 1))
    cum = np.cumsum(counts)
    bounds = np.zeros((n_shards + 1,), np.int64)
    bounds[n_shards] = n_live
    for s in range(1, n_shards):
        prev = int(bounds[s - 1])
        target = total * s / n_shards
        b = int(np.searchsorted(cum, target, side="left")) + 1
        lo = max(prev, n_live - (n_shards - s) * v_cap)
        hi = min(prev + v_cap, n_live)
        bounds[s] = min(max(b, lo), hi)

    widths = np.diff(bounds)
    v_per = max(_pow2_at_least(int(widths.max())),
                _pow2_at_least(-(-LADDER_MIN_N_CAP // n_shards)))
    csum = np.concatenate([np.zeros((1,), np.int64), cum])
    loads = csum[bounds[1:]] - csum[bounds[:-1]]
    frac_after = float(loads.max() / total)
    if frac_after >= frac_before:
        return None
    e_floor = -(-LADDER_MIN_E_CAP // n_shards)
    e_per = _pow2_at_least(max(int(loads.max() * LADDER_SLACK), e_floor))
    return ReshardPlan(bounds, int(v_per), int(e_per),
                       frac_before, frac_after)


# ---------------------------------------------------------------------------
# Multi-tenant fleet admission policy (the ``core.fleet`` serving layer).
#
# The fleet combines the two scaling axes: every tenant graph is sharded
# across the mesh (1-D vertex partition, like ``core.distributed``) AND
# tenants are batched per dispatch (vmap over a tenant lane, like
# ``core.multistream``).  A vmapped program needs ONE compiled shape per
# bucket, so tenants are admitted into power-of-two capacity envelopes
# ``(v_per_shard, e_per_shard, b_cap)`` — tenants sharing an envelope share
# a bucket (one ``jit(vmap(step))`` program); a whale tenant outgrowing its
# envelope MIGRATES to a bigger bucket (one recompile in the destination
# bucket) instead of forcing a fleet-wide recompile.
# ---------------------------------------------------------------------------

#: Headroom multiplier on the worst shard's owned edge slots at admission —
#: mirrors the sharded streaming driver's default 25% slack, so a tenant's
#: first growth event needs genuinely new volume, not admission jitter.
FLEET_E_SLACK = 1.25

#: Per-shard vertex-block floor (tiny tenants keep a usable block).
FLEET_MIN_V_PER = 8

#: Per-shard edge-slot floor (keeps the per-shard sort non-trivial).
FLEET_MIN_E_PER = 32

#: Migration doubles capacity at least this factor — the same geometric
#: growth the single-fleet ``multistream`` regrow and the sharded streaming
#: ``_grow_to`` use, so a whale cannot thrash the bucket ladder.
FLEET_GROW_FACTOR = 2


class FleetEnvelope(NamedTuple):
    """Power-of-two per-tenant capacity envelope of a fleet bucket.

    Tenants with equal envelopes ride one compiled ``jit(vmap(...))``
    program; the implied global capacities on an ``n_shards`` mesh are
    ``v_cap = n_shards * v_per_shard`` (the padded vertex count / sentinel)
    and ``e_cap = n_shards * e_per_shard`` directed edge slots.
    """

    v_per_shard: int
    e_per_shard: int
    b_cap: int           # per-step edge-batch capacity (stacked per lane)

    def v_cap(self, n_shards: int) -> int:
        return self.v_per_shard * n_shards

    def e_cap(self, n_shards: int) -> int:
        return self.e_per_shard * n_shards


def fleet_v_per_shard(n_cap: int, n_shards: int) -> int:
    """Power-of-two per-shard vertex block covering ``n_cap`` vertices."""
    return max(_pow2_at_least(-(-int(n_cap) // max(int(n_shards), 1))),
               FLEET_MIN_V_PER)


def fleet_envelope(n_cap: int, owned_max: int, b_cap: int,
                   n_shards: int) -> FleetEnvelope:
    """Admission envelope for one tenant.

    ``owned_max`` is the worst shard's owned live directed slots under the
    ``fleet_v_per_shard`` owner map (the caller measures it host-side with
    one bincount).  The edge tier reserves ``FLEET_E_SLACK`` headroom plus
    room for one worst-case batch (a batch adds at most ``2 * b_cap``
    directed slots to a single shard), then rounds up to a power of two —
    so organically-near tenants coalesce into the same bucket.
    """
    b_cap = max(_pow2_at_least(int(b_cap)), 1)
    e_need = int(int(owned_max) * FLEET_E_SLACK) + 2 * b_cap
    e_per = max(_pow2_at_least(e_need), FLEET_MIN_E_PER)
    return FleetEnvelope(fleet_v_per_shard(n_cap, n_shards), e_per, b_cap)


def plan_fleet(sizings, n_shards: int) -> Dict[FleetEnvelope, list]:
    """Group tenants into capacity buckets — the fleet admission policy.

    ``sizings`` is a sequence of ``(n_cap, owned_max, b_cap)`` tuples (one
    per tenant, in admission order); returns ``{envelope: [tenant_index]}``
    with deterministic per-envelope ordering.  Pure policy: the router owns
    the arrays, this owns the numbers.
    """
    buckets: Dict[FleetEnvelope, list] = {}
    for i, (n_cap, owned_max, b_cap) in enumerate(sizings):
        env = fleet_envelope(n_cap, owned_max, b_cap, n_shards)
        buckets.setdefault(env, []).append(i)
    return buckets


def migrate_envelope(env: FleetEnvelope, e_need: int) -> FleetEnvelope:
    """The envelope a whale tenant migrates into after an edge overflow.

    ``e_need`` is the measured worst-shard slot requirement of the
    overflowing step; growth is geometric (``FLEET_GROW_FACTOR``) and
    power-of-two quantized, mirroring the sharded streaming driver's
    ``_grow_to(max(2 * e_per, e_max))``.
    """
    e_per = _pow2_at_least(max(FLEET_GROW_FACTOR * env.e_per_shard,
                               int(e_need)))
    return env._replace(e_per_shard=e_per)


def resolve_scan_backend(backend: str, *, use_ell_kernel: bool = False,
                         frontier_frac: float | None = None) -> str:
    """Map the ``scan_backend`` knob to a concrete scanner for ONE pass.

    ``frontier_frac`` is the seed-frontier fraction |F|/n of the pass when a
    delta-screened / warm frontier is active, ``None`` for a cold full-
    frontier pass.  Returns one of ``"full" | "compact" | "ell" |
    "ell_fused"``:

      * explicit values pass through (``"compact"`` still only engages when
        a frontier is active — a cold pass re-scans everything anyway);
      * ``"auto"`` + ELL family -> the fused kernel (it replaces the
        scan-then-apply round-trip, bit-identically);
      * ``"auto"`` + active small frontier -> ``"compact"``;
      * otherwise the full sort-reduce scan.
    """
    if backend not in SCAN_BACKENDS:
        raise ValueError(f"scan_backend must be one of {SCAN_BACKENDS}; "
                         f"got {backend!r}")
    if use_ell_kernel or backend in ("ell", "ell_fused"):
        if backend == "compact":
            raise ValueError(
                "scan_backend='compact' contradicts use_ell_kernel=True — "
                "the compacted scanner is a sort-reduce backend; use "
                "scan_backend='auto'/'ell_fused' for the ELL family or "
                "drop use_ell_kernel")
        if backend in ("auto", "ell_fused"):
            return "ell_fused"
        return "ell"
    if backend == "compact":
        return "compact" if frontier_frac is not None else "full"
    if backend == "auto":
        if (frontier_frac is not None
                and frontier_frac <= AUTO_COMPACT_MAX_FRONTIER_FRAC):
            return "compact"
        return "full"
    return "full"

# name -> (|V|, |E| directed slots, phase)
LOUVAIN_SHAPES: Dict[str, Tuple[int, int, str]] = {
    "web_3.8B_move": (50_636_154, 3_800_000_000, "move"),
    "web_3.8B_aggregate": (50_636_154, 3_800_000_000, "aggregate"),
    "road_108M_move": (50_912_018, 108_109_320, "move"),
    "road_108M_aggregate": (50_912_018, 108_109_320, "aggregate"),
}


def _spec_for(mesh: Mesh, n: int, e: int) -> ShardedGraphSpec:
    n_shards = int(mesh.devices.size)
    v_per = -(-n // n_shards)
    e_per = -(-e // n_shards)
    return ShardedGraphSpec(n_shards, v_per, e_per, v_per * n_shards)


def _move_round_delta(axes, spec: ShardedGraphSpec, move_cap_frac: int,
                      src_l, dst_l, w_l, comm, sigma, comm_sizes, k, m):
    """One local-move round with DELTA-ENCODED state exchange.

    The baseline round all_gathers the full membership C (n_pad int32),
    psums the dense Σ (n_pad f32) and psums the dense community sizes —
    3 x O(n_pad) collectives per round.  Here only the (vertex, new_comm)
    pairs of vertices that actually MOVED are gathered (static cap =
    v_per / move_cap_frac per shard); every shard then reconstructs Σ,
    community sizes and the frontier locally from the replicated k and the
    gathered deltas — redundant O(moved) recompute in place of O(n_pad)
    collectives.  Returns (comm', sigma', sizes', frontier_l, dq, overflow).
    """
    v_per, sent = spec.v_per_shard, spec.sentinel
    frontier_l = jnp.ones((v_per,), bool)
    best_c, best_dq, v0 = _best_moves_shard(
        axes, spec, src_l, dst_l, w_l, comm, sigma, k, frontier_l, m)
    own_comm_l = jax.lax.dynamic_slice_in_dim(comm, v0, v_per)
    k_l = jax.lax.dynamic_slice_in_dim(k, v0, v_per)
    gidx = v0 + jnp.arange(v_per)

    # round-0 gate + singleton guard from the REPLICATED sizes input.
    gate = round_gate(gidx, jnp.int32(0), 2)
    own_single = comm_sizes[own_comm_l] == 1
    tgt_single = comm_sizes[jnp.minimum(best_c, sent)] == 1
    swap_blocked = own_single & tgt_single & (best_c > own_comm_l)
    do_move = ((best_dq > 0.0) & (best_c != own_comm_l) & (best_c < sent)
               & gate & ~swap_blocked)
    dq_round = jax.lax.psum(jnp.sum(jnp.where(do_move, best_dq, 0.0)), axes)

    # --- delta encoding: (global vertex id, new community) of movers -------
    cap = max(v_per // move_cap_frac, 1)
    rank = jnp.cumsum(do_move.astype(I32)) - 1
    keep = do_move & (rank < cap)
    slot = jnp.where(keep, rank, cap)
    idx_buf = jnp.full((cap + 1,), sent, I32).at[slot].set(
        jnp.where(keep, gidx, sent))[:cap]
    val_buf = jnp.full((cap + 1,), sent, I32).at[slot].set(
        jnp.where(keep, best_c, sent))[:cap]
    overflow = jax.lax.pmax(jnp.sum(do_move.astype(I32)) - cap, axes)

    g_idx = jax.lax.all_gather(idx_buf, axes, tiled=True)   # (S*cap,)
    g_val = jax.lax.all_gather(val_buf, axes, tiled=True)

    # --- replicated reconstruction from the deltas --------------------------
    g_live = g_idx < sent
    comm_new = comm.at[jnp.minimum(g_idx, sent)].set(
        jnp.where(g_live, g_val, comm[jnp.minimum(g_idx, sent)]))
    k_moved = jnp.where(g_live, k[jnp.minimum(g_idx, sent)], 0.0)
    old_c = comm[jnp.minimum(g_idx, sent)]
    sigma_new = (sigma
                 .at[jnp.where(g_live, g_val, sent)].add(k_moved)
                 .at[jnp.where(g_live, old_c, sent)].add(-k_moved))
    ones_m = jnp.where(g_live, 1, 0)
    sizes_new = (comm_sizes
                 .at[jnp.where(g_live, g_val, sent)].add(ones_m)
                 .at[jnp.where(g_live, old_c, sent)].add(-ones_m))

    # frontier: neighbors of movers, from the reconstructed moved mask.
    moved_mask = jnp.zeros((sent + 1,), bool).at[
        jnp.minimum(g_idx, sent)].set(g_live)
    src_loc = jnp.where(src_l >= sent, v_per, src_l - v0)
    marked = jax.ops.segment_max(
        moved_mask[dst_l].astype(I32), src_loc, num_segments=v_per + 1)[:v_per]
    frontier_new = (marked > 0) & (gidx < spec.n_pad)
    return comm_new, sigma_new, sizes_new, frontier_new, dq_round, overflow


def _aggregate_a2a_body(axes, spec: ShardedGraphSpec, cap_factor: int,
                        src_l, dst_l, w_l, comm):
    """Owner-routed aggregation: local sort-reduce partials, all_to_all the
    partial coarse edges to the shard owning their source community, local
    re-reduce.  Per-chip traffic = 3 arrays x P x cap x 4B ~ cap_factor x e_l
    x 12B, vs the gather baseline's n_shards x e_l x 12B."""
    v_per, sent = spec.v_per_shard, spec.sentinel
    n_shards = spec.n_shards
    e_l = src_l.shape[0]
    ci = comm[src_l]
    cj = comm[dst_l]

    # local partial reduce (identical to the baseline first stage)
    order = jnp.lexsort((cj, ci))
    s_ci, s_cj, s_w = ci[order], cj[order], w_l[order]
    prev_i = jnp.concatenate([jnp.full((1,), -1, I32), s_ci[:-1]])
    prev_j = jnp.concatenate([jnp.full((1,), -1, I32), s_cj[:-1]])
    new_group = (s_ci != prev_i) | (s_cj != prev_j)
    gid = jnp.cumsum(new_group.astype(I32)) - 1
    gw = jax.ops.segment_sum(s_w, gid, num_segments=e_l)[gid]
    live = new_group & (s_ci != sent)

    # route each live partial to owner shard = ci // v_per, with a static
    # per-destination capacity (cap_factor x fair share).
    cap = cap_factor * (e_l // n_shards)
    dest = jnp.where(live, s_ci // v_per, n_shards)
    d_order = jnp.argsort(dest)
    d_sorted = dest[d_order]
    ranks = jnp.arange(e_l) - jnp.searchsorted(d_sorted, d_sorted,
                                               side="left")
    keep = (d_sorted < n_shards) & (ranks < cap)
    slot = jnp.where(keep, d_sorted * cap + ranks, n_shards * cap)

    def scatter(vals, fill):
        buf = jnp.full((n_shards * cap + 1,), fill, vals.dtype)
        return buf.at[slot].set(jnp.where(keep, vals[d_order], fill))[:-1]

    b_ci = scatter(s_ci, jnp.int32(sent)).reshape(n_shards, cap)
    b_cj = scatter(s_cj, jnp.int32(sent)).reshape(n_shards, cap)
    b_w = scatter(gw, jnp.float32(0)).reshape(n_shards, cap)

    r_ci = jax.lax.all_to_all(b_ci, axes, 0, 0, tiled=True).reshape(-1)
    r_cj = jax.lax.all_to_all(b_cj, axes, 0, 0, tiled=True).reshape(-1)
    r_w = jax.lax.all_to_all(b_w, axes, 0, 0, tiled=True).reshape(-1)

    # local re-reduce of everything this shard owns
    order2 = jnp.lexsort((r_cj, r_ci))
    t_ci, t_cj, t_w = r_ci[order2], r_cj[order2], r_w[order2]
    prev_i = jnp.concatenate([jnp.full((1,), -1, I32), t_ci[:-1]])
    prev_j = jnp.concatenate([jnp.full((1,), -1, I32), t_cj[:-1]])
    ng2 = (t_ci != prev_i) | (t_cj != prev_j)
    gid2 = jnp.cumsum(ng2.astype(I32)) - 1
    gw2 = jax.ops.segment_sum(t_w, gid2, num_segments=t_w.shape[0])[gid2]
    live2 = ng2 & (t_ci != sent)
    n_out = t_w.shape[0]
    pos2 = jnp.where(live2, gid2, n_out)
    o_ci = jnp.full((n_out + 1,), sent, I32).at[pos2].set(t_ci)[:n_out]
    o_cj = jnp.full((n_out + 1,), sent, I32).at[pos2].set(t_cj)[:n_out]
    o_w = jnp.zeros((n_out + 1,), F32).at[pos2].set(
        jnp.where(live2, gw2, 0.0))[:n_out]
    e_valid = jax.lax.psum(jnp.sum(jnp.where(live2, 1, 0)), axes)
    # capacity diagnostic: partials dropped by the per-destination cap
    dropped = jax.lax.psum(
        jnp.sum(jnp.where(live, 1, 0)) - jnp.sum(jnp.where(keep, 1, 0)),
        axes)
    return o_ci, o_cj, o_w, e_valid, dropped


def _aggregate_gather_body(axes, spec: ShardedGraphSpec,
                           src_l, dst_l, w_l, comm):
    """Baseline (core.distributed.make_distributed_aggregate inner body)."""
    from repro.core import distributed as dmod
    # Reuse the library body by constructing it the same way.
    v_per, sent = spec.v_per_shard, spec.sentinel
    e_l = src_l.shape[0]
    ci = comm[src_l]
    cj = comm[dst_l]
    order = jnp.lexsort((cj, ci))
    s_ci, s_cj, s_w = ci[order], cj[order], w_l[order]
    prev_i = jnp.concatenate([jnp.full((1,), -1, I32), s_ci[:-1]])
    prev_j = jnp.concatenate([jnp.full((1,), -1, I32), s_cj[:-1]])
    new_group = (s_ci != prev_i) | (s_cj != prev_j)
    gidl = jnp.cumsum(new_group.astype(I32)) - 1
    gw = jax.ops.segment_sum(s_w, gidl, num_segments=e_l)[gidl]
    live = new_group & (s_ci != sent)
    pos = jnp.where(live, gidl, e_l)
    p_ci = jnp.full((e_l + 1,), sent, I32).at[pos].set(s_ci)[:e_l]
    p_cj = jnp.full((e_l + 1,), sent, I32).at[pos].set(s_cj)[:e_l]
    p_w = jnp.zeros((e_l + 1,), F32).at[pos].set(gw)[:e_l]

    g_ci = jax.lax.all_gather(p_ci, axes, tiled=True)
    g_cj = jax.lax.all_gather(p_cj, axes, tiled=True)
    g_w = jax.lax.all_gather(p_w, axes, tiled=True)

    shard_ix = _shard_index(axes)
    v0 = shard_ix * v_per
    mine = (g_ci >= v0) & (g_ci < v0 + v_per)
    m_ci = jnp.where(mine, g_ci, sent)
    m_cj = jnp.where(mine, g_cj, sent)
    m_w = jnp.where(mine, g_w, 0.0)
    order2 = jnp.lexsort((m_cj, m_ci))
    t_ci, t_cj, t_w = m_ci[order2], m_cj[order2], m_w[order2]
    prev_i = jnp.concatenate([jnp.full((1,), -1, I32), t_ci[:-1]])
    prev_j = jnp.concatenate([jnp.full((1,), -1, I32), t_cj[:-1]])
    ng2 = (t_ci != prev_i) | (t_cj != prev_j)
    gid2 = jnp.cumsum(ng2.astype(I32)) - 1
    gw2 = jax.ops.segment_sum(t_w, gid2, num_segments=t_w.shape[0])[gid2]
    live2 = ng2 & (t_ci != sent)
    pos2 = jnp.where(live2, gid2, e_l)
    o_ci = jnp.full((e_l + 1,), sent, I32).at[pos2].set(
        jnp.where(live2, t_ci, sent))[:e_l]
    o_cj = jnp.full((e_l + 1,), sent, I32).at[pos2].set(
        jnp.where(live2, t_cj, sent))[:e_l]
    o_w = jnp.zeros((e_l + 1,), F32).at[pos2].set(
        jnp.where(live2, gw2, 0.0))[:e_l]
    e_valid = jax.lax.psum(jnp.sum(jnp.where(live2, 1, 0)), axes)
    # overflow diagnostic (see core.distributed.make_distributed_aggregate)
    owned_max = jax.lax.pmax(jnp.sum(jnp.where(live2, 1, 0)), axes)
    return o_ci, o_cj, o_w, e_valid, owned_max


@dataclasses.dataclass(frozen=True)
class LouvainArch:
    """Dry-run protocol wrapper for the paper's own distributed phases."""

    arch_id: str = "louvain"
    family: str = "louvain"
    shapes: Tuple[str, ...] = tuple(LOUVAIN_SHAPES)
    skip_notes: Dict[str, str] = dataclasses.field(default_factory=dict)

    def input_specs(self, shape: str, smoke: bool = False) -> dict:
        n, e, phase = LOUVAIN_SHAPES[shape]
        if smoke:
            n, e = 4096, 32768
        S = jax.ShapeDtypeStruct
        # edge arrays are padded to shard-divisible lengths at build time
        return {"src": S((e,), I32), "dst": S((e,), I32),
                "w": S((e,), F32), "comm": S((n + 1,), I32),
                "sigma": S((n + 1,), F32), "k": S((n + 1,), F32),
                "m": S((), F32)}

    def build_step(self, shape: str, mesh: Mesh, smoke: bool = False,
                   variant: Tuple[str, ...] = ()):
        n, e, phase = LOUVAIN_SHAPES[shape]
        if smoke:
            n, e = 4096, 32768
        spec = _spec_for(mesh, n, e)
        axes = tuple(mesh.axis_names)
        n_pad, e_pad = spec.n_pad, spec.e_per_shard * spec.n_shards
        S = jax.ShapeDtypeStruct
        arg_specs = ({"src": S((e_pad,), I32), "dst": S((e_pad,), I32),
                      "w": S((e_pad,), F32), "comm": S((n_pad + 1,), I32),
                      "sigma": S((n_pad + 1,), F32),
                      "k": S((n_pad + 1,), F32), "m": S((), F32)},)
        edge = P(axes)
        rep = P()
        shardings = ({"src": NamedSharding(mesh, edge),
                      "dst": NamedSharding(mesh, edge),
                      "w": NamedSharding(mesh, edge),
                      "comm": NamedSharding(mesh, rep),
                      "sigma": NamedSharding(mesh, rep),
                      "k": NamedSharding(mesh, rep),
                      "m": NamedSharding(mesh, rep)},)

        if phase == "move" and "delta_c" in variant:
            arg_specs[0]["comm_sizes"] = S((n_pad + 1,), I32)
            shardings[0]["comm_sizes"] = NamedSharding(mesh, rep)
            body = functools.partial(_move_round_delta, axes, spec, 4)
            fn_s = jax.shard_map(
                body, mesh=mesh,
                in_specs=(edge, edge, edge, rep, rep, rep, rep, rep),
                out_specs=(rep, rep, rep, edge, rep, rep),
                check_vma=False)

            def step(batch):
                return fn_s(batch["src"], batch["dst"], batch["w"],
                            batch["comm"], batch["sigma"],
                            batch["comm_sizes"], batch["k"], batch["m"])
            return step, arg_specs, shardings

        if phase == "move":
            def round_shard(src_l, dst_l, w_l, comm, sigma, k, m):
                frontier = jnp.ones((spec.v_per_shard,), bool)
                return _round_body(axes, spec, src_l, dst_l, w_l, comm,
                                   sigma, k, frontier, jnp.int32(0), 2, m)

            fn_s = jax.shard_map(
                round_shard, mesh=mesh,
                in_specs=(edge, edge, edge, rep, rep, rep, rep),
                out_specs=(rep, rep, edge, rep), check_vma=False)
        else:
            if "a2a" in variant:
                body = functools.partial(_aggregate_a2a_body, axes, spec, 4)
            else:
                body = functools.partial(_aggregate_gather_body, axes, spec)
            outs = (edge, edge, edge, rep, rep)

            fn_s = jax.shard_map(body, mesh=mesh,
                                 in_specs=(edge, edge, edge, rep),
                                 out_specs=outs,
                                 check_vma=False)

        if phase == "move":
            def step(batch):
                return fn_s(batch["src"], batch["dst"], batch["w"],
                            batch["comm"], batch["sigma"], batch["k"],
                            batch["m"])
        else:
            def step(batch):
                return fn_s(batch["src"], batch["dst"], batch["w"],
                            batch["comm"])
        return step, arg_specs, shardings


ARCH = LouvainArch()
