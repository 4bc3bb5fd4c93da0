"""Multi-pod distributed GVE-Louvain via shard_map + jax.lax collectives.

The paper is single-node shared-memory; this layer extends it along the lines
of the distributed implementations it benchmarks (Vite / Ghosh et al.):

  - 1-D **vertex partition**: every vertex's full adjacency lives on exactly
    one shard.  Louvain's parallelism is vertex-wise, so the partition flattens
    ALL mesh axes (pod x data x model) into one vertex axis — each of the 512
    chips of the production mesh owns |V|/512 vertices.
  - **Replicated community state**: C, Sigma, K (O(|V|) each) are replicated;
    per-round updates travel as one `all_gather` (the owned C slice + moved
    flags) and one `psum` (Sigma deltas) — the same ghost-exchange pattern as
    Vite, expressed as XLA collectives.  That is the "gather" communication
    backend; the "delta" backend (``DeltaShardedScanner``) replaces the dense
    exchange with compacted, bit-packed owned CHANGES (moved labels + top-k
    Sigma deltas, with a measured-overflow fallback) — replication still
    forces an all_gather, but of O(moved) lanes instead of O(n_pad) arrays.
    Policy and caps live in ``repro.configs.louvain_arch``
    (``resolve_comm_backend``); bytes accounting in ``repro.core.comm``.
  - **Distributed aggregation**: local sort-reduce partially deduplicates each
    shard's relabeled edges, an `all_gather` shares the partials, and each
    shard re-reduces the rows it owns in the coarse partition.  (The gather is
    the faithful baseline; the all_to_all variant lives in
    ``repro.configs.louvain_arch`` as a dry-run cell.)

Everything here is shape-static and lowers AOT on the production meshes — see
launch/dryrun.py.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.comm import (CommPlan, boundary_mask, comm_plan,
                             compact_movers, label_bits, pack_bits,
                             packed_lanes, phase_bytes, size_delta_width,
                             topk_touched_deltas, unpack_bits)
from repro.core.engine import (ConstrainedScanner, EngineConfig, MoveEngine,
                               MoveState, mask_cross_outer_slots,
                               sanitize_outer)
from repro.core.graph import CSRGraph
from repro.core.modularity import delta_modularity


class AggregationOverflow(RuntimeError):
    """A shard owns more coarse edges than ``e_per_shard`` (community-
    ownership skew).  Carries ``owned_max`` so streaming callers can
    re-bucket into grown capacity and retry instead of dying."""

    def __init__(self, owned_max: int, e_per_shard: int):
        super().__init__(
            f"aggregation overflow: a shard owns {owned_max} coarse edges "
            f"> capacity {e_per_shard}; re-partition with more headroom "
            "(community skew)")
        self.owned_max = owned_max


class ShardedGraphSpec(NamedTuple):
    """Static layout facts for a vertex-partitioned edge list."""

    n_shards: int
    v_per_shard: int     # owned vertices per shard
    e_per_shard: int     # padded edge slots per shard
    n_pad: int           # n_shards * v_per_shard  (global padded vertex count)

    @property
    def sentinel(self) -> int:
        return self.n_pad


def bucket_slots_host(
    src: np.ndarray, dst: np.ndarray, w: np.ndarray, spec: ShardedGraphSpec
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Host-side owner bucketing of live directed slots into the padded
    per-shard edge layout described by ``spec`` (the re-bucketing primitive
    shared by initial partitioning and capacity growth)."""
    n_shards, v_per, e_per = spec.n_shards, spec.v_per_shard, spec.e_per_shard
    n_pad = spec.n_pad
    owner = src // v_per
    counts = np.bincount(owner, minlength=n_shards)
    if counts.size > n_shards or (counts.max(initial=0) > e_per):
        raise ValueError(
            f"slots do not fit the shard layout: max owned "
            f"{int(counts.max(initial=0))} > e_per_shard={e_per}")
    s_out = np.full((n_shards, e_per), n_pad, np.int32)
    d_out = np.full((n_shards, e_per), n_pad, np.int32)
    w_out = np.zeros((n_shards, e_per), np.float32)
    order = np.argsort(owner, kind="stable")
    src, dst, w, owner = src[order], dst[order], w[order], owner[order]
    starts = np.searchsorted(owner, np.arange(n_shards))
    ends = np.searchsorted(owner, np.arange(n_shards), side="right")
    for s in range(n_shards):
        cnt = ends[s] - starts[s]
        s_out[s, :cnt] = src[starts[s]:ends[s]]
        d_out[s, :cnt] = dst[starts[s]:ends[s]]
        w_out[s, :cnt] = w[starts[s]:ends[s]]
    return (jnp.asarray(s_out.reshape(-1)), jnp.asarray(d_out.reshape(-1)),
            jnp.asarray(w_out.reshape(-1)))


def partition_graph_host(
    graph: CSRGraph, n_shards: int, *,
    n_target: int | None = None, e_per_shard: int | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, ShardedGraphSpec]:
    """Host-side 1-D vertex partition -> globally laid-out padded edge arrays.

    Shard s owns vertices [s*v, (s+1)*v) and the slice [s*E_l, (s+1)*E_l) of
    each edge array.  Padding slots carry src = dst = sentinel, w = 0.

    ``n_target``/``e_per_shard`` reserve headroom beyond the current live
    graph (streaming callers partition for ``graph.n_cap`` vertices and an
    expected insert volume so the layout survives edge batches in capacity).
    """
    n = int(n_target if n_target is not None else graph.n_valid)
    v_per = -(-n // n_shards)
    n_pad = v_per * n_shards
    src = np.asarray(graph.src)
    dst = np.asarray(graph.indices)
    w = np.asarray(graph.weights)
    live = src < graph.n_cap
    src, dst, w = src[live], dst[live], w[live]

    owner = src // v_per
    e_per = max(int(np.bincount(owner, minlength=n_shards).max()), 1,
                int(e_per_shard or 0))
    spec = ShardedGraphSpec(n_shards, v_per, e_per, n_pad)
    src_g, dst_g, w_g = bucket_slots_host(src, dst, w, spec)
    return src_g, dst_g, w_g, spec


# ---------------------------------------------------------------------------
# shard_map bodies.  ``axes`` is the tuple of mesh axis names the vertex
# partition flattens over, e.g. ("data", "model") or ("pod", "data", "model").
# ---------------------------------------------------------------------------

def _shard_index(axes):
    shard_ix = jax.lax.axis_index(axes[0])
    for ax in axes[1:]:
        shard_ix = shard_ix * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return shard_ix


def _best_moves_shard(axes, spec, src_l, dst_l, w_l, comm, sigma, k,
                      frontier_l, m):
    """Per-shard best (community, dQ) for owned vertices — the sort-reduce
    scanCommunities.  Returns (best_c (v_per,), best_dq (v_per,), v0)."""
    v_per, sent = spec.v_per_shard, spec.sentinel
    v0 = _shard_index(axes) * v_per

    # Local segment space: owned vertices -> [0, v_per), everything else -> v_per.
    src_loc = jnp.where(src_l >= sent, v_per, src_l - v0)
    cdst = comm[dst_l]

    own_comm_l = jax.lax.dynamic_slice_in_dim(comm, v0, v_per)  # (v_per,)
    c_own_e = comm[src_l]                                        # per-edge own community
    own_edge = (cdst == c_own_e) & (dst_l != src_l)
    k_to_own = jax.ops.segment_sum(
        jnp.where(own_edge, w_l, 0.0), src_loc, num_segments=v_per + 1)

    order = jnp.lexsort((cdst, src_loc))
    s_src = src_loc[order]
    s_c = cdst[order]
    s_w = jnp.where(dst_l[order] == src_l[order], 0.0, w_l[order])
    prev_src = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_src[:-1]])
    prev_c = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_c[:-1]])
    new_group = (s_src != prev_src) | (s_c != prev_c)
    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    k_i_to_c = jax.ops.segment_sum(s_w, gid, num_segments=s_w.shape[0])[gid]

    k_l = jax.lax.dynamic_slice_in_dim(k, v0, v_per)
    sig_own_l = sigma[own_comm_l]
    valid_row = s_src < v_per
    dq = delta_modularity(
        k_i_to_c,
        jnp.where(valid_row, k_to_own[s_src], 0.0),
        jnp.where(valid_row, k_l[jnp.minimum(s_src, v_per - 1)], 0.0),
        sigma[jnp.minimum(s_c, sent)],
        jnp.where(valid_row, sig_own_l[jnp.minimum(s_src, v_per - 1)], 0.0),
        m,
    )
    c_own_sorted = comm[src_l[order]]
    valid = valid_row & (s_c != c_own_sorted) & (s_c < sent) & frontier_l[
        jnp.minimum(s_src, v_per - 1)]
    dq = jnp.where(valid, dq, -jnp.inf)
    best_dq = jax.ops.segment_max(dq, s_src, num_segments=v_per + 1)[:v_per]
    best_dq = jnp.where(jnp.isfinite(best_dq), best_dq, -jnp.inf)
    is_best = valid & (dq == jnp.pad(best_dq, (0, 1), constant_values=-jnp.inf)[
        jnp.minimum(s_src, v_per)])
    best_c = jax.ops.segment_min(
        jnp.where(is_best, s_c, sent), s_src, num_segments=v_per + 1)[:v_per]
    best_c = jnp.minimum(best_c, sent)
    return best_c, best_dq, v0


class ShardedScanner:
    """Engine backend: per-shard sort-reduce scan + collective topology.

    Lives inside ``shard_map``: local layout is the shard's ``v_per_shard``
    owned vertices; community state (C, Sigma) is replicated ``(n_pad + 1,)``
    and updated with one ``all_gather`` (owned C slices + moved flags) and
    one ``psum`` (Sigma deltas) per round — the Vite-style ghost exchange,
    expressed as XLA collectives.  See ``repro.core.engine.MoveEngine`` for
    the protocol.
    """

    def __init__(self, axes, spec: ShardedGraphSpec, src_l, dst_l, w_l,
                 k, m):
        v_per, sent = spec.v_per_shard, spec.sentinel
        self.axes, self.spec = axes, spec
        self.src_l, self.dst_l, self.w_l = src_l, dst_l, w_l
        self.k, self.m = k, m
        self.sentinel = sent
        self.v0 = _shard_index(axes) * v_per
        self.local_ids = self.v0 + jnp.arange(v_per)
        self.k_local = jax.lax.dynamic_slice_in_dim(k, self.v0, v_per)
        self.src_loc = jnp.where(src_l >= sent, v_per, src_l - self.v0)
        self.move_valid = None           # invalid slots carry comm == sent
        self.frontier_valid = self.local_ids < spec.n_pad

    def scan(self, comm, sigma, frontier):
        best_c, best_dq, _ = _best_moves_shard(
            self.axes, self.spec, self.src_l, self.dst_l, self.w_l,
            comm, sigma, self.k, frontier, self.m)
        return best_c, best_dq

    def comm_local(self, comm):
        return jax.lax.dynamic_slice_in_dim(comm, self.v0,
                                            self.spec.v_per_shard)

    def count_ones(self, comm_l):
        return jnp.where(comm_l < self.sentinel, 1, 0)  # ghosts excluded

    def psum(self, x):
        return jax.lax.psum(x, self.axes)

    def combine_sigma(self, sigma, add, sub):
        return sigma + self.psum(add - sub)

    def gather_comm(self, comm_l):
        full = jax.lax.all_gather(comm_l, self.axes, tiled=True)
        return jnp.concatenate(
            [full, jnp.asarray([self.sentinel], jnp.int32)])

    def gather_mask(self, mask_l):
        full = jax.lax.all_gather(mask_l, self.axes, tiled=True)
        return jnp.concatenate([full, jnp.zeros((1,), bool)])

    def mark_neighbors(self, moved):
        v_per = self.spec.v_per_shard
        marked = jax.ops.segment_max(
            moved[self.dst_l].astype(jnp.int32), self.src_loc,
            num_segments=v_per + 1)[:v_per]
        return marked > 0


class DeltaShardedScanner(ShardedScanner):
    """Communication-lean engine backend: same scan, movers-only exchange.

    Per round the gather backend ships two dense O(n_pad) psums (Sigma,
    community sizes) plus the owned membership slice and moved mask.  This
    backend ships ONLY the movers — each as a (local index, new label)
    pair bit-packed to the minimum lane width for the layout
    (``repro.core.comm.pack_bits``) — and reconstructs every other array
    locally, because each receiver already replicates the state the deltas
    derive from:

      * Sigma updates: a mover shifts exactly its vertex weight ``K_i``
        from its old to its new community, and both ``k`` and the previous
        membership are replicated, so each shard rebuilds every shard's
        dense (add - sub) from the gathered movers — zero Sigma bytes;
      * community sizes: +1 / -1 at the movers' new / old labels,
        maintained incrementally across rounds (integer-exact in any
        order), seeded once per phase from ``community_sizes``;
      * the moved mask: a move always changes the label, so it is the
        compare ``comm' != comm``.

    The movers ride ONE fused ``all_gather`` per round — the mover count,
    the local dq, and the packed lanes concatenated into a single uint32
    wire word per shard — because collective rendezvous, not payload
    bytes, dominates small-round latency (the gather backend pays five
    collectives per round; this backend pays one).  The gathered counts
    are replicated by construction, so every shard decides the overflow
    branch locally: a round whose movers exceed the static cap runs the
    dense exchange inside ``lax.cond`` — the cap bounds compile shapes,
    never correctness.  Reconstruction mirrors the gather backend's
    arithmetic per shard (identical segment-sum orders, then one dense
    apply), so on one shard the result matches the default path bit for
    bit and every committed sharded golden is reproduced (pinned in
    tests/test_engine_equiv.py).  Cap policy:
    ``repro.configs.louvain_arch.delta_move_cap``.
    """

    def __init__(self, axes, spec: ShardedGraphSpec, src_l, dst_l, w_l,
                 k, m):
        super().__init__(axes, spec, src_l, dst_l, w_l, k, m)
        from repro.configs.louvain_arch import delta_move_cap
        self.move_cap = delta_move_cap(spec.v_per_shard)
        self.idx_width = label_bits(spec.v_per_shard + 1)
        self.lab_width = label_bits(spec.n_pad + 1)
        # Movers ship as ONE fused (index, label) pair per entry when the
        # pair fits an int32 — one pack/unpack instead of two.  Layouts too
        # wide for that (v_per * n_pad ~ 2^31) fall back to separate lanes.
        self.pair_width = self.idx_width + self.lab_width
        if self.pair_width <= 31:
            self.mover_lanes = packed_lanes(self.move_cap, self.pair_width)
        else:
            self.pair_width = None
            self.mover_lanes = (packed_lanes(self.move_cap, self.idx_width)
                                + packed_lanes(self.move_cap, self.lab_width))

    def community_sizes(self, comm, comm_l):
        # The replicated membership already holds every shard's slice, so
        # the psum'd per-shard size reduction collapses to one local
        # segment_sum — integer addition reorders exactly.
        sent = self.sentinel
        body = comm[:sent]
        return jax.ops.segment_sum(
            jnp.where(body < sent, 1, 0), jnp.minimum(body, sent),
            num_segments=sent + 1)

    def exchange_round(self, comm, sigma, sizes, comm_l, do_move, best_c,
                       dq_local):
        axes, spec = self.axes, self.spec
        v_per, sent = spec.v_per_shard, self.sentinel
        S, mcap = spec.n_shards, self.move_cap

        if self.pair_width is not None:
            # Fused (index, label) pairs: one compaction, one pack.  The
            # empty-slot fill decodes to index == v_per -> dropped below.
            pv = (jnp.arange(v_per, dtype=jnp.int32)
                  | (best_c << self.idx_width))
            _, pair_buf, n_moved = compact_movers(
                do_move, pv, mcap, jnp.int32(v_per))
            mover_lanes = pack_bits(pair_buf, self.pair_width)
        else:
            idx_buf, lab_buf, n_moved = compact_movers(
                do_move, best_c, mcap, jnp.int32(sent))
            mover_lanes = jnp.concatenate([
                pack_bits(idx_buf, self.idx_width),
                pack_bits(lab_buf, self.lab_width)])

        # ONE fused collective: mover count + local dq + packed mover
        # lanes, concatenated into a single uint32 word per shard.
        wire = jnp.concatenate([
            jnp.stack([n_moved.astype(jnp.uint32),
                       jax.lax.bitcast_convert_type(
                           dq_local.astype(jnp.float32), jnp.uint32)]),
            mover_lanes,
        ])
        g = jax.lax.all_gather(wire, axes)                 # (S, W)
        dq = jnp.sum(jax.lax.bitcast_convert_type(g[:, 1], jnp.float32))
        # Every shard sees every shard's counts, so the branch choice below
        # is replicated by construction — no extra pmax round-trip.
        over = jnp.max(g[:, 0].astype(jnp.int32)) > mcap
        g_mov = g[:, 2:]                                   # packed lanes

        def dense(_):
            # The per-community segment sums live HERE, not in the engine:
            # lax.cond operands are computed eagerly, so reducing them in
            # the branch means regular rounds never pay for them.
            moved_k = jnp.where(do_move, self.k_local, 0.0)
            add = jax.ops.segment_sum(
                moved_k, jnp.where(do_move, best_c, sent),
                num_segments=sent + 1)
            sub = jax.ops.segment_sum(
                moved_k, jnp.where(do_move, comm_l, sent),
                num_segments=sent + 1)
            comm_full = self.gather_comm(jnp.where(do_move, best_c, comm_l))
            return (comm_full, self.combine_sigma(sigma, add, sub),
                    self.community_sizes(comm_full, comm_l))

        def delta(_):
            if self.pair_width is not None:
                pairs = jax.vmap(
                    lambda r: unpack_bits(r, self.pair_width, mcap))(g_mov)
                idxs = pairs & ((1 << self.idx_width) - 1)
                labs = pairs >> self.idx_width
            else:
                li = packed_lanes(mcap, self.idx_width)
                idxs = jax.vmap(lambda r: unpack_bits(
                    r, self.idx_width, mcap))(g_mov[:, :li])
                labs = jax.vmap(lambda r: unpack_bits(
                    r, self.lab_width, mcap))(g_mov[:, li:])
            live = idxs < v_per                            # (S, mcap)
            base = jnp.arange(S, dtype=jnp.int32)[:, None] * v_per
            # Dead buffer slots route out of bounds -> the scatter drops
            # them (jnp default), leaving the sentinel slots alone.
            gid = jnp.where(live, base + idxs, sent + 1)
            lab = jnp.minimum(labs, sent)
            comm_new = comm.at[gid.reshape(-1)].set(lab.reshape(-1))

            # Sigma reconstruction: k and the pre-move membership are
            # replicated, so each mover's weight and old community are
            # local lookups.  Rebuild the dense mover-weight add / sub
            # arrays in the sender's segment-sum order (movers ascend by
            # vertex index in the buffer), subtract, then apply in ONE
            # dense add — on one shard that is exactly ``combine_sigma``'s
            # sigma + psum(add - sub) arithmetic, bit for bit.
            safe = jnp.where(live, gid, 0)
            kv = jnp.where(live, self.k[safe], 0.0).reshape(-1)
            old = jnp.where(live, comm[safe], sent + 1).reshape(-1)
            new = jnp.where(live, lab, sent + 1).reshape(-1)
            radd = jnp.zeros((sent + 2,), jnp.float32).at[new].add(kv)
            rsub = jnp.zeros((sent + 2,), jnp.float32).at[old].add(kv)
            sigma_new = sigma + (radd - rsub)[:sent + 1]

            # Sizes shift by +-1 at the movers' labels — integer adds
            # reorder exactly, so the running array equals a recompute.
            sizes_new = sizes.at[new].add(1).at[old].add(-1)
            return comm_new, sigma_new, sizes_new

        comm_new, sigma_new, sizes_new = jax.lax.cond(over, dense, delta, 0)
        # Movers are exactly the label changes (a move always changes the
        # label), so the moved mask is a compare, not another collective.
        moved_g = comm_new != comm
        return (comm_new, sigma_new, sizes_new, moved_g,
                over.astype(jnp.int32), dq)


class HybridShardedScanner(DeltaShardedScanner):
    """Owner-partitioned working state, replicated topology (P3 hybrid).

    The replicated-state scanners above rebuild the FULL ``(n_pad + 1,)``
    membership on every shard every round, so per-round payload and
    per-lane working state both scale with n.  This backend keeps
    membership fresh only where scanning actually reads it —

      * the shard's OWN ``v_per_shard`` block (``comm_local`` slices), and
      * each remote shard's BOUNDARY set: vertices with at least one
        cross-shard edge (``repro.core.comm.boundary_mask``).  Symmetric
        slot placement guarantees every remote ``dst_l`` a shard reads is
        in its owner's boundary, so publishing boundary movers keeps all
        cross-shard reads fresh; remote INTERIOR labels go stale and are
        provably never read mid-phase.

    K_i stays fully partitioned (only ``k_local`` is ever indexed), so
    receivers cannot rebuild Sigma from mover ids the way the delta
    backend does.  Instead each sender folds its OWN movers (interior and
    boundary alike) into per-community (Sigma, size) deltas locally and
    ships the compacted touched-community triples — ids, f32 Sigma deltas,
    offset-encoded size deltas (``size_delta_width``) — alongside the
    boundary movers, all fused into the backend's single uint32 wire word
    per round.  Replicated Sigma/sizes then advance by scatter-adds of the
    gathered deltas: identical f32 ops at touched communities and
    untouched slots left byte-identical (the dense paths add +0.0 there),
    so one-shard runs reproduce the committed goldens bit for bit and
    multi-shard runs match on integer-weight graphs.

    The phase ends with ONE owned-slice ``all_gather`` (``resync_comm``,
    priced as the plan's ``phase_fixed_bytes``) that re-replicates the
    final membership, so renumbering, aggregation, refinement's outer
    fold, warm restarts and fleet replay all run unchanged downstream —
    hybrid joins the golden matrix, never forks it.

    Flavors: the delta flavor (this class) keeps the policy mover cap and
    ``hybrid_touched_cap`` with the dense-resync ``lax.cond`` fallback;
    the gather flavor (`HybridGatherScanner`) prices worst-case caps
    (every vertex a boundary mover, every community touched) so it is
    overflow-free and skips the branch entirely — still far below the
    replicated gather backend's five dense O(n_pad) collectives.
    """

    can_overflow = True     # delta flavor: policy caps + dense fallback

    def __init__(self, axes, spec: ShardedGraphSpec, src_l, dst_l, w_l,
                 k, m):
        super().__init__(axes, spec, src_l, dst_l, w_l, k, m)
        from repro.configs.louvain_arch import hybrid_touched_cap
        v_per, sent = spec.v_per_shard, spec.sentinel
        if self.can_overflow:
            self.touched_cap = hybrid_touched_cap(v_per)
        else:
            # Worst-case caps: every owned vertex a boundary mover, each
            # touching a distinct old + new community.  Mover lane widths
            # recomputed to match (DeltaShardedScanner sized them for the
            # policy cap).
            self.move_cap = v_per
            self.touched_cap = 2 * v_per
            if self.pair_width is not None:
                self.mover_lanes = packed_lanes(self.move_cap,
                                                self.pair_width)
            else:
                self.mover_lanes = (
                    packed_lanes(self.move_cap, self.idx_width)
                    + packed_lanes(self.move_cap, self.lab_width))
        self.siz_width = size_delta_width(v_per)
        # The halo set: computed ONCE per phase from the sharded topology
        # (scanner construction), which also re-derives it at aggregation
        # and re-shard boundaries for free — those rebuild the scanner.
        self.bnd_own = boundary_mask(src_l, dst_l, self.v0, v_per, sent)

    def resync_comm(self, comm):
        """Phase-end re-replication: ONE all_gather of the fresh owned
        slices (stale remote-interior labels overwritten), so everything
        downstream of the move phase sees replicated state again."""
        return self.gather_comm(self.comm_local(comm))

    def exchange_round(self, comm, sigma, sizes, comm_l, do_move, best_c,
                       dq_local):
        axes, spec = self.axes, self.spec
        v_per, sent = spec.v_per_shard, self.sentinel
        S, mcap, tcap = spec.n_shards, self.move_cap, self.touched_cap
        lab_w = self.lab_width

        # Own movers — ALL of them, interior included — apply locally.
        comm_own_new = jnp.where(do_move, best_c, comm_l)

        # Sender-side per-community fold from the PARTITIONED K_i: only
        # k_local is read, in the same segment-sum order the dense paths
        # use, so the shipped deltas reproduce their arithmetic exactly.
        moved_k = jnp.where(do_move, self.k_local, 0.0)
        tgt = jnp.where(do_move, best_c, sent)
        old = jnp.where(do_move, comm_l, sent)
        add = jax.ops.segment_sum(moved_k, tgt, num_segments=sent + 1)
        sub = jax.ops.segment_sum(moved_k, old, num_segments=sent + 1)
        cnt_add = jax.ops.segment_sum(do_move.astype(jnp.int32), tgt,
                                      num_segments=sent + 1)
        cnt_sub = jax.ops.segment_sum(do_move.astype(jnp.int32), old,
                                      num_segments=sent + 1)
        touched = (cnt_add > 0) | (cnt_sub > 0)
        # Same compaction for both delta kinds (identical c_buf order).
        c_buf, ds_buf, n_t = topk_touched_deltas(add - sub, touched, tcap,
                                                 sent)
        _, dz_buf, _ = topk_touched_deltas(cnt_add - cnt_sub, touched,
                                           tcap, sent)

        # Only BOUNDARY movers travel; receivers rebuild global ids from
        # the sender's row index, so no id lists ever cross the wire.
        bnd_move = do_move & self.bnd_own
        if self.pair_width is not None:
            pv = (jnp.arange(v_per, dtype=jnp.int32)
                  | (best_c << self.idx_width))
            _, pair_buf, n_bnd = compact_movers(
                bnd_move, pv, mcap, jnp.int32(v_per))
            mover_lanes = pack_bits(pair_buf, self.pair_width)
        else:
            idx_buf, lab_buf, n_bnd = compact_movers(
                bnd_move, best_c, mcap, jnp.int32(sent))
            mover_lanes = jnp.concatenate([
                pack_bits(idx_buf, self.idx_width),
                pack_bits(lab_buf, self.lab_width)])

        # ONE fused collective: [boundary-mover count, touched count, dq]
        # header + boundary movers + touched ids + Sigma f32 deltas +
        # offset-encoded size deltas (delta + v_per, always nonnegative).
        wire = jnp.concatenate([
            jnp.stack([n_bnd.astype(jnp.uint32), n_t.astype(jnp.uint32),
                       jax.lax.bitcast_convert_type(
                           dq_local.astype(jnp.float32), jnp.uint32)]),
            mover_lanes,
            pack_bits(c_buf, lab_w),
            jax.lax.bitcast_convert_type(ds_buf, jnp.uint32),
            pack_bits(dz_buf + v_per, self.siz_width),
        ])
        g = jax.lax.all_gather(wire, axes)                  # (S, W)
        dq = jnp.sum(jax.lax.bitcast_convert_type(g[:, 2], jnp.float32))
        L_m, L_t = self.mover_lanes, packed_lanes(tcap, lab_w)
        g_mov = g[:, 3:3 + L_m]
        g_tid = g[:, 3 + L_m:3 + L_m + L_t]
        g_sig = g[:, 3 + L_m + L_t:3 + L_m + L_t + tcap]
        g_siz = g[:, 3 + L_m + L_t + tcap:]

        def apply_deltas(_):
            # Membership: own block from the local update, remote boundary
            # movers scattered in (dead buffer slots route out of bounds
            # and drop); remote interior stays stale — never read.
            comm_base = jax.lax.dynamic_update_slice(comm, comm_own_new,
                                                     (self.v0,))
            if self.pair_width is not None:
                pairs = jax.vmap(
                    lambda r: unpack_bits(r, self.pair_width, mcap))(g_mov)
                idxs = pairs & ((1 << self.idx_width) - 1)
                labs = pairs >> self.idx_width
            else:
                li = packed_lanes(mcap, self.idx_width)
                idxs = jax.vmap(lambda r: unpack_bits(
                    r, self.idx_width, mcap))(g_mov[:, :li])
                labs = jax.vmap(lambda r: unpack_bits(
                    r, self.lab_width, mcap))(g_mov[:, li:])
            live = idxs < v_per
            base = jnp.arange(S, dtype=jnp.int32)[:, None] * v_per
            gid = jnp.where(live, base + idxs, sent + 1)
            lab = jnp.minimum(labs, sent)
            comm_new = comm_base.at[gid.reshape(-1)].set(lab.reshape(-1))

            # Sigma / sizes: scatter-add every shard's touched deltas.
            # Empty buffer slots carry (id = sent, delta = 0) — adding
            # +0.0 / +0 there is byte-safe (Sigma holds sums of
            # nonnegative K_i, never -0.0).
            cs = jnp.minimum(jax.vmap(
                lambda r: unpack_bits(r, lab_w, tcap))(g_tid),
                sent).reshape(-1)
            sig_d = jax.lax.bitcast_convert_type(
                g_sig, jnp.float32).reshape(-1)
            siz_d = (jax.vmap(lambda r: unpack_bits(
                r, self.siz_width, tcap))(g_siz) - v_per).reshape(-1)
            sigma_new = sigma.at[cs].add(sig_d)
            sizes_new = sizes.at[cs].add(siz_d)

            # Fresh everywhere this mask is read: own block and remote
            # boundary (symmetric placement routes every ``dst_l`` there);
            # stale interior compares equal -> False, which is correct
            # for the LOCAL frontier mark.
            moved_g = comm_new != comm
            return comm_new, sigma_new, sizes_new, moved_g

        if not self.can_overflow:
            # Gather flavor: worst-case caps -> no overflow branch at all.
            comm_new, sigma_new, sizes_new, moved_g = apply_deltas(0)
            over = jnp.zeros((), bool)
        else:
            # Counts are gathered, so the branch choice is replicated.
            over = ((jnp.max(g[:, 0].astype(jnp.int32)) > mcap)
                    | (jnp.max(g[:, 1].astype(jnp.int32)) > tcap))

            def dense(_):
                # Full resync: owned slices are fresh by construction, so
                # one gather rebuilds replicated state exactly.  The moved
                # mask must come from do_move — the stale pre-round comm
                # makes the label compare unsound here.
                comm_full = self.gather_comm(comm_own_new)
                sigma_full = self.combine_sigma(sigma, add, sub)
                sizes_full = sizes + self.psum(cnt_add - cnt_sub)
                return (comm_full, sigma_full, sizes_full,
                        self.gather_mask(do_move))

            comm_new, sigma_new, sizes_new, moved_g = jax.lax.cond(
                over, dense, apply_deltas, 0)
        return (comm_new, sigma_new, sizes_new, moved_g,
                over.astype(jnp.int32), dq)


class HybridGatherScanner(HybridShardedScanner):
    """Hybrid state layout over the gather comm backend: worst-case caps
    (every owned vertex a boundary mover, ``2 * v_per`` touched
    communities) make the exchange overflow-free, so the round is still
    ONE fused collective — ~4-5x fewer bytes than replicated gather's
    five dense O(n_pad) collectives at the bench layout."""

    can_overflow = False


#: comm_backend -> engine scanner class (concrete backends only; "auto"
#: resolves through repro.configs.louvain_arch.resolve_comm_backend).
COMM_SCANNERS = {"gather": ShardedScanner, "delta": DeltaShardedScanner}

#: comm_backend -> scanner under the HYBRID state layout ("auto" resolves
#: through repro.configs.louvain_arch.resolve_state_layout).
HYBRID_SCANNERS = {"gather": HybridGatherScanner,
                   "delta": HybridShardedScanner}


def _scanner_cls(backend: str, state_layout: str):
    """Engine scanner for a (comm backend, state layout) pair — concrete
    values only; policies resolve in the drivers."""
    table = HYBRID_SCANNERS if state_layout == "hybrid" else COMM_SCANNERS
    return table[backend]


def sharded_comm_plan(spec: ShardedGraphSpec, backend: str,
                      state_layout: str = "replicated") -> CommPlan:
    """Bytes-on-wire plan for one engine round of ``spec`` under
    ``backend`` x ``state_layout`` (policy caps applied — ONE home for the
    accounting the pass-loop stats and the distdyn benchmark report)."""
    from repro.configs.louvain_arch import (delta_move_cap,
                                            hybrid_touched_cap)
    return comm_plan(backend, spec.n_shards, spec.v_per_shard, spec.n_pad,
                     delta_move_cap(spec.v_per_shard),
                     state_layout=state_layout,
                     touched_cap=hybrid_touched_cap(spec.v_per_shard))


def measure_boundary_frac(src_g, dst_g, spec: ShardedGraphSpec,
                          n_live: int | None = None) -> float:
    """Host-side boundary fraction of a partitioned layout: the share of
    live (edge-owning) vertices with at least one cross-shard slot.

    This is the measurement the ``state_layout="auto"`` policy consumes
    (``repro.configs.louvain_arch.resolve_state_layout``), mirroring how
    the measured mesh size drives ``resolve_comm_backend`` — hybrid only
    engages when the halo the layout would replicate is small.  ``n_live``
    overrides the denominator with the caller's live-vertex count;
    otherwise vertices owning no live slot are excluded from both sides.
    """
    src = np.asarray(src_g).ravel()
    dst = np.asarray(dst_g).ravel()
    sent, v_per = spec.sentinel, spec.v_per_shard
    live = (src < sent) & (dst < sent)
    if n_live is None:
        n_live = int(np.unique(src[live]).size)
    remote = live & (src // v_per != dst // v_per)
    n_bnd = int(np.unique(src[remote]).size)
    return n_bnd / max(int(n_live), 1)


def _round_body(axes, spec, src_l, dst_l, w_l, comm, sigma, k,
                frontier_l, round_ix, gate_fraction, m):
    """One synchronous local-move round for one shard; returns updates.

    Compatibility adapter over ``MoveEngine.one_round`` (the analysis
    harness in ``repro.configs.louvain_arch`` drives single rounds).
    """
    engine = MoveEngine(ShardedScanner(axes, spec, src_l, dst_l, w_l, k, m),
                        EngineConfig(gate_fraction=gate_fraction))
    zero = jnp.asarray(0.0, jnp.float32)
    st = MoveState(comm, sigma, jnp.asarray(0, jnp.int32), frontier_l,
                   jnp.asarray(0, jnp.int32), zero, zero,
                   jnp.asarray(0, jnp.int32))
    st = engine.one_round(st, frontier_l, round_ix)
    return st.comm, st.sigma, st.frontier, st.dq


@functools.lru_cache(maxsize=None)
def make_distributed_move(
    mesh: Mesh,
    axes: Tuple[str, ...],
    spec: ShardedGraphSpec,
    *,
    max_iterations: int = 20,
    gate_fraction: int = 2,
    use_pruning: bool = True,
    comm_backend: str = "gather",
    state_layout: str = "replicated",
):
    """Build the jit'd distributed local-moving phase for a fixed mesh/layout.

    Returns fn(src_g, dst_g, w_g, comm, sigma, k, frontier_g, m, tolerance)
        -> (comm, sigma, iters, dq_sum, rounds, fallbacks);
    comm/sigma replicated outputs, ``rounds`` the synchronous rounds run
    (sweeps x gate_fraction) and ``fallbacks`` how many of them the delta
    exchange overflowed to the dense path (0 under "gather").

    ``frontier_g`` is a replicated (n_pad + 1,) seed-frontier mask — all-ones
    for the static start, the delta-screened set for warm streaming starts
    (each shard slices its owned v_per entries).  ``comm_backend`` picks the
    per-round exchange (``COMM_SCANNERS``; "auto" resolves per mesh) and
    ``state_layout`` the working-state placement (``HYBRID_SCANNERS`` under
    "hybrid"; "auto" without a measured boundary fraction stays
    replicated).  Hybrid phases resync the membership once before
    returning, so outputs are replicated under every layout.
    """
    from repro.configs.louvain_arch import (resolve_comm_backend,
                                            resolve_state_layout)

    edge_spec = P(axes)      # edge arrays: sharded along dim 0 over all axes
    rep = P()                # replicated state

    scanner_cls = _scanner_cls(
        resolve_comm_backend(comm_backend, spec.n_shards),
        resolve_state_layout(state_layout, spec.n_shards))
    config = EngineConfig(max_iterations=max_iterations,
                          use_pruning=use_pruning,
                          gate_fraction=gate_fraction)

    def phase(src_g, dst_g, w_g, comm, sigma, k, frontier_g, m, tolerance):
        def body_shard(src_l, dst_l, w_l, comm, sigma, k, frontier_g, m,
                       tolerance):
            scanner = scanner_cls(axes, spec, src_l, dst_l, w_l, k, m)
            frontier0 = jax.lax.dynamic_slice_in_dim(
                frontier_g, scanner.v0, spec.v_per_shard
            ) & scanner.frontier_valid
            st = MoveEngine(scanner, config).run(comm, sigma, frontier0,
                                                 tolerance)
            resync = getattr(scanner, "resync_comm", None)
            comm_out = st.comm if resync is None else resync(st.comm)
            return (comm_out, st.sigma, st.iters, st.dq_sum,
                    st.iters * jnp.int32(gate_fraction), st.comm_fb)

        fn = jax.shard_map(
            body_shard, mesh=mesh,
            in_specs=(edge_spec, edge_spec, edge_spec, rep, rep, rep, rep,
                      rep, rep),
            out_specs=(rep, rep, rep, rep, rep, rep),
            check_vma=False,
        )
        return fn(src_g, dst_g, w_g, comm, sigma, k, frontier_g, m, tolerance)

    return jax.jit(phase)


@functools.lru_cache(maxsize=None)
def make_distributed_refine(
    mesh: Mesh,
    axes: Tuple[str, ...],
    spec: ShardedGraphSpec,
    *,
    max_iterations: int = 20,
    gate_fraction: int = 2,
    use_pruning: bool = True,
    comm_backend: str = "gather",
    state_layout: str = "replicated",
):
    """Build the jit'd distributed Leiden REFINEMENT phase.

    Returns fn(src_g, dst_g, w_g, outer, k, n_live, m, tolerance)
        -> (comm, iters, dq_sum, rounds, fallbacks)
    — the constrained engine sweep: every vertex re-seeds as a singleton and
    may only join communities inside its outer community (``outer``, the
    replicated membership from the preceding move phase).  Per shard the
    cross-outer edge slots are masked (dst -> sentinel, w -> 0) and the same
    exchange scanner the move phase uses is wrapped in
    ``engine.ConstrainedScanner`` — so the gather and delta comm backends
    both inherit refinement with zero forks.  ``k``/``m`` stay the FULL
    graph's quantities.

    ``n_live`` is the scalar live count for dense-prefix layouts or a
    replicated ``(n_pad + 1,)`` bool live mask for gappy (skew-resharded)
    layouts — ``sanitize_outer`` and the singleton seed accept both.
    """
    from repro.configs.louvain_arch import (resolve_comm_backend,
                                            resolve_state_layout)

    edge_spec = P(axes)
    rep = P()
    sent = spec.sentinel
    scanner_cls = _scanner_cls(
        resolve_comm_backend(comm_backend, spec.n_shards),
        resolve_state_layout(state_layout, spec.n_shards))
    config = EngineConfig(max_iterations=max_iterations,
                          use_pruning=use_pruning,
                          gate_fraction=gate_fraction)

    def phase(src_g, dst_g, w_g, outer, k, n_live, m, tolerance):
        def body_shard(src_l, dst_l, w_l, outer, k, n_live, m, tolerance):
            outer_s = sanitize_outer(outer, n_live, sent)
            dst_m, w_m = mask_cross_outer_slots(src_l, dst_l, w_l, outer_s,
                                                sent)
            scanner = ConstrainedScanner(
                scanner_cls(axes, spec, src_l, dst_m, w_m, k, m),
                outer_s, n_live, gate_fraction=gate_fraction)
            ids = jnp.arange(sent + 1)
            nv = jnp.asarray(n_live)
            live_v = (nv & (ids < sent)) if nv.ndim else (ids < nv)
            comm0 = jnp.where(live_v, ids, sent).astype(jnp.int32)
            frontier0 = scanner.frontier_valid & live_v[
                jnp.minimum(scanner.local_ids, sent)]
            st = MoveEngine(scanner, config).run(comm0, k, frontier0,
                                                 tolerance)
            resync = getattr(scanner, "resync_comm", None)
            comm_out = st.comm if resync is None else resync(st.comm)
            return (comm_out, st.iters, st.dq_sum,
                    st.iters * jnp.int32(gate_fraction), st.comm_fb)

        fn = jax.shard_map(
            body_shard, mesh=mesh,
            in_specs=(edge_spec, edge_spec, edge_spec, rep, rep, rep, rep,
                      rep),
            out_specs=(rep, rep, rep, rep, rep),
            check_vma=False,
        )
        return fn(src_g, dst_g, w_g, outer, k, n_live, m, tolerance)

    return jax.jit(phase)


@functools.lru_cache(maxsize=None)
def make_tier_phases(mesh: Mesh, axes: Tuple[str, ...], *,
                     max_iterations: int = 20, gate_fraction: int = 2,
                     use_pruning: bool = True, comm_backend: str = "gather",
                     state_layout: str = "replicated", refine: str = "none"):
    """The capacity-ladder phase factory: ``spec -> (move, agg, refine_move)``,
    cached so every tier's phases compile once and are reused across
    passes/batches (static and streaming drivers share this ONE builder).
    ``refine_move`` is ``None`` unless ``refine="leiden"`` — then it is the
    constrained-sweep phase from ``make_distributed_refine``.  The factory
    itself is cached on (mesh, axes, knobs) too — REPEATED driver calls on
    the same mesh (benchmarks, streaming restarts) must reuse the compiled
    phases instead of paying the XLA compile per call, which otherwise
    dominates small-graph wall time."""

    @functools.lru_cache(maxsize=None)
    def phases_for(spec_: ShardedGraphSpec):
        return (make_distributed_move(
                    mesh, axes, spec_, max_iterations=max_iterations,
                    gate_fraction=gate_fraction, use_pruning=use_pruning,
                    comm_backend=comm_backend, state_layout=state_layout),
                make_distributed_aggregate(mesh, axes, spec_),
                (make_distributed_refine(
                     mesh, axes, spec_, max_iterations=max_iterations,
                     gate_fraction=gate_fraction, use_pruning=use_pruning,
                     comm_backend=comm_backend, state_layout=state_layout)
                 if refine == "leiden" else None))

    return phases_for


@functools.lru_cache(maxsize=None)
def make_distributed_aggregate(mesh: Mesh, axes: Tuple[str, ...],
                               spec: ShardedGraphSpec):
    """Distributed coarsening: local sort-reduce, all_gather partials,
    owner-side re-reduce.  Returns fn(src_g, dst_g, w_g, comm_renumbered)
    -> (src_g', dst_g', w_g', e_valid) in the same edge layout for the coarse
    graph (coarse vertex v owned by shard v // v_per_shard)."""
    edge_spec = P(axes)
    rep = P()
    n_shards = spec.n_shards

    def body(src_l, dst_l, w_l, comm):
        v_per, sent = spec.v_per_shard, spec.sentinel
        e_l = src_l.shape[0]
        ci = comm[src_l]
        cj = comm[dst_l]

        # Local partial reduce.
        order = jnp.lexsort((cj, ci))
        s_ci, s_cj, s_w = ci[order], cj[order], w_l[order]
        prev_i = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_ci[:-1]])
        prev_j = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_cj[:-1]])
        new_group = (s_ci != prev_i) | (s_cj != prev_j)
        gidl = jnp.cumsum(new_group.astype(jnp.int32)) - 1
        gw = jax.ops.segment_sum(s_w, gidl, num_segments=e_l)[gidl]
        live = new_group & (s_ci != sent)
        pos = jnp.where(live, gidl, e_l)
        p_ci = jnp.full((e_l + 1,), sent, jnp.int32).at[pos].set(s_ci)[:e_l]
        p_cj = jnp.full((e_l + 1,), sent, jnp.int32).at[pos].set(s_cj)[:e_l]
        p_w = jnp.zeros((e_l + 1,), jnp.float32).at[pos].set(gw)[:e_l]

        # Share partials; each shard re-reduces and keeps its owned rows.
        g_ci = jax.lax.all_gather(p_ci, axes, tiled=True)   # (S * e_l,)
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
        prev_i = jnp.concatenate([jnp.full((1,), -1, jnp.int32), t_ci[:-1]])
        prev_j = jnp.concatenate([jnp.full((1,), -1, jnp.int32), t_cj[:-1]])
        ng2 = (t_ci != prev_i) | (t_cj != prev_j)
        gid2 = jnp.cumsum(ng2.astype(jnp.int32)) - 1
        gw2 = jax.ops.segment_sum(t_w, gid2, num_segments=t_w.shape[0])[gid2]
        live2 = ng2 & (t_ci != sent)
        pos2 = jnp.where(live2, gid2, e_l)  # per-shard capacity: e_l rows
        o_ci = jnp.full((e_l + 1,), sent, jnp.int32).at[pos2].set(
            jnp.where(live2, t_ci, sent))[:e_l]
        o_cj = jnp.full((e_l + 1,), sent, jnp.int32).at[pos2].set(
            jnp.where(live2, t_cj, sent))[:e_l]
        o_w = jnp.zeros((e_l + 1,), jnp.float32).at[pos2].set(
            jnp.where(live2, gw2, 0.0))[:e_l]
        e_valid = jax.lax.psum(jnp.sum(jnp.where(live2, 1, 0)), axes)
        # Overflow detection: a shard owning more than e_l coarse edges
        # (extreme community-ownership skew) would silently drop rows —
        # surface the max owned count so callers can fail loudly.
        owned_max = jax.lax.pmax(jnp.sum(jnp.where(live2, 1, 0)), axes)
        return o_ci, o_cj, o_w, e_valid, owned_max

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(edge_spec, edge_spec, edge_spec, rep),
                       out_specs=(edge_spec, edge_spec, edge_spec, rep, rep),
                       check_vma=False)
    return jax.jit(fn)


@jax.jit
def _vertex_k(w_g, src_g, n_pad_plus_1_zeros):
    """K_i over the partitioned slot arrays (shape token carries n_pad + 1)."""
    return jax.ops.segment_sum(
        w_g, src_g,
        num_segments=n_pad_plus_1_zeros.shape[0]).astype(jnp.float32)


@jax.jit
def _warm_comm_sigma(mem, k, n_valid):
    """(comm0, sigma0) resuming the sharded move phase from ``mem``.

    The replicated analogue of ``repro.core.louvain.warm_init``: valid
    vertices without a previous assignment (id >= n_pad, e.g. entered via an
    edge insert) fall back to their own singleton; sigma is recomputed from
    the CURRENT vertex weights so the snapshot stays exact after updates.

    ``n_valid`` is either the usual scalar (valid ids are the dense prefix
    ``[0, n_valid)``) or a ``(n_pad + 1,)`` bool LIVE MASK — the gappy
    layouts produced by skew-aware re-sharding, where valid ids sit in
    per-shard blocks with padding gaps between them.
    """
    n_pad = mem.shape[0] - 1
    idx = jnp.arange(n_pad + 1)
    nv = jnp.asarray(n_valid)
    valid = (nv & (idx < n_pad)) if nv.ndim else (idx < nv)
    assigned = jnp.where(mem < n_pad, mem.astype(jnp.int32),
                         idx.astype(jnp.int32))
    comm0 = jnp.where(valid, assigned, n_pad).astype(jnp.int32)
    sigma0 = jax.ops.segment_sum(k[:n_pad], comm0[:n_pad],
                                 num_segments=n_pad + 1)
    return comm0, sigma0.astype(jnp.float32)


@jax.jit
def sharded_modularity(src_g, dst_g, w_g, comm):
    """Q of a replicated (n_pad + 1,) membership on partitioned edge arrays."""
    sent = comm.shape[0] - 1
    m = jnp.sum(w_g) * 0.5
    internal = jnp.sum(jnp.where(comm[src_g] == comm[dst_g], w_g, 0.0))
    k = jax.ops.segment_sum(w_g, src_g, num_segments=sent + 1)
    sig = jax.ops.segment_sum(k[:sent], jnp.minimum(comm[:sent], sent),
                              num_segments=sent + 1).at[sent].set(0.0)
    return internal / (2.0 * m) - jnp.sum((sig / (2.0 * m)) ** 2)


def _rebucket_live_host(src_g, dst_g, w_g, old_sent: int,
                        spec_new: ShardedGraphSpec):
    """Pull live slots host-side and re-bucket them into ``spec_new``'s
    layout, doubling ``e_per_shard`` until the ownership fits (the ladder's
    shrink can concentrate coarse edges on few shards).  A VERTEX id beyond
    the layout is a caller bug doubling can never fix — checked up front so
    the retry loop only ever sees edge-capacity overflow (and terminates:
    ``e_per_shard >= len(src)`` always fits)."""
    src = np.asarray(src_g)
    dst = np.asarray(dst_g)
    w = np.asarray(w_g)
    live = src < old_sent
    src, dst, w = src[live], dst[live], w[live]
    if len(src) and int(src.max()) >= spec_new.n_pad:
        raise ValueError(
            f"live vertex id {int(src.max())} does not fit the target "
            f"layout (n_pad={spec_new.n_pad})")
    while True:
        try:
            return (*bucket_slots_host(src, dst, w, spec_new), spec_new)
        except ValueError:
            spec_new = spec_new._replace(
                e_per_shard=2 * spec_new.e_per_shard)


def _reshard_relabel(bounds: np.ndarray, v_per: int, n_pad_new: int,
                     old_cap: int) -> np.ndarray:
    """Monotone relabel LUT for a skew-aware owner split.

    ``bounds`` partitions the dense coarse ids ``[0, bounds[-1])`` into
    contiguous owner ranges; range ``s`` lands at the uniform device block
    ``[s * v_per, s * v_per + width_s)``, so ``owner = id // v_per`` stays
    the layout law and only the id values move.  Returns an
    ``(old_cap + 1,)`` int32 LUT: dense id -> relabelled id, everything
    else (incl. the old sentinel) -> ``n_pad_new`` (the new sentinel).
    The map is strictly increasing on the live ids — relative order (and
    hence every ordered reduction downstream) is preserved.
    """
    n_live = int(bounds[-1])
    lut = np.full(old_cap + 1, n_pad_new, np.int64)
    ids = np.arange(n_live)
    owner = np.searchsorted(bounds, ids, side="right") - 1
    lut[:n_live] = owner * v_per + (ids - bounds[owner])
    return lut.astype(np.int32)


def _reshard_coarse_host(src_g, dst_g, w_g, old_sent: int, plan):
    """Apply a ``configs.louvain_arch.ReshardPlan`` to a coarse graph.

    Pulls the live coarse slots host-side (they are already host-bound for
    the ladder re-bucket), relabels both endpoints through the monotone
    LUT, and re-buckets into the balanced layout.  Returns
    ``(src', dst', w', spec', lut, live_mask)`` — ``live_mask`` is the
    ``(n_pad' + 1,)`` bool mask of live vertex ids in the gappy layout
    (the ``n_valid`` operand of the mask-aware warm/refine paths).
    """
    n_shards = len(plan.bounds) - 1
    spec_new = ShardedGraphSpec(n_shards, plan.v_per_shard, plan.e_per_shard,
                                n_shards * plan.v_per_shard)
    lut = _reshard_relabel(plan.bounds, plan.v_per_shard, spec_new.n_pad,
                           old_sent)
    src = np.asarray(src_g)
    dst = np.asarray(dst_g)
    w = np.asarray(w_g)
    live = src < old_sent
    src, dst, w = lut[src[live]], lut[dst[live]], w[live]
    out = bucket_slots_host(src, dst, w, spec_new)
    n_live = int(plan.bounds[-1])
    live_mask = np.zeros(spec_new.n_pad + 1, bool)
    live_mask[lut[:n_live]] = True
    return (*out, spec_new, lut, live_mask)


def sharded_louvain_passes(
    src_g, dst_g, w_g,
    spec: ShardedGraphSpec,
    move, agg,
    n_live: int,
    *,
    init_membership=None,
    init_frontier=None,
    max_passes: int = 10,
    initial_tolerance: float = 0.01,
    tolerance_drop: float = 10.0,
    aggregation_tolerance: float = 0.8,
    phases_for=None,
    use_ladder: bool = False,
    comm_backend: str = "gather",
    state_layout: str = "replicated",
    refine: str = "none",
    refine_move=None,
    reshard: str = "none",
    pipeline_fetch: bool = False,
):
    """Host pass loop over prebuilt jit'd phases on partitioned edge arrays.

    The shared engine of the static and streaming sharded drivers:
    ``init_membership``/``init_frontier`` warm-start pass 0 ((n_pad + 1,)
    replicated arrays, mirroring ``repro.core.louvain.louvain``); later
    passes restart from singletons on the coarse graph.  The fine edge
    arrays are never mutated (aggregation emits fresh coarse arrays), so
    streaming callers can keep them resident across calls.

    With ``use_ladder`` (requires ``phases_for``, a ``spec -> (move, agg)``
    factory — callers cache it so tiers reuse compiled phases), coarse
    graphs are re-bucketed down through the same host-side machinery the
    streaming driver uses to GROW capacity (``bucket_slots_host``): after
    each aggregation the layout shrinks to the power-of-two tier fitting
    the coarse graph, so later passes' collectives and per-shard sorts run
    at coarse capacity.  Memberships are invariant to the layout.

    An aggregation whose coarse-edge ownership overflows ``e_per_shard``
    (community skew: renumbered coarse ids form a dense prefix that an
    owner map sized for the ORIGINAL vertex range parks on the first
    shards) is retried through the same machinery whenever ``phases_for``
    is available: first the OWNER MAP is laddered — ``v_per_shard``
    re-buckets to the tier fitting the live vertex count, spreading
    ownership across all shards — and only then does ``e_per_shard`` grow.
    Without a phase factory the overflow raises ``AggregationOverflow``.

    ``comm_backend`` / ``state_layout`` must be the CONCRETE exchange
    backend ("gather" | "delta") and state layout ("replicated" |
    "hybrid") matching what ``move``/``phases_for`` were built with —
    they are used for the per-pass bytes-on-wire stats, not for routing.

    With ``refine="leiden"`` every pass runs the constrained refinement
    sweep (``refine_move``, from ``make_distributed_refine`` /
    ``phases_for``) after local-moving: aggregation follows the REFINED
    partition while the reported membership and next-pass warm start stay
    at the OUTER partition — the same Leiden pass semantics as the
    single-device ``repro.core.louvain.louvain``.

    With ``reshard="auto"`` (requires ``phases_for``) every aggregation on
    a multi-shard mesh is followed by a skew check: per-coarse-vertex edge
    counts are measured host-side and, when the worst shard's load exceeds
    ``configs.louvain_arch.RESHARD_IMBALANCE_THRESHOLD`` times the mean
    under the uniform owner map, the coarse ids are monotonically
    relabelled onto contiguous load-balanced owner blocks
    (``plan_reshard`` / ``_reshard_coarse_host``) instead of taking the
    ladder tier.  The relabelled layout is GAPPY — live ids sit in
    per-shard blocks — so the pass threads a live mask through the warm
    start, the refinement sweep and the Leiden fold; the global fold and
    warm membership are remapped through the same LUT.  Balanced graphs
    skip the shuffle entirely, and the one-time relabel traffic is priced
    into the pass's ``comm_bytes`` via ``comm.reshard_bytes``.

    ``pipeline_fetch=True`` dispatches the next aggregation speculatively
    BEFORE the host fetches this pass's convergence scalars, so device
    work overlaps the host control decision; a pass that then breaks
    simply discards the speculative result.  Dispatch order is the only
    change — final memberships are identical (pinned in the golden
    matrix).

    Returns (membership (n_pad,) device array, n_communities, stats);
    the membership stays at the ORIGINAL ``spec.n_pad`` length (with
    refinement it is the outer fold, not the refined dendrogram chain).
    Each stats row carries the comm-plan columns (``comm_backend``,
    ``comm_rounds``, ``comm_fallback_rounds``, ``comm_bytes``) from the
    measured round counters + static shapes, the state-layout columns
    (``state_layout``, ``halo_bytes`` — the boundary-mover share of the
    wire, ``boundary_frac`` — measured under hybrid, else None), the
    measured pass wall-clock ``seconds`` (aggregation and re-bucket
    included), plus the re-shard columns (``reshard``, ``reshard_bytes``,
    ``max_shard_load_frac_before`` / ``_after``) when the pass boundary
    re-balanced ownership.
    """
    from repro.configs.louvain_arch import (LADDER_SLACK, _pow2_at_least,
                                            plan_reshard,
                                            resolve_coarse_capacity,
                                            resolve_reshard)
    from repro.core.comm import reshard_bytes as _reshard_cost
    from repro.core.louvain import _leiden_warm_membership, pad_membership

    if refine not in ("none", "leiden"):
        raise ValueError(f"refine must be 'none' or 'leiden', got {refine!r}")
    reshard_on = resolve_reshard(reshard) == "auto"
    refine_on = refine == "leiden"
    if refine_on and refine_move is None:
        if phases_for is None:
            raise ValueError("refine='leiden' needs refine_move or "
                             "phases_for")
        refine_move = phases_for(spec)[2]
        if refine_move is None:
            raise ValueError("refine='leiden' but the phase factory was "
                             "built with refine='none'")

    n_pad, sent = spec.n_pad, spec.sentinel
    idx = np.arange(n_pad + 1)
    shape_token = jnp.zeros((n_pad + 1,), jnp.float32)
    global_comm = jnp.arange(n_pad, dtype=jnp.int32)
    report_comm = global_comm
    ones_frontier = jnp.ones((n_pad + 1,), bool)
    tol = float(initial_tolerance)
    stats = []
    n_report = n_live
    leiden_warm = None
    live_np = None       # None = dense prefix [0, n_live); ndarray = gappy
    for p in range(max_passes):
        t_pass0 = time.perf_counter()
        # The live-vertex operand of the mask-aware paths: the scalar count
        # for dense-prefix layouts, the replicated bool mask after a
        # skew-aware re-shard made the layout gappy.
        nv_op = (jnp.int32(n_live) if live_np is None
                 else jnp.asarray(live_np))
        k = _vertex_k(w_g, src_g, shape_token)
        m = jnp.sum(w_g) * 0.5
        if p == 0 and init_membership is not None:
            comm0, sigma0 = _warm_comm_sigma(init_membership, k, nv_op)
            frontier0 = (ones_frontier if init_frontier is None
                         else init_frontier)
        elif leiden_warm is not None:
            # Leiden pass semantics: resume from the outer partition
            # expressed on the refined coarse vertices.
            comm0, sigma0 = _warm_comm_sigma(leiden_warm, k, nv_op)
            frontier0 = ones_frontier
        else:
            live_host = (idx < n_live) if live_np is None else live_np
            comm0 = jnp.asarray(
                np.where(live_host, idx, sent).astype(np.int32))
            sigma0 = k
            frontier0 = ones_frontier
        comm, sigma, iters, dq_sum, rounds, fallbacks = move(
            src_g, dst_g, w_g, comm0, sigma0, k, frontier0, m,
            jnp.float32(tol))
        refine_iters_i = None
        outer_ren = None
        rounds_extra = fb_extra = 0
        if refine_on:
            refined, r_iters, _r_dq, r_rounds, r_fb = refine_move(
                src_g, dst_g, w_g, comm, k, nv_op, m, jnp.float32(tol))
            outer_ren, n_outer = replicated_renumber(comm)
            comm_ren, n_comms = replicated_renumber(refined)
        else:
            comm_ren, n_comms = replicated_renumber(comm)
        # Pipelined convergence fetch: enqueue the aggregation BEFORE any
        # host sync below, so the device works through it while the host
        # reads the convergence scalars and decides.  Never on the last
        # pass (its result could only be discarded).  Dispatch order is
        # the only difference from the default path.
        pending_agg = None
        if pipeline_fetch and p < max_passes - 1:
            pending_agg = agg(src_g, dst_g, w_g, comm_ren)
        if refine_on:
            # Outer fold off the PRE-pass chain: what this pass reports.
            report_comm = outer_ren[jnp.minimum(global_comm, sent)]
            n_report = int(n_outer)
            refine_iters_i = int(r_iters)
            rounds_extra, fb_extra = int(r_rounds), int(r_fb)
        global_comm = comm_ren[jnp.minimum(global_comm, sent)]
        if not refine_on:
            report_comm = global_comm
            n_report = int(n_comms)
        iters_i, n_comms_i = int(iters), int(n_comms)
        rounds_i = int(rounds) + rounds_extra
        fb_i = int(fallbacks) + fb_extra
        plan = sharded_comm_plan(spec, comm_backend, state_layout)
        stats.append({"iterations": iters_i, "n_communities": n_report,
                      "n_vertices": n_live, "n_pad": sent,
                      "e_per_shard": spec.e_per_shard,
                      "dq_sum": float(dq_sum),
                      "comm_backend": comm_backend,
                      "comm_rounds": rounds_i,
                      "comm_fallback_rounds": fb_i,
                      "comm_bytes": phase_bytes(plan, rounds_i, fb_i),
                      "state_layout": state_layout,
                      "halo_bytes": plan.halo_round_bytes * rounds_i,
                      "boundary_frac": (
                          measure_boundary_frac(src_g, dst_g, spec, n_live)
                          if state_layout == "hybrid" else None),
                      "seconds": time.perf_counter() - t_pass0,
                      "refine_iterations": refine_iters_i,
                      "n_refined": n_comms_i if refine_on else None,
                      "reshard": False, "reshard_bytes": 0,
                      "max_shard_load_frac_before": None,
                      "max_shard_load_frac_after": None})
        converged = iters_i <= 1
        low_shrink = n_report / max(n_live, 1) > aggregation_tolerance
        if converged or low_shrink or p == max_passes - 1:
            break
        if refine_on:
            # Outer-on-coarse warm start, computed BEFORE aggregation so
            # skew retiers (which rewrite comm_ren's slot space) cannot
            # touch it: values are coarse ids [0, n_comms) regardless of
            # later layout changes.
            warm_flat = np.asarray(_leiden_warm_membership(
                comm_ren, outer_ren, nv_op, n_comms))[:n_comms_i]
        while True:
            if pending_agg is not None:
                a_src, a_dst, a_w, e_valid, owned_max = pending_agg
                pending_agg = None
            else:
                a_src, a_dst, a_w, e_valid, owned_max = agg(
                    src_g, dst_g, w_g, comm_ren)
            owned = int(owned_max)
            if owned <= spec.e_per_shard:
                src_g, dst_g, w_g = a_src, a_dst, a_w
                break
            if phases_for is None:
                # No phase factory: cannot re-bucket into a new layout.
                raise AggregationOverflow(owned, spec.e_per_shard)
            # Community-ownership skew.  After renumbering, coarse ids form
            # a dense [0, n_comms) prefix, so an owner map whose v_per
            # spans the ORIGINAL vertex range parks every coarse edge on
            # the first shards.  Ladder the OWNER MAP first — re-shard to
            # the tier fitting the live vertex count, spreading ownership
            # across all shards for free — and only grow e_per_shard (a
            # real memory cost, pass-local: the coarse arrays never touch
            # the caller's resident buffers) for the residual skew.
            old_sent = spec.sentinel
            v_tight = _pow2_at_least(-(-n_live // spec.n_shards))
            # The owner-map shrink assumes live FINE ids form a dense
            # prefix; a gappy (resharded) layout scatters them across the
            # full range, so only the edge capacity may grow there.
            if live_np is None and v_tight < spec.v_per_shard:
                tier = ShardedGraphSpec(spec.n_shards, v_tight,
                                        spec.e_per_shard,
                                        spec.n_shards * v_tight)
            else:
                tier = spec._replace(e_per_shard=_pow2_at_least(
                    max(owned, 2 * spec.e_per_shard)))
            src_g, dst_g, w_g, spec = _rebucket_live_host(
                src_g, dst_g, w_g, old_sent, tier)
            move, agg, _rmv = phases_for(spec)
            if refine_on and _rmv is not None:
                refine_move = _rmv
            if spec.sentinel != old_sent:
                # The owner map changed: rewrite the renumbered membership
                # (which feeds the retried aggregation) and the loop-level
                # layout trackers into the new sentinel space.  Live
                # entries hold coarse ids < n_live <= new n_pad; stale
                # slots held the OLD sentinel and are forced to the new.
                sent = spec.sentinel
                body = comm_ren[:spec.n_pad]
                comm_ren = jnp.concatenate([
                    jnp.where(jnp.arange(spec.n_pad) < n_live,
                              jnp.minimum(body, sent),
                              sent).astype(jnp.int32),
                    jnp.full((1,), sent, jnp.int32)])
                idx = np.arange(spec.n_pad + 1)
                shape_token = jnp.zeros((spec.n_pad + 1,), jnp.float32)
                ones_frontier = jnp.ones((spec.n_pad + 1,), bool)
        # --- skew-aware re-sharding (reshard="auto") -----------------------
        # The coarse graph is on the device in the CURRENT owner map; pull
        # the per-coarse-vertex edge counts host-side (the ladder re-bucket
        # pulls the same arrays anyway) and measure the skew the next pass
        # would inherit under the uniform layout.  When it clears the
        # threshold, relabel the dense coarse ids onto balanced contiguous
        # owner blocks and thread the remap through every replicated
        # consumer: the dendrogram fold, the Leiden warm start, and the
        # live mask the warm/refine paths read.  A re-shard replaces the
        # ladder tier for this boundary (it already picked the capacity).
        resharded = False
        if reshard_on and phases_for is not None and spec.n_shards > 1:
            src_np = np.asarray(src_g)
            counts = np.bincount(src_np[src_np < spec.sentinel],
                                 minlength=max(n_comms_i, 1))
            if use_ladder:
                n_new, _e_new = resolve_coarse_capacity(
                    n_comms_i, int(e_valid), spec.n_pad,
                    spec.e_per_shard * spec.n_shards)
                v_uniform = -(-n_new // spec.n_shards)
            else:
                v_uniform = spec.v_per_shard
            rplan = plan_reshard(counts, spec.n_shards, v_uniform)
            if rplan is not None:
                old_sent_r = spec.sentinel
                cost = _reshard_cost(spec.n_shards * spec.e_per_shard,
                                     spec.n_shards * rplan.e_per_shard)
                src_g, dst_g, w_g, spec, lut, live_mask = \
                    _reshard_coarse_host(src_g, dst_g, w_g, old_sent_r,
                                         rplan)
                move, agg, _rmv = phases_for(spec)
                if refine_on and _rmv is not None:
                    refine_move = _rmv
                sent = spec.sentinel
                idx = np.arange(spec.n_pad + 1)
                shape_token = jnp.zeros((spec.n_pad + 1,), jnp.float32)
                ones_frontier = jnp.ones((spec.n_pad + 1,), bool)
                # Fold and warm start live in coarse-id VALUE space (and,
                # for the warm start, coarse-id INDEX space) — both sides
                # go through the same monotone LUT.
                global_comm = jnp.asarray(lut)[
                    jnp.minimum(global_comm, old_sent_r)]
                if refine_on:
                    warm_new = np.full(spec.n_pad + 1, sent, np.int32)
                    warm_new[lut[:n_comms_i]] = lut[warm_flat]
                    leiden_warm = jnp.asarray(warm_new)
                live_np = live_mask
                resharded = True
                stats[-1].update(
                    reshard=True, reshard_bytes=cost,
                    max_shard_load_frac_before=rplan.load_frac_before,
                    max_shard_load_frac_after=rplan.load_frac_after,
                    comm_bytes=phase_bytes(plan, rounds_i, fb_i,
                                           reshard_cost=cost))
        if not resharded:
            # Aggregation emits dense coarse ids, so any non-resharded next
            # layout is a dense prefix again.
            live_np = None
        if not resharded and use_ladder and phases_for is not None:
            n_new, e_new = resolve_coarse_capacity(
                n_comms_i, int(e_valid), spec.n_pad,
                spec.e_per_shard * spec.n_shards)
            if (n_new, e_new) != (spec.n_pad,
                                  spec.e_per_shard * spec.n_shards):
                old_sent = spec.sentinel
                # Per-shard edge tier: fair share of the global tier,
                # floored at the MEASURED worst-shard ownership (plus
                # slack) — coarse edges concentrate on few shards, and
                # sizing only by the total would make the re-bucket fail
                # and walk a doubling retry.  Power-of-two quantized so
                # data-dependent skew cannot mint a fresh spec (and a
                # recompile) per pass.  (The bucket retry below stays as
                # the net: a changed v_per shifts ownership.)
                e_tier = _pow2_at_least(max(
                    -(-e_new // spec.n_shards),
                    int(owned * LADDER_SLACK), 1))
                tier = ShardedGraphSpec(
                    spec.n_shards, -(-n_new // spec.n_shards), e_tier,
                    spec.n_shards * (-(-n_new // spec.n_shards)))
                if tier != spec:
                    src_g, dst_g, w_g, spec = _rebucket_live_host(
                        src_g, dst_g, w_g, old_sent, tier)
                    move, agg, _rmv = phases_for(spec)
                    if refine_on and _rmv is not None:
                        refine_move = _rmv
                    sent = spec.sentinel
                    idx = np.arange(spec.n_pad + 1)
                    shape_token = jnp.zeros((spec.n_pad + 1,), jnp.float32)
                    ones_frontier = jnp.ones((spec.n_pad + 1,), bool)
        if refine_on and not resharded:
            # Express the outer-on-coarse warm start in the FINAL next-pass
            # layout (skew retiers / ladder tiers may have changed n_pad);
            # a re-shard already wrote the LUT-remapped warm start above.
            leiden_warm = jnp.asarray(pad_membership(warm_flat, spec.n_pad))
        n_live = n_comms_i
        # Restamp with the FULL pass wall-clock — aggregation, ladder
        # re-buckets and skew re-shards included — so reshard="auto" can
        # be judged against measured time, not slot counts alone.
        stats[-1]["seconds"] = time.perf_counter() - t_pass0
        tol /= tolerance_drop
    return report_comm, n_report, stats


def distributed_louvain(
    graph: CSRGraph,
    mesh: Mesh,
    axes: Tuple[str, ...],
    *,
    max_passes: int = 10,
    max_iterations: int = 20,
    initial_tolerance: float = 0.01,
    tolerance_drop: float = 10.0,
    aggregation_tolerance: float = 0.8,
    gate_fraction: int = 2,
    use_pruning: bool = True,
    init_membership=None,
    init_frontier=None,
    e_per_shard: int | None = None,
    use_ladder: bool = True,
    comm_backend: str = "auto",
    state_layout: str = "replicated",
    refine: str = "none",
    reshard: str = "none",
    pipeline_fetch: bool = False,
):
    """End-to-end multi-device GVE-Louvain (host pass loop, jit'd phases).

    ``init_membership``/``init_frontier`` warm-start the first pass like the
    single-device ``louvain`` (the streaming driver in
    ``repro.core.distributed_dynamic`` builds on this).  ``e_per_shard``
    reserves per-shard slot headroom — community skew can concentrate
    coarse edges on few shards; the pass loop re-shards the owner map and
    grows edge capacity in-flight when that happens.  ``use_ladder``
    re-buckets coarse graphs down the capacity ladder between passes
    (memberships unchanged; per-tier phases are built once and cached for
    the call).  ``comm_backend`` picks the per-round exchange ("gather" |
    "delta" | "auto"; auto resolves per mesh) — memberships are invariant
    to it.  So is ``state_layout`` ("replicated" | "hybrid" | "auto"):
    auto measures the partitioned layout's boundary fraction
    (``measure_boundary_frac``) and engages the hybrid
    partitioned-state scanners when it clears the
    ``configs.louvain_arch`` threshold on a multi-shard mesh.
    ``refine="leiden"`` enables the constrained refinement sweep
    between local-moving and aggregation (see ``sharded_louvain_passes``).
    ``reshard="auto"`` re-balances the coarse owner ranges by measured load
    after each aggregation (skew-aware re-sharding; a no-op on one shard
    and on balanced graphs), and ``pipeline_fetch=True`` overlaps the host
    convergence decision with the speculatively dispatched aggregation —
    both knobs change work placement, never memberships.

    Returns (membership (n,), n_communities, pass_stats list).
    """
    from repro.configs.louvain_arch import (resolve_comm_backend,
                                            resolve_state_layout)

    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    cb = resolve_comm_backend(comm_backend, n_shards)
    src_g, dst_g, w_g, spec = partition_graph_host(
        graph, n_shards, e_per_shard=e_per_shard)
    n = int(graph.n_valid)
    sl = resolve_state_layout(
        state_layout, n_shards,
        boundary_frac=(measure_boundary_frac(src_g, dst_g, spec, n)
                       if state_layout == "auto" and n_shards > 1
                       else None))

    phases_for = make_tier_phases(
        mesh, axes, max_iterations=max_iterations,
        gate_fraction=gate_fraction, use_pruning=use_pruning,
        comm_backend=cb, state_layout=sl, refine=refine)
    move, agg, _ = phases_for(spec)

    from repro.core.louvain import pad_membership
    mem0 = fr0 = None
    if init_membership is not None:
        mem0 = jnp.asarray(pad_membership(
            np.minimum(np.asarray(init_membership, np.int64),
                       spec.n_pad).astype(np.int32)[:spec.n_pad],
            spec.n_pad))
    if init_frontier is not None:
        fr = np.zeros(spec.n_pad + 1, bool)
        src_fr = np.asarray(init_frontier, bool)
        fr[: min(len(src_fr), spec.n_pad)] = src_fr[: spec.n_pad]
        fr0 = jnp.asarray(fr)

    with mesh:
        global_comm, _, stats = sharded_louvain_passes(
            src_g, dst_g, w_g, spec, move, agg, n,
            init_membership=mem0, init_frontier=fr0,
            max_passes=max_passes, initial_tolerance=initial_tolerance,
            tolerance_drop=tolerance_drop,
            aggregation_tolerance=aggregation_tolerance,
            phases_for=phases_for, use_ladder=use_ladder, comm_backend=cb,
            state_layout=sl, refine=refine, reshard=reshard,
            pipeline_fetch=pipeline_fetch)
    membership = np.asarray(global_comm[:n])
    return membership, int(len(np.unique(membership))), stats


@jax.jit
def replicated_renumber(comm: jax.Array, n_pad: int | None = None):
    """Renumber a replicated community array (n_pad + 1,) -> dense ids."""
    n_pad = comm.shape[0] - 1
    idx = jnp.arange(n_pad + 1)
    valid = (comm < n_pad) & (idx < n_pad)
    cs = jnp.where(valid, comm, n_pad)
    present = jnp.zeros((n_pad + 1,), jnp.int32).at[cs].set(1)
    present = present.at[n_pad].set(0)
    new_id = jnp.cumsum(present) - present
    n_comms = jnp.sum(present)
    new_id = new_id.at[n_pad].set(n_pad)
    return jnp.where(valid, new_id[cs], n_pad), n_comms


def sentinel_forced_membership(global_comm, n_valid, n_pad: int):
    """Replicated (n_pad + 1,) membership from a pass-loop fold.

    Invalid slots are forced to the layout sentinel: with the coarse-pass
    ladder they can carry stale SMALL sentinel values (a shrunk tier's
    n_pad) which a later warm start would misread as real assignments.
    Shared by the streaming driver (``distributed_dynamic``) and the
    serving fleet (``repro.core.fleet``) so both produce bit-identical
    resident state.  Works eagerly or inside a trace (``n_valid`` may be a
    traced scalar).
    """
    gc = jnp.where(jnp.arange(n_pad) < n_valid, global_comm[:n_pad],
                   jnp.int32(n_pad))
    return jnp.concatenate([gc, jnp.full((1,), n_pad, jnp.int32)])
