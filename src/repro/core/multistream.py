"""Batched multi-stream serving: one jitted program, many edge streams.

Serving workloads rarely carry ONE stream: a fleet of tenants (per-user
interaction graphs, per-region topologies, A/B shadow graphs) each emits
small edge-batch deltas and wants fresh communities.  Running
``louvain_dynamic`` per stream pays the full dispatch + host-control-flow
cost S times; here the engine's move rounds are ``vmap``-ed over a leading
stream axis instead, so S independent streams ride ONE compiled program:

  * ``stack_graphs`` / ``stack_batches`` stack equal-capacity ``CSRGraph`` /
    ``EdgeBatch`` pytrees along axis 0 (capacities are the compiled shape,
    so serving fleets provision one shared (n_cap, e_cap) envelope).
  * ``louvain_batched`` is the batched pass loop: vmapped warm/singleton
    init, vmapped engine move phase (the ``lax.while_loop`` batches to a
    run-until-all-converge loop with masked updates), vmapped renumber +
    aggregation.  Pass-level decisions stay host-side but are taken ONCE
    for the fleet: converged streams get ``tolerance = +inf`` (their loop
    exits immediately) and their state is frozen via an active-mask select,
    while the rest keep optimizing in lockstep.
  * ``louvain_dynamic_batched`` is the streaming driver: per step, the
    edge batches of all streams apply in one vmapped sort-reduce, delta
    screening (``repro.core.engine.affected_frontier``, community- or
    vertex-granularity) seeds per-stream frontiers, and the batched pass
    loop resumes from the per-stream memberships.

Capacity growth is a FLEET-level event: one whale stream overflowing
``e_cap`` re-buckets every stream into the next power-of-two tier (one
recompile for the fleet, same as the capacity ladder's shrink) and replays
the step, instead of killing the whole serving step mid-fleet.  Callers
that would rather fail fast pass ``grow_capacity=False`` and catch the
typed ``FleetCapacityOverflow``.  The scanner is the sort-reduce backend
(ELL bucketing is per-graph host work that does not batch).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.louvain_arch import (_pow2_at_least, compact_work_cap,
                                        resolve_agg_backend,
                                        resolve_coarse_capacity)
from repro.core.aggregate import renumber_communities
from repro.core.delta import EdgeBatch, _apply_edge_batch
from repro.core.engine import (affected_frontier, normalize_screening,
                               resolve_screening_host)
from repro.core.graph import CSRGraph, rebucket_capacity
from repro.core.louvain import (LouvainConfig, PassStats, _aggregate_phase,
                                _leiden_warm_membership, _move_phase,
                                _refine_phase, _renumber_and_fold,
                                pad_membership, singleton_init, warm_init)
from repro.core.modularity import modularity


class FleetCapacityOverflow(ValueError):
    """A serving step overflows the fleet's shared ``e_cap`` envelope.

    Raised only under ``grow_capacity=False`` (the default driver re-buckets
    the fleet and replays).  Carries the offending ``step``, the worst
    stream's required slot count ``e_need``, and the envelope ``e_cap``."""

    def __init__(self, step: int, e_need: int, e_cap: int):
        super().__init__(
            f"batched step {step} overflows capacity: a stream needs "
            f"{e_need} live directed slots > e_cap={e_cap}")
        self.step, self.e_need, self.e_cap = step, e_need, e_cap


def stack_graphs(graphs: Sequence[CSRGraph]) -> CSRGraph:
    """Stack equal-capacity graphs along a new leading stream axis."""
    g0 = graphs[0]
    for g in graphs[1:]:
        if g.n_cap != g0.n_cap or g.e_cap != g0.e_cap:
            raise ValueError(
                f"stream capacities differ: ({g.n_cap}, {g.e_cap}) vs "
                f"({g0.n_cap}, {g0.e_cap}) — provision one shared envelope")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *graphs)


def stack_batches(batches: Sequence[EdgeBatch]) -> EdgeBatch:
    """Stack equal-capacity edge batches along a new leading stream axis."""
    b0 = batches[0]
    for b in batches[1:]:
        if b.b_cap != b0.b_cap:
            raise ValueError(
                f"batch capacities differ: {b.b_cap} vs {b0.b_cap}")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


@dataclasses.dataclass
class BatchedLouvainResult:
    membership: jax.Array        # (S, n_cap) padded per-stream membership
    n_communities: np.ndarray    # (S,) int
    n_passes: int                # lockstep passes run (max over streams)


@dataclasses.dataclass
class BatchedDynamicResult:
    graphs: CSRGraph             # stacked graphs after all steps
    membership: np.ndarray       # (S, n_cap) final padded membership
    n_communities: np.ndarray    # (S,) int
    frontier_sizes: np.ndarray   # (n_steps, S) delta-screened seed sizes
    modularity: Optional[np.ndarray]  # (S,) final Q per stream (if tracked)
    total_seconds: float
    n_regrows: int = 0           # fleet-level capacity-growth re-buckets
    #: One row per serving step with the knobs the step ACTUALLY ran with
    #: (fleet-level maxima; ``screening``/``scan_backend`` record the
    #: host-resolved choices, ``downgraded`` flags an "auto" request the
    #: vmapped program could not honor as such).
    pass_stats: List[PassStats] = dataclasses.field(default_factory=list)

    def stream_membership(self, s: int) -> np.ndarray:
        n = int(np.asarray(self.graphs.n_valid)[s])
        return np.asarray(self.membership[s, :n])


@functools.lru_cache(maxsize=None)
def _fused_step(max_iterations: int, use_pruning: bool, gate_fraction: int,
                tolerance: float, screen_mode: Optional[str], backend: str,
                work_cap: int = 0):
    """ONE jitted vmapped program for a whole serving step: batch apply ->
    delta screen -> warm init -> engine move -> renumber.

    This is the fast path of ``louvain_dynamic_batched``: warm streaming
    updates almost always converge in a single pass (``iters <= 1``), so the
    per-step host cost collapses to one dispatch + one scalar fetch for the
    fleet.  The returned ``iters``/``e_new`` let the host detect the rare
    step that needs the general pass loop (or overflowed capacity) and
    redo it off the fast path — results stay exactly equal to the
    sequential drivers either way.  ``work_cap > 0`` routes the move phase
    through the frontier-compacted scanner (bit-identical; note that under
    ``vmap`` its overflow ``cond`` lowers to a select that evaluates both
    scans, so this is a correctness-preserving knob here, not a speedup —
    which is why ``scan_backend="auto"`` resolves to the full scan for the
    batched driver).
    """

    def one(g: CSRGraph, mem_row: jax.Array, b: EdgeBatch):
        n_cap = g.n_cap
        g2, touched, e_new = _apply_edge_batch(g, b, backend=backend)
        mem_pad = jnp.concatenate(
            [mem_row[:n_cap], jnp.full((1,), n_cap, jnp.int32)])
        if screen_mode is not None:
            frontier = affected_frontier(touched, mem_pad, g2.n_valid,
                                         screen_mode)
        else:
            frontier = jnp.arange(n_cap + 1) < g2.n_valid
        comm0, sigma0, frontier0 = warm_init(g2, mem_pad, frontier)
        comm, iters, _ = _move_phase(
            g2, comm0, sigma0, frontier0, jnp.float32(tolerance),
            max_iterations=max_iterations, use_pruning=use_pruning,
            gate_fraction=gate_fraction, work_cap=work_cap)
        comm_ren, _ = renumber_communities(comm, g2.n_valid, n_cap)
        return (g2, comm_ren[:n_cap], frontier, iters, e_new,
                jnp.sum(frontier), jnp.sum(touched.astype(jnp.int32)))

    return jax.jit(jax.vmap(one))


@functools.lru_cache(maxsize=None)
def _batched_phases(max_iterations: int, use_pruning: bool,
                    gate_fraction: int, work_cap: int = 0,
                    agg_backend: str = "sort"):
    """vmapped jit'd phases for one static move configuration."""
    move = jax.vmap(functools.partial(
        _move_phase, max_iterations=max_iterations, use_pruning=use_pruning,
        gate_fraction=gate_fraction, work_cap=work_cap))
    return (move, jax.vmap(singleton_init), jax.vmap(warm_init),
            jax.vmap(_renumber_and_fold),
            jax.vmap(functools.partial(_aggregate_phase,
                                       backend=agg_backend)))


def louvain_batched(
    gb: CSRGraph,
    config: LouvainConfig = LouvainConfig(),
    *,
    init_membership: Optional[jax.Array] = None,
    init_frontier: Optional[jax.Array] = None,
) -> BatchedLouvainResult:
    """Batched pass loop over stacked graphs; see the module docstring.

    ``init_membership`` ((S, n_cap) or (S, n_cap + 1)) warm-starts pass 0
    per stream; ``init_frontier`` ((S, n_cap + 1) bool) seeds delta
    screening.  Streams converge independently: a finished stream's
    tolerance flips to +inf (its batched while_loop lane exits immediately)
    and its membership is frozen while the fleet finishes.

    With ``config.use_ladder`` the coarse passes ride the capacity ladder
    at FLEET granularity: one tier per pass, resolved from the max coarse
    size over the still-active streams, so the whole fleet keeps a single
    compiled shape per tier (per-stream tiers would shatter the vmap).

    ``config.refine="leiden"`` vmaps the constrained refinement sweep
    (``repro.core.louvain._refine_phase``) over the fleet: aggregation
    follows each stream's REFINED partition while the reported membership
    and the next pass's warm start stay at the outer partition — the same
    Leiden pass semantics as the single-device driver, one compiled
    program for all streams.
    """
    if config.use_ell_kernel or config.scan_backend in ("ell", "ell_fused"):
        raise ValueError("louvain_batched uses the sort-reduce scanner; "
                         "ELL bucketing is per-graph host work")
    if config.refine not in ("none", "leiden"):
        raise ValueError(
            f"refine must be 'none' or 'leiden', got {config.refine!r}")
    refine_on = config.refine == "leiden"
    S, n_cap = gb.indptr.shape[0], gb.indptr.shape[1] - 1
    # Aggregation backend under vmap mirrors the scanner policy: an
    # EXPLICIT "pallas" is honored (bit-identical, tested in interpret
    # mode), but "auto" stays the sort chain — the vmapped kernel is not a
    # tuned fleet path, so auto never routes production fleets through it.
    agg_backend = (resolve_agg_backend(config.agg_backend)
                   if config.agg_backend != "auto" else "sort")
    move, v_singleton, v_warm, v_renumber, v_aggregate = _batched_phases(
        config.max_iterations, config.use_pruning, config.gate_fraction,
        0, agg_backend)
    # Pass 0 with a seed frontier may use the compacted scanner (explicit
    # "compact" only — "auto" keeps the full scan under vmap, where the
    # overflow cond lowers to a both-branches select).
    move0 = move
    if config.scan_backend == "compact" and init_frontier is not None:
        move0 = _batched_phases(
            config.max_iterations, config.use_pruning, config.gate_fraction,
            compact_work_cap(gb.indices.shape[1],
                             config.compact_cap_frac))[0]
    if refine_on:
        v_refine = jax.vmap(functools.partial(
            _refine_phase, max_iterations=config.max_iterations,
            use_pruning=config.use_pruning,
            gate_fraction=config.gate_fraction))
        v_leiden_warm = jax.vmap(_leiden_warm_membership)

    global_comm = jnp.tile(jnp.arange(n_cap, dtype=jnp.int32)[None], (S, 1))
    report_comm = global_comm
    leiden_mem = None
    n_valid0 = gb.n_valid           # per-stream vertex counts of the INPUT
    active = np.ones(S, bool)       # (gb becomes the coarse graph below)
    tol = float(config.initial_tolerance)
    n_comms_final = np.asarray(gb.n_valid).copy()
    warm = init_membership is not None
    if warm:
        mem = jnp.asarray(init_membership, jnp.int32)
        if mem.shape[1] < n_cap + 1:
            mem = jnp.concatenate(
                [mem, jnp.full((S, n_cap + 1 - mem.shape[1]), n_cap,
                               jnp.int32)], axis=1)
    fr = (jnp.ones((S, n_cap + 1), bool) if init_frontier is None
          else jnp.asarray(init_frontier, bool))

    passes = 0
    for p in range(config.max_passes):
        if p == 0 and warm:
            comm0, sigma0, frontier0 = v_warm(gb, mem, fr)
        elif leiden_mem is not None:
            # Leiden pass semantics: resume from the outer partition
            # expressed on the refined coarse vertices.
            comm0, sigma0, frontier0 = v_warm(
                gb, leiden_mem, jnp.ones_like(leiden_mem, bool))
        else:
            comm0, sigma0, frontier0 = v_singleton(gb)
            if p == 0 and init_frontier is not None:
                frontier0 = frontier0 & fr
        tols = jnp.where(jnp.asarray(active), jnp.float32(tol), jnp.inf)
        comm, iters, _ = (move0 if p == 0 else move)(
            gb, comm0, sigma0, frontier0, tols)
        if refine_on:
            refined, _r_iters, _r_dq = v_refine(gb, comm, tols)
            outer_ren, n_outer, outer_fold = v_renumber(
                comm, gb.n_valid, jnp.zeros((S,), jnp.int32), global_comm)
            comm_ren, n_comms, folded = v_renumber(
                refined, gb.n_valid, jnp.zeros((S,), jnp.int32), global_comm)
            report_fold, n_report = outer_fold, n_outer
        else:
            comm_ren, n_comms, folded = v_renumber(
                comm, gb.n_valid, jnp.zeros((S,), jnp.int32), global_comm)
            report_fold, n_report = folded, n_comms
        mask = jnp.asarray(active)
        global_comm = jnp.where(mask[:, None], folded, global_comm)
        report_comm = jnp.where(mask[:, None], report_fold, report_comm)
        passes = p + 1

        iters_np = np.asarray(iters)
        n_comms_np = np.asarray(n_comms)
        n_report_np = np.asarray(n_report)
        n_valid_np = np.asarray(gb.n_valid)
        n_comms_final = np.where(active, n_report_np, n_comms_final)
        converged = iters_np <= 1
        low_shrink = (n_report_np / np.maximum(n_valid_np, 1)
                      > config.aggregation_tolerance)
        next_active = active & ~converged & ~low_shrink
        if p == config.max_passes - 1 or not next_active.any():
            break
        if refine_on:
            # Outer-on-coarse warm start at the FINE pass capacity; resized
            # below once the coarse layout (ladder tier) is known — values
            # are coarse ids [0, n_comms), invariant to the layout.
            warm_c = v_leiden_warm(comm_ren, outer_ren, gb.n_valid, n_comms)
        gb_new = v_aggregate(gb, comm_ren, n_comms)
        sel = jnp.asarray(next_active)
        gb = jax.tree.map(
            lambda new, old: jnp.where(
                sel.reshape((S,) + (1,) * (new.ndim - 1)), new, old),
            gb_new, gb)
        if config.use_ladder:
            # Fleet-level tier decision: the capacity ladder must keep ONE
            # jit shape for the whole fleet, so the tier is resolved from
            # the max coarse size over the streams that keep optimizing.
            # Frozen lanes' graphs may be truncated by the shrink — they
            # are never read again (membership is already folded and their
            # aggregation output is masked off).
            n_cap_cur = gb.indptr.shape[1] - 1
            e_cap_cur = gb.indices.shape[1]
            e_valid_np = np.asarray(gb.e_valid)
            n_need = int(n_comms_np[next_active].max())
            e_need = int(e_valid_np[next_active].max())
            n_new, e_new = resolve_coarse_capacity(
                n_need, e_need, n_cap_cur, e_cap_cur)
            if (n_new, e_new) != (n_cap_cur, e_cap_cur):
                gb = jax.vmap(lambda g: rebucket_capacity(
                    g, n_cap_new=n_new, e_cap_new=e_new))(gb)
        if refine_on:
            # Resize the warm rows to the (possibly laddered) coarse
            # capacity: live entries (< n_comms) hold valid coarse ids,
            # everything else becomes the new sentinel.
            cap2 = gb.indptr.shape[1] - 1
            idx2 = jnp.arange(cap2 + 1)
            if warm_c.shape[1] >= cap2 + 1:
                body = warm_c[:, : cap2 + 1]
            else:
                body = jnp.concatenate(
                    [warm_c, jnp.full((S, cap2 + 1 - warm_c.shape[1]),
                                      cap2, jnp.int32)], axis=1)
            leiden_mem = jnp.where(idx2[None, :] < n_comms[:, None],
                                   body, jnp.int32(cap2))
        active = next_active
        tol /= config.tolerance_drop

    # Invalid slots (idx >= n_valid) are forced to the ORIGINAL sentinel:
    # folding through a laddered (shrunk) pass leaves them holding the small
    # tier's sentinel, which a later warm start would misread as a real
    # community assignment (matches the un-laddered fold, where they hold
    # n_cap after the first renumber).  With refinement the reported
    # membership is the OUTER fold, not the refined dendrogram chain.
    idx = jnp.arange(n_cap)
    report_comm = jnp.where(idx[None, :] < n_valid0[:, None],
                            report_comm, jnp.int32(n_cap))
    return BatchedLouvainResult(membership=report_comm,
                                n_communities=n_comms_final.astype(int),
                                n_passes=passes)


def louvain_dynamic_batched(
    graphs: Sequence[CSRGraph],
    streams: Sequence[Sequence[EdgeBatch]],
    prevs: Optional[Sequence[np.ndarray]] = None,
    config: LouvainConfig = LouvainConfig(),
    *,
    screening=True,
    track_modularity: bool = False,
    apply_backend: str = "xla",
    grow_capacity: bool = True,
) -> BatchedDynamicResult:
    """Serve S independent edge streams through ONE batched dynamic program.

    ``streams[s]`` is stream s's batch sequence; all streams must have the
    same number of steps and per-step ``b_cap`` (serving fleets share one
    compiled envelope — pad short streams with empty batches).  ``prevs``
    are the per-stream memberships before the stream; ``None`` runs one
    batched cold start.  Per step: one vmapped batch apply, one vmapped
    delta screen (``screening`` as in ``louvain_dynamic``, including
    ``"auto"``), one batched warm pass loop.  ``config.scan_backend=
    "compact"`` routes the vmapped move phase through the frontier-
    compacted scanner (bit-identical; under vmap the overflow cond lowers
    to a both-branches select, so ``"auto"`` keeps the full scan here).
    A step overflowing the fleet's ``e_cap`` re-buckets every stream into
    the next power-of-two edge tier and replays it (``grow_capacity``,
    default; one recompile per growth, counted in ``n_regrows``) — with
    ``grow_capacity=False`` it raises ``FleetCapacityOverflow`` instead.
    Memberships are invariant to capacity either way.
    """
    t_start = time.perf_counter()
    S = len(graphs)
    if len(streams) != S:
        raise ValueError(f"{S} graphs but {len(streams)} streams")
    n_steps = len(streams[0])
    if any(len(s) != n_steps for s in streams):
        raise ValueError("all streams must have the same number of steps")
    screen_mode = normalize_screening(screening)
    gb = stack_graphs(list(graphs))
    n_cap, e_cap = gb.indptr.shape[1] - 1, gb.indices.shape[1]

    if config.use_ell_kernel or config.scan_backend in ("ell", "ell_fused"):
        raise ValueError("louvain_dynamic_batched uses the sort-reduce "
                         "scanner; ELL bucketing is per-graph host work")
    # Scanner selection under vmap: "compact" is honored (bit-identical,
    # though its overflow cond lowers to a both-branches select), but
    # "auto" CANNOT be — the per-batch frontier-fraction resolution is a
    # host decision the one-program-many-streams driver has no per-stream
    # hook for, so it downgrades to the full scan and RECORDS the
    # downgrade in ``pass_stats`` instead of silently staying full.
    compact_on = (config.scan_backend == "compact"
                  and screen_mode is not None)
    scan_used = "compact" if compact_on else "full"
    # (Without screening the auto resolution would pick the full scan
    # anyway — only flag the downgrade when it could have differed.)
    scan_down = config.scan_backend == "auto" and screen_mode is not None
    # Screening "auto" is likewise resolved HOST-side, per fleet step, from
    # the previous step's worst touched fraction (the on-device auto select
    # evaluates BOTH granularities for every lane under vmap): the driver
    # takes the per-step validated path, whose scalar fetch carries the
    # touched counts for free.
    auto_screen = screen_mode == "auto"

    def _fused_for(mode: Optional[str]):
        wc = (compact_work_cap(e_cap, config.compact_cap_frac)
              if compact_on else 0)
        return _fused_step(config.max_iterations, config.use_pruning,
                           config.gate_fraction,
                           float(config.initial_tolerance), mode,
                           apply_backend, wc)

    fused = _fused_for("community" if auto_screen else screen_mode)

    if prevs is None:
        mem = louvain_batched(gb, config).membership
    else:
        # pad_membership accepts (n,), (n_cap,) and sentinel-padded
        # (n_cap + 1,) inputs alike — same contract as louvain_dynamic.
        mem = jnp.stack([
            jnp.asarray(pad_membership(
                np.asarray(p, np.int32)[:n_cap], n_cap)[:n_cap])
            for p in prevs])

    bbs = [stack_batches([streams[s][step] for s in range(S)])
           for step in range(n_steps)]

    n_regrows = 0
    stats: List[PassStats] = []

    def _step_stat(mode, mode_down, iters_max, fsize_max, nv_max):
        return PassStats(
            iterations=int(iters_max), n_communities=0, n_vertices=nv_max,
            dq_sum=0.0, seconds=0.0,
            frontier_size=int(fsize_max), n_cap=n_cap, e_cap=e_cap,
            screening=mode, scan_backend=scan_used,
            downgraded=bool(mode_down or scan_down))

    def serve_carefully(gb, mem):
        """Per-step validated loop: check overflow/convergence every step,
        routing overflowed steps through a fleet re-bucket + replay and
        non-converged steps through the general batched pass loop —
        results stay exactly equal to the sequential driver.  With
        ``screening="auto"`` this is the ONLY path: the step's scalar
        fetch carries the touched counts the next step's host-side mode
        resolution needs."""
        nonlocal e_cap, n_regrows
        frontier_sizes: List[jax.Array] = []
        stats.clear()
        touched_frac = None
        for step in range(n_steps):
            mode, mode_down = resolve_screening_host(screen_mode,
                                                     touched_frac)
            fused_t = _fused_for(mode)
            while True:
                gb_new, mem_new, frontier, iters, e_new, fsize, tch = \
                    fused_t(gb, mem, bbs[step])
                e_max, iters_max, fsz_max, nv_max, frac = jax.device_get((
                    jnp.max(e_new), jnp.max(iters), jnp.max(fsize),
                    jnp.max(gb_new.n_valid),
                    jnp.max(tch / jnp.maximum(gb_new.n_valid, 1)
                            .astype(jnp.float32))))
                if int(e_max) <= e_cap:
                    break
                if not grow_capacity:
                    raise FleetCapacityOverflow(step, int(e_max), e_cap)
                # One whale stream outgrew the envelope: re-bucket the
                # WHOLE fleet into the next power-of-two tier (one shared
                # compiled shape, like the ladder's shrink) and replay
                # this step against the pre-apply state.
                e_cap = _pow2_at_least(int(e_max))
                gb = jax.vmap(lambda g: rebucket_capacity(
                    g, n_cap_new=n_cap, e_cap_new=e_cap))(gb)
                fused_t = _fused_for(mode)
                n_regrows += 1
            touched_frac = float(frac)
            if int(iters_max) > 1:
                res = louvain_batched(
                    gb_new, config, init_membership=mem,
                    init_frontier=(frontier if mode is not None else None))
                mem_new = res.membership
            gb, mem = gb_new, mem_new
            frontier_sizes.append(fsize if mode is not None else gb.n_valid)
            stats.append(_step_stat(mode, mode_down, iters_max,
                                    fsz_max if mode is not None else nv_max,
                                    int(nv_max)))
        return gb, mem, frontier_sizes

    # Optimistic pipelined pass: enqueue every fused step back-to-back with
    # NO host round-trip, then validate the collected per-step scalars
    # once.  Warm serving updates virtually always satisfy both checks; a
    # violation redoes the stream through the per-step validated loop (so
    # overflow raises with its step index and non-converged steps get the
    # full pass loop) — results are identical either way.  Host-resolved
    # "auto" screening needs the per-step fetch, so it always takes the
    # validated loop.
    if auto_screen:
        gb, mem, frontier_sizes = serve_carefully(gb, mem)
    else:
        gb_t, mem_t = gb, mem
        fsz_t: List[jax.Array] = []
        its_t: List[jax.Array] = []
        enew_t: List[jax.Array] = []
        nv_t: List[jax.Array] = []
        for step in range(n_steps):
            gb_t, mem_t, _, iters, e_new, fsize, _tch = fused(
                gb_t, mem_t, bbs[step])
            fsz_t.append(fsize if screen_mode is not None else gb_t.n_valid)
            its_t.append(iters)
            enew_t.append(e_new)
            nv_t.append(gb_t.n_valid)
        if n_steps == 0:
            frontier_sizes = []      # idle fleet: warm membership unchanged
        else:
            e_max, iters_max, its_all, fsz_all, nv_all = jax.device_get(
                (jnp.max(jnp.stack(enew_t)), jnp.max(jnp.stack(its_t)),
                 jnp.stack(its_t), jnp.stack(fsz_t), jnp.stack(nv_t)))
            if int(e_max) > e_cap or int(iters_max) > 1:
                gb, mem, frontier_sizes = serve_carefully(gb, mem)
            else:
                gb, mem, frontier_sizes = gb_t, mem_t, fsz_t
                for step in range(n_steps):
                    stats.append(_step_stat(
                        screen_mode, False, its_all[step].max(),
                        fsz_all[step].max(), int(nv_all[step].max())))

    q = None
    if track_modularity:
        q = np.asarray(jax.vmap(modularity)(gb, _pad_sentinel(mem)))
    return BatchedDynamicResult(
        graphs=gb,
        membership=np.asarray(mem),
        n_communities=np.asarray(
            [len(np.unique(np.asarray(mem[s, :int(np.asarray(gb.n_valid)[s])])))
             for s in range(S)]),
        frontier_sizes=(np.asarray(jnp.stack(frontier_sizes))
                        if frontier_sizes else np.zeros((0, S), int)),
        modularity=q,
        total_seconds=time.perf_counter() - t_start,
        n_regrows=n_regrows,
        pass_stats=list(stats),
    )


@jax.jit
def _pad_sentinel(mem: jax.Array) -> jax.Array:
    """(S, n_cap) membership -> (S, n_cap + 1) with the sentinel column."""
    S, n_cap = mem.shape[0], mem.shape[1]
    return jnp.concatenate(
        [mem, jnp.full((S, 1), n_cap, jnp.int32)], axis=1)
