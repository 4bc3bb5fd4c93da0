"""Pallas-ELL scanner backend + its local-moving adapter.

Vertices are degree-bucketed into fixed-width ELL tiles (graph.to_ell_blocks)
— the TPU analogue of the paper's dynamic load-balanced schedule — and each
tile's best-move scan runs in the fused Pallas kernel.  Hub vertices whose
degree exceeds the largest ELL width fall back to the sort-reduce scan.

The round/sweep loop lives in ``repro.core.engine.MoveEngine``; this module
contributes only the ELL **scanner** and the host-side wrapper.  The compiled
loop is cached per static configuration (``_ell_runner``) — blocks and
leftover ids are passed as jit *arguments*, so repeated calls with the same
shapes reuse one executable instead of re-jitting per invocation (the old
``jax.jit(lambda s: ...)``-per-call bug).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import kernels
from repro.core.engine import (ConstrainedScanner, EngineConfig, MoveEngine,
                               MoveState, gated_move_mask,
                               mask_cross_outer_slots, round_gate,
                               sanitize_outer)
from repro.core.graph import CSRGraph, ELLBlock, to_ell_blocks
from repro.core.local_move import SortReduceScanner, best_moves
from repro.core.modularity import community_weights
from repro.kernels.louvain_scan import ops as scan_ops


class ELLScanner(SortReduceScanner):
    """Engine backend: Pallas ELL scan tiles + sort-reduce hub fallback.

    Topology hooks (identity) and ``mark_neighbors`` come from the
    sort-reduce scanner; only the best-move scan differs.
    """

    def __init__(self, graph: CSRGraph, blocks, leftover, k, m, *,
                 use_pallas: bool, interpret: bool):
        super().__init__(graph, k, m)
        self.blocks = blocks
        self.leftover = leftover        # (n_leftover,) int32; may be empty
        self.use_pallas = use_pallas
        self.interpret = interpret

    def scan(self, comm, sigma, frontier) -> Tuple[jax.Array, jax.Array]:
        graph, k, m = self.graph, self.k_local, self.m
        n_cap = graph.n_cap
        best_c = jnp.full((n_cap + 1,), n_cap, jnp.int32)
        best_dq = jnp.full((n_cap + 1,), -jnp.inf, jnp.float32)

        for block in self.blocks:
            ins = scan_ops.prepare_ell_inputs(block, comm, sigma, k, n_cap)
            bc, bdq = scan_ops.louvain_scan(
                *ins, m, use_pallas=self.use_pallas, interpret=self.interpret
            )
            bc = jnp.where(bc < 0, n_cap, bc)
            # Pad rows carry vertex id n_cap -> land in the sentinel slot.
            best_c = best_c.at[block.rows].set(bc)
            best_dq = best_dq.at[block.rows].set(bdq)

        if self.leftover.shape[0]:
            sc, sdq = best_moves(graph, comm, sigma, k, frontier, m)
            best_c = best_c.at[self.leftover].set(sc[self.leftover])
            best_dq = best_dq.at[self.leftover].set(sdq[self.leftover])

        # Frontier-gate: non-frontier vertices must not move.
        best_dq = jnp.where(frontier, best_dq, -jnp.inf)
        best_c = best_c.at[n_cap].set(n_cap)
        return best_c, best_dq


class FusedELLScanner(ELLScanner):
    """Engine backend: the FUSED Pallas scan+apply round on ELL tiles.

    Supplies the engine's optional ``decide_moves`` hook: each tile leaves
    the fused kernel with its whole move decision made (scan + improvement
    test + in-kernel round gate + singleton guard + frontier mask), so the
    engine skips its generic gate/guard recompute — one kernel trip per tile
    instead of scan kernel + XLA apply round-trip.  Hub vertices beyond the
    widest ELL tile take the sort-reduce scan + the engine's own
    ``gated_move_mask`` — the same boolean the kernel computes, so the two
    halves compose bit-identically with the scan-only path.
    """

    def __init__(self, graph: CSRGraph, blocks, leftover, k, m, *,
                 use_pallas: bool, interpret: bool, gate_fraction: int):
        super().__init__(graph, blocks, leftover, k, m,
                         use_pallas=use_pallas, interpret=interpret)
        self.gate_fraction = gate_fraction

    def decide_moves(self, comm, sigma, frontier, comm_l, sizes, round_ix):
        graph, k, m = self.graph, self.k_local, self.m
        n_cap = graph.n_cap
        front = frontier & self._valid          # frontier & move-valid
        best_c = jnp.full((n_cap + 1,), n_cap, jnp.int32)
        best_dq = jnp.full((n_cap + 1,), -jnp.inf, jnp.float32)
        do_move = jnp.zeros((n_cap + 1,), bool)

        for block in self.blocks:
            ins = scan_ops.prepare_fused_inputs(block, comm, sigma, sizes,
                                                k, front, n_cap)
            bc, bdq, mv = scan_ops.louvain_fused(
                *ins, m, round_ix, gate_fraction=self.gate_fraction,
                sentinel=n_cap, use_pallas=self.use_pallas,
                interpret=self.interpret)
            # Pad rows carry vertex id n_cap -> land in the sentinel slot.
            best_c = best_c.at[block.rows].set(bc)
            best_dq = best_dq.at[block.rows].set(bdq)
            do_move = do_move.at[block.rows].set(mv > 0)

        if self.leftover.shape[0]:
            sc, sdq = best_moves(graph, comm, sigma, k, frontier, m)
            gate = (round_gate(self.local_ids, round_ix, self.gate_fraction)
                    if self.gate_fraction > 1 else None)
            mv_all = gated_move_mask(sc, sdq, comm_l, sizes, frontier, n_cap,
                                     self.move_valid, gate)
            best_c = best_c.at[self.leftover].set(sc[self.leftover])
            best_dq = best_dq.at[self.leftover].set(
                jnp.where(front[self.leftover], sdq[self.leftover],
                          -jnp.inf))
            do_move = do_move.at[self.leftover].set(mv_all[self.leftover])

        best_c = best_c.at[n_cap].set(n_cap)
        do_move = do_move.at[n_cap].set(False)
        return do_move, best_c, best_dq


def _mask_blocks_cross_outer(blocks, outer, n_cap: int):
    """On-device ELL analogue of ``engine.mask_cross_outer_slots``: slots
    whose endpoints disagree on the outer label become padding (col = n_cap,
    w = 0), which ``prepare_ell_inputs`` already treats as dead."""
    masked = []
    for b in blocks:
        row_o = outer[jnp.minimum(b.rows, n_cap)][:, None]
        col_o = outer[jnp.minimum(b.cols, n_cap)]
        cross = row_o != col_o
        masked.append(ELLBlock(b.rows,
                               jnp.where(cross, n_cap, b.cols),
                               jnp.where(cross, 0.0, b.w)))
    return tuple(masked)


@functools.lru_cache(maxsize=None)
def _ell_runner(n_blocks: int, use_pallas: bool, interpret: bool,
                max_iterations: int, use_pruning: bool, gate_fraction: int,
                fused: bool = False, refine: bool = False):
    """One jit'd engine loop per static config; graph/blocks are arguments
    (not closure constants), so calls with equal shapes share the executable."""
    config = EngineConfig(max_iterations=max_iterations,
                          use_pruning=use_pruning,
                          gate_fraction=gate_fraction)

    @jax.jit
    def run(graph, blocks, leftover, k, m, comm0, sigma0, frontier0,
            tolerance, outer=None):
        if refine:
            outer_s = sanitize_outer(outer, graph.n_valid, graph.n_cap)
            dst, w = mask_cross_outer_slots(
                graph.src, graph.indices, graph.weights, outer_s,
                graph.n_cap)
            graph = graph._replace(indices=dst, weights=w)
            blocks = _mask_blocks_cross_outer(blocks, outer_s, graph.n_cap)
        if fused:
            scanner = FusedELLScanner(graph, blocks, leftover, k, m,
                                      use_pallas=use_pallas,
                                      interpret=interpret,
                                      gate_fraction=gate_fraction)
        else:
            scanner = ELLScanner(graph, blocks, leftover, k, m,
                                 use_pallas=use_pallas, interpret=interpret)
        if refine:
            scanner = ConstrainedScanner(scanner, outer_s, graph.n_valid,
                                         gate_fraction=gate_fraction)
        st = MoveEngine(scanner, config).run(comm0, sigma0, frontier0,
                                             tolerance)
        return st.comm, st.iters, st.dq_sum

    return run


def move_phase_ell(
    graph: CSRGraph,
    tolerance: jax.Array,
    *,
    max_iterations: int = 20,
    use_pruning: bool = True,
    gate_fraction: int = 2,
    widths: Tuple[int, ...] = (16, 64, 256),
    use_pallas: bool = True,
    interpret: bool | None = None,
    comm0: jax.Array | None = None,
    sigma0: jax.Array | None = None,
    frontier0: jax.Array | None = None,
    fused: bool = False,
    refine_outer: jax.Array | None = None,
):
    """ELL-kernel local-moving phase: returns (comm, iters, dq_sum).

    Host-side wrapper: buckets the graph once, then runs the cached jit'd
    engine loop.  ``comm0``/``sigma0``/``frontier0`` warm-start the sweep
    from an arbitrary membership snapshot (defaults: singleton start over
    all valid vertices), mirroring the sort-reduce ``_move_phase``.
    ``fused=True`` runs the fused scan+apply kernel (``FusedELLScanner``)
    instead of the scan-only kernel + engine apply — same memberships, bit
    for bit.  ``refine_outer`` runs the Leiden-style constrained sweep
    instead (see ``local_move.louvain_move``): blocks and leftover slots
    are masked on device, so the host-side bucketing is reused as-is.
    """
    if interpret is None:
        interpret = kernels.interpret_mode()
    blocks, leftover_np = to_ell_blocks(graph, widths)
    leftover = jnp.asarray(leftover_np)

    n_cap = graph.n_cap
    k = graph.vertex_weights()
    m = graph.total_weight()
    valid = jnp.arange(n_cap + 1) < graph.n_valid
    if comm0 is None:
        comm0 = jnp.arange(n_cap + 1, dtype=jnp.int32)
        if sigma0 is None:
            sigma0 = k               # singleton start: Sigma_c == K_i
    elif sigma0 is None:
        # Derive Sigma from the warm membership — defaulting to k here
        # would silently pair a non-singleton C with singleton weights.
        sigma0 = community_weights(graph, comm0)
    frontier0 = valid if frontier0 is None else (frontier0 & valid)

    run = _ell_runner(len(blocks), use_pallas, interpret,
                      max_iterations, use_pruning, gate_fraction, fused,
                      refine_outer is not None)
    if refine_outer is not None:
        return run(graph, tuple(blocks), leftover, k, m, comm0, sigma0,
                   frontier0, jnp.float32(tolerance), refine_outer)
    return run(graph, tuple(blocks), leftover, k, m, comm0, sigma0,
               frontier0, jnp.float32(tolerance))
