"""Jit'd public wrapper for the Louvain ELL scan kernel.

`louvain_scan` dispatches to the Pallas kernel (compiled on a TPU, interpreted
on the CPU — ``repro.kernels.interpret_mode``) or the pure-jnp reference,
choosing VMEM-safe block shapes per ELL width.
`prepare_ell_inputs` builds the pre-gathered per-slot arrays from graph state
(the gathers are XLA's job — Pallas TPU kernels keep to dense tiles).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import kernels
from repro.core.graph import ELLBlock
from repro.kernels.louvain_scan.fused import (louvain_fused_pallas,
                                              louvain_fused_ref)
from repro.kernels.louvain_scan.louvain_scan import louvain_scan_pallas
from repro.kernels.louvain_scan.ref import louvain_scan_ref

# width -> most rows per program, keeping the (B, D, D) compare tile +
# operands comfortably inside ~4 MB of VMEM (paper-analogue of Far-KV
# sizing).  Every entry is a multiple of 8, the TPU's sublane tile; 256 is
# the widest tile, since even 8 rows of a wider one overflow VMEM.
_BLOCK_ROWS = {16: 256, 64: 64, 256: 8}


def block_rows_for_width(width: int) -> int:
    for w_key, rows in _BLOCK_ROWS.items():
        if width <= w_key:
            return rows
    raise ValueError(f"ELL width {width} exceeds the widest kernel tile "
                     f"({max(_BLOCK_ROWS)})")


def padded_rows(n: int, d: int) -> int:
    """Rows of a width-``d`` ELL block holding ``n`` vertices.

    ``n`` rounded up to 8 (the TPU's sublane tile), then to a multiple of
    ``tile_rows``: at most one tile of padding rows per width.
    """
    r8 = -(-max(n, 1) // 8) * 8
    tile = min(block_rows_for_width(d), r8)
    return -(-r8 // tile) * tile


def tile_rows(r: int, d: int) -> int:
    """Rows per program for an (R, D) ELL block: the width's cap, or all of
    R when R is smaller.  ``padded_rows`` makes R a multiple of it."""
    return min(block_rows_for_width(d), r)


def prepare_ell_inputs(
    block: ELLBlock,
    comm: jax.Array,       # (n_cap + 1,) int32
    sigma: jax.Array,      # (n_cap + 1,) f32
    k: jax.Array,          # (n_cap + 1,) f32
    n_cap: int,
) -> Tuple[jax.Array, ...]:
    """Gather per-slot community state for one ELL block (outside the kernel)."""
    rows, cols, w = block.rows, block.cols, block.w
    dead = (cols == n_cap) | (cols == rows[:, None])   # padding or self-loop
    c_nbr = jnp.where(dead, -1, comm[cols])
    w_nbr = jnp.where(dead, 0.0, w).astype(jnp.float32)
    sigma_nbr = jnp.where(dead, 0.0, sigma[jnp.maximum(c_nbr, 0)]).astype(jnp.float32)
    k_i = k[rows][:, None].astype(jnp.float32)
    c_own = comm[rows][:, None]
    sigma_own = sigma[c_own[:, 0]][:, None].astype(jnp.float32)
    return c_nbr, w_nbr, sigma_nbr, k_i, c_own, sigma_own


def prepare_fused_inputs(
    block: ELLBlock,
    comm: jax.Array,       # (n_cap + 1,) int32
    sigma: jax.Array,      # (n_cap + 1,) f32
    sizes: jax.Array,      # (n_cap + 1,) int32 — |community| per id
    k: jax.Array,          # (n_cap + 1,) f32
    front: jax.Array,      # (n_cap + 1,) bool — frontier & move-valid
    n_cap: int,
) -> Tuple[jax.Array, ...]:
    """Per-slot state for the fused scan+apply kernel (gathers stay in XLA).

    Extends ``prepare_ell_inputs`` with the decision inputs: per-slot and
    per-row community sizes (the singleton-swap guard), the row's global
    vertex id (the in-kernel round gate) and its frontier/validity bit.
    """
    c_nbr, w_nbr, sigma_nbr, k_i, c_own, sigma_own = prepare_ell_inputs(
        block, comm, sigma, k, n_cap)
    dead = c_nbr < 0
    size_nbr = jnp.where(dead, 0,
                         sizes[jnp.maximum(c_nbr, 0)]).astype(jnp.int32)
    size_own = sizes[c_own[:, 0]][:, None].astype(jnp.int32)
    rows = block.rows[:, None].astype(jnp.int32)
    front_rows = front[block.rows][:, None].astype(jnp.int32)
    return (c_nbr, w_nbr, sigma_nbr, size_nbr, k_i, c_own, sigma_own,
            size_own, rows, front_rows)


def louvain_fused(
    c_nbr: jax.Array,
    w_nbr: jax.Array,
    sigma_nbr: jax.Array,
    size_nbr: jax.Array,
    k_i: jax.Array,
    c_own: jax.Array,
    sigma_own: jax.Array,
    size_own: jax.Array,
    rows: jax.Array,
    front: jax.Array,
    m: jax.Array,
    round_ix: jax.Array,
    *,
    gate_fraction: int,
    sentinel: int,
    use_pallas: bool = True,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused (best_c, best_dq, do_move) per ELL row.  See fused.py."""
    if not use_pallas:
        return louvain_fused_ref(
            c_nbr, w_nbr, sigma_nbr, size_nbr, k_i, c_own, sigma_own,
            size_own, rows, front, m, round_ix,
            gate_fraction=gate_fraction, sentinel=sentinel)
    if interpret is None:
        interpret = kernels.interpret_mode()
    rows_per = block_rows or tile_rows(*c_nbr.shape)
    out_c, out_dq, out_mv = louvain_fused_pallas(
        c_nbr, w_nbr, sigma_nbr, size_nbr, k_i, c_own, sigma_own, size_own,
        rows, front, m, round_ix, gate_fraction=gate_fraction,
        sentinel=sentinel, block_rows=rows_per, interpret=interpret)
    return out_c[:, 0], out_dq[:, 0], out_mv[:, 0]


def louvain_scan(
    c_nbr: jax.Array,
    w_nbr: jax.Array,
    sigma_nbr: jax.Array,
    k_i: jax.Array,
    c_own: jax.Array,
    sigma_own: jax.Array,
    m: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    block_rows: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Best (community, dQ) per ELL row.  See ref.py for exact semantics."""
    if not use_pallas:
        return louvain_scan_ref(c_nbr, w_nbr, sigma_nbr, k_i, c_own, sigma_own, m)
    if interpret is None:
        interpret = kernels.interpret_mode()
    rows = block_rows or tile_rows(*c_nbr.shape)
    out_c, out_dq = louvain_scan_pallas(
        c_nbr, w_nbr, sigma_nbr, k_i, c_own, sigma_own, m,
        block_rows=rows, interpret=interpret,
    )
    return out_c[:, 0], out_dq[:, 0]
