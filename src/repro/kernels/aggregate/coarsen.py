"""Pallas TPU kernel: group-detect + weight-accumulate for aggregation.

``repro.core.aggregate.aggregate_graph`` coarsens a relabeled edge-slot list
``(C[i], C[j], w)`` into the community graph.  The XLA reference resolves the
post-sort slots with a global cumsum (group ids), a ``segment_sum`` (group
weights) and three scatters (coarse src/dst/w).  This kernel fuses the whole
post-sort reduce into ONE forward sweep over the sorted slots:

    tile t:   is_first   = (ci, cj) != shifted(ci, cj)     (group boundaries)
              open-sum   = segmented inclusive sum-scan of w
              finalize   = at each boundary, emit the group that just ended
                           (its key, its accumulated weight, its position)

The TPU grid is sequential, so cross-tile state (previous slot key, the open
group's partial weight sum, the emitted-group count) rides in SMEM scratch
between programs — the same carry-chain as ``repro.kernels.batch_apply``.
The preceding lexsort and the final scatter into the coarse CSR buffers
remain XLA's job (sorting and dynamic scatter are not TPU-kernel-friendly
primitives); the kernel returns per-slot (emit, pos, src, dst, w) group
records at each finalization point.

Exactness: group positions and keys are integers (always exact).  Group
weights are float32 sums; the in-tile segmented scan accumulates with a
balanced-tree association while XLA's ``segment_sum`` order is
implementation-defined, so the two backends agree bit-for-bit whenever the
sums are exact (integer-valued weights < 2^24 — all golden corpora) and to
float32 rounding otherwise.  ``tests/test_aggregate_kernel.py`` asserts
both regimes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels import inclusive_sum, shift_right

_BLOCK = 512  # lanes per program (multiple of 128)


def _coarsen_kernel(sent: int, ci_ref, cj_ref, w_ref,
                    emit_ref, pos_ref, gsrc_ref, gdst_ref, gw_ref,
                    ckey_ref, copen_ref, ccnt_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        # -2 differs from every real key (keys are in [0, sent]), so the
        # very first slot always opens a group; the phantom "previous
        # group" it finalizes is never emitted (prev_ci == -2).
        ckey_ref[0] = -2
        ckey_ref[1] = -2
        copen_ref[0] = 0.0
        ccnt_ref[0] = 0

    ci = ci_ref[...]                       # (1, T) int32
    cj = cj_ref[...]
    w = w_ref[...].astype(jnp.float32)

    # Lane 0's "previous slot" is the carry from the preceding tile.
    lane0 = jax.lax.broadcasted_iota(jnp.int32, ci.shape, 1) == 0
    prev_ci = jnp.where(lane0, ckey_ref[0], shift_right(ci, 1, 0))
    prev_cj = jnp.where(lane0, ckey_ref[1], shift_right(cj, 1, 0))
    is_first = (ci != prev_ci) | (cj != prev_cj)

    # Segmented inclusive sum-scan (Hillis-Steele): per slot, the weight sum
    # of its group FROM the group's first in-tile slot; slots whose group
    # opened in an earlier tile (no boundary anywhere left of them) add the
    # carried open-group partial sum.  The boundary flag rides in int32
    # lanes (0/1) so every shift is a 32-bit one.
    s, f = w, is_first.astype(jnp.int32)
    d = 1
    while d < ci.shape[1]:
        ps = shift_right(s, d, 0.0)
        pf = shift_right(f, d, 0)
        s = jnp.where(f > 0, s, s + ps)
        f = f | pf
        d *= 2
    open_sum = jnp.where(f > 0, s, s + copen_ref[0])

    # Group finalized at slot i = the group open at slot i - 1.
    prev_open = jnp.where(lane0, copen_ref[0], shift_right(open_sum, 1, 0.0))
    emit = is_first & (prev_ci != sent) & (prev_ci >= 0)

    em = emit.astype(jnp.int32)
    incl = inclusive_sum(em)
    emit_ref[...] = em
    pos_ref[...] = ccnt_ref[0] + incl - em
    gsrc_ref[...] = prev_ci
    gdst_ref[...] = prev_cj
    gw_ref[...] = prev_open

    last = ci.shape[1] - 1
    ckey_ref[0] = ci[0, last]
    ckey_ref[1] = cj[0, last]
    copen_ref[0] = open_sum[0, last]
    ccnt_ref[0] = ccnt_ref[0] + incl[0, last]


@functools.partial(jax.jit, static_argnames=("sent", "block", "interpret"))
def coarsen_groups_pallas(
    s_ci: jax.Array,       # (total,) int32 — (ci, cj)-lexsorted src labels
    s_cj: jax.Array,       # (total,) int32 — dst labels in the same order
    s_w: jax.Array,        # (total,) f32 — slot weights in sorted order
    *,
    sent: int,
    block: int = _BLOCK,
    interpret: bool | None = None,
) -> Tuple[jax.Array, ...]:
    """Per-slot group-finalization records over a sorted relabeled slot list.

    Returns (emit, pos, g_src, g_dst, g_w), each of padded length >=
    total + 1 (at least one trailing sentinel pad slot guarantees the last
    live group finalizes).  ``emit`` marks one slot per live group; ``pos``
    is its dense group index (== the sort path's ``gid``, since live groups
    sort before sentinel padding); ``g_w`` its accumulated weight.
    """
    if interpret is None:
        interpret = kernels.interpret_mode()
    total = s_ci.shape[0]
    tiles = total // block + 1             # >= 1 trailing pad slot, always
    padded = tiles * block

    # One (1, padded) row: a (1, block) block spans the whole first dim,
    # which the TPU's (8, 128) block rule accepts; a (tiles, block) layout
    # with (1, block) blocks it refuses.
    def pad(x, fill, dtype):
        return jnp.concatenate(
            [x.astype(dtype), jnp.full((padded - total,), fill, dtype)]
        ).reshape(1, padded)

    ins = (pad(s_ci, sent, jnp.int32), pad(s_cj, sent, jnp.int32),
           pad(s_w, 0.0, jnp.float32))

    row = pl.BlockSpec((1, block), lambda i: (0, i))
    out_shape = (
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # emit
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # pos
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # group src
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # group dst
        jax.ShapeDtypeStruct((1, padded), jnp.float32),  # group weight
    )
    scratch = [pltpu.SMEM((2,), jnp.int32),     # prev slot key (ci, cj)
               pltpu.SMEM((1,), jnp.float32),   # open-group partial sum
               pltpu.SMEM((1,), jnp.int32)]     # emitted-group count

    outs = pl.pallas_call(
        functools.partial(_coarsen_kernel, sent),
        grid=(tiles,),
        in_specs=[row, row, row],
        out_specs=[row] * 5,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*ins)
    emit, pos, gsrc, gdst, gw = (o.reshape(-1) for o in outs)
    return emit > 0, pos, gsrc, gdst, gw
