"""Pallas TPU kernel: group-resolve scan for the edge-batch sort-reduce.

``repro.core.delta.sort_reduce_apply_slots`` — the single shared core of
both the single-device CSR batch apply and the per-shard sharded apply —
resolves a (src, dst)-sorted unified slot list into per-edge groups:
each group's last slot wins (batch slots outrank existing ones), live
groups compact into the output capacity, and groups whose resolved weight
changed report their endpoints.  The XLA reference expresses this with
five segment_* reductions plus two global cumsums over the full slot list;
this kernel fuses the whole post-sort resolve into ONE forward scan:

    tile t:   is_first  = key != shifted(key)           (group boundaries)
              open-first = segmented copy-scan of (w, is_batch)
              finalize   = at each boundary, emit the group that just ended
              pos        = running kept-group prefix (carried in SMEM)

The TPU grid is sequential, so cross-tile state (previous slot, open-group
first values, kept-count prefix) rides in SMEM scratch between programs —
the same pattern as a carry-chained prefix sum.  All emitted weights are
*selected*, never summed, so the kernel output is bit-for-bit identical to
the XLA path (asserted by tests/test_batch_apply_kernel.py).

The scatter into compacted output slots and the preceding lexsort remain
XLA's job (dynamic scatter is not a TPU-kernel-friendly primitive); the
kernel returns per-slot (keep, pos, src, dst, w, changed) records at each
group-finalization point.
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


def _resolve_kernel(sent: int, src_ref, dst_ref, w_ref, batch_ref,
                    keep_ref, pos_ref, fsrc_ref, fdst_ref, fw_ref, chg_ref,
                    ckey_ref, clastw_ref, clastb_ref, copenw_ref, copenb_ref,
                    ckept_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        # -2 differs from every real key (keys are in [0, sent]), so the
        # very first slot always opens a group; the phantom "previous
        # group" it finalizes has w = 0 / batch = 0 -> never kept/changed.
        ckey_ref[0] = -2
        ckey_ref[1] = -2
        clastw_ref[0] = 0.0
        clastb_ref[0] = 0
        copenw_ref[0] = 0.0
        copenb_ref[0] = 0
        ckept_ref[0] = 0

    src = src_ref[...]                     # (1, T) int32
    dst = dst_ref[...]
    w = w_ref[...].astype(jnp.float32)
    b = batch_ref[...]                     # (1, T) int32 0/1

    # Lane 0's "previous slot" is the carry from the preceding tile.
    lane0 = jax.lax.broadcasted_iota(jnp.int32, src.shape, 1) == 0
    prev_src = jnp.where(lane0, ckey_ref[0], shift_right(src, 1, 0))
    prev_dst = jnp.where(lane0, ckey_ref[1], shift_right(dst, 1, 0))
    prev_w = jnp.where(lane0, clastw_ref[0], shift_right(w, 1, 0.0))
    prev_b = jnp.where(lane0, clastb_ref[0], shift_right(b, 1, 0))
    is_first = (src != prev_src) | (dst != prev_dst)

    # Segmented copy-scan (Hillis-Steele): per slot, the (w, batch) of the
    # first slot of the group CONTAINING it; unanchored slots (group opened
    # in an earlier tile) fall back to the carried open-group state.  The
    # anchor flag rides in int32 lanes (0/1) so every shift is 32-bit.
    fw, fb, anch = w, b, is_first.astype(jnp.int32)
    d = 1
    while d < src.shape[1]:
        pfw = shift_right(fw, d, 0.0)
        pfb = shift_right(fb, d, 0)
        panch = shift_right(anch, d, 0)
        fw = jnp.where(anch > 0, fw, pfw)
        fb = jnp.where(anch > 0, fb, pfb)
        anch = anch | panch
        d *= 2
    open_fw = jnp.where(anch > 0, fw, copenw_ref[0])
    open_fb = jnp.where(anch > 0, fb, copenb_ref[0])

    # Group finalized at slot i = the group open at slot i - 1.
    prev_open_fw = jnp.where(lane0, copenw_ref[0],
                             shift_right(open_fw, 1, 0.0))
    prev_open_fb = jnp.where(lane0, copenb_ref[0],
                             shift_right(open_fb, 1, 0))

    new_w = prev_w                                   # last slot wins
    old_w = jnp.where(prev_open_fb == 1, 0.0, prev_open_fw)
    live = prev_src != sent
    keep = is_first & live & (new_w > 0.0)
    # Batch slots outrank existing, so "group contains a batch slot" is
    # exactly "its last slot is a batch slot".
    changed = is_first & live & (prev_b == 1) & (old_w != new_w)

    kp = keep.astype(jnp.int32)
    incl = inclusive_sum(kp)
    keep_ref[...] = kp
    pos_ref[...] = ckept_ref[0] + incl - kp
    fsrc_ref[...] = prev_src
    fdst_ref[...] = prev_dst
    fw_ref[...] = new_w
    chg_ref[...] = changed.astype(jnp.int32)

    last = src.shape[1] - 1
    ckey_ref[0] = src[0, last]
    ckey_ref[1] = dst[0, last]
    clastw_ref[0] = w[0, last]
    clastb_ref[0] = b[0, last]
    copenw_ref[0] = open_fw[0, last]
    copenb_ref[0] = open_fb[0, last]
    ckept_ref[0] = ckept_ref[0] + incl[0, last]


@functools.partial(jax.jit, static_argnames=("sent", "block", "interpret"))
def resolve_groups_pallas(
    s_src: jax.Array,      # (total,) int32 — (src, dst)-sorted keys
    s_dst: jax.Array,      # (total,) int32
    s_w: jax.Array,        # (total,) f32 — slot weights in sorted order
    s_batch: jax.Array,    # (total,) bool — batch-slot flags
    *,
    sent: int,
    block: int = _BLOCK,
    interpret: bool | None = None,
) -> Tuple[jax.Array, ...]:
    """Per-slot group-finalization records over a sorted slot list.

    Returns (keep, pos, src, dst, w, changed), each of padded length
    >= total + 1 (at least one sentinel pad slot guarantees the last real
    group finalizes).  ``keep`` marks one slot per surviving group; ``pos``
    is its compaction position; ``changed`` marks one slot per group whose
    resolved weight differs from its pre-batch weight.
    """
    if interpret is None:
        interpret = kernels.interpret_mode()
    total = s_src.shape[0]
    tiles = total // block + 1             # >= 1 trailing pad slot, always
    padded = tiles * block

    # One (1, padded) row, as in ``repro.kernels.aggregate``: (1, block)
    # blocks then span the whole first dim, which the TPU accepts.
    def pad(x, fill, dtype):
        return jnp.concatenate(
            [x.astype(dtype), jnp.full((padded - total,), fill, dtype)]
        ).reshape(1, padded)

    ins = (pad(s_src, sent, jnp.int32), pad(s_dst, sent, jnp.int32),
           pad(s_w, 0.0, jnp.float32), pad(s_batch, 0, jnp.int32))

    row = pl.BlockSpec((1, block), lambda i: (0, i))
    out_shape = (
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # keep
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # pos
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # src
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # dst
        jax.ShapeDtypeStruct((1, padded), jnp.float32),  # w
        jax.ShapeDtypeStruct((1, padded), jnp.int32),    # changed
    )
    scratch = [pltpu.SMEM((2,), jnp.int32),     # prev slot key
               pltpu.SMEM((1,), jnp.float32),   # prev slot w
               pltpu.SMEM((1,), jnp.int32),     # prev slot batch
               pltpu.SMEM((1,), jnp.float32),   # open-group first w
               pltpu.SMEM((1,), jnp.int32),     # open-group first batch
               pltpu.SMEM((1,), jnp.int32)]     # kept-count prefix

    outs = pl.pallas_call(
        functools.partial(_resolve_kernel, sent),
        grid=(tiles,),
        in_specs=[row, row, row, row],
        out_specs=[row] * 6,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*ins)
    keep, pos, fsrc, fdst, fw, chg = (o.reshape(-1) for o in outs)
    return keep > 0, pos, fsrc, fdst, fw, chg > 0
