"""Bytes a kernel call moves, computed from its shapes.

``kernels/aggregate/coarsen.py`` (``coarsen_groups_pallas``) reads three
``(1, padded)`` rows (the sorted source labels, destination labels, both
int32, and the float32 weights) and writes five ``(1, padded)`` 4-byte rows
(emit, position, group source, group destination, group weight), with
``padded = (total // block + 1) * block`` for ``total`` input slots and the
kernel's block of 512 lanes.  Each byte is read or written once.
"""

COARSEN_BLOCK = 512
COARSEN_IN_BYTES = (4, 4, 4)            # int32, int32, float32 rows
COARSEN_OUT_BYTES = (4, 4, 4, 4, 4)     # emit, pos, src, dst, w rows


def coarsen_padded(total: int, block: int = COARSEN_BLOCK) -> int:
    return (int(total) // block + 1) * block


def coarsen_bytes(total: int, block: int = COARSEN_BLOCK) -> int:
    """HBM bytes of one coarsen call over ``total`` sorted slots."""
    per_slot = sum(COARSEN_IN_BYTES) + sum(COARSEN_OUT_BYTES)
    return per_slot * coarsen_padded(total, block)
