"""The Pallas kernels of the main path compile for a TPU v5e at real sizes.

Nothing runs: each kernel is lowered and compiled for a chip that is
described (``v5e:2x2``) but not attached, which is where the TPU's block
rules and Mosaic's lowering refuse what interpret mode accepts.  The
topology is described inside a fixture, never at import, because only one
process at a time may load the TPU library; a host that cannot describe it
skips these tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aggregate import coarsen_groups_pallas
from repro.kernels.batch_apply import resolve_groups_pallas
from repro.kernels.louvain_scan import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_coarsen_compiles_at_2_22_slots(one_chip):
    n = 1 << 22
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda a, b, w: coarsen_groups_pallas(
        a, b, w, sent=n, interpret=False), i32, i32, f32)
    assert "tpu_custom_call" in text


def test_resolve_compiles_at_stream_buffer_size(one_chip):
    # e_cap existing slots plus 2 * b_cap directed batch slots.
    n = (1 << 22) + 2 * 4096
    S = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    text = _compiled_text(lambda a, b, w, f: resolve_groups_pallas(
        a, b, w, f, sent=1 << 21, interpret=False),
        S(jnp.int32), S(jnp.int32), S(jnp.float32), S(jnp.bool_))
    assert "tpu_custom_call" in text


# 264 rows at width 16 is the regression case: shrinking the block to a
# divisor of R once picked 132 rows, which the TPU refuses (not a multiple
# of 8); the block is now padded to two 256-row tiles.  8 rows is the
# one-tile block of a small graph's sparse width buckets.
@pytest.mark.parametrize("width,live_rows", [(16, 4096), (64, 1024),
                                             (256, 512), (16, 264), (64, 8)])
def test_fused_ell_compiles(one_chip, width, live_rows):
    rows = ops.padded_rows(live_rows, width)

    def S(cols, dt):
        return jax.ShapeDtypeStruct((rows, cols), dt, sharding=one_chip)

    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
    shapes = (S(width, jnp.int32), S(width, jnp.float32),
              S(width, jnp.float32), S(width, jnp.int32), S(1, jnp.float32),
              S(1, jnp.int32), S(1, jnp.float32), S(1, jnp.int32),
              S(1, jnp.int32), S(1, jnp.int32), scalar(jnp.float32),
              scalar(jnp.int32))
    tile = ops.tile_rows(rows, width)
    assert tile % 8 == 0 and rows % tile == 0
    text = _compiled_text(lambda *a: ops.louvain_fused(
        *a, gate_fraction=2, sentinel=1 << 20, interpret=False), *shapes)
    assert "tpu_custom_call" in text
