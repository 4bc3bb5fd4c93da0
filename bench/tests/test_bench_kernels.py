"""Kernel bytes computed from shapes match the kernel as it stands, and the
peak table refuses a device it does not know."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import kernels, peaks
from repro.kernels.aggregate.coarsen import coarsen_groups_pallas


def _pallas_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _pallas_eqn(sub)
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("total", [1, 511, 512, 4096, 8_388_608])
def test_coarsen_bytes_match_the_kernel_operands(total):
    args = (jax.ShapeDtypeStruct((total,), jnp.int32),
            jax.ShapeDtypeStruct((total,), jnp.int32),
            jax.ShapeDtypeStruct((total,), jnp.float32))
    closed = jax.make_jaxpr(
        lambda a, b, c: coarsen_groups_pallas(a, b, c, sent=total,
                                              interpret=True))(*args)
    eqn = _pallas_eqn(closed.jaxpr)
    assert eqn is not None
    nbytes = sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                 for v in list(eqn.invars) + list(eqn.outvars))
    assert kernels.coarsen_bytes(total) == nbytes
    assert kernels.coarsen_padded(total) == eqn.outvars[0].aval.shape[-1]


def test_peaks_know_v5e_and_refuse_others():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert peaks.peak("TPU v5 lite", "hbm_bytes") == 16e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")
