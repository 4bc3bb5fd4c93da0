"""Pallas TPU kernels of the Louvain hot path, and what they share.

``interpret_mode`` is the one place that decides whether a kernel runs
compiled or in the Pallas interpreter.  ``shift_right`` and
``inclusive_sum`` are the lane primitives of the carry-chained scans in
``aggregate`` and ``batch_apply``; both keep to ops Mosaic lowers (lane
concatenates of 32-bit vectors, no ``cumsum``, no bool shifts).
"""

import jax
import jax.numpy as jnp


def interpret_mode() -> bool:
    """True on the CPU (the interpret-mode tests), False on a TPU.

    Any other platform raises: the kernels are written for the TPU, and a
    silent fall back to the interpreter would hide which device ran them.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (compiled) or 'cpu' (interpreted); "
        f"the default backend is {platform!r}")


def shift_right(x: jax.Array, d: int, fill) -> jax.Array:
    """(1, T) lane shift by ``d`` with constant fill on the left.

    ``x`` must be 32-bit: Mosaic refuses the vector casts a bool shift needs.
    """
    return jnp.concatenate(
        [jnp.full((1, d), fill, x.dtype), x[:, :-d]], axis=1)


def inclusive_sum(x: jax.Array) -> jax.Array:
    """(1, T) int32 inclusive prefix sum along lanes (Hillis-Steele).

    Exact for integers, so it equals ``jnp.cumsum(x, axis=1)``, which
    Mosaic does not lower.
    """
    d = 1
    while d < x.shape[1]:
        x = x + shift_right(x, d, 0)
        d *= 2
    return x
