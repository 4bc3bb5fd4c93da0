"""Degree-corrected stochastic block model, on the device from a seed.

The generator family of the IEEE HPEC Streaming Graph Challenge, stochastic
block partition (Kao et al., arXiv:1708.07883): vertices in planted blocks,
each with an expected degree drawn from a truncated power law; an edge's
source is drawn in proportion to expected degree, and its destination, in
proportion to expected degree, inside the source's block with probability
``r / (1 + r)`` (``r`` = in-block to cross-block edges) and in another
block otherwise.  Blocks are contiguous ranges of equal size before the
vertex labels are randomly permuted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sizes(cfg: dict):
    """(vertices, raw edges) of a configuration."""
    return int(cfg["vertices"]), int(cfg["raw_edges"])


@functools.partial(jax.jit, static_argnames=(
    "n", "m", "blocks", "exponent", "degree_min", "degree_max", "ratio"))
def _dcsbm(key, *, n, m, blocks, exponent, degree_min, degree_max, ratio):
    k_deg, k_src, k_in, k_dst, k_perm = jax.random.split(key, 5)
    # Inverse CDF of p(d) ~ d**-exponent on [degree_min, degree_max].
    e1 = 1.0 - exponent
    lo, hi = degree_min ** e1, degree_max ** e1
    theta = (lo + jax.random.uniform(k_deg, (n,)) * (hi - lo)) ** (1.0 / e1)
    cum = jnp.cumsum(theta)
    total = cum[-1]
    start = (jnp.arange(blocks + 1) * n) // blocks          # block ranges
    mass_lo = jnp.where(start[:-1] > 0, cum[start[:-1] - 1], 0.0)
    mass = cum[start[1:] - 1] - mass_lo
    block_of = (jnp.searchsorted(start, jnp.arange(n), side="right")
                - 1).astype(jnp.int32)

    def pick(x):
        return jnp.clip(jnp.searchsorted(cum, x, side="right"), 0, n - 1)

    u = pick(jax.random.uniform(k_src, (m,)) * total)
    bu = block_of[u]
    inside = jax.random.uniform(k_in, (m,)) < ratio / (1.0 + ratio)
    x = jax.random.uniform(k_dst, (m,))
    x_in = mass_lo[bu] + x * mass[bu]
    x_out = x * (total - mass[bu])
    x_out = jnp.where(x_out >= mass_lo[bu], x_out + mass[bu], x_out)
    v = pick(jnp.where(inside, x_in, x_out))
    v = jnp.where(inside, jnp.clip(v, start[bu], start[bu + 1] - 1), v)
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    planted = jnp.zeros((n,), jnp.int32).at[perm].set(block_of)
    return perm[u], perm[v], planted


def raw_edges(cfg: dict, key):
    """(u, v) int32 device arrays of the raw edge list."""
    return _draw(cfg, key)[:2]


def planted_blocks(cfg: dict, key):
    """(vertices,) int32: the planted block of each vertex."""
    return _draw(cfg, key)[2]


def _draw(cfg: dict, key):
    return _dcsbm(key, n=int(cfg["vertices"]), m=int(cfg["raw_edges"]),
                  blocks=int(cfg["blocks"]),
                  exponent=float(cfg["degree_exponent"]),
                  degree_min=float(cfg["degree_min"]),
                  degree_max=float(cfg["degree_max"]),
                  ratio=float(cfg["in_block_ratio"]))
