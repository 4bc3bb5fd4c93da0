"""Graph 500 Kronecker generator, on the device from a seed.

The Graph 500 specification's generator (graph500.org, "Graph 500
Benchmark", Kronecker generator; the reference ``kronecker_generator.m``):
``edge_factor * 2**scale`` edges, each placed bit by bit in the quadrants
of the adjacency matrix with probabilities A, B, C and D = 1 - A - B - C,
then the vertex labels are randomly permuted so that the hubs are spread
over the id range.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def sizes(cfg: dict):
    """(vertices, raw edges) of a configuration."""
    n = 1 << int(cfg["scale"])
    return n, int(cfg["edge_factor"]) * n


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "a",
                                             "b", "c"))
def _kronecker(key, *, scale, edge_factor, a, b, c):
    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def bit(i, uv):
        u, v = uv
        ii = jax.random.uniform(jax.random.fold_in(key, 2 * i), (m,)) > ab
        thr = jnp.where(ii, c_norm, a_norm)
        jj = jax.random.uniform(jax.random.fold_in(key, 2 * i + 1), (m,)) > thr
        return (u + (ii.astype(jnp.int32) << i),
                v + (jj.astype(jnp.int32) << i))

    zeros = jnp.zeros((m,), jnp.int32)
    u, v = jax.lax.fori_loop(0, scale, bit, (zeros, zeros))
    perm = jax.random.permutation(jax.random.fold_in(key, 2 * scale + 1), n)
    perm = perm.astype(jnp.int32)
    return perm[u], perm[v]


def raw_edges(cfg: dict, key):
    """(u, v) int32 device arrays of the raw edge list (with duplicates and
    self loops, as the specification's generator emits them)."""
    return _kronecker(key, scale=int(cfg["scale"]),
                      edge_factor=int(cfg["edge_factor"]),
                      a=float(cfg["a"]), b=float(cfg["b"]),
                      c=float(cfg["c"]))
