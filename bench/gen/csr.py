"""On-device CSR build of an undirected graph, with a held-out stream pool.

From raw endpoint pairs (self loops and duplicates allowed, as generators
emit them) it builds the program's ``CSRGraph`` to the slot contract of
``repro.core.graph``: each distinct undirected edge {u, v}, u != v, becomes
the two directed slots (u, v, 1) and (v, u, 1), sorted by (src, dst);
padding slots hold the sentinel ``n`` and weight 0.  Self loops are dropped
and duplicates collapse to one edge of weight 1.

For a stream, distinct edges drawn from the seed are held out of the graph:
``n_insert`` of them, or ``hold_share`` of all distinct edges where that is
more (the edges still to emerge).  The first ``n_insert`` held-out edges are
the insertions to come, and the next ``n_delete`` distinct edges, still in
the graph, are the deletions to come.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.graph import CSRGraph


@functools.partial(jax.jit,
                   static_argnames=("n", "e_cap", "n_insert", "n_delete",
                                    "hold_share"))
def build(u, v, key, *, n: int, e_cap: int, n_insert: int = 0,
          n_delete: int = 0, hold_share: float = 0.0):
    """(graph, inserts (n_insert, 2), deletes (n_delete, 2), n_distinct,
    n_held).

    ``n_distinct`` counts the distinct undirected edges of the draw and
    ``n_held`` those held out; the caller checks that they cover the pool
    (a short pool would hold sentinels or edges of the graph).
    """
    live = u != v
    a = jnp.where(live, jnp.minimum(u, v), n).astype(jnp.int32)
    b = jnp.where(live, jnp.maximum(u, v), n).astype(jnp.int32)
    a, b = jax.lax.sort((a, b), num_keys=2)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (a[1:] != a[:-1]) | (b[1:] != b[:-1])])
    first = first & (a < n)
    n_distinct = jnp.sum(first.astype(jnp.int32))

    n_held = jnp.zeros((), jnp.int32)
    inserts = deletes = jnp.zeros((0, 2), jnp.int32)
    if n_insert + n_delete:
        prio = jnp.where(first, jax.random.uniform(key, a.shape), 2.0)
        _, idx = jax.lax.sort((prio, jnp.arange(a.shape[0], dtype=jnp.int32)),
                              num_keys=1)
        n_held = jnp.maximum(
            n_insert, jnp.round(hold_share * n_distinct).astype(jnp.int32))
        inserts = jnp.stack([a[idx[:n_insert]], b[idx[:n_insert]]], axis=1)
        later = jax.lax.dynamic_slice(idx, (n_held,), (n_delete,))
        deletes = jnp.stack([a[later], b[later]], axis=1)
        rank = jnp.zeros_like(idx).at[idx].set(
            jnp.arange(a.shape[0], dtype=jnp.int32))
        first = first & (rank >= n_held)

    s = jnp.concatenate([jnp.where(first, a, n), jnp.where(first, b, n)])
    d = jnp.concatenate([jnp.where(first, b, n), jnp.where(first, a, n)])
    s, d = jax.lax.sort((s, d), num_keys=2)
    s, d = s[:e_cap], d[:e_cap]
    live_slot = s < n
    counts = jax.ops.segment_sum(live_slot.astype(jnp.int32), s,
                                 num_segments=n + 1)
    graph = CSRGraph(
        indptr=jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(counts[:n]).astype(jnp.int32)]),
        indices=d,
        weights=live_slot.astype(jnp.float32),
        src=s,
        n_valid=jnp.asarray(n, jnp.int32),
        e_valid=jnp.sum(live_slot.astype(jnp.int32)),
    )
    return graph, inserts, deletes, n_distinct, n_held
