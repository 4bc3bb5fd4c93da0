"""What every closed loop of the benchmark shares: the graph made on the
device from the seed, the program's state fetched for the check, and the
comparison of memberships with the reference."""

from __future__ import annotations

import importlib

import jax
import numpy as np

from bench.gen import csr
from bench.reference import louvain as ref


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (more than 32 bits kept)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def make_graph(config: dict, seed: int, n_insert: int = 0,
               n_delete: int = 0, hold_share: float = 0.0):
    """(graph, inserts, deletes) for ``config`` from ``seed``, on the device.

    The edge capacity is twice the raw edge count, the most the draw can
    fill, so every seed runs the same compiled shapes; the stream's pool
    (``csr.build``) comes back as host (k, 2) arrays.
    """
    gen = importlib.import_module(f"bench.gen.{config['generator']}")
    n, m = gen.sizes(config)
    key = seed_key(seed)
    u, v = gen.raw_edges(config, jax.random.fold_in(key, 0))
    graph, ins, dels, n_distinct, n_held = csr.build(
        u, v, jax.random.fold_in(key, 1), n=n, e_cap=2 * m,
        n_insert=n_insert, n_delete=n_delete, hold_share=hold_share)
    if int(n_held) + n_delete > int(n_distinct):
        raise RuntimeError(f"the draw has {int(n_distinct)} distinct edges, "
                           f"fewer than the {int(n_held)} held out and the "
                           f"pool's {n_delete} deletions")
    return graph, np.asarray(ins), np.asarray(dels)


def host_slots(graph) -> ref.Slots:
    """The live slots of a program graph, on the host."""
    e = int(graph.e_valid)
    return ref.Slots(np.asarray(graph.src)[:e], np.asarray(graph.indices)[:e],
                     np.asarray(graph.weights)[:e], int(graph.n_valid))


def membership_gap(got, want, slots: ref.Slots):
    """(mismatch, modularity gap) of a program membership.

    A membership of the wrong length or with labels outside [0, n) is no
    partition of the graph and reads 1.0 on both.
    """
    got = np.asarray(got)
    if (got.shape != want.shape or got.size == 0 or got.min() < 0
            or got.max() >= slots.n):
        return 1.0, 1.0
    q_got = ref.modularity(slots.src, slots.dst, slots.w, got)
    q_want = ref.modularity(slots.src, slots.dst, slots.w, want)
    return ref.mismatch(got, want), abs(q_got - q_want)
