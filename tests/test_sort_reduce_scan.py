"""The sort-reduce scan's payload-carrying sort against the lexsort + gather
form it replaced: the same (s_src, s_c, K_{i->c}) and the same per-vertex
(best community, best dQ), bit for bit, on inputs where the order of the
per-group sums would show (non-integer weights, self loops, repeated
(src, community) groups, sentinel slots at the end and in the middle, and a
frontier compaction).  Also pins the scan's structure: one stable
three-operand sort and a single gather from an e-sized operand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import local_move
from repro.core.graph import build_csr
from repro.core.local_move import (_scan_communities_slots, best_moves_slots,
                                   gather_frontier_slots)
from repro.core.modularity import community_weights

N_CAP, E_CAP = 48, 1024


def _old_scan(src, dst, w, comm):
    """The former formulation: lexsort, then four permutation gathers."""
    cdst = comm[dst]
    order = jnp.lexsort((cdst, src))
    s_src = src[order]
    s_dst = dst[order]
    s_c = cdst[order]
    s_w = jnp.where(s_src == s_dst, 0.0, w[order])
    prev_src = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_src[:-1]])
    prev_c = jnp.concatenate([jnp.full((1,), -1, jnp.int32), s_c[:-1]])
    new_group = (s_src != prev_src) | (s_c != prev_c)
    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    group_w = jax.ops.segment_sum(s_w, gid, num_segments=src.shape[0])
    return s_src, s_c, group_w[gid]


def _inputs(seed, layout):
    """Slot arrays and a snapshot.  Few communities over dense rows, so most
    (src, community) groups hold several non-integer weights."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(30, N_CAP))
    e0 = int(rng.integers(150, 300))
    src = rng.integers(0, n, e0)
    dst = rng.integers(0, n, e0)
    loops = rng.choice(n, 6, replace=False)
    src = np.concatenate([src, loops])
    dst = np.concatenate([dst, loops])            # self loops
    w = (rng.random(src.size) * 3.0 + 0.1).astype(np.float32)
    g = build_csr(src, dst, w, n, symmetrize=True, dedup=True,
                  n_cap=N_CAP, e_cap=E_CAP)
    e_valid = int(g.e_valid)
    assert e_valid < E_CAP                        # sentinel pads at the end
    comm = np.full(N_CAP + 1, N_CAP, np.int32)
    comm[:n] = rng.integers(0, 5, n)
    comm = jnp.asarray(comm)
    frontier = np.zeros(N_CAP + 1, bool)
    frontier[:n] = rng.random(n) < 0.7
    frontier = jnp.asarray(frontier)

    s, d, ww = g.src, g.indices, g.weights
    if layout == "dead_middle":
        dead = rng.choice(e_valid, e_valid // 8, replace=False)
        s = s.at[dead].set(N_CAP)
        d = d.at[dead].set(N_CAP)
        ww = ww.at[dead].set(0.0)
    elif layout == "compact":
        s, d, ww, overflow = gather_frontier_slots(g, frontier, 512)
        assert not bool(overflow)
    snap = (comm, community_weights(g, comm), g.vertex_weights(), frontier,
            g.total_weight())
    return (s, d, ww), snap


def _assert_bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("layout", ["full", "dead_middle", "compact"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_scan_matches_lexsort_gather_form(seed, layout, monkeypatch):
    (src, dst, w), (comm, sigma, k, frontier, m) = _inputs(seed, layout)
    for a, b in zip(_scan_communities_slots(src, dst, w, comm),
                    _old_scan(src, dst, w, comm)):
        _assert_bits_equal(a, b)
    args = (src, dst, w, comm, sigma, k, frontier, m, N_CAP)
    new = best_moves_slots(*args)
    monkeypatch.setattr(local_move, "_scan_communities_slots", _old_scan)
    old = best_moves_slots(*args)
    for a, b in zip(new, old):
        _assert_bits_equal(a, b)


def test_inputs_exercise_summation_order():
    """Guard on the fixture: the groups really are multi-slot, non-integer
    sums, with self loops present."""
    (src, dst, w), (comm, *_) = _inputs(0, "full")
    src, dst, w = map(np.asarray, (src, dst, w))
    live = src != N_CAP
    assert np.any(src[live] == dst[live])
    assert np.any(w[live] != np.round(w[live]))
    keys = (src[live].astype(np.int64) * (N_CAP + 1)
            + np.asarray(comm)[dst[live]])
    assert np.bincount(np.unique(keys, return_inverse=True)[1]).max() >= 3


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("fn", ["best_moves_slots", "scan"])
def test_scan_structure(fn):
    """At e = 4096: one stable sort over (src, community, weight) and one
    gather whose operand has e elements (``group_w[gid]``)."""
    e, n_cap = 4096, 1000
    i32 = jax.ShapeDtypeStruct((e,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((e,), jnp.float32)
    node_i = jax.ShapeDtypeStruct((n_cap + 1,), jnp.int32)
    node_f = jax.ShapeDtypeStruct((n_cap + 1,), jnp.float32)
    if fn == "scan":
        closed = jax.make_jaxpr(_scan_communities_slots)(i32, i32, f32,
                                                         node_i)
    else:
        closed = jax.make_jaxpr(
            lambda s, d, w, c, sg, k, f, m: best_moves_slots(
                s, d, w, c, sg, k, f, m, n_cap))(
            i32, i32, f32, node_i, node_f, node_f,
            jax.ShapeDtypeStruct((n_cap + 1,), jnp.bool_),
            jax.ShapeDtypeStruct((), jnp.float32))
    eqns = list(_eqns(closed.jaxpr))
    sorts = [q for q in eqns if q.primitive.name == "sort"]
    assert len(sorts) == 1
    (sort,) = sorts
    assert len(sort.invars) == 3
    assert sort.params["num_keys"] == 2
    assert sort.params["is_stable"] is True
    e_gathers = [q for q in eqns if q.primitive.name == "gather"
                 and q.invars[0].aval.shape == (e,)]
    assert len(e_gathers) == 1
