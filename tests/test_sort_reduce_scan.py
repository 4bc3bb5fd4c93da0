"""The sort-reduce scan's payload-carrying sort against the lexsort + gather
form it replaced: the same (s_src, s_c, s_w, K_{i->c}) and the same
per-vertex (best community, best dQ), bit for bit, on inputs where the
order of the per-group sums would show (non-integer weights, self loops,
repeated (src, community) groups, sentinel slots at the end and in the
middle, and a frontier compaction).  The per-vertex values reach the sorted
slots through ``spread_to_slots`` (a difference array and one int32
cumulative sum) in place of ``x[s_src]`` gathers: that expansion is pinned
bit for bit, with the slot contract it relies on.  Also pins the scan's
structure: one stable three-operand sort, a single gather from an e-sized
operand and three gathers by an e-sized index.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregate import aggregate_graph, renumber_communities
from repro.core.delta import apply_edge_batch, make_edge_batch
from repro.core.graph import (TRACE_COUNTS, CSRGraph, build_csr,
                              rebucket_capacity)
from repro.core.local_move import (SortReduceScanner, _scan_communities_slots,
                                   best_moves, best_moves_slots,
                                   gather_frontier_slots, slot_counts,
                                   spread_to_slots)
from repro.core.louvain import _move_phase, _refine_phase
from repro.core.modularity import community_weights, delta_modularity
from repro.core.multistream import stack_graphs

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
    return s_src, s_c, s_w, group_w[gid]


def _old_best_moves_slots(src, dst, w, comm, sigma, k, frontier, m, n_cap):
    """The former formulation: K_{i->own} in input order from ``comm[src]``,
    and every per-vertex value gathered at ``s_src``."""
    own = (comm[dst] == comm[src]) & (dst != src)
    k_to_own = jax.ops.segment_sum(
        jnp.where(own, w, 0.0), src, num_segments=n_cap + 1)
    s_src, s_c, _, k_i_to_c = _old_scan(src, dst, w, comm)
    c_own = comm[s_src]
    dq = delta_modularity(
        k_i_to_c, k_to_own[s_src], k[s_src], sigma[s_c], sigma[c_own], m)
    valid = (s_c != c_own) & (s_src != n_cap) & (s_c != n_cap) & frontier[s_src]
    dq = jnp.where(valid, dq, -jnp.inf)
    best_dq = jax.ops.segment_max(dq, s_src, num_segments=n_cap + 1)
    best_dq = jnp.where(jnp.isfinite(best_dq), best_dq, -jnp.inf)
    is_best = (dq == best_dq[s_src]) & valid
    best_c = jax.ops.segment_min(
        jnp.where(is_best, s_c, n_cap), s_src, num_segments=n_cap + 1)
    return jnp.minimum(best_c, n_cap), best_dq


def _graph(rng, n):
    e0 = int(rng.integers(150, 300))
    src = rng.integers(0, n, e0)
    dst = rng.integers(0, n, e0)
    loops = rng.choice(n, 6, replace=False)
    src = np.concatenate([src, loops])
    dst = np.concatenate([dst, loops])            # self loops
    w = (rng.random(src.size) * 3.0 + 0.1).astype(np.float32)
    return build_csr(src, dst, w, n, symmetrize=True, dedup=True,
                     n_cap=N_CAP, e_cap=E_CAP)


def _inputs(seed, layout):
    """Slot arrays and a snapshot.  Few communities over dense rows, so most
    (src, community) groups hold several non-integer weights."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(30, N_CAP))
    g = _graph(rng, n)
    e_valid = int(g.e_valid)
    assert e_valid < E_CAP                        # sentinel pads at the end
    comm = np.full(N_CAP + 1, N_CAP, np.int32)
    comm[:n] = rng.integers(0, 5, n)
    comm = jnp.asarray(comm)
    frontier = np.zeros(N_CAP + 1, bool)
    frontier[:n] = rng.random(n) < 0.7
    frontier = jnp.asarray(frontier)

    s, d, ww = g.src, g.indices, g.weights
    # The slot counts each layout's caller derives from its own list.
    counts = slot_counts(g)
    if layout == "dead_middle":
        dead = rng.choice(e_valid, e_valid // 8, replace=False)
        s = s.at[dead].set(N_CAP)
        d = d.at[dead].set(N_CAP)
        ww = ww.at[dead].set(0.0)
        counts = jnp.asarray(np.bincount(np.asarray(s), minlength=N_CAP + 1),
                             jnp.int32)
    elif layout == "compact":
        s, d, ww, overflow = gather_frontier_slots(g, frontier, 512)
        assert not bool(overflow)
        counts = slot_counts(g, frontier, 512)
    snap = (comm, community_weights(g, comm), g.vertex_weights(), frontier,
            g.total_weight())
    return (s, d, ww), snap, counts


def _assert_bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("layout", ["full", "dead_middle", "compact"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_scan_matches_lexsort_gather_form(seed, layout):
    """Counts omitted: ``best_moves_slots`` counts the slots of ``src``."""
    (src, dst, w), (comm, sigma, k, frontier, m), _ = _inputs(seed, layout)
    new_scan = _scan_communities_slots(src, dst, w, comm)
    old_scan = _old_scan(src, dst, w, comm)
    assert len(new_scan) == len(old_scan) == 4
    for a, b in zip(new_scan, old_scan):
        _assert_bits_equal(a, b)
    args = (src, dst, w, comm, sigma, k, frontier, m, N_CAP)
    for a, b in zip(best_moves_slots(*args), _old_best_moves_slots(*args)):
        _assert_bits_equal(a, b)


@pytest.mark.parametrize("layout", ["full", "dead_middle", "compact"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_best_moves_slots_with_counts_matches_gather_form(seed, layout):
    """Counts given, as the layout's caller derives them (``slot_counts``
    of the graph or of its compaction; a bincount of the middle-dead list):
    the same answer as the ``x[s_src]`` gathers, bit for bit."""
    (src, dst, w), (comm, sigma, k, frontier, m), counts = _inputs(seed,
                                                                   layout)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(src), minlength=N_CAP + 1))
    args = (src, dst, w, comm, sigma, k, frontier, m, N_CAP)
    new = best_moves_slots(*args, counts)
    for a, b in zip(new, _old_best_moves_slots(*args)):
        _assert_bits_equal(a, b)


def test_best_moves_vmap_over_two_streams():
    """Under ``vmap`` each lane derives its own counts: two streams on
    equal capacities answer as the gather form does on each alone."""
    rng = np.random.default_rng(11)
    graphs = [_graph(rng, 40), _graph(rng, 33)]
    snaps = []
    for g in graphs:
        comm = np.full(N_CAP + 1, N_CAP, np.int32)
        comm[:int(g.n_valid)] = rng.integers(0, 5, int(g.n_valid))
        comm = jnp.asarray(comm)
        frontier = jnp.asarray(
            (np.arange(N_CAP + 1) < int(g.n_valid))
            & (rng.random(N_CAP + 1) < 0.7))
        snaps.append((comm, community_weights(g, comm), g.vertex_weights(),
                      frontier, g.total_weight()))
    stacked = [jnp.stack(x) for x in zip(*snaps)]
    bc, bdq = jax.vmap(best_moves)(stack_graphs(graphs), *stacked)
    for i, (g, snap) in enumerate(zip(graphs, snaps)):
        old = _old_best_moves_slots(g.src, g.indices, g.weights, *snap,
                                    N_CAP)
        _assert_bits_equal(bc[i], old[0])
        _assert_bits_equal(bdq[i], old[1])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mark_neighbors_matches_gather_form(seed):
    rng = np.random.default_rng(seed)
    g = _graph(rng, int(rng.integers(30, N_CAP)))
    moved = jnp.asarray(rng.random(N_CAP + 1) < 0.3)
    sc = SortReduceScanner(g, g.vertex_weights(), g.total_weight())
    want = jax.ops.segment_max(moved[g.src].astype(jnp.int32), g.indices,
                               num_segments=N_CAP + 1) > 0
    np.testing.assert_array_equal(np.asarray(sc.mark_neighbors(moved)),
                                  np.asarray(want))


_SPECIAL = np.array([-0.0, np.inf, -np.inf, np.nan, 1e-45, -1e-45,
                     1.1754942e-38, 3.4028235e38], np.float32)


@pytest.mark.parametrize("f", [1, 4])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_spread_to_slots_exact_bits(seed, f):
    """``spread_to_slots`` equals ``value[s_src]`` bit for bit: rows with
    zero slots, a sentinel row of pads (or none), NaN payloads, signed
    zeros, infinities and denormals."""
    rng = np.random.default_rng(seed)
    n_cap = int(rng.integers(1, 60))
    counts = rng.integers(0, 5, n_cap + 1) * (rng.random(n_cap + 1) < 0.6)
    counts[n_cap] = 0 if seed == 0 else rng.integers(0, 40)
    e = int(counts.sum()) or 1
    counts[n_cap] += e - int(counts.sum())
    s_src = np.repeat(np.arange(n_cap + 1), counts)
    vals = []
    for i in range(f):
        bits = rng.integers(-2**31, 2**31, n_cap + 1, dtype=np.int64)
        x = bits.astype(np.int32).view(np.float32).copy()
        x[rng.random(n_cap + 1) < 0.3] = rng.choice(_SPECIAL)
        x[rng.integers(0, n_cap + 1)] = np.float32(np.nan)
        if i == 1:
            x = bits.astype(np.int32)
        elif i == 2:
            x = bits % 2 == 0
        vals.append(x)
    out = spread_to_slots(jnp.asarray(counts, jnp.int32), e,
                          *map(jnp.asarray, vals))
    assert len(out) == f
    for x, o in zip(vals, out):
        _assert_bits_equal(o, x[s_src])


def _contract_graphs():
    """A graph from every producer of the program's CSR buffers."""
    rng = np.random.default_rng(5)
    g = _graph(rng, 44)
    yield "build_csr", g
    batch = make_edge_batch([0, 3, 7, 40, 2], [5, 3, 9, 1, 44],
                            [2.5, 1.0, 0.0, 0.75, 1.5], N_CAP, b_cap=8)
    applied, _ = apply_edge_batch(g, batch)
    yield "apply_edge_batch", applied
    comm = jnp.asarray(np.concatenate(
        [rng.integers(0, 9, N_CAP), [N_CAP]]).astype(np.int32))
    comm = jnp.where(jnp.arange(N_CAP + 1) < g.n_valid, comm, N_CAP)
    ren, n_comms = renumber_communities(comm, g.n_valid, N_CAP)
    coarse = aggregate_graph(g, ren, n_comms)
    yield "aggregate_graph", coarse
    yield "rebucket_shrink", rebucket_capacity(coarse, n_cap_new=16,
                                               e_cap_new=512)
    yield "rebucket_grow", rebucket_capacity(g, n_cap_new=64,
                                             e_cap_new=2048)


@pytest.mark.parametrize("name", ["build_csr", "apply_edge_batch",
                                  "aggregate_graph", "rebucket_shrink",
                                  "rebucket_grow"])
def test_csr_src_is_rows_then_pads(name):
    """The slot contract ``spread_to_slots`` relies on: ``src`` is
    ``repeat(arange(n_cap), degrees)`` followed by the sentinel pads, so
    ``slot_counts`` counts the slots of every source vertex."""
    g = dict(_contract_graphs())[name]
    assert isinstance(g, CSRGraph)
    deg = np.asarray(g.degrees())
    pads = g.e_cap - int(deg.sum())
    assert pads >= 0
    want = np.concatenate([np.repeat(np.arange(g.n_cap), deg),
                           np.full(pads, g.n_cap)])
    np.testing.assert_array_equal(np.asarray(g.src), want)
    np.testing.assert_array_equal(
        np.asarray(slot_counts(g)),
        np.bincount(np.asarray(g.src), minlength=g.n_cap + 1))


def _abstract_graph(n_cap, e_cap):
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    return CSRGraph(
        indptr=jax.ShapeDtypeStruct((n_cap + 1,), jnp.int32),
        indices=jax.ShapeDtypeStruct((e_cap,), jnp.int32),
        weights=jax.ShapeDtypeStruct((e_cap,), jnp.float32),
        src=jax.ShapeDtypeStruct((e_cap,), jnp.int32),
        n_valid=i32, e_valid=i32)


@pytest.mark.parametrize("phase", ["full", "compact", "leiden"])
def test_move_phase_never_counts_slots_by_bincount(phase):
    """The cells' scanners give ``best_moves_slots`` their own slot counts:
    tracing the move phase (full or compact scanner) or the Leiden sweep
    never takes the bincount fallback.  Capacities unique to each case
    force a fresh trace."""
    n_cap, e_cap = {"full": (41, 320), "compact": (43, 336),
                    "leiden": (45, 352)}[phase]
    g = _abstract_graph(n_cap, e_cap)
    node_i = jax.ShapeDtypeStruct((n_cap + 1,), jnp.int32)
    node_f = jax.ShapeDtypeStruct((n_cap + 1,), jnp.float32)
    node_b = jax.ShapeDtypeStruct((n_cap + 1,), jnp.bool_)
    tol = jax.ShapeDtypeStruct((), jnp.float32)
    before = dict(TRACE_COUNTS)
    if phase == "leiden":
        _refine_phase.trace(g, node_i, tol, max_iterations=3,
                            use_pruning=True)
        key = "refine_phase"
    else:
        _move_phase.trace(g, node_i, node_f, node_b, tol, max_iterations=3,
                          use_pruning=True,
                          work_cap=64 if phase == "compact" else 0)
        key = "move_phase"
    assert TRACE_COUNTS.get(key, 0) == before.get(key, 0) + 1
    assert (TRACE_COUNTS.get("scan.counts_bincount", 0)
            == before.get("scan.counts_bincount", 0))
    # The fallback is counted where it is taken.
    e = jax.ShapeDtypeStruct((e_cap,), jnp.int32)
    jax.make_jaxpr(lambda s, d, w, c, sg, k, f, m: best_moves_slots(
        s, d, w, c, sg, k, f, m, n_cap))(
        e, e, jax.ShapeDtypeStruct((e_cap,), jnp.float32), node_i, node_f,
        node_f, node_b, tol)
    assert (TRACE_COUNTS.get("scan.counts_bincount", 0)
            == before.get("scan.counts_bincount", 0) + 1)


def test_inputs_exercise_summation_order():
    """Guard on the fixture: the groups really are multi-slot, non-integer
    sums, with self loops present."""
    (src, dst, w), (comm, *_), _ = _inputs(0, "full")
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
    gather whose operand has e elements (``group_w[gid]``).  In
    ``best_moves_slots`` at most three gathers take an e-sized index
    (``comm[dst]``, ``sigma[s_c]``, ``group_w[gid]``): no per-vertex value
    is gathered at ``src`` or ``s_src``."""
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
    e_index = [q for q in eqns if q.primitive.name == "gather"
               and q.invars[1].aval.shape[0] == e]
    assert len(e_index) <= (3 if fn == "best_moves_slots" else 2)
