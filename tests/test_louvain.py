"""End-to-end GVE-Louvain behaviour: quality, invariants, paper parameters."""

import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro.core.aggregate import (aggregate_graph, community_vertices_csr,
                                  renumber_communities)
from repro.core.graph import from_networkx
from repro.core.louvain import LouvainConfig, louvain, louvain_modularity
from repro.core.modularity import modularity
from repro.data import sbm_graph


def _nx_louvain_q(nxg, seed=0):
    com = nx.algorithms.community.louvain_communities(nxg, seed=seed)
    return nx.algorithms.community.modularity(nxg, com)


@pytest.mark.parametrize("make", [
    nx.karate_club_graph,
    nx.les_miserables_graph,
    lambda: nx.connected_caveman_graph(8, 6),
])
def test_quality_close_to_networkx(make):
    """Q within 5% of networkx's sequential Louvain (the paper reports GVE
    within ~1% of Grappolo/NetworKit; synchronous rounds wobble slightly)."""
    nxg = make()
    g = from_networkx(nxg)
    res = louvain(g)
    q = louvain_modularity(g, res)
    q_nx = _nx_louvain_q(nxg)
    assert q >= 0.95 * q_nx, (q, q_nx)


def test_sbm_planted_communities_recovered():
    g, truth = sbm_graph(n_communities=8, size=32, p_in=0.3, p_out=0.005,
                         seed=1)
    res = louvain(g)
    # Every planted block should map (almost) 1:1 onto a found community.
    n = int(g.n_valid)
    mem = res.membership
    agree = 0
    for b in range(8):
        ids, counts = np.unique(mem[truth == b], return_counts=True)
        agree += counts.max()
    assert agree / n > 0.95
    assert 4 <= res.n_communities <= 16


def test_aggregation_conserves_weight():
    nxg = nx.les_miserables_graph()
    g = from_networkx(nxg)
    n = int(g.n_valid)
    rng = np.random.default_rng(0)
    comm = rng.integers(0, 6, n)
    comm_j = jnp.asarray(np.concatenate([comm, [g.n_cap]]), jnp.int32)
    comm_ren, n_comms = renumber_communities(comm_j, g.n_valid, g.n_cap)
    coarse = aggregate_graph(g, comm_ren, n_comms)
    assert float(coarse.total_weight()) == pytest.approx(
        float(g.total_weight()), rel=1e-6)
    assert int(coarse.n_valid) == int(n_comms)
    # Q of the coarse singleton partition == Q of comm on the fine graph.
    idx = jnp.arange(coarse.n_cap + 1, dtype=jnp.int32)
    q_coarse = float(modularity(coarse, idx))
    q_fine = float(modularity(g, comm_j))
    assert np.isclose(q_coarse, q_fine, atol=1e-5)


def test_aggregation_matches_networkx_quotient():
    nxg = nx.les_miserables_graph()
    g = from_networkx(nxg)
    n = int(g.n_valid)
    rng = np.random.default_rng(3)
    comm = rng.integers(0, 5, n)
    comm_j = jnp.asarray(np.concatenate([comm, [g.n_cap]]), jnp.int32)
    comm_ren, n_comms = renumber_communities(comm_j, g.n_valid, g.n_cap)
    coarse = aggregate_graph(g, comm_ren, n_comms)

    # Build the same quotient in numpy from the original slot list.
    ren = np.asarray(comm_ren)[:n]
    src = np.asarray(g.src)
    dst = np.asarray(g.indices)
    w = np.asarray(g.weights)
    live = src < g.n_cap
    agg = {}
    for s, d, ww in zip(ren[src[live]], ren[dst[live]], w[live]):
        agg[(int(s), int(d))] = agg.get((int(s), int(d)), 0.0) + float(ww)

    c_src = np.asarray(coarse.src)
    c_dst = np.asarray(coarse.indices)
    c_w = np.asarray(coarse.weights)
    got = {}
    for s, d, ww in zip(c_src, c_dst, c_w):
        if s < coarse.n_cap:
            got[(int(s), int(d))] = got.get((int(s), int(d)), 0.0) + float(ww)
    assert set(got) == set(agg)
    for key in agg:
        assert np.isclose(got[key], agg[key], rtol=1e-5), key


def test_renumber_dense_and_stable():
    # community ids live in vertex-id space [0, n_cap); sentinel = n_cap.
    comm = jnp.asarray([5, 5, 4, 2, 4, 2, 6], jnp.int32)
    out, n = renumber_communities(comm, jnp.int32(6), 6)
    out = np.asarray(out)
    assert int(n) == 3
    assert out[-1] == 6                       # sentinel fixed
    # dense ids, order-preserving (2 -> 0, 4 -> 1, 5 -> 2)
    np.testing.assert_array_equal(out[:6], [2, 2, 1, 0, 1, 0])


def test_community_vertices_csr_groups():
    comm = jnp.asarray([1, 0, 1, 0, 2, 999], jnp.int32)
    offsets, order = community_vertices_csr(comm, jnp.int32(5), 5)
    offsets, order = np.asarray(offsets), np.asarray(order)
    # communities 0: {1,3}, 1: {0,2}, 2: {4}
    assert offsets[0] == 0 and offsets[1] == 2 and offsets[2] == 4
    assert set(order[0:2]) == {1, 3}
    assert set(order[2:4]) == {0, 2}
    assert order[4] == 4


def test_max_passes_and_threshold_scaling_respected():
    nxg = nx.les_miserables_graph()
    g = from_networkx(nxg)
    res = louvain(g, LouvainConfig(max_passes=1))
    assert res.n_passes == 1
    res2 = louvain(g, LouvainConfig(max_iterations=2))
    assert all(p.iterations <= 2 for p in res2.passes)


def test_aggregation_tolerance_stops_early():
    # On a graph with weak structure, |G'|/|G| stays high -> stop pass 1.
    nxg = nx.gnp_random_graph(60, 0.5, seed=0)
    g = from_networkx(nxg)
    res = louvain(g, LouvainConfig(aggregation_tolerance=0.01))
    assert res.n_passes <= 2


def test_pruning_matches_unpruned_quality():
    nxg = nx.les_miserables_graph()
    g = from_networkx(nxg)
    q_on = louvain_modularity(g, louvain(g, LouvainConfig(use_pruning=True)))
    q_off = louvain_modularity(g, louvain(g, LouvainConfig(use_pruning=False)))
    assert abs(q_on - q_off) < 0.05


def test_ell_kernel_path_equivalent_quality():
    nxg = nx.les_miserables_graph()
    g = from_networkx(nxg)
    q_sort = louvain_modularity(g, louvain(g, LouvainConfig()))
    q_ell = louvain_modularity(
        g, louvain(g, LouvainConfig(use_ell_kernel=True)))
    assert abs(q_sort - q_ell) < 0.05
    assert q_ell > 0.4


def test_isolated_vertices_stay_put():
    nxg = nx.Graph()
    nxg.add_edges_from([(0, 1), (1, 2)])
    nxg.add_nodes_from([3, 4])               # isolated
    g = from_networkx(nxg)
    res = louvain(g)
    assert len(res.membership) == 5
    assert np.isfinite(louvain_modularity(g, res))


# -- Leiden-style refinement --------------------------------------------------

from _spans import inside, named, traced  # noqa: E402
from _oracle import (disconnected_communities, modularity_np,  # noqa: E402
                     oracle_graph_slots, refine_oracle)


def _badly_connected_graph():
    """The committed pathology corpus: plain parallel Louvain leaves a
    disconnected community here (see tests/golden/capture_engine_golden)."""
    return from_networkx(nx.gnp_random_graph(120, 0.05, seed=21))


def test_unrefined_louvain_leaves_disconnected_community():
    """The regression the refinement phase exists for: with refine="none"
    the audit finds at least one community whose induced subgraph is NOT
    connected on the pathology corpus."""
    g = _badly_connected_graph()
    src, dst, w, _ = oracle_graph_slots(g)
    mem = louvain(g).membership
    assert len(disconnected_communities(src, dst, mem)) >= 1


def test_leiden_communities_all_connected():
    """refine="leiden" yields ZERO disconnected communities on every golden
    corpus (including the pathology one).  Tier-1 runs the sort-reduce
    family everywhere plus the ELL kernel on the pathology corpus; the full
    ELL-family matrix is the slow test below."""
    from golden.capture_engine_golden import corpora

    for name, g in corpora().items():
        src, dst, w, _ = oracle_graph_slots(g)
        mem = louvain(g, LouvainConfig(refine="leiden")).membership
        assert disconnected_communities(src, dst, mem) == [], name
    g = _badly_connected_graph()
    src, dst, w, _ = oracle_graph_slots(g)
    mem = louvain(g, LouvainConfig(refine="leiden",
                                   use_ell_kernel=True)).membership
    assert disconnected_communities(src, dst, mem) == []


@pytest.mark.slow
def test_leiden_communities_all_connected_ell_full():
    """Full-matrix ELL-kernel variant of the connectivity audit."""
    from golden.capture_engine_golden import corpora

    cfg = LouvainConfig(refine="leiden", use_ell_kernel=True)
    for name, g in corpora().items():
        src, dst, w, _ = oracle_graph_slots(g)
        mem = louvain(g, cfg).membership
        assert disconnected_communities(src, dst, mem) == [], name


def test_leiden_modularity_not_worse():
    """The reported (outer) partition under refinement never loses Q vs the
    unrefined run on the golden corpora."""
    from golden.capture_engine_golden import corpora

    for name, g in corpora().items():
        src, dst, w, _ = oracle_graph_slots(g)
        q_none = modularity_np(src, dst, w, louvain(g).membership)
        q_ref = modularity_np(
            src, dst, w, louvain(g, LouvainConfig(refine="leiden")).membership)
        assert q_ref >= q_none - 1e-9, (name, q_none, q_ref)


def test_refine_rejects_unknown_mode():
    g = from_networkx(nx.karate_club_graph())
    with pytest.raises(ValueError, match="refine"):
        louvain(g, LouvainConfig(refine="bogus"))


def test_refine_oracle_properties():
    """The NumPy reference refinement: refines the outer partition and
    every refined community is connected."""
    g = _badly_connected_graph()
    src, dst, w, n = oracle_graph_slots(g)
    outer = louvain(g).membership
    refined = refine_oracle(src, dst, w, n, outer)
    # Refinement: each refined community lies inside ONE outer community.
    for r in np.unique(refined):
        assert len(np.unique(outer[refined == r])) == 1
    assert disconnected_communities(src, dst, refined) == []
    # It genuinely splits the disconnected community (strict refinement).
    assert len(np.unique(refined)) > len(np.unique(outer))


def test_refine_pass_stats_populated():
    g = _badly_connected_graph()
    res, events = traced(louvain, g, LouvainConfig(refine="leiden"))
    assert all(p.refine_iterations is not None for p in res.passes)
    assert all(p.n_refined is not None and p.n_refined >= p.n_communities
               for p in res.passes)
    passes = named(events, "gve.pass")
    assert len(passes) == len(res.passes)
    assert all(any(inside(r, p) for r in named(events, "gve.refine"))
               for p in passes)
    res_none = louvain(g)
    assert all(p.refine_iterations is None and p.n_refined is None
               for p in res_none.passes)


# -- per-level memberships (LouvainResult.levels) -----------------------------


def _is_coarsening(fine, coarse):
    """coarse is a coarsening of fine: fine-equal pairs stay coarse-equal
    (checked via a single-valued fine -> coarse label map)."""
    m = {}
    for f, c in zip(fine.tolist(), coarse.tolist()):
        if m.setdefault(f, c) != c:
            return False
    return True


def test_levels_nest_and_fold_in_order():
    """refine="none": each level coarsens the previous (the dendrogram fold
    order), the last level IS the membership, and every level's labeling
    matches the recorded per-pass community count."""
    g = from_networkx(nx.les_miserables_graph())
    res = louvain(g)
    assert len(res.levels) == res.n_passes
    np.testing.assert_array_equal(res.levels[-1], res.membership)
    for a, b in zip(res.levels, res.levels[1:]):
        assert _is_coarsening(a, b)
    for lvl, p in zip(res.levels, res.passes):
        assert len(np.unique(lvl)) == p.n_communities


def test_levels_leiden_reports_outer_per_pass():
    """refine="leiden": levels are the OUTER partitions (reported per pass);
    the last one is the membership and per-pass counts line up.  Outer
    levels need not nest — but Q must not decrease across them."""
    g = _badly_connected_graph()
    src, dst, w, _ = oracle_graph_slots(g)
    res = louvain(g, LouvainConfig(refine="leiden"))
    assert len(res.levels) == res.n_passes
    np.testing.assert_array_equal(res.levels[-1], res.membership)
    for lvl, p in zip(res.levels, res.passes):
        assert len(np.unique(lvl)) == p.n_communities
    qs = [modularity_np(src, dst, w, lvl) for lvl in res.levels]
    assert all(b >= a - 1e-9 for a, b in zip(qs, qs[1:])), qs
