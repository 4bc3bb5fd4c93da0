"""End-to-end system behaviour: the full GVE-Louvain pipeline on generated
graph families (the paper's dataset categories), plus determinism."""

import jax.numpy as jnp
import numpy as np
import pytest

from _spans import inside, named, traced
from repro.core.louvain import LouvainConfig, louvain, louvain_modularity
from repro.data import powerlaw_cluster, rmat_graph, sbm_graph


def test_rmat_web_like_end_to_end():
    """R-MAT (web-graph family): converges, sane community count, Q > 0."""
    g = rmat_graph(10, edge_factor=6, seed=0)
    res = louvain(g)
    assert res.n_passes <= 10
    assert 1 <= res.n_communities < int(g.n_valid)
    q = louvain_modularity(g, res)
    assert q > 0.1


def test_powerlaw_social_like_end_to_end():
    g, _ = powerlaw_cluster(600, 4, 0.6, seed=1)
    res = louvain(g)
    q = louvain_modularity(g, res)
    assert q > 0.2
    assert res.n_communities >= 2


def test_sbm_quality_tracks_planted_q():
    g, truth = sbm_graph(n_communities=10, size=30, p_in=0.25, p_out=0.004,
                         seed=2)
    res = louvain(g)
    q_found = louvain_modularity(g, res)
    comm = jnp.concatenate([jnp.asarray(truth, jnp.int32),
                            jnp.full((g.n_cap + 1 - len(truth),), g.n_cap,
                                     jnp.int32)])
    from repro.core.modularity import modularity
    q_planted = float(modularity(g, comm))
    assert q_found >= 0.9 * q_planted


def test_pass_stats_structure():
    g = rmat_graph(8, edge_factor=4, seed=3)
    res, events = traced(louvain, g, LouvainConfig(track_modularity=True))
    assert res.passes
    pass_spans = named(events, "gve.pass")
    assert len(pass_spans) == len(res.passes)
    for i, (p, span) in enumerate(zip(res.passes, pass_spans)):
        assert p.iterations >= 1
        assert p.n_communities <= p.n_vertices
        phases = {e.name for e in events if e is not span and inside(e, span)
                  and e.name in ("gve.move", "gve.renumber", "gve.aggregate")}
        last = i == len(res.passes) - 1
        assert phases == {"gve.move", "gve.renumber"} | (
            set() if last else {"gve.aggregate"})
        assert p.modularity is None or np.isfinite(p.modularity)
    # monotone coarsening
    sizes = [p.n_vertices for p in res.passes]
    assert sizes == sorted(sizes, reverse=True)


def test_deterministic_across_runs():
    """Same graph + same config -> identical membership (the deterministic
    tie-breaking requirement)."""
    g = rmat_graph(8, edge_factor=4, seed=4)
    r1 = louvain(g)
    r2 = louvain(g)
    np.testing.assert_array_equal(r1.membership, r2.membership)
