"""Aggregation + capacity-ladder properties.

``aggregate_graph`` is pinned against the pure-NumPy coarsening oracle
(``tests/_oracle.py::_aggregate``): self-loop creation from intra-community
edges, duplicate-edge merge, sentinel padding, exact weight conservation.
The capacity ladder is tested as a pure policy (``resolve_coarse_capacity``:
tiers, floors, hysteresis), as a graph transform (re-bucket down -> up
round-trips bit-for-bit), and end-to-end (laddered ``louvain`` reproduces
un-laddered memberships with a BOUNDED number of compiles — the trace
counters in ``repro.core.graph.TRACE_COUNTS``).

Uses ``hypothesis`` when installed, ``tests/_hypothesis_fallback`` otherwise.
"""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:         # optional dev dep — see tests/_hypothesis_fallback
    from _hypothesis_fallback import given, settings, st

from _oracle import aggregate_oracle

from repro.configs.louvain_arch import (LADDER_HYSTERESIS, LADDER_MIN_E_CAP,
                                        LADDER_MIN_N_CAP,
                                        resolve_agg_backend,
                                        resolve_coarse_capacity)
from repro.core.aggregate import aggregate_graph, renumber_communities
from repro.core.graph import (TRACE_COUNTS, build_csr, rebucket_graph)
from repro.core.louvain import LouvainConfig, louvain
from repro.data import sbm_graph

N_CAP, E_CAP = 24, 256


def _random_graph(rng, n, e0, *, integer_w=True):
    src = rng.integers(0, n, e0)
    dst = rng.integers(0, n, e0)
    w = (rng.integers(1, 5, e0).astype(np.float32) if integer_w
         else (rng.random(e0) + 0.1).astype(np.float32))
    # Fixed capacities across draws: one compiled aggregate per shape.
    return build_csr(src, dst, w, n, symmetrize=True, dedup=True,
                     n_cap=N_CAP, e_cap=E_CAP)


def _random_renumbered(rng, g, n_groups):
    n = int(g.n_valid)
    comm = np.full(g.n_cap + 1, g.n_cap, np.int32)
    comm[:n] = rng.integers(0, n_groups, n)
    comm_ren, n_comms = renumber_communities(
        jnp.asarray(comm), g.n_valid, g.n_cap)
    return comm_ren, n_comms


def _coarse_dict(g):
    """Live coarse slots of a CSRGraph as {(ci, cj): w}."""
    e = int(g.e_valid)
    src = np.asarray(g.src)
    dst = np.asarray(g.indices)
    w = np.asarray(g.weights)
    return {(int(src[i]), int(dst[i])): float(w[i]) for i in range(e)}


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=2, max_value=8))
def test_aggregate_matches_numpy_oracle(seed, n_groups):
    """Coarse slot set == the oracle's: duplicate coarse edges merged, intra-
    community edges collapsed to (c, c) self loops, weights summed exactly."""
    rng = np.random.default_rng(seed)
    g = _random_graph(rng, 16, 40)
    comm_ren, n_comms = _random_renumbered(rng, g, n_groups)
    coarse = aggregate_graph(g, comm_ren, n_comms)

    e = int(g.e_valid)
    cs, cd, cw = aggregate_oracle(
        np.asarray(g.src)[:e], np.asarray(g.indices)[:e],
        np.asarray(g.weights)[:e],
        np.asarray(comm_ren)[: g.n_cap], int(n_comms))
    want = {(int(a), int(b)): float(x) for a, b, x in zip(cs, cd, cw)}
    got = _coarse_dict(coarse)
    assert set(got) == set(want)
    for key in want:      # integer weights -> float32 sums are exact
        assert got[key] == pytest.approx(want[key], abs=0.0)
    # Intra-community mass appears as (c, c) self loops.
    src_np = np.asarray(g.src)[:e]
    dst_np = np.asarray(g.indices)[:e]
    comm_np = np.asarray(comm_ren)
    if np.any(comm_np[src_np] == comm_np[dst_np]):
        assert any(a == b for a, b in got)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_aggregate_padding_and_conservation(seed):
    """Beyond e_valid every slot is sentinel/0; sum(w') == sum(w) exactly;
    rows are grouped (CSR indptr consistent with src)."""
    rng = np.random.default_rng(seed)
    g = _random_graph(rng, 16, 40)
    comm_ren, n_comms = _random_renumbered(rng, g, 4)
    coarse = aggregate_graph(g, comm_ren, n_comms)

    e = int(coarse.e_valid)
    src = np.asarray(coarse.src)
    dst = np.asarray(coarse.indices)
    w = np.asarray(coarse.weights)
    assert np.all(src[e:] == coarse.n_cap)
    assert np.all(dst[e:] == coarse.n_cap)
    assert np.all(w[e:] == 0.0)
    assert float(w.sum()) == pytest.approx(
        float(np.asarray(g.weights).sum()), abs=0.0)
    # indptr rebuild matches the live rows.
    indptr = np.asarray(coarse.indptr)
    counts = np.zeros(coarse.n_cap, np.int64)
    np.add.at(counts, src[:e], 1)
    np.testing.assert_array_equal(np.diff(indptr), counts)
    assert int(coarse.n_valid) == int(n_comms)


def test_aggregate_pallas_backend_matches_sort():
    """Both group-resolve backends produce the same coarse graph — equal
    bits on integer weights, float32-close otherwise."""
    rng = np.random.default_rng(7)
    for integer_w, exact in ((True, True), (False, False)):
        g = _random_graph(rng, 16, 48, integer_w=integer_w)
        comm_ren, n_comms = _random_renumbered(rng, g, 5)
        a = aggregate_graph(g, comm_ren, n_comms, backend="sort")
        b = aggregate_graph(g, comm_ren, n_comms, backend="pallas")
        np.testing.assert_array_equal(np.asarray(a.src), np.asarray(b.src))
        np.testing.assert_array_equal(np.asarray(a.indices),
                                      np.asarray(b.indices))
        np.testing.assert_array_equal(np.asarray(a.indptr),
                                      np.asarray(b.indptr))
        assert int(a.e_valid) == int(b.e_valid)
        if exact:
            np.testing.assert_array_equal(np.asarray(a.weights),
                                          np.asarray(b.weights))
        else:
            np.testing.assert_allclose(np.asarray(a.weights),
                                       np.asarray(b.weights), rtol=1e-6)


def test_aggregate_unknown_backend_raises():
    rng = np.random.default_rng(0)
    g = _random_graph(rng, 8, 12)
    comm_ren, n_comms = _random_renumbered(rng, g, 2)
    with pytest.raises(ValueError, match="aggregation backend"):
        aggregate_graph(g, comm_ren, n_comms, backend="nope")
    with pytest.raises(ValueError, match="agg_backend"):
        resolve_agg_backend("nope")
    assert resolve_agg_backend("sort") == "sort"
    assert resolve_agg_backend("pallas") == "pallas"
    assert resolve_agg_backend("auto") in ("sort", "pallas")


# ---------------------------------------------------------------------------
# Capacity-ladder policy + re-bucketing.
# ---------------------------------------------------------------------------


def test_resolve_coarse_capacity_policy():
    # Far below current caps -> power-of-two tier with slack.
    n_new, e_new = resolve_coarse_capacity(100, 1000, 4096, 65536)
    assert n_new == 128 and e_new == 2048
    # Floors: tiny coarse graphs stop at the min tier.
    n_new, e_new = resolve_coarse_capacity(3, 10, 4096, 65536)
    assert n_new == LADDER_MIN_N_CAP and e_new == LADDER_MIN_E_CAP
    # Hysteresis: a < LADDER_HYSTERESIS shrink keeps the current capacity.
    n_new, e_new = resolve_coarse_capacity(300, 40_000, 700, 70_000)
    assert (n_new, e_new) == (700, 70_000)
    # Never grows.
    n_new, e_new = resolve_coarse_capacity(60, 200, 64, 256)
    assert (n_new, e_new) == (64, 256)
    # Result always fits the live counts.
    for n_c, e_v in ((1, 1), (63, 255), (65, 257), (1000, 12345)):
        n_new, e_new = resolve_coarse_capacity(n_c, e_v, 1 << 20, 1 << 24)
        assert n_new >= n_c and e_new >= e_v
        assert n_new & (n_new - 1) == 0 and e_new & (e_new - 1) == 0


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_ladder_rebucket_round_trip(seed):
    """Re-bucket a coarse graph down to its tier and back up: every buffer
    reproduces the original bit-for-bit (sentinels rewritten both ways)."""
    rng = np.random.default_rng(seed)
    g = _random_graph(rng, 16, 40)
    comm_ren, n_comms = _random_renumbered(rng, g, 4)
    coarse = aggregate_graph(g, comm_ren, n_comms)

    n_new, e_new = resolve_coarse_capacity(
        int(n_comms), int(coarse.e_valid), coarse.n_cap, coarse.e_cap)
    n_new = min(n_new, max(int(n_comms), 8))   # force a real shrink
    e_new = min(e_new, max(int(coarse.e_valid), 8))
    down = rebucket_graph(coarse, n_new, e_new)
    assert down.n_cap == n_new and down.e_cap == e_new
    up = rebucket_graph(down, coarse.n_cap, coarse.e_cap)
    for a, b in zip(coarse, up):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rebucket_rejects_overflow():
    rng = np.random.default_rng(1)
    g = _random_graph(rng, 16, 40)
    with pytest.raises(ValueError, match="does not fit"):
        rebucket_graph(g, 4, E_CAP)
    with pytest.raises(ValueError, match="does not fit"):
        rebucket_graph(g, N_CAP, 2)


def test_laddered_louvain_matches_and_bounds_compiles():
    """The regression pin for the ladder's whole point: laddered passes
    reproduce un-laddered memberships EXACTLY, the capacities actually
    drop, and the number of phase compiles is bounded by the distinct
    tiers (re-running adds ZERO traces — the per-tier jit cache holds)."""
    # Unique capacities so this test owns its jit cache entries.
    g, _ = sbm_graph(12, 40, p_in=0.3, p_out=0.004, seed=5)
    base = louvain(g, LouvainConfig(use_ladder=False))
    TRACE_COUNTS.clear()
    lad = louvain(g, LouvainConfig(use_ladder=True))
    first = dict(TRACE_COUNTS)

    np.testing.assert_array_equal(base.membership, lad.membership)
    caps = [(p.n_cap, p.e_cap) for p in lad.passes]
    assert caps[0] == (g.n_cap, g.e_cap)
    assert len(lad.passes) >= 2, "test vacuous — need a coarse pass"
    assert caps[1][1] < caps[0][1], f"ladder never engaged: {caps}"

    n_tiers = len(set(caps))
    assert first.get("move_phase", 0) <= n_tiers
    assert first.get("aggregate_phase", 0) <= n_tiers
    assert first.get("rebucket_capacity", 0) <= n_tiers
    # Tier reuse: the same run again re-jits NOTHING.
    louvain(g, LouvainConfig(use_ladder=True))
    assert dict(TRACE_COUNTS) == first
