"""CSR container invariants (paper opts 7/8 rely on exact conservation)."""

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:         # optional dev dep — see tests/_hypothesis_fallback
    from _hypothesis_fallback import given, settings, st

from repro.core.graph import CSRGraph, build_csr, from_networkx, to_ell_blocks
from repro.kernels.louvain_scan.ops import padded_rows, tile_rows

import networkx as nx


def test_build_csr_basic():
    src = np.array([0, 1, 1, 2])
    dst = np.array([1, 0, 2, 1])
    w = np.ones(4, np.float32)
    g = build_csr(src, dst, w, 3)
    assert int(g.n_valid) == 3 and int(g.e_valid) == 4
    assert float(g.total_weight()) == 2.0          # m = sum(w)/2
    np.testing.assert_array_equal(np.asarray(g.degrees())[:3], [1, 2, 1])


def test_symmetrize_adds_reverse_slots():
    src = np.array([0, 1])
    dst = np.array([1, 2])
    g = build_csr(src, dst, np.ones(2, np.float32), 3, symmetrize=True)
    assert int(g.e_valid) == 4
    k = np.asarray(g.vertex_weights())
    np.testing.assert_allclose(k[:3], [1.0, 2.0, 1.0])


def test_dedup_sums_parallel_edges():
    src = np.array([0, 0, 1, 1])
    dst = np.array([1, 1, 0, 0])
    g = build_csr(src, dst, np.full(4, 2.0, np.float32), 2)
    assert int(g.e_valid) == 2
    assert float(g.total_weight()) == 4.0


def test_self_loop_single_slot():
    g = build_csr(np.array([0]), np.array([0]), np.array([3.0]), 2,
                  symmetrize=True)
    assert int(g.e_valid) == 1
    assert float(g.total_weight()) == 1.5


@settings(max_examples=25, deadline=None)
@given(st.integers(5, 40), st.integers(0, 10_000))
def test_weight_conservation_random(n, seed):
    """sum(K_i) == 2m on arbitrary random graphs (property)."""
    rng = np.random.default_rng(seed)
    e = rng.integers(1, 4 * n)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.random(e).astype(np.float32) + 0.1
    # fixed capacities: every example reuses one compiled vertex_weights()
    g = build_csr(src, dst, w, n, symmetrize=True, n_cap=40, e_cap=2 * 160)
    k = np.asarray(g.vertex_weights())
    assert np.isclose(k.sum(), 2 * float(g.total_weight()), rtol=1e-5)
    # padding slots carry zero weight and sentinel indices
    e_valid = int(g.e_valid)
    assert np.all(np.asarray(g.weights)[e_valid:] == 0)
    assert np.all(np.asarray(g.indices)[e_valid:] == g.n_cap)


def test_from_networkx_karate():
    g = from_networkx(nx.karate_club_graph())
    assert int(g.n_valid) == 34
    assert int(g.e_valid) == 2 * 78
    # karate_club_graph is weighted (interaction counts, sum = 231)
    assert float(g.total_weight()) == 231.0


def test_ell_blocks_cover_all_vertices():
    g = from_networkx(nx.les_miserables_graph())
    blocks, leftover = to_ell_blocks(g, widths=(4, 16, 64))
    seen = set(leftover.tolist())
    n_cap = g.n_cap
    for b in blocks:
        rows = np.asarray(b.rows)
        seen.update(rows[rows < n_cap].tolist())
        # every row's neighbor slots either live or sentinel-padded
        cols = np.asarray(b.cols)
        w = np.asarray(b.w)
        assert np.all(w[cols == n_cap] == 0)
    assert seen == set(range(int(g.n_valid)))


def test_ell_blocks_degree_bounds():
    g = from_networkx(nx.les_miserables_graph())
    widths = (4, 16, 64)
    blocks, leftover = to_ell_blocks(g, widths=widths)
    deg = np.asarray(g.degrees())
    for width, b in zip(widths, blocks):
        rows = np.asarray(b.rows)
        live = rows[rows < g.n_cap]
        assert np.all(deg[live] <= width)
    assert all(deg[v] > widths[-1] for v in leftover)


def _ell_blocks_loop(graph, widths):
    """Row-by-row reference of ``to_ell_blocks`` (the pre-vectorised loop)."""
    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    weights = np.asarray(graph.weights)
    n, n_cap = int(graph.n_valid), graph.n_cap
    deg = indptr[1:n + 1] - indptr[:n]
    out, lo = [], 0
    assigned = np.zeros(n, bool)
    for width in widths:
        sel = np.where((deg > lo) & (deg <= width))[0]
        if width == widths[0]:
            sel = np.where(deg <= width)[0]
        lo = width
        n_rows = padded_rows(len(sel), width)
        rows = np.full(n_rows, n_cap, np.int32)
        cols = np.full((n_rows, width), n_cap, np.int32)
        wmat = np.zeros((n_rows, width), np.float32)
        rows[:len(sel)] = sel
        for r, v in enumerate(sel):
            s, e = indptr[v], indptr[v + 1]
            cols[r, :e - s] = indices[s:e]
            wmat[r, :e - s] = weights[s:e]
        assigned[sel] = True
        out.append((rows, cols, wmat))
    return out, np.where(~assigned)[0]


@pytest.mark.parametrize("seed,widths", [(0, (16, 64, 256)), (1, (4, 8)),
                                         (2, (2, 16, 32))])
def test_ell_blocks_match_row_loop(seed, widths):
    from repro.data import rmat_graph
    g = rmat_graph(9, edge_factor=8, seed=seed)
    blocks, leftover = to_ell_blocks(g, widths)
    want, want_left = _ell_blocks_loop(g, widths)
    np.testing.assert_array_equal(leftover, want_left)
    for b, ref in zip(blocks, want):
        for got, exp in zip(b, ref):
            np.testing.assert_array_equal(np.asarray(got), exp)


@pytest.mark.parametrize("width", [2, 16, 64, 256])
def test_ell_rows_pad_to_whole_tiles(width):
    """Every bucket size pads to whole kernel tiles that are multiples of 8,
    with less than one tile (past the 8-row rounding) of padding."""
    for n in [0, 1, 7, 8, 9, 63, 64, 65, 255, 256, 257, 264, 1000, 4103]:
        rows = padded_rows(n, width)
        tile = tile_rows(rows, width)
        assert tile % 8 == 0 and rows % tile == 0
        assert rows >= max(n, 1) and rows - tile < -(-max(n, 1) // 8) * 8


def test_dedup_sums_match_sequential_add():
    """Parallel-slot sums equal a sequential float64 np.add.at."""
    rng = np.random.default_rng(5)
    src = rng.integers(0, 30, 4000)
    dst = rng.integers(0, 30, 4000)
    w = rng.random(4000).astype(np.float32)
    g = build_csr(src, dst, w, 30)
    key = src * 31 + dst
    order = np.argsort(key, kind="stable")
    first = np.ones(len(key), bool)
    first[1:] = key[order][1:] != key[order][:-1]
    gid = np.cumsum(first) - 1
    want = np.zeros(gid[-1] + 1)
    np.add.at(want, gid, w[order].astype(np.float64))
    e = int(g.e_valid)
    np.testing.assert_array_equal(np.asarray(g.weights)[:e],
                                  want.astype(np.float32))
