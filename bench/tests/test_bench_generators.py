"""The on-device generators build the program's CSR to its slot contract."""

import json
import os

import jax
import numpy as np
import pytest

from bench.gen import csr, dcsbm, graph500
from repro.core.graph import build_csr

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _distinct_pairs(u, v):
    u, v = np.asarray(u, np.int64), np.asarray(v, np.int64)
    keep = u != v
    a, b = np.minimum(u, v)[keep], np.maximum(u, v)[keep]
    pairs = np.unique(np.stack([a, b], 1), axis=0)
    return pairs[:, 0], pairs[:, 1]


def _graph500_cfg(scale):
    with open(os.path.join(HERE, "configs", "graph500.json")) as f:
        cfg = json.load(f)
    return dict(cfg, scale=scale)


def _dcsbm_cfg(n):
    with open(os.path.join(HERE, "configs", "gc-sbm.json")) as f:
        cfg = json.load(f)
    blocks = round(n ** 0.35)
    return dict(cfg, vertices=n, blocks=blocks, raw_edges=n * 21)


CASES = [("graph500", graph500, _graph500_cfg(8)),
         ("graph500", graph500, _graph500_cfg(10)),
         ("dcsbm", dcsbm, _dcsbm_cfg(600))]


@pytest.mark.parametrize("name,gen,cfg", CASES,
                         ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_device_csr_equals_build_csr(name, gen, cfg):
    n, m = gen.sizes(cfg)
    key = jax.random.key(7)
    u, v = gen.raw_edges(cfg, key)
    assert u.shape == (m,) and int(np.max(u)) < n and int(np.min(v)) >= 0
    g, _, _, n_distinct, _ = csr.build(u, v, key, n=n, e_cap=2 * m)
    a, b = _distinct_pairs(u, v)
    want = build_csr(np.r_[a, b], np.r_[b, a], np.ones(2 * len(a), np.float32),
                     n, n_cap=n, e_cap=2 * m)
    assert int(n_distinct) == len(a)
    for field in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(g, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)


@pytest.mark.parametrize("hold_share", [0.0, 0.75])
def test_stream_pool_is_held_out_and_present(hold_share):
    cfg = _dcsbm_cfg(600)
    n, m = dcsbm.sizes(cfg)
    key = jax.random.key(3)
    u, v = dcsbm.raw_edges(cfg, key)
    g, ins, dels, n_distinct, n_held = csr.build(
        u, v, key, n=n, e_cap=2 * m, n_insert=40, n_delete=10,
        hold_share=hold_share)
    a, b = _distinct_pairs(u, v)
    assert int(n_distinct) == len(a)
    assert int(n_held) == max(40, round(hold_share * len(a)))
    e = int(g.e_valid)
    assert e == 2 * (len(a) - int(n_held))
    have = set(zip(np.asarray(g.src)[:e].tolist(),
                   np.asarray(g.indices)[:e].tolist()))
    ins, dels = np.asarray(ins), np.asarray(dels)
    assert ins.shape == (40, 2) and dels.shape == (10, 2)
    assert not any((x, y) in have for x, y in ins.tolist())
    assert all((x, y) in have and (y, x) in have for x, y in dels.tolist())
    assert len({tuple(p) for p in np.r_[ins, dels].tolist()}) == 50


def test_same_seed_same_graph():
    cfg = _graph500_cfg(8)
    one = graph500.raw_edges(cfg, jax.random.key(11))
    two = graph500.raw_edges(cfg, jax.random.key(11))
    other = graph500.raw_edges(cfg, jax.random.key(12))
    np.testing.assert_array_equal(np.asarray(one[0]), np.asarray(two[0]))
    assert not np.array_equal(np.asarray(one[0]), np.asarray(other[0]))


def test_dcsbm_plants_its_blocks():
    cfg = _dcsbm_cfg(2000)
    n, m = dcsbm.sizes(cfg)
    key = jax.random.key(5)
    u, v = (np.asarray(x) for x in dcsbm.raw_edges(cfg, key))
    block = np.asarray(dcsbm.planted_blocks(cfg, key))
    assert np.bincount(block).min() == n // cfg["blocks"]
    inside = np.mean(block[u] == block[v])
    ratio = cfg["in_block_ratio"]
    assert inside == pytest.approx(ratio / (1 + ratio), abs=0.02)
    deg = np.bincount(np.r_[u, v], minlength=n)
    assert deg.mean() == pytest.approx(2 * m / n)
