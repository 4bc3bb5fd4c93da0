"""The dry-run Louvain phases: the all_to_all aggregation variant must
produce the same coarse graph as the gather baseline (subprocess, 8 devices),
and the arch protocol must lower on a local mesh."""

import json
import os
import subprocess
import sys

import pytest

from conftest import multi_device as _multi_device

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import functools, json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.louvain_arch import (_aggregate_a2a_body,
                                        _aggregate_gather_body)
from repro.core.distributed import ShardedGraphSpec

P_SHARDS = 8
rng = np.random.default_rng(0)
n, e_l = 64, 48                     # per-shard edges
e = P_SHARDS * e_l
spec = ShardedGraphSpec(P_SHARDS, n // P_SHARDS, e_l, n)

src = rng.integers(0, n, e).astype(np.int32)
dst = rng.integers(0, n, e).astype(np.int32)
w = rng.random(e).astype(np.float32) + 0.1
# 12 community ids spread evenly over the vertex-id range, so each shard
# owns <= 2 communities and coarse-edge ownership stays within e_l
# (the skewed/overflow case is tested separately below).
ids = (np.arange(12) * n) // 12
comm_map = ids[rng.integers(0, 12, n)].astype(np.int32)
comm = jnp.asarray(np.concatenate([comm_map, [n]]))  # sentinel slot

from repro.compat import make_mesh
mesh = make_mesh((P_SHARDS,), ("i",))
axes = ("i",)
edge, rep = P("i"), P()

def run(body):
    fn = jax.shard_map(body, mesh=mesh, in_specs=(edge, edge, edge, rep),
                       out_specs=(edge, edge, edge, rep, rep), check_vma=False)
    with mesh:
        return jax.jit(fn)(jnp.asarray(src), jnp.asarray(dst),
                           jnp.asarray(w), comm)

def coarse_dict(ci, cj, cw):
    ci, cj, cw = np.asarray(ci), np.asarray(cj), np.asarray(cw)
    out = {}
    for a, b, x in zip(ci, cj, cw):
        if a < n:
            out[(int(a), int(b))] = out.get((int(a), int(b)), 0.0) + float(x)
    return out

base = run(functools.partial(_aggregate_gather_body, axes, spec))
a2a = run(functools.partial(_aggregate_a2a_body, axes, spec, 8))

# ground truth from numpy
truth = {}
for s_, d_, ww in zip(comm_map[src], comm_map[dst], w):
    truth[(int(s_), int(d_))] = truth.get((int(s_), int(d_)), 0.0) + float(ww)

d_base, d_a2a = coarse_dict(*base[:3]), coarse_dict(*a2a[:3])
keys_match = set(d_base) == set(d_a2a) == set(truth)
max_diff = max((abs(d_base[k] - d_a2a[k]) for k in d_base), default=0.0)
max_vs_truth = max(abs(d_a2a[k] - truth[k]) for k in truth)

# skewed case: 8 communities all owned by shard 0 (ids < v_per) -> up to 64
# coarse pairs on one shard, beyond e_l=48 -> overflow must be flagged
comm_skew = jnp.asarray(np.concatenate(
    [rng.integers(0, 8, n).astype(np.int32), [n]]))
def run_skew(body):
    fn = jax.shard_map(body, mesh=mesh, in_specs=(edge, edge, edge, rep),
                       out_specs=(edge, edge, edge, rep, rep), check_vma=False)
    with mesh:
        return jax.jit(fn)(jnp.asarray(src), jnp.asarray(dst),
                           jnp.asarray(w), comm_skew)
skew = run_skew(functools.partial(_aggregate_gather_body, axes, spec))

# --- delta-encoded move round == baseline round (singleton start) -----------
from repro.configs.louvain_arch import _move_round_delta
from repro.core.distributed import _round_body

k_arr = np.zeros(n + 1, np.float32)
np.add.at(k_arr, src, w)
k_j = jnp.asarray(k_arr)
m_tot = jnp.float32(w.sum() / 2)
comm0 = jnp.asarray(np.concatenate([np.arange(n), [n]]).astype(np.int32))
sigma0 = k_j
sizes0 = jnp.asarray(np.concatenate([np.ones(n), [0]]).astype(np.int32))

def base_round(src_l, dst_l, w_l, comm_, sigma_, k_, m_):
    frontier = jnp.ones((spec.v_per_shard,), bool)
    return _round_body(axes, spec, src_l, dst_l, w_l, comm_, sigma_, k_,
                       frontier, jnp.int32(0), 2, m_)

fn_b = jax.shard_map(base_round, mesh=mesh,
                     in_specs=(edge, edge, edge, rep, rep, rep, rep),
                     out_specs=(rep, rep, edge, rep), check_vma=False)
fn_d = jax.shard_map(functools.partial(_move_round_delta, axes, spec, 1),
                     mesh=mesh,
                     in_specs=(edge, edge, edge, rep, rep, rep, rep, rep),
                     out_specs=(rep, rep, rep, edge, rep, rep), check_vma=False)
with mesh:
    cb, sb, fb, dqb = jax.jit(fn_b)(jnp.asarray(src), jnp.asarray(dst),
                                    jnp.asarray(w), comm0, sigma0, k_j,
                                    m_tot)
    cd, sd, szd, fd, dqd, ovf = jax.jit(fn_d)(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w), comm0, sigma0,
        sizes0, k_j, m_tot)

move_match = bool(jnp.all(cb == cd))
sigma_diff = float(jnp.max(jnp.abs(sb - sd)))
dq_diff = abs(float(dqb) - float(dqd))
n_moved = int(jnp.sum(cd[:-1] != comm0[:-1]))

print(json.dumps({
    "keys_match": keys_match, "max_diff": max_diff,
    "max_vs_truth": max_vs_truth,
    "e_valid_base": int(base[3]), "e_valid_a2a": int(a2a[3]),
    "base_owned_max": int(base[4]), "a2a_dropped": int(a2a[4]),
    "skew_owned_max": int(skew[4]), "e_l": e_l,
    "n_coarse_edges": len(d_base),
    "move_match": move_match, "sigma_diff": sigma_diff,
    "dq_diff": dq_diff, "n_moved": n_moved,
    "move_overflow": int(ovf)}))
"""


@pytest.fixture(scope="module")
def agg_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@_multi_device
def test_a2a_aggregation_matches_gather_baseline(agg_results):
    r = agg_results
    assert r["keys_match"], r
    assert r["max_diff"] < 1e-4, r
    assert r["max_vs_truth"] < 1e-4, r
    assert r["e_valid_base"] == r["e_valid_a2a"]
    assert r["a2a_dropped"] == 0
    assert r["base_owned_max"] <= r["e_l"]
    assert r["n_coarse_edges"] > 10


@pytest.mark.slow
@_multi_device
def test_gather_baseline_overflow_detected(agg_results):
    """Community-ownership skew beyond per-shard capacity must be flagged
    (the silent-drop bug this test originally caught)."""
    r = agg_results
    assert r["skew_owned_max"] > r["e_l"], r


@pytest.mark.slow
@_multi_device
def test_delta_encoded_move_round_matches_baseline(agg_results):
    """The delta-C exchange reconstructs exactly the same (C, Σ, dQ) as the
    dense all_gather/psum round."""
    r = agg_results
    assert r["move_overflow"] <= 0, r
    assert r["move_match"], r
    assert r["sigma_diff"] < 1e-4, r
    assert r["dq_diff"] < 1e-4, r
    assert r["n_moved"] > 0, "test vacuous — no vertex moved"


def test_louvain_arch_lowers_locally():
    import jax
    from repro.compat import make_mesh
    from repro.configs.louvain_arch import ARCH
    mesh = make_mesh((1, 1), ("data", "model"))
    for shape in ("road_108M_move", "road_108M_aggregate"):
        fn, args, shardings = ARCH.build_step(shape, mesh, smoke=True)
        with mesh:
            jax.jit(fn, in_shardings=shardings).lower(*args).compile()
