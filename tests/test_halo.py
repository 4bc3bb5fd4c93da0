"""Halo-exchange distribution (core/gnn_halo) must compute EXACTLY the same
loss as the single-device model on a real Louvain-partitioned graph — for
both GIN and Equiformer (the latter also validates the m-truncated rotation
is exact).  Runs on 8 forced host devices in a subprocess."""

import json
import os
import subprocess
import sys

import pytest

from conftest import multi_device as _multi_device

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.gnn_halo import (HaloSpec, build_halo_inputs,
                                 equiformer_halo_loss_shard,
                                 gin_halo_loss_shard)
from repro.core.graph import from_networkx
from repro.core.partition import louvain_partition
from repro.models.gnn import equiformer, gin
from repro.models.gnn.common import GraphBatch, node_ce_loss

N_SHARDS = 8
out = {}

# --- a modular graph + its Louvain order ------------------------------------
nxg = nx.connected_caveman_graph(8, 8)          # 64 nodes
g = from_networkx(nxg)
n = int(g.n_valid)
lp = louvain_partition(g, N_SHARDS)
order = lp.order                                # community-contiguous perm

src = np.asarray(g.src)[: int(g.e_valid)]
dst = np.asarray(g.indices)[: int(g.e_valid)]

v_l = n // N_SHARDS
spec = HaloSpec(N_SHARDS, v_l, e_per_shard=len(src), send_cap=v_l)
halo = build_halo_inputs(src, dst, order, N_SHARDS, n, len(src) * N_SHARDS,
                         spec)

rng = np.random.default_rng(0)
feat = rng.standard_normal((n, 8)).astype(np.float32)
pos = rng.standard_normal((n, 3)).astype(np.float32)
labels = rng.integers(0, 4, n).astype(np.int32)

# permuted (Louvain-order) arrays — the layout the halo step consumes
perm = halo["perm"]
feat_p, pos_p, labels_p = feat[perm], pos[perm], labels[perm]
inv = np.argsort(perm)
src_p, dst_p = inv[src], inv[dst]

from repro.compat import make_mesh
mesh = make_mesh((N_SHARDS,), ("i",))
axes = ("i",)
shard1, rep = P("i"), P()

def run_halo(loss_shard, params, arrays, in_specs):
    fn = jax.shard_map(loss_shard, mesh=mesh, in_specs=in_specs, out_specs=rep,
                       check_vma=False)
    with mesh:
        return float(jax.jit(fn)(params, *arrays))

# --- GIN ---------------------------------------------------------------------
cfg = gin.GINConfig(n_layers=2, d_hidden=16, d_feat=8, n_classes=4)
params = gin.init_params(cfg, jax.random.PRNGKey(0))

loss_halo = run_halo(
    lambda p, nf, es, ed, lab, sidx: gin_halo_loss_shard(
        cfg, p, nf, es, ed, lab, sidx, n, spec, axes),
    params,
    (jnp.asarray(feat_p), jnp.asarray(halo["edge_src"]),
     jnp.asarray(halo["edge_dst"]), jnp.asarray(labels_p),
     jnp.asarray(halo["send_idx"])),
    (jax.tree.map(lambda _: rep, params), P("i", None), shard1, shard1,
     shard1, P("i", None)))

gref = GraphBatch(node_feat=jnp.asarray(feat_p),
                  edge_src=jnp.asarray(src_p, jnp.int32),
                  edge_dst=jnp.asarray(dst_p, jnp.int32),
                  n_nodes=jnp.int32(n), labels=jnp.asarray(labels_p),
                  graph_id=jnp.zeros((n,), jnp.int32),
                  n_graphs=jnp.int32(1))
logits = gin.forward(cfg, params, gref)
loss_ref = float(node_ce_loss(logits, jnp.asarray(labels_p),
                              jnp.ones((n,), jnp.float32)))
out["gin"] = {"halo": loss_halo, "ref": loss_ref}

# --- Equiformer (validates m-truncation exactness too) -----------------------
ecfg = equiformer.EquiformerConfig(n_layers=2, d_hidden=8, l_max=3, m_max=1,
                                   n_heads=2, d_feat=8, out_dim=4,
                                   node_level=True)
eparams = equiformer.init_params(ecfg, jax.random.PRNGKey(1))

for trunc in (True, False):
    out[f"equi_trunc_{trunc}"] = run_halo(
        lambda p, nf, po, es, ed, lab, sidx: equiformer_halo_loss_shard(
            ecfg, p, nf, po, es, ed, lab, sidx, n, spec, axes,
            m_truncate=trunc),
        eparams,
        (jnp.asarray(feat_p), jnp.asarray(pos_p),
         jnp.asarray(halo["edge_src"]), jnp.asarray(halo["edge_dst"]),
         jnp.asarray(labels_p), jnp.asarray(halo["send_idx"])),
        (jax.tree.map(lambda _: rep, eparams), P("i", None), P("i", None),
         shard1, shard1, shard1, P("i", None)))

egref = GraphBatch(node_feat=jnp.asarray(feat_p),
                   edge_src=jnp.asarray(src_p, jnp.int32),
                   edge_dst=jnp.asarray(dst_p, jnp.int32),
                   n_nodes=jnp.int32(n), labels=jnp.asarray(labels_p),
                   graph_id=jnp.zeros((n,), jnp.int32),
                   n_graphs=jnp.int32(1),
                   positions=jnp.asarray(pos_p))
elogits = equiformer.forward(ecfg, eparams, egref)
out["equi_ref"] = float(node_ce_loss(elogits, jnp.asarray(labels_p),
                                     jnp.ones((n,), jnp.float32)))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def halo_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@_multi_device
def test_gin_halo_matches_reference(halo_results):
    r = halo_results["gin"]
    assert abs(r["halo"] - r["ref"]) < 1e-4 * max(abs(r["ref"]), 1), r


@pytest.mark.slow
@_multi_device
def test_equiformer_halo_matches_reference(halo_results):
    ref = halo_results["equi_ref"]
    got = halo_results["equi_trunc_False"]
    assert abs(got - ref) < 1e-3 * max(abs(ref), 1), (got, ref)


@pytest.mark.slow
@_multi_device
def test_equiformer_m_truncation_exact(halo_results):
    """Truncated-rotation path == full-rotation path (the |m|>m_max
    coefficients it skips are provably unused)."""
    a = halo_results["equi_trunc_True"]
    b = halo_results["equi_trunc_False"]
    assert abs(a - b) < 1e-4 * max(abs(b), 1), (a, b)


def test_halo_step_lowers_locally():
    """build_halo_step (the --variant halo dry-run path) lowers + compiles
    on a local mesh for the small full-graph shape."""
    import jax
    from repro.configs.registry import get_arch

    from repro.compat import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    arch = get_arch("gin-tu")
    fn, args, shardings = arch.build_step("full_graph_sm", mesh,
                                          variant=("halo",))
    donate = getattr(fn, "donate_argnums", ())
    with mesh:
        jax.jit(fn, in_shardings=shardings,
                donate_argnums=donate).lower(*args).compile()
