"""The program's host spans and counters (``repro.core.spans``), read back
from a profiler trace on the CPU: every device read inside ``louvain`` and
``louvain_dynamic`` is a named ``gve.sync.*`` span, the per-pass counters
match the returned ``PassStats``, tracing changes no membership, and no
program span takes a name of the benchmark's own spans."""

import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

from _spans import inside, named, traced
from repro.configs.louvain_arch import compact_work_cap
from repro.core import spans
from repro.core.delta import make_edge_batch
from repro.core.dynamic import louvain_dynamic
from repro.core.graph import build_csr
from repro.core.louvain import LouvainConfig, louvain
from repro.data import sbm_graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READ = "np.asarray(jax.Array)"


def _bench_spans():
    spec = importlib.util.spec_from_file_location(
        "bench_trace_for_spans", os.path.join(ROOT, "bench", "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SPANS


def _graph():
    return sbm_graph(n_communities=8, size=16, p_in=0.4, p_out=0.01,
                     seed=5)[0]


def _stream(e_slack):
    """A graph short of 40 of its edges and the batches that insert them
    (and delete a few), in ``e_slack`` spare slots: a small slack makes
    the stream grow its capacity."""
    full = _graph()
    e = int(full.e_valid)
    src = np.asarray(full.src)[:e]
    dst = np.asarray(full.indices)[:e]
    und = src < dst
    us, ud = src[und], dst[und]
    hold = np.random.default_rng(1).choice(len(us), 40, replace=False)
    keep = np.ones(len(us), bool)
    keep[hold] = False
    graph = build_csr(np.r_[us[keep], ud[keep]], np.r_[ud[keep], us[keep]],
                      np.ones(2 * int(keep.sum()), np.float32),
                      int(full.n_valid), e_cap=2 * int(keep.sum()) + e_slack)
    batches = [make_edge_batch(np.r_[us[hold[i::4]], us[keep][i]],
                               np.r_[ud[hold[i::4]], ud[keep][i]],
                               np.r_[np.ones(10), 0.0].astype(np.float32),
                               graph.n_cap, b_cap=11)
               for i in range(4)]
    return graph, batches


def _static(config):
    return lambda: louvain(_graph(), config)


def _dynamic(config, e_slack):
    def run():
        graph, batches = _stream(e_slack)
        prev = louvain(graph, config).membership
        return louvain_dynamic(graph, batches, prev=prev, config=config)
    return run


CASES = {
    "static": _static(LouvainConfig()),
    "static_leiden_q": _static(LouvainConfig(refine="leiden",
                                             track_modularity=True)),
    "static_ell": _static(LouvainConfig(scan_backend="ell")),
    "stream_grow": _dynamic(LouvainConfig(), e_slack=8),
    "stream_compact": _dynamic(LouvainConfig(scan_backend="compact"),
                               e_slack=200),
}


@pytest.fixture(scope="module")
def plain():
    """Each case's result without the profiler; this run also compiles
    everything the traced run calls."""
    return {name: case() for name, case in CASES.items()}


@pytest.fixture(scope="module")
def runs(plain):
    """Each case run under the profiler: (result, host events)."""
    return {name: traced(case) for name, case in CASES.items()}


def _membership(result):
    return np.asarray(result.membership)


@pytest.mark.parametrize("case", CASES)
def test_every_read_is_a_named_sync(runs, case):
    _, events = runs[case]
    loops = [e for e in events
               if e.name in ("gve.louvain", "gve.louvain_dynamic")]
    syncs = [e for e in events if e.name.startswith("gve.sync.")]
    assert loops and syncs
    reads = [e for e in events if e.name == READ
             and any(inside(e, d) for d in loops)]
    loose = [e for e in reads if not any(inside(e, s) for s in syncs)]
    assert not loose, f"{len(loose)} device reads outside a gve.sync span"


def test_a_read_outside_fetch_is_seen():
    """The check above finds an ``int(x)`` that bypasses ``fetch``."""
    def run():
        with spans.span("louvain"):
            return int(jnp.int32(3) + 1), int(spans.fetch("x", jnp.int32(4)))
    _, events = traced(run)
    (outer,) = named(events, "gve.louvain")
    (sync,) = named(events, "gve.sync.x")
    reads = [e for e in named(events, READ) if inside(e, outer)]
    assert reads and not any(inside(e, sync) for e in reads)


@pytest.mark.parametrize("case", ["static", "static_leiden_q", "static_ell"])
def test_pass_counters_match_pass_stats(runs, case):
    result, events = runs[case]
    counts = named(events, "gve.pass.counts")
    passes = named(events, "gve.pass")
    assert len(counts) == len(passes) == len(result.passes) >= 1
    for i, (c, span, p) in enumerate(zip(counts, passes, result.passes)):
        assert c.stats == {"pass": i, "sweeps": p.iterations,
                           "slots": p.e_cap, "n_vertices": p.n_vertices,
                           "n_communities": p.n_communities,
                           "frontier": p.frontier_size}
        assert (span.stats["pass"], span.stats["n_cap"],
                span.stats["e_cap"]) == (i, p.n_cap, p.e_cap)
        assert span.end <= c.start


@pytest.mark.parametrize("case", ["stream_grow", "stream_compact"])
def test_stream_counters_match_batch_stats(runs, case):
    result, events = runs[case]
    updates = named(events, "gve.louvain_dynamic")
    assert len(updates) == 1
    # The cold detection of the case runs before the stream.
    stream = [c for c in named(events, "gve.pass.counts")
              if inside(c, updates[0])]
    first = [c for c in stream if c.stats["pass"] == 0]
    assert len(first) == len(result.batch_stats) == 4
    for c, b in zip(first, result.batch_stats):
        assert c.stats["frontier"] == b.frontier_size
        assert c.stats["frontier"] <= c.stats["n_vertices"] == b.n_vertices
    pass0 = [s for s in named(events, "gve.pass")
             if inside(s, updates[0]) and s.stats["pass"] == 0]
    for c, s in zip(first, pass0):
        if case == "stream_compact":
            assert s.stats["backend"] == "compact"
            assert c.stats["slots"] == compact_work_cap(
                s.stats["e_cap"], LouvainConfig().compact_cap_frac)
        else:
            assert c.stats["slots"] == s.stats["e_cap"]
    applies = [s for s in named(events, "gve.apply")
               if inside(s, updates[0])]
    assert len(applies) == 4
    grows = named(events, "gve.apply.grow")
    assert bool(grows) == (case == "stream_grow")
    assert all(any(inside(g, a) for a in applies) for g in grows)


@pytest.mark.parametrize("case", CASES)
def test_tracing_changes_no_membership(plain, runs, case):
    traced_result, _ = runs[case]
    result = plain[case]
    np.testing.assert_array_equal(_membership(result),
                                  _membership(traced_result))
    assert result.n_communities == traced_result.n_communities


@pytest.mark.parametrize("case", CASES)
def test_program_spans_keep_off_the_benchmark_names(runs, case):
    _, events = runs[case]
    bench = _bench_spans()
    program = {e.name for e in events if e.name.startswith(spans.PREFIX)}
    assert program and not program & set(bench)
    assert not any(e.name in bench for e in events)
    assert not any(name.startswith(spans.PREFIX) for name in bench)


def test_phase_spans_nest_in_their_pass(runs):
    """Every pass holds its move and renumber spans; a pass that
    aggregates holds an aggregate span."""
    result, events = runs["static"]
    passes = named(events, "gve.pass")
    assert len(passes) == len(result.passes) >= 2
    for i, p in enumerate(passes):
        within = {e.name for e in events if inside(e, p) and e is not p}
        assert {"gve.move", "gve.renumber", "gve.sync.iters",
                "gve.sync.n_comms", "gve.sync.level"} <= within
        assert ("gve.aggregate" in within) == (i < len(passes) - 1)
