"""``bench/program.py``: the program's own ``gve.*`` host spans and
counters, reduced over the traced window.

``static_s10.xplane.pb.gz`` was recorded before the program had spans;
``stream_spans.xplane.pb.gz`` holds three ``gc-sbm.stream`` updates traced
on a TPU v5e as ``bench/run.py --trace 1`` traces them.  The expected
numbers of the latter were read off its host and device planes by hand."""

import os
from types import SimpleNamespace

import pytest

from bench import program, trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
STATIC = os.path.join(DATA, "static_s10.xplane.pb.gz")
STREAM = os.path.join(DATA, "stream_spans.xplane.pb.gz")

# Read off the stream trace by hand: three updates, each one pass of one
# sweep over all 211,830 slots, and 12 device reads per update.
WINDOW_S = (406608584.0 - 42769889.0) * 1e-9
BUSY_S = 0.32551705400000003
ONCE = ("gve.make_edge_batch", "gve.louvain_dynamic", "gve.apply",
        "gve.screen", "gve.louvain", "gve.warm_start", "gve.pass",
        "gve.move", "gve.renumber", "gve.sync.e_new", "gve.sync.frontier",
        "gve.sync.iters", "gve.sync.n_comms", "gve.sync.level",
        "gve.sync.dq_sum", "gve.sync.b_valid", "gve.sync.n_touched")
COUNTS = dict({name: 3 for name in ONCE}, **{"gve.sync.n_vertices": 12})
IDLE_S = {"gve.louvain_dynamic": 0.033844301, "gve.apply": 0.00572126,
          "gve.move": 0.00605037, "gve.sync.e_new": 0.003552018,
          "gve.sync.iters": 0.004001195}
FRONTIERS = (2749, 1987, 2745)
IDLE_BY_SPAN = [["batch_build > gve.make_edge_batch", 0.00931994],
                ["louvain_dynamic > gve.sync.iters", 0.006434863],
                ["louvain_dynamic > gve.sync.frontier", 0.005411633],
                ["louvain_dynamic > gve.sync.e_new", 0.004766544],
                ["louvain_dynamic > gve.renumber", 0.004725101],
                ["louvain_dynamic > gve.warm_start", 0.002854087],
                ["louvain_dynamic > gve.sync.n_comms", 0.002160092],
                ["louvain_dynamic > gve.sync.n_vertices", 0.001473081],
                ["louvain_dynamic > gve.sync.b_valid", 0.001172794],
                ["louvain_dynamic > gve.apply", 2.148e-06]]


def _ev(**stats):
    return SimpleNamespace(stats=list(stats.items()))


def test_idle_inside_spans():
    """Device busy over [10, 20) and [30, 60) ns of a [0, 100) window."""
    busy = [[10.0, 20.0], [30.0, 60.0]]
    line = [("louvain_dynamic", 0.0, 100.0, _ev()),
            ("gve.louvain_dynamic", 5.0, 90.0, _ev()),
            ("gve.sync.e_new", 15.0, 20.0, _ev()),
            ("gve.sync.iters", 25.0, 40.0, _ev()),
            ("gve.pass.counts", 70.0, 0.0, _ev(sweeps=2, slots=8)),
            ("gve.sync.late", 100.0, 5.0, _ev()),
            ("np.asarray(jax.Array)", 26.0, 1.0, _ev())]
    out = program._spans_and_counters([line], busy, 0.0, 100.0)
    assert set(out["spans"]) == {"gve.louvain_dynamic", "gve.sync.e_new",
                                 "gve.sync.iters"}
    assert out["spans"]["gve.louvain_dynamic"]["idle_s"] == pytest.approx(
        (90 - 10 - 30) * 1e-9)
    assert out["spans"]["gve.sync.e_new"]["idle_s"] == pytest.approx(
        (20 - 5 - 5) * 1e-9)
    assert out["spans"]["gve.sync.iters"]["idle_s"] == pytest.approx(
        (40 - 30) * 1e-9)
    assert out["counters"] == [{"sweeps": 2, "slots": 8,
                                "name": "gve.pass.counts"}]
    # Gaps [0, 10), [20, 30) and [60, 100), each named at its middle.
    gaps = program._idle_by_span(line, busy, 0.0, 100.0)
    assert dict(gaps) == pytest.approx({
        "louvain_dynamic > gve.louvain_dynamic": 50e-9,
        "louvain_dynamic > gve.sync.iters": 10e-9})


def test_per_layer_formulas():
    prog = {"spans": {"gve.sync.a": {"count": 6, "seconds": 0.0,
                                     "idle_s": 0.0},
                      "gve.sync.b": {"count": 3, "seconds": 0.0,
                                     "idle_s": 0.0},
                      "gve.louvain_dynamic": {"count": 3, "seconds": 0.3,
                                              "idle_s": 0.03}},
            "counters": [
                {"name": "gve.pass.counts", "pass": 0, "sweeps": 3,
                 "slots": 100, "frontier": 20, "n_vertices": 50},
                {"name": "gve.pass.counts", "pass": 1, "sweeps": 2,
                 "slots": 10, "frontier": 9, "n_vertices": 9},
                {"name": "gve.pass.counts", "pass": 0, "sweeps": 1,
                 "slots": 100, "frontier": 30, "n_vertices": 50}]}
    summary = {"modules": {"jit__move_phase": {"seconds": 4.2e-6,
                                               "runs": 3}}}
    stream = program.per_layer(prog, summary, {"batches": 3})
    assert stream == pytest.approx({"host_syncs_per_batch": 3.0,
                                    "program_idle_ms_per_batch": 10.0,
                                    "frontier_pct": 50.0})
    static = program.per_layer(prog, summary, {"detections": 2})
    assert static == pytest.approx({"slot_sweeps_per_run": 210.0,
                                    "move_ns_per_slot_sweep": 10.0})


def test_nothing_without_program_spans():
    """A trace of a program without spans gives no program number, and
    every gap falls to the benchmark's span alone."""
    out = program.reduce(STATIC)
    summary = trace.reduce(STATIC)
    assert out["spans"] == {} and out["counters"] == []
    assert program.per_layer(out, summary, {"detections": 1}) == {}
    assert program.per_layer(out, summary, {"batches": 3}) == {}
    [[name, idle]] = out["idle_by_span"]
    assert name == "louvain"
    assert idle == pytest.approx(summary["window_s"] - summary["busy_s"],
                                 rel=1e-9)


@pytest.fixture(scope="module")
def stream():
    return program.reduce(STREAM), trace.reduce(STREAM)


def test_stream_spans_and_idle(stream):
    out, summary = stream
    assert summary["window_s"] == pytest.approx(WINDOW_S, rel=1e-9)
    assert summary["busy_s"] == pytest.approx(BUSY_S, rel=1e-9)
    assert {k: v["count"] for k, v in out["spans"].items()} == COUNTS
    for name, idle in IDLE_S.items():
        assert out["spans"][name]["idle_s"] == pytest.approx(idle,
                                                              rel=1e-6)
    update = out["spans"]["gve.louvain_dynamic"]
    assert update["idle_s"] < update["seconds"] < WINDOW_S


def test_stream_counters(stream):
    out, _ = stream
    assert out["counters"] == [
        {"name": "gve.pass.counts", "pass": 0, "sweeps": 1,
         "slots": 211830, "n_vertices": 5000, "n_communities": 27,
         "frontier": f} for f in FRONTIERS]


def test_stream_idle_by_span(stream):
    out, summary = stream
    assert [k for k, _ in out["idle_by_span"]] == [
        k for k, _ in IDLE_BY_SPAN]
    for (_, got), (_, want) in zip(out["idle_by_span"], IDLE_BY_SPAN):
        assert got == pytest.approx(want, rel=1e-6)
    # The ten hold all but 1.4 us of the window's idle.
    idle = summary["window_s"] - summary["busy_s"]
    named = sum(v for _, v in out["idle_by_span"])
    assert idle - 2e-6 < named <= idle


def test_stream_per_layer(stream):
    out, summary = stream
    assert program.per_layer(out, summary, {"batches": 3}) == pytest.approx(
        {"host_syncs_per_batch": 12.0,
         "program_idle_ms_per_batch": 1e3 * 0.033844301 / 3,
         "frontier_pct": 100.0 * sum(FRONTIERS) / 15000})


def test_stream_reads_are_named_syncs():
    """On the chip every ``np.asarray(jax.Array)`` event of an update lies
    inside a ``gve.sync.*`` span."""
    host = next(p for p in program.load(STREAM).planes
                if p.name == "/host:CPU")
    reads = updates = 0
    for line in host.lines:
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events]
        within = [e for e in events if e[0] == "gve.louvain_dynamic"]
        syncs = [e for e in events if e[0].startswith("gve.sync.")]
        updates += len(within)
        for name, s, e in events:
            if name == "np.asarray(jax.Array)" and any(
                    a <= s and e <= b for _, a, b in within):
                reads += 1
                assert any(a <= s and e <= b for _, a, b in syncs)
    assert updates == 3 and reads == 36
