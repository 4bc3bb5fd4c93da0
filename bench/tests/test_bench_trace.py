"""``bench/trace.py`` on a trace recorded on a TPU v5e: one cold-started
``louvain`` on a scale-10 Graph 500 graph under the benchmark's ``louvain``
span.  The expected numbers were read off the trace by hand (the span's
ends, the ``XLA Modules`` runs, the union of the ``XLA Ops`` intervals)."""

import os

import pytest

from bench import run, trace

TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "static_s10.xplane.pb.gz")

WINDOW_S = (137474309.0 - 44364980.0) * 1e-9
BUSY_S = 0.072959956
MODULE_S = {"jit__move_phase": 0.070375269,
            "jit__aggregate_phase": 0.002258095,
            "jit__renumber_and_fold": 6.3814e-05,
            "jit_singleton_init": 4.1192e-05,
            "jit_rebucket_capacity": 8.346e-06}


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(TRACE)


def test_window_and_busy(summary):
    assert summary["window_s"] == pytest.approx(WINDOW_S, rel=1e-9)
    assert summary["busy_s"] == pytest.approx(BUSY_S, rel=1e-9)
    idle = run.load_reader("device_idle_pct.static").read(summary, {})
    assert idle == pytest.approx(100 * (1 - BUSY_S / WINDOW_S), rel=1e-9)
    assert idle == pytest.approx(21.6406, abs=1e-3)


def test_module_times(summary):
    for name, seconds in MODULE_S.items():
        assert summary["modules"][name]["seconds"] == pytest.approx(
            seconds, rel=1e-6), name
    ctx = {"detections": 1}
    move = run.load_reader("move_ms_per_run.static").read(summary, ctx)
    assert move == pytest.approx(1e3 * (0.070375269 + 4.1192e-05), rel=1e-6)


def test_coarsen_kernel_and_breakdown(summary):
    kernel = [k for k in summary["ops"] if "coarsen_groups_pallas" in k]
    assert kernel and all(k.startswith("jit__aggregate_phase/")
                          for k in kernel)
    share = run.load_reader("coarsen_roofline.static").read(
        summary, {"aggregations_e_cap": [32768, 4096],
                  "device_kind": "TPU v5 lite"})
    assert 0 < share < 100
    for key in ("device_ops", "idle_gaps"):
        entries = summary["breakdown"][key]
        assert 0 < len(entries) <= 10
        assert all(isinstance(n, str) and v > 0 for n, v in entries)
    assert not any(n.split("/")[-1].startswith("while")
                   for n, _ in summary["breakdown"]["device_ops"])


def test_readers_return_nothing_without_their_work(summary):
    assert run.load_reader("apply_ms_per_batch.stream").read(
        summary, {"batches": 3}) is None
    assert run.load_reader("coarsen_roofline.static").read(
        dict(summary, ops={}), {"aggregations_e_cap": [32768],
                                "device_kind": "TPU v5 lite"}) is None
