"""A run end to end on tiny cells, with the chip check skipped: the output
line, the refusal without a TPU, and the check seeing faults planted in the
timed path (``correct`` false) while sound runs pass."""

import json
import os

import numpy as np
import pytest

from bench import run
from bench.loops import static as static_loop
from bench.loops import stream as stream_loop


def _run(root, cell, capsys, seconds=0.3):
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 11),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_chip=False, root=root)
    out, err = capsys.readouterr()
    assert rc == 0, err
    line = json.loads(out.strip().splitlines()[-1])
    return line, err


CELLS = ["graph500.static", "gc-sbm.stream"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell, capsys, no_cache):
    line, err = _run(root, cell, capsys)
    assert line["correct"] is True, err
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] >= 1 and line["failed"] == 0
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit=" in t for t in tail)


def test_refuses_without_tpu(root, capsys, no_cache):
    rc = run.main(["--workload", "graph500.static", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert rc == run.NO_CHIP and out == "" and "no result" in err


def _altered(membership):
    out = np.array(membership, copy=True)
    out[0] = out[1] if out[0] != out[1] else out[0] + 1
    return out


def _static_fault(kind):
    real = static_loop.louvain

    def louvain(graph, config):
        res = real(graph, config)
        if kind == "unchanged":        # the state it started from
            res.membership = np.arange(int(graph.n_valid), dtype=np.int32)
        elif kind == "altered":        # one answer altered where produced
            res.membership = _altered(res.membership)
        return res
    return louvain


def _stream_fault(kind):
    real = stream_loop.louvain_dynamic
    real_batch = stream_loop.make_edge_batch

    def louvain_dynamic(graph, batches, prev, **kw):
        res = real(graph, batches, prev=prev, **kw)
        if kind == "unchanged":        # the state it started from
            res.graph, res.membership = graph, np.asarray(prev).copy()
        elif kind == "unmoved":        # batch applied, warm move skipped
            res.membership = np.asarray(prev)[:len(res.membership)].copy()
        elif kind == "altered":
            res.membership = _altered(res.membership)
        return res

    def make_edge_batch(u, v, w, n_cap, b_cap):
        half = len(u) // 2             # half of the batch left out
        return real_batch(u[:half], v[:half], w[:half], n_cap, b_cap=b_cap)
    return louvain_dynamic, make_edge_batch


@pytest.mark.parametrize("cell,kind", [
    ("graph500.static", "unchanged"), ("graph500.static", "altered"),
    ("gc-sbm.stream", "unchanged"), ("gc-sbm.stream", "altered"),
    ("gc-sbm.stream", "half_batch"), ("gc-sbm.stream", "unmoved")])
def test_planted_fault_fails_the_check(root, cell, kind, capsys, no_cache,
                                       monkeypatch):
    if cell == "graph500.static":
        monkeypatch.setattr(static_loop, "louvain", _static_fault(kind))
    else:
        dyn, half = _stream_fault(kind)
        if kind == "half_batch":
            monkeypatch.setattr(stream_loop, "make_edge_batch", half)
        else:
            monkeypatch.setattr(stream_loop, "louvain_dynamic", dyn)
    line, err = _run(root, cell, capsys)
    assert line["correct"] is False, err
    assert line["failed"] >= 1
