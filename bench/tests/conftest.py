"""Tiny cells for the harness tests: a checkout whose configurations are cut
to a size the CPU runs in seconds, and JAX left without a persistent
compile cache."""

import json
import os
import shutil

import jax
import pytest

from bench import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = {"graph500": {"scale": 9},
        "gc-sbm": {"vertices": 1500, "blocks": 13, "raw_edges": 31500}}
TRAFFIC = {"stream": {"pool_batches": 60, "warmup_batches": 2,
                      "checked_batches": 6, "trace_batches": 3}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose cells are cut to a size the CPU runs in seconds."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(os.path.join(REPO, "bench", sub),
                        root / "bench" / sub)
    for name, change in TINY.items():
        path = root / "bench" / "configs" / f"{name}.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **change)))
    for name, change in TRAFFIC.items():
        path = root / "bench" / "traffic" / f"{name}.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **change)))
    return str(root)


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(run, "setup_jax", lambda root=None: jax)
