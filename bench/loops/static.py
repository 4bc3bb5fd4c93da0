"""Closed loop of cold detections: one user detecting communities in the
graphs they hold, one ``louvain`` call after the other.

The graphs are a fixed suite: the traffic names the generator seeds of its
graphs, and the run's seed only orders them.  Every run then does the same
work, in another order: how many sweeps a detection takes swings with the
graph (two passes at full capacity, or one long pass and a laddered one),
so graphs drawn from the run's seed would move the rate by more than any
change worth measuring.  The window runs whole rounds of the suite, each in
a fresh order, and starts a round only where it would end inside
``--seconds`` at the pace of the round before (the first always runs).

The check compares every detection of the window with the reference, and
one more on a graph drawn from the run's seed, run after the window by the
same compiled ``louvain``, so that every seed reaches data of its own.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench.loops import common
from bench.reference import louvain as ref
from repro.core.louvain import LouvainConfig, louvain


class Loop:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic = config, traffic
        self.suite = [int(s) for s in traffic["graphs"]]
        self.rng = np.random.default_rng(int(seed))
        self.seed = int(seed)
        self.done = []                # (graph index, LouvainResult)
        self.seconds = []             # of each detection in ``done``
        self.window_s = None

    def setup(self):
        self.graphs = [common.make_graph(self.config, s)[0]
                       for s in self.suite]
        self.e_valid = [int(g.e_valid) for g in self.graphs]
        self.lconfig = LouvainConfig()
        for i in range(len(self.graphs)):   # compiles every shape
            self._detect(i)
        self.done.clear()
        self.seconds.clear()

    def _detect(self, i: int):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("louvain"):
            result = louvain(self.graphs[i], self.lconfig)
        self.seconds.append(time.perf_counter() - t0)
        self.done.append((i, result))

    def _round(self):
        t0 = time.perf_counter()
        for i in self.rng.permutation(len(self.graphs)):
            self._detect(int(i))
        self.round_s = time.perf_counter() - t0

    def window(self, seconds: float):
        t0 = time.perf_counter()
        self._round()
        while time.perf_counter() - t0 + self.round_s <= seconds:
            self._round()
        self.window_s = time.perf_counter() - t0

    def traced(self):
        self._round()

    @property
    def attempted(self) -> int:
        """The window's detections and the seed's own."""
        return len(self.done) + 1

    def end_to_end(self) -> dict:
        edges = sum(self.e_valid[i] for i, _ in self.done)
        return {"detect_edges_per_s": (edges / self.window_s, "edges/s"),
                "modularity": (float(np.mean(self.q)), "Q")}

    def notes(self) -> dict:
        rounds = len(self.done) // len(self.suite)
        return {"detections": len(self.done), "rounds": rounds,
                "window_s": self.window_s,
                "detection_s": self.seconds}

    def trace_context(self) -> dict:
        """Detections, and the slot count of each aggregation (one after
        every pass but the last) that the coarsen kernel ran over."""
        return {"detections": len(self.done),
                "aggregations_e_cap": [p.e_cap for _, r in self.done
                                       for p in r.passes[:-1]]}

    def release(self):
        """Detect the seed's own graph, fetch what the check needs and free
        the program's graphs."""
        graph = common.make_graph(self.config, self.seed)[0]
        self.own = (common.host_slots(graph),
                    louvain(graph, self.lconfig).membership)
        del graph
        self.slots = [common.host_slots(g) for g in self.graphs]
        self.q = [ref.modularity(self.slots[i].src, self.slots[i].dst,
                                 self.slots[i].w, r.membership)
                  for i, r in self.done]
        del self.graphs

    def check(self) -> dict:
        """Each detection's gap to the reference run on its graph."""
        want = {i: ref.louvain(self.slots[i]) for i, _ in self.done}
        gaps = [common.membership_gap(r.membership, want[i], self.slots[i])
                for i, r in self.done]
        slots, got = self.own
        gaps.append(common.membership_gap(got, ref.louvain(slots), slots))
        return {"mismatch": [g[0] for g in gaps],
                "q_gap": [g[1] for g in gaps]}

    def control(self, dtype) -> dict:
        """The check's numbers with the reference computed in ``dtype`` in
        the program's place, on each graph of the suite and the seed's."""
        gaps = [common.membership_gap(ref.louvain(s, dtype), ref.louvain(s),
                                      s)
                for s in self.slots + [self.own[0]]]
        return {"mismatch": [g[0] for g in gaps],
                "q_gap": [g[1] for g in gaps]}
