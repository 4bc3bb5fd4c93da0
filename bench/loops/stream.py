"""Closed loop of one edge stream: each batch is sent when the update before
it has returned, since a stream's state depends on the update before it.

An update is one ``louvain_dynamic(graph, [batch], prev=membership)`` call,
from its start to the new membership on the host.  The batches are drawn
from one pool made with the graph: held-out edges of the same draw to
insert, and edges of the graph to delete (weight 0).  The graph starts
with only part of its draw (``held_out_share`` of the distinct edges are
still to emerge), so its communities are still forming and the warm
updates move vertices.
"""

from __future__ import annotations

import copy
import importlib
import time

import jax
import numpy as np

from bench.loops import common
from bench.reference import louvain as ref
from bench.reference import stream as ref_stream
from repro.core.delta import make_edge_batch
from repro.core.dynamic import louvain_dynamic
from repro.core.louvain import LouvainConfig, louvain


class Loop:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        gen = importlib.import_module(f"bench.gen.{config['generator']}")
        self.n, raw = gen.sizes(config)
        self.batch = round(float(traffic["batch_fraction"]) * raw)
        self.n_insert = round(float(traffic["insert_share"]) * self.batch)
        self.n_delete = self.batch - self.n_insert
        self.pool = int(traffic["pool_batches"])
        self.sent = 0                 # batches applied so far
        self.latencies = []           # seconds, window updates only
        self.memberships = []         # every update's, up to the checked
        self.window_s = None

    def setup(self):
        self.graph, self.inserts, self.deletes = common.make_graph(
            self.config, self.seed, self.pool * self.n_insert,
            self.pool * self.n_delete,
            hold_share=float(self.traffic["held_out_share"]))
        self.graph0 = self.graph
        self.lconfig = LouvainConfig()
        with jax.profiler.TraceAnnotation("louvain"):
            self.membership0 = louvain(self.graph, self.lconfig).membership
        self.membership = self.membership0
        for _ in range(int(self.traffic["warmup_batches"])):
            self._update()

    def batch_arrays(self, i: int):
        """(u, v, w) host arrays of batch ``i``: insertions, then deletions."""
        if i >= self.pool:
            raise RuntimeError(f"the stream ran out of its pool of "
                               f"{self.pool} batches")
        ins = self.inserts[i * self.n_insert:(i + 1) * self.n_insert]
        dels = self.deletes[i * self.n_delete:(i + 1) * self.n_delete]
        w = np.r_[np.ones(len(ins)), np.zeros(len(dels))].astype(np.float32)
        return np.r_[ins[:, 0], dels[:, 0]], np.r_[ins[:, 1], dels[:, 1]], w

    def _update(self) -> float:
        u, v, w = self.batch_arrays(self.sent)
        with jax.profiler.TraceAnnotation("batch_build"):
            batch = make_edge_batch(u, v, w, self.graph.n_cap,
                                    b_cap=self.batch)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("louvain_dynamic"):
            res = louvain_dynamic(
                self.graph, [batch], prev=self.membership,
                config=self.lconfig, screening=self.traffic["screening"],
                grow_capacity=self.traffic["grow_capacity"],
                apply_backend=self.traffic["apply_backend"])
        dt = time.perf_counter() - t0
        self.graph, self.membership = res.graph, res.membership
        self.sent += 1
        if len(self.memberships) < self.checked:
            self.memberships.append(self.membership)
        return dt

    @property
    def checked(self) -> int:
        """Updates the check compares, warm-up ones included."""
        return (int(self.traffic["warmup_batches"])
                + int(self.traffic["checked_batches"]))

    def window(self, seconds: float):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.latencies.append(self._update())
        self.window_s = time.perf_counter() - t0

    def traced(self):
        for _ in range(int(self.traffic["trace_batches"])):
            self.latencies.append(self._update())

    @property
    def attempted(self) -> int:
        return len(self.latencies)

    def end_to_end(self) -> dict:
        lat_ms = np.asarray(self.latencies) * 1e3
        return {
            "update_p95_ms": (float(np.percentile(lat_ms, 95)), "ms"),
            "stream_edges_per_s": (len(lat_ms) * self.batch / self.window_s,
                                   "edges/s"),
        }

    def notes(self) -> dict:
        lat_ms = np.asarray(self.latencies) * 1e3
        return {"updates": len(lat_ms),
                "update_p50_ms": float(np.median(lat_ms)),
                "update_max_ms": float(lat_ms.max()),
                "updates_over_2x_median": int(np.sum(
                    lat_ms > 2 * np.median(lat_ms))),
                "batch_edges": self.batch}

    def trace_context(self) -> dict:
        return {"batches": int(self.traffic["trace_batches"])}

    def release(self):
        """Fetch what the check needs and free the program's graphs."""
        self.slots0 = common.host_slots(self.graph0)
        e = int(self.graph.e_valid)
        self.final = (np.asarray(self.graph.src)[:e],
                      np.asarray(self.graph.indices)[:e],
                      np.asarray(self.graph.weights)[:e])
        self.indptr_ok = bool(np.array_equal(
            np.asarray(self.graph.indptr),
            np.r_[0, np.cumsum(np.bincount(self.final[0],
                                           minlength=self.n))]))
        del self.graph, self.graph0

    def check(self) -> dict:
        """The reference replays the stream from the same graph: the cold
        membership and each compared update's, then the graph after every
        batch the run applied."""
        slots = self.slots0
        want = ref.louvain(slots)
        gaps = [common.membership_gap(self.membership0, want, slots)]
        g = ref_stream.StreamGraph(slots.src, slots.dst, slots.w, self.n)
        self.moving = 0     # compared updates in which the reference moves
        for i, got in enumerate(self.memberships):
            prev, want = want, ref_stream.update(g, want,
                                                 *self.batch_arrays(i))
            self.moving += ref.mismatch(prev, want) > 0
            gaps.append(common.membership_gap(got, want, g.slots()))
        rest = [self.batch_arrays(i)
                for i in range(len(self.memberships), self.sent)]
        if rest:
            g.apply(*(np.concatenate(x) for x in zip(*rest)))
        return {"mismatch": [x[0] for x in gaps],
                "q_gap": [x[1] for x in gaps],
                "graph_diff": [self._graph_diff(g)]}

    def control(self, dtype) -> dict:
        """The check's numbers with the reference computed in ``dtype`` in
        the program's place for each timed update: every update starts from
        the float32 reference's graph and membership before it, and the
        cold detection of set-up stays in float32."""
        slots = self.slots0
        want = ref.louvain(slots)
        gaps = [common.membership_gap(want, want, slots)]
        g = ref_stream.StreamGraph(slots.src, slots.dst, slots.w, self.n)
        for i in range(len(self.memberships)):
            before = copy.deepcopy(g)
            prev, want = want, ref_stream.update(g, want,
                                                 *self.batch_arrays(i))
            got = ref_stream.update(before, prev, *self.batch_arrays(i),
                                    dtype)
            gaps.append(common.membership_gap(got, want, g.slots()))
        return {"mismatch": [x[0] for x in gaps],
                "q_gap": [x[1] for x in gaps]}

    def _graph_diff(self, g) -> int:
        """Directed slots in which the run's final graph and the
        reference's differ (a missing, extra or reweighted slot each
        count), plus the whole graph when the row offsets are wrong."""
        src, dst, w = self.final
        key = src.astype(np.int64) * self.n + dst
        if not self.indptr_ok or np.any(np.diff(key) <= 0):
            return len(key) + len(g.key)
        both, i_got, i_want = np.intersect1d(key, g.key, assume_unique=True,
                                             return_indices=True)
        reweighted = int(np.sum(w[i_got] != g.w[i_want]))
        return (len(key) - len(both)) + (len(g.key) - len(both)) + reweighted
