"""The program's own host spans in a profiler trace, and the per-layer
numbers they give.

The program's host loops name their work with spans (``repro.core.spans``):
every name starts with ``gve.``, every device-to-host read is a ``gve.sync.*``
span, and each pass leaves a ``gve.pass.counts`` counter (its sweeps, the
slots one sweep scans, vertices, communities and the seed frontier).  They
share the trace's clock with the device's operations.  Over the window that
``bench/trace.py`` traces (its benchmark spans, ``trace.SPANS``),
``reduce`` returns:

* ``spans``: per ``gve.*`` name that starts in the window, the count, the
  host seconds, and ``idle_s``, the seconds inside the spans in which
  device 0 ran nothing;
* ``counters``: each ``gve.*.counts`` event in the window, its arguments by
  name, in start order;
* ``idle_by_span``: the ten ``<benchmark span> > <gve.* span>`` under which
  device 0 sat idle longest, each gap named by the innermost program span
  open at its middle, as ``trace.py`` names its ``idle_gaps``.

``per_layer`` turns that into the host loops' and phases' numbers listed in
PERF.md section 3.  Nothing in ``bench/run.py`` calls this module yet: a
trace without program spans gives empty ``spans`` and ``counters`` and no
per-layer number.
"""

from __future__ import annotations

import bisect
import gzip
import warnings
from collections import defaultdict

from bench import trace

PREFIX = "gve."
SYNC = "gve.sync."
COUNTERS = ".counts"
PASS_COUNTS = "gve.pass.counts"
UPDATE = "gve.louvain_dynamic"
MOVE_MODULE = "jit__move_phase"


def load(path: str):
    """``ProfileData`` of an ``.xplane.pb`` file or of its gzip."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce(path: str, top: int = 10) -> dict:
    return reduce_profile(load(path), top)


def reduce_profile(profile, top: int = 10) -> dict:
    host_lines, ops = [], None
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            host_lines = [list(trace._events(line)) for line in plane.lines]
        elif ops is None and trace.DEVICE_PLANE.fullmatch(plane.name):
            ops = [(s, s + d) for line in plane.lines
                   if line.name == trace.OPS_LINE
                   for _, s, d, _ in trace._events(line)]
    if ops is None:
        raise RuntimeError("the trace holds no TPU plane")
    spans = [(s, s + d) for line in host_lines for n, s, d, _ in line
             if n in trace.SPANS]
    if not spans:
        raise RuntimeError(f"the trace holds none of the spans {trace.SPANS}")
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans)
    span_line = next(line for line in host_lines
                     if any(n in trace.SPANS for n, *_ in line))
    busy = trace.merge(trace._clip(ops, lo, hi))
    gaps = _idle_by_span(span_line, busy, lo, hi)
    return dict(_spans_and_counters(host_lines, busy, lo, hi),
                idle_by_span=[[k, v] for k, v in sorted(
                    gaps.items(), key=lambda kv: -kv[1])[:top]])


def _idle_by_span(span_line, busy, lo, hi):
    gaps = defaultdict(float)
    label = trace._HostLabels([ev for ev in span_line if ev[0] in trace.SPANS
                               or ev[0].startswith(PREFIX)])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            gaps[label((s + e) / 2)] += (e - s) * 1e-9
    return gaps


def _spans_and_counters(host_lines, busy, lo, hi) -> dict:
    starts = [s for s, _ in busy]
    before = [0.0]                  # busy ns before each busy interval
    for s, e in busy:
        before.append(before[-1] + e - s)

    def busy_until(t):
        i = bisect.bisect_right(starts, t) - 1
        return 0.0 if i < 0 else before[i] + min(t, busy[i][1]) - busy[i][0]

    spans = defaultdict(lambda: {"count": 0, "seconds": 0.0, "idle_s": 0.0})
    counters = []
    for line in host_lines:
        for name, s, d, ev in line:
            if not name.startswith(PREFIX) or not lo <= s < hi:
                continue
            if name.endswith(COUNTERS):
                counters.append((s, dict(_stats(ev), name=name)))
                continue
            a, b = s, min(s + d, hi)
            entry = spans[name]
            entry["count"] += 1
            entry["seconds"] += d * 1e-9
            entry["idle_s"] += ((b - a) - (busy_until(b) - busy_until(a))
                                ) * 1e-9
    counters.sort(key=lambda c: c[0])
    return {"spans": dict(spans), "counters": [c for _, c in counters]}


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        # Reading an event's stats warns that their type has no module.
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def slot_sweeps(program: dict) -> int:
    """Edge slots the move phase scanned: sweeps times the slots one sweep
    scans, summed over the passes."""
    return sum(c["sweeps"] * c["slots"] for c in program["counters"]
               if c["name"] == PASS_COUNTS)


def per_layer(program: dict, summary: dict, ctx: dict) -> dict:
    """The per-layer numbers that the program's spans give, by metric name:
    ``ctx`` holds the traced ``batches`` (stream) or ``detections``
    (static), ``summary`` is ``trace.reduce``'s of the same trace.  A number
    whose spans or counters are missing is left out."""
    out = {}
    spans, counters = program["spans"], program["counters"]
    batches, runs = ctx.get("batches", 0), ctx.get("detections", 0)
    if batches and spans:
        out["host_syncs_per_batch"] = sum(
            v["count"] for k, v in spans.items()
            if k.startswith(SYNC)) / batches
    if batches and UPDATE in spans:
        out["program_idle_ms_per_batch"] = (
            1e3 * spans[UPDATE]["idle_s"] / batches)
    first = [c for c in counters if c["name"] == PASS_COUNTS
             and c["pass"] == 0]
    if batches and first:
        out["frontier_pct"] = 100.0 * sum(c["frontier"] for c in first) / sum(
            c["n_vertices"] for c in first)
    swept = slot_sweeps(program)
    if runs and swept:
        out["slot_sweeps_per_run"] = swept / runs
        move = summary["modules"].get(MOVE_MODULE)
        if move is not None:
            out["move_ns_per_slot_sweep"] = 1e9 * move["seconds"] / swept
    return out
