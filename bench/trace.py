"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``.  The traced window runs from the
start of the first of the benchmark's own host spans (``SPANS``) to the end
of the last.  On each TPU plane (``/device:TPU:<i>``) the line
``XLA Ops`` holds one event per operation run on the device, named by its
whole HLO instruction, and the line ``XLA Modules`` one event per program
run; an operation belongs to the program run that contains it.  From
these:

* ``busy_s``: the union of the operations' intervals inside the window,
  averaged over the TPU planes; ``window_s`` its length;
* ``modules``: device seconds and runs per program, by its jitted name
  (``jit__move_phase``, ...; the trailing run id is dropped);
* ``ops``: device seconds per operation, keyed ``<module>/<op>``, control
  flow that holds other operations (a ``while`` loop) left out;
* ``breakdown``: the ten operations that took most device time, and the
  ten host activities under which the device sat idle longest, each gap
  named by the innermost host event open at its middle on the thread that
  held the benchmark's span, prefixed by that span.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from collections import defaultdict

SPANS = ("louvain", "louvain_dynamic", "batch_build")
DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
RUN_ID = re.compile(r"\(\d+\)$")
# Ops that hold other ops: counted in busy time, left out of the op list.
CONTAINERS = ("while", "conditional", "call")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def module_name(name: str) -> str:
    return RUN_ID.sub("", name).strip()


def op_name(name: str) -> str:
    """``fusion.355`` of an HLO op event named by its whole instruction."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.duration_ns), ev


def reduce(path: str, top: int = 10) -> dict:
    """The reduction of an ``.xplane.pb`` file (or of its gzip)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return reduce_profile(
                ProfileData.from_serialized_xspace(f.read()), top)
    return reduce_profile(ProfileData.from_file(path), top)


def reduce_profile(profile, top: int = 10) -> dict:
    host_lines, devices = [], []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            host_lines = [list(_events(line)) for line in plane.lines]
        elif DEVICE_PLANE.fullmatch(plane.name):
            devices.append({line.name: list(_events(line))
                            for line in plane.lines})
    if not devices:
        raise RuntimeError("the trace holds no TPU plane")

    spans = [(n, s, s + d) for line in host_lines for n, s, d, _ in line
             if n in SPANS]
    if not spans:
        raise RuntimeError(f"the trace holds none of the spans {SPANS}")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    span_line = next(line for line in host_lines
                     if any(n in SPANS for n, *_ in line))

    modules = defaultdict(lambda: [0.0, 0])
    ops = defaultdict(float)
    busy_total = 0.0
    busy0 = None
    for dev in devices:
        runs = sorted((s, s + d, module_name(name))
                      for name, s, d, _ in dev.get(MODULES_LINE, []))
        for s, e, name in runs:
            if lo <= s < hi:
                modules[name][0] += (e - s) * 1e-9
                modules[name][1] += 1
        run_starts = [r[0] for r in runs]
        intervals = []
        for name, s, d, _ in dev.get(OPS_LINE, []):
            intervals.append((s, s + d))
            op = op_name(name)
            if lo <= s < hi and not op.startswith(CONTAINERS):
                i = bisect.bisect_right(run_starts, s) - 1
                mod = runs[i][2] if i >= 0 and s < runs[i][1] else "?"
                ops[f"{mod}/{op}"] += d * 1e-9
        busy = merge(_clip(intervals, lo, hi))
        busy_total += sum(e - s for s, e in busy)
        if busy0 is None:
            busy0 = busy

    gaps = defaultdict(float)
    label = _HostLabels(span_line)
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            gaps[label((s + e) / 2)] += (e - s) * 1e-9

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / len(devices) * 1e-9,
        "modules": {k: {"seconds": v[0], "runs": v[1]}
                    for k, v in modules.items()},
        "ops": dict(ops),
        "breakdown": {"device_ops": ranked(ops), "idle_gaps": ranked(gaps)},
    }


class _HostLabels:
    """``<benchmark span> > <innermost host event>`` open at a time."""

    def __init__(self, line):
        events = sorted((s, s + d, n) for n, s, d, _ in line)
        self.spans = [ev for ev in events if ev[2] in SPANS]
        self.others = [ev for ev in events if ev[2] not in SPANS]
        self.span_starts = [ev[0] for ev in self.spans]
        self.other_starts = [ev[0] for ev in self.others]

    @staticmethod
    def _innermost(events, starts, t, limit=4096):
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(i - limit, -1), -1):
            s, e, name = events[j]
            if e > t:
                return name
        return None

    def __call__(self, t) -> str:
        span = self._innermost(self.spans, self.span_starts, t)
        inner = self._innermost(self.others, self.other_starts, t)
        span = span or "outside spans"
        return span if inner is None else f"{span} > {inner}"
