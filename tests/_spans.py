"""Host events of a call run under the JAX profiler, for the tests of the
program's spans (``repro.core.spans``)."""

import glob
import os
import tempfile
import warnings
from typing import NamedTuple

import jax


class Event(NamedTuple):
    line: int          # host thread the event ran on
    name: str
    start: float       # ns
    end: float         # ns
    stats: dict


def traced(fn, *args, **kwargs):
    """(``fn(*args, **kwargs)``, its host events in start order)."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            out = fn(*args, **kwargs)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        profile = ProfileData.from_file(path)
    host = next(p for p in profile.planes if p.name == "/host:CPU")
    with warnings.catch_warnings():
        # Reading an event's stats warns that their type has no module.
        warnings.simplefilter("ignore", DeprecationWarning)
        events = [Event(i, ev.name, ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats))
                  for i, line in enumerate(host.lines)
                  for ev in line.events]
    return out, sorted(events, key=lambda e: (e.start, -e.end))


def inside(event, outer) -> bool:
    """Whether ``event`` lies within ``outer`` on the same thread."""
    return (event.line == outer.line and outer.start <= event.start
            and event.end <= outer.end)


def named(events, name):
    return [e for e in events if e.name == name]
