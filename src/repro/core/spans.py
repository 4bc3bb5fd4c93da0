"""Host spans and counters of the pass loops, on the profiler's clock.

Each is a ``jax.profiler.TraceAnnotation`` named ``gve.<name>``: it costs
about a microsecond when no profiler runs and, under one, lands on the host
plane of the trace beside the device's operations.  Keyword arguments come
back as the event's stats.

* ``span(name, **counts)`` wraps a stretch of host work;
* ``fetch(name, x)`` is the one way the host reads a device value: the
  ``np.asarray`` inside ``gve.sync.<name>``, so every host sync is named;
* ``mark(name, **counts)`` is a zero-length span that carries counters the
  host already holds (it never reads the device).
"""

from __future__ import annotations

import jax
import numpy as np

PREFIX = "gve."


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)


def fetch(name: str, x) -> np.ndarray:
    with span("sync." + name):
        return np.asarray(x)


def mark(name: str, **counts) -> None:
    with span(name, **counts):
        pass
