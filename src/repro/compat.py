"""Mesh construction shared by every sharded path.

Every mesh in this repo is built with Auto axis semantics; ``make_mesh``
spells that once.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` if given,
    else all devices)."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), devices=devices)
