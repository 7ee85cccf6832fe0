"""The device mesh constructor the repo uses, in one place.

``make_mesh(shape, names)`` is ``jax.make_mesh`` with every axis
``AxisType.Auto``: the sharding rules here are written for Auto axes.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh"]


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
