"""The mesh constructor shared by every mesh-building caller.

`make_mesh` pins every axis to `AxisType.Auto`. `jax.make_mesh` defaults
to Explicit axes, under which `with_sharding_constraint` refuses named
specs and array avals carry their sharding (so a runner's output state
no longer matches the aval it was traced with). The programs here are
written for the Auto (compiler-propagated) sharding model, so they say so.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """`jax.make_mesh` with every axis Auto (module docstring)."""
    axis_shapes = tuple(axis_shapes)
    return jax.make_mesh(axis_shapes, tuple(axis_names), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_shapes))
