"""Device meshes.

Never touches jax device state at import time — meshes are built by
functions.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (sharding constraints and
    ``shard_map`` both accept them; jax's default Explicit axes do not)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
