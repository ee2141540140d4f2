"""Mesh construction for the whole repo.

``jax.make_mesh`` gives Explicit axes by default; the plans here are
applied as ``with_sharding_constraint`` hints that GSPMD propagates, which
needs Auto axes.  Every mesh is built through this helper so the axis type
is decided in one place; enter one with ``jax.set_mesh``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_compat_mesh(axis_shapes: Sequence[int],
                     axis_names: Sequence[str],
                     *, devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))
