"""Mesh construction with explicit axis types.

Everything that builds a mesh — ``launch.mesh``, the distributed
sharding/fault tests, ad-hoc scripts — goes through :func:`make_mesh`, so
every mesh in the tree carries ``AxisType.Auto`` axes.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape), **kwargs)
