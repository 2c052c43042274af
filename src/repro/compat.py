"""The repo's defaults for JAX's mesh and shard_map constructors.

Every mesh here has Auto axes (sharding propagates through the compiler
outside shard_map), while ``jax.make_mesh`` defaults to Explicit axes; and
every shard_map here runs with ``check_vma=False``. Build meshes and
shard_maps through these two wrappers so the defaults stay in one place.
"""
from __future__ import annotations

from typing import Optional, Sequence, Set

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Set[str]] = None,
              check_vma: bool = False):
    """``jax.shard_map``; ``axis_names`` are the mesh axes under manual
    control (None = all of them)."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))
