"""Mesh and shard_map construction in one spelling.

Everything that builds a mesh or wraps a function in shard_map goes through
this module, so every mesh gets explicit ``Auto`` axis types and every
shard_map the same ``check_vma`` default.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(
        shape, axis_names, axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with ``check_vma`` off unless asked for."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


__all__ = ["make_mesh", "shard_map"]
