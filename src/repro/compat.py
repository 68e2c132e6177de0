"""Thin wrappers over the small jax API surface the repo's meshes use.

The repo targets the installed jax (0.9): ``jax.shard_map``,
``jax.sharding.AxisType``, ``jax.make_mesh(..., axis_types=...)``.
Everything in-repo goes through these helpers so the spelling lives in
one place:

  * ``make_mesh(shape, names)``       — Auto-typed mesh
  * ``shard_map(f, mesh=..., ...)``   — the repo's ``check_rep`` spelling
    of ``check_vma``
  * ``abstract_mesh(shape, names)``   — AbstractMesh from sizes and names
  * ``axis_size`` / ``pcast_varying`` — named-axis size, varying cast
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax

__all__ = ["make_mesh", "shard_map", "abstract_mesh", "axis_size", "pcast_varying"]


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Sequence[Any] | None = None,
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes),
        tuple(axis_names),
        devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
    )


def shard_map(f: Callable, *, mesh, in_specs, out_specs, check_rep: bool = True):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_rep,
    )


def axis_size(axis_name) -> int:
    return jax.lax.axis_size(axis_name)


def pcast_varying(x, axes):
    """Mark ``x`` device-varying over ``axes`` (vma typing)."""
    return jax.lax.pcast(x, tuple(axes), to="varying")


def abstract_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str]
) -> jax.sharding.AbstractMesh:
    """AbstractMesh from per-axis sizes and names."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names))
