"""The one place this package builds device meshes and calls ``shard_map``.

Every mesh axis is ``AxisType.Auto``: arrays are placed with
``NamedSharding`` and the compiler propagates shardings through ordinary
jitted code (``lax.scan`` over a time-sharded input, a scatter into a
slot-sharded table).  ``jax.make_mesh`` defaults to ``Explicit`` axes, under
which those same operations raise ``ShardingTypeError`` or refuse a sharded
scan operand, so callers build meshes here and the decode entry points
reject meshes that were not (:func:`check_auto_mesh`).

``shard_map`` bodies in this package communicate only through explicit
collectives and never rely on varying-manual-axes checking, hence
``check_vma=False`` at this single call site.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices: Optional[Sequence] = None):
    """A mesh of ``shape`` over ``devices`` (default: all of them) with
    every axis ``Auto``."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(int(n) for n in shape), axes,
        axis_types=(AxisType.Auto,) * len(axes), devices=devices,
    )


def check_auto_mesh(mesh) -> None:
    """Raise unless every axis of ``mesh`` is ``Auto`` (None passes)."""
    if mesh is None:
        return
    bad = [
        name for name, kind in zip(mesh.axis_names, mesh.axis_types)
        if kind != AxisType.Auto
    ]
    if bad:
        raise ValueError(
            f"mesh axes {bad} are not AxisType.Auto ({mesh.axis_types}); build "
            "the mesh with repro.parallel.mesh.make_mesh"
        )


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
