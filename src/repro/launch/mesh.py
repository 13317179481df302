"""Production meshes.  Defined as functions so importing this module never
touches jax device state (required by the dry-run contract).

All mesh construction (src, tests, examples) goes through ``make_mesh``,
which marks every axis ``AxisType.Auto`` (the sharding-propagation mode
the policies in ``repro.distributed`` are written for).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_desc(mesh) -> str:
    return "x".join(f"{k}{v}" for k, v in mesh.shape.items())
