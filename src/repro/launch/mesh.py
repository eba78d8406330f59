"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).

Every axis is ``AxisType.Auto``. ``jax.make_mesh`` defaults to Explicit
axes, under which a contraction over a sharded dimension (the CG ``dot``
of two row-sharded vectors) is a type error instead of an inserted
reduction; the distributed solvers rely on the compiler inserting it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Auto-axis mesh of ``shape`` over ``axes`` (e.g. ``(8,), ('rows',)``),
    on ``devices`` (default: all of ``jax.devices()``)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a production mesh ('pod' included)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def tp_axis(mesh):
    return "model" if "model" in mesh.axis_names else None


def flat_axes(mesh) -> tuple:
    """All axes flattened — used by the HPCG row partition (512-way)."""
    return tuple(mesh.axis_names)
