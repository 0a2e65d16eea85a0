"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

`jax.make_mesh` defaults every axis to `AxisType.Explicit`; the model zoo
shards with auto axes (sharding constraints + GSPMD propagation), so every
mesh here is built through `make_mesh` below, which asks for `Auto`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.models.transformer import NetCtx


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """`jax.make_mesh` with every axis auto-sharded."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_shapes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod slice, 256 chips) or 2×16×16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh with the production axis names (tests/smokes)."""
    return make_mesh((1, 1), ("data", "model"))


def make_ctx(mesh) -> NetCtx:
    batch_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return NetCtx(mesh=mesh, batch_axes=batch_axes, model_axis="model")
