"""Production meshes (task spec): single-pod 16x16 ('data', 'model') and
multi-pod 2x16x16 ('pod', 'data', 'model'). Defined as a function so that
importing this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axis types: the model code places arrays
    with `with_sharding_constraint` under a ``with mesh:`` context and
    lets GSPMD propagate the rest, which Explicit axes (the default of
    `jax.make_mesh`) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (device count permitting)."""
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
