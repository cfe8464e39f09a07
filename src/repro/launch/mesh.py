"""Production mesh factory.

Single pod : (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

Defined as a FUNCTION so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
*before* any jax import (see dryrun.py), smoke tests see 1 device.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "dp_axes_of", "smoke_mesh"]


def _auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the model code places activations
    with `with_sharding_constraint` and leaves the rest to GSPMD, which
    Explicit axes (make_mesh's default) do not allow."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple:
    """The activation-batch (data-parallel) axes of a mesh."""
    return tuple(a for a in mesh.axis_names if a != "model")


def smoke_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the test host has."""
    return _auto_mesh((data, model), ("data", "model"))
