"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches jax device state — the dry-run driver must set XLA_FLAGS before
the first jax call.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.models.sharding import MeshRules


def _auto_mesh(shape, axes):
    # MeshRules.constrain uses with_sharding_constraint, which needs Auto
    # axes; jax.make_mesh builds Explicit ones unless told otherwise
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def rules_for(mesh) -> MeshRules:
    """FSDP over (pod,)data; tensor over model."""
    fsdp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return MeshRules(mesh=mesh, fsdp=fsdp, tensor="model")


def make_test_mesh(*, multi_pod: bool = False, data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    set by the test's subprocess)."""
    if multi_pod:
        return _auto_mesh((2, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
