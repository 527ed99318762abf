"""Production mesh builders.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    GSPMD; the code annotates with PartitionSpecs, not explicit types) over
    `devices` (default: all devices, first ``prod(shape)`` of them)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 4, model: int = 2, pods: int = 1,
                   devices=None):
    """Small mesh over the first ``pods*data*model`` of `devices` (default:
    ``jax.devices()``), so a 1x1 mesh runs on a host with more chips."""
    if devices is None:
        devices = jax.devices()[:pods * data * model]
    if pods > 1:
        return make_mesh((pods, data, model), ("pod", "data", "model"),
                         devices)
    return make_mesh((data, model), ("data", "model"), devices)


def mesh_dims(mesh) -> dict:
    names = mesh.axis_names
    return {
        "pods": mesh.shape["pod"] if "pod" in names else 1,
        "data": mesh.shape["data"],
        "model": mesh.shape["model"],
    }
