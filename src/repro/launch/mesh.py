"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """jax.make_mesh with Auto axis types: the installed jax defaults to
    Explicit, whose sharding-in-types rules reject the gathers and scatters
    the auto-partitioned (non-shard_map) paths rely on."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_cpu_mesh(n_data: int = 1, n_model: int = 1, pod: int = 0):
    """Small mesh over the first available devices (tests, smoke runs,
    and the chip's own devices)."""
    if pod:
        return auto_mesh((pod, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))


def make_replica_meshes(n_replicas: int, tp: int = 1):
    """Disjoint (1, tp) serving meshes carved from the device list — one per
    data-parallel serving replica, so replicas never contend for a device."""
    import numpy as np
    devs = jax.devices()
    need = n_replicas * tp
    if len(devs) < need:
        raise ValueError(
            f"{n_replicas} replicas x tp={tp} needs {need} devices, "
            f"have {len(devs)}")
    from jax.sharding import Mesh
    return [Mesh(np.array(devs[i * tp:(i + 1) * tp]).reshape(1, tp),
                 ("data", "model")) for i in range(n_replicas)]


def mesh_axes(mesh):
    """(dp_axes, tp_axis) convention used throughout the framework."""
    names = mesh.axis_names
    dp = tuple(n for n in names if n in ("pod", "data"))
    return dp, "model"
