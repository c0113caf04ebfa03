"""Production mesh definitions.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over the real local devices (tests / CPU runs)."""
    n = len(jax.devices())
    if model_axis < 1 or n % model_axis:
        raise ValueError(
            f"model_axis={model_axis} must divide the {n} local device(s); "
            f"force more host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_replica_meshes(num_replicas: int = 1, model_axis: int = 1,
                        devices: Optional[Sequence] = None) -> List[Mesh]:
    """Carve ``num_replicas`` disjoint (data=1, model=model_axis) mesh slices
    out of the local devices — one per data-parallel serving replica
    (DESIGN.md §10). Each slice runs its own tensor-parallel engine; the
    replicas never communicate, so separate meshes (not one global mesh)
    keep every jitted program single-replica."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if num_replicas < 1 or model_axis < 1:
        raise ValueError(
            f"num_replicas={num_replicas} and model_axis={model_axis} "
            f"must both be >= 1")
    need = num_replicas * model_axis
    if need > len(devices):
        raise ValueError(
            f"{num_replicas} replica(s) x TP={model_axis} needs {need} "
            f"device(s) but only {len(devices)} are visible; force more "
            f"host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return [
        Mesh(np.asarray(devices[i * model_axis:(i + 1) * model_axis])
             .reshape(1, model_axis), ("data", "model"))
        for i in range(num_replicas)
    ]


def batch_axes(mesh) -> tuple:
    """Mesh axes a global-batch dimension shards over."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
