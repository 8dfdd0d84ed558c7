"""Production mesh construction.

Single pod : (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state.  The ``pod`` axis is
pure data parallelism across pods (cross-pod traffic = one gradient
all-reduce per step, the only collective that crosses DCI).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: newer JAX defaults to
    ``Explicit`` axes, which ``with_sharding_constraint`` refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests / elastic restarts / smoke runs)."""
    return _auto_mesh(shape, axes)


def serving_mesh(n_devices: Optional[int] = None,
                 devices=None) -> Mesh:
    """1-D ``('data',)`` mesh for the slot-sharded serving engine.

    ``n_devices=None`` takes every visible device; an explicit count
    takes the first N (the elastic-resize path passes the surviving
    device list instead).  Tests get 8 CPU "devices" from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if not 1 <= n_devices <= len(devs):
            raise ValueError(f'need 1..{len(devs)} devices, '
                             f'got {n_devices}')
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), ('data',))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a mesh: ('pod','data') when a pod axis
    exists, else ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ('pod', 'data'))


def mesh_dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def mesh_model_size(mesh: Mesh) -> int:
    return int(mesh.shape.get('model', 1))
