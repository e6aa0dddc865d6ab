"""Production mesh construction (deliverable e).

``make_production_mesh`` builds the assigned meshes:

  * single-pod:  (16, 16)      axes ("data", "model")   = 256 chips
  * multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

``make_mesh_with_devices`` builds a mesh from an explicit device order --
this is how the paper's technique lands: ``launch/placement.py`` computes a
QAP-optimal permutation of physical devices and the mesh is rebuilt with that
order, changing which physical chip backs each logical coordinate.

No jax device state is touched at import time (functions only).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def production_shape(multi_pod: bool = False) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    if multi_pod:
        return (2, 16, 16), ("pod", "data", "model")
    return (16, 16), ("data", "model")


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape, axes = production_shape(multi_pod)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_with_devices(devices: Sequence, shape: Tuple[int, ...],
                           axes: Tuple[str, ...]) -> Mesh:
    dev = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(dev, axes)


def activate_mesh(mesh: Mesh):
    """Context manager making ``mesh`` ambient (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def make_local_mesh(axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Smallest mesh over whatever devices exist (CPU demos / examples)."""
    n = jax.device_count()
    shape = (1,) * (len(axes) - 1) + (n,)
    return make_mesh_with_devices(jax.devices(), shape, axes)


def make_instance_mesh(num_devices: Optional[int] = None,
                       axis: str = "instances") -> Mesh:
    """1-D mesh for sharding a solver wave's *instance* axis
    (``core.batch_sharded``, docs/DESIGN.md §7).

    Takes the first ``num_devices`` devices (all of them by default).  On a
    CPU-only box, ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (set before jax initialises) emulates an N-device host so the sharded
    dispatch path can be exercised and tested without accelerators.
    """
    avail = jax.devices()
    n = len(avail) if num_devices is None else int(num_devices)
    if n < 1 or n > len(avail):
        raise ValueError(
            f"num_devices={num_devices} not in [1, {len(avail)}] -- on CPU, "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "jax initialises to emulate more devices")
    return make_mesh_with_devices(avail[:n], (n,), (axis,))
