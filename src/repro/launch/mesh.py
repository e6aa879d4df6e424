"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches JAX device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
import and only then calls it.

Mesh layout (TPU v5e pods of 256 chips):
  single-pod:  (16, 16)      axes ("data", "model")
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model")

"model" carries tensor/expert parallelism (high-bandwidth inner ICI ring),
"data" carries FSDP + batch parallelism, "pod" is pure data parallel
(one gradient reduction across the inter-pod links per step).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests use small ones, e.g. (2, 2)).

    Axes are ``Auto``: the models state sharding intent with
    ``with_sharding_constraint`` and leave the rest to the partitioner,
    which ``jax.make_mesh``'s default ``Explicit`` axes do not allow.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes the batch dimension shards over (pod+data when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_devices(mesh: Mesh) -> int:
    return int(np.prod(mesh.devices.shape))
