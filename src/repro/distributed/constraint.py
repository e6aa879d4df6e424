"""Best-effort activation sharding constraints.

``shard_activation(x, spec...)`` applies ``with_sharding_constraint`` using
whatever axes the ambient mesh actually has, skipping axes that don't
divide the dimension — so model code can state its *intent* (batch over
("pod","data"), vocab over "model") and still trace fine with no mesh (CPU
tests) or partial meshes (debug runs).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

AxisSpec = Union[None, str, Tuple[str, ...]]


def ambient_mesh():
    """Abstract mesh of the enclosing ``jax.set_mesh`` scope, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def shard_activation(x: jax.Array, *spec: AxisSpec) -> jax.Array:
    """Constrain ``x`` to ``spec`` where the ambient mesh allows it."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    if len(spec) != x.ndim:
        raise ValueError(f"spec rank {len(spec)} != array rank {x.ndim}")
    names = set(mesh.axis_names)
    fixed = []
    for dim, axes in zip(x.shape, spec):
        if axes is None:
            fixed.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        present = tuple(a for a in axes if a in names)
        size = 1
        for a in present:
            size *= mesh.shape[a]
        if present and size > 1 and dim % size == 0:
            fixed.append(present if len(present) > 1 else present[0])
        else:
            fixed.append(None)
    if all(f is None for f in fixed):
        return x
    return jax.lax.with_sharding_constraint(x, P(*fixed))
