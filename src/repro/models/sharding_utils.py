"""Sharding helpers usable both under a mesh (pjit) and on bare CPU."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P


def abstract_mesh(axis_sizes, axis_names):
    """A device-free mesh of the given shape (for sharding-rule checks)."""
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def mesh_axes() -> tuple:
    """Axis names of the ambient mesh ('' tuple when unsharded)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return ()
    if mesh is None or mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def maybe_shard(x: jnp.ndarray, spec: Optional[P]) -> jnp.ndarray:
    """Apply a sharding constraint when a mesh is active; no-op otherwise.

    Axis names in ``spec`` that the ambient mesh lacks are dropped, so the
    same model code runs in smoke tests (1 CPU device), the single-pod
    mesh ('data','model') and the multi-pod mesh ('pod','data','model').
    """
    if spec is None:
        return x
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return x
    if mesh is None or mesh.empty:
        return x
    axes = tuple(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.shape.values())) \
        if hasattr(mesh.shape, "values") else dict(mesh.shape)
    cleaned = []
    for i, entry in enumerate(spec):
        if entry is None:
            cleaned.append(None)
            continue
        names = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
        kept = tuple(a for a in names if a in axes)
        total = 1
        for a in kept:
            total *= sizes[a]
        # drop constraints that do not divide the dim (batch=1 long-context)
        if not kept or (i < x.ndim and x.shape[i] % total != 0):
            cleaned.append(None)
        else:
            cleaned.append(kept if len(kept) > 1 else kept[0])
    return jax.lax.with_sharding_constraint(x, P(*cleaned))


# canonical logical specs used across the model zoo ----------------------------
BATCH = ("pod", "data")     # batch dim shards over pod+data

def batch_spec(*rest) -> P:
    return P(BATCH, *rest)
