"""Production and host meshes.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
Activate a mesh with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1×N ('data','model') mesh —
    used by CPU smoke tests and the examples."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))
