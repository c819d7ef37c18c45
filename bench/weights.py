"""Seeded weights, made on the device in one jitted call.

A weight spec maps a leaf's path (``"stack/u0/mixer/wq"``) to its shape,
its dtype as served, and how its values are drawn. The same spec and
seed give the same values to the program under test and to the plain
reference, which makes them itself and takes nothing from the program.
Each leaf's draws come from a key folded from the seed and a hash of its
path, so a leaf's values do not depend on which other leaves exist.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Spec = Dict[str, Tuple[Tuple[int, ...], str, tuple]]


def key_data(seed: int) -> np.ndarray:
    """Two 32-bit words of a seed of up to 64 bits (``jax.random.key``
    keeps only the low 32 without 64-bit mode)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _draw(key, shape, init) -> jnp.ndarray:
    kind = init[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * init[1]
    if kind == "normal_around":             # mean + std * N(0, 1)
        return init[1] + jax.random.normal(key, shape, jnp.float32) * init[2]
    if kind == "log_uniform":               # log(U(lo, hi))
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          init[1], init[2]))
    if kind == "softplus_inverse_log_uniform":   # dt bias of Mamba-2
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(init[1]), np.log(init[2])))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init {init!r}")


def _make(kd, spec: Spec) -> Dict[str, jnp.ndarray]:
    base = jax.random.wrap_key_data(kd, impl="threefry2x32")
    out = {}
    for path, (shape, dtype, init) in spec.items():
        key = jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        out[path] = _draw(key, shape, init).astype(jnp.dtype(dtype))
    return out


def make(spec: Spec, seed: int) -> Dict[str, jax.Array]:
    """All leaves of ``spec`` from ``seed``, in their served dtypes."""
    return jax.jit(lambda kd: _make(kd, spec))(jnp.asarray(key_data(seed)))


def tree_paths(tree) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """path -> (shape, dtype name) of a pytree of arrays or shape structs."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {path_str(p): (tuple(x.shape), jnp.dtype(x.dtype).name)
            for p, x in flat}


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def to_program_tree(flat: Dict[str, jax.Array], like):
    """Arrange ``flat`` (path -> array) as the pytree ``like``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[path_str(p)] for p, _ in leaves])


def check_layout(spec: Spec, like) -> None:
    """Raise unless ``spec`` names exactly the leaves of ``like`` with
    their shapes and dtypes."""
    have = tree_paths(like)
    want = {p: (tuple(s), jnp.dtype(d).name) for p, (s, d, _) in spec.items()}
    if have != want:
        diff = sorted(set(have.items()) ^ set(want.items()))
        raise ValueError(f"weight spec does not match the program's tree: {diff[:6]}")
