"""Shared building blocks: norms, rotary embeddings, initializers."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0
               ) -> jnp.ndarray:
    """Rotary embedding. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- stacked caches ----------------------------------------------------------------
# A serving cache holds one (L, B, ...) buffer per leaf for the L layers of
# a layer scan. The scan carries it; each layer reads its slice and writes
# only what is new, so that under jit the donated buffer is updated in place.
def layer_of(stack, i):
    """Layer ``i``'s slice of a stacked (L, B, ...) cache tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


def put_layer(stack, i, new, pos=0):
    """Write each leaf of ``new`` (one layer's (B, n, ...) entries) into
    layer ``i`` of the stacked (L, B, ...) tree, from position ``pos`` of
    its third axis (T of a K/V cache)."""
    def put(a, n):
        start = (i, 0, pos) + (0,) * (a.ndim - 3)
        return jax.lax.dynamic_update_slice(a, n[None].astype(a.dtype), start)
    return jax.tree.map(put, stack, new)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


# -- initializers ----------------------------------------------------------------
def dense_init(key, shape: Tuple[int, ...], dtype, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[0]
    std = fan ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def count_params(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))
