"""Pieces every plain reference shares: float32 arithmetic at the
highest matmul precision, and the int8 control that stands in for a
lower precision than the configuration states.

Nothing here imports the program. A ``Numerics`` decides how a matrix
product with a weight is computed; everything else is float32.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Numerics:
    """``int8``: the control, one step below the bfloat16 the
    configurations state. Every product with a weight, and attention's
    two products, round both operands to symmetric int8 along their
    contracted dims (one scale per row or column) and accumulate
    exactly; so do the weight products of the backward pass, cotangents
    included, and the SSM recurrence's products (``round``)."""
    int8: bool = False

    def linear(self, x: jnp.ndarray, w: jnp.ndarray, n_in: int = 1) -> jnp.ndarray:
        """Contract the last ``n_in`` dims of ``x`` with the first
        ``n_in`` dims of ``w``."""
        k = math.prod(w.shape[:n_in])
        out_shape = x.shape[:x.ndim - n_in] + w.shape[n_in:]
        x2 = x.reshape(-1, k).astype(F32)
        w2 = w.reshape(k, -1).astype(F32)
        dot = _int8_dot if self.int8 else _f32_dot
        return dot(x2, w2).reshape(out_shape)

    def einsum(self, spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """A product of two activations (attention's scores and mix);
        under ``int8`` both are rounded along their contracted dims."""
        if self.int8:
            (sa, sb), out = spec.split("->")[0].split(","), spec.split("->")[1]
            summed = set(sa) & set(sb) - set(out)
            a = _int8_round(a, tuple(i for i, c in enumerate(sa) if c in summed))
            b = _int8_round(b, tuple(i for i, c in enumerate(sb) if c in summed))
        return einsum(spec, a, b)

    def round(self, a: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
        """An operand of a product outside ``linear`` and ``einsum``
        (the SSM recurrence's): int8-rounded along ``axis`` under
        ``int8``, else unchanged."""
        return _int8_round(a, axis) if self.int8 else a


def _int8_round(a: jnp.ndarray, axis) -> jnp.ndarray:
    """Symmetric int8 rounding with one scale per slice along ``axis``;
    its gradient passes straight through."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(a / scale), -127, 127) * scale
    return a + jax.lax.stop_gradient(q - a)


def _f32_dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


@jax.custom_vjp
def _int8_dot(x, w):
    return _f32_dot(_int8_round(x, 1), _int8_round(w, 0))


def _int8_dot_fwd(x, w):
    return _int8_dot(x, w), (x, w)


def _int8_dot_bwd(res, g):
    x, w = res
    dx = _f32_dot(_int8_round(g, 1), _int8_round(w.T, 0))
    dw = _f32_dot(_int8_round(x.T, 1), _int8_round(g, 0))
    return dx, dw


_int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)


def einsum(spec: str, *args) -> jnp.ndarray:
    return jnp.einsum(spec, *(a.astype(F32) for a in args), precision=HIGHEST)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm with the ``1 + scale`` parametrisation the zoo stores."""
    x = x.astype(F32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(F32))


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding on (..., S, heads, hd), halves rotated as pairs
    (i, i + hd/2), as in GPT-NeoX and Llama."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = positions.astype(F32)[:, None] * inv[None, :]          # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def padded(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def f32_leaves(tree):
    return jax.tree.map(lambda t: t.astype(F32), tree)
