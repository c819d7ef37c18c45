"""RecurrentGemma / Griffin recurrent block (arXiv:2402.19427).

Temporal mixing = Conv1D(width 4) → RG-LRU, gated by a GeLU branch:

    r_t = σ(W_a x_t + b_a)            (recurrence gate)
    i_t = σ(W_x x_t + b_x)            (input gate)
    a_t = exp(−c · softplus(Λ) · r_t)
    h_t = a_t h_{t−1} + sqrt(1 − a_t²) · (i_t ⊙ x_t)

The sequence form runs via ``jax.lax.associative_scan``; decode is the
O(1) recurrence carrying {lru, conv} state.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import dense_init, split_keys
from .config import ArchConfig

_C = 8.0


def init_rglru(key, cfg: ArchConfig, dtype) -> Dict:
    d, w = cfg.d_model, cfg.lru_dim
    ks = split_keys(key, 6)
    return {
        "w_in": dense_init(ks[0], (d, w), dtype),          # recurrent branch
        "w_gate_branch": dense_init(ks[1], (d, w), dtype), # GeLU branch
        "conv_w": dense_init(ks[2], (cfg.conv_width, w), dtype, fan_in=cfg.conv_width),
        "wa": dense_init(ks[3], (w, w), dtype),
        "wx": dense_init(ks[4], (w, w), dtype),
        "ba": jnp.zeros((w,), jnp.float32),
        "bx": jnp.zeros((w,), jnp.float32),
        # Λ init so that a ≈ 0.9..0.999 at r = 1 (per the paper)
        "lam": jnp.log(jnp.expm1(-jnp.log(
            jnp.linspace(0.9, 0.999, w, dtype=jnp.float32)) / _C)),
        "w_out": dense_init(ks[5], (w, d), dtype, fan_in=w),
    }


def _conv_causal(x: jnp.ndarray, w: jnp.ndarray,
                 tail: jnp.ndarray | None) -> jnp.ndarray:
    K = w.shape[0]
    pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype) if tail is None else tail
    xp = jnp.concatenate([pad, x], axis=1)
    return sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(K))


def _rglru_scan(xg: jnp.ndarray, a_log: jnp.ndarray,
                h0: jnp.ndarray | None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RG-LRU over seq axis 1: the gated input b_t = sqrt(1 − a_t²)·xg_t
    into the linear recurrence. a_log: log a_t (f32)."""
    b = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * a_log), 1e-12)) * xg
    return linear_scan(a_log, b, h0)


def linear_scan(a_log: jnp.ndarray, b: jnp.ndarray,
                h0: jnp.ndarray | None = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h_t = a_t h_{t−1} + b_t over seq axis 1, a_t = exp(a_log_t).
    Returns (h, h_last)."""
    a = jnp.exp(a_log)

    def comb(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    if h0 is not None:
        a = jnp.concatenate([jnp.ones_like(a[:, :1]), a], axis=1)
        b = jnp.concatenate([h0[:, None].astype(b.dtype), b], axis=1)
        _, h = jax.lax.associative_scan(comb, (a, b), axis=1)
        h = h[:, 1:]
    else:
        _, h = jax.lax.associative_scan(comb, (a, b), axis=1)
    return h, h[:, -1]


def apply_rglru(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
                state: Dict | None = None) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, S, D) → (out, new_state {lru (B,W) f32, conv (B,K−1,W)})."""
    B, S, _ = x.shape
    with jax.named_scope("in_proj"):
        gate = jax.nn.gelu(x @ p["w_gate_branch"], approximate=True)
        proj = x @ p["w_in"]
    with jax.named_scope("conv"):
        tail = state["conv"] if state is not None else None
        u = _conv_causal(proj, p["conv_w"], tail)
        K = cfg.conv_width
        hist = proj if tail is None else jnp.concatenate([tail, proj], axis=1)
        if hist.shape[1] < K - 1:
            padz = jnp.zeros((B, K - 1 - hist.shape[1], hist.shape[2]), hist.dtype)
            hist = jnp.concatenate([padz, hist], axis=1)
        new_conv = hist[:, -(K - 1):]

    with jax.named_scope("scan"):
        r = jax.nn.sigmoid((u @ p["wa"]).astype(jnp.float32) + p["ba"])
        i = jax.nn.sigmoid((u @ p["wx"]).astype(jnp.float32) + p["bx"])
        a_log = -_C * jax.nn.softplus(p["lam"]) * r                  # (B,S,W) f32
        xg = i * u.astype(jnp.float32)
        h0 = state["lru"] if state is not None else None
        if h0 is None and S % min(256, S) == 0:
            from ..kernels import ops as _kops       # lazy: ref.py imports us
            if _kops.use_pallas():
                b = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * a_log), 1e-12)) * xg
                h, h_last = _kops.rglru_scan(a_log, b, block_t=min(256, S))
            else:
                h, h_last = _rglru_scan(xg, a_log, h0)
        else:
            h, h_last = _rglru_scan(xg, a_log, h0)
    with jax.named_scope("out"):
        y = (h.astype(x.dtype) * gate) @ p["w_out"]
    return y, {"lru": h_last, "conv": new_conv}


def rglru_state_shape(cfg: ArchConfig, batch: int, dtype):
    w = cfg.lru_dim
    return {"lru": ((batch, w), jnp.float32),
            "conv": ((batch, cfg.conv_width - 1, w), dtype)}
