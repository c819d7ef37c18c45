"""Plain float32 reference of Mamba-2 (arXiv:2405.21060), written as the
sequential state-space recurrence rather than the chunked SSD dual form
that the program's kernel computes.

Per layer: RMSNorm -> in_proj to (z, x, B, C, dt) -> depthwise causal
conv over (x, B, C) then SiLU -> per head h and step t
``s_t = exp(dt_t A_h) s_{t-1} + dt_t x_t B_t^T``, ``y_t = s_t C_t + D_h x_t``
-> gated RMSNorm ``norm(y * silu(z))`` -> out_proj -> residual. Tied
embedding and head.

Departures from the published model, all shared with the program: the
conv has no bias, RMSNorm scales are stored as ``1 + scale``, and the
vocabulary is padded to ``vocab_pad`` (the padded rows are sliced off).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, HIGHEST, Numerics, f32_leaves, padded, rms_norm


def _dims(a: dict):
    d = a["d_model"]
    din = a.get("ssm_expand", 2) * d
    p = a.get("ssm_headdim", 64)
    return d, din, din // p, p, a.get("ssm_ngroups", 1), a["ssm_state"], a.get("ssm_conv", 4)


def param_spec(a: dict) -> dict:
    if not a.get("ssm") or not a.get("tie_embeddings", False):
        raise NotImplementedError("ssm reference: tied Mamba-2 only")
    d, din, h, p, g, n, k = _dims(a)
    L = a["n_layers"]
    vp = padded(a["vocab_size"], a.get("vocab_pad", 256))
    dt = a.get("dtype", "bfloat16")
    m = "stack/u0/mixer/"
    return {
        "embed": ((vp, d), dt, ("normal", d ** -0.5)),
        "ln_f": ((d,), "float32", ("normal", 0.1)),
        "stack/u0/ln1": ((L, d), "float32", ("normal", 0.1)),
        m + "in_proj": ((L, d, 2 * din + 2 * g * n + h), dt, ("normal", d ** -0.5)),
        m + "conv_w": ((L, k, din + 2 * g * n), dt, ("normal", k ** -0.5)),
        m + "a_log": ((L, h), "float32", ("log_uniform", 1.0, 16.0)),
        m + "dt_bias": ((L, h), "float32", ("softplus_inverse_log_uniform", 1e-3, 1e-1)),
        m + "d_skip": ((L, h), "float32", ("normal_around", 1.0, 0.1)),
        m + "norm_scale": ((L, din), "float32", ("normal", 0.1)),
        m + "out_proj": ((L, din, d), dt, ("normal", din ** -0.5)),
    }


def _mixer(lw, x, a: dict, num: Numerics):
    d, din, h, p, g, n, k = _dims(a)
    R, S, _ = x.shape
    eps = a.get("norm_eps", 1e-6)
    proj = num.linear(x, lw["mixer/in_proj"])
    z, xbc, dt = jnp.split(proj, [din, 2 * din + 2 * g * n], axis=-1)
    w = lw["mixer/conv_w"]                                       # (k, C)
    pad = jnp.concatenate([jnp.zeros((R, k - 1, xbc.shape[-1]), F32), xbc], axis=1)
    conv = sum(pad[:, i:i + S] * w[i] for i in range(k))
    xs, bs, cs = jnp.split(jax.nn.silu(conv), [din, din + g * n], axis=-1)
    dt = jax.nn.softplus(dt + lw["mixer/dt_bias"])               # (R, S, h)
    a_h = -jnp.exp(lw["mixer/a_log"])                            # (h,)
    xs = xs.reshape(R, S, h, p)
    rep = h // g
    bs = jnp.repeat(bs.reshape(R, S, g, n), rep, axis=2)         # (R, S, h, n)
    cs = jnp.repeat(cs.reshape(R, S, g, n), rep, axis=2)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp                                # (R,h,p) (R,h,n) (R,h,n) (R,h)
        state = (jnp.exp(dt_t * a_h)[..., None, None] * state
                 + num.round(dt_t[..., None] * x_t)[..., None] * num.round(b_t)[:, :, None, :])
        y = jnp.einsum("rhpn,rhn->rhp", num.round(state), num.round(c_t), precision=HIGHEST)
        return state, y

    seq = tuple(t.swapaxes(0, 1) for t in (xs, bs, cs, dt))
    _, ys = jax.lax.scan(step, jnp.zeros((R, h, p, n), F32), seq)
    y = ys.swapaxes(0, 1) + xs * lw["mixer/d_skip"][:, None]
    y = rms_norm(y.reshape(R, S, din) * jax.nn.silu(z), lw["mixer/norm_scale"], eps)
    return num.linear(y, lw["mixer/out_proj"])


def hidden(w: dict, tokens: jnp.ndarray, a: dict, num: Numerics,
           remat: bool = False) -> jnp.ndarray:
    eps = a.get("norm_eps", 1e-6)
    u = "stack/u0/"
    layers = {k[len(u):]: v for k, v in w.items() if k.startswith(u)}

    def body(x, lw):
        lw = f32_leaves(lw)
        return x + _mixer(lw, rms_norm(x, lw["ln1"], eps), a, num), None

    if remat:
        body = jax.checkpoint(body)
    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(body, x, layers)
    return rms_norm(x, w["ln_f"], eps)


def head(w: dict, x: jnp.ndarray, a: dict, num: Numerics) -> jnp.ndarray:
    return num.linear(x, w["embed"].T)[..., :a["vocab_size"]]


def logits(w: dict, tokens: jnp.ndarray, a: dict, num: Numerics,
           first: int = 0) -> jnp.ndarray:
    return head(w, hidden(w, tokens, a, num)[:, first:], a, num)
