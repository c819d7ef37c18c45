"""Plain float32 reference of a Granite 4.0-H hybrid stack
(ibm-granite/granite-4.0-h-micro, ``model_type`` granitemoehybrid):
Mamba-2 layers and attention layers in one stack, every layer with a
SwiGLU MLP.

The token embedding is multiplied by ``embedding_multiplier``. Then,
per layer of the period ``block_pattern`` (``"ssm"`` a Mamba-2 layer,
anything else an attention layer), repeated over the depth: RMSNorm ->
either the sequential Mamba-2 recurrence (in_proj to (z, x, B, C, dt),
depthwise causal conv over (x, B, C) with its bias, then SiLU;
``s_t = exp(dt_t A_h) s_{t-1} + dt_t x_t B_t^T``, ``y_t = s_t C_t + D_h x_t``;
gated RMSNorm ``norm(y * silu(z))``; out_proj) or causal grouped-query
attention with no positional encoding (NoPE, ``rope_theta`` None) and
softmax scale ``attn_scale`` -> times ``residual_multiplier`` into the
residual; RMSNorm -> SwiGLU MLP -> times ``residual_multiplier`` into
the residual. A final RMSNorm, the tied head, and the logits divided by
``logits_scaling``.

Each of these follows the configuration, so that a test can take any
one away: NoPE gives way to RoPE when ``rope_theta`` is set, the scale
to 1/sqrt(head dim) when ``attn_scale`` is None, the conv bias goes with
``ssm_conv_bias``, an SSD layer's MLP with ``d_ff`` 0, and each
multiplier at 1.

Departures from the published model, all shared with the program:
RMSNorm scales are stored as ``1 + scale``, and the vocabulary is padded
to ``vocab_pad`` (the padded rows are sliced off). Attention is computed
over the whole sequence with no cache, in blocks of query rows
(``dense.attention``) so that the scores fit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dense
from .common import F32, HIGHEST, Numerics, f32_leaves, padded, rms_norm, rope
from .ssm import _dims as ssd_dims


def _supported(a: dict) -> tuple:
    pattern = tuple(a.get("block_pattern") or ())
    off = ("qk_norm", "n_experts", "mla", "prefix_len", "encdec", "vision_stub", "ssm",
           "window")
    bad = [k for k in off if a.get(k)]
    if (bad or not pattern or a["n_layers"] % len(pattern)
            or not a.get("tie_embeddings", False) or not a.get("gated_mlp", True)
            or a.get("act", "silu") != "silu" or not set(pattern) <= {"ssm", "dense"}):
        raise NotImplementedError(f"hybrid reference: {bad or pattern}")
    return pattern


def param_spec(a: dict) -> dict:
    pattern = _supported(a)
    d, din, nh, p, g, n, k = ssd_dims(a)
    h, kv = a["n_heads"], a["n_kv_heads"]
    hd = a.get("head_dim") or d // h
    f = a["d_ff"]
    U = a["n_layers"] // len(pattern)
    vp = padded(a["vocab_size"], a.get("vocab_pad", 256))
    dt = a.get("dtype", "bfloat16")
    norm = ("normal", 0.1)
    conv_dim = din + 2 * g * n
    spec = {
        # times the multiplier, the embedding enters the residual at RMS
        # d ** -0.5, as mamba2-780m's does. At the published
        # initializer_range (0.1) the token's own embedding outweighs the
        # 80 sublayers in the last hidden state, and the tied head then
        # repeats the input token whatever the layers compute, which no
        # comparison of served tokens can tell from a broken program
        "embed": ((vp, d), dt, ("normal", d ** -0.5 / a.get("embedding_multiplier", 1.0))),
        "ln_f": ((d,), "float32", norm),
    }
    for i, kind in enumerate(pattern):
        u = f"stack/u{i}/"
        spec[u + "ln1"] = ((U, d), "float32", norm)
        m = u + "mixer/"
        if kind == "ssm":
            spec.update({
                m + "in_proj": ((U, d, 2 * din + 2 * g * n + nh), dt, ("normal", d ** -0.5)),
                m + "conv_w": ((U, k, conv_dim), dt, ("normal", k ** -0.5)),
                m + "a_log": ((U, nh), "float32", ("log_uniform", 1.0, 16.0)),
                m + "dt_bias": ((U, nh), "float32",
                                ("softplus_inverse_log_uniform", 1e-3, 1e-1)),
                m + "d_skip": ((U, nh), "float32", ("normal_around", 1.0, 0.1)),
                m + "norm_scale": ((U, din), "float32", norm),
                m + "out_proj": ((U, din, d), dt, ("normal", din ** -0.5)),
            })
            if a.get("ssm_conv_bias"):
                spec[m + "conv_b"] = ((U, conv_dim), dt, ("normal", k ** -0.5))
            if not f:
                continue
        else:
            spec.update({
                m + "wq": ((U, d, h, hd), dt, ("normal", d ** -0.5)),
                m + "wk": ((U, d, kv, hd), dt, ("normal", d ** -0.5)),
                m + "wv": ((U, d, kv, hd), dt, ("normal", d ** -0.5)),
                m + "wo": ((U, h, hd, d), dt, ("normal", (h * hd) ** -0.5)),
            })
        spec.update({
            u + "ln2": ((U, d), "float32", norm),
            u + "mlp/w_gate": ((U, d, f), dt, ("normal", d ** -0.5)),
            u + "mlp/w_up": ((U, d, f), dt, ("normal", d ** -0.5)),
            u + "mlp/w_down": ((U, f, d), dt, ("normal", f ** -0.5)),
        })
    return spec


def _ssd(lw, x, a: dict, num: Numerics):
    """The Mamba-2 mixer over the whole sequence, step by step."""
    d, din, h, p, g, n, k = ssd_dims(a)
    R, S, _ = x.shape
    proj = num.linear(x, lw["mixer/in_proj"])
    z, xbc, dt = jnp.split(proj, [din, 2 * din + 2 * g * n], axis=-1)
    w = lw["mixer/conv_w"]                                       # (k, C)
    pad = jnp.concatenate([jnp.zeros((R, k - 1, xbc.shape[-1]), F32), xbc], axis=1)
    conv = sum(pad[:, i:i + S] * w[i] for i in range(k))
    if a.get("ssm_conv_bias"):
        conv = conv + lw["mixer/conv_b"]
    xs, bs, cs = jnp.split(jax.nn.silu(conv), [din, din + g * n], axis=-1)
    dt = jax.nn.softplus(dt + lw["mixer/dt_bias"])               # (R, S, h)
    a_h = -jnp.exp(lw["mixer/a_log"])                            # (h,)
    xs = xs.reshape(R, S, h, p)
    bs = jnp.repeat(bs.reshape(R, S, g, n), h // g, axis=2)      # (R, S, h, n)
    cs = jnp.repeat(cs.reshape(R, S, g, n), h // g, axis=2)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a_h)[..., None, None] * state
                 + num.round(dt_t[..., None] * x_t)[..., None] * num.round(b_t)[:, :, None, :])
        y = jnp.einsum("rhpn,rhn->rhp", num.round(state), num.round(c_t), precision=HIGHEST)
        return state, y

    seq = tuple(t.swapaxes(0, 1) for t in (xs, bs, cs, dt))
    _, ys = jax.lax.scan(step, jnp.zeros((R, h, p, n), F32), seq)
    y = ys.swapaxes(0, 1) + xs * lw["mixer/d_skip"][:, None]
    y = rms_norm(y.reshape(R, S, din) * jax.nn.silu(z), lw["mixer/norm_scale"],
                 a.get("norm_eps", 1e-6))
    return num.linear(y, lw["mixer/out_proj"])


def _attention(lw, x, a: dict, num: Numerics, positions):
    """Causal GQA over the whole sequence at the configured scale."""
    hd = lw["mixer/wq"].shape[-1]
    q = num.linear(x, lw["mixer/wq"])
    k = num.linear(x, lw["mixer/wk"])
    v = num.linear(x, lw["mixer/wv"])
    theta = a.get("rope_theta", 10000.0)
    if theta is not None:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    scale = a.get("attn_scale")
    if scale is not None:            # dense.attention scales scores by hd ** -0.5
        q = q * (scale * hd ** 0.5)
    o = dense.attention(q, k, v, None, num)
    return num.linear(o, lw["mixer/wo"], n_in=2)


def _layer(x, lw, kind: str, a: dict, num: Numerics, positions):
    eps, res = a.get("norm_eps", 1e-6), a.get("residual_multiplier", 1.0)
    h = rms_norm(x, lw["ln1"], eps)
    y = _ssd(lw, h, a, num) if kind == "ssm" else _attention(lw, h, a, num, positions)
    x = x + res * y
    if kind == "ssm" and not a["d_ff"]:
        return x
    h = rms_norm(x, lw["ln2"], eps)
    g = num.linear(h, lw["mlp/w_gate"])
    y = num.linear(jax.nn.silu(g) * num.linear(h, lw["mlp/w_up"]), lw["mlp/w_down"])
    return x + res * y


def hidden(w: dict, tokens: jnp.ndarray, a: dict, num: Numerics,
           remat: bool = False) -> jnp.ndarray:
    """Final-normed hidden states (R, S, d) of ``tokens`` (R, S)."""
    pattern = _supported(a)
    positions = jnp.arange(tokens.shape[1])
    units = {}
    for path, v in w.items():
        if path.startswith("stack/"):
            u, leaf = path[len("stack/"):].split("/", 1)
            units.setdefault(u, {})[leaf] = v

    def body(x, uw):
        for i, kind in enumerate(pattern):
            x = _layer(x, f32_leaves(uw[f"u{i}"]), kind, a, num, positions)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x = w["embed"][tokens].astype(F32) * a.get("embedding_multiplier", 1.0)
    x, _ = jax.lax.scan(body, x, units)
    return rms_norm(x, w["ln_f"], a.get("norm_eps", 1e-6))


def head(w: dict, x: jnp.ndarray, a: dict, num: Numerics) -> jnp.ndarray:
    lg = num.linear(x, w["embed"].T)[..., :a["vocab_size"]]
    return lg / a.get("logits_scaling", 1.0)


def logits(w: dict, tokens: jnp.ndarray, a: dict, num: Numerics,
           first: int = 0) -> jnp.ndarray:
    """Logits (R, S - first, vocab) at positions ``first`` onwards."""
    return head(w, hidden(w, tokens, a, num)[:, first:], a, num)
