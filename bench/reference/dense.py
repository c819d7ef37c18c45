"""Plain float32 reference of a dense decoder (Llama/Mistral family, as
h2o-danube-1.8b, arXiv:2401.16818): token embedding, then per layer
RMSNorm -> grouped-query attention with RoPE and an optional sliding
window -> residual, RMSNorm -> SwiGLU MLP -> residual; a final RMSNorm
and an untied output head.

Departures from the published model, all shared with the program:
RMSNorm scales are stored as ``1 + scale``; the vocabulary is padded to
``vocab_pad`` and the padded rows never win (they are sliced off here).
Attention is computed over the whole sequence with no cache, in blocks
of query rows so that the scores fit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, Numerics, f32_leaves, padded, rms_norm, rope

Q_BLOCK = 512
NEG = -1e30


def _supported(a: dict) -> None:
    off = ("qk_norm", "n_experts", "mla", "prefix_len", "encdec",
           "vision_stub", "block_pattern", "ssm")
    bad = [k for k in off if a.get(k)]
    if bad or not a.get("gated_mlp", True) or a.get("act", "silu") != "silu":
        raise NotImplementedError(f"dense reference: {bad or a.get('act')}")


def param_spec(a: dict) -> dict:
    _supported(a)
    d, h, kv = a["d_model"], a["n_heads"], a["n_kv_heads"]
    hd = a.get("head_dim") or d // h
    f, L = a["d_ff"], a["n_layers"]
    vp = padded(a["vocab_size"], a.get("vocab_pad", 256))
    dt = a.get("dtype", "bfloat16")
    norm = ("normal", 0.1)
    u = "stack/u0/"
    spec = {
        "embed": ((vp, d), dt, ("normal", 0.02)),
        "ln_f": ((d,), "float32", norm),
        u + "ln1": ((L, d), "float32", norm),
        u + "ln2": ((L, d), "float32", norm),
        u + "mixer/wq": ((L, d, h, hd), dt, ("normal", d ** -0.5)),
        u + "mixer/wk": ((L, d, kv, hd), dt, ("normal", d ** -0.5)),
        u + "mixer/wv": ((L, d, kv, hd), dt, ("normal", d ** -0.5)),
        u + "mixer/wo": ((L, h, hd, d), dt, ("normal", (h * hd) ** -0.5)),
        u + "mlp/w_gate": ((L, d, f), dt, ("normal", d ** -0.5)),
        u + "mlp/w_up": ((L, d, f), dt, ("normal", d ** -0.5)),
        u + "mlp/w_down": ((L, f, d), dt, ("normal", f ** -0.5)),
    }
    if not a.get("tie_embeddings", False):
        spec["unembed"] = ((d, vp), dt, ("normal", d ** -0.5))
    return spec


def _layers(w: dict) -> dict:
    u = "stack/u0/"
    return {k[len(u):]: v for k, v in w.items() if k.startswith(u)}


def attention(q, k, v, window, num: Numerics = Numerics()) -> jnp.ndarray:
    """q (R, S, H, hd), k/v (R, S, KV, hd): causal softmax attention in
    float32, key j visible to query i when j <= i and i - j < window."""
    R, S, H, hd = q.shape
    G = H // k.shape[2]
    kpos = jnp.arange(S)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        qpos = jnp.arange(s0, s0 + qb.shape[1])
        qg = qb.reshape(R, qb.shape[1], k.shape[2], G, hd)
        s = num.einsum("rskgh,rtkh->rkgst", qg, k) * hd ** -0.5
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        o = num.einsum("rkgst,rtkh->rskgh", p, v)
        outs.append(o.reshape(R, qb.shape[1], H, hd))
    return jnp.concatenate(outs, axis=1)


def _block(x, lw, a, num: Numerics, positions):
    eps, theta = a.get("norm_eps", 1e-6), a.get("rope_theta", 10000.0)
    lw = f32_leaves(lw)
    h = rms_norm(x, lw["ln1"], eps)
    q = rope(num.linear(h, lw["mixer/wq"]), positions, theta)
    k = rope(num.linear(h, lw["mixer/wk"]), positions, theta)
    v = num.linear(h, lw["mixer/wv"])
    o = attention(q, k, v, a.get("window"), num)
    x = x + num.linear(o, lw["mixer/wo"], n_in=2)
    h = rms_norm(x, lw["ln2"], eps)
    g = num.linear(h, lw["mlp/w_gate"])
    x = x + num.linear(jax.nn.silu(g) * num.linear(h, lw["mlp/w_up"]),
                       lw["mlp/w_down"])
    return x


def hidden(w: dict, tokens: jnp.ndarray, a: dict, num: Numerics,
           remat: bool = False) -> jnp.ndarray:
    """Final-normed hidden states (R, S, d) of ``tokens`` (R, S)."""
    positions = jnp.arange(tokens.shape[1])

    def body(x, lw):
        return _block(x, lw, a, num, positions), None

    if remat:
        body = jax.checkpoint(body)
    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(body, x, _layers(w))
    return rms_norm(x, w["ln_f"], a.get("norm_eps", 1e-6))


def head(w: dict, x: jnp.ndarray, a: dict, num: Numerics) -> jnp.ndarray:
    out = w["embed"].T if a.get("tie_embeddings", False) else w["unembed"]
    return num.linear(x, out)[..., :a["vocab_size"]]


def logits(w: dict, tokens: jnp.ndarray, a: dict, num: Numerics,
           first: int = 0) -> jnp.ndarray:
    """Logits (R, S - first, vocab) at positions ``first`` onwards."""
    return head(w, hidden(w, tokens, a, num)[:, first:], a, num)
