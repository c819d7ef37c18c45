"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) block.

Chunked SSD implementation following the paper's ``ssd_minimal``
(quadratic intra-chunk + linear inter-chunk state passing) — this is
also the reference for ``repro.kernels.ssd_scan``. Decode is the O(1)
recurrent update carrying (B, H, P, N) state + a conv tail.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import dense_init, rms_norm, split_keys
from .config import ArchConfig


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum a[..., j+1..i] (−inf j>i)."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # sum over (j, i]
    mask = jnp.tril(jnp.ones((L, L), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jnp.ndarray, a_log: jnp.ndarray, b: jnp.ndarray,
                c: jnp.ndarray, chunk: int,
                h0: jnp.ndarray | None = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD scan.

    x: (B, S, H, P) inputs (already multiplied by dt);
    a_log: (B, S, H) per-step log-decay (dt·A, ≤ 0);
    b, c: (B, S, G, N) input/output projections (G groups, H % G == 0);
    Returns (y (B,S,H,P), final state (B,H,P,N)).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert S % chunk == 0, f"seq {S} not divisible by chunk {chunk}"
    nc = S // chunk
    rep = H // G
    xb = x.reshape(B, nc, chunk, H, P)
    ab = a_log.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2)   # (B,H,nc,l)
    bb = b.reshape(B, nc, chunk, G, N)
    cb = c.reshape(B, nc, chunk, G, N)

    a_cum = jnp.cumsum(ab, axis=-1)                             # (B,H,nc,l)
    # intra-chunk (quadratic, "attention-like" dual form)
    Lmat = jnp.exp(_segsum(ab))                                 # (B,H,nc,l,l)
    cb_h = jnp.repeat(cb, rep, axis=3)                          # (B,nc,l,H,N)
    bb_h = jnp.repeat(bb, rep, axis=3)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp",
                        cb_h, bb_h, Lmat, xb)
    # chunk-final states (carried in f32 for decode-compatible precision)
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)             # (B,H,nc,l)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", bb_h, decay_states,
                        xb).astype(jnp.float32)
    # inter-chunk recurrence: h_{c+1} = exp(sum a_c) h_c + states_c
    chunk_decay = jnp.exp(a_cum[..., -1])                       # (B,H,nc)

    def comb(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, s2 + a2[..., None, None] * s1

    a_sc = chunk_decay.transpose(0, 2, 1).astype(jnp.float32)   # (B,nc,H)
    init_state = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    # prepend the initial state as a virtual chunk
    a_all = jnp.concatenate([jnp.ones((B, 1, H), jnp.float32), a_sc], axis=1)
    s_all = jnp.concatenate([init_state[:, None], states], axis=1)  # (B,nc+1,H,P,N)
    a_run, s_run = jax.lax.associative_scan(comb, (a_all, s_all), axis=1)
    prev_states = s_run[:, :-1]                                 # state entering chunk c
    final_state = s_run[:, -1]                                  # (B,H,P,N) f32
    # inter-chunk contribution
    state_decay = jnp.exp(a_cum)                                # (B,H,nc,l)
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", cb_h, prev_states, state_decay)
    y = (y_diag + y_off).reshape(B, S, H, P).astype(x.dtype)
    return y, final_state


def ssd_scanned(x: jnp.ndarray, a_log: jnp.ndarray, b: jnp.ndarray,
                c: jnp.ndarray, chunk: int,
                h0: jnp.ndarray | None = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential-over-chunks SSD (same math as ``ssd_chunked``, same
    chunk math as the Pallas kernel): the recurrent state is carried
    through a ``lax.scan`` so only ONE chunk's (l, l) decay matrix is
    live at a time — ``ssd_chunked`` materializes all ``nc`` chunks'
    matrices at once, which costs TBs at 32k-token prefill."""
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    assert S % chunk == 0
    nc = S // chunk
    rep = H // G
    xb = x.reshape(B, nc, chunk, H, P).transpose(1, 0, 2, 3, 4)
    ab = a_log.reshape(B, nc, chunk, H).transpose(1, 0, 3, 2)   # (nc,B,H,l)
    bb = b.reshape(B, nc, chunk, G, N).transpose(1, 0, 2, 3, 4)
    cb = c.reshape(B, nc, chunk, G, N).transpose(1, 0, 2, 3, 4)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(state, inputs):
        xc, ac, bc, cc = inputs                     # (B,l,H,P) (B,H,l) ...
        a_cum = jnp.cumsum(ac, axis=-1)             # (B,H,l)
        seg = a_cum[..., :, None] - a_cum[..., None, :]
        lmat = jnp.where(mask, jnp.exp(seg), 0.0)   # (B,H,l,l)
        cb_h = jnp.repeat(cc, rep, axis=2)          # (B,l,H,N)
        bb_h = jnp.repeat(bc, rep, axis=2)
        y_diag = jnp.einsum("blhn,bshn,bhls,bshp->blhp", cb_h, bb_h, lmat, xc)
        y_off = jnp.einsum("blhn,bhpn,bhl->blhp", cb_h, state,
                           jnp.exp(a_cum))
        decay = jnp.exp(a_cum[..., -1:] - a_cum)    # (B,H,l)
        add = jnp.einsum("blhn,bhl,blhp->bhpn", bb_h, decay, xc)
        state = jnp.exp(a_cum[..., -1])[..., None, None] * state + add
        return state, (y_diag + y_off).astype(x.dtype)

    init = jnp.zeros((B, H, P, N), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    final, ys = jax.lax.scan(jax.remat(step), init, (xb, ab, bb, cb))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    return y, final


# -- full block ---------------------------------------------------------------------
def init_mamba2(key, cfg: ArchConfig, dtype) -> Dict:
    d, din = cfg.d_model, cfg.d_inner
    g, n, h = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    ks = split_keys(key, 4)
    conv_dim = din + 2 * g * n
    p = {
        "in_proj": dense_init(ks[0], (d, 2 * din + 2 * g * n + h), dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv, conv_dim), dtype,
                             fan_in=cfg.ssm_conv),
        "a_log": jnp.zeros((h,), jnp.float32),          # A = -exp(a_log) in [-1, 0)
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.zeros((din,), jnp.float32),
        "out_proj": dense_init(ks[2], (din, d), dtype, fan_in=din),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((conv_dim,), dtype)
    return p


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray | None,
                 tail: jnp.ndarray | None = None) -> jnp.ndarray:
    """Depthwise causal conv, then SiLU. x: (B, S, C); w: (K, C); b: (C,)
    or None; tail: (B, K-1, C)."""
    K = w.shape[0]
    pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype) if tail is None else tail
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(K))
    if b is not None:
        out = out + b
    return jax.nn.silu(out)


def apply_mamba2(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
                 state: Dict | None = None) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, S, D) → (out, new_state). ``state`` carries {ssm, conv} for
    decode; None runs the chunked parallel scan from zero state."""
    B, S, D = x.shape
    din, g, n, h = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    pdim = cfg.ssm_headdim
    with jax.named_scope("in_proj"):
        proj = x @ p["in_proj"]
        z, xc, bc, cc, dt = jnp.split(
            proj, [din, 2 * din, 2 * din + g * n, 2 * din + 2 * g * n], axis=-1)
    with jax.named_scope("conv"):
        conv_in = jnp.concatenate([xc, bc, cc], axis=-1)
        tail = state["conv"] if state is not None else None
        conv_out = _causal_conv(conv_in, p["conv_w"], p.get("conv_b"), tail)
        K = cfg.ssm_conv
        hist = conv_in if tail is None else jnp.concatenate([tail, conv_in], axis=1)
        if hist.shape[1] < K - 1:       # very short prefill: left-pad with zeros
            pad = jnp.zeros((B, K - 1 - hist.shape[1], hist.shape[2]), hist.dtype)
            hist = jnp.concatenate([pad, hist], axis=1)
        new_conv = hist[:, -(K - 1):]
        xc, bc, cc = jnp.split(conv_out, [din, din + g * n], axis=-1)

    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])       # (B,S,H)
        a = -jnp.exp(p["a_log"])                                          # (H,)
        a_log_steps = dt * a                                              # (B,S,H) ≤ 0
        xh = xc.reshape(B, S, h, pdim)
        xdt = xh * dt[..., None].astype(x.dtype)
        bmat = bc.reshape(B, S, g, n)
        cmat = cc.reshape(B, S, g, n)

        h0 = state["ssm"] if state is not None else None
        chunk = min(cfg.ssm_chunk, S)
        if h0 is None and S % chunk == 0:
            from ..kernels import ops as _kops       # lazy: ref.py imports us
            if _kops.use_pallas():
                y, hfin = _kops.ssd_scan(xdt, a_log_steps, bmat, cmat, chunk=chunk)
            elif S // chunk > 4:
                # long sequences: sequential chunk scan — one (l, l) decay
                # matrix live at a time instead of all nc at once
                y, hfin = ssd_scanned(xdt, a_log_steps, bmat, cmat, chunk, h0)
            else:
                y, hfin = ssd_chunked(xdt, a_log_steps, bmat, cmat, chunk=chunk)
        elif S % chunk == 0 and S // chunk > 4:
            y, hfin = ssd_scanned(xdt, a_log_steps, bmat, cmat, chunk, h0)
        else:
            y, hfin = ssd_chunked(xdt, a_log_steps, bmat, cmat, chunk=chunk, h0=h0)
        y = y + xh * p["d_skip"][None, None, :, None].astype(x.dtype)
    with jax.named_scope("out"):
        y = y.reshape(B, S, din)
        y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
        out = y @ p["out_proj"]
    return out, {"ssm": hfin, "conv": new_conv}


def apply_mamba2_decode(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
                        state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """Single-token recurrent update. x: (B, 1, D)."""
    B, S, D = x.shape
    din, g, n, h = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    pdim = cfg.ssm_headdim
    with jax.named_scope("in_proj"):
        proj = x @ p["in_proj"]
        z, xc, bc, cc, dt = jnp.split(
            proj, [din, 2 * din, 2 * din + g * n, 2 * din + 2 * g * n], axis=-1)
    with jax.named_scope("conv"):
        conv_in = jnp.concatenate([xc, bc, cc], axis=-1)                 # (B,1,C)
        window = jnp.concatenate([state["conv"], conv_in], axis=1)       # (B,K,C)
        conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"])
        if "conv_b" in p:
            conv_out = conv_out + p["conv_b"]
        conv_out = jax.nn.silu(conv_out)[:, None]
        new_conv = window[:, 1:]
        xc, bc, cc = jnp.split(conv_out, [din, din + g * n], axis=-1)

    with jax.named_scope("scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]   # (B,H)
        a = jnp.exp(dt * -jnp.exp(p["a_log"]))                              # (B,H)
        xh = xc.reshape(B, h, pdim)
        bmat = jnp.repeat(bc.reshape(B, g, n), h // g, axis=1)              # (B,H,N)
        cmat = jnp.repeat(cc.reshape(B, g, n), h // g, axis=1)
        hs = state["ssm"].astype(jnp.float32)
        hs = a[..., None, None] * hs + (dt[..., None] * xh.astype(jnp.float32)
                                        )[..., None] * bmat[:, :, None, :].astype(jnp.float32)
        y = jnp.einsum("bhpn,bhn->bhp", hs, cmat.astype(jnp.float32)).astype(x.dtype)
        y = y + xh * p["d_skip"][None, :, None].astype(x.dtype)
        new_ssm = hs.astype(state["ssm"].dtype)
    with jax.named_scope("out"):
        y = y.reshape(B, 1, din)
        y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
        out = y @ p["out_proj"]
    return out, {"ssm": new_ssm, "conv": new_conv}


def mamba2_state_shape(cfg: ArchConfig, batch: int, dtype):
    h, pdim, n = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return {"ssm": ((batch, h, pdim, n), jnp.float32),
            "conv": ((batch, cfg.ssm_conv - 1, conv_dim), dtype)}
