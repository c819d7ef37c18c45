"""Reference attention implementations (pure jnp, GSPMD-friendly).

These are the oracles for the Pallas kernels in ``repro.kernels`` and
the path used off the TPU, the 512-device dry-run included (Pallas TPU
kernels cannot lower on the CPU backend). On a TPU backend the dispatch
points below call the compiled kernels.

Layouts: q (B, S, H, hd); k/v (B, T, KV, hd). GQA groups are computed
via einsum without materializing repeated K/V.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharding_utils import BATCH, maybe_shard

NEG_INF = -2.0e38


def _pallas_ops():
    """Kernel dispatch (lazy import — kernels.ref imports this module)."""
    from ..kernels import ops
    return ops if ops.use_pallas() else None


def _mask_bias(s_len: int, t_len: int, *, causal: bool, window: Optional[int],
               prefix_len: int, offset: int) -> jnp.ndarray:
    """(s_len, t_len) additive bias. ``offset`` = absolute position of the
    first query row (for chunked prefill / decode)."""
    qpos = jnp.arange(s_len)[:, None] + offset
    kpos = jnp.arange(t_len)[None, :]
    ok = jnp.ones((s_len, t_len), bool)
    if causal:
        ok = kpos <= qpos
        if prefix_len > 0:
            ok = ok | (kpos < prefix_len)
    if window is not None:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, NEG_INF)


def gqa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: Optional[int] = None,
                  prefix_len: int = 0, offset: int = 0,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """Grouped-query attention. Returns (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(B, S, KV, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    bias = _mask_bias(S, k.shape[1], causal=causal, window=window,
                      prefix_len=prefix_len, offset=offset)
    logits = logits + bias[None, None, None]
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, S, H, hd)


def gqa_attention_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          causal: bool = True, window: Optional[int] = None,
                          prefix_len: int = 0, q_chunk: int = 1024,
                          scale: Optional[float] = None) -> jnp.ndarray:
    """Long-sequence attention for prefill/training: the flash kernel on
    a TPU backend (causal, no prefix), else the query-chunked reference."""
    if causal and prefix_len == 0:
        ops = _pallas_ops()
        if ops is not None:
            return ops.flash_attention(q, k, v, causal=True, window=window,
                                       scale=scale)
    return chunked_attention_ref(q, k, v, causal=causal, window=window,
                                 prefix_len=prefix_len, q_chunk=q_chunk,
                                 scale=scale)


def chunked_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          causal: bool = True, window: Optional[int] = None,
                          prefix_len: int = 0, q_chunk: int = 1024,
                          scale: Optional[float] = None) -> jnp.ndarray:
    """Query-chunked attention (pure jnp, never dispatches): bounds live
    score memory at (B, H, q_chunk, T) — the flash kernel's stand-in off
    the TPU and the function its backward differentiates."""
    B, S, H, hd = q.shape
    if S % q_chunk:
        return gqa_attention(q, k, v, causal=causal, window=window,
                             prefix_len=prefix_len, scale=scale)
    nc = S // q_chunk
    qs = q.reshape(B, nc, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)

    # per-chunk remat: the backward pass recomputes each chunk's scores
    # (flash-attention-style) instead of saving (nc, B, H, chunk, T) logits
    @jax.remat
    def chunk_body(qc, i):
        return gqa_attention(qc, k, v, causal=causal, window=window,
                             prefix_len=prefix_len, offset=i * q_chunk,
                             scale=scale)

    def chunk_fn(_, args):
        i, qc = args
        return None, chunk_body(qc, i)

    _, outs = jax.lax.scan(chunk_fn, None, (jnp.arange(nc), qs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_len: jnp.ndarray, layer=None, *,
                     window: Optional[int] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-position decode vs a (B, T, KV, hd) cache, or vs layer
    ``layer`` of a stacked (L, B, T, KV, hd) cache.

    q: (B, 1, H, hd); ``cache_len``: (B,) int32 — number of valid cache
    entries (the new token's k/v must already be written at
    ``cache_len - 1``). Masked positions are length-masked in f32.
    """
    ops = _pallas_ops()
    if ops is not None:
        return ops.decode_attention(q, k_cache, v_cache, cache_len, layer,
                                    window=window, scale=scale)
    return decode_attention_ref(q, k_cache, v_cache, cache_len, layer,
                                window=window, scale=scale)


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, cache_len: jnp.ndarray,
                         layer=None, *, window: Optional[int] = None,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Pure-jnp decode attention (the kernel oracle — never dispatches)."""
    if layer is not None:
        k_cache = jax.lax.dynamic_index_in_dim(k_cache, layer, keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(v_cache, layer, keepdims=False)
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    T = k_cache.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    qg = q.reshape(B, KV, G, hd)
    logits = jnp.einsum("bkgh,btkh->bkgt", qg, k_cache).astype(jnp.float32) * scale
    kpos = jnp.arange(T)[None, :]
    ok = kpos < cache_len[:, None]
    if window is not None:
        ok = ok & (kpos > cache_len[:, None] - 1 - window)
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,btkh->bkgh", w, v_cache)
    return out.reshape(B, 1, H, hd)


# -- MLA (DeepSeek-V2 §2.1) --------------------------------------------------------
def mla_prefill(cq: jnp.ndarray, ckv: jnp.ndarray, k_rope: jnp.ndarray,
                wq_nope: jnp.ndarray, wq_rope: jnp.ndarray,
                wk_nope: jnp.ndarray, wv: jnp.ndarray, *,
                rope_theta: float, causal: bool = True,
                q_chunk: Optional[int] = None) -> jnp.ndarray:
    """Multi-head latent attention, materialized (prefill/training) path.

    cq:  (B, S, Rq)      — compressed queries (post q_a + norm)
    ckv: (B, T, Rkv)     — compressed KV latent (post kv_a + norm)
    k_rope: (B, T, dr)   — decoupled RoPE key (shared across heads, pre-rope)
    wq_nope: (Rq, H, dn); wq_rope: (Rq, H, dr)
    wk_nope: (Rkv, H, dn); wv: (Rkv, H, dv)
    Returns (B, S, H, dv). ``q_chunk`` bounds score memory for long S.
    """
    from .common import apply_rope
    B, S, _ = cq.shape
    T = ckv.shape[1]
    k_nope = jnp.einsum("btr,rhd->bthd", ckv, wk_nope)
    v = jnp.einsum("btr,rhd->bthd", ckv, wv)
    k_pos = jnp.arange(T)[None, :]
    k_rope_r = apply_rope(k_rope[:, :, None, :], k_pos, rope_theta)  # (B,T,1,dr)

    def block(cq_blk, offset):
        q_nope = jnp.einsum("bsr,rhd->bshd", cq_blk, wq_nope)
        q_rope = jnp.einsum("bsr,rhd->bshd", cq_blk, wq_rope)
        q_nope = maybe_shard(q_nope, P(BATCH, None, "model", None))
        q_pos = jnp.arange(cq_blk.shape[1])[None, :] + offset
        q_rope = apply_rope(q_rope, q_pos, rope_theta)
        dn, dr = q_nope.shape[-1], q_rope.shape[-1]
        scale = (dn + dr) ** -0.5
        logits = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
                  + jnp.einsum("bshd,btxd->bhst", q_rope, k_rope_r)
                  ).astype(jnp.float32) * scale
        logits = maybe_shard(logits, P(BATCH, "model", None, None))
        bias = _mask_bias(cq_blk.shape[1], T, causal=causal, window=None,
                          prefix_len=0, offset=offset)
        w = jax.nn.softmax(logits + bias[None, None], axis=-1).astype(cq.dtype)
        out = jnp.einsum("bhst,bthd->bshd", w, v)
        return maybe_shard(out, P(BATCH, None, "model", None))

    if not q_chunk or S <= q_chunk or S % q_chunk:
        return block(cq, 0)
    nc = S // q_chunk
    cqs = cq.reshape(B, nc, q_chunk, -1).transpose(1, 0, 2, 3)

    rematted = jax.remat(block)          # recompute per-chunk scores in bwd

    def chunk_fn(_, args):
        i, blk = args
        return None, rematted(blk, i * q_chunk)

    _, outs = jax.lax.scan(chunk_fn, None, (jnp.arange(nc), cqs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, -1, wv.shape[-1])


def mla_decode(cq: jnp.ndarray, ckv_cache: jnp.ndarray, krope_cache: jnp.ndarray,
               cache_len: jnp.ndarray, wq_nope: jnp.ndarray, wq_rope: jnp.ndarray,
               wk_nope: jnp.ndarray, wv: jnp.ndarray, *,
               rope_theta: float) -> jnp.ndarray:
    """Weight-absorbed MLA decode: attention runs in the compressed
    latent space — the cache stays (B, T, Rkv) + (B, T, dr).

    cq: (B, 1, Rq). krope_cache rows are stored *post-rope*. Returns
    (B, 1, H, dv).
    """
    from .common import apply_rope
    B = cq.shape[0]
    q_nope = jnp.einsum("bsr,rhd->bshd", cq, wq_nope)          # (B,1,H,dn)
    q_rope = jnp.einsum("bsr,rhd->bshd", cq, wq_rope)
    q_rope = apply_rope(q_rope, cache_len[:, None] - 1, rope_theta)
    # absorb W_uk: q' = q_nope @ wk_nope^T  -> latent-space query
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk_nope)      # (B,1,H,Rkv)
    dn, dr = q_nope.shape[-1], q_rope.shape[-1]
    scale = (dn + dr) ** -0.5
    logits = (jnp.einsum("bshr,btr->bhst", q_lat, ckv_cache)
              + jnp.einsum("bshd,btd->bhst", q_rope, krope_cache)
              ).astype(jnp.float32) * scale
    T = ckv_cache.shape[1]
    ok = jnp.arange(T)[None, :] < cache_len[:, None]
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(cq.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", w, ckv_cache)           # (B,1,H,Rkv)
    return jnp.einsum("bshr,rhd->bshd", ctx, wv)               # (B,1,H,dv)
