"""Split-KV flash-decode attention as a Pallas TPU kernel.

One new token attends to a (B, T, KV, d) cache, or to layer ``layer`` of
a stacked (L, B, T, KV, d) cache as the layer scan carries it, read where
it lies with no copy of the layer. The KV sequence is split into tiles
that stream through VMEM (the whole 32k decode cache never fits); the
running (max, sum, acc) softmax state is carried in scratch across
tiles — the same log-sum-exp rescaling that lets the sharded serve-path
combine per-shard partial attention with a psum.

Layout: the kernel reads the cache in the layout the TPU gives it, so
that no copy of the cache precedes the call. A head size that is not a
multiple of 128 lanes would be padded as the minor dim, so the TPU puts
the positions minor: the cache lies as (L, B, KV, d, T), and a tile is
(KV, d, block_k). A head size that is a multiple of 128 stays row-major,
viewed (free) as (L, B, T, KV·d), and a tile is (block_k, KV·d), whose
head h is the 128-aligned lane slice h·d:(h+1)·d. A per-layer cache is
the case L = 1. Either way each KV head scores its G query heads, a
(G, d) block of the (KV, G, d) query, against its (d, block_k) or
(block_k, d) slice on the MXU.

``cache_len`` (B,) and the layer index arrive via scalar prefetch: the
kernel masks invalid cache positions (and the ring-buffer window)
without host branching, and the K/V index maps pick the layer's tiles.

Grid = (B, KV tiles); KV innermost/sequential.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
TILE_BYTES = 1 << 21        # one K (or V) tile in VMEM, double-buffered


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, block_k: int,
                   n_kv: int, head_dim: int, pos_minor: bool, ragged: bool,
                   window: Optional[int], n_kblocks: int):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = len_ref[b]
    k_start = ik * block_k

    @pl.when(k_start < valid)
    def _step():
        group = q_ref.shape[1]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (group, block_k), 1)
        ok = kpos < valid
        if window is not None:
            ok = jnp.logical_and(ok, kpos > valid - 1 - window)
        if ragged:     # positions past the cache's end hold unspecified data
            vshape = (head_dim, block_k) if pos_minor else (block_k, head_dim)
            vpos = k_start + jax.lax.broadcasted_iota(jnp.int32, vshape,
                                                      1 if pos_minor else 0)
        # (d, bk) slices contract their first dim with q, their last with p
        kc, vc = (0, 1) if pos_minor else (1, 0)
        for h in range(n_kv):
            if pos_minor:
                k, v = k_ref[h], v_ref[h]                          # (d, bk)
            else:
                lanes = slice(h * head_dim, (h + 1) * head_dim)
                k, v = k_ref[:, lanes], v_ref[:, lanes]            # (bk, d)
            s = jax.lax.dot_general(q_ref[h], k, (((1,), (kc,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(ok, s, NEG_INF)                          # (G, bk)
            if ragged:
                v = jnp.where(vpos < valid, v, jnp.zeros_like(v))

            m_prev = m_scr[h]                                      # (G, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (vc,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_cur

    @pl.when(ik == n_kblocks - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len: jnp.ndarray,
                     layer=None, *, window: Optional[int] = None,
                     scale: Optional[float] = None, block_k: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, H, d); k/v cache: (B, T, KV, d), or (L, B, T, KV, d) with
    ``layer`` the index (int32 scalar) of the layer to read; cache_len:
    (B,) int32. Returns (B, 1, H, d). ``block_k`` (a multiple of 128)
    caps the positions of a tile, which a ``TILE_BYTES`` tile caps too."""
    if layer is None:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    B, _, H, d = q.shape
    L, T, KV = k_cache.shape[0], k_cache.shape[2], k_cache.shape[3]
    G = H // KV
    scale_v = float(scale) if scale is not None else d ** -0.5
    per_pos = KV * d * k_cache.dtype.itemsize
    bk = min(block_k, max(128, TILE_BYTES // per_pos // 128 * 128))
    bk = T if T <= bk else bk
    nk = pl.cdiv(T, bk)
    pos_minor = d % 128 != 0
    if pos_minor:
        k_view = jnp.transpose(k_cache, (0, 1, 3, 4, 2))       # (L, B, KV, d, T)
        v_view = jnp.transpose(v_cache, (0, 1, 3, 4, 2))
        kv_spec = pl.BlockSpec((None, None, KV, d, bk),
                               lambda b, ik, lens, li: (li[0], b, 0, 0, ik))
    else:
        k_view = k_cache.reshape(L, B, T, KV * d)
        v_view = v_cache.reshape(L, B, T, KV * d)
        kv_spec = pl.BlockSpec((None, None, bk, KV * d),
                               lambda b, ik, lens, li: (li[0], b, ik, 0))

    kernel = functools.partial(_decode_kernel, scale=scale_v, block_k=bk,
                               n_kv=KV, head_dim=d, pos_minor=pos_minor,
                               ragged=T % bk != 0, window=window, n_kblocks=nk)
    qo_spec = pl.BlockSpec((None, KV, G, d), lambda b, ik, lens, li: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nk),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(B, KV, G, d), k_view, v_view)
    return out.reshape(B, 1, H, d)
