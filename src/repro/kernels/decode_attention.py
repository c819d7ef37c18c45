"""Split-KV flash-decode attention as a Pallas TPU kernel.

One new token attends to a (B, T, KV, d) cache. The KV sequence is
split into tiles that stream through VMEM (the whole 32k decode cache
never fits); the running (max, sum, acc) softmax state is carried in
scratch across tiles — the same log-sum-exp rescaling that lets the
sharded serve-path combine per-shard partial attention with a psum.

Layout: the cache is viewed (free, row-major) as (B, T·KV, d), so a tile
is ``block_k · KV`` rows by the full head dim — a shape Mosaic tiles for
any KV count, with no transpose of the cache. All H query heads of a
batch row are one (H, d) block, so one grid step scores every head
against every row of the tile on the MXU; row r belongs to cache
position r // KV and KV head r % KV, and a score survives only where the
row's KV head is its query head's group (h // G). The KV-fold redundant
multiply is cheap next to the cache read that bounds decode.

``cache_len`` (B,) arrives via scalar prefetch so the kernel masks
invalid cache rows (and the ring-buffer window) without host branching.

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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, block_k: int, n_kv: int, group: int,
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
    run = k_start < valid

    @pl.when(run)
    def _step():
        q = q_ref[...]                                     # (H, d)
        k = k_ref[...]                                     # (bk·KV, d)
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + row // n_kv
        ok = jnp.logical_and(row % n_kv == head // group, kpos < valid)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > valid - 1 - window)
        s = jnp.where(ok, s, NEG_INF)
        # rows past the end of a partial tile hold unspecified data
        vrow = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
        v = jnp.where(k_start + vrow // n_kv < valid, v, jnp.zeros_like(v))

        m_prev = m_scr[...]                                # (H, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ik == n_kblocks - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len: jnp.ndarray, *,
                     window: Optional[int] = None,
                     scale: Optional[float] = None, block_k: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, H, d); k/v cache: (B, T, KV, d); cache_len: (B,) int32.
    Returns (B, 1, H, d)."""
    B, _, H, d = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale_v = float(scale) if scale is not None else d ** -0.5
    bk = min(block_k, T)
    nk = pl.cdiv(T, bk)
    rows = bk * KV

    kernel = functools.partial(_decode_kernel, scale=scale_v, block_k=bk,
                               n_kv=KV, group=G, window=window, n_kblocks=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((None, H, d), lambda b, ik, lens: (b, 0, 0)),
            pl.BlockSpec((None, rows, d), lambda b, ik, lens: (b, ik, 0)),
            pl.BlockSpec((None, rows, d), lambda b, ik, lens: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, H, d), lambda b, ik, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q.reshape(B, H, d),
      k_cache.reshape(B, T * KV, d), v_cache.reshape(B, T * KV, d))
    return out.reshape(B, 1, H, d)
