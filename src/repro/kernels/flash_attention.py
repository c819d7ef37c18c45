"""Flash attention (causal / sliding-window / GQA) as a Pallas TPU kernel.

TPU-native adaptation of the standard flash algorithm:

* The wrapper puts heads ahead of the sequence — q (B, H, S, d),
  k/v (B, KV, T, d) — so every block's last two dims are
  (block, head_dim): a multiple of 8 rows by the full head dim, which is
  what Mosaic tiles (head_dim 80 on danube is legal as the full dim).
* grid = (batch, q_heads, Q blocks, KV blocks); the KV dimension is the
  innermost, sequential ("arbitrary") axis so the running softmax state
  lives in VMEM scratch across KV steps.
* GQA indexes the KV head as ``h // group_size`` in the BlockSpec index
  map — K/V tiles are never materialized per q-head.
* causal + sliding-window masking is applied from block coordinates;
  tiles that are fully masked skip their matmuls via ``pl.when``.
* The backward is the VJP of the query-chunked jnp reference
  (``ref.flash_attention_ref`` with ``q_chunk``), recomputed from the
  saved q/k/v — a ``jax.custom_vjp`` around the forward kernel. A
  backward kernel is later work.

Validated against ``ref.flash_attention_ref`` in interpret mode on CPU
(tests/test_kernels.py sweeps shapes, windows and dtypes).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

NEG_INF = -2.0e38
BWD_Q_CHUNK = 512          # query chunk of the reference backward


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int,
                  seq_k: int, causal: bool, window: Optional[int],
                  n_kblocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # tile-level reachability: skip tiles fully above the causal diagonal
    # or entirely left of the sliding window
    run = k_start < seq_k
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[...]                                     # (bq, d)
        k = k_ref[...]                                     # (bk, d)
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos < seq_k
        if causal:
            ok = jnp.logical_and(ok, kpos <= qpos)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)
        # rows past the end of a partial KV tile hold unspecified data
        krow = k_start + jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0)
        v = jnp.where(krow < seq_k, v, jnp.zeros_like(v))

        m_prev = m_scr[...]                                # (bq, 1)
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


def _flash_forward(q, k, v, causal, window, scale, block_q, block_k,
                   interpret):
    B, S, H, d = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale_v = float(scale) if scale is not None else d ** -0.5

    bq = min(block_q, S)
    bk = min(block_k, T)
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(T, bk)

    kernel = functools.partial(
        _flash_kernel, scale=scale_v, block_q=bq, block_k=bk,
        seq_k=T, causal=causal, window=window, n_kblocks=nk)

    qt = q.transpose(0, 2, 1, 3)                           # (B, H, S, d)
    kt = k.transpose(0, 2, 1, 3)                           # (B, KV, T, d)
    vt = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, d),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((None, None, bk, d),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((None, None, bk, d),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max
            pltpu.VMEM((bq, 1), jnp.float32),      # running sum
            pltpu.VMEM((bq, d), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, window, scale, block_q, block_k,
                          interpret)


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out = _flash_forward(q, k, v, causal, window, scale, block_q, block_k,
                         interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(functools.partial(
        ref.flash_attention_ref, causal=causal, window=window, scale=scale,
        q_chunk=BWD_Q_CHUNK), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, d); k/v: (B, T, KV, d) with H % KV == 0 → (B, S, H, d).
    Differentiable: the backward is the reference's VJP (module doc)."""
    return _flash(q, k, v, causal, window, scale, block_q, block_k,
                  interpret)
