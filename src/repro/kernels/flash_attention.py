"""Flash attention (causal / sliding-window / GQA) as a Pallas TPU kernel.

TPU-native adaptation of the standard flash algorithm:

* The wrapper puts heads ahead of the sequence — q (B, H, S, d),
  k/v (B, KV, T, d) — so every block's last two dims are
  (block, head_dim): a multiple of 16 rows by the full head dim, which is
  what Mosaic tiles (head_dim 80 on danube is legal as the full dim).
* GQA fold: q is viewed (free, row-major) as (B, KV, G, S, d) with
  G = H / KV, and grid = (batch, KV heads, Q blocks, KV blocks). A step
  takes the (G, bq, d) queries of one KV head's whole group as G·bq rows
  and scores them against one (bk, d) K/V tile, so each K/V tile is
  fetched once per group, not once per query head. Row r is query
  position ``q_start + r % bq``. The KV dimension is the innermost,
  sequential ("arbitrary") axis, so the running softmax state lives in
  (G·bq, ·) VMEM scratch across KV steps.
* Blocks come from the shape (``_tile_plan``): about ``ROWS`` query rows
  and ``BLOCK_K`` keys a step, fewer under a short window, and as large
  as v5e's scoped VMEM holds — grid steps, not FLOPs, bound small tiles.
* Dead tiles fetch nothing: the K/V index map clamps the KV block to the
  span the query block can see (``_visible``: the causal diagonal, the
  window's far edge), so a step outside it names the block already in
  VMEM and the pipeline issues no copy; ``pl.when`` skips its body.
* The iota/compare mask is built only on tiles that straddle the
  diagonal, the window's edge or the end of T; interior tiles run QK^T,
  the online softmax and PV unmasked. V rows past T are zeroed only when
  ``T % bk != 0``.
* PV takes the probabilities as two bf16 terms (hi + lo) against bf16
  V, accumulated in f32: about 16 bits a weight, where one bf16 term
  would lose accuracy as tiles grow (fewer weights are a tile's exact
  maximum of 1).
* The backward is the VJP of the query-chunked jnp reference
  (``ref.flash_attention_ref`` with ``q_chunk``), recomputed from the
  saved q/k/v — a ``jax.custom_vjp`` around the forward kernel. A
  backward kernel is later work.

Validated against ``ref.flash_attention_ref`` in interpret mode on CPU
(tests/test_kernels.py sweeps shapes, windows and dtypes).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

NEG_INF = -2.0e38
BWD_Q_CHUNK = 512          # query chunk of the reference backward
ROWS = 1024                # target query rows (G * bq) of a step
BLOCK_K = 1024             # target keys of a step
VMEM_BUDGET = 16 << 20     # v5e's default scoped VMEM per kernel
SUBLANES = 16              # bf16 sublane packing: bq's multiple below S
LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _fit(target: int, full: int, multiple: int) -> int:
    """A block of about ``target``: the whole ``full`` dim, or a multiple
    of ``multiple`` under it."""
    if target >= full:
        return full
    return max(multiple, target // multiple * multiple)


def _visible(q_start, bq: int, bk: int, nk: int, seq_q: int, causal: bool,
             window: Optional[int], xp=jnp):
    """First and last KV block that some row of the query block starting
    at ``q_start`` sees. ``xp`` is ``jnp`` on the kernel's traced grid
    indices, ``np`` on the plan's arrays."""
    lo = xp.zeros_like(q_start)
    hi = lo + nk - 1
    if causal:
        q_last = xp.minimum(q_start + bq, seq_q) - 1
        hi = xp.minimum(hi, q_last // bk)
    if window is not None:
        lo = xp.maximum(q_start - window + 1, 0) // bk
    return lo, hi


def _vmem_bytes(rows: int, bk: int, d: int, itemsize: int) -> int:
    """Estimated VMEM of one step: double-buffered q, o, k, v blocks,
    the m/l/acc scratch (lane-padded), and the (rows, bk) f32 scores and
    probabilities and the probabilities' two bf16 terms."""
    dl = _round_up(d, LANES)
    blocks = 2 * (2 * rows + 2 * bk) * dl * itemsize
    scratch = rows * (2 * LANES + dl) * 4
    scores = rows * _round_up(bk, LANES) * (4 + 4 + 2 * itemsize)
    return blocks + scratch + scores


@dataclasses.dataclass(frozen=True)
class TilePlan:
    block_q: int                       # query positions per block
    block_k: int                       # keys per block
    grid: Tuple[int, int, int, int]    # (B, KV, Q blocks, KV blocks)
    window: Optional[int]              # the mask's window; None if inert
    live: int                          # steps whose body runs
    fetched: int                       # K/V tiles the pipeline copies in
    vmem_bytes: int                    # estimate (``_vmem_bytes``)


def _tile_plan(B: int, S: int, T: int, H: int, KV: int, d: int,
               window: Optional[int], causal: bool, itemsize: int = 2,
               block_q: Optional[int] = None,
               block_k: Optional[int] = None) -> TilePlan:
    """Blocks for one call, from its shape: about ``ROWS`` query rows of
    the group (bq = ROWS / G, a multiple of 16 or all of S) against
    ``BLOCK_K`` keys, each cut to a window shorter than them, and bq
    halved until the step fits ``VMEM_BUDGET``. ``block_q``/``block_k``
    override the rule (tests)."""
    G = H // KV
    if window is not None and window >= max(S, T):
        window = None                  # reaches past every pair: no mask
    span = None if window is None else _round_up(window, SUBLANES)
    bq = block_q or _fit(min(ROWS // G, span or S), S, SUBLANES)
    bk = block_k or _fit(min(BLOCK_K, _round_up(span or T, LANES)), T,
                         LANES)
    if block_q is None:
        while (_vmem_bytes(G * bq, bk, d, itemsize) > VMEM_BUDGET
               and bq > SUBLANES):
            bq = _fit(bq // 2, S, SUBLANES)
    bq, bk = min(bq, S), min(bk, T)
    nq, nk = pl.cdiv(S, bq), pl.cdiv(T, bk)

    lo, hi = _visible(np.arange(nq) * bq, bq, bk, nk, S, causal, window,
                      xp=np)
    per_block = np.maximum(hi - lo + 1, 0)
    # consecutive query blocks of one (b, kv) that start on the block
    # the last one ended on reuse it: the pipeline copies it once
    reused = np.sum(lo[1:] == hi[:-1])
    return TilePlan(
        block_q=bq, block_k=bk, grid=(B, KV, nq, nk), window=window,
        live=int(B * KV * per_block.sum()),
        fetched=int(B * KV * (per_block.sum() - reused)),
        vmem_bytes=_vmem_bytes(G * bq, bk, d, itemsize))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, seq_q: int, seq_k: int, causal: bool,
                  window: Optional[int], n_kblocks: int):
    G, bq, d = q_ref.shape
    bk = k_ref.shape[0]
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    lo, hi = _visible(q_start, bq, bk, n_kblocks, seq_q, causal, window)
    run = jnp.logical_and(ik >= lo, ik <= hi)

    # a tile needs the mask where some pair in it is not visible:
    edges = []
    if causal:                     # its last key lies past its first query
        edges.append(k_start + bk - 1 > q_start)
    if window is not None:         # its first key is out of its last query's window
        edges.append(k_start <= q_start + bq - 1 - window)
    ragged = seq_k % bk != 0
    if ragged:                     # its last rows lie past T
        edges.append(ik == n_kblocks - 1)

    def body(masked: bool):
        q = q_ref[...].reshape(G * bq, d)
        k = k_ref[...]                                     # (bk, d)
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = kpos < seq_k
            if causal:
                ok = jnp.logical_and(ok, kpos <= qpos)
            if window is not None:
                ok = jnp.logical_and(ok, kpos > qpos - window)
            s = jnp.where(ok[None], s.reshape(G, bq, bk), NEG_INF)
            s = s.reshape(G * bq, bk)
            if ragged:
                # rows past the end of a partial KV tile hold unspecified data
                krow = k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                v = jnp.where(krow < seq_k, v, jnp.zeros_like(v))

        m_prev = m_scr[...]                                # (G*bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # two bf16 terms keep about 16 bits of each weight (module doc)
        p_hi = p.astype(v.dtype)
        p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
        pv = functools.partial(jax.lax.dot_general,
                               dimension_numbers=(((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv(p_hi, v) + pv(p_lo, v)
        m_scr[...] = m_cur

    if edges:
        edge = functools.reduce(jnp.logical_or, edges)

        @pl.when(jnp.logical_and(run, edge))
        def _edge():
            body(masked=True)

        run = jnp.logical_and(run, jnp.logical_not(edge))

    @pl.when(run)
    def _interior():
        body(masked=False)

    @pl.when(ik == n_kblocks - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype).reshape(
            G, bq, d)


def _flash_forward(q, k, v, causal, window, scale, block_q, block_k,
                   interpret):
    B, S, H, d = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale_v = float(scale) if scale is not None else d ** -0.5
    plan = _tile_plan(B, S, T, H, KV, d, window, causal,
                      itemsize=q.dtype.itemsize, block_q=block_q,
                      block_k=block_k)
    bq, bk, window = plan.block_q, plan.block_k, plan.window
    nk = plan.grid[3]

    kernel = functools.partial(
        _flash_kernel, scale=scale_v, seq_q=S, seq_k=T, causal=causal,
        window=window, n_kblocks=nk)

    def kv_block(b, h, iq, ik):
        lo, hi = _visible(iq * bq, bq, bk, nk, S, causal, window)
        return b, h, jnp.clip(ik, lo, hi), 0

    qt = q.transpose(0, 2, 1, 3).reshape(B, KV, G, S, d)   # free reshape
    kt = k.transpose(0, 2, 1, 3)                           # (B, KV, T, d)
    vt = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=plan.grid,
        in_specs=[
            pl.BlockSpec((None, None, G, bq, d),
                         lambda b, h, iq, ik: (b, h, 0, iq, 0)),
            pl.BlockSpec((None, None, bk, d), kv_block),
            pl.BlockSpec((None, None, bk, d), kv_block),
        ],
        out_specs=pl.BlockSpec((None, None, G, bq, d),
                               lambda b, h, iq, ik: (b, h, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, 1), jnp.float32),      # running max
            pltpu.VMEM((G * bq, 1), jnp.float32),      # running sum
            pltpu.VMEM((G * bq, d), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, S, d).transpose(0, 2, 1, 3)


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
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, d); k/v: (B, T, KV, d) with H % KV == 0 → (B, S, H, d).
    Blocks follow ``_tile_plan``; ``block_q``/``block_k`` override it
    (tests). Differentiable: the backward is the reference's VJP (module
    doc)."""
    return _flash(q, k, v, causal, window, scale, block_q, block_k,
                  interpret)
