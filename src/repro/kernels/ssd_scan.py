"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060, `ssd_minimal`):

* grid = (batch, heads, chunks); the chunk axis is innermost and
  sequential — the (P, N) recurrent state lives in VMEM scratch and is
  carried across chunk steps (h_{c+1} = decay_c · h_c + states_c).
* per (head, chunk) tile the kernel computes the quadratic *dual form*
  intra-chunk (an (L, L) masked "attention" matmul — MXU work), plus
  the rank-1 inter-chunk contribution from the carried state.
* The wrapper puts heads ahead of the sequence — x (B, H, S, P), b/c
  (B, G, S, N) — so every block is (L, P) or (L, N): rows a multiple of
  8, columns the full dim, which Mosaic tiles. The within-chunk cumulative
  log-decay is summed in the wrapper (exact f32) and arrives as a
  (1, L) row; the kernel takes its column form through a diagonal
  select, so no in-kernel scan or transpose is needed.
* Per-head tiling keeps VMEM small: x tile (L, P), b/c tiles (L, N),
  the (L, L) decay matrix, and the f32 (P, N) state — ~0.5 MB at
  L=256, P=64, N=128.
* GQA-style B/C groups index as ``h // (H // G)`` in the BlockSpec maps.
* The backward is the VJP of ``ref.ssd_scan_ref``, recomputed from the
  saved inputs — a ``jax.custom_vjp`` around the forward kernel.

Outputs y (B, S, H, P) and the final state (B, H, P, N) — the latter
seeds the O(1) recurrent decode path.

Oracle: ``repro.models.ssm.ssd_chunked`` (pure jnp).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref


def _ssd_kernel(x_ref, acum_ref, b_ref, c_ref, y_ref, hfin_ref, state_scr, *,
                chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)                  # (L, P)
    b = b_ref[...].astype(jnp.float32)                  # (L, N)
    c = c_ref[...].astype(jnp.float32)                  # (L, N)
    a_row = acum_ref[...]                               # (1, L) cumsum a
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    a_rows = jnp.broadcast_to(a_row, (chunk, chunk))    # [i, j] = a_cum[j]
    a_col = jnp.sum(jnp.where(row == col, a_rows, 0.0), axis=1,
                    keepdims=True)                      # (L, 1) a_cum[i]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    a_last = jnp.sum(jnp.where(lane == chunk - 1, a_row, 0.0), axis=1,
                     keepdims=True)                     # (1, 1) a_cum[L-1]

    # intra-chunk dual form: masked decay "attention"
    lmat = jnp.where(col <= row, jnp.exp(a_col - a_rows), 0.0)   # (L, L)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (L, L)
    y_diag = jax.lax.dot_general(lmat * cb, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state + state update
    state = state_scr[...]                              # (P, N)
    y_off = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * jnp.exp(a_col)                                # (L, P)
    decay_states = jnp.exp(a_last - a_col)              # (L, 1)
    states_new = jax.lax.dot_general(
        x * decay_states, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (P, N)
    state_scr[...] = jnp.exp(a_last) * state + states_new

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        hfin_ref[...] = state_scr[...]


def _ssd_forward(x, a_log, b, c, chunk, interpret):
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    L = min(chunk, S)
    assert S % L == 0, f"seq {S} % chunk {L} != 0"
    nc = S // L

    a_cum = jnp.cumsum(a_log.astype(jnp.float32).reshape(B, nc, L, H),
                       axis=2)                          # within-chunk
    a_cum = a_cum.reshape(B, S, H).transpose(0, 2, 1)[:, :, None, :]
    kernel = functools.partial(_ssd_kernel, chunk=L, n_chunks=nc)
    y, hfin = pl.pallas_call(
        kernel,
        name="ssd_scan",
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, L, P), lambda bi, h, ic: (bi, h, ic, 0)),
            pl.BlockSpec((None, None, 1, L), lambda bi, h, ic: (bi, h, 0, ic)),
            pl.BlockSpec((None, None, L, N),
                         lambda bi, h, ic: (bi, h // rep, ic, 0)),
            pl.BlockSpec((None, None, L, N),
                         lambda bi, h, ic: (bi, h // rep, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, L, P), lambda bi, h, ic: (bi, h, ic, 0)),
            pl.BlockSpec((None, None, P, N), lambda bi, h, ic: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), a_cum, b.transpose(0, 2, 1, 3),
      c.transpose(0, 2, 1, 3))
    return y.transpose(0, 2, 1, 3), hfin


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(x, a_log, b, c, chunk, interpret):
    return _ssd_forward(x, a_log, b, c, chunk, interpret)


def _ssd_fwd(x, a_log, b, c, chunk, interpret):
    return _ssd_forward(x, a_log, b, c, chunk, interpret), (x, a_log, b, c)


def _ssd_bwd(chunk, interpret, res, g):
    x, a_log, b, c = res
    _, vjp = jax.vjp(functools.partial(ref.ssd_scan_ref,
                                       chunk=min(chunk, x.shape[1])),
                     x, a_log, b, c)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, a_log: jnp.ndarray, b: jnp.ndarray,
             c: jnp.ndarray, *, chunk: int = 256,
             interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, H, P) pre-scaled by dt; a_log: (B, S, H); b/c: (B, S, G, N).
    Returns (y (B, S, H, P), final_state (B, H, P, N) f32). Differentiable:
    the backward is the reference's VJP (module doc)."""
    return _ssd(x, a_log, b, c, chunk, interpret)
