"""RG-LRU linear recurrence (RecurrentGemma/Griffin) as a Pallas kernel.

Computes h_t = a_t · h_{t-1} + b_t over the sequence axis.

TPU adaptation: instead of a sequential per-step loop (VPU-hostile), a
Hillis–Steele *doubling scan* runs the recurrence in ⌈log2 L⌉ rounds of
full-width vector multiplies on an (L, W) tile:

    (A, h) ← (A · shift(A, k), h + A · shift(h, k)),  k = 1, 2, 4, ...

after which A_t = Π_{s≤t} a_s and h_t is the in-block scan. The shift
is a sublane rotation (``pltpu.roll``) whose k wrapped-around rows are
replaced by the identity (0 for h, 1 for A), so the body has no slices
at traced offsets. The carried cross-block state enters as
``h_t += A_t · h_block_in``.

* grid = (batch, W tiles, T blocks); T innermost/sequential, the
  (1, Wb) f32 state carried in VMEM scratch.
* a is passed in log space (a = exp(a_log), a_log ≤ 0) exactly like the
  model's ``linear_scan`` oracle; b is the gated input.
* The backward is the VJP of ``ref.rglru_scan_ref``, recomputed from the
  saved inputs — a ``jax.custom_vjp`` around the forward kernel.

Oracle: ``repro.models.rglru.linear_scan``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref


def _rglru_kernel(alog_ref, b_ref, h_ref, hlast_ref, state_scr, *,
                  block_t: int, n_tblocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    acc = jnp.exp(alog_ref[...].astype(jnp.float32))      # (L, Wb)
    h = b_ref[...].astype(jnp.float32)                    # (L, Wb)
    row = jax.lax.broadcasted_iota(jnp.int32, h.shape, 0)
    k = 1
    while k < block_t:                                    # Hillis–Steele
        head = row < k                                    # wrapped rows
        prev_h = jnp.where(head, 0.0, pltpu.roll(h, k, 0))      # additive id
        prev_a = jnp.where(head, 1.0, pltpu.roll(acc, k, 0))    # mult. id
        h = h + acc * prev_h
        acc = acc * prev_a
        k *= 2
    # inject the carried state: h_t += (Π_{s≤t} a_s) · h_in
    h = h + acc * state_scr[...]
    last = h[block_t - 1:block_t, :]                      # (1, Wb)
    state_scr[...] = last
    h_ref[...] = h.astype(h_ref.dtype)

    @pl.when(it == n_tblocks - 1)
    def _emit():
        hlast_ref[...] = last


def _rglru_forward(a_log, b, block_t, block_w, interpret):
    B, S, W = a_log.shape
    bt = min(block_t, S)
    bw = min(block_w, W)
    assert S % bt == 0 and W % bw == 0
    nt, nw = S // bt, W // bw

    kernel = functools.partial(_rglru_kernel, block_t=bt, n_tblocks=nt)
    h, h_last = pl.pallas_call(
        kernel,
        name="rglru_scan",
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((None, bt, bw), lambda bi, iw, it: (bi, it, iw)),
            pl.BlockSpec((None, bt, bw), lambda bi, iw, it: (bi, it, iw)),
        ],
        out_specs=[
            pl.BlockSpec((None, bt, bw), lambda bi, iw, it: (bi, it, iw)),
            pl.BlockSpec((None, 1, bw), lambda bi, iw, it: (bi, 0, iw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_log, b)
    return h, h_last[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rglru(a_log, b, block_t, block_w, interpret):
    return _rglru_forward(a_log, b, block_t, block_w, interpret)


def _rglru_fwd(a_log, b, block_t, block_w, interpret):
    return _rglru_forward(a_log, b, block_t, block_w, interpret), (a_log, b)


def _rglru_bwd(block_t, block_w, interpret, res, g):
    _, vjp = jax.vjp(ref.rglru_scan_ref, *res)
    return vjp(g)


_rglru.defvjp(_rglru_fwd, _rglru_bwd)


@functools.partial(jax.jit, static_argnames=("block_t", "block_w", "interpret"))
def rglru_scan(a_log: jnp.ndarray, b: jnp.ndarray, *, block_t: int = 256,
               block_w: int = 512,
               interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """a_log, b: (B, S, W) → (h (B, S, W) f32, h_last (B, W) f32).
    Differentiable: the backward is the reference's VJP (module doc)."""
    return _rglru(a_log, b, block_t, block_w, interpret)
