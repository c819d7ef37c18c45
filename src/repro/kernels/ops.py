"""Public kernel entry points with backend dispatch.

On a TPU backend every entry point runs its compiled Pallas kernel;
on any other backend it runs the pure-jnp reference from ``ref.py``.
There is no switch between the two: interpret mode exists only as the
``interpret=`` argument that tests pass to the kernel modules directly.

The scans fall back to their references only for shapes their blocks
cannot tile (a sequence that does not divide into chunks, or a chunk
that is neither the whole sequence nor a multiple of 128 lanes).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .rglru_scan import rglru_scan as _rglru_pallas
from .ssd_scan import ssd_scan as _ssd_pallas


def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _tiles(block: int, full: int, multiple: int) -> bool:
    """A block dim Mosaic can tile: the whole dim, or a divisor of it
    that is a multiple of the hardware tile."""
    return block == full or (full % block == 0 and block % multiple == 0)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None) -> jnp.ndarray:
    if use_pallas():
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             scale=scale)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   scale=scale)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    if use_pallas():
        return _decode_pallas(q, k_cache, v_cache, cache_len, window=window,
                              scale=scale)
    return ref.decode_attention_ref(q, k_cache, v_cache, cache_len,
                                    window=window, scale=scale)


def ssd_scan(x, a_log, b, c, *, chunk: int = 256
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    S = x.shape[1]
    chunk = min(chunk, S)
    if use_pallas() and _tiles(chunk, S, 128):
        return _ssd_pallas(x, a_log, b, c, chunk=chunk)
    return ref.ssd_scan_ref(x, a_log, b, c, chunk=chunk)


def rglru_scan(a_log, b, *, block_t: int = 256
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    _, S, W = a_log.shape
    bt, bw = min(block_t, S), min(512, W)
    if use_pallas() and _tiles(bt, S, 8) and _tiles(bw, W, 128):
        return _rglru_pallas(a_log, b, block_t=bt, block_w=bw)
    return ref.rglru_scan_ref(a_log, b)
