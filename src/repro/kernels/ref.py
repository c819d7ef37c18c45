"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

These re-export the model-zoo reference implementations so kernels and
models are validated against a single source of truth.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from ..models.attention import (chunked_attention_ref, decode_attention_ref,
                                gqa_attention)
from ..models.rglru import linear_scan
from ..models.ssm import ssd_chunked as ssd_scan_ref


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None,
                        q_chunk: Optional[int] = None) -> jnp.ndarray:
    """``q_chunk`` bounds score memory at (B, H, q_chunk, T) — same result."""
    if q_chunk:
        return chunked_attention_ref(q, k, v, causal=causal, window=window,
                                     q_chunk=q_chunk, scale=scale)
    return gqa_attention(q, k, v, causal=causal, window=window, scale=scale)


def rglru_scan_ref(a_log: jnp.ndarray, b: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Matches kernels.rglru_scan's (a_log, b) interface: the model
    oracle's recurrence on the already-gated input b."""
    return linear_scan(a_log, b)


__all__ = ["flash_attention_ref", "decode_attention_ref", "ssd_scan_ref",
           "rglru_scan_ref", "gqa_attention"]
