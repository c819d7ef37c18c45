"""Work of causal (optionally sliding-window) self-attention over a
prompt: the QK^T and PV products of every visible (query, key) pair, and
q, k, v and the output each moved once. Counted from shapes, whatever
computes it."""
from __future__ import annotations


def visible_pairs(s: int, window: int | None) -> int:
    """Pairs (i, j) with j <= i < s and i - j < window."""
    w = s if window is None else min(window, s)
    return w * (w + 1) // 2 + (s - w) * w


def work(batch: int, seq: int, heads: int, kv_heads: int, head_dim: int,
         window: int | None, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one call."""
    flops = 4.0 * batch * heads * head_dim * visible_pairs(seq, window)
    moved = itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return flops, float(moved)
