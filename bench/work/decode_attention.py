"""Work of one decode step's attention for one layer: the query against
each live cache entry (QK^T and PV), every live K and V entry read once,
the query read and the output written."""
from __future__ import annotations


def work(batch: int, live: int, heads: int, kv_heads: int, head_dim: int,
         itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one call with ``live`` cache entries per sequence."""
    flops = 4.0 * batch * heads * head_dim * live
    moved = itemsize * batch * head_dim * (2 * live * kv_heads + 2 * heads)
    return flops, float(moved)


def live_entries(prompt_len: int, gen_len: int, window: int | None) -> list[int]:
    """Live entries at each decode step of a request: the token decoded
    at position p attends to positions (p - window, p]."""
    cap = window or (prompt_len + gen_len)
    return [min(p + 1, cap) for p in range(prompt_len, prompt_len + gen_len - 1)]
