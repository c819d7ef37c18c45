"""Work of Mamba-2's chunked SSD scan over a prompt (arXiv:2405.21060,
section 6): per head and chunk of L steps, C B^T (L x L x N) and its
product with x (L x L x P), the read-out of the carried state (L x N x P)
and the chunk's state update (N x L x P); x, B, C and y moved once,
the per-step log decay read once and the final state written once."""
from __future__ import annotations


def work(batch: int, seq: int, heads: int, head_dim: int, groups: int,
         state: int, chunk: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one call."""
    chunk = min(chunk, seq)
    per_chunk = 2.0 * chunk * chunk * (state + head_dim) + 4.0 * chunk * state * head_dim
    flops = batch * heads * (seq // chunk) * per_chunk
    moved = (itemsize * batch * seq * (2 * heads * head_dim + 2 * groups * state)
             + 4 * batch * seq * heads + 4 * batch * heads * head_dim * state)
    return flops, float(moved)
