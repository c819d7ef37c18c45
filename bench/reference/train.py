"""Plain float32 reference of a training run's first steps: the mean
next-token cross-entropy, its gradient, global-norm clipping, the
warmup-cosine learning rate and AdamW, followed for a few steps from
the seeded weights on the batches the program was fed.

AdamW's moments stay on the host and are updated on the device one leaf
at a time, so that float32 weights, gradients and moments of a model
the chip holds in bfloat16 never sit on the device together.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .common import F32, Numerics


def loss(w: dict, tokens, labels, a: dict, ref, num: Numerics) -> jnp.ndarray:
    x = ref.hidden(w, tokens, a, num, remat=True)
    lg = ref.head(w, x, a, num)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


FLOOR = 0.1      # the program's schedule decays to a fixed 0.1 of the peak


def learning_rate(step: int, s: dict) -> float:
    """Linear warmup, then cosine decay to ``FLOOR`` of the peak."""
    peak, warm, total = s["peak_lr"], s["warmup"], s["total"]
    if step < warm:
        return peak * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (FLOOR + (1 - FLOOR) * 0.5 * (1 + math.cos(math.pi * frac)))


@jax.jit
def _adamw_leaf(p, g, m, v, hp):
    """One leaf's AdamW step; ``hp`` = (scale, lr, b1, b2, eps, decay, c1, c2)."""
    scale, lr, b1, b2, eps, decay, c1, c2 = (hp[i] for i in range(8))
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p * (1 - lr * decay) - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
    return p, m, v


def leaf_norms(tree: Dict[str, jnp.ndarray]) -> Dict[str, float]:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))) for k, v in tree.items()}


def follow(flat: Dict[str, jnp.ndarray], batches: Sequence[dict], a: dict, ref,
           opt: dict, sched: dict, num: Numerics = Numerics()) -> dict:
    """Train from ``flat`` (path -> weights as served) on ``batches``
    (tokens/labels as numpy). Returns each step's loss, the per-leaf
    norms of the first gradient as the optimizer gets it (clipped), and
    the per-leaf norms of the weights' change over all the steps."""
    w = {k: v.astype(F32) for k, v in flat.items()}
    w0 = {k: np.asarray(v) for k, v in w.items()}
    moments = {k: (np.zeros(v.shape, np.float32), np.zeros(v.shape, np.float32))
               for k, v in w0.items()}
    grad_fn = jax.jit(jax.value_and_grad(
        lambda w_, t, l_: loss(w_, t, l_, a, ref, num)))
    losses: List[float] = []
    grad1 = None
    for step, batch in enumerate(batches):
        value, grads = grad_fn(w, jnp.asarray(batch["tokens"]), jnp.asarray(batch["labels"]))
        losses.append(float(value))
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(g))) for g in grads.values()))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
        if step == 0:
            grad1 = {k: n * scale for k, n in leaf_norms(grads).items()}
        count = step + 1
        lr = learning_rate(step, sched)
        for k in list(w):
            decay = opt["weight_decay"] if w[k].ndim >= 2 else 0.0
            hp = jnp.asarray([scale, lr, opt["b1"], opt["b2"], opt["eps"], decay,
                              1 - opt["b1"] ** count, 1 - opt["b2"] ** count], F32)
            m, v = moments[k]
            w[k], m, v = _adamw_leaf(w[k], grads.pop(k), jnp.asarray(m), jnp.asarray(v), hp)
            moments[k] = (np.asarray(m), np.asarray(v))
        del grads
    change = {k: float(np.sqrt(np.sum(np.square(np.asarray(w[k]) - w0[k])))) for k in w}
    return {"losses": losses, "grad1": grad1, "change": change}
