"""Faults planted in the program's steps, where they produce their
output, to show that the comparison catches them: a step that returns
its state unchanged, half of the batch left out, and a token or an
answer altered. One-chip cells have no exchange between chips to leave
out. ``tests/bench/test_bench_faults.py`` drives whole runs with each;
``control.py --fault`` reads them on the chip at a cell's own size.

Each fault is a stand-in for a step builder of ``repro.launch.steps``,
with its signature.
"""
from __future__ import annotations

import types

import jax.numpy as jnp


def _real():
    from repro.launch import steps
    return steps


def decode_state_unchanged(cfg):
    model, step = _real().make_serve_step(cfg)
    return model, lambda p, tok, cache, pos: (step(p, tok, cache, pos)[0], cache)


def decode_half_batch(cfg):
    """The first half of the batch's tokens stand for the whole batch."""
    model, step = _real().make_serve_step(cfg)

    def broken(p, tok, cache, pos):
        nxt, cache = step(p, tok, cache, pos)
        half = nxt.shape[0] // 2
        return jnp.concatenate([nxt[:half], nxt[:nxt.shape[0] - half]]), cache
    return model, broken


def decode_token_altered(cfg):
    model, step = _real().make_serve_step(cfg)

    def broken(p, tok, cache, pos):
        nxt, cache = step(p, tok, cache, pos)
        return (nxt + 1) % cfg.vocab_size, cache
    return model, broken


def train_state_unchanged(cfg, **kw):
    model, step = _real().make_train_step(cfg, **kw)
    return model, lambda p, o, b, s: (p, o, step(p, o, b, s)[2])


def train_half_batch(cfg, **kw):
    """The loss is the mean over half of the batch's tokens (the batch
    is one row)."""
    model, step = _real().make_train_step(cfg, **kw)

    def broken(p, o, b, s):
        n = b["labels"].shape[1]
        mask = jnp.broadcast_to((jnp.arange(n) < n // 2).astype(jnp.float32),
                                b["labels"].shape)
        return step(p, o, {**b, "mask": mask}, s)
    return model, broken


def train_loss_altered(cfg, **kw):
    """The step reports its loss 1 % high."""
    model, step = _real().make_train_step(cfg, **kw)

    def broken(p, o, b, s):
        p, o, out = step(p, o, b, s)
        return p, o, {**out, "loss": out["loss"] * 1.01}
    return model, broken


FAULTS = {
    "serve": {"state_unchanged": decode_state_unchanged, "half_batch": decode_half_batch,
              "token_altered": decode_token_altered},
    "train": {"state_unchanged": train_state_unchanged, "half_batch": train_half_batch,
              "answer_altered": train_loss_altered},
}


def steps_with(kind: str, fault: str | None):
    """A stand-in for ``repro.launch.steps`` with ``fault`` planted in
    the step that ``kind`` traffic times (``None``: unbroken)."""
    real = _real()
    shim = types.SimpleNamespace(make_prefill_step=real.make_prefill_step,
                                 make_serve_step=real.make_serve_step,
                                 make_train_step=real.make_train_step)
    if fault is not None:
        name = "make_train_step" if kind == "train" else "make_serve_step"
        setattr(shim, name, FAULTS[kind][fault])
    return shim
