"""Training traffic: the program's jitted train step (``make_train_step``:
forward and backward with remat, global-norm clipping, AdamW) fed by its
own ``TokenPipeline``, in a loop of the shape of
``repro.launch.train.train``, with each step's loss read on the host
``in_flight`` steps after the step was sent, so that the chip stays fed
while the host stands still. The pipeline reads rows at random offsets
of a token corpus (its memmapped ``corpus_path`` mode, as fine-tuning
on a tokenized corpus does); the corpus is drawn from the seed into a
file under ``$TMPDIR``.

Set-up builds the one compiled step with its state and drives it from
the seed through its first ``check.steps`` steps, through the window's
own call and feed; the window then continues with the same object. What
those steps did is read as they happen (each loss, the first gradient
from AdamW's first moment, the weights' change) and compared with the
plain reference once the window has closed.

Traffic file keys: ``batch``, ``seq_len``, ``corpus_tokens``, ``remat``, ``optimizer``
(AdamW's b1, b2, eps, weight_decay, clip_norm), ``schedule`` (peak_lr,
warmup, total; the program's cosine decays to a fixed 0.1 of the peak),
``in_flight`` (steps sent ahead of the one whose loss is read),
``trace_steps`` (steps in a traced window), ``check.steps``.
"""
from __future__ import annotations

import collections
import gc
import os
import tempfile
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import DataConfig, TokenPipeline
from repro.optim import AdamWConfig, adamw_init

from .. import weights
from ..harness import Cell, reference
from ..reference import train as ref_train
from ..reference.common import Numerics
from . import program


def _leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {weights.path_str(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


class Driver:
    def __init__(self, cell: Cell, seed: int):
        self.cell, self.seed = cell, int(seed)
        self.t = cell.traffic
        self.step = 0
        self.seen: Dict[str, Any] = {"losses": [], "batches": []}

    def setup(self) -> None:
        t, a = self.t, self.cell.arch
        cfg = program.arch_config(a)
        opt_cfg = AdamWConfig(**t["optimizer"])
        s = t["schedule"]
        model, train_step = program.steps().make_train_step(
            cfg, peak_lr=s["peak_lr"], warmup=s["warmup"], total=s["total"],
            remat=t["remat"], opt=opt_cfg)
        fd, self.corpus = tempfile.mkstemp(prefix="bench-corpus-", suffix=".bin")
        with os.fdopen(fd, "wb") as f:
            rng = np.random.default_rng([self.seed, 3])
            rng.integers(0, a["vocab_size"], t["corpus_tokens"], dtype=np.uint16).tofile(f)
        self.pipe = TokenPipeline(DataConfig(
            vocab_size=a["vocab_size"], seq_len=t["seq_len"], global_batch=t["batch"],
            seed=self.seed, corpus_path=self.corpus))
        self.params = program.params(model, self.cell, self.seed)
        self.opt = jax.jit(adamw_init)(self.params)
        batch = next(self.pipe)
        self._step = jax.jit(train_step, donate_argnums=(0, 1)).lower(
            self.params, self.opt, batch, jnp.asarray(0)).compile()
        norms = jax.jit(_leaf_norms)
        b1 = t["optimizer"]["b1"]
        for i in range(t["check"]["steps"]):
            if i:
                batch = next(self.pipe)
            self.seen["batches"].append({k: np.asarray(v) for k, v in batch.items()})
            self._one(batch)
            if i == 0:       # AdamW's first moment after one step is (1 - b1) g
                self.seen["grad1"] = {k: float(v) / (1 - b1)
                                      for k, v in norms(self.opt["m"]).items()}
        start = program.params(model, self.cell, self.seed)
        moved = jax.jit(lambda p, q: _leaf_norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), p, q)))
        self.seen["change"] = {k: float(v) for k, v in moved(self.params, start).items()}
        del start

    def _one(self, batch) -> None:
        self._read(self._send(batch))

    def _send(self, batch) -> tuple:
        with jax.profiler.TraceAnnotation("bench.step"):
            self.params, self.opt, out = self._step(
                self.params, self.opt, batch, jnp.asarray(self.step))
        self.step += 1
        return self.step - 1, out["loss"]

    def _read(self, sent: tuple) -> None:
        step, loss = sent
        with jax.profiler.TraceAnnotation("bench.loss"):
            loss = float(loss)
        if step < self.t["check"]["steps"]:
            self.seen["losses"].append(loss)

    def window(self, seconds: float = 0.0, rounds: int = 0) -> Dict[str, Any]:
        """Steps are sent until ``seconds`` have passed (or ``rounds``
        steps are sent), each loss read ``in_flight`` steps late. Then
        nothing more is sent, and the window closes once every step sent
        has finished: all of them count, over all of that time."""
        pending: collections.deque = collections.deque()
        t0 = time.perf_counter()
        n = 0
        while (rounds and n < rounds) or (not rounds and time.perf_counter() - t0 < seconds):
            with jax.profiler.TraceAnnotation("bench.batch"):
                batch = next(self.pipe)
            pending.append(self._send(batch))
            n += 1
            if len(pending) > self.t["in_flight"]:
                self._read(pending.popleft())
        while pending:
            self._read(pending.popleft())
        return {"window_s": time.perf_counter() - t0, "steps": n}

    def traced_rounds(self) -> int:
        return self.t["trace_steps"]

    @staticmethod
    def end_to_end(w: Dict[str, Any]) -> Dict[str, float]:
        return {"train_step_ms": w["window_s"] / w["steps"] * 1e3}

    def release(self) -> None:
        self.pipe.close()
        os.unlink(self.corpus)
        for name in ("params", "opt", "_step"):
            self.__dict__.pop(name, None)
        gc.collect()

    # -- the check -------------------------------------------------------------
    def follow_reference(self, num: Numerics) -> dict:
        ref = reference(self.cell.config["reference"])
        flat = weights.make(ref.param_spec(self.cell.arch), self.seed)
        return ref_train.follow(flat, self.seen["batches"], self.cell.arch, ref,
                                self.t["optimizer"], self.t["schedule"], num)

    def check(self, control: bool = False) -> Dict[str, float]:
        """``first_loss_gap``: the relative gap of the first step's loss
        (the later steps' losses swing with the rounding of the updates
        to bfloat16 weights; their work is in the change);
        ``grad_norm_gap`` / ``change_norm_gap``: the worst leaf's gap
        between the program's and the reference's norm of the first
        clipped gradient / of the weights' change, over the larger of
        that leaf's and the median leaf's reference norm. Leaves whose
        reference gradient is under a thousandth of the median leaf's
        move by round-off alone and are left out of the change. With
        ``control``, the int8 reference's gaps as ``control_*``."""
        want = self.follow_reference(Numerics())
        out = compare(self.seen, want)
        self.losses = {"program": self.seen["losses"], "reference": want["losses"]}
        if control:
            lower = self.follow_reference(Numerics(int8=True))
            self.losses["control"] = lower["losses"]
            out.update({f"control_{k}": v for k, v in compare(lower, want).items()})
        return out


def compare(got: dict, want: dict) -> Dict[str, float]:
    g, w = got["losses"], want["losses"]
    first_loss_gap = abs(g[0] - w[0]) / abs(w[0]) if len(g) == len(w) else float("inf")
    g_med = float(np.median(list(want["grad1"].values())))
    c_med = float(np.median(list(want["change"].values())))
    moving = [k for k, v in want["grad1"].items() if v >= 1e-3 * g_med]
    return {
        "first_loss_gap": first_loss_gap,
        "grad_norm_gap": _worst(got["grad1"], want["grad1"], list(want["grad1"]), g_med),
        "change_norm_gap": _worst(got["change"], want["change"], moving, c_med),
    }


def _worst(got: Dict[str, float], want: Dict[str, float], keys: List[str],
           median: float) -> float:
    return max(abs(got[k] - want[k]) / max(want[k], median) for k in keys)
