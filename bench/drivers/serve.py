"""Serving traffic as a closed loop of fixed batches.

The program serves one batch at one shared position (no admission, no
per-sequence lengths), so the load is B clients that each wait for
their reply: every round draws B fresh prompts from the seed, prefills
them together, decodes ``gen_len - 1`` more tokens, and the next round
starts when this one ends. The loop has the shape of
``repro.launch.serve.serve``: the program's own jitted prefill and decode
steps (``make_prefill_step`` / ``make_serve_step``, greedy, the token fed
back on the device). Each token is fetched to the host as it is
produced, as a streaming server would, and timestamped there. Up to
``in_flight`` decode steps of the round are sent ahead of the token
being fetched, so that the chip stays fed while the host stands still;
a round's replies end it, so nothing is sent past them.

Traffic file keys: ``batch``, ``prompt_len``, ``gen_len``, ``in_flight``,
``trace_rounds`` (rounds in a traced window), and ``check``:
``requests`` (how many finished requests the reference compares) and
``ref_rows`` (rows per reference call).
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights
from ..harness import Cell, reference
from ..reference.common import Numerics
from . import program


class Driver:
    def __init__(self, cell: Cell, seed: int):
        t = cell.traffic
        self.cell, self.seed = cell, int(seed)
        self.B, self.P, self.G = t["batch"], t["prompt_len"], t["gen_len"]
        self.ahead = t["in_flight"]
        self.vocab = cell.arch["vocab_size"]
        self.finished: Dict[int, np.ndarray] = {}      # round -> served (B, G)
        self.next_round = 0

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        cfg = program.arch_config(self.cell.arch)
        steps = program.steps()
        model, prefill_step = steps.make_prefill_step(cfg)
        _, serve_step = steps.make_serve_step(cfg)
        self.params = program.params(model, self.cell, self.seed)
        B, P, G = self.B, self.P, self.G
        self._new_cache = jax.jit(lambda: model.init_cache(B, P + G)).lower().compile()
        cache = jax.eval_shape(lambda: model.init_cache(B, P + G))
        tokens = jax.ShapeDtypeStruct((B, P), jnp.int32)
        token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        self._prefill = jax.jit(prefill_step, donate_argnums=(2,)).lower(
            self.params, tokens, cache, {}).compile()
        self._decode = jax.jit(serve_step, donate_argnums=(2,)).lower(
            self.params, token, cache, pos).compile()
        self._pos = [jax.device_put(np.full((B,), P + i, np.int32))
                     for i in range(G - 1)]
        self._round(warm=True)                    # every shape, once
        self.finished.clear()

    def prompts(self, rnd: int | None) -> np.ndarray:
        """Round ``rnd``'s prompts (``None``: the warm-up round's)."""
        rng = np.random.default_rng([self.seed, 1] if rnd is None else [self.seed, 0, rnd])
        return rng.integers(0, self.vocab, (self.B, self.P), dtype=np.int32)

    # -- one round ------------------------------------------------------------
    def _round(self, warm: bool = False):
        rnd = None if warm else self.next_round
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round"):
            with jax.profiler.TraceAnnotation("bench.prompts"):
                toks = jax.device_put(self.prompts(rnd))
                cache = self._new_cache()
            with jax.profiler.TraceAnnotation("bench.prefill"):
                tok, cache = self._prefill(self.params, toks, cache, {})
            sent = [tok]                  # the round's tokens on the device
            served, stamps = [], []
            for i in range(self.G):
                while len(sent) < min(self.G, i + 1 + self.ahead):
                    with jax.profiler.TraceAnnotation("bench.decode"):
                        nxt, cache = self._decode(self.params, sent[-1], cache,
                                                  self._pos[len(sent) - 1])
                    sent.append(nxt)
                with jax.profiler.TraceAnnotation("bench.fetch"):
                    served.append(np.asarray(sent[i])[:, 0])
                stamps.append(time.perf_counter())
                sent[i] = None
        del cache
        if not warm:
            self.finished[rnd] = np.stack(served, axis=1)
            self.next_round += 1
        return stamps[0] - t0, list(np.diff(stamps)), stamps[-1]

    # -- the window -------------------------------------------------------------
    def window(self, seconds: float = 0.0, rounds: int = 0) -> Dict[str, Any]:
        """Rounds start until ``seconds`` have passed (or ``rounds`` are
        done); the window closes when the last one finishes."""
        ttft: List[float] = []
        itl: List[float] = []
        t0 = time.perf_counter()
        end = t0
        n = 0
        while (rounds and n < rounds) or (not rounds and end - t0 < seconds):
            first, gaps, end = self._round()
            ttft.append(first)
            itl.extend(gaps)
            n += 1
        return {"window_s": end - t0, "rounds": n, "requests": n * self.B,
                "tokens": n * self.B * self.G, "ttft_s": ttft, "itl_s": itl}

    def traced_rounds(self) -> int:
        return self.cell.traffic["trace_rounds"]

    @staticmethod
    def end_to_end(w: Dict[str, Any]) -> Dict[str, float]:
        return {"ttft_p50_ms": float(np.percentile(w["ttft_s"], 50)) * 1e3,
                "itl_p95_ms": float(np.percentile(w["itl_s"], 95)) * 1e3,
                "out_tok_per_s": w["tokens"] / w["window_s"]}

    def release(self) -> None:
        for name in ("params", "_prefill", "_decode", "_new_cache", "_pos"):
            self.__dict__.pop(name, None)
        gc.collect()

    # -- the check -----------------------------------------------------------------
    def sample(self) -> List[tuple]:
        """Finished requests (round, slot) the reference compares, drawn
        from the seed; every request has the same (longest) length."""
        done = [(r, s) for r in sorted(self.finished) for s in range(self.B)]
        n = min(self.cell.traffic["check"]["requests"], len(done))
        pick = np.random.default_rng([self.seed, 2]).choice(len(done), n, replace=False)
        return [done[i] for i in sorted(pick)]

    def check(self, control: bool = False) -> Dict[str, float]:
        """``served_logit_gap``: the widest gap by which a served token's
        reference logit lies below the reference's best at its position.
        With ``control``, also ``control_served_logit_gap``: the same for the
        token that the int8 reference puts first; and for both, the mean
        gap (``*_mean``) and the share of positions whose token is not the
        reference's best (``*_flips``), which the study reads beside it."""
        ref = reference(self.cell.config["reference"])
        a = self.cell.arch
        flat = weights.make(ref.param_spec(a), self.seed)
        picked = self.sample()
        rows = self.cell.traffic["check"]["ref_rows"]
        P = self.P

        def f32_logits(w, seq):
            return ref.logits(w, seq, a, Numerics(), first=P - 1)

        def int8_logits(w, seq):
            return ref.logits(w, seq, a, Numerics(int8=True), first=P - 1)

        f32_fn = jax.jit(f32_logits)
        int8_fn = jax.jit(int8_logits) if control else None
        gaps: Dict[str, List[np.ndarray]] = {"served_logit_gap": [],
                                             "control_served_logit_gap": []}
        for i in range(0, len(picked), rows):
            part = picked[i:i + rows]
            prompts = np.stack([self.prompts(r)[s] for r, s in part])
            served = np.stack([self.finished[r][s] for r, s in part])
            seq = jnp.asarray(np.concatenate([prompts, served[:, :-1]], axis=1))
            lg = f32_fn(flat, seq)                                  # (rows, G, V)
            best = jnp.max(lg, axis=-1)
            got = jnp.take_along_axis(lg, jnp.asarray(served)[..., None], -1)[..., 0]
            gaps["served_logit_gap"].append(np.asarray(best - got).ravel())
            if control:
                pick = jnp.argmax(int8_fn(flat, seq), axis=-1)
                got = jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
                gaps["control_served_logit_gap"].append(np.asarray(best - got).ravel())
        out = {}
        for name, parts in gaps.items():
            if not parts:
                continue
            g = np.concatenate(parts)
            out[name] = float(g.max())
            if control:
                out[f"{name}_mean"] = float(g.mean())
                out[f"{name}_flips"] = float(np.mean(g > 0))
        return out
