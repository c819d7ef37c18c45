"""Unified decoder-only language model covering every assigned family.

One ``LM`` object per ``ArchConfig`` exposes:

    init(rng)                          → params
    apply(params, tokens, ...)        → logits          (train / eval)
    loss(params, batch)               → (scalar, aux)
    init_cache(batch, max_len)        → cache pytree
    prefill(params, tokens, cache)    → (logits, cache)
    decode(params, token, cache, pos) → (logits, cache)

Layer stacks run under ``jax.lax.scan`` with stacked parameters (compile
time at 512 devices stays flat in depth); heterogeneous-pattern models
(RecurrentGemma 2:1, Granite 4.0-H 9 SSD : 1 attention, DeepSeek
dense-first) scan over *pattern units* with the remainder unrolled; a
unit's cache holds each of its blocks' state side by side.

Every op runs under a ``jax.named_scope`` of one vocabulary, so that a
profile names the sublayer that issued each device op: ``embed``,
``layers`` (the scan and the unrolled blocks), inside it ``mixer``
(attention and MLA: ``qkv``, ``cache``, ``attention``, ``out``; SSD and
RG-LRU: ``in_proj``, ``conv``, ``scan``, ``out``) and ``mlp`` or
``moe``, then ``head`` and ``loss``; the train step adds ``optimizer``.
Scopes change op metadata only, not the compiled program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as attn_lib
from .common import apply_rope, dense_init, dtype_of, embed_init, rms_norm, split_keys
from .config import ArchConfig
from .mlp import apply_mlp, apply_moe, init_mlp, init_moe
from .rglru import apply_rglru, init_rglru, rglru_state_shape
from .sharding_utils import maybe_shard
from .ssm import (apply_mamba2, apply_mamba2_decode, init_mamba2,
                  mamba2_state_shape)


# ==============================================================================
# per-layer init
# ==============================================================================
def init_attn(key, cfg: ArchConfig, dtype) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = split_keys(key, 4)
    p = {"wq": dense_init(ks[0], (d, h, hd), dtype),
         "wk": dense_init(ks[1], (d, kv, hd), dtype),
         "wv": dense_init(ks[2], (d, kv, hd), dtype),
         "wo": dense_init(ks[3], (h, hd, d), dtype, fan_in=h * hd)}
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def init_mla(key, cfg: ArchConfig, dtype) -> Dict:
    d, h = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = split_keys(key, 7)
    return {
        "wq_a": dense_init(ks[0], (d, rq), dtype),
        "q_norm": jnp.zeros((rq,), jnp.float32),
        "wq_nope": dense_init(ks[1], (rq, h, dn), dtype, fan_in=rq),
        "wq_rope": dense_init(ks[2], (rq, h, dr), dtype, fan_in=rq),
        "wkv_a": dense_init(ks[3], (d, rkv + dr), dtype),
        "kv_norm": jnp.zeros((rkv,), jnp.float32),
        "wk_nope": dense_init(ks[4], (rkv, h, dn), dtype, fan_in=rkv),
        "wv": dense_init(ks[5], (rkv, h, dv), dtype, fan_in=rkv),
        "wo": dense_init(ks[6], (h, dv, d), dtype, fan_in=h * dv),
    }


def init_block(key, cfg: ArchConfig, kind: str, dtype) -> Dict:
    """kind ∈ {dense, moe, dense_mlp, ssm, rec, local_attn}."""
    ks = split_keys(key, 3)
    p: Dict[str, Any] = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32)}
    if kind == "ssm":
        p["mixer"] = init_mamba2(ks[0], cfg, dtype)
        if not cfg.d_ff:                  # Mamba-2 proper: no feed-forward
            return p
    elif kind == "rec":
        p["mixer"] = init_rglru(ks[0], cfg, dtype)
    elif kind in ("dense", "moe", "dense_mlp", "local_attn"):
        p["mixer"] = init_mla(ks[0], cfg, dtype) if cfg.mla \
            else init_attn(ks[0], cfg, dtype)
    p["ln2"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if kind == "moe":
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


# ==============================================================================
# per-layer apply (mode: train | prefill | decode)
# ==============================================================================
def _project_qkv(p: Dict, x: jnp.ndarray, cfg: ArchConfig,
                 positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_theta is not None:        # None: NoPE
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_attn(p: Dict, x: jnp.ndarray, cfg: ArchConfig, *, mode: str,
               cache: Optional[Dict], pos, window: Optional[int],
               prefix_len: int = 0,
               cross_kv: Optional[Tuple] = None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, S, D = x.shape
    if cross_kv is not None:          # encoder-decoder cross attention
        with jax.named_scope("qkv"):
            q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k, v = cross_kv
        with jax.named_scope("attention"):
            o = attn_lib.gqa_attention(q, k, v, causal=False)
        with jax.named_scope("out"):
            return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), None

    if mode == "decode":
        positions = pos[:, None] if pos.ndim == 1 else pos
        with jax.named_scope("qkv"):
            q, k, v = _project_qkv(p, x, cfg, positions)
        t_buf = cache["k"].shape[1]
        ring = window is not None and t_buf <= window
        with jax.named_scope("cache"):
            slot = pos % t_buf if ring else pos
            kc = _write_cache(cache["k"], k, slot)
            vc = _write_cache(cache["v"], v, slot)
        with jax.named_scope("attention"):
            if ring:
                # ring holds exactly the in-window tokens; no window re-mask
                valid = jnp.minimum(pos + 1, t_buf)
                o = attn_lib.decode_attention(q, kc, vc, valid, window=None,
                                              scale=cfg.attn_scale)
            else:
                o = attn_lib.decode_attention(q, kc, vc, pos + 1, window=window,
                                              scale=cfg.attn_scale)
        with jax.named_scope("out"):
            out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        return out, {"k": kc, "v": vc}

    with jax.named_scope("qkv"):
        positions = jnp.arange(S)[None, :]
        q, k, v = _project_qkv(p, x, cfg, positions)
    with jax.named_scope("attention"):
        if S > cfg.attn_chunk:
            o = attn_lib.gqa_attention_chunked(q, k, v, causal=True, window=window,
                                               prefix_len=prefix_len,
                                               q_chunk=cfg.attn_chunk // 4,
                                               scale=cfg.attn_scale)
        else:
            o = attn_lib.gqa_attention(q, k, v, causal=True, window=window,
                                       prefix_len=prefix_len, scale=cfg.attn_scale)
    with jax.named_scope("out"):
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    new_cache = None
    if mode == "prefill":
        with jax.named_scope("cache"):
            kc = _fit_cache(cache["k"], k)
            vc = _fit_cache(cache["v"], v)
        new_cache = {"k": kc, "v": vc}
    return out, new_cache


def _write_cache(cache: jnp.ndarray, kv: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Write (B,1,KV,hd) at per-batch position ``pos`` (uniform scalar)."""
    return jax.lax.dynamic_update_slice_in_dim(cache, kv.astype(cache.dtype),
                                               pos[0], axis=1)


def _fit_cache(cache: jnp.ndarray, kv: jnp.ndarray) -> jnp.ndarray:
    """Place prefill K/V into the cache buffer. When the prefill is longer
    than a (windowed) ring buffer, keep the last T_buf entries laid out at
    their ring slots (slot = absolute_pos % T_buf)."""
    t_buf = cache.shape[1]
    s = kv.shape[1]
    if s <= t_buf:
        return jax.lax.dynamic_update_slice_in_dim(cache, kv.astype(cache.dtype),
                                                   0, axis=1)
    last = kv[:, -t_buf:].astype(cache.dtype)
    return jnp.roll(last, s % t_buf, axis=1)


def apply_mla_block(p: Dict, x: jnp.ndarray, cfg: ArchConfig, *, mode: str,
                    cache: Optional[Dict], pos) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, S, D = x.shape
    with jax.named_scope("qkv"):
        cq = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        kv_a = x @ p["wkv_a"]
        ckv, k_rope = jnp.split(kv_a, [cfg.kv_lora_rank], axis=-1)
        ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    if mode == "decode":
        with jax.named_scope("qkv"):
            positions = pos[:, None]
            k_rope_rot = apply_rope(k_rope[:, :, None, :], positions,
                                    cfg.rope_theta)[:, :, 0]
        with jax.named_scope("cache"):
            ckv_c = _write_cache(cache["ckv"], ckv, pos)
            kr_c = _write_cache(cache["krope"], k_rope_rot, pos)
        with jax.named_scope("attention"):
            o = attn_lib.mla_decode(cq, ckv_c, kr_c, pos + 1,
                                    p["wq_nope"], p["wq_rope"], p["wk_nope"], p["wv"],
                                    rope_theta=cfg.rope_theta)
        with jax.named_scope("out"):
            out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        return out, {"ckv": ckv_c, "krope": kr_c}
    with jax.named_scope("attention"):
        o = attn_lib.mla_prefill(cq, ckv, k_rope, p["wq_nope"], p["wq_rope"],
                                 p["wk_nope"], p["wv"], rope_theta=cfg.rope_theta,
                                 q_chunk=cfg.attn_chunk // 4 if S > cfg.attn_chunk else None)
    with jax.named_scope("out"):
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    new_cache = None
    if mode == "prefill":
        with jax.named_scope("qkv"):
            positions = jnp.arange(S)[None, :]
            k_rope_rot = apply_rope(k_rope[:, :, None, :], positions,
                                    cfg.rope_theta)[:, :, 0]
        with jax.named_scope("cache"):
            new_cache = {"ckv": _fit_cache(cache["ckv"], ckv),
                         "krope": _fit_cache(cache["krope"], k_rope_rot)}
    return out, new_cache


def apply_block(p: Dict, x: jnp.ndarray, cfg: ArchConfig, kind: str, *,
                mode: str = "train", cache=None, pos=None,
                prefix_len: int = 0) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Returns (x_out, new_cache, aux_loss). The mixer and the
    feed-forward each run under a named scope (``mixer``, ``mlp`` or
    ``moe``) that covers their pre-norm and residual. An SSD block has a
    feed-forward only when ``cfg.d_ff`` is set."""
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mixer"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if kind == "ssm":
            if mode == "decode":
                y, new_cache = apply_mamba2_decode(p["mixer"], h, cfg, cache)
            else:
                y, new_cache = apply_mamba2(p["mixer"], h, cfg, None)
                new_cache = new_cache if mode == "prefill" else None
            if not cfg.d_ff:
                return x + _residual(y, cfg), new_cache, aux
        elif kind == "rec":
            y, new_cache = apply_rglru(p["mixer"], h, cfg,
                                       cache if mode == "decode" else None)
            if mode == "train":
                new_cache = None
        elif cfg.mla and kind in ("dense", "moe", "dense_mlp"):
            y, new_cache = apply_mla_block(p["mixer"], h, cfg, mode=mode,
                                           cache=cache, pos=pos)
        else:
            window = cfg.window if kind in ("dense", "moe", "dense_mlp") else cfg.window
            if kind == "local_attn":
                window = cfg.window or 2048
            y, new_cache = apply_attn(p["mixer"], h, cfg, mode=mode, cache=cache,
                                      pos=pos, window=window, prefix_len=prefix_len)
        x = x + _residual(y, cfg)
    with jax.named_scope("moe" if kind == "moe" else "mlp"):
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind == "moe":
            y2, aux = apply_moe(p["moe"], h2, cfg)
        else:
            y2 = apply_mlp(p["mlp"], h2, cfg.act)
        x = x + _residual(y2, cfg)
    x = maybe_shard(x, P(("pod", "data"), "model", None))
    return x, new_cache, aux


def _residual(y: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """A sublayer's output as it enters the residual stream."""
    return y if cfg.residual_multiplier == 1.0 else y * cfg.residual_multiplier


def _embed(params: Dict, tokens: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    x = params["embed"][tokens]
    return x if cfg.embedding_multiplier == 1.0 else x * cfg.embedding_multiplier


# ==============================================================================
# the LM
# ==============================================================================
@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ArchConfig

    # -- structure ------------------------------------------------------------
    def layer_kinds(self) -> Tuple[str, ...]:
        cfg = self.cfg
        if cfg.ssm:
            return ("ssm",) * cfg.n_layers
        if cfg.block_pattern:
            pat = cfg.block_pattern
            return tuple(pat[i % len(pat)] for i in range(cfg.n_layers))
        if cfg.n_experts:
            return ("dense_mlp",) * cfg.n_dense_layers + \
                ("moe",) * (cfg.n_layers - cfg.n_dense_layers)
        return ("dense",) * cfg.n_layers

    def scan_groups(self) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
        """(unit_pattern, n_units, tail_kinds): layers = unit×n + tail."""
        kinds = self.layer_kinds()
        cfg = self.cfg
        if cfg.block_pattern:
            u = len(cfg.block_pattern)
            n_units = cfg.n_layers // u
            return tuple(cfg.block_pattern), n_units, kinds[n_units * u:]
        if cfg.n_experts and cfg.n_dense_layers:
            nd = cfg.n_dense_layers
            return ("moe",), cfg.n_layers - nd, kinds[:nd]   # tail = leading dense
        return (kinds[0],), cfg.n_layers, ()

    # -- init -------------------------------------------------------------------
    def init(self, rng) -> Dict:
        cfg = self.cfg
        dtype = dtype_of(cfg.dtype)
        unit, n_units, tail = self.scan_groups()
        k_emb, k_stack, k_tail, k_out = jax.random.split(rng, 4)
        params: Dict[str, Any] = {
            "embed": embed_init(k_emb, (cfg.padded_vocab, cfg.d_model), dtype),
            "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = dense_init(k_out, (cfg.d_model, cfg.padded_vocab),
                                           dtype)
        def unit_init(key):
            ks = split_keys(key, len(unit))
            return {f"u{i}": init_block(ks[i], cfg, kind, dtype)
                    for i, kind in enumerate(unit)}
        params["stack"] = jax.vmap(unit_init)(
            jax.random.split(k_stack, n_units))
        if tail:
            ks = split_keys(k_tail, len(tail))
            params["tail"] = {f"t{i}": init_block(ks[i], cfg, kind, dtype)
                              for i, kind in enumerate(tail)}
        return params

    # -- caches -------------------------------------------------------------------
    def _block_cache_shape(self, kind: str, batch: int, max_len: int, dtype):
        cfg = self.cfg
        if kind == "ssm":
            return mamba2_state_shape(cfg, batch, dtype)
        if kind == "rec":
            return rglru_state_shape(cfg, batch, dtype)
        if cfg.mla:
            return {"ckv": ((batch, max_len, cfg.kv_lora_rank), dtype),
                    "krope": ((batch, max_len, cfg.qk_rope_dim), dtype)}
        cache_len = max_len
        if kind == "local_attn" or (cfg.window and not cfg.block_pattern):
            cache_len = min(max_len, (cfg.window or max_len))
        return {"k": ((batch, cache_len, cfg.n_kv_heads, cfg.hd), dtype),
                "v": ((batch, cache_len, cfg.n_kv_heads, cfg.hd), dtype)}

    def init_cache(self, batch: int, max_len: int) -> Dict:
        cfg = self.cfg
        dtype = dtype_of(cfg.dtype)
        unit, n_units, tail = self.scan_groups()
        stack_cache = {}
        for i, kind in enumerate(unit):
            sh = self._block_cache_shape(kind, batch, max_len, dtype)
            stack_cache[f"u{i}"] = jax.tree.map(
                lambda sd: jnp.zeros((n_units,) + sd[0], sd[1]), sh,
                is_leaf=_is_shape_leaf)
        cache: Dict[str, Any] = {"stack": stack_cache}
        if tail:
            cache["tail"] = {
                f"t{i}": zeros_from(self._block_cache_shape(tk, batch, max_len, dtype))
                for i, tk in enumerate(tail)}
        return cache

    # -- forward (train/eval) -------------------------------------------------------
    def apply(self, params: Dict, tokens: jnp.ndarray, *,
              prefix_len: int = 0, extra_embeddings: Optional[jnp.ndarray] = None,
              remat: str = "full") -> Tuple[jnp.ndarray, jnp.ndarray]:
        """tokens (B, S) → (logits (B, S, V), aux_loss). ``extra_embeddings``
        (B, P, D) are prepended (VLM patch / audio frame stubs)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = _embed(params, tokens, cfg)
            if extra_embeddings is not None:
                x = jnp.concatenate([extra_embeddings.astype(x.dtype), x], axis=1)
                prefix_len = max(prefix_len, extra_embeddings.shape[1])
            x = maybe_shard(x, P(("pod", "data"), "model", None))
        unit, n_units, tail = self.scan_groups()
        tail_first = bool(cfg.n_experts and cfg.n_dense_layers)

        def run_tail(x, aux):
            kinds = self.layer_kinds()
            tail_kinds = kinds[:len(tail)] if tail_first else kinds[cfg.n_layers - len(tail):]
            for i, kind in enumerate(tail_kinds):
                x, _, a = apply_block(params["tail"][f"t{i}"], x, cfg, kind,
                                      mode="train", prefix_len=prefix_len)
                aux = aux + a
            return x, aux

        block_fn = functools.partial(self._unit_apply, cfg=cfg, unit=unit,
                                     prefix_len=prefix_len)
        if remat == "full":
            block_fn = jax.remat(block_fn)
        elif remat == "dots":
            block_fn = jax.remat(
                block_fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

        def body(carry, unit_params):
            x, aux = carry
            x, a = block_fn(x, unit_params)
            return (x, aux + a), None

        with jax.named_scope("layers"):
            aux0 = jnp.zeros((), jnp.float32)
            if tail and tail_first:
                x, aux0 = run_tail(x, aux0)
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["stack"],
                                       unroll=cfg.scan_unroll)
            if tail and not tail_first:
                x, aux = run_tail(x, aux)
        with jax.named_scope("head"):
            x = rms_norm(x, params["ln_f"], cfg.norm_eps)
            logits = self._head(params, x)
        return logits, aux

    @staticmethod
    def _unit_apply(x, unit_params, *, cfg, unit, prefix_len):
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(unit):
            x, _, a = apply_block(unit_params[f"u{i}"], x, cfg, kind,
                                  mode="train", prefix_len=prefix_len)
            aux = aux + a
        return x, aux

    def _head(self, params, x):
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = (x @ w).astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if cfg.padded_vocab != cfg.vocab_size:
            bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                             0.0, attn_lib.NEG_INF)
            logits = logits + bias
        return logits

    # -- loss ----------------------------------------------------------------------
    def loss(self, params: Dict, batch: Dict, *, remat: str = "full"
             ) -> Tuple[jnp.ndarray, Dict]:
        logits, aux = self.apply(params, batch["tokens"], remat=remat,
                                 extra_embeddings=batch.get("extra_embeddings"))
        with jax.named_scope("loss"):
            labels = batch["labels"]
            if logits.shape[1] != labels.shape[1]:      # VLM prefix rows carry no loss
                logits = logits[:, -labels.shape[1]:]
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
            mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
            nll = jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return nll + aux, {"nll": nll, "aux": aux}

    # -- prefill / decode -------------------------------------------------------------
    def prefill(self, params: Dict, tokens: jnp.ndarray, cache: Dict, *,
                extra_embeddings: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict]:
        return self._serve(params, tokens, cache, mode="prefill",
                           pos=None, extra_embeddings=extra_embeddings)

    def decode(self, params: Dict, token: jnp.ndarray, cache: Dict,
               pos: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
        """token (B, 1); pos (B,) — uniform position of the new token."""
        return self._serve(params, token, cache, mode="decode", pos=pos)

    def _serve(self, params, tokens, cache, *, mode, pos,
               extra_embeddings=None):
        cfg = self.cfg
        prefix_len = cfg.prefix_len
        with jax.named_scope("embed"):
            x = _embed(params, tokens, cfg)
            if extra_embeddings is not None:
                x = jnp.concatenate([extra_embeddings.astype(x.dtype), x], axis=1)
                prefix_len = max(prefix_len, extra_embeddings.shape[1])
        unit, n_units, tail = self.scan_groups()
        tail_first = bool(cfg.n_experts and cfg.n_dense_layers)
        kinds = self.layer_kinds()
        tail_kinds = kinds[:len(tail)] if tail_first else \
            (kinds[cfg.n_layers - len(tail):] if tail else ())

        def run_tail(x, cache_tail):
            new_tail = {}
            for i, kind in enumerate(tail_kinds):
                x, nc, _ = apply_block(params["tail"][f"t{i}"], x, cfg, kind,
                                       mode=mode, cache=cache_tail[f"t{i}"],
                                       pos=pos, prefix_len=prefix_len)
                new_tail[f"t{i}"] = nc if nc is not None else cache_tail[f"t{i}"]
            return x, new_tail

        def body(x, xs):
            unit_params, unit_cache = xs
            new_uc = {}
            for i, kind in enumerate(unit):
                x, nc, _ = apply_block(unit_params[f"u{i}"], x, cfg, kind,
                                       mode=mode, cache=unit_cache[f"u{i}"],
                                       pos=pos, prefix_len=prefix_len)
                new_uc[f"u{i}"] = nc if nc is not None else unit_cache[f"u{i}"]
            return x, new_uc

        new_cache: Dict[str, Any] = {}
        with jax.named_scope("layers"):
            if tail and tail_first:
                x, new_cache["tail"] = run_tail(x, cache["tail"])
            x, stack_cache = jax.lax.scan(body, x, (params["stack"], cache["stack"]),
                                          unroll=cfg.scan_unroll)
            new_cache["stack"] = stack_cache
            if tail and not tail_first:
                x, new_cache["tail"] = run_tail(x, cache["tail"])
        with jax.named_scope("head"):
            x = rms_norm(x, params["ln_f"], cfg.norm_eps)
            logits = self._head(params, x[:, -1:])
        return logits, new_cache


def _is_shape_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def zeros_from(shapes):
    return jax.tree.map(lambda sd: jnp.zeros(sd[0], sd[1]), shapes,
                        is_leaf=_is_shape_leaf)
