"""Encoder-decoder transformer (Whisper-style backbone).

The conv audio frontend is a STUB per the assignment: ``input_specs()``
feeds precomputed frame embeddings (B, enc_seq, D). Norms are RMSNorm
for substrate uniformity (noted in DESIGN.md §assumption changes).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as attn_lib
from .common import dense_init, dtype_of, embed_init, rms_norm, split_keys
from .config import ArchConfig
from .mlp import apply_mlp, init_mlp
from .sharding_utils import maybe_shard
from .transformer import _fit_cache, init_attn


def _init_enc_layer(key, cfg: ArchConfig, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
            "attn": init_attn(k1, cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)}


def _init_dec_layer(key, cfg: ArchConfig, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
            "self_attn": init_attn(k1, cfg, dtype),
            "ln_x": jnp.zeros((cfg.d_model,), jnp.float32),
            "cross_attn": init_attn(k2, cfg, dtype),
            "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
            "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)}


def _attn_noncausal(p, x, cfg):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    o = attn_lib.gqa_attention(q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _cross_kv(p, enc_out):
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"])
    return k, v


def _cross_attn(p, x, k, v):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    o = attn_lib.gqa_attention(q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _write_cache(cache, kv, start):
    """Write (B, n, KV, hd) into a (B, T, KV, hd) cache from position ``start``."""
    return jax.lax.dynamic_update_slice_in_dim(cache, kv.astype(cache.dtype),
                                               start, axis=1)


def _self_attn(p, x, cfg, *, mode, cache, pos):
    from .common import apply_rope
    B, S, _ = x.shape
    positions = pos[:, None] if mode == "decode" else jnp.arange(S)[None, :]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if mode == "decode":
        kc = _write_cache(cache["k"], k, pos[0])
        vc = _write_cache(cache["v"], v, pos[0])
        o = attn_lib.decode_attention(q, kc, vc, pos + 1)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), {"k": kc, "v": vc}
    if S > cfg.attn_chunk:
        # long prefill: never materialize the (S, S) score matrix
        o = attn_lib.gqa_attention_chunked(q, k, v, causal=True,
                                           q_chunk=cfg.attn_chunk // 4)
    else:
        o = attn_lib.gqa_attention(q, k, v, causal=True)
    new_cache = None
    if mode == "prefill":
        t_buf = cache["k"].shape[1]
        new_cache = {"k": _write_cache(cache["k"], _fit_cache(t_buf, k), 0),
                     "v": _write_cache(cache["v"], _fit_cache(t_buf, v), 0)}
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), new_cache


@dataclasses.dataclass(frozen=True)
class EncDecLM:
    cfg: ArchConfig

    def init(self, rng) -> Dict:
        cfg = self.cfg
        dtype = dtype_of(cfg.dtype)
        ks = split_keys(rng, 5)
        return {
            "embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype),
            "enc_pos": embed_init(ks[1], (cfg.enc_seq, cfg.d_model), dtype),
            "enc": jax.vmap(lambda k: _init_enc_layer(k, cfg, dtype))(
                jax.random.split(ks[2], cfg.n_enc_layers)),
            "dec": jax.vmap(lambda k: _init_dec_layer(k, cfg, dtype))(
                jax.random.split(ks[3], cfg.n_layers)),
            "ln_enc": jnp.zeros((cfg.d_model,), jnp.float32),
            "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
        }

    # -- encoder ------------------------------------------------------------------
    def encode(self, params: Dict, frames: jnp.ndarray, remat: str = "full"
               ) -> jnp.ndarray:
        cfg = self.cfg
        x = frames + params["enc_pos"][None, : frames.shape[1]].astype(frames.dtype)
        x = maybe_shard(x, P(("pod", "data"), "model", None))

        def layer(x, p):
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            x = x + _attn_noncausal(p["attn"], h, cfg)
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            return x + apply_mlp(p["mlp"], h, cfg.act)

        fn = jax.remat(layer) if remat == "full" else layer
        x, _ = jax.lax.scan(lambda c, p: (fn(c, p), None), x, params["enc"],
                            unroll=cfg.scan_unroll)
        return rms_norm(x, params["ln_enc"], cfg.norm_eps)

    # -- decoder (train) --------------------------------------------------------------
    def apply(self, params: Dict, tokens: jnp.ndarray, *,
              encoder_frames: jnp.ndarray, remat: str = "full",
              **_ignored) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        enc_out = self.encode(params, encoder_frames, remat)
        x = params["embed"][tokens]
        x = maybe_shard(x, P(("pod", "data"), "model", None))

        def layer(x, p):
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            y, _ = _self_attn(p["self_attn"], h, cfg, mode="train",
                              cache=None, pos=None)
            x = x + y
            h = rms_norm(x, p["ln_x"], cfg.norm_eps)
            k, v = _cross_kv(p["cross_attn"], enc_out)
            x = x + _cross_attn(p["cross_attn"], h, k, v)
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            return x + apply_mlp(p["mlp"], h, cfg.act)

        fn = jax.remat(layer) if remat == "full" else layer
        x, _ = jax.lax.scan(lambda c, p: (fn(c, p), None), x, params["dec"],
                            unroll=cfg.scan_unroll)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = (x @ params["embed"].T).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            logits = logits + jnp.where(
                jnp.arange(cfg.padded_vocab) < cfg.vocab_size, 0.0, attn_lib.NEG_INF)
        return logits, jnp.zeros((), jnp.float32)

    def loss(self, params: Dict, batch: Dict, remat: str = "full"):
        logits, aux = self.apply(params, batch["tokens"],
                                 encoder_frames=batch["encoder_frames"],
                                 remat=remat)
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = jnp.mean(lse - ll)
        return nll, {"nll": nll, "aux": aux}

    # -- serving --------------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Dict:
        cfg = self.cfg
        dtype = dtype_of(cfg.dtype)
        L, kv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        z = lambda *s: jnp.zeros(s, dtype)
        return {"self": {"k": z(L, batch, max_len, kv, hd),
                         "v": z(L, batch, max_len, kv, hd)},
                "cross": {"k": z(L, batch, cfg.enc_seq, kv, hd),
                          "v": z(L, batch, cfg.enc_seq, kv, hd)}}

    def prefill(self, params: Dict, tokens: jnp.ndarray, cache: Dict, *,
                encoder_frames: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        enc_out = self.encode(params, encoder_frames, remat="none")

        def layer(x, xs):
            p, sc = xs
            k, v = _cross_kv(p["cross_attn"], enc_out)
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            y, nc = _self_attn(p["self_attn"], h, cfg, mode="prefill",
                               cache=sc, pos=None)
            x = x + y
            h = rms_norm(x, p["ln_x"], cfg.norm_eps)
            x = x + _cross_attn(p["cross_attn"], h, k, v)
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + apply_mlp(p["mlp"], h, cfg.act)
            x = maybe_shard(x, P(("pod", "data"), "model", None))
            return x, (nc, {"k": k, "v": v})

        x = params["embed"][tokens]
        x = maybe_shard(x, P(("pod", "data"), "model", None))
        x, (self_c, cross_c) = jax.lax.scan(layer, x, (params["dec"], cache["self"]),
                                            unroll=cfg.scan_unroll)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = (x[:, -1:] @ params["embed"].T).astype(jnp.float32)
        return logits, {"self": self_c, "cross": cross_c}

    def decode(self, params: Dict, token: jnp.ndarray, cache: Dict,
               pos: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg

        def layer(x, xs):
            p, sc, cc = xs
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            y, nc = _self_attn(p["self_attn"], h, cfg, mode="decode",
                               cache=sc, pos=pos)
            x = x + y
            h = rms_norm(x, p["ln_x"], cfg.norm_eps)
            x = x + _cross_attn(p["cross_attn"], h, cc["k"], cc["v"])
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            x = x + apply_mlp(p["mlp"], h, cfg.act)
            x = maybe_shard(x, P(("pod", "data"), None, None))
            return x, nc

        x = params["embed"][token]
        x = maybe_shard(x, P(("pod", "data"), None, None))
        x, self_c = jax.lax.scan(layer, x, (params["dec"], cache["self"], cache["cross"]),
                                 unroll=cfg.scan_unroll)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = (x @ params["embed"].T).astype(jnp.float32)
        return logits, {"self": self_c, "cross": cache["cross"]}
