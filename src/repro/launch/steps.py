"""Step builders + ShapeDtypeStruct input specs for every (arch × shape).

``input_specs(cfg, shape, mesh)`` returns weak-type-correct, shardable
stand-ins (no device allocation) for:

* ``train``   — (params, opt_state, batch, step)
* ``prefill`` — (params, tokens, cache [, frontend stubs])
* ``decode``  — (params, token, cache, pos)

The modality frontends are stubs per the assignment: whisper receives
precomputed frame embeddings, paligemma precomputed patch embeddings.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ShapeSpec
from ..models import build_model
from ..models.common import dtype_of
from ..models.config import ArchConfig
from ..models.sharding import ShardingRules
from ..optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine


# ==================================================================================
# steps
# ==================================================================================
def make_train_step(cfg: ArchConfig, *, peak_lr: float = 3e-4,
                    warmup: int = 100, total: int = 10000,
                    remat: str = "full", opt: AdamWConfig = AdamWConfig()):
    model = build_model(cfg)

    def train_step(params, opt_state, batch, step):
        def loss_fn(p):
            loss, metrics = model.loss(p, batch, remat=remat)
            return loss, metrics
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("optimizer"):
            lr = warmup_cosine(step, peak_lr=peak_lr, warmup=warmup, total=total)
            params, opt_state, om = adamw_update(grads, opt_state, params, lr, opt)
        out = {"loss": loss, "lr": lr, **metrics, **om}
        return params, opt_state, out

    return model, train_step


def make_prefill_step(cfg: ArchConfig):
    model = build_model(cfg)

    def prefill_step(params, tokens, cache, extras):
        kw = {k: v for k, v in extras.items()} if extras else {}
        if cfg.encdec:
            logits, cache = model.prefill(params, tokens, cache,
                                          encoder_frames=kw["encoder_frames"])
        elif cfg.vision_stub:
            logits, cache = model.prefill(params, tokens, cache,
                                          extra_embeddings=kw["extra_embeddings"])
        else:
            logits, cache = model.prefill(params, tokens, cache)
        with jax.named_scope("head"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return model, prefill_step


def make_serve_step(cfg: ArchConfig):
    model = build_model(cfg)

    def serve_step(params, token, cache, pos):
        logits, cache = model.decode(params, token, cache, pos)
        with jax.named_scope("head"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return model, serve_step


# ==================================================================================
# ShapeDtypeStruct specs
# ==================================================================================
def _sds(tree_shape, spec_tree, mesh):
    def fn(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(fn, tree_shape, spec_tree)


def param_structs(cfg: ArchConfig, mesh) -> Tuple[Any, Any]:
    """(params, opt_state) ShapeDtypeStructs with production shardings."""
    model = build_model(cfg)
    rules = ShardingRules(cfg, mesh)
    p_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_spec = rules.param_specs(p_shape)
    o_shape = jax.eval_shape(adamw_init, p_shape)
    o_spec = {"m": p_spec, "v": p_spec,
              "count": P()}
    params = _sds(p_shape, p_spec, mesh)
    opt = {"m": _sds(o_shape["m"], p_spec, mesh),
           "v": _sds(o_shape["v"], p_spec, mesh),
           "count": jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=NamedSharding(mesh, P()))}
    return params, opt


def batch_structs(cfg: ArchConfig, shape: ShapeSpec, mesh) -> Dict[str, Any]:
    """Training batch stand-ins."""
    rules = ShardingRules(cfg, mesh)
    B, S = shape.global_batch, shape.seq_len
    dt = dtype_of(cfg.dtype)
    tree = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.encdec:
        tree["encoder_frames"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model), dt)
    if cfg.vision_stub:
        tree["extra_embeddings"] = jax.ShapeDtypeStruct((B, cfg.n_patches, cfg.d_model), dt)
    spec = rules.batch_specs(tree, B)
    return _sds(tree, spec, mesh)


def serve_structs(cfg: ArchConfig, shape: ShapeSpec, mesh) -> Dict[str, Any]:
    """Serving stand-ins: token/tokens, cache, pos, frontend stubs."""
    model = build_model(cfg)
    rules = ShardingRules(cfg, mesh)
    B, S = shape.global_batch, shape.seq_len
    dt = dtype_of(cfg.dtype)
    cache_shape = jax.eval_shape(lambda: model.init_cache(B, S))
    cache_spec = rules.cache_specs(cache_shape, B)
    cache = _sds(cache_shape, cache_spec, mesh)
    plain: Dict[str, Any] = {}
    if shape.mode == "prefill":
        plain["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        extras = {}
        if cfg.encdec:
            extras["encoder_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), dt)
        if cfg.vision_stub:
            extras["extra_embeddings"] = jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), dt)
        plain["extras"] = extras
    else:
        plain["token"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        plain["pos"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    spec = rules.batch_specs(plain, B)
    out = _sds(plain, spec, mesh)
    out["cache"] = cache
    return out


def step_struct(mesh):
    return jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
