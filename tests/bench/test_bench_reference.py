"""The plain float32 references against the program at small sizes on
the CPU: the forward pass, prefill and decode through the cache, and the
training loss with its gradients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import dense, ssm, train
from bench.reference.common import Numerics
from repro.configs import reduced_config
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import build_model

SEED = 2 ** 40 + 17
CASES = {
    # window 32 < 64 tokens; attn_chunk 16 takes the query-chunked path
    "dense": (dense, dataclasses.replace(reduced_config("h2o_danube_1_8b"), attn_chunk=16)),
    "dense_no_window": (dense, dataclasses.replace(reduced_config("h2o_danube_1_8b"),
                                                   window=None)),
    "ssm": (ssm, reduced_config("mamba2_780m")),
}


def _setup(case):
    ref, cfg = CASES[case]
    a = dataclasses.asdict(cfg)
    model = build_model(cfg)
    flat = weights.make(ref.param_spec(a), SEED)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return ref, cfg, a, model, flat, weights.to_program_tree(flat, like)


def _tokens(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, shape), jnp.int32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_the_program(case):
    ref, cfg, a, model, flat, params = _setup(case)
    toks = _tokens(cfg, (2, 64))
    got = model.apply(params, toks)[0][..., :cfg.vocab_size]
    want = ref.logits(flat, toks, a, Numerics())
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_reference_sees_the_window():
    ref, cfg, a, model, flat, params = _setup("dense")
    toks = _tokens(cfg, (1, 64))
    got = model.apply(params, toks)[0][..., :cfg.vocab_size]
    wrong = ref.logits(flat, toks, {**a, "window": None}, Numerics())
    assert float(jnp.max(jnp.abs(got - wrong))) > 1e-2


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_tokens_are_the_references_best(case):
    """Prefill, then greedy decode through the cache: every served token
    is the reference's best at its position."""
    ref, cfg, a, model, flat, params = _setup(case)
    B, P, G = 2, 64, 6
    _, prefill = make_prefill_step(cfg)
    _, decode = make_serve_step(cfg)
    prompts = _tokens(cfg, (B, P), seed=1)
    tok, cache = prefill(params, prompts, model.init_cache(B, P + G), {})
    served = [tok]
    for i in range(G - 1):
        tok, cache = decode(params, tok, cache, jnp.full((B,), P + i, jnp.int32))
        served.append(tok)
    served = jnp.concatenate(served, axis=1)
    seq = jnp.concatenate([prompts, served[:, :-1]], axis=1)
    lg = ref.logits(flat, seq, a, Numerics(), first=P - 1)
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
    assert float(jnp.max(gap)) < 1e-4


def test_int8_control_departs_from_float32():
    ref, cfg, a, model, flat, params = _setup("dense")
    toks = _tokens(cfg, (2, 64))
    f32 = ref.logits(flat, toks, a, Numerics())
    int8 = ref.logits(flat, toks, a, Numerics(int8=True))
    assert float(jnp.max(jnp.abs(f32 - int8))) > 1e-2


@pytest.mark.parametrize("case", ["dense", "ssm"])
def test_training_loss_and_gradients_match_the_program(case):
    ref, cfg, a, model, flat, params = _setup(case)
    toks = _tokens(cfg, (1, 65), seed=2)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, remat="full"), has_aux=True)(params)
    want, want_g = jax.value_and_grad(
        lambda w: train.loss(w, batch["tokens"], batch["labels"], a, ref, Numerics()))(
        {k: v.astype(jnp.float32) for k, v in flat.items()})
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    got = {weights.path_str(p): g for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    for k, g in want_g.items():
        err = float(jnp.linalg.norm(got[k] - g))
        assert err <= 1e-4 * float(jnp.linalg.norm(g)) + 1e-7, k


def test_learning_rate_follows_warmup_cosine():
    s = {"peak_lr": 1.0, "warmup": 10, "total": 110}
    assert train.learning_rate(5, s) == 0.5
    assert train.learning_rate(10, s) == pytest.approx(1.0)
    assert train.learning_rate(60, s) == pytest.approx(0.55)
    assert train.learning_rate(500, s) == pytest.approx(0.1)
