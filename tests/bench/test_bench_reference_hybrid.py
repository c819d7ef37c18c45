"""The plain float32 reference of the Granite 4.0-H hybrid against the
program at its reduced size on the CPU (one whole period of ten layers:
five SSD, one attention, four SSD, each with its MLP): the forward pass,
prefill and decode through the mixed cache, the int8 control, and each
of Granite's departures from a plain stack, which the reference must
hold so that the comparison would catch a program that left it out."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import hybrid
from bench.reference.common import Numerics
from repro.configs import reduced_config
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import build_model

SEED = 2 ** 40 + 23
# program and reference both compute in float32 here; they differ only in
# the order of sums (the chunked SSD dual form against the step-by-step
# recurrence, the program's einsums against HIGHEST matmuls), which reads
# 3e-7 of the largest logit
FORWARD_TOL = 1e-4
# Granite's departures, each set back to what a plain stack would do
REMOVED = {
    "nope": {"rope_theta": 10000.0},
    "attention_scale": {"attn_scale": None},
    "conv_bias": {"ssm_conv_bias": False},
    "ssd_mlp": {"d_ff": 0},
    "embedding_multiplier": {"embedding_multiplier": 1.0},
    "residual_multiplier": {"residual_multiplier": 1.0},
    "logits_scaling": {"logits_scaling": 1.0},
}


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("granite_4_0_h_micro")
    a = dataclasses.asdict(cfg)
    model = build_model(cfg)
    flat = weights.make(hybrid.param_spec(a), SEED)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return cfg, a, model, flat, weights.to_program_tree(flat, like)


def _tokens(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, cfg.vocab_size, shape), jnp.int32)


def _tolerance(logits):
    return FORWARD_TOL * float(jnp.max(jnp.abs(logits)))


def test_the_period_holds_both_kinds_of_block(setup):
    cfg, a, model, flat, params = setup
    assert model.layer_kinds() == ("ssm",) * 5 + ("dense",) + ("ssm",) * 4
    unit = params["stack"]
    assert {"ln2", "mlp"} <= set(unit["u0"]) and "conv_b" in unit["u0"]["mixer"]
    assert set(unit["u5"]["mixer"]) == {"wq", "wk", "wv", "wo"}
    cache = jax.eval_shape(lambda: model.init_cache(2, 80))["stack"]
    assert set(cache["u0"]) == {"ssm", "conv"} and set(cache["u5"]) == {"k", "v"}
    assert cache["u5"]["k"].shape == (1, 2, 80, cfg.n_kv_heads, cfg.hd)


def test_forward_matches_the_program(setup):
    cfg, a, model, flat, params = setup
    toks = _tokens(cfg, (2, 64))
    got = model.apply(params, toks)[0][..., :cfg.vocab_size]
    want = hybrid.logits(flat, toks, a, Numerics())
    assert float(jnp.max(jnp.abs(got - want))) < _tolerance(want)


def test_served_tokens_are_the_references_best(setup):
    """Prefill, then greedy decode through the cache that holds SSD state
    and K/V side by side: every served token is the reference's best at
    its position (the gap's 1e-4 is the forward tolerance: the largest
    logit is near 1)."""
    cfg, a, model, flat, params = setup
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
    lg = hybrid.logits(flat, seq, a, Numerics(), first=P - 1)
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
    assert float(jnp.max(gap)) < 1e-4


def test_int8_control_departs_beyond_the_tolerance(setup):
    """The control, one step below the bfloat16 the configuration states,
    moves the logits by more than a hundred times what the forward
    comparison allows the program (a thousand times at this seed)."""
    cfg, a, model, flat, params = setup
    toks = _tokens(cfg, (2, 64))
    f32 = hybrid.logits(flat, toks, a, Numerics())
    int8 = hybrid.logits(flat, toks, a, Numerics(int8=True))
    assert float(jnp.max(jnp.abs(f32 - int8))) > 100 * _tolerance(f32)


@pytest.mark.parametrize("removed", sorted(REMOVED))
def test_each_granite_departure_moves_the_logits(setup, removed):
    """Without it the reference's logits move by more than the forward
    tolerance, so a program that left it out would fail the forward
    comparison (the smallest, NoPE, moves them by 297 times that at this seed)."""
    cfg, a, model, flat, params = setup
    toks = _tokens(cfg, (2, 64))
    want = hybrid.logits(flat, toks, a, Numerics())
    plain = hybrid.logits(flat, toks, {**a, **REMOVED[removed]}, Numerics())
    assert float(jnp.max(jnp.abs(plain - want))) > _tolerance(want)
