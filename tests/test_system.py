"""End-to-end system tests: Algorithm 1 end to end, training loss
actually decreases, serve loop generates, dry-run machinery importable.
"""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, all_cells, applicable_shapes, reduced_config
from repro.core.cost_model import Workload
from repro.core.device import make_setting
from repro.core.graph_builders import paper_model
from repro.core.planner import DoraPlanner
from repro.core.qoe import QoESpec
from repro.data import DataConfig, TokenPipeline
from repro.launch.steps import make_train_step
from repro.optim import adamw_init


def test_algorithm1_end_to_end():
    """ParallelismPlanner(G_M, D): partition → schedule → adapter."""
    topo = make_setting("traffic_monitor")
    graph = paper_model("bert", seq_len=512)
    qoe = QoESpec(t_qoe=10.0, lam=100.0)
    planner = DoraPlanner(graph, topo, qoe)
    wl = Workload(global_batch=32, microbatch_size=4, optimizer_mult=3.0)
    result = planner.plan(wl)
    assert result.best.latency > 0
    assert result.total_s < 60.0
    assert len(result.pareto) >= 1
    adapter = planner.make_adapter(result)
    out = adapter.run_interruptible(total_iters=50, deadline=3600.0)
    assert out["met_deadline"]


def test_assigned_cells_enumeration():
    cells = all_cells()
    assert len(cells) == 36          # 44 assigned − 8 documented long_500k skips
    assert len(ARCH_IDS) == 11
    for arch in ARCH_IDS:
        assert len(applicable_shapes(arch)) in (3, 4)


@pytest.mark.slow
def test_training_loss_decreases():
    """~40 steps of a tiny qwen-family model on the synthetic stream."""
    cfg = dataclasses.replace(reduced_config("qwen3_32b"), n_layers=2)
    model, train_step = make_train_step(cfg, peak_lr=3e-3, warmup=5,
                                        total=40, remat="none")
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    data = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                    global_batch=8, seed=0))
    jit_step = jax.jit(train_step, donate_argnums=(0, 1))
    losses = []
    for step in range(40):
        batch = next(data)
        params, opt, metrics = jit_step(params, opt, batch, jnp.asarray(step))
        losses.append(float(metrics["loss"]))
    data.close()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.9, losses


@pytest.mark.slow
def test_greedy_decode_runs():
    cfg = reduced_config("h2o_danube_1_8b")
    from repro.models import build_model
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, prompt_len, gen = 2, 8, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, prompt_len),
                              0, cfg.vocab_size)
    cache = model.init_cache(B, prompt_len + gen)
    logits, cache = model.prefill(params, toks, cache)
    cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    outs = [cur]
    decode = jax.jit(model.decode)
    for i in range(gen - 1):
        pos = jnp.full((B,), prompt_len + i, jnp.int32)
        logits, cache = decode(params, cur, cache, pos)
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs.append(cur)
    seq = jnp.concatenate(outs, axis=1)
    assert seq.shape == (B, gen)
    assert bool(jnp.all(seq >= 0)) and bool(jnp.all(seq < cfg.vocab_size))


def test_dryrun_module_importable_without_devices():
    """Importing launch modules must not lock jax device state."""
    import os
    code = ("import jax; "
            "from repro.launch import mesh; "
            "assert len(jax.devices()) == 1, jax.devices()")
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    # keep the ambient backend selection: without it jax probes for
    # accelerator runtimes (TPU libtpu discovery), which takes minutes
    if "JAX_PLATFORMS" in os.environ:
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, cwd=".")
    assert res.returncode == 0, res.stderr
