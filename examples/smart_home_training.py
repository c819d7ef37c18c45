"""End-to-end training driver — plan with Dora, then actually train.

1. Dora plans hybrid parallelism for the Smart Home 2 fleet (QoE-aware).
2. The JAX substrate trains a small qwen-family model on the synthetic
   token stream with AdamW, async sharded checkpointing and restart.

On this CPU container the model defaults to a ~10M-param reduced config
(~300 steps in minutes); pass ``--big`` for a ~100M-param model if you
have the patience or a real accelerator.

    PYTHONPATH=src python examples/smart_home_training.py --steps 200
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import dora
from repro.checkpoint import Checkpointer, latest_step
from repro.configs import reduced_config
from repro.core.cost_model import Workload
from repro.core.graph_builders import GraphSpec, build_lm_graph
from repro.core.qoe import QoESpec
from repro.data import DataConfig, TokenPipeline
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models.common import count_params
from repro.optim import adamw_init


def model_cfg(big: bool):
    base = reduced_config("qwen3_32b")
    if big:   # ~100M params
        return dataclasses.replace(base, n_layers=12, d_model=768,
                                   n_heads=12, n_kv_heads=4, head_dim=64,
                                   d_ff=2048, vocab_size=32768)
    return dataclasses.replace(base, n_layers=8, d_model=256, n_heads=8,
                               n_kv_heads=4, head_dim=32, d_ff=1024,
                               vocab_size=8192)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--big", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/dora_smart_home_ckpt")
    args = ap.parse_args()

    # ---- 1. QoE-aware plan for the edge fleet -----------------------------
    # the scenario supplies fleet + workload; we swap in the actual
    # (reduced) model being trained and this run's QoE target.
    cfg = model_cfg(args.big)
    spec = GraphSpec("home-lm", cfg.n_layers, cfg.d_model, cfg.n_heads,
                     cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size,
                     head_dim=cfg.head_dim, seq_len=args.seq)
    report = dora.plan("smart_home_2", graph=build_lm_graph(spec),
                       qoe=QoESpec(t_qoe=2.0, lam=10.0),
                       workload=Workload(global_batch=32, microbatch_size=4,
                                         optimizer_mult=3.0))
    result = report.result
    print("Dora plan for the fleet:", report.best.summary())
    print(f"(planned in {result.total_s:.2f}s; executing the training loop "
          f"locally on {jax.device_count()} JAX device(s))\n")

    # ---- 2. real training on the JAX substrate ----------------------------
    mesh = make_host_mesh()
    model, train_step = make_train_step(cfg, peak_lr=1e-3,
                                        warmup=max(args.steps // 20, 5),
                                        total=args.steps, remat="none")
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        print(f"model: {count_params(params) / 1e6:.1f}M params")
        opt = adamw_init(params)
        ckpt = Checkpointer(args.ckpt_dir)
        step0 = latest_step(args.ckpt_dir) or 0
        if step0:
            tree = ckpt.restore(step0, {"params": params, "opt": opt})
            params, opt = tree["params"], tree["opt"]
            print(f"resumed from checkpoint step {step0}")

        data = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=args.seq,
                                        global_batch=args.global_batch), mesh)
        jit_step = jax.jit(train_step, donate_argnums=(0, 1))
        losses, t0 = [], time.time()
        for step in range(step0, args.steps):
            params, opt, m = jit_step(params, opt, next(data),
                                      jnp.asarray(step))
            losses.append(float(m["loss"]))
            if step % 20 == 0 or step == args.steps - 1:
                print(f"step {step:4d}  loss {losses[-1]:.4f}  "
                      f"lr {float(m['lr']):.2e}  ({time.time() - t0:.0f}s)",
                      flush=True)
            if (step + 1) % 100 == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt})
        ckpt.save(args.steps, {"params": params, "opt": opt}, wait=True)
        data.close()
        print(f"\nloss {np.mean(losses[:10]):.3f} → {np.mean(losses[-10:]):.3f}"
              f"  (checkpoints in {args.ckpt_dir})")


if __name__ == "__main__":
    main()
