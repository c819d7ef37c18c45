"""End-to-end training driver.

Composes the full substrate: model zoo, AdamW, token pipeline, sharded
async checkpointing with restart, heartbeat-driven elastic handling, and
(optionally) a Dora plan for the edge-simulator path.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_32b \
        --reduced --steps 200 --global-batch 8 --seq 128

``train(cfg, ...)`` is the callable entry point and returns its numbers;
``main`` is its command line.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer, latest_step
from ..configs import get_config, reduced_config
from ..data import DataConfig, TokenPipeline
from ..models.config import ArchConfig
from ..optim import adamw_init
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh
from .serve import kernel_calls, peak_bytes_in_use
from .steps import make_train_step


def train(cfg: ArchConfig, *, steps: int = 100, global_batch: int = 8,
          seq: int = 128, lr: float = 3e-4, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, log_every: int = 10, seed: int = 0,
          remat: str = "none",
          log: Callable[[str], None] = print) -> Dict[str, Any]:
    """Train ``steps`` steps on the synthetic token stream from seeded
    random weights (or the latest checkpoint in ``ckpt_dir``). Returns
    per-step losses and milliseconds, compile seconds, the kernels in the
    compiled step and the device's peak bytes in use."""
    mesh = make_host_mesh()
    model, train_step = make_train_step(cfg, peak_lr=lr,
                                        warmup=max(steps // 20, 5),
                                        total=steps, remat=remat)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(seed))
        opt = adamw_init(params)
        step0 = 0
        ckpt = None
        if ckpt_dir:
            ckpt = Checkpointer(ckpt_dir)
            last = latest_step(ckpt_dir)
            if last is not None:
                tree = ckpt.restore(last, {"params": params, "opt": opt})
                params, opt = tree["params"], tree["opt"]
                step0 = last
                log(f"restored checkpoint step {last}")

        data = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=seq,
                                        global_batch=global_batch,
                                        seed=seed), mesh)

        def next_batch():
            batch = next(data)
            if cfg.encdec:
                batch["encoder_frames"] = jnp.zeros(
                    (global_batch, cfg.enc_seq, cfg.d_model), jnp.float32)
            if cfg.vision_stub:
                batch["extra_embeddings"] = jnp.zeros(
                    (global_batch, cfg.n_patches, cfg.d_model), jnp.float32)
            return batch

        batch = next_batch()
        t0 = time.perf_counter()
        step_fn = jax.jit(train_step, donate_argnums=(0, 1)).lower(
            params, opt, batch, jnp.asarray(step0)).compile()
        compile_s = time.perf_counter() - t0
        losses, step_ms = [], []
        t0 = time.perf_counter()
        for step in range(step0, steps):
            if step > step0:
                batch = next_batch()
            t1 = time.perf_counter()
            params, opt, metrics = step_fn(params, opt, batch,
                                           jnp.asarray(step))
            losses.append(float(metrics["loss"]))
            step_ms.append((time.perf_counter() - t1) * 1e3)
            if step % log_every == 0 or step == steps - 1:
                dt = time.perf_counter() - t0
                log(f"step {step:5d} loss {losses[-1]:.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} "
                    f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)")
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt})
        if ckpt:
            ckpt.save(steps, {"params": params, "opt": opt}, wait=True)
        data.close()
    return {
        "losses": np.array(losses),
        "step_ms": np.array(step_ms),
        "compile_s": compile_s,
        "kernel_calls": {"train": kernel_calls(step_fn)},
        "peak_bytes_in_use": peak_bytes_in_use(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    r = train(cfg, steps=args.steps, global_batch=args.global_batch,
              seq=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
              ckpt_every=args.ckpt_every, log_every=args.log_every,
              seed=args.seed)
    losses = r["losses"]
    if len(losses):
        first, final = np.mean(losses[:10]), np.mean(losses[-10:])
        print(f"loss {first:.4f} -> {final:.4f} "
              f"({'improved' if final < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
