"""Batched serving driver with Dora-planned placement and a QoE monitor.

Runs prefill + decode over synthetic request batches, reporting
per-token latency against the QoE target; with ``--dynamics`` it injects
a mid-run slowdown and shows the runtime adapter's network-only
rescheduling decision (paper Fig. 16 behavior at example scale).

``serve(cfg, ...)`` is the callable entry point and returns its numbers;
``main`` is its command line.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import dora
from ..configs import get_config, reduced_config
from ..core import DynamicsEvent, QoESpec, Workload
from ..models.config import ArchConfig
from ..models.registry import planning_graph
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh
from .steps import make_prefill_step, make_serve_step


def kernel_calls(compiled) -> int:
    """Pallas kernels in a compiled program (custom calls to Mosaic)."""
    return compiled.as_text().count("tpu_custom_call")


def peak_bytes_in_use() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def serve(cfg: ArchConfig, *, batch: int = 4, prompt_len: int = 32,
          gen_len: int = 32, t_qoe_ms: float = 200.0, dynamics: bool = False,
          setting: str = "smart_home_2",
          log: Callable[[str], None] = print) -> Dict[str, Any]:
    """Plan with Dora, then prefill ``prompt_len`` tokens and generate
    ``gen_len`` tokens per request on the local devices, with seeded
    random weights. Returns the generated ids (batch, gen_len), compile
    seconds, prefill and per-step decode milliseconds, the kernels in
    each compiled step and the device's peak bytes in use."""
    # --- Dora plans the edge deployment for this model --------------------
    # scenario fleet + this invocation's model/batch/QoE via overrides
    session = dora.serve(
        setting, graph=planning_graph(cfg, prompt_len),
        qoe=QoESpec(t_qoe=t_qoe_ms / 1e3, lam=100.0),
        workload=Workload(global_batch=batch, microbatch_size=1,
                          training=False))
    result = session.report.result
    log(f"Dora plan: {result.best.summary()}")
    log(f"planning took {result.total_s*1e3:.0f}ms "
        f"(phase1 {result.phase1_s*1e3:.0f}ms, "
        f"phase2 {result.phase2_s*1e3:.0f}ms)")

    # --- local JAX execution of the serving loop ---------------------------
    mesh = make_host_mesh()
    model, prefill_step = make_prefill_step(cfg)
    _, serve_step = make_serve_step(cfg)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_cache(batch, prompt_len + gen_len)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                          (batch, prompt_len)), jnp.int32)
        extras = {}
        if cfg.encdec:
            extras["encoder_frames"] = jnp.zeros(
                (batch, cfg.enc_seq, cfg.d_model), jnp.float32)
        if cfg.vision_stub:
            extras["extra_embeddings"] = jnp.zeros(
                (batch, cfg.n_patches, cfg.d_model), jnp.float32)
        offset = cfg.n_patches if cfg.vision_stub else 0
        pos0 = jnp.full((batch,), prompt_len + offset, jnp.int32)
        t0 = time.perf_counter()
        prefill = jax.jit(prefill_step, donate_argnums=(2,)).lower(
            params, tokens, cache, extras).compile()
        tok_spec = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        decode = jax.jit(serve_step, donate_argnums=(2,)).lower(
            params, tok_spec, cache, pos0).compile()
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        tok, cache = prefill(params, tokens, cache, extras)
        jax.block_until_ready(tok)
        prefill_ms = (time.perf_counter() - t0) * 1e3
        log(f"prefill({prompt_len} tokens): {prefill_ms:.1f}ms")
        out = [tok]
        lat = []
        for i in range(gen_len - 1):
            pos = pos0 + i
            t1 = time.perf_counter()
            tok, cache = decode(params, tok, cache, pos)
            jax.block_until_ready(tok)
            lat.append((time.perf_counter() - t1) * 1e3)
            out.append(tok)
            if dynamics and i == gen_len // 2:
                ev = DynamicsEvent(t=time.perf_counter() - t0,
                                   compute_speed={0: 0.6},
                                   bandwidth_scale={"wifi": 0.7})
                plan, action, dt = session.adapter.on_dynamics(result.best, ev)
                log(f"  [dynamics] adapter action={action} in {dt*1e3:.0f}ms; "
                    f"plan latency {result.best.latency*1e3:.0f} -> "
                    f"{plan.latency*1e3:.0f}ms")
    lat = np.array(lat) if lat else np.zeros(1)
    return {
        "compile_s": compile_s,
        "prefill_ms": prefill_ms,
        "decode_ms": lat,
        "decode_p50_ms": float(np.percentile(lat, 50)),
        "decode_p99_ms": float(np.percentile(lat, 99)),
        "tokens": np.concatenate([np.asarray(t) for t in out], axis=1),
        "kernel_calls": {"prefill": kernel_calls(prefill),
                         "decode": kernel_calls(decode)},
        "peak_bytes_in_use": peak_bytes_in_use(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--t-qoe-ms", type=float, default=200.0)
    ap.add_argument("--dynamics", action="store_true")
    ap.add_argument("--setting", default="smart_home_2")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    r = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
              gen_len=args.gen_len, t_qoe_ms=args.t_qoe_ms,
              dynamics=args.dynamics, setting=args.setting)
    p99 = r["decode_p99_ms"]
    print(f"compile: {r['compile_s']:.1f}s")
    print(f"decode: p50={r['decode_p50_ms']:.1f}ms p99={p99:.1f}ms "
          f"QoE target={args.t_qoe_ms:.0f}ms "
          f"({'MET' if p99 < args.t_qoe_ms else 'MISSED'} locally)")


if __name__ == "__main__":
    main()
