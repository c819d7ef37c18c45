"""On-chip smoke test of the substrate's main path, at full model width.

Run from the root of a checkout, on a machine whose JAX backend is a TPU:

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: the pipeline phase only

One chip, in order:

  (a) serve h2o-danube-1.8b at its published config (24 layers, d_model
      2560, window 4096): batch 8, prompt 4096, 32 generated tokens;
  (b) serve mamba2-780m at its published config: batch 8, prompt 2048,
      16 generated tokens;
  (c) train h2o-danube-1.8b at full width, depth cut to fit 16 GB: three
      steps at sequence 4096;
  (d) each Pallas kernel against its jnp reference at published widths.

Each serve/train step is compiled ahead of time and its compiled program
must call a Pallas kernel (``tpu_custom_call``), so a reference cannot
stand in unnoticed; mamba2's decode step is the O(1) jnp recurrence and
has no kernel by design.

Four chips: Dora plans h2o-danube on the four-chip ``edge_pod_v5e``
topology; the best pool plan with one chip per stage runs through
``DoraPipelineExecutor`` on pre-norm residual gated-MLP blocks
(``calibrate.microbench.gated_mlp_layer``) at danube widths (bf16),
each stage's parameters on its own chip, and its output is compared with
the same layers run in sequence on one chip.

Weights and inputs are random from fixed seeds. The compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in the
checkout. The last line of output is one JSON object naming the device.
Off a TPU, or outside a checkout, it exits non-zero with no result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# phase (c): the compiler puts 14 full-width layers of the AdamW step at
# 15.3 of the chip's 15.75 GB (params bf16 + fp32 moments + remat'd
# activations at batch 1); 12 leave headroom for the process's other
# buffers.
TRAIN_LAYERS = 12


def _log(msg: str) -> None:
    print(msg, flush=True)


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _gb(n) -> str:
    return "n/a" if n is None else f"{n / 1e9:.2f} GB"


# ----------------------------------------------------------------- phases
def phase_serve(tag: str, cfg, *, batch: int, prompt_len: int,
                gen_len: int) -> dict:
    from repro.launch.serve import serve

    r = serve(cfg, batch=batch, prompt_len=prompt_len, gen_len=gen_len,
              log=lambda m: _log(f"[{tag}] {m}"))
    toks = r["tokens"]
    _log(f"[{tag}] {cfg.name}: batch {batch}, prompt {prompt_len}, "
         f"{toks.shape[1]} tokens generated")
    _log(f"[{tag}] compile {r['compile_s']:.1f} s, prefill "
         f"{r['prefill_ms']:.1f} ms, decode p50 {r['decode_p50_ms']:.2f} ms "
         f"p99 {r['decode_p99_ms']:.2f} ms, peak_bytes_in_use "
         f"{_gb(r['peak_bytes_in_use'])}, kernels {r['kernel_calls']}")
    _require(toks.shape == (batch, gen_len), f"{tag}: token shape {toks.shape}")
    _require(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
             f"{tag}: token ids outside [0, {cfg.vocab_size})")
    return r


def phase_train(tag: str, cfg, *, steps: int, seq: int, batch: int) -> dict:
    from repro.launch.train import train

    r = train(cfg, steps=steps, global_batch=batch, seq=seq, remat="full",
              log_every=1, log=lambda m: _log(f"[{tag}] {m}"))
    losses = r["losses"]
    _log(f"[{tag}] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"batch {batch}, seq {seq}: compile {r['compile_s']:.1f} s, "
         f"step ms {[round(float(t), 1) for t in r['step_ms']]}, "
         f"peak_bytes_in_use {_gb(r['peak_bytes_in_use'])}, "
         f"kernels {r['kernel_calls']}")
    _require(len(losses) == steps and bool(np.isfinite(losses).all()),
             f"{tag}: losses not finite: {losses}")
    return r


def phase_kernels(tag: str, *, interpret: bool = False, small: bool = False
                  ) -> dict:
    """Each kernel vs its reference, on the same inputs, at published
    widths (danube attention, mamba2 SSD, recurrentgemma RG-LRU)."""
    from repro.kernels import ref
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rglru_scan import rglru_scan
    from repro.kernels.ssd_scan import ssd_scan

    S = 256 if small else 2048
    W = 256 if small else 4096
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    rnd = lambda k, shape, dt, s=1.0: (jax.random.normal(k, shape, f32)
                                       * s).astype(dt)
    errs = {}

    def check(name, got, want, tol):
        got = [np.asarray(g, np.float32) for g in got]
        want = [np.asarray(w, np.float32) for w in want]
        err = max(float(np.max(np.abs(g - w) / (tol + tol * np.abs(w))))
                  for g, w in zip(got, want))
        errs[name] = err
        _log(f"[{tag}] {name}: max |err| / (tol·(1+|ref|)) = {err:.3f} "
             f"(tol {tol})")
        _require(err <= 1.0, f"{tag}: {name} disagrees with its reference")

    q, k, v = (rnd(ks[0], (1, S, 32, 80), bf), rnd(ks[1], (1, S, 8, 80), bf),
               rnd(ks[2], (1, S, 8, 80), bf))
    check("flash_attention",
          [flash_attention(q, k, v, window=4096, interpret=interpret)],
          [ref.flash_attention_ref(q, k, v, window=4096)], 2e-2)

    T = S * 2
    qd = rnd(ks[3], (8, 1, 32, 80), bf)
    kc, vc = rnd(ks[4], (8, T, 8, 80), bf), rnd(ks[5], (8, T, 8, 80), bf)
    lens = jnp.arange(1, 9, dtype=jnp.int32) * (T // 8) - 3
    check("decode_attention",
          [decode_attention(qd, kc, vc, lens, interpret=interpret)],
          [ref.decode_attention_ref(qd, kc, vc, lens)], 2e-2)

    x = rnd(ks[6], (1, S, 48, 64), bf, 0.1)
    a = -jnp.abs(rnd(ks[7], (1, S, 48), f32, 0.1))
    b, c = rnd(ks[0], (1, S, 1, 128), bf, 0.1), rnd(ks[1], (1, S, 1, 128), bf, 0.1)
    check("ssd_scan", ssd_scan(x, a, b, c, chunk=256, interpret=interpret),
          ref.ssd_scan_ref(x, a, b, c, chunk=256), 2e-2)

    a_log = -jnp.abs(rnd(ks[2], (1, S, W), f32, 0.5))
    bb = rnd(ks[3], (1, S, W), f32)
    check("rglru_scan", rglru_scan(a_log, bb, interpret=interpret),
          ref.rglru_scan_ref(a_log, bb), 1e-4)
    return errs


def mlp_block(lp, h):
    """Pre-norm residual gated-MLP block (danube's MLP sublayer), so
    activations stay bounded through the stack."""
    from repro.calibrate.microbench import gated_mlp_layer

    hf = h.astype(jnp.float32)
    hn = hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + 1e-6)
    return h + gated_mlp_layer(lp, hn.astype(h.dtype))


def plan_pipeline(n_chips: int, seq: int = 1024):
    """Best Dora pool plan for h2o-danube on ``edge_pod_v5e`` that puts
    one stage on each of the ``n_chips`` chips."""
    from repro import dora
    from repro.configs import get_config
    from repro.models.registry import planning_graph

    planner, _, wl = dora.planner_for(
        "edge_pod_v5e", graph=planning_graph(get_config("h2o_danube_1_8b"),
                                             seq))
    pool = planner.partitioner.plan(wl, pool=True)
    fits = [p for p in pool if p.n_stages == n_chips
            and all(len(s.devices) == 1 for s in p.stages)]
    _require(bool(fits), f"no Dora pool plan puts one stage on each of "
             f"{n_chips} chips")
    return planner.scheduler.refine_candidates(fits, keep=len(fits))[0]


def phase_pipeline(tag: str, devices, *, n_layers: int = 24,
                   d_model: int = 2560, d_ff: int = 6912,
                   tokens: int = 1024) -> dict:
    from repro.calibrate.microbench import init_gated_mlp
    from repro.runtime.pipeline import DoraPipelineExecutor

    plan = plan_pipeline(len(devices))
    _log(f"[{tag}] Dora plan: {plan.summary()}")
    order = [s.devices[0] for s in plan.stages]
    mesh = Mesh(np.array([devices[d] for d in order]), ("stage",))
    ex = DoraPipelineExecutor(plan, n_layers, mesh, mlp_block)
    _log(f"[{tag}] layers per stage {ex.spec.layers_per_stage} "
         f"(pad {ex.spec.pad}), {ex.spec.n_microbatches} microbatches of "
         f"{plan.microbatch_size}x{tokens} rows, d_model {d_model}, "
         f"d_ff {d_ff}, bf16")

    stacked = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                           init_gated_mlp(n_layers, d_model, d_ff))
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        (ex.spec.n_microbatches, plan.microbatch_size * tokens, d_model),
        jnp.float32).astype(jnp.bfloat16)

    @jax.jit
    def sequential(params, xs):
        def body(h, lp):
            return mlp_block(lp, h), None
        return jax.vmap(lambda xm: jax.lax.scan(body, xm, params)[0])(xs)

    t0 = time.perf_counter()
    want = np.asarray(sequential(stacked, x), np.float32)
    seq_s = time.perf_counter() - t0
    packed = ex.pack_params(stacked)
    del stacked
    per_chip = [d.memory_stats().get("bytes_in_use") if d.memory_stats()
                else None for d in mesh.devices.flat]
    _log(f"[{tag}] bytes in use per chip after placing stages: "
         f"{[_gb(b) for b in per_chip]}")
    shards = {s.device: s.data.shape for s in
              jax.tree.leaves(packed)[0].addressable_shards}
    _log(f"[{tag}] stage block per chip: "
         f"{[shards[d] for d in mesh.devices.flat]}")

    fwd = jax.jit(ex.forward)
    x = jax.device_put(x, NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        out = fwd(packed, x)
        jax.block_until_ready(out)
        pipe_s = time.perf_counter() - t0
    got = np.asarray(out, np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    _log(f"[{tag}] pipeline vs one-chip sequential: relative L2 error "
         f"{rel:.2e} (first calls, compile included: pipeline "
         f"{pipe_s:.1f} s, sequential {seq_s:.1f} s)")
    _require(bool(np.isfinite(got).all()), f"{tag}: non-finite output")
    _require(rel < 1e-2, f"{tag}: pipeline output disagrees with the "
             f"sequential run (relative L2 error {rel:.2e})")
    return {"rel_err": rel, "bytes_in_use": per_chip}


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run this from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"'{dev.platform}' ({dev.device_kind})", file=sys.stderr)
        return 1
    _require(len(jax.devices()) >= args.chips,
             f"--chips {args.chips} but JAX sees {len(jax.devices())}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    _log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
         f"{jax.__version__}, compile cache {enable_compile_cache()}")
    t_all = time.perf_counter()
    compile_s = 0.0
    if args.chips == 4:
        phase_pipeline("pipeline", jax.devices()[:4])
    else:
        danube = get_config("h2o_danube_1_8b")
        r = phase_serve("a", danube, batch=8, prompt_len=4096, gen_len=32)
        _require(r["kernel_calls"]["prefill"] > 0
                 and r["kernel_calls"]["decode"] > 0,
                 "a: a serve step compiled without a Pallas kernel")
        compile_s += r["compile_s"]
        r = phase_serve("b", get_config("mamba2_780m"), batch=8,
                        prompt_len=2048, gen_len=16)
        _require(r["kernel_calls"]["prefill"] > 0,
                 "b: the prefill step compiled without a Pallas kernel")
        compile_s += r["compile_s"]
        cut = dataclasses.replace(danube, n_layers=TRAIN_LAYERS)
        _log(f"[c] depth cut {danube.n_layers} -> {TRAIN_LAYERS} layers to "
             f"fit params, grads and fp32 AdamW moments in 16 GB")
        r = phase_train("c", cut, steps=3, seq=4096, batch=1)
        _require(r["kernel_calls"]["train"] > 0,
                 "c: the train step compiled without a Pallas kernel")
        compile_s += r["compile_s"]
        phase_kernels("d")
        _log(f"step compile seconds (a+b+c): {compile_s:.1f}")
    _log(f"total seconds: {time.perf_counter() - t_all:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
