import os
# This MUST run before any other import (jax locks the device count on
# first initialization).  Append to XLA_FLAGS rather than overwrite so a
# user-set flag string survives; an explicit device-count choice wins.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Dict, Optional, Tuple

import jax

from ..configs import ARCH_IDS, ShapeSpec, applicable_shapes, get_config
from ..models.config import ArchConfig
from .mesh import make_production_mesh
from .steps import (batch_structs, make_prefill_step, make_serve_step,
                    make_train_step, param_structs, serve_structs, step_struct)

# TPU v5e constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link
N_LINKS = 4                  # usable links per chip

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}

_COLL_LINE = re.compile(
    r"=\s+(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred)"
    r"\[([0-9,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return float(n * _DTYPE_BYTES[dtype])


def _group_size(line: str, default: int = 16) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device ICI traffic (bytes) per collective opcode, estimated
    from *result* shapes with ring-algorithm multipliers:

      all-gather        (g-1)/g × result        (result = gathered)
      reduce-scatter    (g-1)   × result        (input  = g × result)
      all-reduce        2(g-1)/g × result
      all-to-all        (g-1)/g × result
      collective-permute 1 × result
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_LINE.search(line)
        if not m:
            continue
        dtype, dims, op = m.group(1), m.group(2), m.group(3).lower()
        nbytes = _shape_bytes(dtype, dims)
        g = _group_size(line)
        mult = {"all-gather": (g - 1) / g,
                "reduce-scatter": float(g - 1),
                "all-reduce": 2.0 * (g - 1) / g,
                "all-to-all": (g - 1) / g,
                "collective-permute": 1.0}[op]
        out[op] = out.get(op, 0.0) + nbytes * mult
    return out


def roofline(per_dev_flops: float, per_dev_bytes: float,
             coll: Dict[str, float]) -> Dict[str, float]:
    coll_total = sum(coll.values())
    t_compute = per_dev_flops / PEAK_FLOPS
    t_memory = per_dev_bytes / HBM_BW
    t_coll = coll_total / (N_LINKS * LINK_BW)
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    bound = max(terms, key=terms.get)
    return {"t_compute": t_compute, "t_memory": t_memory,
            "t_collective": t_coll, "bound": bound,
            "collective_bytes": coll_total}


# ------------------------------------------------------------------------------
# depth extrapolation: XLA cost_analysis counts a scan body ONCE, so we
# lower shallow variants with k and k+1 scan units and reconstruct
# full-depth cost as cost(k) + unit × (F − k).
# ------------------------------------------------------------------------------
def _unit_len(cfg: ArchConfig) -> int:
    if cfg.block_pattern:
        return len(cfg.block_pattern)
    return 1


def _n_units(cfg: ArchConfig) -> int:
    if cfg.block_pattern:
        return cfg.n_layers // len(cfg.block_pattern)
    if cfg.n_experts and cfg.n_dense_layers:
        return cfg.n_layers - cfg.n_dense_layers
    return cfg.n_layers


def _shallow_cfg(cfg: ArchConfig, k: int) -> ArchConfig:
    u = _unit_len(cfg)
    if cfg.block_pattern:
        tail = cfg.n_layers - _n_units(cfg) * u
        n = k * u + tail
    elif cfg.n_experts and cfg.n_dense_layers:
        n = cfg.n_dense_layers + k
    else:
        n = k
    kw = {"n_layers": n, "scan_unroll": True}
    if cfg.encdec:
        kw["n_enc_layers"] = k
    return dataclasses.replace(cfg, **kw)


def _lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, remat: str):
    # donation mirrors production: params/opt update in place (train),
    # caches update in place (serving)
    if shape.mode == "train":
        _, train_step = make_train_step(cfg, remat=remat)
        params, opt = param_structs(cfg, mesh)
        batch = batch_structs(cfg, shape, mesh)
        return jax.jit(train_step, donate_argnums=(0, 1)).lower(
            params, opt, batch, step_struct(mesh))
    if shape.mode == "prefill":
        _, prefill_step = make_prefill_step(cfg)
        params, _ = param_structs(cfg, mesh)
        sv = serve_structs(cfg, shape, mesh)
        return jax.jit(prefill_step, donate_argnums=(2,)).lower(
            params, sv["tokens"], sv["cache"], sv["extras"])
    _, serve_step = make_serve_step(cfg)
    params, _ = param_structs(cfg, mesh)
    sv = serve_structs(cfg, shape, mesh)
    return jax.jit(serve_step, donate_argnums=(2,)).lower(
        params, sv["token"], sv["cache"], sv["pos"])


def _cost_terms(compiled) -> Tuple[float, float, Dict[str, float]]:
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)), coll


def run_cell(arch: str, shape: ShapeSpec, multi_pod: bool,
             remat: str = "full", extra: Optional[dict] = None) -> dict:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec: dict = {"arch": arch, "shape": shape.name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "mode": shape.mode, "devices": int(mesh.devices.size)}
    t0 = time.time()
    with jax.set_mesh(mesh):
        # 1) full-depth lower + compile — THE dry-run proof + memory truth
        lowered = _lower_cell(cfg, shape, mesh, remat)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gb": ma.argument_size_in_bytes / 1e9,
            "output_gb": ma.output_size_in_bytes / 1e9,
            "temp_gb": ma.temp_size_in_bytes / 1e9,
            "alias_gb": ma.alias_size_in_bytes / 1e9,
            "peak_gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 1e9,
        }
        f_full, b_full, c_full = _cost_terms(compiled)

        # 2) depth extrapolation for scan-body costs (shallow variants run
        # UNROLLED so every layer is counted exactly; per-unit cost is the
        # k=3 minus k=2 difference, immune to loop-structure quirks)
        F = _n_units(cfg)
        k1, k2 = (2, 3) if F >= 3 else (F, F)
        if k2 > k1:
            c1 = _lower_cell(_shallow_cfg(cfg, k1), shape, mesh, remat).compile()
            c2 = _lower_cell(_shallow_cfg(cfg, k2), shape, mesh, remat).compile()
            f1, b1, co1 = _cost_terms(c1)
            f2, b2, co2 = _cost_terms(c2)
            uf, ub = max(f2 - f1, 0.0), max(b2 - b1, 0.0)
            flops = f1 + uf * (F - k1)
            hbytes = b1 + ub * (F - k1)
            coll = {}
            for op in set(co1) | set(co2):
                u = max(co2.get(op, 0.0) - co1.get(op, 0.0), 0.0)
                coll[op] = co1.get(op, 0.0) + u * (F - k1)
            rec["extrapolated"] = True
            rec["scan_body_flops_once"] = f_full
        else:
            flops, hbytes, coll = f_full, b_full, c_full
            rec["extrapolated"] = False
    rec["per_device_flops"] = flops
    rec["per_device_bytes"] = hbytes
    rec["collectives"] = {k: round(v, 1) for k, v in coll.items()}
    rec["roofline"] = roofline(flops, hbytes, coll)
    if extra:
        rec.update(extra)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        for shape in applicable_shapes(arch):
            if args.shape and shape.name != args.shape:
                continue
            for mp in meshes:
                tag = f"{arch} × {shape.name} × {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, mp, remat=args.remat)
                    r = rec["roofline"]
                    print(f"[OK] {tag}: compile={rec['compile_s']}s "
                          f"peak={rec['memory']['peak_gb']:.2f}GB "
                          f"Tc={r['t_compute']*1e3:.2f}ms Tm={r['t_memory']*1e3:.2f}ms "
                          f"Tn={r['t_collective']*1e3:.2f}ms bound={r['bound']}",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    rec = {"arch": arch, "shape": shape.name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}"}
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")
    print("ALL DRY-RUN CELLS PASSED")


if __name__ == "__main__":
    main()
