"""What every cell shares: finding its files by name, checking the chip,
the compile cache and the guard against compiling inside the window,
the per-layer metric readers, and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its files:

- ``configs`` entry -> ``file`` (``bench/configs/<name>.json``): the
  program's ``ArchConfig`` fields under ``arch``, the reference family;
- ``traffic/<traffic>.json``: the mix, whose ``kind`` names a driver in
  ``drivers/<kind>.py``;
- ``limits/<cell>.json``: each compared number's limit and the readings
  it was set from;
- ``metrics/<metric>.py``: a per-layer metric's reader.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file
    traffic: Dict[str, Any]         # the traffic file
    limits: Dict[str, Any]          # limits/<cell>.json ("numbers" -> {name: {limit}})
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path = ROOT               # the checkout whose files define the cell

    @property
    def arch(self) -> Dict[str, Any]:
        return self.config["arch"]


def manifest(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reported(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    m = manifest(root)
    work = {w["name"]: w for w in m["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    cfg = {c["name"]: c for c in m["configs"]}[w["config"]]
    limits_file = root / "bench" / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads(limits_file.read_text()) if limits_file.exists() else {},
        end_to_end=[e for e in m["end_to_end"] if _reported(e, name)],
        per_layer=[p for p in m["per_layer"] if _reported(p, name)], root=root)


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics._{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(family: str):
    return importlib.import_module(f"bench.reference.{family}")


# -- the chip -----------------------------------------------------------------
def require_chips(n: int) -> Dict[str, Any]:
    """The device as JAX reports it; raises ``NoChip`` off a TPU or with
    fewer than ``n`` chips."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:            # no backend at all
        raise NoChip(str(e)) from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- compilation ----------------------------------------------------------------
class CompileGuard:
    """Counts lowerings (new programs) in the process. Every shape is
    warmed in set-up, so the window must see none."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, _secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.count += 1


def enable_compile_cache() -> str:
    """The program's persistent cache (``$JAX_COMPILATION_CACHE_DIR``,
    else ``.jax_cache/`` in the checkout), keeping every program."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


# -- per-layer metrics ------------------------------------------------------------
def read_per_layer(cell: Cell, run: Any) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"], cell.root)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# -- the result ---------------------------------------------------------------------
def judge(numbers: Dict[str, float], limits: Dict[str, Any]) -> Dict[str, Any]:
    """Each compared number beside its limit; a number passes when it is
    at most its limit."""
    table = limits.get("numbers", {})
    out = {}
    for name, value in numbers.items():
        limit = table[name]["limit"] if name in table else None
        out[name] = {"value": value, "limit": limit}
    return out


def correct(check: Dict[str, Any]) -> bool:
    return bool(check) and all(
        c["limit"] is not None and c["value"] == c["value"] and c["value"] <= c["limit"]
        for c in check.values())


def emit(result: Dict[str, Any], check: Dict[str, Any]) -> None:
    """The numbers compared as the last lines on stderr, then the result
    as the last line on stdout with ``check`` as its last key."""
    for name, c in check.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({**result, "check": check}), flush=True)
