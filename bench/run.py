"""The on-chip benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up loads the cell's files, makes the weights on the device from the
seed, compiles the cell's own step shapes through the persistent cache
and warms each once. The window then drives the program's steps for
``--seconds``. With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` a profiled window of a few rounds or steps
gives its per-layer metrics and a breakdown instead. Either way the
outputs of the timed path are then compared with the plain reference,
which decides ``correct``. The last line of standard output is the
result as JSON. Off a TPU, or with fewer chips than the cell asks for,
it prints no result and exits with code 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


class TraceRun:
    """What a per-layer metric reader gets: the reduced trace and its
    window, the cell, the host's timings of the traced window, and the
    chip's peaks."""

    def __init__(self, cell, trace, stats, peaks):
        self.cell, self.trace, self.stats, self.peaks = cell, trace, stats, peaks
        span = trace.span("bench.window")
        self.t0, self.t1 = span.start_ns, span.end_ns

    @property
    def arch(self):
        return self.cell.arch

    @property
    def traffic(self):
        return self.cell.traffic

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


def traced(drv, cell, device):
    """A profiled window of the traffic file's ``trace_rounds`` or
    ``trace_steps``, reduced to per-layer metrics and a breakdown."""
    import jax
    from bench import peaks, trace
    out_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                stats = drv.window(rounds=drv.traced_rounds())
        finally:
            jax.profiler.stop_trace()
        tr = trace.load(out_dir)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    run = TraceRun(cell, tr, stats, peaks.peaks(device["kind"]))
    metrics = harness.read_per_layer(cell, run)
    busy = tr.busy_s(run.t0, run.t1)
    breakdown = {"device_ops": tr.top_ops(run.t0, run.t1),
                 "idle_gaps": tr.idle_gaps(run.t0, run.t1)}
    return stats, metrics, {"busy_s": busy, "window_s": run.window_s}, breakdown


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             t_start: float = T_START):
    """Set up, run the window, compare; returns (result, check)."""
    guard = harness.CompileGuard()
    harness.enable_compile_cache()
    drv = harness.driver(cell.traffic["kind"]).Driver(cell, seed)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    before = guard.count
    extra = {}
    if trace:
        stats, metrics, dev_times, extra["breakdown"] = traced(drv, cell, device)
    else:
        stats = drv.window(seconds=seconds)
        e2e = drv.end_to_end(stats)
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items() if k in units}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        dev_times = {}
    if guard.count != before:
        raise RuntimeError(f"{guard.count - before} program(s) compiled inside the window")
    device = {**device, "memory_peak_bytes": harness.memory_peak_bytes(), **dev_times}
    attempted = stats.get("requests", stats.get("steps", 0))
    drv.release()
    t_check = time.perf_counter()
    check = harness.judge(drv.check(), cell.limits)
    print(f"bench: set-up {setup_s:.1f} s, window {stats['window_s']:.1f} s, "
          f"check {time.perf_counter() - t_check:.1f} s", file=sys.stderr)
    result = {"correct": harness.correct(check), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device, **extra}
    return result, check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        device = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result, check = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    harness.emit(result, check)
    return 0


if __name__ == "__main__":
    sys.exit(main())
