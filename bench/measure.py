"""Shared arithmetic of the per-layer metric readers in ``metrics/``:
step device time, a kernel's share of its roofline, shares of the chip's
peaks, and the device's idle share, all over the traced window."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def step_dev_ms(run, fn: str) -> Optional[float]:
    """Mean device time of one execution of the jitted step ``fn``."""
    runs = run.trace.module_runs(fn, run.t0, run.t1)
    return float(np.mean([r.dur_ns for r in runs])) / 1e6 if runs else None


def roofline(run, metric: str, kernel: str,
             work: Sequence[Tuple[float, float]]) -> Optional[float]:
    """Percent of the least time the chip could take for the kernel's
    calls in the window (each call's larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s), over the time they took. ``work`` lists
    (FLOPs, bytes) of the calls expected, in order; a different number
    of calls found means the kernel's path changed, and gives nothing."""
    calls = run.trace.kernel_calls(kernel, run.t0, run.t1)
    if not calls or len(calls) != len(work):
        if calls:
            print(f"{metric}: {len(calls)} {kernel} calls, {len(work)} expected; not read")
        return None
    pf, pb = run.peaks["flops_per_s"], run.peaks["hbm_bytes_per_s"]
    compute = sum(f / pf for f, _ in work)
    memory = sum(b / pb for _, b in work)
    least = sum(max(f / pf, b / pb) for f, b in work)
    took = sum(c.dur_ns for c in calls) / 1e9
    print(f"{metric}: {len(calls)} calls of {kernel}, {took * 1e3:.3f} ms; "
          f"{'compute' if compute >= memory else 'memory'}-bound "
          f"(FLOPs {compute * 1e3:.3f} ms, bytes {memory * 1e3:.3f} ms at peak)")
    return 100.0 * least / took


def share_of_peak(amount: float, seconds: float, peak: float) -> float:
    return 100.0 * amount / (seconds * peak)


def idle_share(run) -> Optional[float]:
    if not run.trace.ops:
        return None
    return 100.0 * (1.0 - run.trace.busy_s(run.t0, run.t1) / run.window_s)


def serve_shape(run):
    """(batch, prompt_len, gen_len, rounds) of a traced serving window."""
    t = run.traffic
    return t["batch"], t["prompt_len"], t["gen_len"], run.stats["rounds"]
