"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time as the union of op intervals, kernel and
step time by name, the longest idle gaps with what the host was doing in
them, and the device ops that took most time.

What the TPU trace holds (recorded on a v5e, jax 0.9): a plane
``/device:TPU:<n>`` per chip, with a line ``XLA Ops`` whose events are
named by their HLO text (``%flash_attention.4 = bf16[...] custom-call(...),
custom_call_target="tpu_custom_call"``) and a line ``XLA Modules`` with one
event per executed program (``jit_prefill_step(<hash>)``). Host threads
are lines of ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land
there on the same clock. A Pallas kernel carries the name of the jitted
function that wraps its ``pallas_call``, as no kernel sets ``name=`` yet.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_OP_NAME = re.compile(r"%([\w.\-]+) = ")
_SUFFIX = re.compile(r"\.\d+$")
CONTROL_OPS = {"while", "conditional", "call"}   # they span their children
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Op(Event):
    base: str = ""          # op name without its numeric suffix
    kernel: bool = False    # a Pallas (Mosaic) custom call


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]            # device plane -> ops, by start
    modules: Dict[str, List[Event]]     # device plane -> executed programs
    spans: List[Event]                  # host annotations named bench.*

    # -- windows ---------------------------------------------------------
    def span(self, name: str) -> Event:
        """The one host span called ``name`` (the traced window)."""
        found = [s for s in self.spans if s.name == name]
        if len(found) != 1:
            raise ValueError(f"expected one span {name!r}, found {len(found)}")
        return found[0]

    # -- device time -----------------------------------------------------
    def busy_intervals(self, device: str, t0: float, t1: float) -> List[Tuple[float, float]]:
        """Merged intervals in which an op ran on ``device``, clipped to
        [t0, t1]."""
        out: List[List[float]] = []
        for op in sorted(self.ops[device], key=lambda o: o.start_ns):
            a, b = max(op.start_ns, t0), min(op.end_ns, t1)
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self, t0: float, t1: float) -> float:
        """Busy seconds in [t0, t1], averaged over the devices."""
        per = [sum(b - a for a, b in self.busy_intervals(d, t0, t1))
               for d in self.ops]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def kernel_calls(self, base: str, t0: float, t1: float) -> List[Op]:
        """Pallas kernel ops called ``base`` that start in [t0, t1]."""
        return [op for ops in self.ops.values() for op in ops
                if op.kernel and op.base == base and t0 <= op.start_ns < t1]

    def module_runs(self, fn: str, t0: float, t1: float) -> List[Event]:
        """Executions of the jitted function ``fn`` that start in [t0, t1]."""
        pre = f"jit_{fn}("
        return [m for ms in self.modules.values() for m in ms
                if m.name.startswith(pre) and t0 <= m.start_ns < t1]

    # -- breakdown -------------------------------------------------------
    def top_ops(self, t0: float, t1: float, n: int = 10) -> List[Tuple[str, float]]:
        """Seconds by op name (numeric suffix dropped), largest first,
        averaged over the devices; loops and calls are left out because
        their children are counted."""
        tot: Dict[str, float] = defaultdict(float)
        for ops in self.ops.values():
            for op in ops:
                if op.base not in CONTROL_OPS and t0 <= op.start_ns < t1:
                    tot[op.base] += op.dur_ns
        k = max(len(self.ops), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k / 1e9] for name, t in ranked]

    def idle_gaps(self, t0: float, t1: float, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps of any device in [t0, t1], each
        named by the innermost host span that covers its middle."""
        gaps = []
        for dev in self.ops:
            edge = t0
            for a, b in self.busy_intervals(dev, t0, t1) + [(t1, t1)]:
                if a > edge:
                    gaps.append((a - edge, (a + edge) / 2))
                edge = max(edge, b)
        gaps.sort(reverse=True)
        return [[self.host_doing(mid), dur / 1e9] for dur, mid in gaps[:n]]

    def host_doing(self, t: float) -> str:
        inner: Optional[Event] = None
        for s in self.spans:
            if s.start_ns <= t < s.end_ns and (inner is None or s.dur_ns < inner.dur_ns):
                inner = s
        return inner.name[len(SPAN_PREFIX):] if inner else "outside any span"


def _op(ev) -> Op:
    m = _OP_NAME.match(ev.name)
    name = m.group(1) if m else ev.name.split(" ", 1)[0]
    return Op(name, ev.start_ns, ev.duration_ns, base=_SUFFIX.sub("", name),
              kernel='custom_call_target="tpu_custom_call"' in ev.name)


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = sorted((_op(e) for e in line.events),
                                             key=lambda o: o.start_ns)
                elif line.name == "XLA Modules":
                    modules[plane.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=sorted(spans, key=lambda s: s.start_ns))


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return files[0]


def load(path: str) -> Trace:
    """Reduce the trace file at ``path`` (or the one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return from_profile(ProfileData.from_file(path))
