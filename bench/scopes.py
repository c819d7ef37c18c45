"""Which layer of the program each device op of a trace belongs to, by
the named scopes the program opens (``jax.named_scope``).

An XLA op's scope path is its JAX name stack: ``op_name`` in the HLO
metadata of the compiled program, ``tf_op`` (with a ``:<type>`` suffix)
in a trace file's op metadata. A path reads, for example,
``jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/mixer/qkv/dot_general``;
its last component is the op's primitive and names no scope. JAX 0.9
wraps the scopes open at a transformation in these forms (read from the
HLO metadata of the danube and mamba2 train steps compiled on the CPU):

- ``jvp(x)``: ``x`` run forward under differentiation; counts as ``x``;
- ``transpose(jvp(x))``: the backward of ``x``; counts as ``x``, and the
  op counts as backward;
- ``checkpoint``, ``rematted_computation`` (and ``remat``): a remat
  boundary and its recompute; dropped;
- ``jit(f)``, ``closed_call``, ``while``, ``body``, ``cond`` and einsum
  specs: kept as they are; they are no scope of the program's.

The program's vocabulary (``VOCABULARY``) splits a step into the
partition ``PARTS``: an op under ``mixer``, ``mlp`` or ``moe`` counts
there; else under ``layers`` as ``layers_self`` (the layer scan's own
slicing, stacking and carry copies, and the copy of its stacked output);
else under ``embed``, ``head``, ``loss`` or ``optimizer``; else
``unscoped``.

Two sources give each op its path: a trace file's ``tf_op`` stats
(``from_file``, with ``xplane``), and, while the programs that ran are
still loaded, their HLO metadata joined to the trace's op names by the
executed program (``from_programs``), which is what the per-layer
readers use (``of_run``). They agree on every op but the copies the
compiler adds at a program's top level, which ``tf_op`` leaves empty and
the HLO names by the value they copy (``program_paths``).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace as trace_lib
from .measure import step_dev_ms
from .trace import CONTROL_OPS, Op, Trace

VOCABULARY = ("embed", "layers", "mixer", "qkv", "cache", "attention", "out",
              "in_proj", "conv", "scan", "mlp", "moe", "head", "loss", "optimizer")
SUBLAYERS = ("mixer", "mlp", "moe")
MIXER = ("qkv", "cache", "attention", "in_proj", "conv", "scan", "out")
TOP = ("embed", "head", "loss", "optimizer")
PARTS = ("embed", "layers_self", "mixer", "mlp", "moe", "head", "loss", "optimizer",
         "unscoped")
TRANSFORMS = ("jvp", "transpose", "vmap")
DROPPED = {"checkpoint", "remat", "rematted_computation"}
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_HLO_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_HLO_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)|(?:branch_computations=\{|, )%([\w.\-]+)")
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_COPIED = re.compile(r" (copy|copy-start|copy-done|bitcast|get-tuple-element)\(%([\w.\-]+)")
COPIES = ("copy", "copy-start", "copy-done")


@functools.lru_cache(maxsize=None)
def normalise(path: str) -> Tuple[Tuple[str, ...], bool]:
    """(components, backward) of a scope path: every component but the
    last (the primitive), each with its transform wrappers peeled and
    remat components dropped; ``backward`` when any was transposed."""
    out: List[str] = []
    backward = False
    for comp in path.split("/")[:-1]:
        m = _WRAPPED.match(comp)
        while m and m.group(1) in TRANSFORMS:
            backward |= m.group(1) == "transpose"
            comp = m.group(2)
            m = _WRAPPED.match(comp)
        if comp not in DROPPED:
            out.append(comp)
    return tuple(out), backward


def part(path: str) -> str:
    """The part of ``PARTS`` an op with this scope path counts in."""
    comps = normalise(path)[0]
    for s in SUBLAYERS:
        if s in comps:
            return s
    if "layers" in comps:
        return "layers_self"
    for s in TOP:
        if s in comps:
            return s
    return "unscoped"


def matches(path: str, scope: str) -> bool:
    """Whether an op with this path counts in ``scope``: a name of the
    vocabulary, a part of ``PARTS``, or ``backward``."""
    if scope == "backward":
        return normalise(path)[1]
    if scope in ("layers_self", "unscoped"):
        return part(path) == scope
    return scope in normalise(path)[0]


@dataclasses.dataclass
class Scoped:
    """A reduced trace with each device op's scope path."""
    trace: Trace
    paths: Dict[str, List[str]]     # device -> scope path of each op of trace.ops[device]

    # -- steps -----------------------------------------------------------
    def step_ops(self, fn: str, t0: float, t1: float) -> Tuple[List[Tuple[Op, str]], int]:
        """Leaf ops (loops and calls left out) inside the executions of
        the jitted function ``fn`` that start in [t0, t1], with their
        paths, and the number of executions."""
        pre = f"jit_{fn}("
        found: List[Tuple[Op, str]] = []
        runs = 0
        for dev, mods in self.trace.modules.items():
            ops = self.trace.ops.get(dev, [])
            starts = [o.start_ns for o in ops]
            for m in mods:
                if not (m.name.startswith(pre) and t0 <= m.start_ns < t1):
                    continue
                runs += 1
                lo = bisect.bisect_left(starts, m.start_ns)
                hi = bisect.bisect_left(starts, m.end_ns)
                found.extend((ops[i], self.paths[dev][i]) for i in range(lo, hi)
                             if ops[i].base not in CONTROL_OPS)
        return found, runs

    def scope_ms(self, fn: str, scope: str, t0: float, t1: float) -> Optional[float]:
        """Summed time of the step's leaf ops that count in ``scope``, per
        execution of ``fn``; None without executions, or where the program
        opens no scope of its vocabulary."""
        ops, runs = self.step_ops(fn, t0, t1)
        if not runs or not any(part(p) != "unscoped" for _, p in ops):
            return None
        return sum(o.dur_ns for o, p in ops if matches(p, scope)) / runs / 1e6

    def by_op(self, fn: str, scope: str, t0: float, t1: float,
              n: int = 3) -> List[Tuple[str, float]]:
        """The ``n`` op names (numeric suffix dropped) that take most of
        ``scope``'s milliseconds per execution of ``fn``."""
        ops, runs = self.step_ops(fn, t0, t1)
        tot: Dict[str, float] = {}
        for o, p in ops:
            if matches(p, scope):
                tot[o.base] = tot.get(o.base, 0.0) + o.dur_ns / runs / 1e6
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def partition(self, fn: str, t0: float, t1: float) -> Optional[Dict[str, float]]:
        """Milliseconds per execution of ``fn`` in each part of ``PARTS``."""
        ops, runs = self.step_ops(fn, t0, t1)
        if not runs:
            return None
        out = dict.fromkeys(PARTS, 0.0)
        for o, p in ops:
            out[part(p)] += o.dur_ns / runs / 1e6
        return out


# -- paths from a trace file ------------------------------------------------
def from_file(path: str) -> Scoped:
    """Read the trace file at ``path`` (or the one under a directory)
    with each op's path from its ``tf_op`` stat."""
    from jax.profiler import ProfileData

    from . import xplane
    if os.path.isdir(path):
        path = trace_lib.find_xplane(path)
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    tr = trace_lib.from_profile(pd)
    paths: Dict[str, List[str]] = {}
    for plane in xplane.read(data):
        if plane.name not in tr.ops:
            continue
        events = next(l.events for l in plane.lines if l.name == "XLA Ops")
        pd_line = next(l for p in pd.planes if p.name == plane.name
                       for l in p.lines if l.name == "XLA Ops")
        starts = [e.start_ns for e in pd_line.events]
        order = sorted(range(len(starts)), key=starts.__getitem__)   # as from_profile sorts
        if [e[0] for e in events] != [e.name for e in pd_line.events]:
            raise ValueError(f"{plane.name}: the ops' stats do not line up with the trace")
        paths[plane.name] = [str(events[i][1].get("tf_op", "")).rsplit(":", 1)[0]
                             for i in order]
    return Scoped(trace=tr, paths=paths)


# -- paths from the programs that ran ---------------------------------------
def program_paths(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction -> path) of a compiled program's HLO.
    An instruction's path is its ``op_name``; one without (a copy or a
    buffer the compiler adds) takes the path of the instruction that
    calls its computation, as the profiler's ``tf_op`` does: a copy in
    the layer scan's body counts as the scan's. A copy with neither,
    at the program's top level, takes the path of the value it copies
    (through bitcasts and tuple elements), where that is a path of the
    program: the copy of the layer scan's stacked output into the step's
    result counts as the scan's, where ``tf_op`` leaves it empty; a
    parameter's prefetch keeps no path."""
    m = _HLO_MODULE.search(hlo_text)
    comps: Dict[str, List[Tuple[str, str, List[str]]]] = {}
    copied: Dict[str, Tuple[str, str]] = {}        # instruction -> (opcode, operand)
    entry, cur = "", None
    for line in hlo_text.splitlines():
        head = _HLO_COMP.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
            continue
        inst = _HLO_INST.match(line) if cur is not None else None
        if inst:
            op_name = _OP_NAME.search(line)
            src = _COPIED.search(line)
            if src:
                copied[inst.group(1)] = src.groups()
            cur.append((inst.group(1), op_name.group(1) if op_name else "",
                        [a or b for a, b in _CALLED.findall(line)]))
    def source(name: str) -> str:
        while name not in out and name in copied:
            name = copied[name][1]
        path = out.get(name, "")
        return path if path.startswith("jit(") else ""     # not an argument's name

    inherited, todo, out = {entry: ""}, [entry], {}
    while todo:
        comp = todo.pop()
        for name, op_name, called in comps.get(comp, []):
            path = op_name or inherited[comp]
            if not path and copied.get(name, ("",))[0] in COPIES:
                path = source(copied[name][1])
            if path:
                out[name] = path
            for c in called:
                if c not in inherited:
                    inherited[c] = path
                    todo.append(c)
    return (m.group(1) if m else ""), out


def live_programs() -> List[str]:
    """The HLO text of every program loaded on the local devices."""
    import jax
    out = []
    for exe in jax.local_devices()[0].client.live_executables():
        try:
            out.extend(mod.to_string() for mod in exe.hlo_modules())
        except RuntimeError:               # an executable that keeps no HLO
            continue
    return out


def from_programs(tr: Trace, hlo_texts: Iterable[str]) -> Scoped:
    """Each op's path from the HLO metadata of the program whose
    execution it ran in: the program of the execution's name whose
    instructions cover most of the execution's ops."""
    programs: Dict[str, List[Dict[str, str]]] = {}
    for text in hlo_texts:
        name, ops = program_paths(text)
        programs.setdefault(name, []).append(ops)
    paths: Dict[str, List[str]] = {}
    for dev, ops in tr.ops.items():
        starts = [o.start_ns for o in ops]
        got = [""] * len(ops)
        for m in tr.modules.get(dev, []):
            lo = bisect.bisect_left(starts, m.start_ns)
            hi = bisect.bisect_left(starts, m.end_ns)
            names = [ops[i].name for i in range(lo, hi)]
            cands = programs.get(m.name.split("(", 1)[0], [])
            if not cands or not names:
                continue
            best = max(cands, key=lambda c: sum(n in c for n in names))
            for i, n in zip(range(lo, hi), names):
                got[i] = best.get(n, "")
        paths[dev] = got
    return Scoped(trace=tr, paths=paths)


def of_run(run) -> Scoped:
    """The scoped trace of a traced run, from the programs still loaded
    (made once per run)."""
    if getattr(run, "_scoped", None) is None:
        run._scoped = from_programs(run.trace, live_programs())
    return run._scoped


def read_scope_ms(run, fn: str, scope: str, metric: str) -> Optional[float]:
    """A per-layer reader's value: ``scope_ms`` of the step ``fn`` in the
    traced window. It also prints the step's whole partition, and the
    partition's sum beside the step's device time, so that what the
    scope leaves out shows; the mixer's own scopes; and the ops that
    take most of the scope's time and of the unscoped time."""
    s = of_run(run)
    t0, t1 = run.t0, run.t1
    value = s.scope_ms(fn, scope, t0, t1)
    if value is None:
        return None
    parts = s.partition(fn, t0, t1)
    print(f"{metric}: {value:.3f} ms of {fn}; partition (ms per step) "
          + _ms(parts.items())
          + f"; sum {sum(parts.values()):.3f} of step_dev_ms {step_dev_ms(run, fn):.3f}")
    if scope == "mixer":
        print(f"{metric}: mixer scopes " + _ms((k, s.scope_ms(fn, k, t0, t1)) for k in MIXER))
    for k in (scope, "unscoped"):
        print(f"{metric}: top ops of {k} " + _ms(s.by_op(fn, k, t0, t1)))
    return value


def _ms(items) -> str:
    return ", ".join(f"{k} {v:.3f}" for k, v in items if v)
