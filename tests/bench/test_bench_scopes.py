"""The program's named scopes, and the reduction that reads them
(``bench/scopes.py``): every op of the reduced danube and mamba2
steps compiled on the CPU carries a scope, the transform forms JAX wraps
them in normalise, ``scope_ms`` and the partition on hand-made events,
and the scoped traces recorded on a TPU v5e (``data/serve_scoped``,
``data/train_scoped``, by ``record_fixtures.py``; ``data/serve_tiny``
from before the program opened any scope)."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import scopes
from bench.scopes import Scoped
from bench.trace import Event, Op, Trace
from repro.configs import reduced_config
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.optim import adamw_init

DATA = Path(__file__).with_name("data")
DEV = "/device:TPU:0"


# -- normalising a path ---------------------------------------------------------------
@pytest.mark.parametrize("path,comps,backward", [
    ("jit(serve_step)/layers/while/body/closed_call/mixer/cache/dynamic_update_slice",
     ("jit(serve_step)", "layers", "while", "body", "closed_call", "mixer", "cache"), False),
    ("jit(train_step)/jvp(layers)/while/body/closed_call/mlp/dot_general",
     ("jit(train_step)", "layers", "while", "body", "closed_call", "mlp"), False),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/mixer/qkv/bsd,dhk->bshk/dot_general",
     ("jit(train_step)", "layers", "while", "body", "closed_call", "mixer", "qkv",
      "bsd,dhk->bshk"), True),
    ("jit(train_step)/transpose(jvp(head))/dot_general", ("jit(train_step)", "head"), True),
    ("jit(serve_step)/while", ("jit(serve_step)",), False),
    ("", (), False),
])
def test_paths_normalise(path, comps, backward):
    assert scopes.normalise(path) == (comps, backward)


@pytest.mark.parametrize("path,part", [
    ("jit(serve_step)/layers/while/body/dynamic_slice", "layers_self"),
    ("jit(serve_step)/layers/while", "layers_self"),
    ("jit(serve_step)/layers/while/body/closed_call/mixer/attention/jit(decode_attention)/"
     "pallas_call", "mixer"),
    ("jit(train_step)/mixer/qkv/cos", "mixer"),          # hoisted out of the scan
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/mlp/mul", "mlp"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(serve_step)/head/argmax", "head"),
    ("jit(serve_step)/while/body/dynamic_slice", "unscoped"),
])
def test_paths_fall_in_one_part(path, part):
    assert scopes.part(path) == part


# -- every op of the compiled steps carries a scope --------------------------------------
_INST = re.compile(r'(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(')
STRUCTURAL = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
              "while", "call", "conditional"}
LOOPS = {"while", "body", "cond", "closed_call"}        # what lax.scan and calls put in a path


def _computations(text):
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur and line.strip():
            comps[cur].append(line.strip())
    return comps, entry


def _executed(text):
    """Instructions of the computations that run as ops (the entry, loop
    bodies and conditions, calls), each with the instructions of the
    computations it fuses or wraps."""
    comps, entry = _computations(text)
    todo, seen = [entry], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            m = _INST.match(line)
            op = m.group(2)
            called = re.findall(r"(?:body|condition|to_apply|calls|branch_computations)="
                                r"\{?%([\w.\-]+)", line)
            if op in ("while", "call", "conditional"):
                todo.extend(called)
            inner = [x for k in called if op not in ("while", "call", "conditional")
                     for x in comps.get(k, [])]
            yield op, line, inner


def _op_name(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else None


def _compiled(arch, step):
    cfg = reduced_config(arch)
    S = jax.ShapeDtypeStruct
    B, P = 1, 64
    if step == "train":
        model, fn = make_train_step(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        args = (params, jax.eval_shape(adamw_init, params),
                {"tokens": S((B, P), jnp.int32), "labels": S((B, P), jnp.int32)},
                S((), jnp.int32))
    else:
        model, fn = (make_prefill_step if step == "prefill" else make_serve_step)(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: model.init_cache(B, P + 4))
        args = ((params, S((B, P), jnp.int32), cache, {}) if step == "prefill" else
                (params, S((B, 1), jnp.int32), cache, S((B,), jnp.int32)))
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("step", ["prefill", "decode", "train"])
@pytest.mark.parametrize("arch", ["h2o_danube_1_8b", "mamba2_780m"])
def test_every_op_of_a_step_carries_a_scope(arch, step):
    """Each instruction that comes from the program (it or what it fuses
    carries an ``op_name``) carries one of the program's scopes in its own
    ``op_name``; the rest are the compiler's own (copies it inserts,
    broadcasts and split reductions it wraps), with no ``op_name`` inside."""
    text = _compiled(arch, step)
    named = 0
    for op, line, inner in _executed(text):
        if op in STRUCTURAL:
            continue
        name = _op_name(line)
        if name is None:
            assert not any(_op_name(x) for x in inner), line[:200]
            continue
        named += 1
        assert scopes.part(name) != "unscoped", name
    assert named > 20
    comps = {c for n in re.findall(r'op_name="([^"]*)"', text) for c in scopes.normalise(n)[0]}
    opened = {c for c in comps if not c.startswith("jit(") and "->" not in c} - LOOPS
    assert opened <= set(scopes.VOCABULARY)         # one fixed vocabulary


def test_backward_forms_normalise():
    """The forms JAX 0.9 wraps a train step's scopes in: ``jvp(x)``
    forward, ``transpose(jvp(x))`` backward, ``checkpoint`` and
    ``rematted_computation`` for remat; none is left after normalising."""
    names = set(re.findall(r'op_name="([^"]*)"', _compiled("h2o_danube_1_8b", "train")))
    wrapped = {re.sub(r"\((\w+)\)", "(x)", re.sub(r"jvp\((\w+)\)", "jvp(x)", c))
               for n in names for c in n.split("/")[:-1] if "(" in c and not c.startswith("jit(")}
    assert wrapped == {"jvp(x)", "transpose(jvp(x))"}
    back = [n for n in names if "transpose(" in n]
    fwd = [n for n in names if "jvp(" in n and "transpose(" not in n]
    assert back and fwd
    for n in back + fwd:
        comps, backward = scopes.normalise(n)
        assert backward == (n in back)
        assert not any(c.startswith(("jvp(", "transpose(")) or c in scopes.DROPPED
                       for c in comps)
    assert any("rematted_computation" in n for n in back)


# -- scope_ms and the partition on hand-made events ----------------------------------------
def _scoped(paths_ops, modules=None):
    ops = [Op(f"op.{i}", s, d, base=base) for i, (_, base, s, d) in enumerate(paths_ops)]
    mods = modules or [Event("jit_serve_step(1)", 0, 50e6), Event("jit_serve_step(1)", 100e6, 50e6)]
    return Scoped(trace=Trace(ops={DEV: ops}, modules={DEV: mods}, spans=[]),
                  paths={DEV: [p for p, _, _, _ in paths_ops]})


L = "jit(serve_step)/layers/while"
HAND = [
    (f"{L}/body/dynamic_slice", "fusion", 0, 4e6),
    (L, "while", 4e6, 30e6),                                   # spans its children
    (f"{L}/body/closed_call/mixer/attention/jit(decode_attention)/pallas_call",
     "decode_attention", 5e6, 10e6),
    (f"{L}/body/closed_call/mlp/dot_general", "convolution_fusion", 16e6, 6e6),
    (L, "copy", 23e6, 8e6),
    ("jit(serve_step)/head/argmax", "fusion", 40e6, 2e6),
    ("", "copy-done", 44e6, 1e6),
    ("jit(other)/layers/while/body/add", "fusion", 60e6, 5e6),   # outside the step
    (f"{L}/body/closed_call/mixer/qkv/dot_general", "fusion", 100e6, 6e6),
]


def test_scope_ms_is_per_execution_of_the_step():
    s = _scoped(HAND)
    assert s.scope_ms("serve_step", "mixer", 0, 200e6) == pytest.approx(8.0)   # (10 + 6) / 2
    assert s.scope_ms("serve_step", "attention", 0, 200e6) == pytest.approx(5.0)
    assert s.scope_ms("serve_step", "layers_self", 0, 200e6) == pytest.approx(6.0)  # (4 + 8) / 2
    assert s.scope_ms("serve_step", "layers", 0, 200e6) == pytest.approx(17.0)
    assert s.scope_ms("serve_step", "unscoped", 0, 200e6) == pytest.approx(0.5)
    assert s.scope_ms("serve_step", "mixer", 0, 90e6) == pytest.approx(10.0)   # one execution
    assert s.scope_ms("prefill_step", "mixer", 0, 200e6) is None
    assert s.by_op("serve_step", "layers_self", 0, 200e6) == [("copy", 4.0), ("fusion", 2.0)]


def test_partition_covers_every_leaf_op_once():
    parts = _scoped(HAND).partition("serve_step", 0, 200e6)
    assert set(parts) == set(scopes.PARTS)
    assert parts["mixer"] == pytest.approx(8.0) and parts["mlp"] == pytest.approx(3.0)
    assert parts["layers_self"] == pytest.approx(6.0) and parts["head"] == pytest.approx(1.0)
    assert sum(parts.values()) == pytest.approx((4 + 10 + 6 + 8 + 2 + 1 + 6) / 2)


def test_backward_is_every_transposed_op():
    t = "jit(train_step)"
    s = _scoped([(f"{t}/jvp(layers)/while/body/closed_call/mixer/add", "fusion", 0, 3e6),
                 (f"{t}/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
                  "rematted_computation/mixer/add", "fusion", 3e6, 4e6),
                 (f"{t}/transpose(jvp(head))/dot_general", "fusion", 7e6, 2e6),
                 (f"{t}/optimizer/sqrt", "fusion", 9e6, 1e6)],
                modules=[Event("jit_train_step(7)", 0, 10e6)])
    assert s.scope_ms("train_step", "backward", 0, 1e9) == pytest.approx(6.0)
    assert s.scope_ms("train_step", "optimizer", 0, 1e9) == pytest.approx(1.0)
    assert s.scope_ms("train_step", "mixer", 0, 1e9) == pytest.approx(7.0)


def test_a_program_without_scopes_reads_nothing():
    s = _scoped([("jit(serve_step)/while/body/dynamic_slice", "fusion", 0, 4e6),
                 ("jit(serve_step)/while", "copy", 5e6, 4e6)])
    assert s.scope_ms("serve_step", "layers_self", 0, 1e9) is None
    assert s.partition("serve_step", 0, 1e9)["unscoped"] == pytest.approx(4.0)


# -- paths from the programs that ran -----------------------------------------------------
def test_paths_come_from_the_loaded_programs_hlo():
    def serve_step(x, w):
        with jax.named_scope("layers"):
            with jax.named_scope("mixer"):
                y = jnp.tanh(x @ w)
        with jax.named_scope("head"):
            return jnp.argmax(y, axis=-1)

    x = jnp.ones((8, 16))
    compiled = jax.jit(serve_step).lower(x, x.T).compile()       # kept loaded
    texts = [t for t in scopes.live_programs() if "jit(serve_step)/layers/mixer" in t]
    assert texts
    name, by_inst = scopes.program_paths(texts[-1])
    assert name == "jit_serve_step"
    insts = sorted(by_inst)
    ops = [Op(n, 10 + i, 1, base=n.split(".")[0]) for i, n in enumerate(insts)]
    tr = Trace(ops={DEV: ops + [Op("fusion.99", 500, 1, base="fusion")]},
               modules={DEV: [Event("jit_serve_step(123)", 0, 100)]}, spans=[])
    s = scopes.from_programs(tr, texts)
    assert s.paths[DEV] == [by_inst[n] for n in insts] + [""]    # outside any execution
    assert s.scope_ms("serve_step", "mixer", 0, 1e9) > 0
    del compiled


_HLO = """HloModule jit_serve_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%body.1 (p.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.1 = f32[4]{0} get-tuple-element(%p.1), index=1
  %add.1 = f32[4]{0} add(%get-tuple-element.1, %get-tuple-element.1), metadata={op_name="jit(serve_step)/layers/while/body/closed_call/mixer/add"}
  %copy.1 = f32[4]{0} copy(%add.1)
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%get-tuple-element.1, %copy.1)
}

ENTRY %main.1 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%Arg_0.1)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %custom-call.1 = f32[4]{0} custom-call(), custom_call_target="AllocateBuffer"
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(serve_step)/layers/while"}
  %get-tuple-element.5 = f32[4]{0} get-tuple-element(%while.1), index=1
  %bitcast.5 = f32[4]{0} bitcast(%get-tuple-element.5)
  %copy.9 = f32[4]{0} copy(%bitcast.5)
  ROOT %multiply.1 = f32[4]{0} multiply(%copy.9, %copy-done.1), metadata={op_name="jit(serve_step)/head/mul"}
}
"""


@pytest.mark.parametrize("inst,path", [
    ("add.1", "jit(serve_step)/layers/while/body/closed_call/mixer/add"),   # its own op_name
    ("copy.1", "jit(serve_step)/layers/while"),       # in the scan's body: its caller's
    ("copy.9", "jit(serve_step)/layers/while"),       # top level: the value it copies
    ("copy-start.1", None),                           # a parameter's prefetch
    ("copy-done.1", None),
    ("custom-call.1", None),                          # a buffer, no copy
    ("multiply.1", "jit(serve_step)/head/mul"),
])
def test_an_op_without_op_name_takes_its_callers_or_its_sources_path(inst, path):
    name, paths = scopes.program_paths(_HLO)
    assert name == "jit_serve_step"
    assert paths.get(inst) == path


# -- recorded traces -------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    loaded = {}

    def load(name):
        if name not in loaded:
            path = DATA / f"{name}.xplane.pb"
            if not path.exists():
                pytest.fail(f"missing fixture {path}")
            loaded[name] = scopes.from_file(str(path))
        return loaded[name]
    return load


def _window(s):
    w = s.trace.span("bench.window")
    return w.start_ns, w.end_ns


def test_unscoped_trace_names_its_layer_scans_copies(recorded):
    """Before the program opened scopes, the decode step's largest copies
    were the layer scan's own: 96 tagged ``jit(serve_step)/while``,
    1.33 ms over the window's six decode steps."""
    s = recorded("serve_tiny")
    copies = [o for o, p in zip(s.trace.ops[DEV], s.paths[DEV])
              if p == "jit(serve_step)/while" and o.base.startswith("copy")]
    assert len(copies) == 96
    assert sum(o.dur_ns for o in copies) / 1e6 == pytest.approx(1.33, abs=0.005)
    assert s.scope_ms("serve_step", "mixer", *_window(s)) is None


@pytest.mark.parametrize("name,fn", [("serve_scoped", "prefill_step"),
                                     ("serve_scoped", "serve_step"),
                                     ("train_scoped", "train_step")])
def test_recorded_partition_sums_to_the_step(recorded, name, fn):
    """The partition adds up to the step's device time, and what it leaves
    unscoped is only what the compiler adds at the program's top level
    (parameter prefetches, zeroed gradient buffers): ops with no name at
    all. In serving that is under 2 % of the step; in the 2-layer train
    step it is 4.4 % of 15 ms, a fixed cost that the 12-layer cell's
    725-ms step reads as 1.6 %."""
    s = recorded(name)
    t0, t1 = _window(s)
    runs = s.trace.module_runs(fn, t0, t1)
    step = sum(r.dur_ns for r in runs) / len(runs) / 1e6
    parts = s.partition(fn, t0, t1)
    assert sum(parts.values()) == pytest.approx(step, rel=0.02)
    ops, _ = s.step_ops(fn, t0, t1)
    assert all(p == "" for _, p in ops if scopes.part(p) == "unscoped")
    assert parts["unscoped"] <= (0.02 if name.startswith("serve") else 0.05) * step
    assert parts["mixer"] > 0 and parts["layers_self"] > 0 and parts["head"] > 0


def _programs_in(path):
    """The HLO text of each program a trace file holds (its
    ``/host:metadata`` plane: one ``Hlo Proto`` stat per program)."""
    from jax._src.lib import xla_client

    from bench import xplane
    out = []
    for plane in (v for f, _, v in xplane._fields(Path(path).read_bytes()) if f == 1):
        fields = list(xplane._fields(plane))
        if next(bytes(v).decode() for f, _, v in fields if f == 2) != "/host:metadata":
            continue
        for meta in (xplane._map_entry(v)[1] for f, _, v in fields if f == 4):
            for stat in (v for f, _, v in xplane._fields(meta) if f == 5):
                proto = bytes(next(v for f, _, v in xplane._fields(stat) if f == 6))
                module = next(bytes(v) for f, _, v in xplane._fields(proto) if f == 1)
                out.append(xla_client.XlaComputation(module).as_hlo_module().to_string())
    return out


def test_loaded_programs_give_the_trace_files_paths(recorded):
    """The readers' source (the programs' HLO metadata, an op without
    ``op_name`` taking its caller's) gives every op of the recorded
    serving trace the path that the trace file's ``tf_op`` gives it, bar
    the copies the compiler adds at the program's top level: ``tf_op``
    leaves them empty, the HLO names them by the value they copy. In the
    decode step that is the copy of the layer scan's stacked cache, which
    then counts in ``layers_self`` and leaves under 1 % of the step unscoped."""
    s = recorded("serve_scoped")
    programs = _programs_in(DATA / "serve_scoped.xplane.pb")
    assert {scopes.program_paths(t)[0] for t in programs} >= {"jit_prefill_step",
                                                              "jit_serve_step"}
    live = scopes.from_programs(s.trace, programs)
    t0, t1 = _window(s)
    for fn in ("prefill_step", "serve_step"):
        ops, runs = s.step_ops(fn, t0, t1)
        live_ops, live_runs = live.step_ops(fn, t0, t1)
        assert live_runs == runs and [o for o, _ in live_ops] == [o for o, _ in ops]
        differ = [(o.base, p, q) for (o, p), (_, q) in zip(ops, live_ops) if p != q]
        assert all(p == "" and base in scopes.COPIES and q.startswith(f"jit({fn})/")
                   for base, p, q in differ)
    top = [q for (o, p), (_, q) in zip(*(x.step_ops("serve_step", t0, t1)[0] for x in (s, live)))
           if p != q]
    assert top and all(q == "jit(serve_step)/layers/while" for q in top)
    parts, file_parts = (x.partition("serve_step", t0, t1) for x in (live, s))
    assert parts["layers_self"] > file_parts["layers_self"]
    assert parts["unscoped"] < min(0.01 * sum(parts.values()), file_parts["unscoped"])
    layer_copies = [p for o, p in zip(s.trace.ops[DEV], live.paths[DEV])
                    if o.base == "copy-done" and p == "jit(serve_step)/layers/while"]
    assert layer_copies                       # named by their caller, the layer scan


def test_recorded_kernels_keep_their_names(recorded):
    s = recorded("serve_scoped")
    t0, t1 = _window(s)
    rounds, layers, gen = 2, 2, 4
    assert len(s.trace.kernel_calls("flash_attention", t0, t1)) == rounds * layers
    assert len(s.trace.kernel_calls("decode_attention", t0, t1)) == rounds * layers * (gen - 1)


def test_recorded_train_step_names_backward_and_optimizer(recorded):
    s = recorded("train_scoped")
    t0, t1 = _window(s)
    runs = s.trace.module_runs("train_step", t0, t1)
    assert len(runs) == 2
    step = sum(r.dur_ns for r in runs) / len(runs) / 1e6
    assert 0 < s.scope_ms("train_step", "backward", t0, t1) < step
    assert 0 < s.scope_ms("train_step", "optimizer", t0, t1) < step
