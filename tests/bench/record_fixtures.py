"""Records the scoped trace fixtures in ``data/`` on a TPU, through the
benchmark's own drivers and its traced window:

- ``serve_scoped.xplane.pb``: two rounds of a 2-layer danube-shaped model
  (batch 2, prompt 256, 4 tokens out), as ``serve_tiny`` was recorded;
- ``train_scoped.xplane.pb``: two steps of the 2-layer model at
  sequence 256, fed by ``TokenPipeline``, less the trace's
  ``/host:metadata`` plane (the programs' HLO, 0.74 MB, which the
  reduction does not read), so that the file stays under 1 MB.

    python3 tests/bench/record_fixtures.py <out_dir>

It prints, per fixture, the size, each step's scope partition beside its
device time, and how far the paths from the trace file's ``tf_op`` stats
agree with those from the loaded programs' HLO metadata.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import harness, scopes, xplane  # noqa: E402

DANUBE = json.loads((ROOT / "bench/configs/h2o-danube-1.8b.json").read_text())
ARCH = {**DANUBE["arch"], "n_layers": 2, "attn_chunk": 128}     # 256 tokens take the flash kernel
CELLS = {
    "serve_scoped": ("serve", {"kind": "serve", "batch": 2, "prompt_len": 256, "gen_len": 4,
                               "in_flight": 3, "trace_rounds": 2,
                               "check": {"requests": 2, "ref_rows": 2}}),
    "train_scoped": ("train", {"kind": "train", "batch": 1, "seq_len": 256,
                               "corpus_tokens": 65536, "remat": "full",
                               "optimizer": {"b1": 0.9, "b2": 0.95, "eps": 1e-8,
                                             "weight_decay": 0.1, "clip_norm": 1.0},
                               "schedule": {"peak_lr": 1e-3, "warmup": 0, "total": 10000},
                               "in_flight": 2, "trace_steps": 2, "check": {"steps": 3}}),
}
STEPS = {"serve": ("prefill_step", "serve_step"), "train": ("train_step",)}
DROP = {"train_scoped": "/host:metadata"}


def without_plane(data: bytes, name: str) -> bytes:
    """A serialized ``XSpace`` without its plane called ``name``."""
    out, i = [], 0
    while i < len(data):
        start = i
        key, i = xplane._varint(data, i)
        size, i = xplane._varint(data, i)           # every field of XSpace is length-delimited
        body = data[i:i + size]
        i += size
        plane_name = next((bytes(v).decode() for f, _, v in xplane._fields(body) if f == 2), "")
        if not (key >> 3 == 1 and plane_name == name):
            out.append(data[start:i])
    return b"".join(out)


def record(name: str, out_dir: Path) -> None:
    kind, traffic = CELLS[name]
    cell = harness.Cell(name=name, chips=1, config={**DANUBE, "arch": ARCH}, traffic=traffic,
                        limits={}, end_to_end=[], per_layer=[])
    drv = harness.driver(kind).Driver(cell, 2 ** 35 + 11)
    drv.setup()
    tmp = tempfile.mkdtemp(prefix="fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        drv.window(rounds=drv.traced_rounds())
    jax.profiler.stop_trace()
    path = out_dir / f"{name}.xplane.pb"
    data = Path(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]).read_bytes()
    path.write_bytes(without_plane(data, DROP[name]) if name in DROP else data)
    shutil.rmtree(tmp, ignore_errors=True)

    s = scopes.from_file(str(path))
    live = scopes.from_programs(s.trace, scopes.live_programs())
    w = s.trace.span("bench.window")
    print(f"== {name}: {path.stat().st_size} bytes")
    for fn in STEPS[kind]:
        runs = s.trace.module_runs(fn, w.start_ns, w.end_ns)
        step = sum(r.dur_ns for r in runs) / len(runs) / 1e6
        parts = s.partition(fn, w.start_ns, w.end_ns)
        print(f"{fn}: {len(runs)} runs, {step:.4f} ms; partition "
              f"{json.dumps({k: round(v, 4) for k, v in parts.items()})}; "
              f"sum {sum(parts.values()):.4f}; backward "
              f"{s.scope_ms(fn, 'backward', w.start_ns, w.end_ns):.4f}")
        ops, _ = s.step_ops(fn, w.start_ns, w.end_ns)
        same = sum(o.dur_ns for (o, p), (_, q) in zip(ops, live.step_ops(fn, w.start_ns,
                                                                          w.end_ns)[0])
                   if p == q)
        print(f"{fn}: tf_op and HLO metadata agree on {100 * same / sum(o.dur_ns for o, _ in ops):.2f} % "
              f"of leaf time")
    for k in ("flash_attention", "decode_attention"):
        print(f"{k} calls: {len(s.trace.kernel_calls(k, w.start_ns, w.end_ns))}")
    print(f"idle gaps: {s.trace.idle_gaps(w.start_ns, w.end_ns, 5)}")
    drv.release()


def main() -> None:
    out_dir = Path(sys.argv[1])
    out_dir.mkdir(parents=True, exist_ok=True)
    harness.enable_compile_cache()
    for name in CELLS:
        record(name, out_dir)


if __name__ == "__main__":
    main()
