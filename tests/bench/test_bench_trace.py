"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e by the harness itself (``data/serve_tiny.xplane.pb``: two rounds
of a 2-layer danube-shaped model, batch 2, prompt 256, 4 tokens out)."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace
from bench.trace import Event, Op, Trace

FIXTURE = Path(__file__).with_name("data") / "serve_tiny.xplane.pb"
DEV = "/device:TPU:0"


def _op(name, start, dur, kernel=False):
    return Op(name, start, dur, base=trace._SUFFIX.sub("", name), kernel=kernel)


@pytest.fixture
def hand():
    ops = [_op("fusion.1", 0, 10), _op("while.3", 5, 15), _op("flash_attention.4", 6, 4, True),
           _op("fusion.2", 30, 10), _op("decode_attention.1", 45, 5, True)]
    spans = [Event("bench.window", 0, 100), Event("bench.fetch", 18, 14),
             Event("bench.decode", 41, 3), Event("bench.round", 0, 60)]
    mods = [Event("jit_prefill_step(1)", 0, 20), Event("jit_serve_step(2)", 30, 10),
            Event("jit_serve_step(2)", 45, 5)]
    return Trace(ops={DEV: ops}, modules={DEV: mods}, spans=spans)


def test_busy_is_the_clipped_union(hand):
    assert hand.busy_intervals(DEV, 2, 48) == [(2, 20), (30, 40), (45, 48)]
    assert hand.busy_s(2, 48) == pytest.approx(31e-9)


def test_idle_gaps_are_named_by_the_innermost_span(hand):
    assert hand.idle_gaps(0, 50) == [["fetch", 10e-9], ["decode", 5e-9]]
    assert hand.host_doing(99) == "window"
    assert hand.host_doing(200) == "outside any span"


def test_kernels_modules_and_top_ops(hand):
    assert [o.name for o in hand.kernel_calls("flash_attention", 0, 100)] == ["flash_attention.4"]
    assert hand.kernel_calls("fusion", 0, 100) == []          # not a kernel
    assert len(hand.module_runs("serve_step", 0, 100)) == 2
    assert len(hand.module_runs("serve_step", 0, 40)) == 1
    top = dict(hand.top_ops(0, 100))
    assert "while" not in top and top["fusion"] == pytest.approx(20e-9)


def test_op_names_parse_from_hlo_text():
    class E:
        name = ('%flash_attention.4 = bf16[2,32,4096,80]{3,2,1,0} custom-call(%a, %b), '
                'custom_call_target="tpu_custom_call"')
        start_ns, duration_ns = 1.0, 2.0
    op = trace._op(E)
    assert (op.name, op.base, op.kernel) == ("flash_attention.4", "flash_attention", True)


def _union_by_raster(intervals, t0, t1):
    """Busy time by marking every nanosecond (the plain loop version)."""
    grid = np.zeros(int(t1 - t0) + 1, bool)
    for a, b in intervals:
        lo, hi = max(int(a - t0), 0), min(int(b - t0), len(grid) - 1)
        if hi > lo:
            grid[lo:hi] = True
    return grid.sum()


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.exists():
        pytest.fail(f"missing fixture {FIXTURE}")
    return trace.load(str(FIXTURE))


def test_recorded_trace_has_the_window_and_one_chip(recorded):
    w = recorded.span("bench.window")
    assert list(recorded.ops) == [DEV]
    busy = recorded.busy_s(w.start_ns, w.end_ns)
    assert 0 < busy <= (w.end_ns - w.start_ns) / 1e9


def test_recorded_busy_matches_a_raster(recorded):
    w = recorded.span("bench.window")
    t0, t1 = int(w.start_ns), int(w.start_ns) + 20_000_000       # first 20 ms
    ops = [(o.start_ns, o.end_ns) for o in recorded.ops[DEV]]
    raster = _union_by_raster(ops, t0, t1)
    got = recorded.busy_s(t0, t1) * 1e9
    assert abs(got - raster) <= 2 * len(ops)                    # 1 ns rounding per edge


def test_recorded_steps_and_kernels_are_counted(recorded):
    w = recorded.span("bench.window")
    rounds, layers, gen = 2, 2, 4
    assert len(recorded.module_runs("prefill_step", w.start_ns, w.end_ns)) == rounds
    assert len(recorded.module_runs("serve_step", w.start_ns, w.end_ns)) == rounds * (gen - 1)
    assert len(recorded.kernel_calls("flash_attention", w.start_ns, w.end_ns)) == rounds * layers
    assert len(recorded.kernel_calls("decode_attention", w.start_ns, w.end_ns)) == \
        rounds * layers * (gen - 1)
    gaps = recorded.idle_gaps(w.start_ns, w.end_ns)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert {g[0] for g in gaps} <= {"window", "round", "prompts", "prefill", "decode", "fetch"}
