"""The ``granite-h-serve-8k`` cell at the program's reduced size on the
CPU (one period of the Granite 4.0-H hybrid, served in bfloat16), with
the look for a chip skipped: ``correct`` comes out true for the unbroken
program and false for each fault a one-chip serving cell can have
(``bench/faults.py``), and the int8 control reads above the limit where
the program reads below it (``bench/control.py``).

The limit is set for this size from my CPU readings on seeds 2**35 + 11
and 2**36 + 1..12: ``served_logit_gap`` of the program at most 0.00107,
of the int8 control at least 0.00184, and of the faults at least 0.050
(state unchanged 0.057, half the batch 0.051, token altered 0.062); the
limit lies near their geometric middle (the chip size's limit is in
``bench/limits``)."""
import dataclasses

import pytest

from bench import control, faults, harness
from bench import run as bench_run
from bench.drivers import program
from repro.configs import reduced_config

CELL = "granite-h-serve-8k"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2 ** 35 + 11
CONTROL_SEEDS = (2 ** 36 + 1, 2 ** 36 + 2, 2 ** 36 + 3)
SERVE = {"batch": 8, "prompt_len": 64, "gen_len": 32, "check": {"requests": 8, "ref_rows": 8}}
LIMIT = 0.0014


def small_cell():
    cell = harness.load_cell(CELL)
    a = {**dataclasses.asdict(reduced_config("granite_4_0_h_micro")), "dtype": "bfloat16"}
    return dataclasses.replace(
        cell, config={**cell.config, "arch": a}, traffic={**cell.traffic, **SERVE},
        limits={"numbers": {"served_logit_gap": {"limit": LIMIT}}})


def _run(monkeypatch, fault=None):
    cell = small_cell()
    monkeypatch.setattr(program, "steps", lambda: faults.steps_with("serve", fault))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    return bench_run.run_cell(cell, SEED, 0.5, False, DEVICE)


def test_the_unbroken_program_is_correct(monkeypatch):
    result, check = _run(monkeypatch)
    assert result["correct"], check


@pytest.mark.parametrize("fault", sorted(faults.FAULTS["serve"]))
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    result, check = _run(monkeypatch, fault)
    assert not result["correct"], check


def test_control_fails_where_the_program_passes(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    for seed in CONTROL_SEEDS:
        r = control.readings(small_cell(), seed, control=True)
        assert r["served_logit_gap"] <= LIMIT < r["control_served_logit_gap"], r
