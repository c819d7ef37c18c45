"""A run at a small size on the CPU, with the look for a chip skipped
and the timed path broken underneath (``bench/faults.py``): ``correct``
comes out false for each fault a one-chip cell can have, and true for
the unbroken program.

The cells keep their traffic's kind and check, at the program's reduced
sizes served in bfloat16. Their limits are set for this size from my
CPU readings on seeds 2**36 + 1..10 (the chip sizes' limits are in
``bench/limits``): served_logit_gap, program at most 0.047 and int8
control at least 0.102 over 256 served tokens; training, program at
most loss 8.7e-4 (the largest gap over three steps, so at least the
first step's), gradient 2.3e-3 and change 0.017, the control's
gradient at least 0.010, and the faults at least loss 0.0105 (loss
altered), gradient 0.25 and change 0.14 (half the batch)."""
import dataclasses

import pytest

from bench import faults, harness
from bench import run as bench_run
from bench.drivers import program
from repro.configs import reduced_config

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2 ** 35 + 11
SERVE = {"batch": 8, "prompt_len": 64, "gen_len": 32, "check": {"requests": 8, "ref_rows": 8}}
SERVE_LIMITS = {"served_logit_gap": 0.08}
TRAIN_LIMITS = {"first_loss_gap": 0.003, "grad_norm_gap": 0.006, "change_norm_gap": 0.05}
CELLS = {
    "danube-serve-long": ("h2o_danube_1_8b", SERVE, SERVE_LIMITS),
    "mamba2-serve-long": ("mamba2_780m", SERVE, SERVE_LIMITS),
    "danube-serve-chat": ("h2o_danube_1_8b", SERVE, SERVE_LIMITS),
    "danube-train-4k": ("h2o_danube_1_8b", {"seq_len": 64, "corpus_tokens": 4096},
                        TRAIN_LIMITS),
}


def small_cell(name):
    """The cell at the program's reduced sizes, served in bfloat16, with
    short requests and this size's limits."""
    arch, traffic, limits = CELLS[name]
    cell = harness.load_cell(name)
    a = {**dataclasses.asdict(reduced_config(arch)), "dtype": "bfloat16"}
    return dataclasses.replace(
        cell, config={**cell.config, "arch": a}, traffic={**cell.traffic, **traffic},
        limits={"numbers": {k: {"limit": v} for k, v in limits.items()}})


def _run(monkeypatch, name, fault=None):
    cell = small_cell(name)
    monkeypatch.setattr(program, "steps", lambda: faults.steps_with(cell.traffic["kind"], fault))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    return bench_run.run_cell(cell, SEED, 0.5, False, DEVICE)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_unbroken_program_is_correct(monkeypatch, name):
    result, check = _run(monkeypatch, name)
    assert result["correct"], check


@pytest.mark.parametrize("name,fault", [(n, f) for n in sorted(CELLS) for f in
                                        faults.FAULTS["train" if "train" in n else "serve"]])
def test_a_broken_step_is_not_correct(monkeypatch, name, fault):
    result, check = _run(monkeypatch, name, fault)
    assert not result["correct"], check
