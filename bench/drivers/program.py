"""Where the benchmark meets the program under test: its configuration
type, its step builders (looked up on each use, so that a test can
plant a fault in them), and the layout of its parameter tree, which the
seeded weights are fitted to."""
from __future__ import annotations

import jax

from .. import weights
from ..harness import Cell, reference


def arch_config(arch: dict):
    """The program's ``ArchConfig`` from a configuration file's ``arch``."""
    from repro.models.config import ArchConfig
    fields = {k: tuple(v) if isinstance(v, list) else v for k, v in arch.items()}
    return ArchConfig(**fields)


def steps():
    from repro.launch import steps as mod
    return mod


def params(model, cell: Cell, seed: int):
    """The cell's seeded weights, made on the device in one call and
    arranged as the program's parameter tree."""
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    spec = reference(cell.config["reference"]).param_spec(cell.arch)
    weights.check_layout(spec, like)
    return weights.to_program_tree(weights.make(spec, seed), like)
