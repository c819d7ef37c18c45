"""Persistent compilation cache shared by the launchers and the chip smoke.

JAX keys cache entries by the cache directory too, so the directory must
not move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here; otherwise the cache lives at a
fixed ``.jax_cache/`` in the checkout root (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    where = os.environ.get(ENV_VAR)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
