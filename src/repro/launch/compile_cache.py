"""Persistent compilation cache shared by the launchers and the chip smoke.

JAX keys cache entries by the cache directory too, so the directory must
not move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here; otherwise the cache lives at a
fixed ``.jax_cache/`` in the checkout root (listed in ``.gitignore``).

JAX's key leaves op metadata out by default, so a program that differs
from a cached one only in its named scopes would load the cached
executable, and its ops would carry the cached program's scopes. The key
here holds the metadata; the source file and line are left out of it
(``jax_traceback_in_locations_limit`` 0, which takes them out of the
HLO metadata too), so the key does not change with the checkout's path
or with a moved line.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    where = os.environ.get(ENV_VAR)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
