"""Callable launchers, the compile-cache helper and chip_smoke.py's
phases, at reduced configs on the CPU (the script's main refuses the CPU)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import reduced_config
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


CACHE_FLAGS = ("jax_compilation_cache_dir", "jax_compilation_cache_include_metadata_in_key",
               "jax_traceback_in_locations_limit")


@pytest.fixture()
def cache_dir_config():
    before = {k: getattr(jax.config, k) for k in CACHE_FLAGS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_honours_env(monkeypatch, cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch,
                                                   cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = compile_cache.enable_compile_cache()
    assert where == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == where
    assert compile_cache.enable_compile_cache() == where      # stable


@pytest.mark.parametrize("other", ["mlp", None])
def test_compile_cache_key_holds_the_scopes(monkeypatch, cache_dir_config, other):
    """Programs alike but for a named scope get cache entries of their
    own, so neither loads the other's executable and its scopes."""
    import jax.numpy as jnp
    import numpy as np
    from jax._src import cache_key, compiler

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    compile_cache.enable_compile_cache()

    def key(scope):
        def f(x):
            if scope is None:
                return jnp.sin(x) * 2
            with jax.named_scope(scope):
                return jnp.sin(x) * 2
        module = jax.jit(f).lower(jnp.ones(4)).compiler_ir("stablehlo")
        return cache_key.get(module, np.array(jax.devices()[:1]),
                             compiler.get_compile_options(1, 1), jax.devices()[0].client)

    assert key("mixer") == key("mixer")
    assert key("mixer") != key(other)


def test_phase_serve_attention_reduced():
    cfg = reduced_config("h2o_danube_1_8b")
    r = chip_smoke.phase_serve("a", cfg, batch=2, prompt_len=48, gen_len=4)
    assert r["tokens"].shape == (2, 4)
    assert len(r["decode_ms"]) == 3 and r["compile_s"] > 0
    assert r["kernel_calls"] == {"prefill": 0, "decode": 0}   # CPU: refs


def test_phase_serve_ssm_reduced():
    cfg = reduced_config("mamba2_780m")
    r = chip_smoke.phase_serve("b", cfg, batch=2, prompt_len=64, gen_len=3)
    assert r["tokens"].shape == (2, 3)


def test_phase_train_reduced():
    cfg = reduced_config("h2o_danube_1_8b")
    r = chip_smoke.phase_train("c", cfg, steps=2, seq=32, batch=2)
    assert len(r["losses"]) == 2 and len(r["step_ms"]) == 2


def test_phase_kernels_small_interpret():
    errs = chip_smoke.phase_kernels("d", interpret=True, small=True)
    assert set(errs) == {"flash_attention", "decode_attention", "ssd_scan",
                         "rglru_scan"}
    assert max(errs.values()) <= 1.0


def test_phase_pipeline_reduced_on_four_host_devices():
    code = ("import chip_smoke, jax; "
            "r = chip_smoke.phase_pipeline('p', jax.devices()[:4], "
            "n_layers=8, d_model=128, d_ff=256, tokens=4); "
            "print('REL', r['rel_err'])")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REL" in out.stdout
    assert "layers per stage" in out.stdout


def test_main_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_main_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
