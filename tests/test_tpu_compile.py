"""The four Pallas kernels compile for a described TPU v5e chip at
published widths (no chip needed: the TPU compiler runs here)."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, shapes, one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (kernel call, argument shapes) at published widths: h2o-danube-1.8b
# attention (32 heads / 8 KV heads, head dim 80, window 4096), mamba2-780m
# SSD (48 heads x 64, state 128), recurrentgemma-9b RG-LRU (W = 4096)
CASES = {
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096),
        [((8, 4096, 32, 80), BF), ((8, 4096, 8, 80), BF),
         ((8, 4096, 8, 80), BF)]),
    "decode_attention": (
        lambda q, k, v, n: decode_attention(q, k, v, n),
        [((8, 1, 32, 80), BF), ((8, 4096, 8, 80), BF),
         ((8, 4096, 8, 80), BF), ((8,), I32)]),
    "ssd_scan": (
        lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=256),
        [((8, 2048, 48, 64), BF), ((8, 2048, 48), F32),
         ((8, 2048, 1, 128), BF), ((8, 2048, 1, 128), BF)]),
    "rglru_scan": (
        lambda a, b: rglru_scan(a, b),
        [((2, 2048, 4096), F32), ((2, 2048, 4096), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    """The kernel compiles, and its op is named after it (``name=``)."""
    fn, shapes = CASES[name]
    text = _compiled_text(fn, shapes, one_chip)
    assert re.search(rf"%{name}\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", text)


def test_flash_attention_train_step_compiles_for_v5e(one_chip):
    """Forward kernel plus the reference backward, as a train step runs."""
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, window=4096)
                       .astype(F32) ** 2)

    text = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                          [((1, 4096, 32, 80), BF), ((1, 4096, 8, 80), BF),
                           ((1, 4096, 8, 80), BF)], one_chip)
    assert "tpu_custom_call" in text


def test_flash_attention_mqa_compiles_for_v5e(one_chip):
    """One KV head for 32 query heads: the whole group folds into one
    tile of 32 x block_q rows, the largest group the plan folds."""
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((2, 4096, 32, 128), BF), ((2, 4096, 1, 128), BF),
         ((2, 4096, 1, 128), BF)], one_chip)
    assert re.search(r"%flash_attention\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
