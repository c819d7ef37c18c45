"""The four Pallas kernels compile for a described TPU v5e chip at
published widths (no chip needed: the TPU compiler runs here), and the
serving decode step updates its donated cache in place."""
import dataclasses
import os
import re
from unittest import mock

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

# (kernel call, argument shapes) at published widths, keyed
# "<kernel>[.<variant>]": h2o-danube-1.8b attention (32 heads / 8 KV
# heads, head dim 80, window 4096; its decode also through layer 1 of a
# stacked 2-layer cache), qwen3-32b decode (head dim 128: the row-major
# cache view), mamba2-780m SSD (48 heads x 64, state 128),
# recurrentgemma-9b RG-LRU (W = 4096)
CASES = {
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096),
        [((8, 4096, 32, 80), BF), ((8, 4096, 8, 80), BF),
         ((8, 4096, 8, 80), BF)]),
    "decode_attention": (
        lambda q, k, v, n: decode_attention(q, k, v, n),
        [((8, 1, 32, 80), BF), ((8, 4096, 8, 80), BF),
         ((8, 4096, 8, 80), BF), ((8,), I32)]),
    "decode_attention.stacked": (
        lambda q, k, v, n, layer: decode_attention(q, k, v, n, layer),
        [((8, 1, 32, 80), BF), ((2, 8, 4096, 8, 80), BF),
         ((2, 8, 4096, 8, 80), BF), ((8,), I32), ((), I32)]),
    "decode_attention.hd128": (
        lambda q, k, v, n, layer: decode_attention(q, k, v, n, layer),
        [((8, 1, 64, 128), BF), ((2, 8, 4096, 8, 128), BF),
         ((2, 8, 4096, 8, 128), BF), ((8,), I32), ((), I32)]),
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
    kernel = name.split(".")[0]
    assert re.search(rf"%{kernel}\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"", text)


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


# serving decode steps at published widths, cut in depth: (arch, layers,
# batch, cache length). Granite runs two periods of its ten-layer unit:
# with one, the compiler drops the one-trip layer loop.
SERVE_CASES = {
    "h2o_danube_1_8b": ("h2o_danube_1_8b", 2, 8, 4096 + 32),   # ring of 4096
    "mamba2_780m": ("mamba2_780m", 2, 32, 2048 + 32),
    "granite_4_0_h_micro": ("granite_4_0_h_micro", 20, 8, 8256),
}


@pytest.mark.parametrize("name", sorted(SERVE_CASES))
def test_serve_step_updates_the_cache_in_place(name, one_chip):
    """The donated decode step, Pallas path on, copies no cache buffer:
    no ``copy`` (or ``copy-start``) in the optimized program has the
    shape of a cache leaf, stacked or one layer's slice, and each cache
    output aliases its donated parameter."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.steps import make_serve_step

    arch, layers, B, T = SERVE_CASES[name]
    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    model, step = make_serve_step(cfg)

    def structs(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = structs(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = structs(jax.eval_shape(lambda: model.init_cache(B, T)))
    token = jax.ShapeDtypeStruct((B, 1), I32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)
    with mock.patch.object(ops, "use_pallas", lambda: True):
        text = jax.jit(step, donate_argnums=(2,)).lower(
            params, token, cache, pos).compile().as_text()

    leaves = jax.tree.leaves(cache)
    shapes = {s for a in leaves for s in (a.shape, a.shape[1:], (1,) + a.shape[1:])}
    copies = [line.strip() for line in text.splitlines()
              for m in [re.search(r"= \w+\[([\d,]*)\]\{[^}]*\} copy(?:-start)?\(", line)]
              if m and tuple(int(x) for x in m.group(1).split(",") if x) in shapes]
    assert not copies, "\n".join(c[:160] for c in copies)

    # outputs: (next token, cache leaves); parameters: (param leaves,
    # token, cache leaves, pos)
    header = text.splitlines()[0]
    alias = header.split("input_output_alias=")[1].split("entry_computation_layout")[0]
    pairs = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}", alias))
    first = len(jax.tree.leaves(params)) + 1
    assert {int(k): int(v) for k, v in pairs.items()} == {
        1 + j: first + j for j in range(len(leaves))}
