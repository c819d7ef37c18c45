"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Sweeps shapes/dtypes per kernel and asserts allclose against ref.py.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import (ROWS, VMEM_BUDGET, _tile_plan,
                                          flash_attention)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("B,S,H,KV,d", [
    (2, 256, 4, 2, 64),
    (1, 384, 8, 8, 128),      # S % block_q != 0 under window 100 (bq 112)
    (2, 128, 4, 1, 64),       # MQA
    (1, 512, 16, 4, 32),
    (1, 256, 16, 2, 32),      # G = 8
    (1, 200, 32, 1, 64),      # MQA, S % block_q != 0 (bq = ROWS / 32)
])
# 100: a window shorter than block_k (128) and not a multiple of it
@pytest.mark.parametrize("window", [None, 128, 100])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, KV, d, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, d), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, d), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, d), dtype)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,KV,d,window", [
    (2, 256, 4, 2, 64, None),
    (1, 384, 16, 2, 32, 100),
    (1, 300, 8, 1, 64, 128),  # T % block_k != 0
])
def test_flash_attention_blocks_128(B, S, H, KV, d, window):
    """Forced 128 x 128 blocks: many query and KV blocks per call."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, d), jnp.float32)
    out = flash_attention(q, k, v, window=window, block_q=128, block_k=128,
                          interpret=True)
    exp = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(out, exp, **_tol(jnp.float32))


def test_flash_attention_bf16_weights_keep_16_bits():
    """bf16 inputs, one 512-key tile: the output is the f32 reference
    rounded to bf16 but for a few last-bit flips (RMS 2.5e-5 here). With
    one bf16 term per softmax weight in PV it reads about 7e-4."""
    ks = jax.random.split(KEY, 3)
    q = (2 * jax.random.normal(ks[0], (1, 512, 8, 80))).astype(jnp.bfloat16)
    k = (2 * jax.random.normal(ks[1], (1, 512, 2, 80))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 512, 2, 80)).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True).astype(jnp.float32)
    exp = ref.flash_attention_ref(*(x.astype(jnp.float32) for x in (q, k, v)))
    rounded = exp.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.sqrt(jnp.mean((out - rounded) ** 2))) < 1e-4


def _pipeline_walk(B, S, T, KV, bq, bk, window):
    """Live steps and K/V copies of a causal flash grid, by brute force:
    a tile is live where some (query, key) pair in it is visible; a step
    names the nearest live KV block of its query block, and the pipeline
    copies a block in only when the name differs from the step before."""
    nq, nk = -(-S // bq), -(-T // bk)
    live, copies, prev = 0, 0, None
    for iq in range(nq):
        qpos = np.arange(iq * bq, min(S, iq * bq + bq))[:, None]
        vis = []
        for ik in range(nk):
            kpos = np.arange(ik * bk, min(T, ik * bk + bk))[None, :]
            ok = kpos <= qpos
            if window is not None:
                ok &= kpos > qpos - window
            vis.append(bool(ok.any()))
        lo = vis.index(True)
        hi = nk - 1 - vis[::-1].index(True)
        assert all(vis[lo:hi + 1])
        live += hi - lo + 1
        for ik in range(nk):
            name = min(max(ik, lo), hi)
            copies += name != prev
            prev = name
    return B * KV * live, B * KV * copies


@pytest.mark.parametrize("B,S,H,KV,d,window,steps", [
    (8, 4096, 32, 8, 80, 4096, 4096),      # danube long prefill
    (1, 4096, 32, 8, 80, 4096, 512),       # danube train step
    (1, 4096, 16, 16, 128, None, 256),     # MHA
    (1, 4096, 32, 1, 80, None, 512),       # MQA
    (1, 4096, 16, 16, 128, 1000, None),    # a local window
])
def test_flash_tile_plan(B, S, H, KV, d, window, steps):
    plan = _tile_plan(B, S, S, H, KV, d, window, True)
    if steps is not None:
        assert math.prod(plan.grid) == steps
    assert plan.grid[:2] == (B, KV)
    assert (H // KV) * plan.block_q <= ROWS
    assert plan.block_q == S or plan.block_q % 16 == 0
    assert plan.vmem_bytes <= VMEM_BUDGET
    live, copies = _pipeline_walk(B, S, S, KV, plan.block_q, plan.block_k,
                                  plan.window)
    # dead steps fetch nothing: every copy is of a tile whose body runs
    assert (plan.live, plan.fetched) == (live, copies)
    assert plan.fetched <= plan.live < math.prod(plan.grid)


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 4, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 4, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- decode
@pytest.mark.parametrize("B,T,H,KV,d", [
    (2, 512, 4, 2, 64),
    (3, 300, 8, 1, 128),      # T % block_k != 0
    (2, 512, 4, 4, 64),
])
@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
# None: a per-layer (B, T, KV, d) cache; 2: layer 2 of a stacked
# (L, B, T, KV, d) cache, read in place through the layer index
@pytest.mark.parametrize("layer", [None, 2])
def test_decode_attention(B, T, H, KV, d, window, dtype, layer):
    ks = jax.random.split(KEY, 4)
    stack = () if layer is None else (4,)
    q = jax.random.normal(ks[0], (B, 1, H, d), dtype)
    kc = jax.random.normal(ks[1], stack + (B, T, KV, d), dtype)
    vc = jax.random.normal(ks[2], stack + (B, T, KV, d), dtype)
    lens = jnp.array([T // 3 + 1] * B, jnp.int32)
    # 128-position tiles: several per sequence, the last one partial at T 300
    out = decode_attention(q, kc, vc, lens, layer, window=window, block_k=128,
                           interpret=True)
    if layer is not None:
        kc, vc = kc[layer], vc[layer]
    exp = ref.decode_attention_ref(q, kc, vc, lens, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("layer", [None, 1])
def test_decode_attention_per_batch_lengths(layer):
    ks = jax.random.split(KEY, 4)
    B, T, H, d = 4, 256, 4, 64
    stack = () if layer is None else (3,)
    q = jax.random.normal(ks[0], (B, 1, H, d), jnp.float32)
    kc = jax.random.normal(ks[1], stack + (B, T, H, d), jnp.float32)
    vc = jax.random.normal(ks[2], stack + (B, T, H, d), jnp.float32)
    lens = jnp.array([1, 17, 100, 256], jnp.int32)
    out = decode_attention(q, kc, vc, lens, layer, block_k=128, interpret=True)
    exp = ref.decode_attention_ref(q, kc, vc, lens, layer)
    np.testing.assert_allclose(out, exp, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- ssd
@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (2, 512, 4, 64, 1, 128, 128),
    (1, 256, 8, 32, 2, 64, 64),
    (1, 128, 2, 64, 1, 32, 128),     # chunk > S → clamped
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, S, H, P, G, N, chunk, dtype):
    ks = jax.random.split(KEY, 4)
    x = (jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.1).astype(dtype)
    a = -jnp.abs(jax.random.normal(ks[1], (B, S, H), jnp.float32)) * 0.1
    b = (jax.random.normal(ks[2], (B, S, G, N), jnp.float32) * 0.1).astype(dtype)
    c = (jax.random.normal(ks[3], (B, S, G, N), jnp.float32) * 0.1).astype(dtype)
    y, hf = ssd_scan(x, a, b, c, chunk=min(chunk, S), interpret=True)
    ye, he = ref.ssd_scan_ref(x, a, b, c, chunk=min(chunk, S))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), **_tol(dtype))
    np.testing.assert_allclose(hf, he, atol=1e-2 if dtype == jnp.bfloat16
                               else 1e-4, rtol=1e-2)


def test_ssd_scan_matches_sequential_recurrence():
    """The chunked kernel equals the O(S) sequential SSM recurrence."""
    B, S, H, P, N = 1, 64, 2, 8, 16
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.2
    a = -jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.2
    b = jax.random.normal(ks[2], (B, S, 1, N)) * 0.2
    c = jax.random.normal(ks[3], (B, S, 1, N)) * 0.2
    y, hf = ssd_scan(x, a, b, c, chunk=16, interpret=True)

    h = np.zeros((B, H, P, N))
    ys = np.zeros((B, S, H, P))
    for t in range(S):
        at = np.exp(np.asarray(a[:, t]))                      # (B,H)
        h = at[:, :, None, None] * h + np.einsum(
            "bhp,bn->bhpn", np.asarray(x[:, t]), np.asarray(b[:, t, 0]))
        ys[:, t] = np.einsum("bhpn,bn->bhp", h, np.asarray(c[:, t, 0]))
    np.testing.assert_allclose(y, ys, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hf, h, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- rglru
@pytest.mark.parametrize("B,S,W,bt", [
    (2, 512, 256, 128),
    (1, 384, 128, 128),
    (2, 256, 512, 256),
])
def test_rglru_scan(B, S, W, bt):
    ks = jax.random.split(KEY, 2)
    a_log = -jnp.abs(jax.random.normal(ks[0], (B, S, W), jnp.float32)) * 0.5
    b = jax.random.normal(ks[1], (B, S, W), jnp.float32)
    h, hl = rglru_scan(a_log, b, block_t=bt, interpret=True)
    he, hle = ref.rglru_scan_ref(a_log, b)
    np.testing.assert_allclose(h, he, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(hl, hle, atol=2e-5, rtol=2e-5)


def test_rglru_matches_sequential():
    B, S, W = 1, 96, 32
    ks = jax.random.split(KEY, 2)
    a_log = -jnp.abs(jax.random.normal(ks[0], (B, S, W))) * 0.3
    b = jax.random.normal(ks[1], (B, S, W))
    h, _ = rglru_scan(a_log, b, block_t=32, interpret=True)
    a = np.exp(np.asarray(a_log))
    hs = np.zeros((B, W))
    expected = np.zeros((B, S, W))
    for t in range(S):
        hs = a[:, t] * hs + np.asarray(b[:, t])
        expected[:, t] = hs
    np.testing.assert_allclose(h, expected, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- gradients
# Each kernel's custom_vjp backward must equal jax.grad of its reference.
def _grad_check(kernel_fn, ref_fn, args, argnums, tol):
    def loss(fn):
        def f(*a):
            outs = fn(*a)
            outs = outs if isinstance(outs, tuple) else (outs,)
            return sum(jnp.sum(jnp.sin(o.astype(jnp.float32))) for o in outs)
        return f
    got = jax.grad(loss(kernel_fn), argnums=argnums)(*args)
    want = jax.grad(loss(ref_fn), argnums=argnums)(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_grad(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 32), jnp.float32)
    _grad_check(
        lambda q, k, v: flash_attention(q, k, v, window=window,
                                        interpret=True),
        lambda q, k, v: ref.flash_attention_ref(q, k, v, window=window),
        (q, k, v), (0, 1, 2), 1e-4)


def test_ssd_scan_grad():
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (1, 128, 2, 16)) * 0.2
    a = -jnp.abs(jax.random.normal(ks[1], (1, 128, 2))) * 0.2
    b = jax.random.normal(ks[2], (1, 128, 1, 32)) * 0.2
    c = jax.random.normal(ks[3], (1, 128, 1, 32)) * 0.2
    _grad_check(
        lambda *a_: ssd_scan(*a_, chunk=32, interpret=True),
        lambda *a_: ref.ssd_scan_ref(*a_, chunk=32),
        (x, a, b, c), (0, 1, 2, 3), 1e-4)


def test_rglru_scan_grad():
    ks = jax.random.split(KEY, 2)
    a_log = -jnp.abs(jax.random.normal(ks[0], (1, 128, 128))) * 0.5
    b = jax.random.normal(ks[1], (1, 128, 128))
    _grad_check(
        lambda a_, b_: rglru_scan(a_, b_, block_t=32, interpret=True),
        ref.rglru_scan_ref, (a_log, b), (0, 1), 1e-4)
