"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode — the kernel body runs in Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    flash_attention,
    rglru_linear_scan,
    wkv6,
)
from repro.kernels.ref import (
    ref_attention,
    ref_rglru,
    ref_wkv6,
)

TOL = dict(rtol=2e-2, atol=2e-3)
TOL32 = dict(rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- flash attn

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,h,kh,d,causal,window,softcap",
    [
        (1, 128, 128, 2, 2, 64, True, 0, 0.0),      # MHA causal
        (2, 128, 128, 4, 2, 64, True, 0, 0.0),      # GQA
        (1, 256, 256, 2, 1, 128, True, 128, 0.0),   # MQA + sliding window
        (1, 128, 128, 2, 2, 64, True, 0, 50.0),     # gemma2 softcap
        (1, 128, 128, 2, 2, 256, False, 0, 0.0),    # non-causal (encoder)
        (1, 384, 384, 2, 2, 64, True, 0, 0.0),      # multi-tile both axes
    ],
)
def test_flash_attention_matches_ref(b, sq, sk, h, kh, d, causal, window,
                                     softcap, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, sk, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, sk, kh, d), dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        interpret=True,
    )
    ref = ref_attention(q, k, v, causal=causal, window=window,
                        softcap=softcap)
    # bf16 outputs are O(1): one ulp at 1.0 is 7.8e-3, so atol below that
    # flags single-element online-softmax rounding differences as failures
    tol = dict(rtol=2e-2, atol=8e-3) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol
    )


def test_flash_attention_small_blocks():
    """Block sizes that force many tiles (exercises the online softmax)."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-4
    )


# --------------------------------------------------------------- rg-lru

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,w,bs,bw", [
    (2, 64, 128, 16, 128),
    (1, 128, 256, 128, 128),   # multiple width tiles
    (1, 96, 128, 32, 128),     # multiple seq tiles
])
def test_rglru_matches_ref(b, s, w, bs, bw, dtype):
    ks = jax.random.split(jax.random.key(2), 3)
    a = jax.random.uniform(ks[0], (b, s, w), jnp.float32, 0.7, 0.999)
    x = jax.random.normal(ks[1], (b, s, w), dtype)
    h0 = jax.random.normal(ks[2], (b, w), jnp.float32)
    ys, hf = rglru_linear_scan(a, x, h0, block_s=bs, block_w=bw,
                               interpret=True)
    ys_ref, hf_ref = ref_rglru(a, x, h0)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(
        np.asarray(ys, np.float32), np.asarray(ys_ref), **tol
    )
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_ref),
                               rtol=1e-4, atol=1e-4)


def test_rglru_chunked_equals_whole():
    """State handoff: two chunks of S/2 == one chunk of S."""
    ks = jax.random.split(jax.random.key(3), 3)
    b, s, w = 1, 64, 128
    a = jax.random.uniform(ks[0], (b, s, w), jnp.float32, 0.8, 0.99)
    x = jax.random.normal(ks[1], (b, s, w), jnp.float32)
    h0 = jnp.zeros((b, w), jnp.float32)
    y_all, h_all = rglru_linear_scan(a, x, h0, interpret=True)
    y1, h1 = rglru_linear_scan(a[:, :32], x[:, :32], h0, interpret=True)
    y2, h2 = rglru_linear_scan(a[:, 32:], x[:, 32:], h1, interpret=True)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_all),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_all),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- wkv6

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,dk,dv,bs", [
    (1, 32, 2, 16, 16, 16),
    (2, 64, 2, 64, 64, 32),    # full rwkv6 head size, multiple seq tiles
    (1, 48, 1, 32, 16, 16),    # dk != dv
])
def test_wkv6_matches_ref(b, s, h, dk, dv, bs, dtype):
    ks = jax.random.split(jax.random.key(4), 6)
    r = jax.random.normal(ks[0], (b, s, h, dk), dtype)
    k = jax.random.normal(ks[1], (b, s, h, dk), dtype)
    v = jax.random.normal(ks[2], (b, s, h, dv), dtype)
    w = jax.random.uniform(ks[3], (b, s, h, dk), jnp.float32, 0.8, 0.999)
    u = jax.random.normal(ks[4], (h, dk), jnp.float32)
    s0 = jax.random.normal(ks[5], (b, h, dk, dv), jnp.float32)
    y, sf = wkv6(r, k, v, w, u, s0, block_s=bs, interpret=True)
    y_ref, sf_ref = ref_wkv6(r, k, v, w, u, s0)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), **tol
    )
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sf_ref),
                               rtol=1e-3, atol=1e-3)


def test_wkv6_chunked_equals_whole():
    ks = jax.random.split(jax.random.key(5), 6)
    b, s, h, d = 1, 64, 1, 16
    r = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    w = jax.random.uniform(ks[3], (b, s, h, d), jnp.float32, 0.8, 0.999)
    u = jax.random.normal(ks[4], (h, d), jnp.float32)
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    y_all, s_all = wkv6(r, k, v, w, u, s0, interpret=True)
    y1, s1 = wkv6(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, s0,
                  interpret=True)
    y2, s2 = wkv6(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s1,
                  interpret=True)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_all),
        rtol=1e-4, atol=1e-4,
    )
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all),
                               rtol=1e-4, atol=1e-4)
