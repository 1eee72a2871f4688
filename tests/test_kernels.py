"""Pallas kernel validation (interpret=True): shape/dtype sweeps +
hypothesis properties, assert_allclose vs the pure-jnp oracles in ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import flash_attention, rglru_scan, ssm_scan
from repro.kernels.ref import attention_ref, rglru_scan_ref, ssm_scan_ref


# ------------------------------------------------------ flash attention ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hk,Sq,Sk,D", [
    (2, 4, 4, 128, 128, 64),     # MHA square
    (1, 8, 2, 128, 128, 32),     # GQA 4:1
    (2, 4, 1, 64, 256, 64),      # MQA, q suffix of longer kv
    (1, 2, 2, 256, 256, 128),    # MXU-aligned head dim
])
def test_flash_attention_sweep(B, Hq, Hk, Sq, Sk, D, dtype):
    key = jax.random.PRNGKey(B * Sq + D)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hk, Sk, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hk, Sk, D)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_sliding_window(window):
    key = jax.random.PRNGKey(window)
    B, H, S, D = 1, 2, 256, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64, 128]))
@settings(max_examples=6, deadline=None)
def test_flash_attention_block_size_invariance(bq, bk):
    key = jax.random.PRNGKey(42)
    B, H, S, D = 1, 2, 128, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ------------------------------------------------------------- ssm scan ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Di,N,bd", [
    (2, 64, 128, 16, 64),
    (1, 128, 64, 8, 64),
    (3, 32, 96, 4, 32),   # Di not a multiple of the preferred block
])
def test_ssm_scan_sweep(B, S, Di, N, bd, dtype):
    key = jax.random.PRNGKey(S + Di)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, Di)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di))).astype(
        jnp.float32)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (Di, N)))
    Bm = jax.random.normal(ks[3], (B, S, N)).astype(dtype)
    Cm = jax.random.normal(ks[4], (B, S, N)).astype(dtype)
    h0 = jnp.zeros((B, Di, N), jnp.float32)
    y, h = ssm_scan(x, dt, A, Bm, Cm, h0, block_d=bd, interpret=True)
    yr, hr = ssm_scan_ref(x, dt, A, Bm, Cm, h0)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=atol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=atol)


def test_ssm_scan_nonzero_initial_state():
    key = jax.random.PRNGKey(5)
    B, S, Di, N = 1, 16, 32, 4
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, S, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di)))
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (Di, N)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    h0 = jax.random.normal(ks[5], (B, Di, N))
    y, h = ssm_scan(x, dt, A, Bm, Cm, h0, block_d=16, interpret=True)
    yr, hr = ssm_scan_ref(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)


# ----------------------------------------------------------- rglru scan ----

@pytest.mark.parametrize("B,S,W,bw", [(2, 64, 128, 64), (1, 32, 48, 16)])
def test_rglru_scan_sweep(B, S, W, bw):
    key = jax.random.PRNGKey(W)
    ks = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W)))
    gx = jax.random.normal(ks[1], (B, S, W))
    h0 = jax.random.normal(ks[2], (B, W))
    hs, h = rglru_scan(a, gx, h0, block_w=bw, interpret=True)
    hsr, hr = rglru_scan_ref(a, gx, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hsr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5)


@given(st.integers(0, 10))
@settings(max_examples=8, deadline=None)
def test_rglru_decay_bound_property(seed):
    """With |a|<1 and bounded input, the state stays bounded (stability)."""
    key = jax.random.PRNGKey(seed)
    B, S, W = 1, 64, 16
    a = jax.nn.sigmoid(jax.random.normal(key, (B, S, W))) * 0.99
    gx = jnp.clip(jax.random.normal(jax.random.fold_in(key, 1), (B, S, W)),
                  -1, 1)
    h0 = jnp.zeros((B, W))
    hs, _ = rglru_scan(a, gx, h0, block_w=16, interpret=True)
    bound = 1.0 / (1.0 - 0.99) + 1.0
    assert float(jnp.max(jnp.abs(hs))) < bound
