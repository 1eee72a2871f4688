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


def _qkv_do(Hq, Hk, S, D, dtype, seed=0, R=None):
    lead = (1,) if R is None else (R, 1)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], lead + (Hq, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], lead + (Hk, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], lead + (Hk, S, D)).astype(dtype)
    do = jax.random.normal(ks[3], lead + (Hq, S, D)).astype(dtype)
    return q, k, v, do


def _value_and_grads(attn, q, k, v, do):
    o, vjp = jax.vjp(attn, q, k, v)
    return [np.asarray(x, np.float32) for x in (o,) + vjp(do)]


def _bshd(attn):
    """A (B,S,H,D)-layout attention as a (B,H,S,D) one."""
    def f(q, k, v):
        return attn(q.swapaxes(1, 2), k.swapaxes(1, 2),
                    v.swapaxes(1, 2)).swapaxes(1, 2)
    return f


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("Hq,Hk,D,window", [  # G = 1, 4, 3 each twice
    (4, 4, 64, 0), (4, 4, 128, 48), (8, 2, 128, 0), (8, 2, 64, 48),
    (6, 2, 64, 0), (6, 2, 128, 48)])
def test_flash_attention_grads_match_references(Hq, Hk, D, window):
    """Output and dq, dk, dv of the kernel against the f32 reference (f32
    inputs, tight) and, in bf16, against `multihead_attention`: no
    further from the f32 reference than the jnp path is."""
    from repro.models.attention import multihead_attention
    S = 128
    kern = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, block_q=64, block_k=32,
        interpret=True)
    ref = lambda q, k, v: attention_ref(  # noqa: E731
        q, k, v, causal=True, window=window)
    args = _qkv_do(Hq, Hk, S, D, jnp.float32)
    for got, want in zip(_value_and_grads(kern, *args),
                         _value_and_grads(ref, *args)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    args16 = _qkv_do(Hq, Hk, S, D, jnp.bfloat16, seed=1)
    exact = _value_and_grads(ref, *[x.astype(jnp.float32) for x in args16])
    jnp_path = _bshd(lambda q, k, v: multihead_attention(
        q, k, v, causal=True, window=window, q_chunk=32))
    got = _value_and_grads(kern, *args16)
    base = _value_and_grads(jnp_path, *args16)
    for g, b, e in zip(got, base, exact):
        assert _rel(g, b) < 2e-2
        assert _rel(g, e) <= 1.5 * _rel(b, e) + 1e-3


def test_flash_attention_scale_is_applied_to_the_scores():
    """A scale other than D ** -0.5 (Granite's attention multiplier)
    multiplies the f32 scores, as in `multihead_attention`."""
    from repro.models.attention import multihead_attention
    args = _qkv_do(8, 2, 128, 64, jnp.float32, seed=2)
    kern = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, scale=0.015625, block_q=64, block_k=64, interpret=True)
    jnp_path = _bshd(lambda q, k, v: multihead_attention(
        q, k, v, q_chunk=64, scale=0.015625))
    for got, want in zip(_value_and_grads(kern, *args),
                         _value_and_grads(jnp_path, *args)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("blocks,bwd_blocks", [
    ((32, 32), (32, 32)), ((128, 32), (32, 128)), ((64, 128), (128, 64)),
    ((128, 128), (64, 32))])
def test_flash_attention_grads_block_size_invariance(blocks, bwd_blocks):
    """At 256 tokens and a window of 80 the smaller blocks skip whole
    block pairs on both sides of the band, forward and backward."""
    args = _qkv_do(4, 2, 256, 64, jnp.float32, seed=3)
    one = lambda b, bb: (lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, window=80, block_q=b[0], block_k=b[1], bwd_blocks=bb,
        interpret=True))
    for got, want in zip(_value_and_grads(one(blocks, bwd_blocks), *args),
                         _value_and_grads(one((64, 64), (64, 64)), *args)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_under_replica_vmap():
    """vmapped over a replica axis, as the DASO step runs it: each
    replica's output and gradients are those of the kernel alone."""
    q, k, v, do = _qkv_do(8, 2, 128, 64, jnp.bfloat16, seed=4, R=2)
    kern = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block_q=64, block_k=64, interpret=True)
    both = _value_and_grads(jax.vmap(kern), q, k, v, do)
    for r in range(2):
        alone = _value_and_grads(kern, q[r], k[r], v[r], do[r])
        for b, a in zip(both, alone):
            np.testing.assert_array_equal(b[r], a)


@pytest.mark.parametrize("save_kernel_residuals", [False, True])
def test_flash_attention_in_checkpointed_layer_scan(save_kernel_residuals):
    """Inside a 2-layer `lax.scan` under `jax.checkpoint(policy=
    SCAN_SAVE)`, as the layer scan of a model with experts runs it: the
    gradients match the unchecked scan. Saving the kernel's named
    residuals leaves one forward kernel in the gradient program; without
    them the backward pass runs it again."""
    from repro.kernels.flash_attention import SAVED_NAMES
    from repro.models.lm import SCAN_SAVE
    policy = SCAN_SAVE
    if save_kernel_residuals:
        policy = jax.checkpoint_policies.save_from_both_policies(
            SCAN_SAVE,
            jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES))
    q, k, v, _ = _qkv_do(4, 2, 128, 64, jnp.float32, seed=5)
    w = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64)) / 8

    def body(x, wl):
        y = flash_attention(x @ wl, k, v, block_q=64, block_k=64,
                            interpret=True)
        return x + y, None

    def loss(w, scan_body):
        out, _ = jax.lax.scan(scan_body, q, w)
        return jnp.sum(out ** 2)

    checked = jax.checkpoint(body, policy=policy, prevent_cse=False)
    got = jax.grad(loss)(w, checked)
    want = jax.grad(loss)(w, body)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    jaxpr = jax.make_jaxpr(lambda w: jax.grad(loss)(w, checked))(w)
    fwd_calls = [e for e in _eqns(jaxpr.jaxpr)
                 if e.params.get("name") == "flash_attn_fwd"]
    assert len(fwd_calls) == (1 if save_kernel_residuals else 2)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


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
