"""Model-component unit + property tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ref import attention_ref, rglru_scan_ref, ssm_scan_ref
from repro.models.attention import multihead_attention
from repro.models.common import cross_entropy_loss
from repro.models.mamba import linear_recurrence, selective_scan
from repro.models.rope import apply_mrope, apply_rope


def _bhsd_to_bshd(x):
    return x.swapaxes(1, 2)


@pytest.mark.parametrize("Hq,Hk", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window", [0, 16])
def test_chunked_attention_matches_ref(Hq, Hk, window):
    key = jax.random.PRNGKey(0)
    B, S, D = 2, 64, 32
    q = jax.random.normal(key, (B, Hq, S, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hk, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hk, S, D))
    ref = attention_ref(q, k, v, causal=True, window=window)
    out = multihead_attention(_bhsd_to_bshd(q), _bhsd_to_bshd(k),
                              _bhsd_to_bshd(v), causal=True, window=window,
                              q_chunk=16)
    np.testing.assert_allclose(np.asarray(_bhsd_to_bshd(out)),
                               np.asarray(ref), atol=2e-5)


def test_attention_chunk_size_invariance():
    key = jax.random.PRNGKey(3)
    B, H, S, D = 1, 2, 128, 16
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, D))
    outs = [multihead_attention(q, k, v, q_chunk=c) for c in (16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=1e-5)


@given(st.integers(8, 64).filter(lambda s: s % 8 == 0),
       st.sampled_from([4, 8, 16]))
@settings(max_examples=10, deadline=None)
def test_selective_scan_chunk_invariance(S, chunk):
    key = jax.random.PRNGKey(S)
    B, Di, N = 2, 16, 4
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di)))
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (Di, N)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    h0 = jnp.zeros((B, Di, N))
    y, h = selective_scan(x, dt, A, Bm, Cm, h0, chunk=chunk)
    yr, hr = ssm_scan_ref(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr.astype(y.dtype)),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_selective_scan_state_carry():
    """Scanning two halves with carried state == scanning the whole."""
    key = jax.random.PRNGKey(7)
    B, S, Di, N = 1, 32, 8, 4
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di)))
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (Di, N)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    h0 = jnp.zeros((B, Di, N))
    y_full, h_full = selective_scan(x, dt, A, Bm, Cm, h0, chunk=8)
    y1, h1 = selective_scan(x[:, :16], dt[:, :16], A, Bm[:, :16],
                            Cm[:, :16], h0, chunk=8)
    y2, h2 = selective_scan(x[:, 16:], dt[:, 16:], A, Bm[:, 16:],
                            Cm[:, 16:], h1, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-5)


def test_linear_recurrence_matches_ref():
    key = jax.random.PRNGKey(9)
    B, S, W = 2, 48, 8
    a = jax.nn.sigmoid(jax.random.normal(key, (B, S, W)))
    gx = jax.random.normal(jax.random.fold_in(key, 1), (B, S, W))
    h0 = jnp.zeros((B, W))
    hs, h = linear_recurrence(a, gx, h0, chunk=16)
    hsr, hr = rglru_scan_ref(a, gx, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hsr), atol=1e-5)


def test_rope_preserves_norm_and_relative_property():
    key = jax.random.PRNGKey(11)
    B, S, H, D = 1, 16, 2, 32
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    qr, kr = apply_rope(q, k, pos, 10000.0)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(qr, axis=-1)),
                               np.asarray(jnp.linalg.norm(q, axis=-1)),
                               rtol=1e-5)
    # relative property: q_i . k_j depends only on i - j
    d1 = float(jnp.einsum("d,d->", qr[0, 5, 0], kr[0, 3, 0]))
    qr2, kr2 = apply_rope(q, k, pos + 7, 10000.0)
    d2 = float(jnp.einsum("d,d->", qr2[0, 5, 0], kr2[0, 3, 0]))
    assert abs(d1 - d2) < 1e-3


def test_mrope_equals_rope_when_positions_equal():
    """With t==h==w positions, M-RoPE must reduce to standard RoPE."""
    key = jax.random.PRNGKey(13)
    B, S, H, D = 1, 8, 2, 32
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    pos1 = jnp.broadcast_to(jnp.arange(S), (B, S))
    pos3 = jnp.tile(pos1[..., None], (1, 1, 3))
    q1, k1 = apply_rope(q, k, pos1, 10000.0)
    q3, k3 = apply_mrope(q, k, pos3, 10000.0)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q3), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k1), np.asarray(k3), atol=1e-5)


def test_cross_entropy_matches_naive_and_chunked():
    key = jax.random.PRNGKey(17)
    B, S, V = 2, 8, 64
    logits = jax.random.normal(key, (B, S, V))
    labels = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, V)
    labels = labels.at[0, 0].set(-1)  # ignored position
    naive = -jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1),
        jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]
    naive = jnp.where(labels >= 0, naive, 0.0).sum() / (labels >= 0).sum()
    got = cross_entropy_loss(logits, labels)
    np.testing.assert_allclose(float(got), float(naive), rtol=1e-5)
    chunked = cross_entropy_loss(logits, labels, vocab_chunk=16)
    np.testing.assert_allclose(float(chunked), float(naive), rtol=1e-5)


def test_moe_router_aux_losses_behave():
    """Uniform router -> minimal load-balance loss; skewed -> larger."""
    from repro.configs import get_reduced
    from repro.models.moe import init_moe, moe_apply
    cfg = get_reduced("mixtral-8x22b")
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    _, aux = moe_apply(p, x, cfg)
    assert float(aux["moe_drop_frac"]) >= 0.0
    # router pushed to always pick expert 0 -> lb loss rises
    p_skew = dict(p)
    p_skew["router"] = p["router"].at[:, 0].set(50.0)
    _, aux_skew = moe_apply(p_skew, x, cfg)
    assert float(aux_skew["moe_lb_loss"]) > float(aux["moe_lb_loss"])


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x22b"])
def test_pallas_attention_impl_matches_jnp(arch):
    """Full model forward with attn_impl='pallas' (flash kernel,
    interpret=True on CPU) == the jnp chunked path."""
    from repro.configs import get_reduced
    from repro.models.lm import forward, init_params
    cfg = get_reduced(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (2, 128), 0, cfg.vocab_size)
    ref = forward(params, toks, cfg, attn_impl="jnp")["logits"]
    got = forward(params, toks, cfg, attn_impl="pallas")["logits"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-3)


def test_kernel_gradients_match_jnp_in_the_checkpointed_layer_scan():
    """Granite (reduced; experts, so the layer scan runs under
    SCAN_SAVE, and its attention multiplier as the scale): the loss and
    every parameter gradient through the fused kernel (interpret mode)
    equal the jnp path's."""
    from repro.configs import get_reduced
    from repro.models.lm import forward, init_params
    cfg = get_reduced("granite-moe-3b-a800m")
    assert cfg.moe is not None and cfg.attention_multiplier
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (2, 128), 0, cfg.vocab_size)

    def loss(p, impl):
        logits = forward(p, toks, cfg, attn_impl=impl)["logits"]
        return cross_entropy_loss(logits, toks)

    (l_ref, g_ref), (l_got, g_got) = (
        jax.value_and_grad(loss)(params, impl) for impl in ("jnp", "pallas"))
    np.testing.assert_allclose(float(l_got), float(l_ref), rtol=1e-5)
    for got, want in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-6)


def _kernel_calls(jaxpr) -> int:
    n = 0
    for e in jaxpr.eqns:
        n += str(e.params.get("name", "")).startswith("flash_attn")
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _kernel_calls(inner)
    return n


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch rule as it reads on a TPU backend; nothing is
    lowered, so the jaxprs below trace on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("case,kernels", [
    ("one_device", True), ("placement", False), ("mesh_policy", False),
    ("odd_length", False), ("head_dim_32", False), ("decode", False),
    ("prefill", False)])
def test_default_attention_path_follows_the_dispatch_rule(on_tpu, case,
                                                          kernels):
    """The default ("auto") training path takes the fused kernel only on
    a TPU, in a program traced as device-local, under no mesh policy, at
    a length its blocks divide and a head_dim it is tuned for; decode and
    prefill with a cache keep the jnp path."""
    import contextlib
    from jax.sharding import Mesh
    from repro.configs import get_reduced
    from repro.models import attention
    from repro.models.lm import forward, init_cache, init_params
    from repro.sharding import MeshPolicy, trace_device_local, use_policy
    cfg = get_reduced("llama3.2-1b")
    if case == "head_dim_32":
        cfg = cfg.replace(head_dim=32, n_heads=8, n_kv_heads=8)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    S = 384 if case == "odd_length" else 512
    toks = jax.ShapeDtypeStruct((2, 1 if case == "decode" else S), jnp.int32)
    policy = None
    if case == "mesh_policy":
        policy = MeshPolicy(Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                                 ("data", "model")))

    def loss(p, t):
        return jnp.sum(forward(p, t, cfg)["logits"])

    def run(p, t):
        if case == "decode":
            cache = init_cache(cfg, 2, 64)
            return forward(p, t, cfg, cache=cache, pos=3)["logits"]
        if case == "prefill":
            return forward(p, t, cfg, cache=init_cache(cfg, 2, S))["logits"]
        return jax.grad(loss)(p, t)

    with (use_policy(policy) if policy else contextlib.nullcontext()), \
            trace_device_local(case != "placement"), \
            attention.counting_paths() as traced:
        jaxpr = jax.make_jaxpr(run)(params, toks).jaxpr
    assert (_kernel_calls(jaxpr) > 0) == kernels
    if case == "decode":
        assert traced == {"kernel": 0, "jnp": 0}
    else:
        assert traced == ({"kernel": 1, "jnp": 0} if kernels
                          else {"kernel": 0, "jnp": 1})


def test_macro_executor_traces_its_program_as_device_local(on_tpu):
    """The launcher's DASO job on the macro-cycle executor without a
    placement: its cycle program holds the fused kernel's forward and
    both backward kernels."""
    from repro.core.executor import MacroCycleExecutor
    from repro.launch import train
    from repro.optim.optimizers import sgd
    from repro.train.loop import build_strategy
    args = train.build_parser().parse_args(
        ["--arch", "llama3.2-1b", "--seq-len", "512", "--per-node-batch",
         "1", "--nodes", "2", "--steps", "8", "--b-max", "2"])
    job = train.build_job(args, train.arch_config(args))
    strat = build_strategy(job.loss_fn, job.loop_cfg, sgd(momentum=0.9))
    shape = strat.plan_cycle(0, 8).shape
    ex = MacroCycleExecutor(strat)
    carry = jax.eval_shape(strat.init_carry, job.params0)
    tokens = jax.ShapeDtypeStruct((len(shape), 2, 1, 512), jnp.int32)
    lrs = jax.ShapeDtypeStruct((len(shape),), jnp.float32)
    program = ex.program_for(shape)
    jaxpr = jax.make_jaxpr(program)(
        carry, {"tokens": tokens, "labels": tokens}, lrs).jaxpr
    assert _kernel_calls(jaxpr) >= 3
    # a chip trace finds the cycle programs by their name, jit_program
    assert program.__name__ == "program"
