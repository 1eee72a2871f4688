"""DASO semantics: Eq.(1) staleness merge, phase machine, B/W schedule,
blocking-sync == flat-sync equivalence, replica divergence behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_exchange
from hypothesis import given, settings, strategies as st

from repro.core.daso import (DasoConfig, blocking_sync, daso_train_step,
                             dereplicate_params, global_receive, global_send,
                             replica_divergence, replica_mean,
                             replicate_params, sync_train_step)
from repro.core.schedule import DasoController, Mode
from repro.optim.optimizers import sgd


def _quadratic_loss(params, batch):
    # simple convex problem: ||W x - y||^2
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


def _make_problem(key, R=4, per=8, d=16):
    wtrue = jax.random.normal(key, (d, 1))
    def data_fn(step):
        k = jax.random.fold_in(key, step)
        x = jax.random.normal(k, (R, per, d))
        y = x @ wtrue + 0.01 * jax.random.normal(k, (R, per, 1))
        return {"x": x, "y": y}
    return wtrue, data_fn


# ---------------------------------------------------------------- Eq. (1) --

@given(st.integers(1, 64), st.integers(2, 1024))
@settings(max_examples=30, deadline=None)
def test_eq1_is_convex_combination(S, P):
    """Eq (1) weights: 2S/(2S+P) on local, P/(2S+P) on global — sum to 1."""
    local = {"w": jnp.full((2, 3), 2.0)}
    glob = {"w": jnp.full((2, 3), -1.0)}
    merged = global_receive(local, glob, ex=make_exchange(2, P),
                            staleness=S)
    expect = (2 * S * 2.0 + P * (-1.0)) / (2 * S + P)
    np.testing.assert_allclose(np.asarray(merged["w"]), expect, rtol=1e-6)
    # convexity: merged between min and max
    assert -1.0 <= float(merged["w"][0, 0]) <= 2.0


def test_eq1_staleness_monotonicity():
    """More staleness -> more weight on local params (paper's rationale)."""
    local = {"w": jnp.ones((1,))}
    glob = {"w": jnp.zeros((1,))}
    vals = [float(global_receive(local, glob, ex=make_exchange(1, 16),
                                 staleness=s)["w"][0])
            for s in (1, 2, 4, 8)]
    assert all(b > a for a, b in zip(vals, vals[1:]))


def test_send_is_replica_mean():
    params = replicate_params({"w": jnp.zeros((2,))}, 4)
    params = {"w": params["w"].at[:, 0].set(jnp.arange(4.0))}
    inflight = global_send(params, ex=make_exchange(4))
    np.testing.assert_allclose(np.asarray(inflight["w"][:, 0]), 1.5)
    # every replica holds the same buffer
    assert float(jnp.max(jnp.abs(inflight["w"] - inflight["w"][0]))) == 0.0


def test_blocking_sync_bf16_compression_roundtrip():
    params = replicate_params({"w": jnp.array([1.0 + 1e-5, 2.0])}, 2)
    out = blocking_sync(params, ex=make_exchange(2, wire_format="bf16"))
    # values pass through bf16: small perturbations are quantized away
    assert out["w"].dtype == params["w"].dtype
    assert abs(float(out["w"][0, 0]) - 1.0) < 1e-2


# ------------------------------------------------- step-variant semantics --

def test_blocking_daso_equals_sync():
    """With blocking sync every step (and no compression), DASO on R replicas
    of batch b == flat sync on the R*b batch (iid split), bitwise-close."""
    key = jax.random.PRNGKey(0)
    _, data_fn = _make_problem(key)
    params0 = {"w": jnp.zeros((16, 1))}
    opt = sgd(momentum=0.9, weight_decay=0.0)
    cfg = DasoConfig(n_replicas=4, global_world=16, wire_format="f32")
    step = jax.jit(daso_train_step(_quadratic_loss, opt, cfg,
                                   mode="blocking"))
    sstep = jax.jit(sync_train_step(_quadratic_loss, opt))

    p = replicate_params(params0, 4)
    o = replicate_params(opt.init(params0), 4)
    infl = p
    ps, os_ = params0, opt.init(params0)
    for t in range(5):
        batch = data_fn(t)
        p, o, infl, _ = step(p, o, infl, batch, 0.05)
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()}
        ps, os_, _ = sstep(ps, os_, flat, 0.05)
    np.testing.assert_allclose(np.asarray(dereplicate_params(p)["w"]),
                               np.asarray(ps["w"]), rtol=2e-5, atol=1e-6)


def test_local_steps_diverge_and_sync_restores():
    key = jax.random.PRNGKey(1)
    _, data_fn = _make_problem(key)
    params0 = {"w": jnp.zeros((16, 1))}
    opt = sgd(momentum=0.0, weight_decay=0.0)
    cfg = DasoConfig(n_replicas=4, global_world=16)
    local = jax.jit(daso_train_step(_quadratic_loss, opt, cfg, mode="local"))
    hard = jax.jit(daso_train_step(_quadratic_loss, opt, cfg,
                                   mode="hard_avg"))
    p = replicate_params(params0, 4)
    o = replicate_params(opt.init(params0), 4)
    infl = p
    p, o, infl, _ = local(p, o, infl, data_fn(0), 0.05)
    assert float(replica_divergence(p)) > 0.0  # replicas saw different data
    p, o, infl, _ = hard(p, o, infl, data_fn(1), 0.05)
    assert float(replica_divergence(p)) < 1e-7


def test_receive_applies_weighted_merge():
    key = jax.random.PRNGKey(2)
    _, data_fn = _make_problem(key)
    params0 = {"w": jnp.zeros((16, 1))}
    opt = sgd(momentum=0.0, weight_decay=0.0)
    cfg = DasoConfig(n_replicas=4, global_world=16)
    send = jax.jit(daso_train_step(_quadratic_loss, opt, cfg, mode="send"))
    recv = jax.jit(daso_train_step(_quadratic_loss, opt, cfg, mode="receive",
                                   staleness=2))
    p = replicate_params(params0, 4)
    o = replicate_params(opt.init(params0), 4)
    infl = jax.tree.map(jnp.zeros_like, p)
    p, o, infl, _ = send(p, o, infl, data_fn(0), 0.05)
    assert float(jnp.max(jnp.abs(infl["w"]))) > 0  # buffer captured
    p_before = p
    p, o, infl, _ = recv(p, o, infl, data_fn(1), 0.05)
    # after receive+local the replicas were pulled toward the global mean
    assert float(replica_divergence(p)) < float(
        replica_divergence(p_before)) + 1e-6


# ----------------------------------------------------------- controller ----

def _cfg(b_max=4, warm=10, cool=10, total=100):
    return DasoConfig(n_replicas=4, global_world=16, b_max=b_max,
                      warmup_steps=warm, cooldown_steps=cool,
                      total_steps=total, plateau_patience=2)


def test_controller_phases():
    c = DasoController(_cfg(), loss_window=1000)
    modes = [c.mode_for_step(t)[0] for t in range(100)]
    assert all(m == Mode.BLOCKING for m in modes[:10])
    assert all(m == Mode.BLOCKING for m in modes[90:])
    assert any(m in (Mode.SEND, Mode.SEND_RECEIVE) for m in modes[10:90])
    assert any(m == Mode.LOCAL for m in modes[10:90])


def test_controller_send_receive_spacing():
    c = DasoController(_cfg(warm=0, cool=0, total=0), loss_window=10**9)
    events = [(t,) + c.mode_for_step(t) for t in range(40)]
    sends = [t for t, m, _ in events if m in (Mode.SEND, Mode.SEND_RECEIVE)]
    recvs = [(t, s) for t, m, s in events if m in (Mode.RECEIVE,
                                                   Mode.SEND_RECEIVE)]
    assert sends, "no sends happened"
    # B=4 spacing between sends
    assert all(b - a == 4 for a, b in zip(sends, sends[1:]))
    # every receive waits exactly W=1 steps and reports that staleness
    for t, s in recvs:
        assert s == 1


def test_controller_plateau_halves_and_resets():
    c = DasoController(_cfg(b_max=4, warm=0, cool=0, total=0), loss_window=1)
    assert (c.b, c.w) == (4, 1)
    c.observe_loss(1.0)  # first window sets the best-loss reference
    # constant loss -> plateau every `patience` windows
    for _ in range(2):
        c.observe_loss(1.0)
    assert (c.b, c.w) == (2, 1)
    for _ in range(2):
        c.observe_loss(1.0)
    assert (c.b, c.w) == (1, 1)
    for _ in range(2):
        c.observe_loss(1.0)
    assert (c.b, c.w) == (4, 1)  # paper: reset once both reach 1


def test_controller_improvement_keeps_b():
    c = DasoController(_cfg(warm=0, cool=0, total=0), loss_window=1)
    for i in range(20):
        c.observe_loss(1.0 / (i + 1))
    assert c.b == 4


def test_microbatched_grads_match_full_batch():
    """Gradient accumulation (beyond-paper memory optimization) must be
    numerically equivalent to the full-batch step."""
    import numpy as np
    from repro.core.daso import sync_train_step
    key = jax.random.PRNGKey(0)
    _, data_fn = _make_problem(key, R=1, per=16)
    params0 = {"w": jnp.zeros((16, 1))}
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    batch = {k: v[0] for k, v in data_fn(0).items()}  # flat (16, d)
    outs = {}
    for n in (1, 2, 4):
        step = jax.jit(sync_train_step(_quadratic_loss, opt, n_micro=n))
        p, _, m = step(params0, opt.init(params0), batch, 0.05)
        outs[n] = p["w"]
    for n in (2, 4):
        np.testing.assert_allclose(np.asarray(outs[n]),
                                   np.asarray(outs[1]), atol=1e-6)
