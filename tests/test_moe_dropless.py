"""The dropless expert layer (`models/moe.py`, sorted rows and grouped
matrix products) and Granite's scalar multipliers, against a plain dense
reference on seeded weights at a small size on the CPU.

The reference evaluates every expert on every token in float32 at HIGHEST
precision and weights each by its renormalised top-k gate (zero for the
experts a token did not choose): the same sum with no dispatch at all.
The program runs in float32 here too, so every gap below is float32
rounding of sums taken in another order: the sorted path adds a token's k
slots after the grouped products, the reference adds all E experts. Each
entry is held to 1e-5 of the largest entry of its tensor (an entry near
zero is the difference of terms of that size, so its rounding is of
their size, not its own): about a hundred float32 ulps, and far below
what a dropped or misrouted pair moves (a gate of 1/k of a row, order
1e-1 of the tensor)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import subprocess_env
from repro.configs import get_config, get_reduced
from repro.configs.base import MoEConfig
from repro.models import blocks
from repro.models.lm import init_params
from repro.models.moe import _moe_grouped, init_moe, moe_apply
from repro.train.step import make_lm_loss

HI = jax.lax.Precision.HIGHEST
TOL = 1e-5


def moe_reference(p, x, cfg):
    """Plain dense mixture of experts: (out, aux) as `moe_apply` gives."""
    m = cfg.moe
    E, K = m.n_experts, m.top_k
    x = x.astype(jnp.float32)
    logits = jnp.einsum("bsd,de->bse", x, p["router"], precision=HI)
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / gate.sum(-1, keepdims=True)
    w = jnp.sum(gate[..., None] * jax.nn.one_hot(idx, E), axis=-2)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h1 = jnp.einsum("bsd,edf->bsef", x, f32(p["we1"]), precision=HI)
    h3 = jnp.einsum("bsd,edf->bsef", x, f32(p["we3"]), precision=HI)
    out = jnp.einsum("bsef,efd->bsd", jax.nn.silu(h1) * h3 * w[..., None],
                     f32(p["we2"]), precision=HI)
    me = probs.mean((0, 1))
    top1 = jax.nn.one_hot(idx[..., 0], E).mean((0, 1))
    aux = {"moe_lb_loss": E * jnp.sum(me * top1) * m.load_balance_loss,
           "moe_z_loss": jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
           * m.router_z_loss,
           "moe_drop_frac": jnp.float32(0.0),
           "moe_load_max": jnp.float32(0.0)}
    return out, aux


def _cfg(n_experts=8, top_k=2, capacity_factor=None):
    base = get_reduced("granite-moe-3b-a800m")
    moe = MoEConfig(n_experts=n_experts, top_k=top_k, d_ff=24,
                    capacity_factor=capacity_factor, group_size=64,
                    load_balance_loss=0.01, router_z_loss=0.001)
    return base.replace(d_model=32, moe=moe)


def _layer(cfg, seed=0, B=2, S=16):
    key = jax.random.PRNGKey(seed)
    p = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 9), (B, S, cfg.d_model))
    return p, x


def _layer_loss(apply, cfg, probe):
    def loss(p, x):
        out, aux = apply(p, x, cfg)
        return (jnp.sum(out * probe) + aux["moe_lb_loss"]
                + aux["moe_z_loss"])
    return loss


def _close(got, want, tol=TOL, msg=""):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                   atol=tol * np.max(np.abs(b)),
                                   err_msg=msg)


@pytest.mark.parametrize("n_experts,top_k", [(8, 2), (6, 3), (5, 4)])
def test_dropless_layer_matches_dense_reference(n_experts, top_k):
    cfg = _cfg(n_experts, top_k)
    assert cfg.moe.dropless
    p, x = _layer(cfg)
    probe = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    out, aux = moe_apply(p, x, cfg)
    ref, ref_aux = moe_reference(p, x, cfg)
    _close(out, ref)
    _close((aux["moe_lb_loss"], aux["moe_z_loss"]),
           (ref_aux["moe_lb_loss"], ref_aux["moe_z_loss"]))
    assert float(aux["moe_drop_frac"]) == 0.0
    # the loss's gradient by every parameter leaf and by the input
    got = jax.grad(_layer_loss(moe_apply, cfg, probe), (0, 1))(p, x)
    want = jax.grad(_layer_loss(moe_reference, cfg, probe), (0, 1))(p, x)
    assert set(got[0]) == {"router", "we1", "we3", "we2"}
    _close(got, want)


def test_dropless_matches_capacity_path_where_capacity_cannot_bind():
    # capacity_factor x top_k = experts: capacity is the whole group
    cfg = _cfg(8, 2, capacity_factor=4.0)
    assert cfg.moe.dropless
    p, x = _layer(cfg, seed=1, S=64)
    probe = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    out, aux = moe_apply(p, x, cfg)
    cap, cap_aux = _moe_grouped(p, x, cfg)
    assert float(cap_aux["moe_drop_frac"]) == 0.0
    _close(out, cap)
    _close(aux, cap_aux)
    got = jax.grad(_layer_loss(moe_apply, cfg, probe), (0, 1))(p, x)
    want = jax.grad(_layer_loss(_moe_grouped, cfg, probe), (0, 1))(p, x)
    _close(got, want)


def test_capacity_path_still_drops_where_capacity_binds():
    cfg = _cfg(8, 2, capacity_factor=0.5)
    assert not cfg.moe.dropless
    p, x = _layer(cfg, S=64)
    p["router"] = p["router"].at[:, 0].add(20.0)   # every token -> expert 0
    _, aux = moe_apply(p, x, cfg)
    assert float(aux["moe_drop_frac"]) > 0.3


def test_skewed_routing_drops_nothing():
    """Every token picks the same top-k experts: those k experts take all
    the rows, none is lost, and the load peak reads E / k."""
    cfg = _cfg(8, 2)
    p, x = _layer(cfg, S=32)
    p["router"] = p["router"].at[:, 3].add(40.0).at[:, 5].add(30.0)
    x = jnp.abs(x)            # positive rows: the bias wins for every token
    out, aux = moe_apply(p, x, cfg)
    _close(out, moe_reference(p, x, cfg)[0])
    assert float(aux["moe_drop_frac"]) == 0.0
    assert float(aux["moe_load_max"]) == pytest.approx(8 / 2)


def test_dropless_layer_under_replica_vmap():
    """Replicas with their own weights and tokens, vmapped as the DASO
    step does: each replica's output and gradients are its own."""
    cfg = _cfg(8, 2)
    ps, xs = zip(*[_layer(cfg, seed=s) for s in (0, 1)])
    p = jax.tree.map(lambda *a: jnp.stack(a), *ps)
    x = jnp.stack(xs)
    probe = jax.random.normal(jax.random.PRNGKey(7), x.shape[1:])
    loss = _layer_loss(moe_apply, cfg, probe)
    out = jax.vmap(lambda p, x: moe_apply(p, x, cfg)[0])(p, x)
    grads = jax.vmap(jax.grad(loss))(p, x)
    for r in range(2):
        _close(out[r], moe_reference(ps[r], xs[r], cfg)[0])
        _close(jax.tree.map(lambda g: g[r], grads),
               jax.grad(_layer_loss(moe_reference, cfg, probe))(ps[r],
                                                                 xs[r]))


def _granite_tiny(**kw):
    """Granite's layer, routing and multipliers at a test's size."""
    full = get_config("granite-moe-3b-a800m")
    moe = dataclasses.replace(full.moe, n_experts=8, top_k=4, d_ff=24)
    return get_reduced("granite-moe-3b-a800m").replace(
        d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, vocab_size=64,
        moe=moe, **kw)


def lm_reference(params, tokens, labels, cfg):
    """Plain float32 decoder of `cfg` on the program's parameter layout:
    RMSNorm x (1 + scale), rotate-half RoPE, GQA causal attention, the
    dense mixture of experts above, tied LM head, mean cross-entropy plus
    the routing losses; Granite's multipliers where the config sets them."""
    one = lambda v, dflt: dflt if v is None else v  # noqa: E731
    emb_m = one(cfg.embedding_multiplier, 1.0)
    att_m = one(cfg.attention_multiplier, cfg.head_dim ** -0.5)
    res_m = one(cfg.residual_multiplier, 1.0)
    logit_d = one(cfg.logits_scaling, 1.0)

    def rms(x, s):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + cfg.norm_eps) * (1.0 + s))

    def rope(t):
        s, d = t.shape[1], t.shape[-1]
        inv = 1.0 / cfg.rope_theta ** (jnp.arange(d // 2) / (d // 2))
        ang = jnp.arange(s)[:, None] * inv
        sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
        a, b = t[..., :d // 2], t[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    B, S = tokens.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"]["tok"][tokens] * emb_m
    aux = 0.0
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["blocks"][0])
        a = p["attn"]
        y = rms(x, a["norm"])
        q = rope(jnp.einsum("bsd,de->bse", y, a["wq"], precision=HI)
                 .reshape(B, S, H, hd))
        k = rope(jnp.einsum("bsd,de->bse", y, a["wk"], precision=HI)
                 .reshape(B, S, KV, hd))
        v = (jnp.einsum("bsd,de->bse", y, a["wv"], precision=HI)
             .reshape(B, S, KV, hd))
        k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * att_m
        sc = jnp.where(jnp.tril(jnp.ones((S, S), bool)), sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                       precision=HI).reshape(B, S, H * hd)
        x = x + res_m * jnp.einsum("bse,ed->bsd", o, a["wo"], precision=HI)
        delta, moe_aux = moe_reference(p["moe"], rms(x, p["moe_norm"]), cfg)
        x = x + res_m * delta
        aux = aux + moe_aux["moe_lb_loss"] + moe_aux["moe_z_loss"]
    x = rms(x, params["final_norm"]["scale"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["tok"],
                        precision=HI) / logit_d
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    return jnp.mean(nll) + aux


@pytest.mark.parametrize("multipliers", [True, False])
def test_lm_loss_and_every_gradient_leaf_match_reference(multipliers):
    cfg = _granite_tiny()
    if not multipliers:
        cfg = cfg.replace(embedding_multiplier=None,
                          attention_multiplier=None,
                          residual_multiplier=None, logits_scaling=None)
    assert cfg.moe.dropless and cfg.param_dtype == "float32"
    key = jax.random.PRNGKey(11)
    params = init_params(cfg, key)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (2, 16), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    loss_fn = make_lm_loss(cfg)
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"tokens": tokens, "labels": labels})
    ref_loss, ref_grads = jax.value_and_grad(lm_reference)(params, tokens,
                                                           labels, cfg)
    assert float(aux["moe_drop_frac"]) == 0.0
    assert float(aux["moe_load_max"]) >= 1.0
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=TOL)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    # attention and the LM head add more terms, in another order, than
    # the expert layer alone: twice the layer's tolerance
    for (path, g), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                               jax.tree.leaves(ref_grads)):
        _close(g, want, 2 * TOL, jax.tree_util.keystr(path))


def test_multipliers_change_the_model():
    """Each multiplier alone moves the loss: none is silently dropped."""
    cfg = _granite_tiny()
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    base = float(make_lm_loss(cfg)(params, batch)[0])
    for name in ("embedding_multiplier", "attention_multiplier",
                 "residual_multiplier", "logits_scaling"):
        other = cfg.replace(**{name: None})
        assert abs(float(make_lm_loss(other)(params, batch)[0]) - base) \
            > 1e-4, name


@pytest.mark.parametrize("capacity_factor", [None, 1.25])
def test_float8_configuration_trains(capacity_factor):
    """The float8_e4m3fn configuration runs through the loss and its
    gradient on both routing paths (the capacity path used to fail on
    float32 meeting float8 in the expert layer)."""
    cfg = _granite_tiny(param_dtype="float8_e4m3fn",
                        compute_dtype="float8_e4m3fn")
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, capacity_factor=capacity_factor))
    params = init_params(cfg, jax.random.PRNGKey(2))
    assert params["blocks"][0]["moe"]["we1"].dtype == jnp.float8_e4m3fn
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    (loss, _), grads = jax.value_and_grad(make_lm_loss(cfg), has_aux=True)(
        params, batch)
    assert np.isfinite(float(loss))
    assert all(g.dtype == p.dtype for g, p in zip(jax.tree.leaves(grads),
                                                  jax.tree.leaves(params)))


def test_granite_config_is_dropless_granite():
    cfg = get_config("granite-moe-3b-a800m")
    assert cfg.source == "[hf:ibm-granite/granite-3.0-3b-a800m-base]"
    assert cfg.moe.dropless and cfg.moe.n_experts == 40
    assert (cfg.moe.load_balance_loss, cfg.moe.router_z_loss) == (0.001,
                                                                  0.0)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (12.0, 0.015625,
                                                             0.22, 6.0)


@pytest.mark.parametrize("name,checkpointed", [
    ("llama3.2-1b", False),
    ("granite-moe-3b-a800m", True),
    ("mixtral-8x22b", True),
])
def test_only_models_with_experts_checkpoint_their_layers(name,
                                                          checkpointed):
    """A model with experts keeps only its products for the backward pass
    (`models/lm.py` SCAN_SAVE); a dense model's backward pass keeps
    everything, as it did before the policy existed."""
    cfg = get_reduced(name)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jnp.zeros((1, 8), jnp.int32)
    loss = make_lm_loss(cfg)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: loss(
        p, {"tokens": tokens, "labels": tokens})[0]))(params)
    text = str(jaxpr)   # the checkpoint primitive: remat2 or checkpoint
    assert ("remat2[" in text or "checkpoint[" in text) == checkpointed


def test_dense_models_report_no_expert_load():
    """A model without experts keeps its aux entries as they were, so
    its programs are unchanged by the expert layer's new counter."""
    cfg = get_reduced("llama3.2-1b")
    assert "moe_load_max" not in blocks.zero_aux(cfg)
    assert "moe_load_max" in blocks.zero_aux(_cfg())


def test_launcher_trains_granite_dropless_and_counts_its_routing(tmp_path):
    """Granite (reduced) through the launcher's DASO path, `build_strategy`
    and the macro-cycle executor: the expert layer's step metrics reach the
    summary line and the run trace's `expert_layer` counter, and no pair
    is dropped in any step."""
    trace = tmp_path / "run.jsonl"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = subprocess_env(1, {"PYTHONPATH": os.path.join(repo, "src")})
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "granite-moe-3b-a800m", "--strategy", "daso", "--steps", "8",
         "--nodes", "2", "--local-world", "1", "--per-node-batch", "2",
         "--seq-len", "32", "--trace-out", str(trace)],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "dispatches=2/8" in r.stdout
    assert "[train] expert layer, largest over steps: moe_drop_frac=0 " \
        in r.stdout
    with open(trace) as f:
        events = [json.loads(line) for line in f]
    (counter,) = [e["args"] for e in events if e["name"] == "expert_layer"]
    assert counter["moe_drop_frac"] == 0.0
    # the reduced Granite routes top-2 of 4 experts: the load peak lies
    # between 1 (even) and E / k (every token on the same k experts)
    assert 1.0 <= counter["moe_load_max"] <= 4 / 2
    # on the CPU every attention call keeps the jnp path, and the
    # launcher says so beside the expert layer's counters
    (paths,) = [e["args"] for e in events if e["name"] == "attention_path"]
    assert paths["kernel"] == 0 and paths["jnp"] > 0
    assert (f"[train] attention calls traced: kernel=0 jnp={paths['jnp']}"
            in r.stdout)


def test_expert_counters_take_the_largest_step():
    from repro.launch.train import expert_counters
    steps = [{"loss": 3.0, "moe_drop_frac": 0.0, "moe_load_max": 1.5},
             {"loss": 2.0, "moe_drop_frac": 0.25, "moe_load_max": 1.2}]
    assert expert_counters(steps) == {"moe_drop_frac": 0.25,
                                      "moe_load_max": 1.5}
    assert expert_counters([{"loss": 1.0}]) == {}
    assert expert_counters([]) == {}
