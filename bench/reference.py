"""The plain reference that decides `correct`.

Straightforward `jax.numpy` of what a configuration file states, written
from the published equations and importing nothing of the program: a
decoder of RMSNorm (x * (1 + scale)), rotate-half RoPE, grouped-query
causal attention and a SwiGLU MLP or a top-k mixture of experts, tied or
untied LM head, mean token cross-entropy; momentum SGD with weight decay
on the gradient; DASO's cycling phase (send: the replica mean becomes the
in-flight buffer; receive after S steps: x <- (2S x + P x_mean) / (2S + P),
paper Eq. (1)).

Numerics. Every product and sum runs in float32 at `HIGHEST` precision;
parameters are stored in the type `bench/weights.py` gives them (the
configuration's `torch_dtype`, bfloat16; a router in float32) and rounded
to it after each update, as the configuration states; momentum is
float32. The control (`numerics="fp8"`) is the same code computed in
float8_e4m3fn, the next precision below bfloat16: the parameters are
stored on its grid, and every matrix product's operands and result are
rounded to it, each tensor scaled by its own absolute maximum.

Expert routing (mixture of experts) follows the configuration's `routing`
group: capacity int(group x top_k x capacity_factor / experts) per group
and expert, (token, slot) pairs kept in slot-major order, gates
renormalised over the top k before dropping, a switch load-balance loss
on the top-1 choice and a router z-loss added to the loss. Every expert
is evaluated densely and weighted by its kept gates, which gives the
same sum without any dispatch."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import head_dim, is_moe
from bench.weights import leaf_paths, make_params

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = float(jnp.finfo(F8).max)


def _fp8_round(x):
    """Per-tensor scaled e4m3 rounding of a float32 tensor."""
    s = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(F8).astype(jnp.float32) / s


def _fp8(x):
    """`_fp8_round`, straight through for gradients."""
    return x + jax.lax.stop_gradient(_fp8_round(x) - x)


def _einsum(numerics: str, spec: str, a, b):
    if numerics == "fp8":
        return _fp8(jnp.einsum(spec, _fp8(a), _fp8(b), precision=HI))
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x (B, S, H, D): rotate-half RoPE at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg, num, q_block=1024):
    b, s, d = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    y = _rms(x, p["norm"], cfg["rms_norm_eps"])
    q = _einsum(num, "bsd,de->bse", y, p["wq"]).reshape(b, s, h, hd)
    k = _einsum(num, "bsd,de->bse", y, p["wk"]).reshape(b, s, kv, hd)
    v = _einsum(num, "bsd,de->bse", y, p["wv"]).reshape(b, s, kv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)

    @jax.checkpoint
    def block(qb, kb, vb, q0):
        sc = _einsum(num, "bqhd,bkhd->bhqk", qb, kb) * hd ** -0.5
        row = q0 + jnp.arange(qb.shape[1])[:, None]
        col = jnp.arange(kb.shape[1])[None, :]
        sc = jnp.where(col <= row, sc, -jnp.inf)
        return _einsum(num, "bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vb)

    qb = min(q_block, s)
    outs = [block(q[:, i:i + qb], k[:, :i + qb], v[:, :i + qb], i)
            for i in range(0, s, qb)]
    o = jnp.concatenate(outs, axis=1).reshape(b, s, h * hd)
    return _einsum(num, "bse,ed->bsd", o, p["wo"])


def _mlp(p, x, cfg, num):
    y = _rms(x, p["norm"], cfg["rms_norm_eps"])
    g = _einsum(num, "bsd,df->bsf", y, p["w1"])
    u = _einsum(num, "bsd,df->bsf", y, p["w3"])
    return _einsum(num, "bsf,fd->bsd", jax.nn.silu(g) * u, p["w2"])


def _moe(p, norm, x, cfg, num):
    """Returns (delta, load-balance loss + z-loss)."""
    r = cfg["routing"]
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    b0, s0, d = x.shape
    y = _rms(x, norm, cfg["rms_norm_eps"])
    g = r["group_size"]
    if s0 > g and s0 % g == 0:
        y = y.reshape(b0 * s0 // g, g, d)
    b, s, _ = y.shape
    cap = max(1, int(s * k * r["capacity_factor"] / e))
    logits = _einsum(num, "bsd,de->bse", y, p["router"])
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, k)                       # (b, s, k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, e)                           # (b, s, k, e)
    # position of each (token, slot) among its expert's assignments in
    # slot-major order: all slot-0 choices of the group, then slot 1, ...
    order = onehot.transpose(0, 2, 1, 3).reshape(b, k * s, e)
    pos = (jnp.cumsum(order, 1) - order).reshape(b, k, s, e)
    kept = (pos.transpose(0, 2, 1, 3) < cap) * onehot
    w = jnp.sum(gate[..., None] * kept, axis=2)               # (b, s, e)
    h1 = _einsum(num, "bsd,edf->bsef", y, p["we1"])
    h3 = _einsum(num, "bsd,edf->bsef", y, p["we3"])
    hid = jax.nn.silu(h1) * h3 * w[..., None]
    out = _einsum(num, "bsef,efd->bsd", hid, p["we2"]).reshape(b0, s0, d)
    lb = (e * jnp.sum(probs.mean((0, 1))
                      * jax.nn.one_hot(idx[..., 0], e).mean((0, 1)))
          * r["load_balance_loss"])
    z = (jnp.mean(jax.nn.logsumexp(logits, -1) ** 2) * r["router_z_loss"])
    return out, lb + z


def loss_fn(params, tokens, labels, cfg, num="f32"):
    """Mean next-token cross-entropy (+ routing losses) of one replica's
    batch; params in float32."""
    x = params["embed"]["tok"][tokens]
    blk = params["blocks"][0]
    aux = 0.0
    for layer in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[layer], blk)
        x = x + _attention(p["attn"], x, cfg, num)
        if is_moe(cfg):
            delta, a = _moe(p["moe"], p["moe_norm"], x, cfg, num)
            x, aux = x + delta, aux + a
        else:
            x = x + _mlp(p["ffn"], x, cfg, num)
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    w_out = (params["embed"]["tok"].T if cfg["tie_word_embeddings"]
             else params["unembed"]["w"])
    logits = _einsum(num, "bsd,dv->bsv", x, w_out)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - tgt) + aux


@functools.lru_cache(maxsize=None)
def _step_fn(cfg_json: str, num: str, half_batch: bool):
    cfg = json.loads(cfg_json)

    def step(p, mu, tokens, labels, lr, momentum, wd):
        if half_batch:   # planted fault: the mean over half the batch
            if tokens.shape[0] > 1:
                n = tokens.shape[0] // 2
                tokens, labels = tokens[:n], labels[:n]
            else:
                n = tokens.shape[1] // 2
                tokens, labels = tokens[:, :n], labels[:, :n]
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        loss, g = jax.value_and_grad(loss_fn)(p32, tokens, labels, cfg, num)
        mu = jax.tree.map(lambda m, gi, pi: momentum * m + gi + wd * pi,
                          mu, g, p32)
        store = _fp8_round if num == "fp8" else (lambda x: x)
        p = jax.tree.map(
            lambda old, pi, m: store(pi - lr * m).astype(old.dtype),
            p, p32, mu)
        return p, mu, loss

    return jax.jit(step, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _eval_fn(cfg_json: str, num: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda p, tokens, labels: loss_fn(
        jax.tree.map(lambda a: a.astype(jnp.float32), p), tokens, labels,
        cfg, num))


@jax.jit
def _fp8_params(p):
    return jax.tree.map(
        lambda a: _fp8_round(a.astype(jnp.float32)).astype(a.dtype), p)


@jax.jit
def _merge(p, stale, s2, world):
    return jax.tree.map(
        lambda a, b: ((s2 * a.astype(jnp.float32)
                       + world * b.astype(jnp.float32))
                      / (s2 + world)).astype(a.dtype), p, stale)


@jax.jit
def _mean(trees):
    n = len(trees)
    return jax.tree.map(
        lambda *xs: (sum(x.astype(jnp.float32) for x in xs) / n)
        .astype(xs[0].dtype), *trees)


def cycle_modes(b_max: int, n_steps: int):
    """DASO's cycling schedule at W = max(1, B // 4): (mode, S) per step."""
    w = max(1, b_max // 4)
    out = []
    for t in range(n_steps):
        c = t % b_max
        out.append(("send", 1) if c == 0 else
                   ("receive", w) if c == w else ("local", 1))
    return out


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _diff_norm(x, y):
    return _norm(x.astype(jnp.float32) - y.astype(jnp.float32))


def leaf_norms(tree, cfg, base=None):
    """{leaf path: norm of the leaf (less `base`'s)} in `leaf_paths`
    order, one leaf at a time."""
    paths = leaf_paths(cfg)
    leaves = jax.tree.leaves(tree)
    if base is None:
        vals = [_norm(x) for x in leaves]
    else:
        vals = [_diff_norm(x, y) for x, y in zip(leaves,
                                                 jax.tree.leaves(base))]
    return dict(zip(paths, map(float, jax.device_get(vals))))


def run(cfg: dict, traffic: dict, seed: int, pool: dict, n_steps: int, *,
        numerics: str = "f32", fault: str | None = None, devices=None):
    """Follow the first `n_steps` steps of a cell from the seed.

    Returns {"losses": [n_steps], "mom": [R x {leaf: norm}] (momentum after
    the last step), "upd": [R x {leaf: norm}] (parameter change from the
    initial weights), "div": [R x {leaf: norm}] (distance from replica
    0), "sent": [R x {leaf: norm}] (the in-flight buffer the last send
    left, less the initial weights), "spread": [R x {leaf: norm}] (that
    buffer less replica 0's)}. `fault` plants one of the faults a
    training cell can have: "unchanged" (a step returns its state),
    "half_batch", "no_exchange". Replica r runs on
    devices[r % len(devices)]."""
    devices = devices or [jax.devices()[0]]
    R = traffic["replicas"]
    world = traffic["replicas"] * traffic["local_world"]
    cfg_json = json.dumps(cfg, sort_keys=True)
    step = _step_fn(cfg_json, numerics, fault == "half_batch")
    dev = [devices[r % len(devices)] for r in range(R)]
    # one fresh copy per replica: the step donates its parameters
    params = [jax.device_put(make_params(cfg, seed), dev[r])
              for r in range(R)]
    if numerics == "fp8":
        params = [_fp8_params(p) for p in params]
    mu = [jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
          for p in params]
    hp = (traffic["lr"], traffic["momentum"], traffic["weight_decay"])
    inflight, losses = None, []
    for t, (mode, stale) in enumerate(cycle_modes(traffic["b_max"],
                                                  n_steps)):
        if mode == "receive":
            params = [_merge(params[r], jax.device_put(inflight[r], dev[r]),
                             2.0 * stale, float(world)) for r in range(R)]
        step_losses = []
        for r in range(R):
            tok = jax.device_put(pool["tokens"][t, r], dev[r])
            lab = jax.device_put(pool["labels"][t, r], dev[r])
            if fault == "unchanged":
                step_losses.append(_eval_fn(cfg_json, numerics)(
                    params[r], tok, lab))
                continue
            params[r], mu[r], loss = step(params[r], mu[r], tok, lab, *hp)
            step_losses.append(loss)
        losses.append(float(np.mean(jax.device_get(step_losses))))
        if mode == "send" and fault == "no_exchange":
            # planted fault: each replica "receives" its own parameters
            inflight = [jax.tree.map(jnp.copy, p) for p in params]
        elif mode == "send":
            mean = _mean([jax.device_put(p, dev[0]) for p in params])
            inflight = [mean] * R
    p0 = jax.device_put(make_params(cfg, seed), dev[0])
    upd = [leaf_norms(jax.device_put(params[r], dev[0]), cfg, base=p0)
           for r in range(R)]
    mom = [leaf_norms(m, cfg) for m in mu]
    div = [leaf_norms(jax.device_put(params[r], dev[0]), cfg,
                      base=jax.device_put(params[0], dev[0]))
           for r in range(R)]
    sent = [leaf_norms(jax.device_put(x, dev[0]), cfg, base=p0)
            for x in inflight]
    spread = [leaf_norms(jax.device_put(x, dev[0]), cfg,
                         base=jax.device_put(inflight[0], dev[0]))
              for x in inflight]
    return {"losses": losses, "mom": mom, "upd": upd, "div": div,
            "sent": sent, "spread": spread}
