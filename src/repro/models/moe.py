"""Mixture-of-Experts FFN: a dropless sorted path and a GShard capacity path.

Both route alike (`_route`): a float32 router, softmax, top-k, the k gates
renormalised to sum to 1 (which equals a softmax over the top-k logits).
What happens to the (token, slot) pairs depends on whether capacity can
bind (`MoEConfig.dropless`):

* dropless (capacity never binds): the pairs are sorted by expert, each
  token's row gathered once per slot into expert-contiguous order, every
  expert's SwiGLU run over its own contiguous rows by a grouped matrix
  product (`kernels/grouped_matmul.py`, grouped in the backward pass
  too), and the gated rows summed back per token. No pair is dropped and
  no expert computes a row it was not given.
* capacity: TPU-idiomatic dense dispatch (one-hot capacity einsums, no
  gather/scatter) in routing groups of `group_size` tokens; pairs past an
  expert's capacity are dropped. Under GSPMD this partitions as expert
  parallelism (expert axis over "model") or tensor parallelism (per-expert
  d_ff over "model") per MoEConfig.sharding — see DESIGN.md §6.

Aux: switch load-balance loss on the top-1 choice, router z-loss, the
share of pairs dropped, and the largest expert's rows over the mean.
Device ops carry the scopes `repro.moe.route`, `.dispatch`, `.experts`,
`.combine`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels.grouped_matmul import gmm
from repro.models.common import dense_init, silu_mlp


# the grouped products' outputs, by name: what a recomputing backward pass
# (the layer scan's checkpoint policy, models/lm.py) keeps of the sorted
# path, so that it recomputes the row gathers, the gating and the gated
# sum but no product
SAVED_NAMES = ("moe_h1", "moe_h3", "moe_y")


def init_moe(key, cfg, dtype):
    m = cfg.moe
    D, F, E = cfg.d_model, m.d_ff, m.n_experts
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "we1": dense_init(ks[1], (E, D, F), dtype),
        "we3": dense_init(ks[2], (E, D, F), dtype),
        "we2": dense_init(ks[3], (E, F, D), dtype,
                          scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if m.n_shared_experts:
        Fs = m.d_ff * m.n_shared_experts
        p["shared"] = {
            "w1": dense_init(ks[4], (D, Fs), dtype),
            "w3": dense_init(ks[5], (D, Fs), dtype),
            "w2": dense_init(ks[6], (Fs, D), dtype,
                             scale=1.0 / (2 * cfg.n_layers) ** 0.5),
        }
    return p


def moe_apply(p, x, cfg):
    """x (B,S,D) -> (out (B,S,D), aux dict of scalars).

    On the capacity path tokens are routed in groups of moe.group_size
    (GShard-style): capacity is per group, so the dispatch/combine tensors
    stay O(G^2 K/E) per group regardless of sequence length (a 32k
    sequence routed as ONE group would need a (32768, E, 8192)-sized
    combine — see EXPERIMENTS.md §Perf P3)."""
    m = cfg.moe
    if m.dropless:
        out, aux = _moe_dropless(p, x, cfg)
    else:
        B0, S0, D = x.shape
        G = m.group_size
        xg = x
        if S0 > G and S0 % G == 0:
            xg = x.reshape(B0 * (S0 // G), G, D)
        out, aux = _moe_grouped(p, xg, cfg)
        out = out.reshape(B0, S0, D)
    if "shared" in p:
        sh = p["shared"]
        out = out + silu_mlp(x, sh["w1"], sh["w3"], sh["w2"])
    return out, aux


def _route(x, router, k):
    """float32 logits, probabilities, the top-k gates renormalised over
    the k, and the k experts, of every token (leading axes of x)."""
    logits = x.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, gate_idx


def _aux(m, logits, probs, gate_idx, dropped, load_max):
    """Switch load-balance loss (mean router probability against the
    share of tokens whose top-1 choice is each expert) and router z-loss,
    over every token; the drop share and the load peak as given."""
    E = m.n_experts
    lead = tuple(range(probs.ndim - 1))
    me = probs.mean(axis=lead)
    top1 = jax.nn.one_hot(gate_idx[..., 0], E).mean(axis=lead)
    lb_loss = E * jnp.sum(me * top1) * m.load_balance_loss
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_loss
    return {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss,
            "moe_drop_frac": jnp.asarray(dropped, jnp.float32),
            "moe_load_max": jnp.asarray(load_max, jnp.float32)}


def _swiglu_gate(h1, h3):
    """silu(h1) * h3 in float32, rounded once to the compute dtype: in
    float8 the sigmoid's exp overflows to NaN (e4m3fn has no inf)."""
    return (jax.nn.silu(h1.astype(jnp.float32))
            * h3.astype(jnp.float32)).astype(h1.dtype)


@jax.custom_vjp
def _permute_rows(x, idx, inv):
    """x[idx] for a permutation `idx` whose inverse is `inv`: the backward
    pass is the gather by `inv`, not a scatter-add."""
    return jnp.take(x, idx, axis=0)


def _permute_rows_fwd(x, idx, inv):
    return jnp.take(x, idx, axis=0), (idx, inv)


def _permute_rows_bwd(res, g):
    _, inv = res
    return jnp.take(g, inv, axis=0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _moe_dropless(p, x, cfg):
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    xt = x.reshape(B * S, D)
    with jax.named_scope("repro.moe.route"):
        logits, probs, gate_vals, gate_idx = _route(xt, p["router"], K)
    with jax.named_scope("repro.moe.dispatch"):
        expert = gate_idx.reshape(-1)                # (T*K,) token-major
        order = jnp.argsort(expert, stable=True)     # rows in expert order
        inv = jnp.argsort(order)
        sizes = jnp.bincount(expert, length=E).astype(jnp.int32)
    out = _sorted_experts(xt, p["we1"], p["we3"], p["we2"], gate_vals,
                          order, inv, sizes)
    load_max = sizes.max() * (E / (B * S * K))
    aux = _aux(m, logits, probs, gate_idx, 0.0, load_max)
    return out.astype(x.dtype).reshape(B, S, D), aux


def _sorted_experts(xt, we1, we3, we2, gate_vals, order, inv, sizes):
    T, K = gate_vals.shape
    with jax.named_scope("repro.moe.dispatch"):
        rows = _permute_rows(jnp.repeat(xt, K, axis=0), order, inv)
    with jax.named_scope("repro.moe.experts"):
        h1 = checkpoint_name(gmm(rows, we1, sizes), SAVED_NAMES[0])
        h3 = checkpoint_name(gmm(rows, we3, sizes), SAVED_NAMES[1])
        y = checkpoint_name(gmm(_swiglu_gate(h1, h3), we2, sizes),
                            SAVED_NAMES[2])
    with jax.named_scope("repro.moe.combine"):
        # back to (token, slot) order, then the gated sum over the slots:
        # a scatter-add of each row into its token, without the scatter
        y = _permute_rows(y, inv, order).reshape(T, K, -1)
        return jnp.sum(y.astype(jnp.float32) * gate_vals[..., None], axis=1)


def _moe_grouped(p, x, cfg):
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    C = max(1, int(S * K * m.capacity_factor / E))

    with jax.named_scope("repro.moe.route"):
        logits, probs, gate_vals, gate_idx = _route(x, p["router"], K)

    # -- capacity assignment (per-group), GShard style ---------------------
    # combine accumulates in the compute dtype: it holds disjoint one-hot
    # slots weighted by gates in [0,1], so bf16 is exact enough and halves
    # the largest routing tensor (§Perf P3).
    with jax.named_scope("repro.moe.dispatch"):
        combine = jnp.zeros((B, S, E, C), x.dtype)
        counts = jnp.zeros((B, E), jnp.float32)
        for slot in range(K):
            oh = jax.nn.one_hot(gate_idx[:, :, slot], E)    # (B,S,E)
            pos = jnp.cumsum(oh, axis=1) - 1 + counts[:, None]
            in_cap = ((pos < C) * oh).astype(x.dtype)        # (B,S,E)
            pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, C - 1).astype(jnp.int32),
                                    C, dtype=x.dtype)
            combine = combine + (gate_vals[:, :, slot, None, None]
                                 .astype(x.dtype) * in_cap[..., None]
                                 * pos_oh)
            counts = counts + oh.sum(axis=1)
        dispatch = (combine > 0).astype(x.dtype)            # (B,S,E,C)
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
    with jax.named_scope("repro.moe.experts"):
        h = _swiglu_gate(jnp.einsum("ebcd,edf->ebcf", expert_in, p["we1"]),
                         jnp.einsum("ebcd,edf->ebcf", expert_in, p["we3"]))
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, p["we2"])
    with jax.named_scope("repro.moe.combine"):
        out = jnp.einsum("bsec,ebcd->bsd", combine, expert_out)

    kept = dispatch.astype(jnp.float32).sum()
    load_max = counts.max() * (E / (S * K))
    aux = _aux(m, logits, probs, gate_idx, 1.0 - kept / (B * S * K),
               load_max)
    return out, aux
