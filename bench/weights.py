"""Seeded weights in the program's parameter layout, made on the device
in one jitted call, in the type they are trained in.

The layout (`leaf_specs`) is written out here, not taken from the
program, so that the plain reference can build the same weights from the
seed without importing anything of the program; the harness checks it
against the program's own `init_params` shapes before a run. The
distributions are the usual ones for this family: truncated normal,
std 1/sqrt(fan_in) (0.02 for the embedding), output projections scaled
by 1/sqrt(2 x layers), norm scales zero (the norm multiplies by
1 + scale)."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from bench.model import head_dim, is_moe


def leaf_specs(cfg: dict):
    """[(path, shape, dtype, std)] in the order of the program's pytree;
    std None means zeros. Blocks are stacked over layers (leading axis)."""
    d, v, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    f = cfg["intermediate_size"]
    pdt = jnp.dtype(cfg["torch_dtype"])
    out_std = 1.0 / (2 * L) ** 0.5
    specs = [(("embed", "tok"), (v, d), pdt, 0.02)]
    blk = ("blocks", 0)
    specs += [
        (blk + ("attn", "wq"), (L, d, h * hd), pdt, d ** -0.5),
        (blk + ("attn", "wk"), (L, d, kv * hd), pdt, d ** -0.5),
        (blk + ("attn", "wv"), (L, d, kv * hd), pdt, d ** -0.5),
        (blk + ("attn", "wo"), (L, h * hd, d), pdt,
         out_std * (h * hd) ** -0.5),
        (blk + ("attn", "norm"), (L, d), pdt, None)]
    if is_moe(cfg):
        e = cfg["num_local_experts"]
        specs += [
            (blk + ("moe", "router"), (L, d, e), jnp.dtype(jnp.float32),
             d ** -0.5),
            (blk + ("moe", "we1"), (L, e, d, f), pdt, d ** -0.5),
            (blk + ("moe", "we3"), (L, e, d, f), pdt, d ** -0.5),
            (blk + ("moe", "we2"), (L, e, f, d), pdt, out_std * f ** -0.5),
            (blk + ("moe_norm",), (L, d), pdt, None)]
    else:
        specs += [
            (blk + ("ffn", "norm"), (L, d), pdt, None),
            (blk + ("ffn", "w1"), (L, d, f), pdt, d ** -0.5),
            (blk + ("ffn", "w3"), (L, d, f), pdt, d ** -0.5),
            (blk + ("ffn", "w2"), (L, f, d), pdt, out_std * f ** -0.5)]
    specs.append((("final_norm", "scale"), (d,), pdt, None))
    if not cfg["tie_word_embeddings"]:
        specs.append((("unembed", "w"), (d, v), pdt, d ** -0.5))
    return specs


def _nest(specs, leaves):
    tree = {"rem": []}
    for (path, *_), x in zip(specs, leaves):
        node = tree
        for key, nxt in zip(path[:-1], path[1:]):
            if key == "blocks":
                node = node.setdefault("blocks", [{}])[0]
                continue
            if isinstance(key, int):
                continue
            node = node.setdefault(key, {})
        node[path[-1]] = x
    return tree


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def leaf_paths(cfg: dict):
    return ["/".join(str(k) for k in path) for path, *_ in leaf_specs(cfg)]


def make_fn(cfg: dict):
    """key -> initial parameters (untraced; `make_params` jits it)."""
    specs = leaf_specs(cfg)

    def make(key):
        leaves = []
        for i, (_, shape, dtype, std) in enumerate(specs):
            if std is None:
                leaves.append(jnp.zeros(shape, dtype))
                continue
            x = jax.random.truncated_normal(jax.random.fold_in(key, i),
                                            -2.0, 2.0, shape, jnp.float32)
            leaves.append((x * std).astype(dtype))
        return _nest(specs, leaves)

    return make


@functools.lru_cache(maxsize=None)
def _maker(cfg_json: str):
    return jax.jit(make_fn(json.loads(cfg_json)))


def make_params(cfg: dict, seed: int):
    """The initial parameters of a run, on the default device."""
    return _maker(json.dumps(cfg, sort_keys=True))(seed_key(seed))
