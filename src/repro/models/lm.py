"""Unified decoder LM covering all assigned transformer-family architectures.

Layers follow cfg.layer_pattern (e.g. recurrentgemma's (rglru, rglru,
attn_local)). The repeated pattern groups are stacked and iterated with
jax.lax.scan to keep HLO size / compile time bounded for 64-layer configs;
remainder layers (n_layers % len(pattern)) are applied unrolled.

Entry points:
  init_params(cfg, key)                      -> params pytree
  forward(params, tokens, cfg, ...)          -> {"logits", "aux", "cache"}
  init_cache(cfg, batch, cache_len, ...)     -> decode cache pytree
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.blocks import (MAX_AUX, apply_block, block_window,
                                 init_block, init_block_cache, zero_aux)
from repro.models.common import dense_init, embed_init, rms_norm, \
    sinusoidal_positions
from repro.models.moe import SAVED_NAMES
from repro.sharding import constrain


# What the backward pass of a model with experts keeps of each layer: the
# outputs of the matrix products without batch dimensions (projections,
# router) and the expert layer's grouped products (moe.SAVED_NAMES). The
# rest (norms, RoPE, each attention chunk's float32 scores and
# probabilities, the expert gathers and gating) is recomputed: such a
# model's step is bound by bytes rather than FLOPs, so the recomputation
# is cheap, and a scan of L layers would otherwise hold L layers' of those
# at once (at 4 layers of 2 x 4096 tokens of Granite-3.0-3B-A800M, 11.25
# GB of attention scores and probabilities alone, more than the chip has
# beside the training state). A dense model keeps everything.
SCAN_SAVE = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES))


def _pattern_counts(cfg: ArchConfig):
    plen = len(cfg.layer_pattern)
    return cfg.n_layers // plen, cfg.n_layers % plen


def init_params(cfg: ArchConfig, key):
    dtype = cfg.pdtype()
    n_full, n_rem = _pattern_counts(cfg)
    k_embed, k_blocks, k_rem, k_out = jax.random.split(key, 4)
    params = {"embed": {"tok": embed_init(k_embed,
                                          (cfg.vocab_size, cfg.d_model),
                                          dtype)}}
    blocks = []
    bkeys = jax.random.split(k_blocks, max(n_full, 1) * len(cfg.layer_pattern))
    for j, kind in enumerate(cfg.layer_pattern):
        per_repeat = [init_block(bkeys[r * len(cfg.layer_pattern) + j],
                                 cfg, kind, dtype) for r in range(n_full)]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_repeat))
    params["blocks"] = blocks
    rkeys = jax.random.split(k_rem, max(n_rem, 1))
    params["rem"] = [init_block(rkeys[j], cfg, cfg.layer_pattern[j], dtype)
                     for j in range(n_rem)]
    params["final_norm"] = {"scale": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        params["unembed"] = {"w": dense_init(k_out,
                                             (cfg.d_model, cfg.vocab_size),
                                             dtype)}
    return params


def init_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype=None,
               window_override: int = 0):
    """Decode cache. cache_len: max positions for full-attention layers;
    windowed layers allocate min(window, cache_len)."""
    dtype = dtype or cfg.cdtype()
    n_full, n_rem = _pattern_counts(cfg)

    def one(kind):
        win = block_window(cfg, kind, window_override)
        clen = min(win, cache_len) if win else cache_len
        return init_block_cache(cfg, kind, batch, clen, dtype)

    groups = []
    for kind in cfg.layer_pattern:
        per = [one(kind) for _ in range(n_full)]
        groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    rem = [one(cfg.layer_pattern[j]) for j in range(n_rem)]
    return {"groups": groups, "rem": rem}


def _acc_aux(acc, aux):
    return {k: jnp.maximum(acc[k], aux[k]) if k in MAX_AUX else acc[k] + aux[k]
            for k in acc}


def forward(params, tokens, cfg: ArchConfig, *,
            prefix_embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            cache: Optional[dict] = None, pos=None,
            window_override: int = 0, q_chunk: int = 1024,
            mamba_chunk: int = 64, remat: bool = False,
            logits_f32: bool = False, unroll_layers: bool = False,
            attn_impl: str = "auto"):
    """tokens (B, S_tok) int32. Returns {"logits" (B,S,V), "aux", "cache"}.

    prefix_embeds (B, P, D): frontend stub embeddings (vlm/audio) spliced
    before the token embeddings; logits/labels cover the full spliced length.
    decode: tokens (B,1), cache + pos given.
    """
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if cfg.embedding_multiplier is not None:
        x = x * cfg.embedding_multiplier
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, D = x.shape
    if positions is None:
        base = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        positions = (jnp.tile(base[..., None], (1, 1, 3))
                     if cfg.rope_type == "mrope" else base)
    if cfg.rope_type == "none":
        pos1 = positions if positions.ndim == 2 else positions[..., 0]
        x = x + sinusoidal_positions(pos1, D).astype(x.dtype)
    x = constrain(x, "batch", None, None)

    n_full, n_rem = _pattern_counts(cfg)
    decode_mode = cache is not None and x.shape[1] == 1
    prefill_mode = cache is not None and not decode_mode

    block_fn = functools.partial(
        apply_block, positions=positions, cfg=cfg, pos=pos,
        window_override=window_override, q_chunk=q_chunk,
        mamba_chunk=mamba_chunk, unroll_inner=unroll_layers,
        attn_impl=attn_impl)

    def group_body(carry, xs):
        x, aux = carry
        p_slices, c_slices = xs
        new_caches = []
        for j, kind in enumerate(cfg.layer_pattern):
            c_j = None if c_slices is None else c_slices[j]
            x, nc, a = block_fn(kind, p_slices[j], x, cache=c_j)
            aux = _acc_aux(aux, a)
            new_caches.append(nc)
        return (x, aux), (tuple(new_caches) if cache is not None else None)

    if remat:
        body = jax.checkpoint(group_body)
    elif cfg.moe is not None:
        body = jax.checkpoint(group_body, policy=SCAN_SAVE, prevent_cse=False)
    else:
        body = group_body
    aux0 = {k: jnp.zeros((), jnp.float32) for k in zero_aux(cfg)}
    cache_groups = tuple(cache["groups"]) if cache is not None else None
    if n_full > 0 and unroll_layers:
        # python-loop over repeats: larger HLO, but XLA cost_analysis counts
        # every repeat (scan bodies are counted once) — used by the roofline
        # per-layer cost extraction, never by the production path.
        carry, ys = (x, aux0), []
        xs = (tuple(params["blocks"]), cache_groups)
        for r in range(n_full):
            xs_r = jax.tree.map(lambda a: a[r], xs)
            carry, y = body(carry, xs_r)
            ys.append(y)
        (x, aux) = carry
        new_groups = (jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
                      if cache is not None else None)
    elif n_full > 0:
        (x, aux), new_groups = jax.lax.scan(
            body, (x, aux0), (tuple(params["blocks"]), cache_groups))
    else:
        aux, new_groups = aux0, None

    new_rem = []
    for j in range(n_rem):
        kind = cfg.layer_pattern[j]
        c_j = None if cache is None else cache["rem"][j]
        x, nc, a = block_fn(kind, params["rem"][j], x, cache=c_j)
        aux = _acc_aux(aux, a)
        new_rem.append(nc)

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["tok"].T
    else:
        logits = x @ params["unembed"]["w"]
    if cfg.logits_scaling is not None:
        logits = logits / cfg.logits_scaling
    if logits_f32:
        logits = logits.astype(jnp.float32)
    logits = constrain(logits, "batch", None, "model")
    new_cache = None
    if cache is not None:
        new_cache = {"groups": list(new_groups), "rem": new_rem}
    return {"logits": logits, "aux": aux, "cache": new_cache}


class DecoderLM:
    """Thin OO convenience wrapper used by examples."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def init(self, key):
        return init_params(self.cfg, key)

    def __call__(self, params, tokens, **kw):
        return forward(params, tokens, self.cfg, **kw)

    def init_cache(self, batch, cache_len, **kw):
        return init_cache(self.cfg, batch, cache_len, **kw)
