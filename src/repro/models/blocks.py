"""Residual block assembly: mixer (attn / mamba / rglru) + FFN (dense / MoE)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ATTN_LOCAL, ATTN_SWA, MAMBA, RGLRU)
from repro.models.attention import attn_apply, init_attn
from repro.models.common import dense_init, rms_norm, silu_mlp
from repro.models.mamba import init_mamba, init_mamba_cache, mamba_apply
from repro.models.moe import init_moe, moe_apply
from repro.models.rglru import init_rglru, init_rglru_cache, rglru_apply
from repro.sharding import constrain

ZERO_AUX = {"moe_lb_loss": 0.0, "moe_z_loss": 0.0, "moe_drop_frac": 0.0}
# aux entries taken as the largest over layers; the others are summed
MAX_AUX = ("moe_load_max",)


def zero_aux(cfg) -> dict:
    """The aux entries every block of `cfg` reports: the expert layer's
    load peak only where the model has experts."""
    if cfg.moe is None:
        return dict(ZERO_AUX)
    return dict(ZERO_AUX, moe_load_max=0.0)


def _init_ffn(key, cfg, dtype):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "norm": jnp.zeros((D,), dtype),
        "w1": dense_init(ks[0], (D, F), dtype),
        "w3": dense_init(ks[1], (D, F), dtype),
        "w2": dense_init(ks[2], (F, D), dtype,
                         scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }


def _has_ffn(cfg, kind) -> bool:
    return kind != MAMBA and (cfg.d_ff > 0 or cfg.moe is not None)


def init_block(key, cfg, kind, dtype):
    k1, k2 = jax.random.split(key)
    p = {}
    if kind in (ATTN, ATTN_SWA, ATTN_LOCAL):
        p["attn"] = init_attn(k1, cfg, dtype)
    elif kind == MAMBA:
        p["mamba"] = init_mamba(k1, cfg, dtype)
    elif kind == RGLRU:
        p["rec"] = init_rglru(k1, cfg, dtype)
    else:
        raise ValueError(kind)
    if _has_ffn(cfg, kind):
        if cfg.moe is not None:
            p["moe"] = init_moe(k2, cfg, dtype)
            p["moe_norm"] = jnp.zeros((cfg.d_model,), dtype)
        else:
            p["ffn"] = _init_ffn(k2, cfg, dtype)
    return p


def init_block_cache(cfg, kind, batch, cache_len, dtype):
    if kind in (ATTN, ATTN_SWA, ATTN_LOCAL):
        K, hd = max(cfg.n_kv_heads, 1), max(cfg.head_dim, 1)
        return {"k": jnp.zeros((batch, cache_len, K, hd), dtype),
                "v": jnp.zeros((batch, cache_len, K, hd), dtype)}
    if kind == MAMBA:
        return init_mamba_cache(cfg, batch, dtype)
    if kind == RGLRU:
        return init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def _residual(x, delta, cfg):
    if cfg.residual_multiplier is not None:
        delta = delta * cfg.residual_multiplier
    return x + delta


def block_window(cfg, kind, window_override: int) -> int:
    """Effective attention window for this block kind (0 = unbounded)."""
    if kind in (ATTN_SWA, ATTN_LOCAL):
        return cfg.sliding_window
    if kind == ATTN and window_override:
        return window_override
    return 0


def apply_block(kind, p, x, positions, cfg, *, cache: Optional[dict] = None,
                pos=None, window_override: int = 0, q_chunk: int = 1024,
                mamba_chunk: int = 64, unroll_inner: bool = False,
                attn_impl: str = "auto"):
    """x (B,S,D) -> (x, new_cache, aux)."""
    aux = zero_aux(cfg)
    new_cache = {}
    if kind in (ATTN, ATTN_SWA, ATTN_LOCAL):
        win = block_window(cfg, kind, window_override)
        delta, nc = attn_apply(p["attn"], x, positions, cfg, window=win,
                               cache=None if cache is None else cache,
                               pos=pos, q_chunk=q_chunk, impl=attn_impl)
        x = _residual(x, delta, cfg)
        new_cache = nc
    elif kind == MAMBA:
        h = rms_norm(x, p["mamba"]["norm"], cfg.norm_eps)
        delta, nc = mamba_apply(p["mamba"], h, cfg, cache=cache,
                                chunk=mamba_chunk, unroll=unroll_inner)
        x = _residual(x, delta, cfg)
        new_cache = nc
    elif kind == RGLRU:
        h = rms_norm(x, p["rec"]["norm"], cfg.norm_eps)
        delta, nc = rglru_apply(p["rec"], h, cfg, cache=cache,
                                unroll=unroll_inner)
        x = _residual(x, delta, cfg)
        new_cache = nc
    if _has_ffn(cfg, kind):
        if cfg.moe is not None:
            h = rms_norm(x, p["moe_norm"], cfg.norm_eps)
            delta, aux = moe_apply(p["moe"], h, cfg)
            x = _residual(x, delta, cfg)
        else:
            h = rms_norm(x, p["ffn"]["norm"], cfg.norm_eps)
            x = _residual(x, silu_mlp(h, p["ffn"]["w1"], p["ffn"]["w3"],
                                      p["ffn"]["w2"]), cfg)
    x = constrain(x, "batch", None, None)
    return x, new_cache, aux
