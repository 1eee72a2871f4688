"""GQA attention: chunked-q causal/sliding-window training path + cached decode.

The training/prefill path iterates q-chunks in a *python* loop with static
slice bounds: (a) only the causally/window-reachable K/V slice is read per
chunk, so FLOPs match the true masked cost (0.5x full for causal, O(S*W) for
windowed); (b) no lax.scan, so XLA cost_analysis counts every chunk (scan
bodies are counted once — see EXPERIMENTS.md roofline methodology).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import head_rms_norm, rms_norm
from repro.models.rope import apply_mrope, apply_rope
from repro.sharding import constrain

NEG_INF = -1e30


def _pick_chunk(seq: int, target: int) -> int:
    c = min(target, seq)
    while seq % c:
        c //= 2
    return max(c, 1)


def multihead_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        q_chunk: int = 1024, scale=None):
    """q (B,Sq,Hq,D); k,v (B,Sk,K,D); GQA via grouped einsum. Returns (B,Sq,Hq,D).

    Assumes q and k cover the same token range starting at position 0
    (training / prefill). window > 0 restricts attention to the last `window`
    positions (inclusive of self). scale: of the scores (None: D ** -0.5).
    """
    B, Sq, Hq, D = q.shape
    K = k.shape[2]
    G = Hq // K
    qg = q.reshape(B, Sq, K, G, D)
    if scale is None:
        scale = D ** -0.5
    C = _pick_chunk(Sq, q_chunk)
    outs = []
    for qc in range(0, Sq, C):
        # static K/V slice reachable from rows [qc, qc+C)
        hi = min(qc + C, k.shape[1]) if causal else k.shape[1]
        lo = max(0, qc - window + 1) if window else 0
        # NOTE (EXPERIMENTS.md §Perf P1): constraining these slices was
        # tried to remove a small GSPMD pod-axis partial-reduction in the
        # chunk backward — it backfired (forces k/v resharding per chunk,
        # ~2x more cross-pod bytes). Refuted; left unconstrained.
        ks, vs = k[:, lo:hi], v[:, lo:hi]
        qs = qg[:, qc:qc + C]
        scores = jnp.einsum("bckgd,blkd->bkgcl", qs, ks,
                            preferred_element_type=jnp.float32) * scale
        row = qc + jnp.arange(C)[:, None]
        col = lo + jnp.arange(hi - lo)[None, :]
        mask = jnp.ones((C, hi - lo), bool)
        if causal:
            mask &= col <= row
        if window:
            mask &= col > row - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("bkgcl,blkd->bckgd", probs, vs))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(B, Sq, Hq, D)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     scale=None):
    """One-token decode. q (B,1,Hq,D); caches:
      full:  (B,S_max,K,D), valid slots are indices <= pos
      ring:  (B,W,K,D) with W == window; slot i holds absolute position
             pos - ((pos - i) mod W)
    pos: scalar int32 — absolute position of the current token (0-based).
    """
    B, _, Hq, D = q.shape
    K = k_cache.shape[2]
    G = Hq // K
    qg = q.reshape(B, 1, K, G, D)
    if scale is None:
        scale = D ** -0.5
    scores = jnp.einsum("bckgd,blkd->bkgcl", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    S = k_cache.shape[1]
    slots = jnp.arange(S)
    if window:
        abs_pos = pos - jnp.mod(pos - slots, S)
        valid = abs_pos >= 0
    else:
        valid = slots <= pos
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgcl,blkd->bckgd", probs, v_cache)
    return out.reshape(B, 1, Hq, D)


def init_attn(key, cfg, dtype):
    from repro.models.common import dense_init
    D, Hq, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, Hq * hd), dtype),
        "wk": dense_init(ks[1], (D, K * hd), dtype),
        "wv": dense_init(ks[2], (D, K * hd), dtype),
        "wo": dense_init(ks[3], (Hq * hd, D), dtype,
                         scale=1.0 / (2 * cfg.n_layers) ** 0.5),
        "norm": jnp.zeros((D,), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def pallas_attention(q, k, v, *, causal: bool = True, window: int = 0,
                     scale=None):
    """Route (B,S,H,D)-layout attention through the Pallas flash kernel
    (repro.kernels). Per-device execution: use on single-device paths or
    inside shard_map; the GSPMD dry-run path uses the jnp implementation
    (identical math, freely partitionable)."""
    from repro.kernels.ops import flash_attention as _fa
    if scale is not None:  # the kernel scales by head_dim ** -0.5
        q = q * (scale * q.shape[-1] ** 0.5)
    out = _fa(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
              causal=causal, window=window)
    return out.swapaxes(1, 2)


def attn_apply(p, x, positions, cfg, *, window: int = 0,
               cache: Optional[dict] = None, pos=None, q_chunk: int = 1024,
               impl: str = "jnp"):
    """Pre-norm attention sub-block. Returns (residual_delta, new_cache).

    Training/prefill: cache is None or an empty cache dict to fill.
    Decode: x is (B,1,D), cache holds K/V, pos is the absolute position.
    """
    B, S, D = x.shape
    Hq, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(B, S, Hq, hd)
    k = (h @ p["wk"]).reshape(B, S, K, hd)
    v = (h @ p["wv"]).reshape(B, S, K, hd)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, None, None)
    v = constrain(v, "batch", None, None, None)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_type == "standard":
        q, k = apply_rope(q, k, positions, cfg.rope_theta)
    elif cfg.rope_type == "mrope":
        q, k = apply_mrope(q, k, positions, cfg.rope_theta)

    decode = cache is not None and pos is not None and S == 1
    if decode:
        S_c = cache["k"].shape[1]
        slot = jnp.mod(pos, S_c) if window else pos
        iota = jnp.arange(S_c)[None, :, None, None]
        k_cache = jnp.where(iota == slot, k, cache["k"])
        v_cache = jnp.where(iota == slot, v, cache["v"])
        out = decode_attention(q, k_cache, v_cache, pos, window=window,
                               scale=cfg.attention_multiplier)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        if impl == "pallas":
            out = pallas_attention(q, k, v, causal=True, window=window,
                                   scale=cfg.attention_multiplier)
        else:
            out = multihead_attention(q, k, v, causal=True, window=window,
                                      q_chunk=q_chunk,
                                      scale=cfg.attention_multiplier)
        new_cache = None
        if cache is not None:  # prefill: populate cache
            S_c = cache["k"].shape[1]
            if window and S_c < S:
                # keep the last S_c positions; ring layout slot = pos % S_c
                tail_k, tail_v = k[:, -S_c:], v[:, -S_c:]
                shift = S % S_c
                new_cache = {"k": jnp.roll(tail_k, shift, axis=1),
                             "v": jnp.roll(tail_v, shift, axis=1)}
            else:
                pad = [(0, 0), (0, S_c - S), (0, 0), (0, 0)]
                new_cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    out = constrain(out, "batch", None, "model", None)
    delta = out.reshape(B, S, Hq * hd) @ p["wo"]
    return delta, new_cache
