"""Flash attention forward kernel (Pallas TPU): blocked online-softmax,
causal + sliding-window + GQA.

TPU adaptation of the FlashAttention blocking: q tiles of (block_q, head_dim)
stream from HBM into VMEM per grid step; the full K/V for one (batch, kv-head)
pair is VMEM-resident and walked in block_k chunks by an in-kernel fori_loop
carrying the running (max, denom, acc) — MXU-aligned tiles (block sizes are
multiples of 128 on the contracting dims).

Layout: q (B, Hq, Sq, D); k/v (B, Hk, Sk, D); Hq = G * Hk (GQA). Grid is
(B, Hq, Sq/block_q); the k/v BlockSpec index map folds the GQA group
(h -> h // G), so no materialized head expansion.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                block_k, kv_len, q_offset):
    block_q, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    qi = pl.program_id(2)
    row = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)

    nk = kv_len // block_k
    if causal:
        # skip kv blocks strictly above the causal frontier of this q block
        hi = ((q_offset + (qi + 1) * block_q + block_k - 1) // block_k)
        nk_eff = jnp.minimum(nk, hi)
    else:
        nk_eff = nk

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.dslice(j * block_k, block_k), :]
        v = v_ref[pl.dslice(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())))  # (bq, bk)
        col = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= col <= row
        if window > 0:
            mask &= col > row - window
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m)
        alpha = jnp.exp(m_prev - m)
        l = l_prev * alpha + p.sum(axis=1, keepdims=True)
        # f32 accumulator: the TPU compiler refuses a bf16 x bf16 matmul
        # that accumulates in bf16
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m, l, acc

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q (B,Hq,Sq,D); k,v (B,Hk,Sk,D) -> (B,Hq,Sq,D).

    Sq may be shorter than Sk (the q rows are the suffix of the kv range,
    e.g. chunked prefill); rows are aligned at the end."""
    B, Hq, Sq, D = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    G = Hq // Hk
    bq = min(block_q, Sq)
    while Sq % bq:
        bq //= 2
    bk = min(block_k, Sk)
    while Sk % bk:
        bk //= 2
    grid = (B, Hq, Sq // bq)
    kernel = functools.partial(
        _fwd_kernel, scale=D ** -0.5, causal=causal, window=window,
        block_k=bk, kv_len=Sk, q_offset=Sk - Sq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, Sk, D), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((None, None, Sk, D), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
