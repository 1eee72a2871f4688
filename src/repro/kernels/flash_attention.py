"""Flash attention (Pallas TPU) with a backward pass: blocked online
softmax, causal + sliding window + GQA.

Forward: grid (B, Hq, Sq/bq, Sk/bk), the kv blocks innermost. Each step
brings one (bk, D) block of k and v into VMEM and folds it into its q
block's running max, denominator and (bq, D) accumulator, held in VMEM
scratch, so the (bq, bk) scores and probabilities never reach HBM. It
writes o and each row's log-sum-exp.

Backward: two kernels recompute the probabilities block by block from q,
k and the log-sum-exp. `dq`: grid (B, Hq, Sq/bq, Sk/bk), kv innermost.
`dkv`: grid (B, Hk, Sk/bk, G * Sq/bq), the G q heads of a kv head and
their q blocks innermost, so dk and dv come out summed over the group.
Within it the scores are taken transposed, (bk, bq), so that the
log-sum-exp and the row term sum(o * dO) are read as lane rows and every
product is a plain one.

A block that the causal or window mask leaves empty is skipped: its grid
step computes nothing, and its index map repeats a block the kernel needs
anyway, so nothing is copied for it.

Precision: q, k and v enter the MXU in their own dtype with f32
accumulation; the scale multiplies the f32 scores (it is not folded into
q); the softmax runs in f32; P is rounded to v's dtype before P.V and
before the dv product; the score gradient is rounded to q's dtype before
the dq and dk products.

Layout: q (B, Hq, Sq, D); k, v (B, Hk, Sk, D); Hq = G * Hk, and the k/v
index maps fold the group (h -> h // G), so the head expansion is never
materialised. Sq may be shorter than Sk: the q rows are then the suffix of
the kv range. The log-sum-exp is stored as rows (B, Hq, 1, Sq), f32.

Every `pallas_call` is named with the prefix `KERNEL_PREFIX`, which is
what a trace reader finds the kernel's operations by.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
KERNEL_PREFIX = "flash_attn"
# The names the forward pass gives its residuals (o and the log-sum-exp),
# for a checkpoint policy that keeps them (`jax.checkpoint_policies.
# save_only_these_names`) instead of running the forward kernel again.
SAVED_NAMES = ("flash_attn_out", "flash_attn_lse")

_NT = (((1,), (1,)), ((), ()))  # a @ b.T

# Blocks by head_dim, ((bq, bk) forward, (bq, bk) backward), tuned on a
# TPU v5e at the benchmark cells' shapes (PERF.md); the head dims the
# model's default path sends here. A length they do not divide runs them
# cut down (`_fit_block`), to no less than SEQ_MULTIPLE.
TUNED_BLOCKS = {64: ((1024, 1024), (512, 512)),
                128: ((1024, 1024), (1024, 1024))}
SEQ_MULTIPLE = 512


class _Spec(NamedTuple):
    """What the kernels are specialised on (static under jit)."""
    scale: float
    causal: bool
    window: int
    blocks: Tuple[int, int]        # forward (bq, bk)
    bwd_blocks: Tuple[int, int]    # dq and dkv (bq, bk)
    interpret: bool


def _fit_block(block: int, n: int) -> int:
    """The largest block of at most `block` that divides `n` (halving)."""
    b = min(block, n)
    while n % b:
        b //= 2
    return max(b, 1)


# -- which blocks the mask leaves non-empty ----------------------------------
# Rows of q block i: [r0, r1] = q_offset + [i bq, (i + 1) bq - 1]; columns
# of kv block j: [c0, c1] = [j bk, (j + 1) bk - 1]. The pair has a visible
# entry iff c0 <= r1 (causal) and c1 >= r0 - window + 1 (window).

def _kv_range(i, *, bq, bk, nk, q_offset, causal, window):
    """First and last kv block that q block i sees."""
    r0 = q_offset + i * bq
    lo = (jnp.maximum(r0 - window + 1, 0) // bk) if window > 0 else 0
    hi = jnp.minimum((r0 + bq - 1) // bk, nk - 1) if causal else nk - 1
    return lo, hi


def _q_range(j, *, bq, bk, nq, q_offset, causal, window):
    """First and last q block that sees kv block j."""
    c0 = j * bk
    lo = jnp.maximum(c0 - q_offset, 0) // bq if causal else 0
    hi = (jnp.minimum((c0 + bk + window - 2 - q_offset) // bq, nq - 1)
          if window > 0 else nq - 1)
    return lo, hi


def _visible(rows, cols, causal, window):
    """Mask of the entries a row may see (None: all of them)."""
    mask = None
    if causal:
        mask = cols <= rows
    if window > 0:
        w = cols > rows - window
        mask = w if mask is None else mask & w
    return mask


def _col_to_row(x):
    """(n, 1) -> (1, n), through a lane-aligned transpose."""
    return jnp.broadcast_to(x, (x.shape[0], LANES)).T[:1, :]


def _row_to_col(x):
    """(1, n) -> (n, 1)."""
    return jnp.broadcast_to(x, (LANES, x.shape[1])).T[:, :1]


# -- forward ------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, causal, window, bq, bk, nk, q_offset):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    lo, hi = _kv_range(i, bq=bq, bk=bk, nk=nk, q_offset=q_offset,
                       causal=causal, window=window)

    @pl.when((j >= lo) & (j <= hi))
    def _step():
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        rows = q_offset + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _visible(rows, cols, causal, window)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m)
        alpha = jnp.exp(m_prev - m)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m

    @pl.when(j == nk - 1)
    def _out():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = _col_to_row(m_sc[...] + jnp.log(l))


def _cost(spec: _Spec, q, k, products: int, *extra):
    """The kernel's work for the XLA scheduler: `products` matrix products
    over the visible (causal: about half) score entries."""
    B, Hq, Sq, D = q.shape
    entries = B * Hq * Sq * k.shape[2] // (2 if spec.causal else 1)
    nbytes = sum(x.size * x.dtype.itemsize for x in (q, k) + extra)
    return pl.CostEstimate(flops=2 * products * entries * D,
                           transcendentals=entries, bytes_accessed=nbytes)


def _forward(q, k, v, spec: _Spec):
    B, Hq, Sq, D = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    G = Hq // Hk
    bq, bk = _fit_block(spec.blocks[0], Sq), _fit_block(spec.blocks[1], Sk)
    nq, nk = Sq // bq, Sk // bk
    q_offset = Sk - Sq
    rng = functools.partial(_kv_range, bq=bq, bk=bk, nk=nk,
                            q_offset=q_offset, causal=spec.causal,
                            window=spec.window)

    def kv_map(b, h, i, j):
        lo, hi = rng(i)
        return b, h // G, jnp.minimum(jnp.maximum(j, lo), hi), 0

    kernel = functools.partial(
        _fwd_kernel, scale=spec.scale, causal=spec.causal,
        window=spec.window, bq=bq, bk=bk, nk=nk, q_offset=q_offset)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, D), kv_map),
            pl.BlockSpec((None, None, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=_cost(spec, q, k, 2, v, q),
        interpret=spec.interpret,
        name=f"{KERNEL_PREFIX}_fwd",
    )(q, k, v)
    return o, lse


# -- backward -----------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_sc, di_sc, acc_sc, *, scale, causal, window, bq, bk, nk,
               q_offset):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        lse_sc[...] = _row_to_col(lse_ref[...])
        di_sc[...] = _row_to_col(di_ref[...])

    lo, hi = _kv_range(i, bq=bq, bk=bk, nk=nk, q_offset=q_offset,
                       causal=causal, window=window)

    @pl.when((j >= lo) & (j <= hi))
    def _step():
        k = k_ref[...]
        s = jax.lax.dot_general(q_ref[...], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_sc[...])
        rows = q_offset + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _visible(rows, cols, causal, window)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_sc[...]) * scale
        acc_sc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _out():
        dq_ref[...] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, causal, window, bq, bk, nq, n_inner,
                q_offset):
    j, t = pl.program_id(2), pl.program_id(3)
    i = jax.lax.rem(t, nq)

    @pl.when(t == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    lo, hi = _q_range(j, bq=bq, bk=bk, nq=nq, q_offset=q_offset,
                      causal=causal, window=window)

    @pl.when((i >= lo) & (i <= hi))
    def _step():
        q, do = q_ref[...], do_ref[...]
        # transposed: row = kv position, column = q position
        s = jax.lax.dot_general(k_ref[...], q, _NT,
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[...])
        cols = q_offset + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bk, bq), 1)
        rows = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        mask = _visible(cols, rows, causal, window)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        nn = (((1,), (0,)), ((), ()))
        dv_sc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, nn, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...]) * scale
        dk_sc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, nn, preferred_element_type=jnp.float32)

    @pl.when(t == n_inner - 1)
    def _out():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, do, spec: _Spec):
    B, Hq, Sq, D = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    G = Hq // Hk
    bq = _fit_block(spec.bwd_blocks[0], Sq)
    bk = _fit_block(spec.bwd_blocks[1], Sk)
    nq, nk = Sq // bq, Sk // bk
    q_offset = Sk - Sq
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    common = dict(scale=spec.scale, causal=spec.causal, window=spec.window,
                  bq=bq, bk=bk, q_offset=q_offset)
    params = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))

    kv_rng = functools.partial(_kv_range, bq=bq, bk=bk, nk=nk,
                               q_offset=q_offset, causal=spec.causal,
                               window=spec.window)

    def dq_kv_map(b, h, i, j):
        lo, hi = kv_rng(i)
        return b, h // G, jnp.minimum(jnp.maximum(j, lo), hi), 0

    def dq_q_map(b, h, i, j):
        return b, h, i, 0

    def dq_row_map(b, h, i, j):
        return b, h, 0, i

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(B, Hq, nq, nk),
        in_specs=[pl.BlockSpec((None, None, bq, D), dq_q_map),
                  pl.BlockSpec((None, None, bk, D), dq_kv_map),
                  pl.BlockSpec((None, None, bk, D), dq_kv_map),
                  pl.BlockSpec((None, None, bq, D), dq_q_map),
                  pl.BlockSpec((None, None, 1, bq), dq_row_map),
                  pl.BlockSpec((None, None, 1, bq), dq_row_map)],
        out_specs=pl.BlockSpec((None, None, bq, D), dq_q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=params,
        cost_estimate=_cost(spec, q, k, 3, v, do, q),
        interpret=spec.interpret,
        name=f"{KERNEL_PREFIX}_bwd_dq",
    )(q, k, v, do, lse, di)

    q_rng = functools.partial(_q_range, bq=bq, bk=bk, nq=nq,
                              q_offset=q_offset, causal=spec.causal,
                              window=spec.window)

    def dkv_q_index(j, t):
        lo, hi = q_rng(j)
        return jnp.minimum(jnp.maximum(jax.lax.rem(t, nq), lo), hi)

    def dkv_q_map(b, h, j, t):
        return b, h * G + t // nq, dkv_q_index(j, t), 0

    def dkv_row_map(b, h, j, t):
        return b, h * G + t // nq, 0, dkv_q_index(j, t)

    def dkv_kv_map(b, h, j, t):
        return b, h, j, 0

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, n_inner=G * nq, **common),
        grid=(B, Hk, nk, G * nq),
        in_specs=[pl.BlockSpec((None, None, bq, D), dkv_q_map),
                  pl.BlockSpec((None, None, bk, D), dkv_kv_map),
                  pl.BlockSpec((None, None, bk, D), dkv_kv_map),
                  pl.BlockSpec((None, None, bq, D), dkv_q_map),
                  pl.BlockSpec((None, None, 1, bq), dkv_row_map),
                  pl.BlockSpec((None, None, 1, bq), dkv_row_map)],
        out_specs=[pl.BlockSpec((None, None, bk, D), dkv_kv_map),
                   pl.BlockSpec((None, None, bk, D), dkv_kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=params,
        cost_estimate=_cost(spec, q, k, 4, v, do, k, v),
        interpret=spec.interpret,
        name=f"{KERNEL_PREFIX}_bwd_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, spec: _Spec):
    return _forward(q, k, v, spec)[0]


def _attention_fwd(q, k, v, spec: _Spec):
    o, lse = _forward(q, k, v, spec)
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return o, (q, k, v, o, lse)


def _attention_bwd(spec: _Spec, res, do):
    return _backward(*res, do, spec)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 512,
                    bwd_blocks: Optional[Tuple[int, int]] = None,
                    interpret: bool = False):
    """q (B,Hq,Sq,D); k,v (B,Hk,Sk,D) -> (B,Hq,Sq,D), differentiable.

    Sq may be shorter than Sk (the q rows are the suffix of the kv range,
    e.g. chunked prefill); rows are aligned at the end. scale: of the
    scores (None: D ** -0.5). Blocks are cut down to divide the lengths;
    `bwd_blocks` (bq, bk) of both backward kernels, default the forward's.
    """
    D = q.shape[-1]
    spec = _Spec(scale=float(D ** -0.5 if scale is None else scale),
                 causal=bool(causal), window=int(window),
                 blocks=(block_q, block_k),
                 bwd_blocks=tuple(bwd_blocks or (block_q, block_k)),
                 interpret=bool(interpret))
    return _attention(q, k, v, spec)
