"""jit'd public wrappers around the Pallas kernels.

`interpret=None` (the default) resolves when the wrapper is traced: the
kernels run in interpret mode (the kernel body executes op-by-op, same
math, same blocking) on the CPU backend only, and are compiled on any
other backend. A caller that needs interpret mode elsewhere passes
`interpret=True` itself."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import comm_kernels as _comm
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.ssm_scan import ssm_scan as _ssm

# bytes of one f32 operand tile of the comm kernels: small enough that
# every kernel's double-buffered operands fit the default scoped VMEM
_TILE_BYTES = 1 << 20


def _interpret(interpret):
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _pad_rows(x, block: int, sublane: int):
    """View (…, N) as (lead, rows, block): the leading axes flattened into
    one (blocks never span them: replica rows stay apart), the trailing
    axis padded to a whole number of (tile, block) tiles and split into
    rows of `block`. Returns the view, the row tile, and what
    `_unpad_rows` needs."""
    lead, n = x.shape[:-1], x.shape[-1]
    n_lead = 1
    for d in lead:
        n_lead *= d
    n_rows = -(-n // block)
    tile = max(sublane, _TILE_BYTES // (4 * block) // sublane * sublane)
    tile = min(tile, -(-n_rows // sublane) * sublane)
    npad = -(-n_rows // tile) * tile * block
    xr = x.reshape((n_lead, n))
    if npad != n:
        xr = jnp.pad(xr, ((0, 0), (0, npad - n)))
    return xr.reshape((n_lead, npad // block, block)), tile, (lead, n, npad)


def _unpad_rows(view, meta):
    lead, n, npad = meta
    return view.reshape((-1, npad))[:, :n].reshape(lead + (n,))


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ssm_scan(x, dt, A, Bm, Cm, h0, *, block_d: int = 512,
             interpret: bool | None = None):
    return _ssm(x, dt, A, Bm, Cm, h0, block_d=block_d,
                interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def rglru_scan(a, gx, h0, *, block_w: int = 512,
               interpret: bool | None = None):
    return _rglru(a, gx, h0, block_w=block_w,
                  interpret=_interpret(interpret))


# -- fused flat-buffer exchange kernels (core/flatbuf.py arenas) ---------------

@functools.partial(jax.jit, static_argnames=("staleness", "global_world",
                                             "extra_staleness", "block",
                                             "interpret"))
def eq1_merge(local, stale, *, staleness: int, global_world: int,
              extra_staleness: int = 0, block: int = 1024,
              interpret: bool | None = None):
    """Paper Eq. (1) merge fused over an arena of any shape (trailing axis
    is the packed axis). Output in local's dtype. `extra_staleness` is the
    overlap executor's one-cycle buffer age, added to S (0 = the
    pre-overlap kernel, bit-exact)."""
    sub = _comm.sublane_rows(local.dtype, stale.dtype)
    lr, tile, meta = _pad_rows(local, block, sub)
    sr, _, _ = _pad_rows(stale, block, sub)
    out = _comm.eq1_merge(lr, sr, staleness=staleness,
                          global_world=global_world,
                          extra_staleness=extra_staleness, rows_tile=tile,
                          interpret=_interpret(interpret))
    return _unpad_rows(out, meta)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bf16_pack(x, *, block: int = 1024, interpret: bool | None = None):
    """Arena -> bf16 wire buffer (same shape)."""
    xr, tile, meta = _pad_rows(x, block, _comm.sublane_rows(x.dtype,
                                                            jnp.bfloat16))
    return _unpad_rows(_comm.bf16_pack(xr, rows_tile=tile,
                                       interpret=_interpret(interpret)),
                       meta)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block",
                                             "interpret"))
def bf16_unpack(x, *, out_dtype=jnp.float32, block: int = 1024,
                interpret: bool | None = None):
    """bf16 wire buffer -> arena in `out_dtype` (same shape)."""
    xr, tile, meta = _pad_rows(x, block, _comm.sublane_rows(x.dtype,
                                                            out_dtype))
    return _unpad_rows(_comm.bf16_unpack(xr, out_dtype=out_dtype,
                                         rows_tile=tile,
                                         interpret=_interpret(interpret)),
                       meta)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_int8(x, bits=None, *, block: int = 256,
                  interpret: bool | None = None):
    """Block-scaled int8 quantization over the trailing axis. `bits`
    (uint32, same shape as x) enables stochastic rounding; None =
    round-to-nearest. Returns (values int8 like x,
    scales f32 (*lead, ceil(N/block)))."""
    sub = _comm.sublane_rows(jnp.int8)
    xr, tile, meta = _pad_rows(x, block, sub)
    if bits is not None:
        bits, _, _ = _pad_rows(bits, block, sub)
    values, scales = _comm.quantize_int8(xr, bits, rows_tile=tile,
                                         interpret=_interpret(interpret))
    lead, n, _ = meta
    n_blocks = -(-n // block)
    return (_unpad_rows(values, meta),
            scales[:, :n_blocks, 0].reshape(lead + (n_blocks,)))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequantize_int8(values, scales, *, block: int = 256,
                    interpret: bool | None = None):
    """Inverse of `quantize_int8` (f32 output, values' shape)."""
    vr, tile, meta = _pad_rows(values, block, _comm.sublane_rows(jnp.int8))
    sr = scales.reshape((vr.shape[0], -1))
    sr = jnp.pad(sr, ((0, 0), (0, vr.shape[1] - sr.shape[1])))[..., None]
    out = _comm.dequantize_int8(vr, sr, rows_tile=tile,
                                interpret=_interpret(interpret))
    return _unpad_rows(out, meta)
