"""Pallas kernels for the fused flat-buffer global exchange (core/flatbuf.py).

The exchange hot path operates on one contiguous arena per dtype instead of
per parameter leaf; these kernels fuse the elementwise exchange math over
that arena:

  * `eq1_merge`       — paper Eq. (1): (2S*x_local + P*x_stale) / (2S + P),
                        f32 accumulation, output in the arena dtype;
  * `bf16_pack` /
    `bf16_unpack`     — the paper's 16-bit transfer packaging over the
                        arena (one cast kernel instead of one per leaf);
  * `quantize_int8` /
    `dequantize_int8` — beyond-paper int8 tier: per-block absmax scales
                        (QSGD-style), optional stochastic rounding from
                        caller-supplied uint32 bits.

All kernels view the arena as (lead, rows, block): one slab of rows of
`block` contiguous elements per leading (replica) row. The
`repro.kernels.ops` wrappers pad the trailing axis to a whole number of
(rows_tile, block) tiles and split it, and the kernels run a
(lead, rows / rows_tile) grid over such tiles. A tile is what the TPU
compiler accepts: its second-to-last dimension is a multiple of the
sublane tile (8 rows for 32-bit data, 16 for 16-bit, 32 for int8, see
`sublane_rows`) and its last dimension spans the whole row. (Splitting the
trailing axis keeps the view cheap: flattening a (R, N) arena into
(R * N / block, block) rows is a relayout whose TPU compile time grows
with N.) Blocks never span the leading batch (replica) axis, so int8
scales are always per-replica; each tile carries a (rows_tile, 1) column
of scales, one per row. On the CPU backend the kernels run with
interpret=True; on the TPU they are compiled.

Random bits for stochastic rounding are passed in as a uint32 arena
(generated with jax.random.bits) rather than drawn via pltpu.prng_* so the
same kernel body runs under plain interpret mode; a TPU deployment can
swap in the on-core PRNG without changing the contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import INT8_SCALE_FLOOR


def sublane_rows(*dtypes) -> int:
    """Sublane rows of one native TPU tile by element width: (8, 128) for
    32-bit, (16, 128) for 16-bit and (32, 128) for 8-bit data. A rows_tile
    that is a multiple of the largest among a kernel's operands is legal
    for all of them."""
    return max({1: 32, 2: 16}.get(jnp.dtype(d).itemsize, 8) for d in dtypes)


def _tile_spec(rows_tile: int, cols: int):
    """One (rows_tile, cols) tile of a (lead, rows, cols) array per grid
    step; the lead axis is squeezed out of the kernel's view."""
    return pl.BlockSpec((None, rows_tile, cols), lambda r, i: (r, i, 0))


def _grid(shape, rows_tile: int):
    lead, rows, _ = shape
    assert rows % rows_tile == 0, (shape, rows_tile)
    return (lead, rows // rows_tile)


# -- Eq. (1) merge -------------------------------------------------------------

def _eq1_kernel(local_ref, stale_ref, out_ref, *, s2, p):
    x = local_ref[...].astype(jnp.float32)
    y = stale_ref[...].astype(jnp.float32)
    out_ref[...] = ((s2 * x + p * y) / (s2 + p)).astype(out_ref.dtype)


def eq1_merge(local, stale, *, staleness: int, global_world: int,
              extra_staleness: int = 0, rows_tile: int = 8,
              interpret: bool = False):
    """local, stale: (lead, rows, block) arena views, same shape/dtype,
    rows a multiple of `rows_tile`. Returns the Eq. (1) merge in local's
    dtype, computed exactly as `ref.eq1_merge_ref` computes it.
    `extra_staleness` adds the overlap executor's one-cycle buffer age to S
    (0 = the pre-overlap kernel, bit-exact)."""
    block = local.shape[-1]
    kernel = functools.partial(_eq1_kernel,
                               s2=2.0 * (staleness + extra_staleness),
                               p=float(global_world))
    spec = _tile_spec(rows_tile, block)
    return pl.pallas_call(
        kernel,
        grid=_grid(local.shape, rows_tile),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(local.shape, local.dtype),
        interpret=interpret,
    )(local, stale)


# -- bf16 wire packaging -------------------------------------------------------

def _cast_kernel(x_ref, out_ref):
    out_ref[...] = x_ref[...].astype(out_ref.dtype)


def _cast(x, out_dtype, rows_tile: int, interpret: bool):
    spec = _tile_spec(rows_tile, x.shape[-1])
    return pl.pallas_call(
        _cast_kernel,
        grid=_grid(x.shape, rows_tile),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        interpret=interpret,
    )(x)


def bf16_pack(x, *, rows_tile: int = 16, interpret: bool = False):
    """(lead, rows, block) floating arena view -> bf16 wire buffer."""
    return _cast(x, jnp.bfloat16, rows_tile, interpret)


def bf16_unpack(x, *, out_dtype=jnp.float32, rows_tile: int = 16,
                interpret: bool = False):
    """bf16 wire buffer -> (lead, rows, block) arena view in
    `out_dtype`."""
    return _cast(x, out_dtype, rows_tile, interpret)


# -- int8 block-scaled quantization --------------------------------------------

def _quantize_kernel(x_ref, bits_ref, v_ref, s_ref, *, stochastic):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                        INT8_SCALE_FLOOR) / 127.0
    v = x / scale
    if stochastic:
        # floor(v + u), u ~ U[0,1) from the top 24 bits: E[q] = v exactly.
        # The 24-bit value goes through int32 (exact), because the TPU
        # compiler has no uint32 -> float32 cast
        top = (bits_ref[...] >> 8).astype(jnp.int32)
        u = top.astype(jnp.float32) * (1.0 / (1 << 24))
        q = jnp.floor(v + u)
    else:
        q = jnp.round(v)
    v_ref[...] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    s_ref[...] = scale


def quantize_int8(x, bits, *, rows_tile: int = 32, interpret: bool = False):
    """x: (lead, blocks, block) arena view; bits: uint32 of the same shape
    or None (deterministic round-to-nearest). Returns (int8 values like x,
    f32 scales (lead, blocks, 1)) with scale = absmax(block)/127."""
    lead, rows, block = x.shape
    stochastic = bits is not None
    if bits is None:
        bits = jnp.zeros(x.shape, jnp.uint32)
    kernel = functools.partial(_quantize_kernel, stochastic=stochastic)
    spec = _tile_spec(rows_tile, block)
    return pl.pallas_call(
        kernel,
        grid=_grid(x.shape, rows_tile),
        in_specs=[spec, spec],
        out_specs=[spec, _tile_spec(rows_tile, 1)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.int8),
                   jax.ShapeDtypeStruct((lead, rows, 1), jnp.float32)],
        interpret=interpret,
    )(x, bits)


def _dequantize_kernel(v_ref, s_ref, out_ref):
    out_ref[...] = v_ref[...].astype(jnp.float32) * s_ref[...]


def dequantize_int8(values, scales, *, rows_tile: int = 32,
                    interpret: bool = False):
    """Inverse of `quantize_int8`: (lead, blocks, block) int8 +
    (lead, blocks, 1) f32 scales -> f32 (lead, blocks, block)."""
    spec = _tile_spec(rows_tile, values.shape[-1])
    return pl.pallas_call(
        _dequantize_kernel,
        grid=_grid(values.shape, rows_tile),
        in_specs=[spec, _tile_spec(rows_tile, 1)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(values.shape, jnp.float32),
        interpret=interpret,
    )(values, scales)
