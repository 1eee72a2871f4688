"""Grouped matrix product of the dropless expert layer: rows sorted by
expert, each contiguous group multiplied by its own expert's matrix.

    gmm(lhs (M, K), rhs (G, K, N), group_sizes (G,)) -> (M, N)

Rows [o_g, o_g + group_sizes[g]) of `lhs` meet `rhs[g]`, where o_g is the
sum of the sizes before g; the sizes add up to M. It is
`jax.lax.ragged_dot` (the TPU compiler's own grouped product) with a
backward pass that is grouped too: the data gradient is the same product
against the transposed matrices, and the weight gradient is the product
ragged along the contracted rows (`grouped_weight_grad`, one (K, N) block
per group), never a dense product over all groups.

Under `vmap` (the replicas of a DASO step) the batch is folded into the
groups: B batches of M rows and G groups are one product of B*M rows and
B*G groups. The chip's compiler refuses a ragged product with batch
dimensions, and the folded one is the same arithmetic.

Operands narrower than 16 bits (float8) are widened to bfloat16 before the
product and the result is rounded back: the v5e's matrix unit has no
float8 path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

# the weight gradient: rows contracted, ragged over groups -> (G, K, N)
_WEIGHT_GRAD_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _widen(*xs):
    return [x.astype(jnp.bfloat16) if x.dtype.itemsize < 2 else x
            for x in xs]


def _fold(axis_size, in_batched, args):
    """Every argument with a leading batch axis of `axis_size`."""
    return [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip(args, in_batched)]


@custom_vmap
def _ragged(lhs, rhs, group_sizes):
    dtype = lhs.dtype
    lhs, rhs = _widen(lhs, rhs)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype).astype(dtype)


@_ragged.def_vmap
def _ragged_vmap(axis_size, in_batched, lhs, rhs, group_sizes):
    lhs, rhs, group_sizes = _fold(axis_size, in_batched,
                                  (lhs, rhs, group_sizes))
    b, m = lhs.shape[:2]
    out = _ragged(lhs.reshape((b * m,) + lhs.shape[2:]),
                  rhs.reshape((-1,) + rhs.shape[2:]),
                  group_sizes.reshape(-1))
    return out.reshape((b, m) + out.shape[1:]), True


@custom_vmap
def grouped_weight_grad(lhs, rhs, group_sizes):
    """lhs (M, K), rhs (M, N) -> (G, K, N): for each group, its rows of
    `lhs` transposed times its rows of `rhs`."""
    dtype = lhs.dtype
    lhs, rhs = _widen(lhs, rhs)
    return jax.lax.ragged_dot_general(
        lhs, rhs, group_sizes, _WEIGHT_GRAD_DIMS,
        preferred_element_type=lhs.dtype).astype(dtype)


@grouped_weight_grad.def_vmap
def _weight_grad_vmap(axis_size, in_batched, lhs, rhs, group_sizes):
    lhs, rhs, group_sizes = _fold(axis_size, in_batched,
                                  (lhs, rhs, group_sizes))
    b, g = group_sizes.shape
    out = grouped_weight_grad(lhs.reshape((-1,) + lhs.shape[2:]),
                              rhs.reshape((-1,) + rhs.shape[2:]),
                              group_sizes.reshape(-1))
    return out.reshape((b, g) + out.shape[1:]), True


@jax.custom_vjp
def gmm(lhs, rhs, group_sizes):
    """(M, K) x (G, K, N) over row groups -> (M, N), in `lhs`'s dtype."""
    return _ragged(lhs, rhs, group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes):
    return _ragged(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _ragged(g, rhs.swapaxes(1, 2), group_sizes).astype(lhs.dtype)
    d_rhs = grouped_weight_grad(lhs, g, group_sizes).astype(rhs.dtype)
    return d_lhs, d_rhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)
