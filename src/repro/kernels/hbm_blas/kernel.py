"""Pallas TPU kernels for the memory-bound BLAS ops.

Arrays are 2-D ``[rows, lanes]`` (vectors of length ``rows × lanes``) so
the 8×128 VPU tiling gets contiguous sublanes; every kernel tiles rows
into ``[block_rows, lanes]`` VMEM blocks, sized to scoped VMEM when the
caller gives no ``block_rows`` (:mod:`repro.kernels.vmem`).  These ops
move far more bytes than they compute — on the FPGA side each grid step is
one shard streaming out of its own HBM pseudo-channel, which is exactly
how the app graphs decompose them (one task per block row-range).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..vmem import block_rows_for


def _axpy_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[0, 0] * x_ref[...] + y_ref[...]


def axpy(a: jax.Array, x: jax.Array, y: jax.Array,
         block_rows: Optional[int] = None,
         interpret: bool = False) -> jax.Array:
    """a*x + y.  x, y: [R, C]; a: scalar array."""
    R, C = x.shape
    # x, y and the output, double-buffered.
    block_rows = block_rows_for(R, C * x.dtype.itemsize, 6, block_rows)
    grid = (R // block_rows,)
    return pl.pallas_call(
        _axpy_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(a.reshape(1, 1), x, y)


def _dot_partials_kernel(x_ref, y_ref, o_ref):
    # A [1, 1] vector store: Mosaic cannot store a scalar to VMEM.
    o_ref[...] = jnp.sum(x_ref[...] * y_ref[...], keepdims=True)


def dot_partials(x: jax.Array, y: jax.Array,
                 block_rows: Optional[int] = None,
                 interpret: bool = False) -> jax.Array:
    """Per-block partial sums of x·y: [R, C] → [R // block_rows, 1].

    One partial per grid step — the same per-shard partial the app graph's
    shard tasks emit.  The caller folds them (``fold_partials``) in block
    order, fixing the reduction order on both paths.
    """
    R, C = x.shape
    # x and y, double-buffered.
    block_rows = block_rows_for(R, C * x.dtype.itemsize, 4, block_rows)
    nblk = R // block_rows
    # Each partial is its own [1, 1] array (a leading squeezed block axis):
    # a (1, 1) block of an [nblk, 1] array breaks the (8, 128) tiling.
    out = pl.pallas_call(
        _dot_partials_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk, 1, 1), x.dtype),
        interpret=interpret,
    )(x, y)
    return out.reshape(nblk, 1)


def fold_partials(partials) -> jax.Array:
    """Sequential left fold of per-shard partials, index order.

    Shared by the kernel ops and the app graphs' reduce tasks: one
    canonical reduction order makes decomposed == monolithic bit-tight.
    Accepts a [nblk, 1] array or a list of scalar arrays.
    """
    if hasattr(partials, "shape"):
        parts = [partials[i, 0] for i in range(partials.shape[0])]
    else:
        parts = list(partials)
    acc = parts[0]
    for p in parts[1:]:
        acc = acc + p
    return acc


def _gemv_kernel(a_ref, x_ref, o_ref):
    # Row-wise multiply + lane reduction rather than jnp.dot: the dot
    # lowering is not grid-stable (its accumulation shape depends on the
    # whole pallas_call), and the app graphs need block == shard bit-wise.
    o_ref[...] = jnp.sum(a_ref[...] * x_ref[...], axis=1, keepdims=True)


def gemv(A: jax.Array, x: jax.Array, block_rows: Optional[int] = None,
         interpret: bool = False) -> jax.Array:
    """A @ x with row-block tiling.  A: [M, N]; x: [1, N] → [M, 1]."""
    M, N = A.shape
    # The A block, double-buffered; x and the [block_rows, 1] output are
    # small next to it.
    block_rows = block_rows_for(M, N * A.dtype.itemsize, 2, block_rows)
    grid = (M // block_rows,)
    return pl.pallas_call(
        _gemv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), A.dtype),
        interpret=interpret,
    )(A, x)
