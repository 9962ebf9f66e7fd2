"""KNN Pallas TPU kernel — fused pairwise distance + running top-k.

This is the paper's KNN accelerator (CHIP-KNN [44]) adapted to TPU: the FPGA
design streams the dataset from HBM through distance PEs (blue modules) into
sorting PEs (yellow).  On TPU the dataset streams through VMEM in
[BLOCK_N, D] tiles; the distance phase is an MXU matmul (−2·q·xᵀ plus norms)
and the "sorting" phase is a K-step running selection held in VMEM scratch
across dataset tiles — the fusion means distances are never written to HBM
(the paper's insight that phase-2 traffic is tiny: only K survivors).

Grid = (q_blocks, n_blocks); n innermost (sequential) so scratch carries the
running top-k.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_N = 512
BIG = 3.4e38  # plain float — a jnp scalar would be captured as a const


def _knn_kernel(q_ref, x_ref, od_ref, oi_ref, best_d, best_i, *,
                k: int, block_n: int, n_total: int):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d, BIG)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...].astype(jnp.float32)            # [BQ, D]
    x = x_ref[...].astype(jnp.float32)            # [BN, D]
    # Squared L2 via the MXU: |q|² − 2 q·xᵀ + |x|².  HIGHEST keeps the
    # products in float32: one bfloat16 pass misorders near neighbours.
    d2 = (jnp.sum(q * q, -1, keepdims=True)
          - 2.0 * jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                                      precision=jax.lax.Precision.HIGHEST,
                                      preferred_element_type=jnp.float32)
          + jnp.sum(x * x, -1)[None, :])          # [BQ, BN]
    gidx = ni * block_n + jax.lax.broadcasted_iota(
        jnp.int32, d2.shape, 1)
    d2 = jnp.where(gidx < n_total, d2, BIG)       # mask tail padding

    # Merge block distances into the running top-k: K extract-min passes.
    cand_d = jnp.concatenate([best_d[...], d2], axis=1)     # [BQ, K+BN]
    cand_i = jnp.concatenate([best_i[...], gidx], axis=1)
    new_d = jnp.zeros((q.shape[0], k), jnp.float32)
    new_i = jnp.zeros((q.shape[0], k), jnp.int32)
    # Column j of the result is written with a lane-iota select: Mosaic
    # cannot lower the scatter that ``.at[:, j].set`` becomes.
    out_lane = jax.lax.broadcasted_iota(jnp.int32, new_d.shape, 1)
    cand_lane = jax.lax.broadcasted_iota(jnp.int32, cand_d.shape, 1)
    for j in range(k):
        m = jnp.min(cand_d, axis=1)                          # [BQ]
        am = jnp.argmin(cand_d, axis=1)                      # [BQ]
        sel = cand_lane == am[:, None]
        mi = jnp.sum(jnp.where(sel, cand_i, 0), axis=1)
        new_d = jnp.where(out_lane == j, m[:, None], new_d)
        new_i = jnp.where(out_lane == j, mi[:, None], new_i)
        cand_d = jnp.where(sel, BIG, cand_d)
    best_d[...] = new_d
    best_i[...] = new_i

    @pl.when(ni == pl.num_programs(1) - 1)
    def _finish():
        od_ref[...] = best_d[...]
        oi_ref[...] = best_i[...]


def knn(queries: jax.Array, data: jax.Array, k: int = 10,
        block_q: int = DEFAULT_BLOCK_Q, block_n: int = DEFAULT_BLOCK_N,
        n_valid: Optional[int] = None, interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array]:
    """queries: [Q, D]; data: [N, D] → (dists [Q,k], idx [Q,k]) ascending.

    ``n_valid``: only the first ``n_valid`` rows of ``data`` are points; the
    rest is padding that the kernel's tail mask skips, and N must then be a
    multiple of ``block_n``.  None pads ``data`` here when N is not.
    """
    Q, D = queries.shape
    N, _ = data.shape
    block_q = min(block_q, Q)
    block_n = min(block_n, N)
    pad_q = (-Q) % block_q
    pad_n = (-N) % block_n
    if n_valid is None:
        n_valid = N
    elif pad_n:
        raise ValueError(f"data of {N} rows with n_valid={n_valid} is not "
                         f"padded to a multiple of block_n={block_n}")
    if pad_q:
        queries = jnp.pad(queries, ((0, pad_q), (0, 0)))
    if pad_n:
        data = jnp.pad(data, ((0, pad_n), (0, 0)))
    Qp, Np = Q + pad_q, N + pad_n
    grid = (Qp // block_q, Np // block_n)
    od, oi = pl.pallas_call(
        functools.partial(_knn_kernel, k=k, block_n=block_n,
                          n_total=n_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_n, D), lambda qi, ni: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, data)
    return od[:Q], oi[:Q]
