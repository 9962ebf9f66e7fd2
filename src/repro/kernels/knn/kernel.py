"""KNN Pallas TPU kernel — fused pairwise distance + running top-k.

This is the paper's KNN accelerator (CHIP-KNN [44]) adapted to TPU: the FPGA
design streams the dataset from HBM through distance PEs (blue modules) into
sorting PEs (yellow).  On TPU the dataset streams through VMEM in
[BLOCK_N, D] tiles; the distance phase is an MXU matmul (−2·x·qᵀ plus norms)
and the "sorting" phase inserts a block's survivors into a sorted best list
held in VMEM scratch across dataset tiles — the fusion means distances are
never written to HBM (the paper's insight that phase-2 traffic is tiny:
only K survivors).

A block's distances lie points-by-queries, ``[BLOCK_N, BLOCK_Q]``, so every
reduction below runs down sublanes, query by query in the lanes.  The
selection is gated by what the block can change: only a point strictly
below its query's running k-th distance can enter that query's top-k, so
the block runs ``min(k, most such points any of its queries has)``
extract-min passes over the masked tile, each inserting the extracted value
into the sorted list (a count of entries ``<=`` it, a roll and two
selects).  A query with fewer candidates extracts ``BIG``, which inserts
nothing.  The kernel also returns those passes, summed per query block: over
``k`` times the blocks, the share of the ungated selection it ran.

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


def _knn_kernel(q_ref, x_ref, od_ref, oi_ref, passes_ref, best_d, best_i,
                work, *, k: int, block_n: int, n_total: int):
    qi, ni = pl.program_id(0), pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d, BIG)
        best_i[...] = jnp.full_like(best_i, -1)
        passes_ref[qi] = 0

    q = q_ref[...].astype(jnp.float32)            # [BQ, D]
    x = x_ref[...].astype(jnp.float32)            # [BN, D]
    nt = functools.partial(jax.lax.dot_general,
                           dimension_numbers=(((1,), (1,)), ((), ())),
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    # Squared L2 via the MXU: |q|² − 2 q·x + |x|², transposed so points lie
    # on sublanes and queries on lanes.  HIGHEST keeps the products in
    # float32: one bfloat16 pass misorders near neighbours.
    qn = nt(jnp.ones((8, q.shape[1]), jnp.float32), q * q)[:1]   # [1, BQ]
    d2 = (qn - 2.0 * nt(x, q)
          + jnp.sum(x * x, -1, keepdims=True))    # [BN, BQ]
    row = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    d2 = jnp.where(ni * block_n + row < n_total, d2, BIG)   # tail padding

    # The gate: only points below a query's running k-th can enter its
    # top-k; the block needs as many passes as its busiest query.
    kth = best_d[k - 1:k, :]                      # [1, BQ]
    cand = d2 < kth
    work[...] = jnp.where(cand, d2, BIG)
    n_cand = jnp.sum(cand.astype(jnp.int32), axis=0, keepdims=True)
    n_pass = jnp.minimum(jnp.max(n_cand), k)
    slot = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 0)

    def insert(best, pos, v):
        return jnp.where(slot < pos, best,
                         jnp.where(slot == pos, v, pltpu.roll(best, 1, 0)))

    def extract(_, carry):
        bd, bi = carry
        w = work[...]
        m = jnp.min(w, axis=0, keepdims=True)     # [1, BQ]
        r = jnp.min(jnp.where(w == m, row, block_n), axis=0, keepdims=True)
        work[...] = jnp.where(row == r, BIG, w)
        # Lands after its equals; BIG lands past the list and is dropped.
        pos = jnp.sum((bd <= m).astype(jnp.int32), axis=0, keepdims=True)
        return insert(bd, pos, m), insert(bi, pos, ni * block_n + r)

    bd, bi = jax.lax.fori_loop(0, n_pass, extract, (best_d[...], best_i[...]))
    best_d[...] = bd
    best_i[...] = bi
    passes_ref[qi] += n_pass

    @pl.when(ni == pl.num_programs(1) - 1)
    def _finish():
        od_ref[...] = best_d[...]
        oi_ref[...] = best_i[...]


def knn(queries: jax.Array, data: jax.Array, k: int = 10,
        block_q: int = DEFAULT_BLOCK_Q, block_n: int = DEFAULT_BLOCK_N,
        n_valid: Optional[int] = None, interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """queries: [Q, D]; data: [N, D] → (dists [Q,k], idx [Q,k]) ascending,
    and ``passes`` [Q blocks]: the selection passes each query block ran.

    ``n_valid``: only the first ``n_valid`` rows of ``data`` are points; the
    rest is padding that the kernel's tail mask skips, and N must then be a
    multiple of ``block_n``.  None pads ``data`` here when N is not.  A
    query with fewer than ``k`` points gets ``BIG`` and index -1 past them.
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
    slots = -(-k // 8) * 8                        # the list, whole sublanes
    od, oi, passes = pl.pallas_call(
        functools.partial(_knn_kernel, k=k, block_n=block_n,
                          n_total=n_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, D), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_n, D), lambda qi, ni: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((slots, block_q), lambda qi, ni: (0, qi)),
            pl.BlockSpec((slots, block_q), lambda qi, ni: (0, qi)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((slots, Qp), jnp.float32),
            jax.ShapeDtypeStruct((slots, Qp), jnp.int32),
            jax.ShapeDtypeStruct((grid[0],), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((slots, block_q), jnp.float32),
            pltpu.VMEM((slots, block_q), jnp.int32),
            pltpu.VMEM((block_n, block_q), jnp.float32),
        ],
        interpret=interpret,
    )(queries, data)
    return od[:k, :Q].T, oi[:k, :Q].T, passes
