"""13-point Dilate stencil Pallas TPU kernel (paper benchmark, §5.2).

TPU adaptation of the paper's line-buffered FPGA dataflow PE: the FPGA
version streams rows through BRAM line buffers; on TPU we tile rows into
VMEM blocks of [block_rows, W] (W = full row so the 8×128 VPU lanes stream
contiguous sublanes; block_rows is sized to scoped VMEM), with a 2-row
halo realized by passing the same input under three BlockSpecs
(prev/cur/next row-block) — Pallas blocks cannot overlap, so the halo is
explicit.  Column shifts happen in-register.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..vmem import block_rows_for
from .ref import OFFSETS

# prev/cur/next input blocks and the output block, each double-buffered.
RESIDENT_BLOCKS = 8


def _dilate_kernel(prev_ref, cur_ref, next_ref, o_ref, *, block_rows: int):
    neg = jnp.finfo(o_ref.dtype).min
    pi = pl.program_id(0)
    np_ = pl.num_programs(0)
    top = jnp.where(pi > 0, 0.0, 1.0)       # 1 → top halo invalid
    bot = jnp.where(pi < np_ - 1, 0.0, 1.0)

    halo_top = prev_ref[-2:, :]             # last 2 rows of previous block
    halo_bot = next_ref[:2, :]              # first 2 rows of next block
    halo_top = jnp.where(top > 0, neg, halo_top)
    halo_bot = jnp.where(bot > 0, neg, halo_bot)
    ext = jnp.concatenate([halo_top, cur_ref[...], halo_bot], axis=0)
    W = ext.shape[1]

    def shift_cols(x, dj):
        if dj == 0:
            return x
        pad = jnp.full((x.shape[0], abs(dj)), neg, x.dtype)
        if dj > 0:   # neighbour at +dj → shift left
            return jnp.concatenate([x[:, dj:], pad], axis=1)
        return jnp.concatenate([pad, x[:, :dj]], axis=1)

    out = jnp.full((block_rows, W), neg, o_ref.dtype)
    for di, dj in OFFSETS:
        rows = ext[2 + di:2 + di + block_rows, :]
        out = jnp.maximum(out, shift_cols(rows, dj))
    o_ref[...] = out


def dilate(img: jax.Array, block_rows: Optional[int] = None,
           interpret: bool = False) -> jax.Array:
    """One dilate iteration.  img: [H, W].

    ``block_rows`` None sizes the row blocks to scoped VMEM; a given size
    must divide H and fit (see :func:`repro.kernels.vmem.block_rows_for`).
    """
    H, W = img.shape
    block_rows = block_rows_for(H, W * img.dtype.itemsize, RESIDENT_BLOCKS,
                                block_rows)
    grid = (H // block_rows,)
    nblk = H // block_rows

    def clamp(i, lo, hi):
        return jnp.clip(i, lo, hi)

    return pl.pallas_call(
        functools.partial(_dilate_kernel, block_rows=block_rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, W),
                         lambda i: (clamp(i - 1, 0, nblk - 1), 0)),
            pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, W),
                         lambda i: (clamp(i + 1, 0, nblk - 1), 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), img.dtype),
        interpret=interpret,
    )(img, img, img)
