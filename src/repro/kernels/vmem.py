"""Row-block sizing against the TPU's scoped VMEM.

Pallas double-buffers every blocked operand: while the kernel works on
block ``i`` the next one streams in.  Kernels whose blocks span a whole row
must pick ``block_rows`` so that these buffers fit the scoped VMEM limit
(16 MiB by default on TPU v5e) with room left for the kernel body's own
temporaries; the compiler refuses a kernel that overflows it.  Half the
limit goes to the buffers: the 4096-wide f32 Dilate kernel (four blocks,
double-buffered) compiles at 64 rows and is refused at 128.
"""
from __future__ import annotations

from typing import Optional

SCOPED_VMEM_BYTES = 16 << 20
BLOCK_BUDGET_BYTES = SCOPED_VMEM_BYTES // 2


def max_block_rows(row_bytes: int, buffers: int) -> int:
    """Most rows per block when ``buffers`` row blocks share the budget."""
    return BLOCK_BUDGET_BYTES // (buffers * row_bytes)


def block_rows_for(rows: int, row_bytes: int, buffers: int,
                   block_rows: Optional[int] = None) -> int:
    """Rows per block for an array of ``rows`` rows of ``row_bytes`` each.

    ``buffers`` counts the row blocks resident at once (every blocked
    operand and output, twice for double buffering).  ``None`` picks the
    largest power of two that divides ``rows`` and fits, or ``rows`` itself
    when the whole array fits; a requested size that cannot fit raises.
    """
    limit = max_block_rows(row_bytes, buffers)
    if block_rows is None:
        if rows <= limit:
            return rows
        br = 1 << (limit.bit_length() - 1) if limit >= 1 else 0
        while br >= 8 and rows % br:
            br //= 2
        if br < 8:
            raise ValueError(
                f"no row block of {rows} rows x {row_bytes} B fits "
                f"{BLOCK_BUDGET_BYTES} B of VMEM in {buffers} buffers")
        return br
    br = min(block_rows, rows)
    if br > limit:
        raise ValueError(
            f"block_rows={br} at {row_bytes} B per row needs "
            f"{buffers * br * row_bytes} B of VMEM for {buffers} buffers; "
            f"at most {limit} rows fit {BLOCK_BUDGET_BYTES} B")
    if rows % br:
        raise ValueError(f"block_rows={br} does not divide {rows} rows")
    return br
