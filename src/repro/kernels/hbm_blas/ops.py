"""Jit'd wrappers for the memory-bound BLAS kernels."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import axpy, dot_partials, fold_partials, gemv
from .ref import axpy_ref, axpydot_ref, dot_ref, gemv_ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def axpy_op(a, x, y, block_rows: Optional[int] = None,
            interpret: Optional[bool] = None):
    interp = _on_cpu() if interpret is None else interpret
    return axpy(jnp.asarray(a, x.dtype), x, y, block_rows, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dot_partials_op(x, y, block_rows: Optional[int] = None,
                    interpret: Optional[bool] = None):
    interp = _on_cpu() if interpret is None else interpret
    return dot_partials(x, y, block_rows, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dot_op(x, y, block_rows: Optional[int] = None,
           interpret: Optional[bool] = None):
    """x·y via per-block partials folded in block order (bit-fixed)."""
    return fold_partials(dot_partials_op(x, y, block_rows=block_rows,
                                         interpret=interpret))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gemv_op(A, x, block_rows: Optional[int] = None,
            interpret: Optional[bool] = None):
    interp = _on_cpu() if interpret is None else interpret
    return gemv(A, x, block_rows, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def axpydot_op(a, x, y, w, block_rows: Optional[int] = None,
               interpret: Optional[bool] = None):
    """(a*x + y)·w — the FpgaHbmForDaCe fused two-stage workload."""
    z = axpy_op(a, x, y, block_rows=block_rows, interpret=interpret)
    return dot_op(z, w, block_rows=block_rows, interpret=interpret)


__all__ = ["axpy_op", "axpydot_op", "axpy_ref", "axpydot_ref", "dot_op",
           "dot_partials_op", "dot_ref", "fold_partials", "gemv_op",
           "gemv_ref"]
