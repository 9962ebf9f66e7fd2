"""Jit'd wrapper for the fused KNN kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import knn
from .ref import knn_ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_n",
                                             "n_valid", "interpret"))
def knn_op(queries, data, k: int = 10, block_q: int = 128,
           block_n: int = 512, n_valid: Optional[int] = None,
           interpret: Optional[bool] = None):
    interp = _on_cpu() if interpret is None else interpret
    d, i, _ = knn(queries, data, k=k, block_q=block_q, block_n=block_n,
                  n_valid=n_valid, interpret=interp)
    return d, i


__all__ = ["knn_op", "knn_ref"]
