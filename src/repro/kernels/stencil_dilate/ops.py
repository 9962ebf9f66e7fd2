"""Jit'd wrapper: multi-iteration Dilate (paper sweeps 64–512 iterations)."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from .kernel import dilate
from .ref import dilate_iters_ref, dilate_ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("iters", "block_rows",
                                             "interpret"))
def dilate_op(img, iters: int = 1, block_rows: Optional[int] = None,
              interpret: Optional[bool] = None):
    interp = _on_cpu() if interpret is None else interpret

    def body(i, x):
        return dilate(x, block_rows=block_rows, interpret=interp)

    return jax.lax.fori_loop(0, iters, body, img)


__all__ = ["dilate_op", "dilate_ref", "dilate_iters_ref"]
