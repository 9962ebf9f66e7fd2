"""Oracle for the 13-point 2-D Dilate stencil (Rodinia leukocyte tracking).

Morphological dilation with a diamond structuring element of radius 2
(|di|+|dj| <= 2 → 13 points); out-of-bounds neighbours are ignored.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

OFFSETS = tuple((di, dj)
                for di in range(-2, 3) for dj in range(-2, 3)
                if abs(di) + abs(dj) <= 2)
assert len(OFFSETS) == 13


def dilate_ref(img: jnp.ndarray) -> jnp.ndarray:
    """img: [H, W] → [H, W] max over the 13-point diamond."""
    neg = jnp.finfo(img.dtype).min
    padded = jnp.pad(img, 2, constant_values=neg)
    H, W = img.shape
    out = jnp.full_like(img, neg)
    for di, dj in OFFSETS:
        out = jnp.maximum(out, padded[2 + di:2 + di + H, 2 + dj:2 + dj + W])
    return out


def dilate_iters_ref(img: jnp.ndarray, iters: int) -> jnp.ndarray:
    return jax.lax.fori_loop(0, iters, lambda _, x: dilate_ref(x), img)
