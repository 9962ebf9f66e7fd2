"""Dilate (paper §5.2): the design, what one ``execute()`` must do, and the
plain reference its outputs are held to.

The reference imports nothing of the program: it regenerates the images
from the seed with a copy of the app's generator and dilates them with
plain ``jnp``, one image at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The 13-point diamond: |di| + |dj| <= 2.
OFFSETS = tuple((di, dj) for di in range(-2, 3) for dj in range(-2, 3)
                if abs(di) + abs(dj) <= 2)
# Maximum operations per output point: 13 values need 12.
OPS_PER_POINT = len(OFFSETS) - 1


def build_graph(config):
    from repro.apps import stencil
    return stencil.build_graph(config["fpgas"], iters=config["iters"])


def bind_spec(config, mix, seed, interpret=None):
    h, w = config["grid"]
    return {"h": h, "w": w, "streams": mix["images"], "seed": seed,
            "stage_iters": config["iters"] // config["fpgas"],
            "interpret": interpret}


def work(config, mix):
    """The least one ``execute()`` must do: every iteration of every image
    reads the grid once and writes it once, 12 maxima per point."""
    h, w = config["grid"]
    n = mix["images"] * config["iters"]
    itemsize = jnp.dtype(config["dtype"]).itemsize
    return {"bytes": n * 2 * h * w * itemsize,
            "ops": n * OPS_PER_POINT * h * w}


def images(config, mix, seed):
    """The images the app's binding streams: a copy of its generator."""
    h, w = config["grid"]
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, t), (h, w),
                              jnp.float32) for t in range(mix["images"])]


def _dilate(x):
    neg = jnp.finfo(x.dtype).min
    padded = jnp.pad(x, 2, constant_values=neg)
    h, w = x.shape
    out = x
    for di, dj in OFFSETS:
        out = jnp.maximum(out, padded[2 + di:2 + di + h, 2 + dj:2 + dj + w])
    return out


def _dilate_iters(img, iters, dtype):
    x = jax.lax.fori_loop(0, iters, lambda _, x: _dilate(x), img.astype(dtype))
    return x.astype(jnp.float32)


_dilate_iters_jit = jax.jit(_dilate_iters, static_argnums=(1, 2))


def reference(config, mix, seed, dtype=jnp.float32):
    """Every image dilated ``iters`` times in ``dtype``, stacked in f32."""
    return jnp.stack([_dilate_iters_jit(img, config["iters"], dtype)
                      for img in images(config, mix, seed)])


@jax.jit
def _max_abs_err(got, want):
    return jnp.max(jnp.abs(got - want))


def compare(got, want):
    """The numbers held to the config's limits."""
    if got.shape != want.shape or got.dtype != want.dtype:
        return {"max_abs_err": float("inf")}
    return {"max_abs_err": float(_max_abs_err(got, want))}
