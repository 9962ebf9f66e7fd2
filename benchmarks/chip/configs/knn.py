"""KNN (paper §5.4, CHIP-KNN): the design, what one ``execute()`` must do,
and the plain reference its outputs are held to.

The reference imports nothing of the program: it regenerates the points and
the query batches from the seed with a copy of the app's generator and
finds each query's K nearest points by exact squared L2 distance,
``Σ(q − x)²``, a block of points at a time.  It returns their distances,
ascending, and keeps the points and queries it searched so that
``compare`` can recompute the exact distance of every index the program
returns: two points closer together than the limits may come in either
order, a wrong neighbour reads its gap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Points per block of the reference's search: [128, 65536] distances.
BLOCK = 1 << 16

# (points, queries) of the last reference() computed, for compare().
_SEARCHED = None


def build_graph(config):
    from repro.apps import knn
    return knn.build_graph(config["fpgas"], n_points=config["n_points"],
                           dim=config["dim"])


def bind_spec(config, mix, seed, interpret=None):
    return {"n": config["n_points"], "dim": config["dim"],
            "q": mix["queries"], "k": config["k"], "streams": mix["batches"],
            "seed": seed, "interpret": interpret}


def work(config, mix):
    """The least one ``execute()`` must do: read the points and queries
    once, and for each query-point pair D multiplies, D adds and one
    compare against the running k-th nearest."""
    n, dim = config["n_points"], config["dim"]
    queries = mix["batches"] * mix["queries"]
    itemsize = jnp.dtype(config["dtype"]).itemsize
    return {"bytes": (n + queries) * dim * itemsize,
            "ops": queries * n * (2 * dim + 1)}


def points(config, mix, seed):
    """The points and query batches the app's binding searches: a copy of
    its generator.  Returns ([N, D], [batches, Q, D])."""
    key = jax.random.PRNGKey(seed)
    shape = (mix["queries"], config["dim"])
    data = jax.random.normal(key, (config["n_points"], config["dim"]),
                             jnp.float32)
    queries = jnp.stack([jax.random.normal(jax.random.fold_in(key, 1 + t),
                                           shape, jnp.float32)
                         for t in range(mix["batches"])])
    return data, queries


@jax.jit
def _pad_to_block(data):
    return jnp.pad(data, ((0, -data.shape[0] % BLOCK), (0, 0)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _nearest(queries, data, k, n):
    """The ``k`` smallest ``Σ(q − x)²`` of each query over the first ``n``
    rows of ``data`` (padded to a multiple of ``BLOCK``), ascending, in the
    inputs' dtype."""
    def step(best, b):
        x = jax.lax.dynamic_slice_in_dim(data, b * BLOCK, BLOCK)
        d = jnp.sum((queries[:, None, :] - x[None, :, :]) ** 2, axis=-1)
        row = b * BLOCK + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        d = jnp.concatenate([best, jnp.where(row < n, d, jnp.inf)], axis=1)
        return -jax.lax.top_k(-d, k)[0], None

    best = jnp.full((queries.shape[0], k), jnp.inf, queries.dtype)
    best, _ = jax.lax.scan(step, best, jnp.arange(data.shape[0] // BLOCK))
    return best


def reference(config, mix, seed, dtype=jnp.float32):
    """Each query's ``k`` nearest distances computed in ``dtype``, as
    [batches, Q, k] float32."""
    global _SEARCHED
    data, queries = points(config, mix, seed)
    _SEARCHED = (data, queries)
    padded = _pad_to_block(data).astype(dtype)
    return jnp.stack([_nearest(qs.astype(dtype), padded, config["k"],
                               config["n_points"]).astype(jnp.float32)
                      for qs in queries])


@jax.jit
def _max_abs_err(got, want):
    return jnp.max(jnp.abs(got - want))


@jax.jit
def _judge_indices(idx, want, data, queries):
    """The largest gap between the exact distances of the returned indices,
    sorted, and the reference's; and the indices out of range or repeated
    within a query."""
    n = data.shape[0]
    valid = (idx >= 0) & (idx < n)
    ordered = jnp.sort(idx, axis=-1)
    repeated = jnp.sum(ordered[..., 1:] == ordered[..., :-1])
    x = data[jnp.clip(idx, 0, n - 1)]
    d = jnp.sum((queries[:, :, None, :] - x) ** 2, axis=-1)
    d = jnp.sort(jnp.where(valid, d, jnp.inf), axis=-1)
    return jnp.max(jnp.abs(d - want)), jnp.sum(~valid) + repeated


def compare(got, want):
    """The numbers held to the config's limits.  ``got`` is the program's
    ``(dists, idx)``, or distances alone (another reference in its place),
    which are judged by ``max_dist_err`` only."""
    dists, idx = got if isinstance(got, (tuple, list)) else (got, None)
    if dists.shape != want.shape or dists.dtype != want.dtype:
        return {"max_dist_err": float("inf")}
    nums = {"max_dist_err": float(_max_abs_err(dists, want))}
    if idx is None:
        return nums
    if idx.shape != want.shape:
        return dict(nums, max_index_err=float("inf"), bad_indices=want.size)
    err, bad = _judge_indices(idx, want, *_SEARCHED)
    return dict(nums, max_index_err=float(err), bad_indices=int(bad))
