"""Oracle for the KNN distance + top-k kernel (CHIP-KNN, paper §3/§5.4)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def knn_ref(queries: jnp.ndarray, data: jnp.ndarray, k: int
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """queries: [Q, D]; data: [N, D].  Returns (dists [Q,k], idx [Q,k]) —
    squared-L2, ascending, in the inputs' precision: ``HIGHEST`` keeps a
    float32 product in float32 on a TPU, whose default is one bfloat16
    pass."""
    d2 = (jnp.sum(queries ** 2, -1, keepdims=True)
          - 2.0 * jnp.matmul(queries, data.T,
                             precision=jax.lax.Precision.HIGHEST)
          + jnp.sum(data ** 2, -1)[None, :])
    neg_d, idx = jax.lax.top_k(-d2, k)
    return -neg_d, idx
