"""Pipeline parallelism over the pod axis — GPipe-style microbatch pipeline
via shard_map + lax.ppermute (the `pp` strategy the TAPA-CS partitioner
recommends when a model's train state exceeds one pod's Eq. 1 budget, e.g.
deepseek-v3).

Mechanics: stage parameters are stacked on a leading axis sharded over
'pod'; shard_map is MANUAL over 'pod' only (data/model stay auto-GSPMD, so
each stage's internals still shard over the 16×16 intra-pod mesh).  The
schedule runs M + P − 1 ticks; each tick every pod applies its stage to the
activation it holds, then `ppermute`s it to the next pod — the paper's
latency-insensitive FIFO channel (C3/C5): buffering depth = 1 microbatch
per hop, correctness independent of added latency.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_forward(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                  microbatches: int):
    """Run x through P pipeline stages (P = mesh.shape['pod']).

    stage_fn(params_one_stage, x_mb) -> y_mb, applied by each pod to the
    microbatch currently resident on it.
    stacked_params: pytree with leading axis P (sharded over 'pod').
    x: [B, ...] global batch (replicated over 'pod', sharded over 'data'
    inside as usual).  Returns y: [B, ...] after all P stages.
    """
    num_stages = mesh.shape["pod"]
    M = microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    x_mb = x.reshape((M, B // M) + x.shape[1:])
    fwd = [(i, i + 1) for i in range(num_stages - 1)]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        # Manual over 'pod' ONLY — specs mention just the manual axis;
        # 'data'/'model' shardings ride along in the types (auto-GSPMD).
        in_specs=(P("pod"), P()),
        out_specs=P(), check_vma=False, axis_names={"pod"},
    )
    def run(params_local, x_local):
        # params_local: [1, ...] this pod's stage slice.
        p_one = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("pod")
        M_, mb = x_local.shape[0], x_local.shape[1:]
        state = jnp.zeros(mb, x_local.dtype)       # current activation
        outs = jnp.zeros_like(x_local)             # last stage's results

        def tick(t, carry):
            state, outs = carry
            # Stage 0 injects microbatch t (when one remains); others use
            # what arrived over the pipe.
            inject = x_local[jnp.minimum(t, M_ - 1)]
            cur = jnp.where(stage == 0, inject, state)
            y = stage_fn(p_one, cur)
            # Valid window: stage s processes mb (t - s) for 0 <= t-s < M.
            mb_idx = t - stage
            valid = (mb_idx >= 0) & (mb_idx < M_)
            y = jnp.where(valid, y, state)
            # Last stage writes its finished microbatch.
            outs = jnp.where(
                (stage == num_stages - 1) & valid,
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, jnp.clip(mb_idx, 0, M_ - 1), 0),
                outs)
            # Hand activation to the next stage (FIFO hop).
            state = jax.lax.ppermute(y, "pod", fwd)
            return state, outs

        _, outs = jax.lax.fori_loop(0, M_ + num_stages - 1, tick,
                                    (state, outs))
        # Only the last pod holds real outputs; psum broadcasts them
        # (non-final pods contribute zeros).
        outs = jnp.where(stage == num_stages - 1, outs,
                         jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pod")
        return outs

    y = run(stacked_params, x_mb)
    return y.reshape((B,) + y.shape[2:])
