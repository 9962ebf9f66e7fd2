"""Cross-check analytic FLOP accounting against XLA cost_analysis via
layer-count differencing (unrolled configs so while-undercounting cannot
bias the check) — DESIGN.md §6."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.launch.analytic import prefill_flops
from repro.launch.graphs import layer_flops
from repro.launch.hlo_analysis import cost_summary
from repro.models import LayerSpec, init_params
from repro.models import transformer as T
from repro.models import layers


def _forward_flops(cfg, batch, seq):
    """cost_analysis FLOPs of the full forward (logits of last position)."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    def fwd(p, t):
        x = T._embed_inputs(p, cfg, {"tokens": t})
        pos = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
        x, _ = T._run_stack(p, cfg, x, pos)
        x = layers.rmsnorm(p["final_norm"], x)
        return layers.unembed(T._unembed_table(p, cfg), x[:, -1, :])

    compiled = jax.jit(fwd).lower(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)),
        toks).compile()
    return cost_summary(compiled)["flops"]


def test_layer_flops_matches_hlo_differencing():
    base = get_arch("qwen3-4b").smoke()
    B, S = 2, 64
    # Unrolled stacks: pattern×L with ONE superblock → no while loop.
    cfg1 = dataclasses.replace(base, pattern=(LayerSpec("gqa", "dense"),),
                               num_superblocks=1, q_chunk=S)
    cfg3 = dataclasses.replace(base,
                               pattern=(LayerSpec("gqa", "dense"),) * 3,
                               num_superblocks=1, q_chunk=S)
    f1 = _forward_flops(cfg1, B, S)
    f3 = _forward_flops(cfg3, B, S)
    hlo_per_layer = (f3 - f1) / 2.0
    analytic = layer_flops(cfg1, LayerSpec("gqa", "dense"), B, S)
    # within 25% (HLO counts softmax/norm flops the analytic model rounds)
    assert abs(hlo_per_layer - analytic) / analytic < 0.25, \
        (hlo_per_layer, analytic)


def test_prefill_flops_scale_with_seq():
    cfg = get_arch("qwen3-4b").full()
    f4k = prefill_flops(cfg, 1, 4096)
    f8k = prefill_flops(cfg, 1, 8192)
    # Between 2× (pure linear) and 4× (pure quadratic).
    assert 2.0 < f8k / f4k < 4.0
