"""Launch layer: sharding rules, plans, graphs, analytic accounting, and a
small-mesh dry-run in a subprocess (8 fake host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_arch, input_specs
from repro.launch.analytic import active_param_count, analyze
from repro.launch.graphs import build_lm_graph, total_param_bytes
from repro.launch.plan import make_plan
from repro.models import init_params


def test_total_param_bytes_matches_eval_shape():
    """Analytic param accounting vs the real init tree (hard consistency)."""
    for arch in ("qwen3-4b", "gemma2-27b", "deepseek-v2-236b", "xlstm-1.3b",
                 "recurrentgemma-9b"):
        cfg = get_arch(arch).full()
        shapes = jax.eval_shape(
            lambda c=cfg: init_params(jax.random.PRNGKey(0), c))
        true_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(shapes))
        est = total_param_bytes(cfg)
        # Analytic skips norm scales/router bias (tiny) — within 3%.
        assert abs(est - true_bytes) / true_bytes < 0.03, \
            (arch, est / 1e9, true_bytes / 1e9)


def test_lm_graph_structure():
    cfg = get_arch("qwen3-4b").full()
    g = build_lm_graph(cfg, 256, 4096)
    assert len(g.tasks) == 36 + 2          # layers + embed + head
    g.validate()
    # chain topology with single path
    assert len(g.channels) == 37


def test_encdec_graph_has_reconvergent_edges():
    cfg = get_arch("seamless-m4t-large-v2").full()
    g = build_lm_graph(cfg, 256, 4096)
    enc_out = g.out_channels("encoder")
    assert len(enc_out) == 24              # fan-out to every decoder layer


def test_plan_optimizer_gates():
    assert make_plan("qwen3-4b", get_arch("qwen3-4b").full(),
                     "train_4k").optimizer == "adamw"
    assert make_plan("deepseek-v3-671b", get_arch("deepseek-v3-671b").full(),
                     "train_4k").optimizer == "adafactor"
    assert make_plan("deepseek-v2-236b", get_arch("deepseek-v2-236b").full(),
                     "train_4k").optimizer == "adafactor"


def test_plan_multi_pod_partitions():
    cfg = get_arch("gemma2-27b").full()
    p = make_plan("gemma2-27b", cfg, "train_4k", num_pods=2)
    assert p.partition is not None
    assert p.partition.num_devices() == 2
    assert p.pipeline_depths is not None


def test_analytic_flops_scale():
    """6·N·D sanity: train FLOPs within 2× band of 6·N_active·tokens."""
    for arch in ("qwen3-4b", "mistral-nemo-12b", "deepseek-v2-236b"):
        cfg = get_arch(arch).full()
        cell = analyze(cfg, "train_4k")
        ratio = cell.model_flops / cell.flops_global
        assert 0.3 < ratio <= 1.0, (arch, ratio)


def test_input_specs_cover_all_cells():
    from repro.configs import SHAPES, supported_shapes
    for arch in ALL_ARCHS:
        mod = get_arch(arch)
        cfg = mod.full()
        for shape in supported_shapes(mod):
            specs = input_specs(cfg, shape)
            assert "tokens" in specs
            if SHAPES[shape].kind == "decode":
                assert "cache" in specs and "pos" in specs


SUBPROC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, json
    import jax.numpy as jnp
    from repro.configs import get_arch, input_specs
    from repro.launch import hlo_analysis, steps
    from repro.launch.mesh import make_mesh

    import dataclasses
    cfg = dataclasses.replace(get_arch("qwen3-4b").smoke(),
                              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    out = {}
    for name, mesh in (("single", make_mesh((2, 4), ("data", "model"))),
                       ("multi", make_mesh((2, 2, 2),
                                           ("pod", "data", "model")))):
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            "targets": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            "weights": jax.ShapeDtypeStruct((8, 16), jnp.float32),
        }
        lowered = steps.lower_train(cfg, mesh, batch, microbatches=2)
        compiled = lowered.compile()
        ca = hlo_analysis.cost_summary(compiled)
        out[name] = {"flops": ca["flops"], "ok": True}
    print(json.dumps(out))
""")


def test_dryrun_small_mesh_subprocess():
    """lower+compile on 8 fake devices, single- and multi-pod meshes.
    Run in a subprocess: device count locks at first jax init."""
    env = dict(os.environ)
    # Hermetic w.r.t. the caller's environment: the script needs src/ on the
    # path (prepended so an ambient PYTHONPATH can't shadow the repo) and
    # must own XLA_FLAGS (the device count locks at first jax init).
    ambient = env.get("PYTHONPATH")
    env["PYTHONPATH"] = "src" + (os.pathsep + ambient if ambient else "")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SUBPROC_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["single"]["ok"] and out["multi"]["ok"]
    assert out["single"]["flops"] > 0
