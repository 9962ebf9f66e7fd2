"""The KNN binding (``repro.apps.knn.bind_programs``) on its normal path.

Each blue runs the jitted Pallas kernel on a contiguous shard padded once
at bind time; the sorters and the aggregator run one jitted merge.  Through
``compile → execute`` the answers equal the plain float32 search, with
every returned index judged by its exact distance, for the one-FPGA (27
blues) and the four-FPGA (72 blues) graphs, on shards whose sizes are and
are not a multiple of the kernel's block.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import knn
from repro.compiler import CompileOptions, compile as tapa_compile
from repro.core import fpga_ring_cluster
from repro.exec import bind_programs, execute

# Placement only: the floorplan of the 97-task graph takes minutes on the
# CPU and decides nothing the numerics read.
_OPTS = CompileOptions(exact_limit=10, passes=(
    "normalize_units", "partition", "pipeline_interconnect", "schedule"))
# Float32 squared distances in 16-D are good to a few 1e-6 (the kernel
# forms |q|² − 2q·x + |x|², the check Σ(q − x)² in float64).
ATOL = 2e-5


@pytest.mark.parametrize("ndev,n", [
    (1, 27 * 64),       # 27 shards of 64 points: whole blocks
    (1, 1000),          # 27 shards of 37 or 38 points
    (4, 4000),          # 72 shards of 55 or 56 points
])
def test_binding_matches_plain_reference(ndev, n):
    graph = knn.build_graph(ndev)
    design = tapa_compile(graph, fpga_ring_cluster(ndev), _OPTS)
    spec = {"n": n, "dim": 16, "q": 8, "streams": 2, "seed": 3}
    binding = bind_programs(graph, spec)
    result = execute(design, binding)
    dists, idx = result.outputs
    want_d, _ = binding.reference()
    np.testing.assert_allclose(dists, want_d, atol=ATOL, rtol=0)

    data = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (n, 16),
                                        jnp.float32), np.float64)
    queries = np.stack([np.asarray(binding.source_inputs["dist0"][t],
                                   np.float64) for t in range(2)])
    idx = np.asarray(idx)
    assert ((idx >= 0) & (idx < n)).all()
    assert (np.diff(np.sort(idx, -1), axis=-1) > 0).all()   # no repeats
    exact = ((queries[:, :, None, :] - data[idx]) ** 2).sum(-1)
    full = ((queries[:, :, None, :] - data[None, None]) ** 2).sum(-1)
    np.testing.assert_allclose(np.sort(exact, -1),
                               np.sort(full, -1)[..., :knn.K],
                               atol=ATOL, rtol=0)

    report = result.report
    assert len(report.channels) == len(graph.channels)
    assert sum(report.device_fired.values()) == 2 * len(graph.tasks)
    if ndev == 4:
        assert len(graph.tasks) == 97 and len(graph.channels) == 96


def test_binding_holds_no_device_copy_of_the_dataset():
    """The blues' padded shards are the dataset's only copy on a device:
    the generated points are staged to the host and let go at bind time,
    and ``reference()`` regenerates them from the seed."""
    n, dim = 1111, 16
    graph = knn.build_graph(1)
    design = tapa_compile(graph, fpga_ring_cluster(1), _OPTS)
    binding = bind_programs(graph, {"n": n, "dim": dim, "q": 8, "seed": 5})
    shapes = [a.shape for a in jax.live_arrays()]
    assert (n, dim) not in shapes
    placed = shapes.count((48, dim))          # 41 or 42 points, block 48
    jax.block_until_ready(execute(design, binding).outputs)
    shapes = [a.shape for a in jax.live_arrays()]
    assert (n, dim) not in shapes
    assert shapes.count((48, dim)) - placed == 27
    want_d, _ = binding.reference()
    assert want_d.shape == (2, 8, knn.K)


def test_too_few_points_for_the_blues():
    with pytest.raises(ValueError, match="fewer than k"):
        bind_programs(knn.build_graph(4), {"n": 700})


def _primitives(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    return set(re.findall(r"\b(pad|gather)\[", text))


def test_shard_program_neither_pads_nor_gathers():
    """The blue's jitted program, on a shard padded at bind time, has no
    ``pad`` or ``gather``; the kernel's own entry point pads an unpadded
    shard, which the check can see."""
    q = jnp.zeros((8, 16), jnp.float32)
    shard = jnp.zeros((64, 16), jnp.float32)
    kw = dict(k=10, n_valid=55, block_n=64, interpret=True)
    assert _primitives(lambda a, b, o: knn.knn_shard(a, b, o, **kw),
                       q, shard, jnp.int32(7)) == set()
    from repro.kernels import knn_op
    assert _primitives(lambda a, b: knn_op(a, b, k=10, block_n=32,
                                           interpret=True),
                       q, shard[:55]) == {"pad"}
