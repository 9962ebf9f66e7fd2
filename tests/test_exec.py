"""End-to-end tests for the dataflow executor (repro.exec).

Numerics parity: every paper app, compiled onto 2- and 4-device rings and
run through the executor, must reproduce its single-device Pallas/jnp
reference.  Accounting: the measured inter-device traffic must land on
exactly the channels the partitioner's Eq. 2 objective charged.
Regression: a FIFO clamped below its §4.6 balanced depth is caught by the
starvation detector, while the compiler's balanced depths run clean.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import APPS
from repro.compiler import CompileOptions, compile as tapa_compile
from repro.core import ResourceProfile, Task, TaskGraph, fpga_ring_cluster
from repro.exec import (DeadlockError, ProgramBinding, SOURCE_KEY,
                        StarvationError, bind_programs, execute)

# Small exact_limit keeps the larger graphs on the fast recursive-bisect
# path; the executor only needs *a* valid partition, not the optimum.
_OPTS = CompileOptions(balance_kind="LUT", balance_tol=0.8,
                       floorplan_devices=(0,), exact_limit=1500,
                       partition_time_limit=20.0)


def _compile(app: str, ndev: int):
    graph = APPS[app].build_graph(ndev)
    return tapa_compile(graph, fpga_ring_cluster(ndev), _OPTS)


@pytest.mark.parametrize(
    "ndev", [2, pytest.param(4, marks=pytest.mark.slow)])
@pytest.mark.parametrize("app", ["stencil", "pagerank", "knn", "cnn"])
def test_numerics_parity(app, ndev):
    design = _compile(app, ndev)
    binding = bind_programs(design.graph)
    result = execute(design, binding)
    expected = binding.reference()
    got = result.outputs
    if app == "knn":        # the same neighbours, in any order among
        same = jnp.sort(got[1], -1) == jnp.sort(expected[1], -1)   # ties,
        assert bool(jnp.all(same))
        got, expected = got[0], expected[0]          # then their distances
    err = float(jnp.max(jnp.abs(got - expected)))
    assert err <= binding.atol, (app, ndev, err)


@pytest.mark.parametrize("app", ["stencil", "pagerank", "knn", "cnn"])
def test_measured_traffic_matches_partition_accounting(app):
    design = _compile(app, 2)
    report = execute(design).report
    agree = report.agreement()
    assert agree["cut_set_match"], report.summary()["comm"]
    assert agree["comm_cost_match"], report.summary()["comm"]
    assert report.measured_inter_bytes > 0
    # Every task fired `iterations` times on its assigned device.
    assert sum(report.device_fired.values()) == \
        report.iterations * len(design.graph.tasks)
    # Balanced §4.6 depths: the pipeline never starved.
    assert not report.starvation_events


def test_executor_respects_channel_depths():
    """Occupancy stays within the compiled FIFO capacities."""
    design = _compile("knn", 4)
    report = execute(design).report
    for tr in report.channels:
        assert 0 < tr.tokens
        assert tr.max_occupancy <= tr.depth


# ---------------------------------------------------------------------------
# Deadlock / starvation regression (§4.6 cut-set balancing).
# ---------------------------------------------------------------------------

def _forkjoin_graph():
    """a → b → c plus a direct a → c edge: reconvergent paths whose latency
    differs when b lands on the remote device."""
    g = TaskGraph("forkjoin")
    for n in ("a", "b", "c"):
        g.add_task(Task(n, ResourceProfile({"LUT": 1000.0})))
    g.add_channel("a", "b", 512, bytes_per_step=64.0)
    g.add_channel("b", "c", 512, bytes_per_step=64.0)
    g.add_channel("a", "c", 512, bytes_per_step=64.0)
    return g


def _forkjoin_binding(g, T=8):
    xs = [jnp.full((4,), float(t)) for t in range(T)]
    programs = {"a": lambda i: i[SOURCE_KEY],
                "b": lambda i: i["a"] + 1.0,
                "c": lambda i: i["a"] + i["b"]}
    return ProgramBinding(
        graph=g, programs=programs, iterations=T,
        source_inputs={"a": xs},
        finalize=lambda s: jnp.stack(s["c"]),
        reference=lambda: jnp.stack([2.0 * x + 1.0 for x in xs]))


def _forkjoin_design(g):
    return tapa_compile(g, fpga_ring_cluster(2), CompileOptions(
        balance_kind="LUT", balance_tol=2.0,
        pins={"a": 0, "c": 0, "b": 1},
        passes=("normalize_units", "partition", "pipeline_interconnect",
                "schedule")))


def test_balanced_depths_run_clean():
    g = _forkjoin_graph()
    design = _forkjoin_design(g)
    # The §4.6 pass deepened the short a→c path to absorb the slack.
    depths = {(c.src, c.dst): c.depth for c in g.channels}
    assert depths[("a", "c")] > depths[("a", "b")]
    result = execute(design, _forkjoin_binding(g))
    binding = _forkjoin_binding(g)
    np.testing.assert_allclose(np.asarray(result.outputs),
                               np.asarray(binding.reference()), atol=1e-6)


def test_unbalanced_fifo_caught_by_starvation_detector():
    g = _forkjoin_graph()
    design = _forkjoin_design(g)
    # Clamp the short path's FIFO below its balanced depth: the join must
    # starve behind it instead of silently throttling.
    direct = next(c for c in g.channels if (c.src, c.dst) == ("a", "c"))
    direct.depth = 1
    with pytest.raises(StarvationError, match=r"join 'c' .* a->c"):
        execute(design, _forkjoin_binding(g))


def test_hard_deadlock_diagnosed():
    """An unseeded back edge can never fire — the executor must say why."""
    g = TaskGraph("cycle")
    for n in ("x", "y"):
        g.add_task(Task(n, ResourceProfile({"LUT": 1000.0})))
    g.add_channel("x", "y", 512)
    g.add_channel("y", "x", 512, back=True)
    design = tapa_compile(g, fpga_ring_cluster(2), CompileOptions(
        balance_kind="LUT", balance_tol=2.0,
        passes=("normalize_units", "partition", "pipeline_interconnect")))
    binding = ProgramBinding(
        graph=g, iterations=2,
        programs={"x": lambda i: i["y"], "y": lambda i: i["x"]},
        prime={})                 # deliberately missing the seed token
    with pytest.raises(DeadlockError, match="deadlock"):
        execute(design, binding)


# ---------------------------------------------------------------------------
# Binding plumbing.
# ---------------------------------------------------------------------------

def test_execute_entry_point_on_artifact():
    design = _compile("stencil", 2)
    result = design.execute(inputs={"h": 32, "w": 32, "streams": 2})
    assert result.outputs.shape == (2, 32, 32)
    assert result.report.iterations == 2


def test_stencil_stages_run_the_kernel(monkeypatch):
    """Stage bodies run the Pallas kernel; ``reference()`` is plain jnp,
    and the two agree bit for bit."""
    import repro.kernels as kernels
    calls = []
    real = kernels.dilate_op

    def spy(img, **kw):
        calls.append(kw)
        return real(img, **kw)

    monkeypatch.setattr(kernels, "dilate_op", spy)
    design = _compile("stencil", 2)
    binding = bind_programs(design.graph, {"h": 32, "w": 128,
                                           "stage_iters": 3, "streams": 2})
    assert binding.atol == 0.0
    result = execute(design, binding)
    assert [c["iters"] for c in calls] == [3] * 4    # 2 stages x 2 images
    np.testing.assert_array_equal(np.asarray(result.outputs),
                                  np.asarray(binding.reference()))
    assert len(calls) == 4                           # reference: no kernel


def test_placement_recorded_and_checked():
    design = _compile("stencil", 2)
    dev = jax.devices()[0]
    name = f"{dev.platform}:{dev.id}"
    report = execute(design, device_map=[0, 0]).report
    assert report.placement == {0: name, 1: name}
    summary = report.summary()
    assert summary["placement"] == {"0": name, "1": name}
    assert summary["task_devices"] == {t: [name]
                                       for t in design.graph.tasks}
    # A map naming a device the pool lacks is refused, not wrapped.
    with pytest.raises(ValueError, match="only 2 devices"):
        execute(design, devices=[dev, dev], device_map=[0, 2])


def test_bind_programs_rejects_unknown_graph():
    g = TaskGraph("mystery-app")
    g.add_task(Task("t", ResourceProfile({"LUT": 1.0})))
    with pytest.raises(KeyError, match="no program binding"):
        bind_programs(g)


def test_binding_validates_coverage():
    g = _forkjoin_graph()
    with pytest.raises(ValueError, match="no program bound"):
        ProgramBinding(graph=g, programs={"a": lambda i: i},
                       iterations=1).validate()


def test_parallel_channels_rejected():
    """Two channels between one task pair would shadow a token — refuse."""
    g = TaskGraph("twin")
    for n in ("p", "q"):
        g.add_task(Task(n, ResourceProfile({"LUT": 1000.0})))
    g.add_channel("p", "q", 512)
    g.add_channel("p", "q", 256)
    design = tapa_compile(g, fpga_ring_cluster(2), CompileOptions(
        balance_kind="LUT", balance_tol=2.0,
        passes=("normalize_units", "partition", "pipeline_interconnect")))
    binding = ProgramBinding(
        graph=g, iterations=1,
        programs={"p": lambda i: i[SOURCE_KEY], "q": lambda i: i["p"]},
        source_inputs={"p": [jnp.zeros(2)]})
    with pytest.raises(ValueError, match="parallel channels"):
        execute(design, binding)
