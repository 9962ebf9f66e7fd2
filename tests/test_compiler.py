"""repro.compiler: golden equivalence vs the legacy hand-wired chain,
bit-exact unit-normalization round trips, pipeline composition, and the
num_devices fix."""
import json
import math

import numpy as np
import pytest

from repro.apps import knn
from repro.compiler import (CompileError, CompileOptions, CompilerPipeline,
                            DEFAULT_PASSES)
from repro.compiler import compile as tapa_compile
from repro.core import (ALVEO_U55C, ResourceProfile, Task, TaskGraph,
                        fpga_ring_cluster, linear_graph, tpu_pod_cluster)
from repro.core.costmodel import simulate
from repro.core.floorplan import floorplan_device as raw_floorplan_device
from repro.core.partitioner import partition as raw_partition
from repro.core.pipelining import pipeline_interconnect as raw_pipeline


def test_golden_knn_ring_matches_legacy_chain():
    """End-to-end: KNN on a 4-FPGA ring through compile() must match the
    legacy hand-wired chain pass-for-pass (assignment, floorplan, FIFO
    depths, simulated makespan)."""
    cl = fpga_ring_cluster(4)
    # exact_limit below the problem size keeps both sides on the fast
    # recursive-bisect path so each MILP solves well inside its budget.
    g1 = knn.build_graph(4)
    p1 = raw_partition(g1, cl, balance_kind="LUT", balance_tol=0.8,
                       exact_limit=100, time_limit=30.0)
    d_small = min((d for d in range(4) if p1.device_tasks(d)),
                  key=lambda d: len(p1.device_tasks(d)))
    fp1 = raw_floorplan_device(
        g1, p1.device_tasks(d_small), ALVEO_U55C.resources,
        hbm_tasks=[t for t in p1.device_tasks(d_small)
                   if t.startswith("dist")])
    rep1 = raw_pipeline(g1, p1, {d_small: fp1}, cl)
    res1 = simulate(g1, p1, cl, {d: 220e6 for d in range(4)})

    g2 = knn.build_graph(4)
    design = tapa_compile(g2, cl, CompileOptions(
        balance_kind="LUT", balance_tol=0.8,
        exact_limit=100, partition_time_limit=30.0,
        floorplan_devices=(d_small,),
        hbm_tasks=tuple(t for t in g2.tasks if t.startswith("dist")),
        freq_hz=220e6))

    assert [r.name for r in design.pass_records] == list(DEFAULT_PASSES)
    p2 = design.partition
    assert p2.assignment == p1.assignment
    assert p2.comm_cost == p1.comm_cost
    np.testing.assert_array_equal(p2.usage, p1.usage)
    fp2 = design.floorplans[d_small]
    assert fp2.slot_of == fp1.slot_of
    assert fp2.wirelength == fp1.wirelength
    assert design.pipeline_report.added_latency == rep1.added_latency
    assert design.pipeline_report.depth == rep1.depth
    # Depths were written back onto the caller's graph, as before.
    assert [c.depth for c in g2.channels] == [c.depth for c in g1.channels]
    assert design.schedule.makespan == res1.makespan
    # Artifact digest is JSON-clean and carries every stage.
    digest = json.loads(design.to_json())
    assert {"partition", "floorplans", "pipeline", "schedule",
            "passes"} <= set(digest)


def _tpu_like_graph(n=8):
    g = TaskGraph("lm-chain")
    for i in range(n):
        g.add_task(Task(f"l{i}", ResourceProfile(
            {"hbm_bytes": (3.1 + i) * 1e9,
             "flops": (1.7 + 0.3 * i) * 1e15})))
    for i in range(n - 1):
        g.add_channel(f"l{i}", f"l{i + 1}", 512, bytes_per_step=2e6)
    return g


def test_unit_normalization_round_trips_exactly():
    """The normalize_units pass must (a) never touch the caller's graph or
    cluster, (b) use power-of-two scales, (c) report usage in original
    units bit-exactly — replacing the in-place rescaling that used to live
    in launch/plan.py."""
    g = _tpu_like_graph()
    orig_areas = {n: dict(t.area.amounts) for n, t in g.tasks.items()}
    cl = tpu_pod_cluster(2)
    orig_resources = dict(cl.device.resources)
    design = tapa_compile(g, cl, CompileOptions(
        passes=("normalize_units", "partition", "pipeline_interconnect"),
        balance_kind="flops", balance_tol=0.9,
        capacity_override={"hbm_bytes": 16 * 1024 ** 3 * 256},
        relax_capacity_kinds=("flops",)))

    # (a) no in-place mutation of areas or the (module-global) DeviceSpec.
    assert {n: dict(t.area.amounts) for n, t in g.tasks.items()} == orig_areas
    assert cl.device.resources == orig_resources
    # (b) nontrivial power-of-two scales for both out-of-range kinds.
    assert design.unit_scale["hbm_bytes"] > 1.0
    assert design.unit_scale["flops"] > 1.0
    for s in design.unit_scale.values():
        assert math.frexp(s)[0] == 0.5          # exact power of two
    # Scaled areas round-trip bit-for-bit.
    for t in g.tasks.values():
        for k, v in t.area.amounts.items():
            s = design.unit_scale[k]
            assert (v / s) * s == v
    # (c) usage comes back in original units, exactly.
    p = design.partition
    assert p.num_devices() == 2
    for d in range(2):
        for ki, k in enumerate(p.kinds):
            expect = 0.0
            for name, dd in p.assignment.items():
                if dd == d:
                    expect += g.tasks[name].area[k]
            assert p.usage[d, ki] == expect
    # Subset pipeline: later stages simply absent from the artifact.
    assert design.floorplans == {}
    assert design.schedule is None
    assert design.pipeline_report is not None


def test_fpga_scale_units_pass_through_unscaled():
    g = linear_graph(4, width_bits=64, area={"LUT": 30000.0, "DSP": 64.0})
    design = tapa_compile(g, fpga_ring_cluster(2), CompileOptions(
        passes=("normalize_units", "partition")))
    assert all(s == 1.0 for s in design.unit_scale.values())


def test_partition_num_devices_counts_empty_devices():
    """num_devices() must report the cluster size even when high-indexed
    devices received no tasks (the old max(assignment)+1 undercounted)."""
    g = linear_graph(3, width_bits=64, area={"LUT": 10.0})
    p = raw_partition(g, fpga_ring_cluster(4))
    # Min-cut with ample capacity co-locates everything…
    assert len(set(p.assignment.values())) < 4
    # …but the partition still describes a 4-device cluster.
    assert p.num_devices() == 4
    assert p.usage.shape[0] == 4


def test_unknown_pass_rejected():
    with pytest.raises(CompileError, match="unknown pass"):
        CompilerPipeline(("partition", "no_such_pass"))


def test_later_passes_require_partition():
    g = linear_graph(2, area={"LUT": 10.0})
    for lone in ("floorplan", "pipeline_interconnect", "schedule"):
        with pytest.raises(CompileError, match="requires a partition"):
            tapa_compile(g, fpga_ring_cluster(2),
                         CompileOptions(passes=(lone,)))


def test_empty_passes_runs_no_passes():
    g = linear_graph(2, area={"LUT": 10.0})
    design = tapa_compile(g, fpga_ring_cluster(2),
                          CompileOptions(passes=()))
    assert design.pass_records == ()
    assert design.partition is None and design.schedule is None


def test_pipeline_rejects_conflicting_options_passes():
    g = linear_graph(2, area={"LUT": 10.0})
    with pytest.raises(CompileError, match="conflicts"):
        CompilerPipeline(("partition",)).run(
            g, fpga_ring_cluster(2), CompileOptions(passes=("schedule",)))


def test_explicit_empty_floorplan_device_rejected():
    g = linear_graph(3, width_bits=64, area={"LUT": 10.0})
    # Min-cut co-locates everything on one device, so some explicitly
    # requested device is guaranteed empty (and 7 is out of range).
    with pytest.raises(CompileError, match="received no tasks"):
        tapa_compile(g, fpga_ring_cluster(4), CompileOptions(
            passes=("normalize_units", "partition", "floorplan"),
            floorplan_devices=(0, 1, 2, 3, 7)))

