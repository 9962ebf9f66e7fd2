"""The ``knn-1chip`` cell on the CPU at a small size, with no chip.

* It resolves its config, app module, mix and metric readers by name, as
  the Dilate cells do; its work is the hand-computed count, and its plain
  reference agrees with the app's own.
* A whole run (set-up, the window, the check) of a sound program comes out
  correct; each fault the cell can have comes out not correct: the
  bfloat16 reference in the program's place, one returned index altered,
  one blue's shard left out of the merge.
* ``control.py``'s control fails the limits the program's readings meet.
* ``knn_roofline`` and ``knn_merge_ms`` (the merge's device time and the
  chip idle under its dispatch) read a hand-made trace exactly, and are
  silent untraced or where no KNN program ran.

The design is compiled without the floorplan pass: on one chip placement
decides nothing the check reads, and the paper flow's floorplan of the
97-task graph takes minutes on the CPU.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_chip_bench_check import (HERE, SEED, _load, _wrap, broken,
                                   control_in_place)
from test_chip_bench_data import test_cell_resolves_by_name as _resolves

WORKLOAD = "knn-1chip"
# 55 or 56 points a shard over 72 blues: padded to the kernel's block.
SMALL_CONFIG = {"n_points": 4000}
SMALL_MIX = {"batches": 2, "queries": 8}


@pytest.fixture(scope="module")
def run():
    return _load("chipbench_run", HERE / "run.py")


@pytest.fixture(autouse=True)
def quick_compile(monkeypatch):
    import repro.compiler
    from repro.compiler import CompileOptions
    real = repro.compiler.compile
    options = CompileOptions(
        exact_limit=10, passes=("normalize_units", "partition",
                                "pipeline_interconnect", "schedule"))
    monkeypatch.setattr(repro.compiler, "compile",
                        lambda graph, cluster, _=None:
                        real(graph, cluster, options))


def small_cell(run):
    cell = run.resolve(run.load_spec(), WORKLOAD)
    cell.config = dict(cell.config, **SMALL_CONFIG)
    cell.mix = dict(cell.mix, **SMALL_MIX)
    return cell


def drive(run, seed=SEED):
    return run.run_cell(small_cell(run), seed, 0.2, False,
                        jax.devices()[:1], interpret=True)


def test_knn_cell_resolves_by_name(run):
    _resolves(run, run.load_spec(), WORKLOAD)


def test_knn_work_is_hand_computed(run):
    cell = run.resolve(run.load_spec(), WORKLOAD)
    work = cell.app.work(cell.config, cell.mix)
    # 512 queries a call against 4M points of 16 f32, read once; 16
    # multiplies, 16 adds and one compare per query-point pair.
    assert work == {"bytes": (4_000_000 + 512) * 16 * 4,
                    "ops": 512 * 4_000_000 * 33}
    peaks = _load("peaks", HERE / "peaks.py")
    least = peaks.least_time_s(work, peaks.peak("TPU v5 lite"))
    assert least == pytest.approx(0.343e-3, rel=2e-3)


def test_knn_reference_matches_the_app(run):
    from repro.exec import bind_programs
    cell = small_cell(run)
    graph = cell.app.build_graph(cell.config)
    seed = 2**31 + 5
    binding = bind_programs(graph, cell.app.bind_spec(
        cell.config, cell.mix, seed, interpret=True))
    want_d, want_i = binding.reference()
    got = cell.app.reference(cell.config, cell.mix, seed)
    assert got.shape == (2, 8, 10) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_d),
                               rtol=0, atol=1e-5)
    nums = cell.app.compare((want_d, want_i), got)
    assert nums["bad_indices"] == 0
    assert nums["max_index_err"] <= cell.config["limits"]["max_index_err"]


def test_sound_run_is_correct(run):
    out = drive(run)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    cell = run.resolve(run.load_spec(), WORKLOAD)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(out["check"]) == set(cell.config["limits"])
    for c in out["check"].values():
        assert c["value"] <= c["limit"]


def _index_altered(binding):
    # The aggregator hands on one neighbour's index moved to the next point.
    def fault(inputs, body):
        d, i = body(inputs)
        return d, i.at[0, 0].add(1)
    _wrap(binding, "agg", fault)


def _shard_left_out(binding):
    # The first sorter merges its blues but dist0.
    _wrap(binding, "sort0", lambda inputs, body: body(
        {k: v for k, v in inputs.items() if k != "dist0"}))


@pytest.mark.parametrize("fault", [_index_altered, _shard_left_out])
def test_knn_fault_is_not_correct(run, monkeypatch, fault):
    broken(monkeypatch, "knn", fault)
    out = drive(run)
    assert not out["correct"] and out["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in out["check"].values())


def test_control_in_the_programs_place_is_not_correct(run, monkeypatch):
    cell = small_cell(run)
    control_in_place(monkeypatch, cell, SEED)
    out = run.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                       interpret=True)
    assert not out["correct"] and out["failed"] >= 1
    c = out["check"]["max_dist_err"]
    assert c["value"] > c["limit"]


def test_control_fails_where_the_program_passes(run):
    control = _load("chipbench_control", HERE / "control.py")
    out = control.readings(small_cell(run), [SEED, SEED + 1],
                           [SEED + 2, SEED + 3], 0.2, jax.devices()[:1],
                           interpret=True)
    e = out["ends"]["max_dist_err"]
    assert e["upper"] > e["limit"]
    for e in out["ends"].values():
        assert e["lower"] <= e["limit"]


# -- the readers ----------------------------------------------------------

KERNEL = "jit_knn_shard/custom-call:tpu_custom_call"


@pytest.fixture(scope="module")
def readers():
    return {name: _load(f"chipbench_metric_{name}",
                        HERE / "metrics" / f"{name}.py").read
            for name in ("knn_roofline", "knn_merge_ms")}


def _readings(run, op_s, calls, least_exec_s=0.343e-3, idle_s=None):
    tr = _load("trace_reduce", HERE / "trace_reduce.py")
    summary = None if op_s is None else tr.TraceSummary(
        window_s=0.5, busy_s={0: 0.4}, chip_op_s={0: op_s},
        chip_op_count={0: {k: 1 for k in op_s}}, edges_s={0: (0.0, 0.0)},
        idle_s=idle_s or {})
    return run.Readings(chips=1, setup_s=0.0, compile_design_s=0.0,
                        xla_compile_s=0.0, walls=[0.1] * calls,
                        window_s=0.5, least_exec_s=least_exec_s,
                        trace=summary)


def test_readers_read_a_hand_made_trace_exactly(run, readers):
    """Two calls: 80 ms in the KNN kernel, 6 ms in the merge program and
    10 ms of chip idle under its dispatch, and ops and idle of other
    programs that neither reader counts."""
    r = _readings(run, {KERNEL: 0.080, "jit_knn_shard/fusion": 0.010,
                        "jit_knn_merge/sort": 0.004,
                        "jit_knn_merge/fusion": 0.002,
                        "jit_dilate_op/custom-call:tpu_custom_call": 1.0,
                        "jit_concatenate/concatenate": 0.003}, calls=2,
                  idle_s={"PjitFunction(knn_merge)": 0.010,
                          "PjitFunction(knn_shard)": 0.001,
                          "exec.fire": 0.003})
    assert readers["knn_roofline"](r) == pytest.approx(
        100 * 0.343e-3 * 2 / 0.080)
    assert readers["knn_merge_ms"](r) == pytest.approx(1e3 * 0.016 / 2)


def test_merge_reader_counts_dispatch_idle_alone(run, readers):
    """A merge whose device time falls outside the trace still reads the
    chip idle under its dispatch."""
    r = _readings(run, {KERNEL: 0.080}, calls=4,
                  idle_s={"PjitFunction(knn_merge)": 0.002})
    assert readers["knn_merge_ms"](r) == pytest.approx(1e3 * 0.002 / 4)


@pytest.mark.parametrize("op_s", [
    None,                                              # untraced
    {"jit_dilate_op/custom-call:tpu_custom_call": 1.0,  # no KNN program
     "jit_knn_ref/fusion": 0.2}])
def test_readers_are_silent_without_their_programs(run, readers, op_s):
    r = _readings(run, op_s, calls=1, least_exec_s=math.nan,
                  idle_s={"exec.dispatch": 0.1,
                          "PjitFunction(broadcast_in_dim)": 0.05})
    for read in readers.values():
        assert read(r) is None
