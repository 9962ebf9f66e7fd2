"""The chip benchmark's data layer, on the CPU with no chip.

* ``BENCHMARK.json`` keeps to its contract, and every cell resolves its
  config, app module, mix and metric readers by name;
* the work counts give the hand-computed bytes and operations;
* the plain reference agrees with the app's own reference at a small size;
* ``peaks`` refuses an unknown chip;
* the trace reduction reads a trace recorded on a v5e, and its interval
  arithmetic and per-chip readings hold on hand-made cases.

Nothing here loads the TPU library: the modules are imported with JAX on
the CPU, and no test asks for a TPU device.
"""
import importlib.util
import pathlib
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE = HERE / "testdata" / "dilate-1exec.xplane.pb.gz"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _load(name, path):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="module")
def run():
    return _load("chipbench_run", HERE / "run.py")


@pytest.fixture(scope="module")
def spec(run):
    return run.load_spec()


def test_spec_keeps_to_its_contract(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmarks/chip"]
    assert all("/" not in w or w.startswith("benchmarks/chip/")
               for w in spec["command"][1:])
    assert 1 <= spec["run_seconds"] <= 51
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    names += [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}


@pytest.mark.parametrize("workload", ["dilate-1chip", "dilate-4chip"])
def test_cell_resolves_by_name(run, spec, workload):
    cell = run.resolve(spec, workload)
    assert cell.chips in (1, 4)
    for fn in ("build_graph", "bind_spec", "work", "reference", "compare"):
        assert callable(getattr(cell.app, fn))
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported
    assert set(cell.readers) == reported | {m["name"] for m in cell.per_layer}
    assert set(cell.config["limits"]) >= {"misplaced_tasks"}


def test_every_metric_moves_a_metric_its_cells_report(run, spec):
    cells = {w["name"]: run.resolve(spec, w["name"])
             for w in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert m["moves"] in {e["name"] for e in cells[w].end_to_end}
            assert m in cells[w].per_layer


def test_dilate_work_is_hand_computed(run, spec):
    cell = run.resolve(spec, "dilate-1chip")
    work = cell.app.work(cell.config, cell.mix)
    # 4 images x 64 iterations = 256 passes over a 4096^2 f32 grid, each
    # one read and one write; 12 maxima per point.
    assert work == {"bytes": 256 * 2 * 4096 * 4096 * 4,
                    "ops": 256 * 12 * 4096 * 4096}
    assert work["bytes"] == 34_359_738_368


def _small(cell, **over):
    cell.config = dict(cell.config, **over)
    return cell


def test_dilate_reference_matches_the_app(run, spec):
    from repro.exec import bind_programs
    cell = _small(run.resolve(spec, "dilate-1chip"), grid=[16, 128])
    graph = cell.app.build_graph(cell.config)
    seed = 2**31 + 5
    binding = bind_programs(graph, cell.app.bind_spec(
        cell.config, cell.mix, seed, interpret=True))
    want = binding.reference()
    got = cell.app.reference(cell.config, cell.mix, seed)
    assert got.shape == (4, 16, 128) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert cell.app.compare(got, want) == {"max_abs_err": 0.0}


def test_peaks_refuse_an_unknown_chip():
    peaks = _load("peaks", HERE / "peaks.py")
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peak("TPU v9")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_least_time_takes_the_larger_bound():
    peaks = _load("peaks", HERE / "peaks.py")
    pk = peaks.peak("TPU v5 lite")
    assert peaks.least_time_s({"bytes": 819e9, "ops": 0}, pk) == 1.0
    assert peaks.least_time_s({"bytes": 0, "ops": 394e12}, pk) == 2.0


def test_refuses_without_a_tpu(run, capsys):
    assert run.main(["--workload", "dilate-1chip", "--seed", "1",
                     "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_interval_arithmetic():
    tr = _load("trace_reduce", HERE / "trace_reduce.py")
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []
    spans = [(0, 100, "window"), (10, 40, "execute"), (20, 30, "put"),
             (50, 90, "execute"), (60, 61, "x")]
    assert tr.innermost(spans, [5, 15, 25, 35, 45, 55, 95, 120]) == [
        "window", "execute", "put", "execute", "window", "execute",
        "window", "untraced"]


def _plane(pid, name, lines):
    """A text-proto XPlane: ``lines`` maps a line's name to its events,
    (name, start_us, duration_us) on a clock that starts at 1 us."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    text = f'planes {{ id: {pid} name: "{name}"\n'
    for lid, (line, events) in enumerate(lines.items()):
        text += f'lines {{ id: {lid + 1} name: "{line}" timestamp_ns: 1000\n'
        text += "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1e6)} "
            f"duration_ps: {int(d * 1e6)} }}\n" for n, s, d in events)
        text += "}\n"
    text += "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{_quote(n)}" '
        f'}} }}\n' for n, i in ids.items())
    return text + "}\n"


def _quote(name):
    return name.replace("\\", "\\\\").replace('"', '\\"')


KERNEL = ('%closed_call.4 = f32[8,128]{1,0:T(8,128)} custom-call(f32[8,128]'
          '{1,0:T(8,128)} %copy.11), custom_call_target="tpu_custom_call"')
WHILE = ('%while = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)S(1)}) '
         'while((s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)S(1)}) %tuple.13)')
COPY = '%copy.11 = f32[8,128]{1,0:T(8,128)} copy(f32[8,128]{1,0} %gte.25)'
FUSION = '%fusion = f32[8]{0:T(1024)} fusion(f32[8]{0} %a), kind=kLoop'


def test_hand_made_trace_reduces_exactly():
    """Known intervals: busy unions, clipping to the window, self time of
    nested ops by program and opcode, and each idle gap named by the
    innermost host span at its midpoint."""
    from jax.profiler import ProfileData
    tr = _load("trace_reduce", HERE / "trace_reduce.py")
    text = (
        _plane(1, "/host:CPU", {"python3": [
            ("bench.window", 0, 100), ("bench.execute", 10, 30),
            ("bench.block", 40, 5), ("outside", 150, 10)]})
        + _plane(2, "/device:TPU:0", {
            "XLA Modules": [("jit_dilate_op(123)", 18, 14),
                            ("jit_add(9)", 50, 10), ("jit_tail(1)", 95, 25)],
            "XLA Ops": [(WHILE, 20, 10), (KERNEL, 21, 5), (COPY, 26, 2),
                        (FUSION, 50, 10), (FUSION, 95, 25),
                        (FUSION, -50, 10)]})
        + _plane(3, "/device:TPU:1", {"XLA Ops": [(FUSION, 5, 90)]})
        + _plane(4, "/device:TPU:2", {"XLA Ops": [(FUSION, 0, 100)]}))
    s = tr.reduce(ProfileData.from_text_proto(text), [0, 1])
    us = 1e-6
    assert s.window_s == pytest.approx(100 * us)
    assert s.busy_s == {0: pytest.approx(25 * us), 1: pytest.approx(90 * us)}
    assert s.busy_mean_s == pytest.approx(57.5 * us)
    assert s.op_s == {
        "jit_dilate_op/while": pytest.approx(3 * us),
        "jit_dilate_op/custom-call:tpu_custom_call": pytest.approx(5 * us),
        "jit_dilate_op/copy": pytest.approx(2 * us),
        "jit_add/fusion": pytest.approx(10 * us),
        "jit_tail/fusion": pytest.approx(5 * us),
        "unknown/fusion": pytest.approx(90 * us)}
    assert s.op_count["jit_dilate_op/custom-call:tpu_custom_call"] == 1
    # Chip 0 waits 20 us in execute, 20 in block, 35 in the window; chip 1
    # waits 5 + 5 in the window.  Averaged over the two chips.
    assert s.idle_s == {"bench.execute": pytest.approx(10 * us),
                        "bench.block": pytest.approx(10 * us),
                        "bench.window": pytest.approx(22.5 * us)}
    top = s.breakdown()["device_ops"]
    assert top[0] == ["unknown/fusion", pytest.approx(90 * us)]


def test_hand_made_trace_keeps_each_chip():
    """Op time, events and the window's edges per chip: the sums over
    chips are what the metrics read."""
    from jax.profiler import ProfileData
    tr = _load("trace_reduce", HERE / "trace_reduce.py")
    text = (
        _plane(1, "/host:CPU", {"python3": [("bench.window", 0, 100)]})
        + _plane(2, "/device:TPU:0", {"XLA Ops": [
            (KERNEL, 10, 5), (KERNEL, 20, 5), (COPY, 30, 2)]})
        + _plane(3, "/device:TPU:1", {"XLA Ops": [
            (KERNEL, 40, 4), (FUSION, 90, 20)]}))
    s = tr.reduce(ProfileData.from_text_proto(text), [0, 1])
    us = 1e-6
    kernel = "unknown/custom-call:tpu_custom_call"
    assert s.chip_op_count == {0: {kernel: 2, "unknown/copy": 1},
                               1: {kernel: 1, "unknown/fusion": 1}}
    assert s.chip_op_s[0][kernel] == pytest.approx(10 * us)
    assert s.chip_op_s[1][kernel] == pytest.approx(4 * us)
    assert s.op_count[kernel] == 3
    assert s.op_s[kernel] == pytest.approx(14 * us)
    assert s.edges_s[0] == (pytest.approx(10 * us), pytest.approx(68 * us))
    assert s.edges_s[1] == (pytest.approx(40 * us), pytest.approx(0.0))
    chips = s.per_chip(top=1)
    assert sorted(chips) == [0, 1]
    assert chips[0]["ops"] == [[kernel, pytest.approx(10 * us), 2]]
    assert chips[1]["busy_s"] == pytest.approx(14 * us)


def test_op_key_reads_hlo_text():
    tr = _load("trace_reduce", HERE / "trace_reduce.py")
    assert tr.op_key("jit_dilate_op(269366300294418346)", KERNEL) == \
        "jit_dilate_op/custom-call:tpu_custom_call"
    assert tr.op_key("jit_dilate_op(1)", WHILE) == "jit_dilate_op/while"
    assert tr.op_key("jit_gather(7)", FUSION) == "jit_gather/fusion"
    assert tr.self_times([(0, 10, "a"), (1, 4, "b"), (5, 12, "c")]) == \
        [10 - 3 - 5, 3, 7]


@pytest.fixture(scope="module")
def recorded():
    tr = _load("trace_reduce", HERE / "trace_reduce.py")
    return tr, tr.load(TRACE)


def test_recorded_trace_reduces(recorded):
    """One warm ``execute()`` of dilate-1chip, traced on a v5e: 4 images x
    64 iterations of the Dilate kernel on chip 0."""
    tr, profile = recorded
    s = tr.reduce(profile, [0])
    assert set(s.busy_s) == {0}
    assert 0 < s.busy_s[0] < s.window_s
    kernel = "jit_dilate_op/custom-call:tpu_custom_call"
    # 256 launches; the device clock runs about 0.8 ms ahead of the host's
    # in this trace, so the first starts before the host span opens and
    # falls outside the window.
    assert s.op_count[kernel] == 255
    assert 0 < s.op_s[kernel] <= s.busy_s[0]
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s[0], rel=1e-6)
    idle = sum(s.idle_s.values())
    assert idle == pytest.approx(s.window_s - s.busy_s[0], rel=1e-6)
    assert set(s.idle_s) <= _host_names(profile)
    top = s.breakdown()
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    (chip, one), = s.per_chip().items()
    assert chip == 0 and one["busy_s"] == s.busy_s[0]
    assert one["ops"][0] == [kernel, s.op_s[kernel], 255]
    # The kernel's first launch lies before the window opens.
    assert one["edges_s"][0] < 1e-3 and 0 <= one["edges_s"][1] < s.window_s


def _host_names(profile):
    host = profile.find_plane_with_name("/host:CPU")
    return {ev.name for line in host.lines for ev in line.events} | {
        "untraced"}


def test_recorded_trace_busy_by_brute_force(recorded):
    """The busy union, recomputed by marking every microsecond."""
    tr, profile = recorded
    s = tr.reduce(profile, [0])
    host = profile.find_plane_with_name("/host:CPU")
    (w0, w1), = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                 for line in host.lines for e in line.events
                 if e.name == "bench.window"]
    plane = profile.find_plane_with_name("/device:TPU:0")
    marks = np.zeros((w1 - w0) // 1000 + 1, bool)
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            a = max(int(e.start_ns), w0)
            b = min(int(e.start_ns + e.duration_ns), w1)
            if b > a:
                marks[(a - w0) // 1000:(b - w0) // 1000] = True
    assert marks.sum() * 1e-6 == pytest.approx(s.busy_s[0], rel=0.02)


def test_recorded_trace_refuses_an_absent_chip(recorded):
    tr, profile = recorded
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce(profile, [0, 3])
