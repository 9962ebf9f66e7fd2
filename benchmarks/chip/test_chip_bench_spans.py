"""The executor's span metrics, on the CPU with no chip.

``exec_host_idle_ms`` and ``exec_block_idle_ms`` read the chip idle that
the trace reduction names after the program's ``exec.*`` spans: exact on
a hand-made trace, and bounded by the whole idle time on one
``execute()`` of ``dilate-1chip`` recorded on a v5e with those spans.
"""
import math
import pathlib

import pytest
from jax.profiler import ProfileData

from test_chip_bench_data import _load, _plane

HERE = pathlib.Path(__file__).resolve().parent
TRACE = HERE / "testdata" / "dilate-1exec-spans.xplane.pb.gz"
READERS = ("exec_host_idle_ms", "exec_block_idle_ms")


@pytest.fixture(scope="module")
def tr():
    return _load("trace_reduce", HERE / "trace_reduce.py")


@pytest.fixture(scope="module")
def readers():
    return {name: _load(f"chipbench_metric_{name}",
                        HERE / "metrics" / f"{name}.py").read
            for name in READERS}


def _readings(summary, calls):
    run = _load("chipbench_run", HERE / "run.py")
    return run.Readings(chips=1, setup_s=0.0, compile_design_s=0.0,
                        xla_compile_s=0.0, walls=[0.1] * calls,
                        window_s=summary.window_s if summary else 0.0,
                        least_exec_s=math.nan, trace=summary)


def test_hand_made_trace_reads_exactly(tr, readers):
    """Chip 0 waits 10 us under exec.block, 8 under exec.fire, 6 under
    exec.sweep, 8 under exec.report, 2 under a JAX span nested in
    exec.dispatch and 4 in the window alone, over 2 calls."""
    text = (
        _plane(1, "/host:CPU", {"python3": [
            ("bench.window", 0, 100), ("bench.execute", 10, 85),
            ("exec.execute", 11, 83), ("exec.sweep", 12, 60),
            ("exec.fire", 13, 50), ("exec.dispatch", 14, 6),
            ("PjitFunction(dilate_op)", 15, 4), ("exec.block", 20, 30),
            ("exec.report", 75, 10)]})
        + _plane(2, "/device:TPU:0", {"XLA Ops": [
            ("%a = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", s, d)
            for s, d in ((0, 16), (18, 22), (50, 2), (60, 4), (70, 6),
                         (84, 12))]}))
    s = tr.reduce(ProfileData.from_text_proto(text), [0])
    us = 1e-6
    assert s.idle_s == {
        "PjitFunction(dilate_op)": pytest.approx(2 * us),
        "exec.block": pytest.approx(10 * us),
        "exec.fire": pytest.approx(8 * us),
        "exec.sweep": pytest.approx(6 * us),
        "exec.report": pytest.approx(8 * us),
        "bench.window": pytest.approx(4 * us)}
    r = _readings(s, calls=2)
    host = readers["exec_host_idle_ms"](r)
    block = readers["exec_block_idle_ms"](r)
    assert host == pytest.approx(1e3 * 22 * us / 2)
    assert block == pytest.approx(1e3 * 10 * us / 2)
    # The JAX-named and window-only idle is in neither.
    total_ms = 1e3 * sum(s.idle_s.values()) / 2
    assert host + block == pytest.approx(total_ms - 1e3 * 6 * us / 2)


def test_silent_without_spans_or_trace(tr, readers):
    """Untraced, or traced on a program without exec.* spans: no value."""
    text = (
        _plane(1, "/host:CPU", {"python3": [
            ("bench.window", 0, 100), ("bench.execute", 10, 80)]})
        + _plane(2, "/device:TPU:0", {"XLA Ops": [
            ("%a = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", 20, 10)]}))
    s = tr.reduce(ProfileData.from_text_proto(text), [0])
    assert "bench.execute" in s.idle_s
    for read in readers.values():
        assert read(_readings(s, calls=1)) is None
        assert read(_readings(None, calls=1)) is None


def test_recorded_trace_names_exec_spans(tr, readers):
    """One warm ``execute()`` of dilate-1chip with the executor's spans,
    traced on a v5e: the chip's idle falls under exec.block and other
    exec.* spans, and the two metrics hold no more than the call's idle."""
    s = tr.reduce(tr.load(TRACE), [0])
    names = {k for k in s.idle_s if k.startswith("exec.")}
    assert "exec.block" in names and names - {"exec.block"}
    r = _readings(s, calls=1)
    host = readers["exec_host_idle_ms"](r)
    block = readers["exec_block_idle_ms"](r)
    assert host > 0 and block > 0
    assert host + block <= 1e3 * sum(s.idle_s.values()) * (1 + 1e-9)
