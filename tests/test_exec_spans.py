"""The executor's profiler spans (``repro.exec``'s ``exec.*`` names).

One small Dilate design runs through ``execute()`` under
``jax.profiler.trace`` on the CPU, its four logical devices on one host
device, and the recorded xplane is read back with
``jax.profiler.ProfileData``: how many of each span the call opened, with
what arguments, how they nest, and that tracing leaves the outputs
bit-identical.
"""
import glob

import jax
import numpy as np
import pytest

from repro.apps import stencil
from repro.compiler import compile as tapa_compile
from repro.core import fpga_ring_cluster
from repro.exec import bind_programs, execute

IMAGES, STAGE_ITERS = 3, 2


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    graph = stencil.build_graph(4, iters=64)
    design = tapa_compile(graph, fpga_ring_cluster(4))
    binding = bind_programs(graph, {"h": 16, "w": 128, "streams": IMAGES,
                                    "stage_iters": STAGE_ITERS})
    run = dict(devices=jax.devices()[:1], device_map=[0, 1, 2, 3])
    plain = execute(design, binding, **run)       # also the warm-up
    log_dir = tmp_path_factory.mktemp("exec-spans")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        result = execute(design, binding, **run)
        jax.block_until_ready(result.outputs)
    path, = glob.glob(str(log_dir / "plugins/profile/*/*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    host = profile.find_plane_with_name("/host:CPU")
    spans, = [[(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name,
                dict(e.stats)) for e in line.events
               if e.name.startswith("exec.")]
              for line in host.lines
              if any(e.name == "exec.execute" for e in line.events)]
    return design, plain, result, spans


def _named(spans, name):
    return [sp for sp in spans if sp[2] == name]


def _inside(child, parents):
    return any(p[0] <= child[0] and child[1] <= p[1] for p in parents)


def test_span_counts(traced):
    design, _, result, spans = traced
    report = result.report
    firings = len(design.graph.tasks) * IMAGES
    counts = {name: len(_named(spans, name)) for name in (
        "exec.execute", "exec.state", "exec.sweep", "exec.fire",
        "exec.dispatch", "exec.block", "exec.xfer", "exec.finalize",
        "exec.report")}
    moves = sum(c.tokens for c in report.channels if c.inter_device)
    assert moves == 3 * IMAGES
    assert counts == {
        "exec.execute": 1, "exec.state": 1, "exec.sweep": report.sweeps,
        "exec.fire": firings, "exec.dispatch": firings,
        "exec.block": 1, "exec.xfer": moves, "exec.finalize": 1,
        "exec.report": 1}


def test_span_arguments(traced):
    design, _, result, spans = traced
    (*_, call), = _named(spans, "exec.execute")
    assert call["graph"] == design.graph.name and call["call"] >= 1
    assign = design.partition.assignment
    fired = sorted((a["task"], a["device"])
                   for *_, a in _named(spans, "exec.fire"))
    assert fired == sorted((t, assign[t]) for t in design.graph.tasks
                           for _ in range(IMAGES))
    assert [a["sweep"] for *_, a in _named(spans, "exec.sweep")] == \
        list(range(result.report.sweeps))
    xfer = _named(spans, "exec.xfer")
    assert sum(a["nbytes"] for *_, a in xfer) == \
        result.report.measured_inter_bytes
    inter = {c.index for c in result.report.channels if c.inter_device}
    assert {a["channel"] for *_, a in xfer} == inter


def test_spans_nest(traced):
    _, _, _, spans = traced
    execute_ = _named(spans, "exec.execute")
    sweeps, fires = _named(spans, "exec.sweep"), _named(spans, "exec.fire")
    for name in ("exec.state", "exec.sweep", "exec.finalize", "exec.report"):
        assert all(_inside(sp, execute_) for sp in _named(spans, name)), name
    assert all(_inside(sp, sweeps) for sp in fires)
    for name in ("exec.dispatch", "exec.xfer"):
        assert all(_inside(sp, fires) for sp in _named(spans, name)), name
    dispatches = _named(spans, "exec.dispatch")
    for fire in fires:
        assert sum(_inside(sp, [fire]) for sp in dispatches) == 1
    # The run waits once, after the last firing and before the report.
    block, = _named(spans, "exec.block")
    assert _inside(block, execute_) and not _inside(block, sweeps)
    assert max(sp[1] for sp in fires) <= block[0]
    (r0, *_), = _named(spans, "exec.report")
    assert block[1] <= r0


def test_no_dispatch_inside_a_block(traced):
    _, _, _, spans = traced
    blocks = _named(spans, "exec.block")
    assert not any(_inside(sp, blocks)
                   for sp in _named(spans, "exec.dispatch"))


def test_dispatch_queued_argument(traced):
    design, _, result, spans = traced
    queued = [a["queued"] for *_, a in _named(spans, "exec.dispatch")]
    assert set(queued) <= {0, 1}
    assert sum(queued) == result.report.queued_firings


def test_outputs_bit_identical_under_the_profiler(traced):
    _, plain, result, _ = traced
    np.testing.assert_array_equal(np.asarray(result.outputs),
                                  np.asarray(plain.outputs))
    assert result.report.task_devices == plain.report.task_devices
