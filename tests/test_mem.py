"""repro.mem — HBM banks, async memory channels, memory feedback.

Covers the bank model's acceptance criteria: all four memory-bound apps
(axpy / dot / gemv / axpydot) are bit-identical through the bank-modeled
path to both the ideal path and the monolithic Pallas reference; the bank
accounting conserves bytes exactly; the measured and projected
MemContentionReports agree on an uncontended config and diverge in the
documented offered-vs-achieved way on a hot bank; and the memory_feedback
pass re-maps (stage 1) or re-partitions with the ``hbm_bank_frac``
capacity (stage 2, ``-membound`` method tag).
"""
import jax.numpy as jnp
import pytest

from repro.apps import APPS
from repro.compiler import CompileOptions, compile as tapa_compile
from repro.core import ResourceProfile, Task, TaskGraph, fpga_ring_cluster
from repro.exec import ProgramBinding, bind_programs, execute
from repro.mem import (AsyncMemChannel, MemConfig, MemorySystem,
                       default_bank_map, measure, project,
                       rebalance_bank_map)


# ---------------------------------------------------------------------------
# Bank mechanics: bursts, budgets, fairness, exact conservation.
# ---------------------------------------------------------------------------

def test_burst_math_and_budget_floor():
    cfg = MemConfig(burst_bytes=512)
    assert cfg.bursts_for(1) == 1
    assert cfg.bursts_for(512) == 1
    assert cfg.bursts_for(513) == 2
    # A bank too slow for even one burst per sweep still gets the floor.
    slow = MemConfig(bank_bandwidth_Bps=1.0, burst_bytes=512)
    assert slow.budget_bursts() == 1


def _drain_memsys(memsys, channels=None, start=0):
    """Step until idle, routing completions back to their channels."""
    sweep = start
    while memsys.active:
        for rid, ci in memsys.step(sweep):
            if channels is not None:
                channels[ci].on_complete(rid, sweep)
        sweep += 1
        assert sweep < 10_000, "memory system failed to make progress"
    return sweep


def test_bank_byte_conservation_is_exact():
    """Odd request sizes: the last burst carries the exact remainder."""
    # 64 B/sweep at the 1 µs base → budget of 1 burst per sweep.
    cfg = MemConfig(banks_per_device=2, bank_bandwidth_Bps=64e6,
                    credits=4, burst_bytes=64)
    ms = MemorySystem(2, cfg)
    sizes = [(0, 0, 0, 1234), (1, 0, 1, 999), (2, 1, 0, 100), (3, 1, 1, 65)]
    for ch, dev, bank, n in sizes:
        ms.submit(ch, dev, bank, n, 0)
    _drain_memsys(ms)
    assert ms.total_served_bytes == ms.total_requested_bytes == \
        sum(n for _, _, _, n in sizes)
    assert sum(c.bytes for c in ms.counters) == ms.total_served_bytes
    assert sum(c.bursts for c in ms.counters) == \
        sum(cfg.bursts_for(n) for _, _, _, n in sizes)
    for bid in range(4):
        assert ms.utilization(bid) <= 1.0


def test_contended_bank_shares_fairly():
    """Two channels on one bank genuinely halve each other's throughput,
    and neither starves (round-robin, one burst per channel per lap)."""
    cfg = MemConfig(banks_per_device=1, bank_bandwidth_Bps=64e6,
                    credits=8, burst_bytes=64)
    solo = MemorySystem(1, cfg)
    solo.submit(0, 0, 0, 8 * 64, 0)
    solo_sweeps = _drain_memsys(solo)

    both = MemorySystem(1, cfg)
    both.submit(0, 0, 0, 8 * 64, 0)
    both.submit(1, 0, 0, 8 * 64, 0)
    done = []
    sweep = 0
    while both.active:
        done.extend(both.step(sweep))
        sweep += 1
    assert sweep >= 2 * solo_sweeps - 1          # bandwidth genuinely shared
    # Both complete within one sweep of each other.
    assert both.counters[0].saturated_sweeps > 0
    assert {ci for _, ci in done} == {0, 1}


# ---------------------------------------------------------------------------
# Async memory channels: credits, reorder window, FIFO responses.
# ---------------------------------------------------------------------------

def _tokens(n, elems=16):
    # elems float32 lanes = 4 × elems bytes per token.
    return [jnp.full((elems,), float(i)) for i in range(n)]


def test_ideal_channel_is_immediate_fifo():
    toks = _tokens(4)
    ch = AsyncMemChannel(0, "t", "x", toks, 4, device=0, bank=0, memsys=None)
    out = []
    for sweep in range(4):
        ch.pump(sweep)
        assert ch.response_ready(sweep)
        out.append(ch.consume(sweep))
    assert ch.total_bursts() == 0                # ideal path: no bank bursts
    assert ch.stats.issued == ch.stats.consumed == 4
    assert ch.stats.delivered_bytes == ch.stats.requested_bytes
    for got, want in zip(out, toks):
        assert bool(jnp.all(got == want))


def test_banked_channel_credits_bound_outstanding():
    cfg = MemConfig(banks_per_device=1, bank_bandwidth_Bps=64e6,
                    credits=2, burst_bytes=64)
    ms = MemorySystem(1, cfg)
    toks = _tokens(6)
    ch = AsyncMemChannel(0, "t", "x", toks, 6, device=0, bank=0, memsys=ms)
    out, sweep = [], 0
    while ch.stats.consumed < ch.count:
        ch.pump(sweep)
        assert ch.outstanding <= cfg.credits
        if ch.response_ready(sweep):
            out.append(ch.consume(sweep))
        for rid, ci in ms.step(sweep):
            ch.on_complete(rid, sweep)
        sweep += 1
        assert sweep < 1000
    # More firings than credits: the pump must have hit request_full.
    assert ch.stats.blocked_issues > 0
    assert ch.stats.max_outstanding == cfg.credits
    assert ch.stats.response_waits > 0           # vis = completion sweep + 1
    assert ch.stats.delivered_bytes == ch.stats.requested_bytes
    # Responses consumed in issue order — bit-exact FIFO.
    for got, want in zip(out, toks):
        assert bool(jnp.all(got == want))


def test_channel_rejects_short_token_list():
    with pytest.raises(ValueError, match="2 tokens < 3 firings"):
        AsyncMemChannel(0, "t", "x", _tokens(2), 3, device=0, bank=0)


# ---------------------------------------------------------------------------
# Bank maps: declared pins, round-robin default, LPT rebalance.
# ---------------------------------------------------------------------------

def _readers_graph(loads, pins=None):
    g = TaskGraph("readers")
    for i, b in enumerate(loads):
        meta = {"hbm_bank": pins[i]} if pins else {}
        g.add_task(Task(f"r{i}", ResourceProfile({"LUT": 1000.0}),
                        hbm_bytes=b, meta=meta))
    g.add_task(Task("sink", ResourceProfile({"LUT": 1000.0})))
    for i in range(len(loads)):
        g.add_channel(f"r{i}", "sink", 32, bytes_per_step=4.0)
    return g


def test_default_bank_map_pins_and_round_robin():
    cfg = MemConfig(banks_per_device=2)
    g = _readers_graph([100, 100, 100], pins=[5, None, None])
    g.tasks["r1"].meta.pop("hbm_bank", None)
    g.tasks["r2"].meta.pop("hbm_bank", None)
    asg = {n: 0 for n in g.tasks}
    m = default_bank_map(g, asg, cfg)
    assert m["r0"] == 5 % 2                      # declared pin, mod banks
    assert m["r1"] == 0 and m["r2"] == 1         # round-robin in graph order
    assert "sink" not in m                       # hbm_bytes == 0: no bank


def test_rebalance_overrides_pins_with_lpt():
    cfg = MemConfig(banks_per_device=2, bank_bandwidth_Bps=1e9)
    g = _readers_graph([800.0, 500.0, 400.0], pins=[0, 0, 0])
    asg = {n: 0 for n in g.tasks}
    pinned = project(g, asg, cfg)                # all on bank 0
    m = rebalance_bank_map(g, asg, cfg)
    spread = project(g, asg, cfg, bank_map=m)
    assert m["r0"] != m["r1"]                    # heaviest two split
    assert spread.max_utilization < pinned.max_utilization
    # LPT: 800 alone, 500+400 together — the best 2-bank makespan.
    assert spread.bank(0, m["r0"]).bytes == 800.0


# ---------------------------------------------------------------------------
# Differential: measured vs projected MemContentionReport.
# ---------------------------------------------------------------------------

def _two_reader_binding(g, iters=3, elems=32):
    # One 128-byte token per firing — exactly each task's hbm_bytes.
    toks = {n: [jnp.full((elems,), float(10 * i + t))
                for t in range(iters)]
            for i, n in enumerate(("r0", "r1"))}
    return ProgramBinding(
        graph=g, iterations=iters,
        programs={"r0": lambda i: i["x"], "r1": lambda i: i["x"],
                  "sink": lambda i: i["r0"] + i["r1"]},
        mem_reads={"r0": {"x": toks["r0"]}, "r1": {"x": toks["r1"]}},
        finalize=lambda s: jnp.stack(s["sink"]),
        reference=lambda: jnp.stack([toks["r0"][t] + toks["r1"][t]
                                     for t in range(iters)]),
        atol=0.0)


def _compile_readers(g, config, feedback=True):
    passes = ["normalize_units", "partition"]
    if feedback:
        passes.append("memory_feedback")
    passes += ["pipeline_interconnect", "schedule"]
    return tapa_compile(g, fpga_ring_cluster(1), CompileOptions(
        balance_kind="LUT", balance_tol=2.0, mem=config,
        passes=tuple(passes)))


def test_uncontended_measured_agrees_with_projection():
    """One reader per bank, service ≥ demand: per-bank measured bytes are
    exactly the projected per-step bytes × iterations, nothing saturates,
    and neither report flags a hotspot."""
    cfg = MemConfig(banks_per_device=2, bank_bandwidth_Bps=256e6,
                    credits=2, burst_bytes=64)   # 256 B/step ≥ 128 B demand
    g = _readers_graph([128.0, 128.0])
    design = _compile_readers(g, cfg)
    binding = _two_reader_binding(g, iters=3)
    rep = execute(design, binding).report
    assert all(rep.agreement().values()), rep.agreement()
    measured, projected = rep.mem_contention, design.mem_contention
    assert measured.kind == "measured" and projected.kind == "projected"
    bank_map = design.bank_map
    for task in ("r0", "r1"):
        b = bank_map[task]
        assert measured.bank(0, b).bytes == \
            projected.bank(0, b).bytes * rep.iterations
        assert measured.bank(0, b).saturated_sweeps == 0
    assert projected.max_utilization == pytest.approx(0.5)
    assert not measured.hotspots(0.75) and not projected.hotspots(0.75)


def test_hot_bank_diverges_offered_vs_achieved():
    """Both readers pinned to one bank, demand 4× service: the projection
    reports *offered* load (> 1, the slowdown factor) while the measured
    utilization is *achieved* throughput (≤ 1) with saturation counted —
    the documented way the two reports are allowed to diverge."""
    cfg = MemConfig(banks_per_device=2, bank_bandwidth_Bps=64e6,
                    credits=2, burst_bytes=64)   # 64 B/step vs 256 B demand
    g = _readers_graph([128.0, 128.0], pins=[0, 0])
    # No memory_feedback: keep the declared pins (the hot configuration).
    design = _compile_readers(g, cfg, feedback=False)
    binding = _two_reader_binding(g, iters=3)
    result = execute(design, binding)
    rep = result.report
    assert bool(jnp.all(result.outputs == binding.reference()))
    assert all(rep.agreement().values()), rep.agreement()
    projected = project(g, {n: 0 for n in g.tasks}, cfg)
    measured = rep.mem_contention
    assert projected.bank(0, 0).utilization == pytest.approx(4.0)
    assert measured.max_utilization <= 1.0 + 1e-12
    assert measured.bank(0, 0).saturated_sweeps > 0
    assert measured.bank(0, 1).bytes == 0        # the other bank idles
    assert sum(rep.task_mem_waits.values()) > 0       # pipeline genuinely stalled
    # Both reports still account the same total traffic per step vs run.
    assert measured.total_bytes == projected.total_bytes * rep.iterations


# ---------------------------------------------------------------------------
# memory_feedback: stage-1 re-map and stage-2 membound repartition.
# ---------------------------------------------------------------------------

def _assert_hot_bank_remapped(cfg):
    """One reader per bank, each demanding 80% of a bank's per-sweep
    service, all pinned to bank 0: the re-map must spread them one per
    bank, cutting max projected utilization by the reader count."""
    n = cfg.banks_per_device
    per = 0.8 * cfg.bank_bandwidth_Bps * cfg.sweep_time_s
    g = _readers_graph([per] * n, pins=[0] * n)
    design = _compile_readers(g, cfg)
    d = design.pass_record("memory_feedback").detail
    assert d["remapped"] and not d["repartitioned"]
    assert d["max_utilization_before"] == pytest.approx(0.8 * n)
    assert d["max_utilization_after"] == pytest.approx(0.8)
    assert len({design.bank_map[f"r{i}"] for i in range(n)}) == n


def test_memory_feedback_remaps_hot_bank():
    _assert_hot_bank_remapped(MemConfig(banks_per_device=2,
                                        bank_bandwidth_Bps=1e9))


def test_memory_feedback_spreads_sixteen_readers_off_one_bank():
    """16 readers on bank 0 project to 12.8x overload; spread, 0.8 each."""
    _assert_hot_bank_remapped(MemConfig(banks_per_device=16,
                                        bank_bandwidth_Bps=1e9, credits=8,
                                        burst_bytes=512))


def test_membound_repartition_splits_device_aggregate():
    """One bank per device: no re-map can cool a device holding both hot
    readers — the stage-2 repartition must split them, charging bank
    bandwidth as an Eq. 1 capacity and re-tagging the method."""
    cfg = MemConfig(banks_per_device=1, bank_bandwidth_Bps=1e9)
    per = 0.9 * cfg.bank_bandwidth_Bps * cfg.sweep_time_s
    g = TaskGraph("membound")
    for n in ("h0", "h1"):
        g.add_task(Task(n, ResourceProfile({"LUT": 1000.0}), hbm_bytes=per))
    g.add_task(Task("sink", ResourceProfile({"LUT": 1000.0})))
    # Heavy h0—h1 coupling: the plain Eq. 2 objective co-locates them.
    g.add_channel("h0", "h1", 512, bytes_per_step=4096.0)
    g.add_channel("h1", "sink", 32, bytes_per_step=4.0)
    design = tapa_compile(g, fpga_ring_cluster(2), CompileOptions(
        balance_kind="LUT", balance_tol=2.0, mem=cfg,
        passes=("normalize_units", "partition", "memory_feedback")))
    d = design.pass_record("memory_feedback").detail
    assert d["repartitioned"], d
    assert design.partition.stats.method.endswith("-membound")
    a = design.partition.assignment
    assert a["h0"] != a["h1"]                    # the aggregate was split
    assert d["max_utilization_after"] == pytest.approx(0.9)
    assert d["comm_cost_after"] >= d["comm_cost_before"]  # paid in cut bytes


def test_membound_gives_up_when_one_task_outruns_a_device():
    """A single task demanding more than a whole device's banks: no
    partition can fix it — the pass must leave the partition untouched."""
    cfg = MemConfig(banks_per_device=1, bank_bandwidth_Bps=1e9)
    per = 3.0 * cfg.bank_bandwidth_Bps * cfg.sweep_time_s
    g = _readers_graph([per])
    design = tapa_compile(g, fpga_ring_cluster(2), CompileOptions(
        balance_kind="LUT", balance_tol=2.0, mem=cfg,
        passes=("normalize_units", "partition", "memory_feedback")))
    d = design.pass_record("memory_feedback").detail
    assert not d["repartitioned"]
    assert not design.partition.stats.method.endswith("-membound")
    assert d["max_utilization_after"] == pytest.approx(3.0)


def test_compile_inserts_memory_feedback_with_default_passes():
    cfg = MemConfig(banks_per_device=4, bank_bandwidth_Bps=2e9,
                    credits=4, burst_bytes=512)
    g = APPS["axpy"].build_graph(2)
    design = tapa_compile(g, fpga_ring_cluster(2), CompileOptions(
        balance_kind="LUT", balance_tol=0.8, exact_limit=1500,
        floorplan_devices=None, mem=cfg))
    names = [r.name for r in design.pass_records]
    assert "memory_feedback" in names
    assert names.index("memory_feedback") > names.index("partition")
    assert design.bank_map is not None
    assert design.summary()["mem"]["banks_per_device"] == 4


# ---------------------------------------------------------------------------
# The four memory-bound apps: bit-identical through the bank model.
# ---------------------------------------------------------------------------

_MEM_CFG = MemConfig(banks_per_device=4, bank_bandwidth_Bps=2e9,
                     credits=4, burst_bytes=512)
_MEM_OPTS = CompileOptions(
    balance_kind="LUT", balance_tol=0.8, exact_limit=1500,
    floorplan_devices=None, mem=_MEM_CFG,
    passes=("normalize_units", "partition", "memory_feedback",
            "pipeline_interconnect", "schedule"))


@pytest.mark.parametrize("app,ndev", [
    pytest.param("axpy", 2, id="axpy"),
    pytest.param("dot", 2, id="dot"),
    pytest.param("gemv", 2, id="gemv"),
    pytest.param("axpydot", 2, id="axpydot"),
    pytest.param("axpy", 4, id="axpy-4dev"),
    pytest.param("axpydot", 4, id="axpydot-4dev"),
])
def test_apps_bit_identical_through_banks(app, ndev):
    graph = APPS[app].build_graph(ndev)
    design = tapa_compile(graph, fpga_ring_cluster(ndev), _MEM_OPTS)
    binding = bind_programs(graph)
    banked = execute(design, binding)
    ideal = execute(design, bind_programs(graph), mem=None)
    assert bool(jnp.all(banked.outputs == ideal.outputs)), \
        f"{app}: bank model changed numerics"
    assert bool(jnp.all(banked.outputs == binding.reference())), \
        f"{app}: diverged from the Pallas reference (atol is 0.0)"
    rep = banked.report
    agree = rep.agreement()
    assert all(agree.values()), (app, agree)
    assert agree["mem_delivery_match"] and agree["bank_conservation"]
    assert int(rep.mem_bank_bytes) == rep.mem_delivered_bytes > 0
    assert rep.mem_contention.max_utilization <= 1.0 + 1e-12
    # The bank path costs real sweeps; the ideal path never waits on memory.
    assert rep.sweeps > ideal.report.sweeps
    assert sum(rep.task_mem_waits.values()) > 0
    assert not ideal.report.mem_channels or \
        sum(ideal.report.task_mem_waits.values()) == 0


def test_mem_reads_binding_validation():
    g = _readers_graph([64.0, 64.0])
    good = _two_reader_binding(g)
    good.validate()
    with pytest.raises(ValueError, match="unknown task"):
        ProgramBinding(
            graph=g, iterations=1,
            programs=dict(good.programs),
            mem_reads={"r0": {"x": _tokens(1)},
                       "r1": {"x": _tokens(1)},
                       "ghost": {"x": _tokens(1)}}).validate()
    # A memory stream may not shadow a predecessor channel's token name.
    with pytest.raises(ValueError, match="shadow"):
        ProgramBinding(
            graph=g, iterations=1,
            programs=dict(good.programs),
            mem_reads={"r0": {"x": _tokens(1)},
                       "r1": {"x": _tokens(1)},
                       "sink": {"r0": _tokens(1)}}).validate()
