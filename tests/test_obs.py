"""repro.obs — sweep-granular tracing, metrics registry, critical path.

The observability contract, asserted end to end:

* the default ``NULL_TRACER`` is a no-op and a recording ``Tracer`` is
  **transparent** — traced runs are bit-identical to untraced runs on the
  exec, net, mem, tenant and chaos paths, with identical report counters;
* summed trace-event bytes reconcile with every legacy counter exactly
  (``assert_trace_report_consistent`` / ``assert_registry_consistent``);
* the exported Chrome trace-event JSON is structurally valid;
* the critical-path decomposition sums to the measured makespan exactly.
"""
import jax.numpy as jnp
import pytest

from repro.apps import APPS
from repro.compiler import CompileOptions, compile as tapa_compile
from repro.core import ResourceProfile, Task, TaskGraph, fpga_ring_cluster
from repro.exec import ProgramBinding, bind_programs, execute
from repro.mem import MemConfig
from repro.net import cluster_fabric
from repro.obs import (EVENT_FIELDS, NULL_TRACER, CritPath, MetricsRegistry,
                       Tracer, analyze, assert_registry_consistent,
                       assert_trace_report_consistent, coerce_tracer,
                       format_table, from_report, from_trace, makespan_row,
                       to_chrome_trace, validate_chrome_trace)
from repro.tenants import SLO, Tenant, TenantServer, bit_identical


def _counters(report):
    """Every counter the tracer must not perturb."""
    return {
        "sweeps": report.sweeps,
        "congestion_waits": dict(report.task_congestion_waits),
        "mem_waits": dict(report.task_mem_waits),
        "device_fired": dict(report.device_fired),
        "retransmit_bytes": report.net_retransmit_bytes_total,
        "link_bytes": ([int(l.bytes) for l in report.congestion.links]
                       if report.congestion is not None else []),
        "channel_bytes": [c.measured_bytes for c in report.channels],
    }


# ---------------------------------------------------------------------------
# Tracer mechanics.
# ---------------------------------------------------------------------------

def test_null_tracer_is_the_disabled_default():
    assert NULL_TRACER.enabled is False
    assert coerce_tracer(None) is NULL_TRACER
    t = Tracer()
    assert coerce_tracer(t) is t
    # Every typed emit on the null tracer is a no-op.
    NULL_TRACER.task_fire(0, "t", 0, 0)
    NULL_TRACER.flit_hop(0, 0, 64, 0, 0)
    NULL_TRACER.bank_burst(0, 0, 0, 64, 0, 0)
    assert len(NULL_TRACER) == 0
    assert NULL_TRACER.link_goodput_bytes() == {}
    assert NULL_TRACER.bank_bytes() == {}


def test_typed_emits_match_their_schemas():
    t = Tracer()
    t.task_fire(3, "stage0", 1, 0)
    t.task_wait(4, "stage1", 0, "net", 0)
    t.channel_push(5, 0, "a", "b", 128, 0)
    t.flit_hop(6, 2, 64, 0, 9)
    t.bank_burst(7, 5, 0, 512, 0, 1)
    assert len(t) == 5
    for e in t.events:
        assert len(e) == 2 + len(EVENT_FIELDS[e[0]]), e
    d = t.as_dicts()
    assert d[0]["kind"] == "task_fire" and d[0]["task"] == "stage0"
    assert t.count("task_fire") == 1
    assert [e[2] for e in t.iter_kind("flit_hop")] == [2]


def test_metrics_registry_basics():
    reg = MetricsRegistry()
    reg.counter_add("x.y", 2, a="1")
    reg.counter_add("x.y", 3, a="1")
    reg.counter_add("x.y", 5, a="2")
    reg.gauge_set("g", 0.5)
    reg.observe("h", 1.0)
    reg.observe("h", 3.0)
    assert reg.value("x.y", 0, a="1") == 5
    assert reg.total("x.y") == 10
    assert reg.kind("g") == "gauge"
    h = reg.value("h", None)
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 3.0
    j = reg.to_json()
    assert j["x.y"]["type"] == "counter"


# ---------------------------------------------------------------------------
# Exec + net path: transparency, consistency, Chrome export, critpath.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fabric_run():
    cluster = fpga_ring_cluster(2)
    graph = APPS["stencil"].build_graph(2)
    design = tapa_compile(graph, cluster, CompileOptions(
        balance_kind="LUT", balance_tol=0.8, exact_limit=1500,
        fabric=cluster_fabric(cluster),
        passes=("normalize_units", "partition", "congestion_feedback",
                "pipeline_interconnect", "schedule")))
    base = execute(design, bind_programs(graph))
    tracer = Tracer()
    res = execute(design, bind_programs(graph), tracer=tracer)
    return graph, design, base, res, tracer


def test_traced_run_is_bit_identical_and_counter_identical(fabric_run):
    _, _, base, res, tracer = fabric_run
    assert bit_identical(base.outputs, res.outputs)
    assert _counters(base.report) == _counters(res.report)
    assert base.report.trace is None
    assert res.report.trace is tracer


def test_trace_and_registry_reconcile_exactly(fabric_run):
    _, _, _, res, tracer = fabric_run
    assert_trace_report_consistent(tracer, res.report)
    reg = from_report(res.report)
    assert_registry_consistent(reg, res.report)
    # The report's cached registry view is the same reconciliation.
    assert res.report.metrics is res.report.metrics       # cached
    assert_registry_consistent(res.report.metrics, res.report)
    # Trace-derived series carry the trace. prefix and agree per link.
    treg = from_trace(tracer)
    for l in res.report.congestion.links:
        assert treg.value("trace.net.link.goodput_bytes", 0,
                          link=l.index) == l.bytes


def test_chrome_trace_export_is_valid(fabric_run):
    _, _, _, _, tracer = fabric_run
    doc = to_chrome_trace(tracer)
    validate_chrome_trace(doc)
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    assert all(isinstance(e["pid"], int) and isinstance(e["tid"], int)
               for e in evs)
    assert doc["otherData"]["format"] == "repro-obs/v1"


def test_critpath_sums_to_makespan_exactly(fabric_run):
    _, design, _, res, tracer = fabric_run
    crit = analyze(tracer, sweeps=res.report.sweeps)
    assert isinstance(crit, CritPath)
    for t in crit.tasks:
        assert sum(t.buckets().values()) == res.report.sweeps, t.task
        assert t.idle >= 0
    row = makespan_row("stencil", design, res.report, crit)
    assert row["measured_sweeps"] == res.report.sweeps
    table = format_table([row])
    assert "stencil" in table and "crit task" in table


def test_empty_trace_analyzes_to_no_tasks():
    crit = analyze(Tracer(), sweeps=5)
    assert crit.tasks == [] and crit.fault_link_sweeps == {}
    with pytest.raises(ValueError):
        crit.critical()


# ---------------------------------------------------------------------------
# Mem path.
# ---------------------------------------------------------------------------

def _readers_graph():
    g = TaskGraph("obs-readers")
    for i in range(2):
        g.add_task(Task(f"r{i}", ResourceProfile({"LUT": 1000.0}),
                        hbm_bytes=128.0, meta={"hbm_bank": 0}))
    g.add_task(Task("sink", ResourceProfile({"LUT": 1000.0})))
    for i in range(2):
        g.add_channel(f"r{i}", "sink", 32, bytes_per_step=4.0)
    return g


def _readers_binding(g, iters=3, elems=32):
    toks = {n: [jnp.full((elems,), float(10 * i + t)) for t in range(iters)]
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


def test_mem_path_traced_identity_and_byte_agreement():
    cfg = MemConfig(banks_per_device=2, bank_bandwidth_Bps=64e6,
                    credits=2, burst_bytes=64)    # hot bank: genuine waits
    g = _readers_graph()
    design = tapa_compile(g, fpga_ring_cluster(1), CompileOptions(
        balance_kind="LUT", balance_tol=2.0, mem=cfg,
        passes=("normalize_units", "partition",
                "pipeline_interconnect", "schedule")))
    base = execute(design, _readers_binding(g))
    tracer = Tracer()
    res = execute(design, _readers_binding(g), tracer=tracer)
    assert bit_identical(base.outputs, res.outputs)
    assert _counters(base.report) == _counters(res.report)
    assert tracer.count("bank_burst") > 0
    assert tracer.count("mem_issue") > 0
    assert sum(res.report.task_mem_waits.values()) > 0
    assert_trace_report_consistent(tracer, res.report)
    assert_registry_consistent(from_report(res.report), res.report)
    validate_chrome_trace(to_chrome_trace(tracer))
    crit = analyze(tracer, sweeps=res.report.sweeps)
    waits = {t.task: t.memory for t in crit.tasks}
    assert waits["r0"] + waits["r1"] \
        == sum(res.report.task_mem_waits.values())


# ---------------------------------------------------------------------------
# Tenant path.
# ---------------------------------------------------------------------------

def test_tenant_server_traced_identity_and_metrics():
    opts = CompileOptions(balance_kind="LUT", balance_tol=0.8,
                          exact_limit=1500, floorplan_devices=(0,))
    specs = {"a": {"seed": 0}, "b": {"seed": 7}}
    graphs = {n: APPS["stencil"].build_graph(2) for n in specs}
    designs = {n: tapa_compile(graphs[n], fpga_ring_cluster(2), opts)
               for n in specs}

    def tenants():
        return [Tenant("a", designs["a"], device_map=[0, 2],
                       slo=SLO(1e-3, weight=2.0), inputs=specs["a"]),
                Tenant("b", designs["b"], device_map=[0, 1],
                       slo=SLO(1e-3, weight=1.0), inputs=specs["b"])]

    fabric = cluster_fabric(fpga_ring_cluster(4))
    base = TenantServer(fabric, tenants()).run()
    tracer = Tracer()
    server = TenantServer(fabric, tenants(), tracer=tracer)
    out = server.run()
    assert out.sweeps == base.sweeps
    for n in specs:
        assert bit_identical(out.record(n).result.outputs,
                             base.record(n).result.outputs), n
    assert tracer.count("tenant_admit") == 2
    validate_chrome_trace(to_chrome_trace(tracer))
    # Per-flow attribution covers both tenants with distinct flow ids.
    crit = analyze(tracer, sweeps=out.sweeps)
    assert crit.flows() == [0, 1]
    reg = server.metrics()
    assert reg.total("tenant.flow.admissions") == 2
    for rec in out.records:
        rep = rec.result.report
        assert reg.value("tenant.flow.sweeps", 0, tenant=rec.name) \
            == rep.sweeps
        assert reg.value("tenant.flow.net_bytes", 0, tenant=rec.name) \
            == sum(c.net_bytes for c in rep.channels)


def test_tenant_kill_emits_cancel_and_counts_recovery():
    from repro.tenants import DeviceKill
    opts = CompileOptions(balance_kind="LUT", balance_tol=0.8,
                          exact_limit=1500, floorplan_devices=(0,))
    g = APPS["stencil"].build_graph(2)
    design = tapa_compile(g, fpga_ring_cluster(2), opts)
    fabric = cluster_fabric(fpga_ring_cluster(4))
    tracer = Tracer()
    server = TenantServer(
        fabric, [Tenant("a", design, device_map=[0, 2],
                        slo=SLO(1e-3), inputs={"seed": 0})],
        tracer=tracer)
    out = server.run(faults=[DeviceKill(device=2, sweep=2)])
    assert out.record("a").status == "killed"
    assert tracer.count("tenant_cancel") == 1
    assert tracer.count("tenant_admit") == 2          # admit + re-admit
    reg = server.metrics()
    assert reg.total("tenant.flow.kills") == 1
    assert reg.total("tenant.flow.recompiles") == 1


# ---------------------------------------------------------------------------
# Chaos path: ARQ events and fault attribution.
# ---------------------------------------------------------------------------

def test_chaos_drop_cell_attributes_fault_sweeps():
    from repro.chaos.runner import compile_app, run_scenario
    from repro.chaos.scenario import ChaosScenario
    drop = ChaosScenario("drop-mid", drop=0.05, corrupt=0.02,
                         reorder=0.03, seed=5)
    tracer = Tracer()
    cell = run_scenario("stencil", drop, tracer=tracer)
    assert cell["ok"] and cell["bit_identical"]
    assert tracer.count("retransmit") > 0
    validate_chrome_trace(to_chrome_trace(tracer))
    crit = analyze(tracer, sweeps=cell["sweeps"])
    faulted = {e[2] for e in tracer.iter_kind("retransmit")}
    assert any(crit.fault_link_sweeps.get(li, 0) >= 1 for li in faulted)
    assert sum(t.fault for t in crit.tasks) >= 1
    # The traced faulted run still reconciles byte-exactly.
    _, design = compile_app("stencil", 4)
    from repro.chaos.runner import _execute as chaos_execute
    g, design = compile_app("stencil", 4)
    tr2 = Tracer()
    res = chaos_execute(g, design, faults=drop.fault_model(), tracer=tr2)
    assert_trace_report_consistent(tr2, res.report)

