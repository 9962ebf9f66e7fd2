"""repro.tenants — multi-tenant serving over one shared fabric.

Covers the tentpole's acceptance criteria: two tenants co-running over one
shared ``FabricTransport`` stay bit-identical to their solo runs with
exact per-tenant link-byte conservation; a device kill mid-flight drains
the victim without perturbing the survivor, and the victim re-admits onto
its surviving devices after a re-compile.  Plus the traffic generator's
determinism and the admission controller's admit/queue/reject +
priority-aging semantics.
"""
import dataclasses

import numpy as np
import pytest

from repro.apps import APPS
from repro.compiler import CompileOptions, compile as tapa_compile
from repro.core import Bus, DaisyChain, Ring, fpga_ring_cluster
from repro.core.topology import Cluster, ALVEO_U55C
from repro.exec import bind_programs, execute
from repro.net import cluster_fabric
from repro.net.transport import NetConfig
from repro.tenants import (ADMIT, QUEUE, REJECT, SLO, AdmissionController,
                           DeviceKill, RecoveryPlan, Tenant, TenantServer,
                           TrafficConfig, bit_identical, generate, merge,
                           offered_load, plan_recovery, recompile,
                           shrink_cluster)

# ---------------------------------------------------------------------------
# Traffic: seeded, open-loop, deterministic.
# ---------------------------------------------------------------------------

_TRAFFIC = TrafficConfig(rate_rps=200.0, mean_size=4096.0, duration_s=2.0)


def test_traffic_is_deterministic_per_seed_and_tenant():
    a1 = generate(_TRAFFIC, 0, np.random.default_rng([7, 0]))
    a2 = generate(_TRAFFIC, 0, np.random.default_rng([7, 0]))
    b = generate(_TRAFFIC, 1, np.random.default_rng([7, 1]))
    assert a1 == a2
    assert a1 != b
    assert all(r.tenant == 0 for r in a1)
    assert all(r.size > 0 for r in a1)
    arr = [r.t_arrival for r in a1]
    assert arr == sorted(arr) and arr[-1] <= _TRAFFIC.duration_s


def test_traffic_rate_and_mean_size_are_calibrated():
    cfg = dataclasses.replace(_TRAFFIC, duration_s=50.0)
    reqs = generate(cfg, 0, np.random.default_rng([3, 0]))
    rate = len(reqs) / cfg.duration_s
    assert rate == pytest.approx(cfg.rate_rps, rel=0.1)
    mean = np.mean([r.size for r in reqs])
    assert mean == pytest.approx(cfg.mean_size, rel=0.2)
    assert offered_load(reqs, cfg.duration_s) == pytest.approx(
        cfg.rate_rps * cfg.mean_size, rel=0.25)
    assert offered_load([], 0.0) == 0.0


def test_traffic_scaled_and_merge():
    doubled = _TRAFFIC.scaled(2.0)
    assert doubled.rate_rps == 2 * _TRAFFIC.rate_rps
    a = generate(_TRAFFIC, 0, np.random.default_rng([1, 0]))
    b = generate(_TRAFFIC, 1, np.random.default_rng([1, 1]))
    m = merge([a, b])
    assert len(m) == len(a) + len(b)
    assert [r.t_arrival for r in m] == sorted(r.t_arrival for r in m)


def test_profiles_modulate_the_rate():
    diurnal = dataclasses.replace(_TRAFFIC, profile="diurnal", swing=0.5,
                                  period_s=10.0)
    assert diurnal.rate_at(2.5) > diurnal.rate_at(0.0) > diurnal.rate_at(7.5)
    ramp = dataclasses.replace(_TRAFFIC, profile="ramp", swing=0.9,
                               duration_s=20.0)
    assert ramp.rate_at(20.0) > ramp.rate_at(0.0)
    # A steep ramp skews the stream late: its median arrival lands well
    # past the flat stream's mid-horizon median.
    flat = dataclasses.replace(_TRAFFIC, duration_s=20.0)
    mf = np.median([r.t_arrival
                    for r in generate(flat, 0, np.random.default_rng([5, 0]))])
    mr = np.median([r.t_arrival
                    for r in generate(ramp, 0, np.random.default_rng([5, 0]))])
    assert mr > mf + 2.0


# ---------------------------------------------------------------------------
# Admission: admit / queue / reject + deadline-aware priority aging.
# ---------------------------------------------------------------------------

def _req(rid, tenant, t, size=1000.0):
    from repro.tenants import Request
    return Request(rid=rid, tenant=tenant, t_arrival=t, size=size)


def test_admission_three_way_call():
    slo = SLO(target_latency_s=1.0, max_inflight=1, deadline_factor=3.0)
    ctrl = AdmissionController({0: slo}, {0: 1000.0})  # 1 req/s of work
    assert ctrl.offer(_req(0, 0, 0.0), 0.0) == ADMIT
    # One second of backlog ahead: finishes at ~2.1s, inside the 3.1s
    # deadline — but the single service slot is taken, so it queues.
    assert ctrl.offer(_req(1, 0, 0.1), 0.1) == QUEUE
    # Three more seconds of work could only finish at ~5.2s > 3.2s.
    assert ctrl.offer(_req(2, 0, 0.2, size=3000.0), 0.2) == REJECT
    assert ctrl.stats[0].admitted == 1
    assert ctrl.stats[0].queued == 1
    assert ctrl.stats[0].rejected == 1


def test_priority_aging_prefers_tight_slo():
    tight = SLO(target_latency_s=0.1, max_inflight=1, deadline_factor=40.0)
    loose = SLO(target_latency_s=10.0, max_inflight=1, deadline_factor=4.0)
    ctrl = AdmissionController({0: loose, 1: tight},
                              {0: 1e6, 1: 1e6})
    assert ctrl.offer(_req(0, 0, 0.0), 0.0) == ADMIT
    assert ctrl.offer(_req(1, 1, 0.0), 0.0) == ADMIT
    # The loose request has waited longer in wall time...
    assert ctrl.offer(_req(2, 0, 0.1), 0.1) == QUEUE
    assert ctrl.offer(_req(3, 1, 0.3), 0.3) == QUEUE
    ctrl.complete(_req(0, 0, 0.0))
    ctrl.complete(_req(1, 1, 0.0))
    # ...but age normalized by target ranks the tight one far ahead.
    first = ctrl.release(1.0)
    assert first.tenant == 1 and first.rid == 3
    second = ctrl.release(1.0)
    assert second.tenant == 0 and second.rid == 2


def test_expired_pending_is_shed_as_rejected():
    slo = SLO(target_latency_s=0.1, max_inflight=1, deadline_factor=2.0)
    ctrl = AdmissionController({0: slo}, {0: 1e9})
    assert ctrl.offer(_req(0, 0, 0.0), 0.0) == ADMIT
    assert ctrl.offer(_req(1, 0, 0.0), 0.0) == QUEUE
    ctrl.complete(_req(0, 0, 0.0))
    assert ctrl.release(10.0) is None          # deadline long gone
    assert ctrl.stats[0].rejected == 1
    assert ctrl.pending == 0


# ---------------------------------------------------------------------------
# Recovery: cluster shrink + full re-compile.
# ---------------------------------------------------------------------------

def test_shrink_cluster_topology_families():
    ring = fpga_ring_cluster(4)
    assert isinstance(shrink_cluster(ring, 3).topology, Ring)
    assert isinstance(shrink_cluster(ring, 2).topology, DaisyChain)
    bus = Cluster(ALVEO_U55C, Bus(4))
    shrunk = shrink_cluster(bus, 3)
    assert isinstance(shrunk.topology, Bus)
    assert shrunk.topology.num_devices == 3
    grouped = fpga_ring_cluster(4, devices_per_node=2)
    assert shrink_cluster(grouped, 2).devices_per_node is None


# ---------------------------------------------------------------------------
# The tenant server: shared-substrate co-execution (the acceptance tests).
# ---------------------------------------------------------------------------

_OPTS = CompileOptions(balance_kind="LUT", balance_tol=0.8,
                       exact_limit=1500, floorplan_devices=(0,))
_SPECS = {"a": {"seed": 0}, "b": {"seed": 7}}


@pytest.fixture(scope="module")
def compiled():
    graphs = {n: APPS["stencil"].build_graph(2) for n in _SPECS}
    designs = {n: tapa_compile(graphs[n], fpga_ring_cluster(2), _OPTS)
               for n in _SPECS}
    solo = {n: execute(designs[n], bind_programs(graphs[n], _SPECS[n]),
                       fabric=None) for n in _SPECS}
    return graphs, designs, solo


def _tenants(designs):
    return [
        Tenant("a", designs["a"], device_map=[0, 2],
               slo=SLO(1e-3, weight=2.0), inputs=_SPECS["a"]),
        Tenant("b", designs["b"], device_map=[0, 1],
               slo=SLO(1e-3, weight=1.0), inputs=_SPECS["b"]),
    ]


def test_corun_is_bit_identical_with_exact_conservation(compiled):
    _, designs, solo = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    server = TenantServer(fabric, _tenants(designs))
    out = server.run()
    for n in _SPECS:
        rec = out.record(n)
        assert rec.status == "done"
        assert bit_identical(rec.result.outputs, solo[n].outputs), n
        assert all(rec.result.report.agreement().values()), n
    # Both tenants crossed the shared 0->1 link, and every link's per-flow
    # buckets sum to its total (asserted again inside conservation()).
    assert any(len(c.flow_bytes) >= 2 for c in server.transport.counters)
    cons = out.conservation
    assert cons["exact"]
    assert sum(cons["per_tenant_link_bytes"].values()) \
        == cons["total_link_bytes"]
    assert all(b > 0 for b in cons["per_tenant_link_bytes"].values())
    # Per-tenant congestion reports are scoped to each flow's bytes.
    for n in _SPECS:
        cong = out.record(n).result.report.congestion
        assert sum(l.bytes for l in cong.links) \
            == cons["per_tenant_link_bytes"][n]
        assert cong.kind.endswith(f"flow{out.record(n).flow}")


def test_device_kill_drains_readmits_and_spares_the_peer(compiled):
    graphs, designs, solo = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    server = TenantServer(fabric, _tenants(designs))
    out = server.run(faults=[DeviceKill(device=2, sweep=2)])
    killed = out.record("a")
    assert killed.status == "killed" and killed.killed_at == 2
    recovered = out.record("a+recovered")
    assert recovered.status == "done"
    assert recovered.flow != killed.flow        # fresh incarnation id
    peer = out.record("b")
    assert peer.status == "done"
    assert bit_identical(peer.result.outputs, solo["b"].outputs)
    binding = bind_programs(graphs["a"], _SPECS["a"])
    ref = np.asarray(binding.reference())
    got = np.asarray(recovered.result.outputs)
    assert np.max(np.abs(got - ref)) <= binding.atol
    assert out.conservation["exact"]


def test_kill_without_readmit_leaves_victim_dead(compiled):
    _, designs, _ = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    server = TenantServer(fabric, _tenants(designs))
    out = server.run(faults=[DeviceKill(device=2, sweep=2, readmit=False)])
    assert out.record("a").status == "killed"
    assert out.record("a").recovered_as is None
    assert out.record("b").status == "done"
    with pytest.raises(KeyError):
        out.record("a+recovered")


def test_recompile_survivor_design_is_first_class(compiled):
    _, designs, _ = compiled
    degraded = recompile(designs["a"], 1)
    assert degraded.cluster.topology.num_devices == 1
    assert degraded.partition is not None
    assert set(degraded.partition.assignment.values()) == {0}
    assert degraded.options.fabric is None


def test_duplicate_tenant_names_rejected(compiled):
    _, designs, _ = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    tenants = _tenants(designs)
    tenants[1] = dataclasses.replace(tenants[1], name="a")
    with pytest.raises(ValueError):
        TenantServer(fabric, tenants)


def test_solo_tenant_matches_solo_execution(compiled):
    """One tenant through the server == the plain executor (flow machinery
    is invisible when nobody shares)."""
    _, designs, solo = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    server = TenantServer(fabric, [_tenants(designs)[0]],
                          net_config=NetConfig())
    out = server.run()
    rec = out.record("a")
    assert rec.status == "done"
    assert bit_identical(rec.result.outputs, solo["a"].outputs)
    assert out.conservation["exact"]


# ---------------------------------------------------------------------------
# Recovery planning: restore-over-recompile + the kill edge cases.
# ---------------------------------------------------------------------------

def test_plan_recovery_prefers_restore_when_cluster_survives(tmp_path):
    # No snapshot yet: recompile onto the survivors.
    plan = plan_recovery([0, 2], [], checkpoint_dir=str(tmp_path))
    assert plan.action == "recompile" and plan.ndev == 2
    # A published snapshot + intact placement: restore from the barrier.
    (tmp_path / "step_4").mkdir()
    plan = plan_recovery([0, 2], [], checkpoint_dir=str(tmp_path))
    assert isinstance(plan, RecoveryPlan)
    assert plan.action == "restore" and plan.step == 4 and plan.ndev == 2
    # A permanently dead placement device disqualifies the snapshot.
    plan = plan_recovery([0, 2], [2], checkpoint_dir=str(tmp_path))
    assert plan.action == "recompile" and plan.ndev == 1
    # Nothing survives: the plan says decline (ndev 0), never restore.
    plan = plan_recovery([2], [2], checkpoint_dir=str(tmp_path))
    assert plan.action == "recompile" and plan.ndev == 0


def test_transient_kill_restores_from_barrier(compiled, tmp_path):
    """A transient device kill of a checkpointing tenant restores the SAME
    design from its last sweep barrier (recovered_via='restore') and still
    finishes bit-identical; the un-checkpointed peer is untouched."""
    _, designs, solo = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    tenants = _tenants(designs)
    tenants[0] = dataclasses.replace(tenants[0],
                                     checkpoint_dir=str(tmp_path))
    server = TenantServer(fabric, tenants)
    out = server.run(faults=[DeviceKill(device=2, sweep=4, transient=True)],
                     checkpoint_every=2)
    killed = out.record("a")
    assert killed.status == "killed" and killed.recovered_as == "a+recovered"
    rec = out.record("a+recovered")
    assert rec.status == "done"
    assert rec.recovered_via == "restore"
    assert rec.tenant.device_map == [0, 2]      # same placement, no shrink
    assert rec.tenant.design is designs["a"]    # same design, no recompile
    assert bit_identical(rec.result.outputs, solo["a"].outputs)
    assert bit_identical(out.record("b").result.outputs, solo["b"].outputs)
    assert out.conservation["exact"]


def test_permanent_kill_recompiles_and_labels_it(compiled, tmp_path):
    """Snapshots exist, but the device is permanently gone: the snapshot's
    cluster no longer exists, so recovery recompiles onto survivors."""
    _, designs, _ = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    tenants = _tenants(designs)
    tenants[0] = dataclasses.replace(tenants[0],
                                     checkpoint_dir=str(tmp_path))
    server = TenantServer(fabric, tenants)
    out = server.run(faults=[DeviceKill(device=2, sweep=4)],
                     checkpoint_every=2)
    rec = out.record("a+recovered")
    assert rec.status == "done"
    assert rec.recovered_via == "recompile"
    assert rec.tenant.device_map == [0]
    assert rec.tenant.checkpoint_dir is None    # old snapshots unusable


def test_kill_that_leaves_no_survivors_declines_gracefully(compiled):
    """A kill wiping a tenant's whole placement cannot recompile onto
    anything: recovery raises the named DeadlockError instead of
    admitting a zero-device design or hanging."""
    from repro.exec.executor import DeadlockError
    _, designs, _ = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    one_dev = recompile(designs["a"], 1)
    server = TenantServer(fabric, [
        Tenant("solo", one_dev, device_map=[2], inputs=_SPECS["a"]),
    ])
    with pytest.raises(DeadlockError, match="no surviving devices"):
        server.run(faults=[DeviceKill(device=2, sweep=2)])
    # recompile itself also refuses a zero-device ask.
    with pytest.raises(ValueError):
        recompile(designs["a"], 0)


def test_double_kill_of_same_device_is_idempotent(compiled):
    """The second kill of an already-dead device finds no running victim
    on it: the first incarnation is not re-killed, the recovered one
    (living elsewhere) is untouched, everyone finishes."""
    _, designs, solo = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    server = TenantServer(fabric, _tenants(designs))
    out = server.run(faults=[DeviceKill(device=2, sweep=2),
                             DeviceKill(device=2, sweep=4)])
    killed = out.record("a")
    assert killed.status == "killed" and killed.killed_at == 2
    assert killed.recovered_as == "a+recovered"
    rec = out.record("a+recovered")
    assert rec.status == "done"
    # Exactly one recovered incarnation: the second kill was a no-op.
    assert len([r for r in out.records if r.name.startswith("a")]) == 2
    assert bit_identical(out.record("b").result.outputs, solo["b"].outputs)
    assert out.conservation["exact"]


def test_cancel_flow_twice_is_a_noop(compiled):
    """cancel_flow is idempotent: the second call finds nothing, returns
    nothing, and leaves every counter exactly where the first left it."""
    _, designs, _ = compiled
    fabric = cluster_fabric(fpga_ring_cluster(4))
    server = TenantServer(fabric, _tenants(designs))
    tr = server.transport
    # Drive a few sweeps so flow 0 has traffic in flight, then tear it
    # down twice.
    for rec in server.records:
        pass
    sweep = 0
    while not tr.active and sweep < 16:
        for rec in server.records:
            if rec.state is not None:
                rec.state.advance(sweep)
        tr.step(sweep)
        sweep += 1
    assert tr.active, "no in-flight traffic to cancel"
    first = tr.cancel_flow(0)
    snap = [(c.bytes, dict(c.flow_bytes)) for c in tr.counters]
    second = tr.cancel_flow(0)
    assert first and second == []
    assert snap == [(c.bytes, dict(c.flow_bytes)) for c in tr.counters]
    assert not tr.flow_active(0)
