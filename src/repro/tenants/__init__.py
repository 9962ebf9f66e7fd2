"""repro.tenants — multi-tenant SLO serving over one shared fabric.

The ROADMAP's endgame scenario: N independent ``CompiledDesign``s admitted
as tenants onto ONE physical cluster, sharing one
:class:`~repro.net.transport.FabricTransport` (weighted-fair flow
arbitration, exact per-tenant byte accounting) and optionally one
:class:`~repro.mem.banks.MemorySystem`, fronted by an admission/SLO
scheduler and driven by open-loop traffic.

    from repro.tenants import SLO, Tenant, TenantServer, DeviceKill

    server = TenantServer(fabric, [
        Tenant("a", design_a, device_map=[0, 2], slo=SLO(1e-3, weight=2)),
        Tenant("b", design_b, device_map=[0, 1], slo=SLO(1e-3, weight=1)),
    ])
    out = server.run(faults=[DeviceKill(device=2, sweep=40)])
    out.conservation            # Σ per-tenant link bytes == totals, exact
    out.record("b").result      # bit-identical to b's solo run

:mod:`~repro.tenants.server` co-executes real designs flit by flit and
*asserts* the substrate's properties (bit-identity with solo runs, exact
conservation, fault drain without collateral damage);
:mod:`~repro.tenants.traffic` generates open-loop request streams and
:mod:`~repro.tenants.slo` holds the admission controller.

``python -m repro.tenants.smoke`` is the CI entry point: 2 tenants on 4
emulated devices, one injected device kill, re-admission on survivors.
"""
from .recover import (RecoveryPlan, plan_recovery, recompile,
                      shrink_cluster)
from .server import (DeviceKill, FlowMemory, FlowTransport, ServeOutcome,
                     Tenant, TenantRecord, TenantServer, bit_identical)
from .slo import ADMIT, QUEUE, REJECT, SLO, AdmissionController
from .traffic import Request, TrafficConfig, generate, merge, offered_load

__all__ = [
    "ADMIT", "AdmissionController", "DeviceKill", "FlowMemory",
    "FlowTransport", "QUEUE", "REJECT", "Request", "SLO", "ServeOutcome",
    "RecoveryPlan", "Tenant", "TenantRecord", "TenantServer",
    "bit_identical", "generate", "merge", "offered_load", "plan_recovery",
    "recompile", "shrink_cluster",
]
