"""Multi-tenant serving smoke run (CI): 2 tenants, 1 shared ring, 1 kill.

Two independently compiled stencil designs are admitted as tenants onto ONE
shared 4-device ring fabric (the paper's testbed shape) with weighted-fair
link arbitration, placed so their routes genuinely contend for a link:

* tenant ``a`` (weight 2) maps its 2 logical devices to fabric ``[0, 2]``
  (route 0→1→2 under deterministic BFS);
* tenant ``b`` (weight 1) maps to fabric ``[0, 1]`` (route 0→1) — both
  tenants cross link 0→1.

The run asserts the tentpole's acceptance criteria end to end:

* **isolation** — each tenant's outputs are bit-identical to its solo run
  on the ideal path (sharing the substrate never touches payloads);
* **conservation** — Σ per-tenant link bytes == total link bytes, exact
  integers per link (checked inside ``TenantServer.conservation``);
* **fault drain** — a second serve kills fabric device 2 mid-flight:
  tenant ``a`` is torn down (its in-network flits cancelled, credits
  released), re-compiled onto its surviving device and re-admitted under a
  fresh flow id, and finishes there; tenant ``b`` is bit-identical to its
  solo run anyway;
* **attribution** — the per-tenant cost ledger
  (:func:`repro.obs.build_ledger`) sums bit-exactly to the global
  counters on both runs, the kill charges tenant ``a``'s lineage
  (cancelled bytes + restore sweeps) while its peer pays exactly zero,
  and an online :class:`repro.obs.SLOMonitor` rides the serve loop.

Writes the per-tenant latency/goodput JSON (the CI artifact):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.tenants.smoke \
        [--kill-sweep 2] [--out results/serve_smoke.json] \
        [--trace results/serve_trace.json]
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
# ^ MUST precede any jax import: device count locks on first init.

import argparse
import json


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kill-sweep", type=int, default=2)
    ap.add_argument("--out", default="results/serve_smoke.json")
    ap.add_argument("--trace", default=None,
                    help="write the co-run's Chrome trace JSON here")
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp

    from ..apps import APPS
    from ..compiler import CompileOptions, compile as tapa_compile
    from ..core import fpga_ring_cluster
    from ..exec import bind_programs, execute
    from ..net import cluster_fabric
    from ..net.transport import NetConfig
    from ..obs import (SLOMonitor, analyze, assert_ledger_consistent,
                       assert_peers_uncharged, build_ledger,
                       substrate_metrics)
    from ..obs.trace import Tracer, write_chrome_trace
    from . import SLO, DeviceKill, Tenant, TenantServer, bit_identical
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    print(f"devices: {jax.devices()}")
    shared = fpga_ring_cluster(4)
    fabric = cluster_fabric(shared)
    net_config = NetConfig()

    # Each tenant compiles independently on a private 2-device cluster —
    # admission onto the shared 4-ring happens purely via device_map.
    opts = CompileOptions(balance_kind="LUT", balance_tol=0.8,
                          exact_limit=1500, floorplan_devices=(0,))
    stencil = APPS["stencil"]
    specs = {"a": {"seed": 0}, "b": {"seed": 7}}
    graphs = {n: stencil.build_graph(2) for n in specs}
    designs = {n: tapa_compile(graphs[n], fpga_ring_cluster(2), opts)
               for n in specs}

    # Solo baselines on the ideal path: the bit-identity references.
    solo = {n: execute(designs[n], bind_programs(graphs[n], specs[n]),
                       fabric=None) for n in specs}

    def tenants():
        return [
            Tenant("a", designs["a"], device_map=[0, 2],
                   slo=SLO(1e-3, weight=2.0), inputs=specs["a"]),
            Tenant("b", designs["b"], device_map=[0, 1],
                   slo=SLO(1e-3, weight=1.0), inputs=specs["b"]),
        ]

    # -- serve 1: clean co-run over the shared fabric ------------------------
    # Always traced: the cost ledger and the online SLO monitor both read
    # the trace (the Chrome export is only written when --trace is given).
    tracer = Tracer()
    monitor = SLOMonitor(window=32)
    server = TenantServer(fabric, tenants(), net_config=net_config,
                          tracer=tracer)
    out = server.run(monitor=monitor)
    for n in specs:
        rec = out.record(n)
        assert rec.status == "done", f"tenant {n}: {rec.status}"
        assert bit_identical(rec.result.outputs, solo[n].outputs), \
            f"tenant {n}: co-run outputs diverged from solo run"
        agree = rec.result.report.agreement()
        assert all(agree.values()), f"tenant {n} accounting: {agree}"
    contended = [c for c in server.transport.counters
                 if len(c.flow_bytes) >= 2]
    assert contended, "placement bug: no link carried both tenants"
    conservation = out.conservation

    # Cost ledger over the clean co-run: rows must sum bit-exactly to the
    # global critical-path and registry totals (the tentpole invariant).
    crit = analyze(tracer, sweeps=out.sweeps)
    ledger = build_ledger(server, crit=crit)
    assert_ledger_consistent(ledger, server, crit=crit,
                             registry=substrate_metrics(server))
    slo_summary = monitor.summary(out.sweeps)

    # -- serve 2: kill tenant a's device mid-flight, re-admit ----------------
    ftracer = Tracer()
    fserver = TenantServer(fabric, tenants(), net_config=net_config,
                           tracer=ftracer)
    fout = fserver.run(faults=[DeviceKill(device=2, sweep=args.kill_sweep)])
    killed = fout.record("a")
    assert killed.status == "killed" and killed.killed_at == args.kill_sweep
    assert killed.recovered_as == "a+recovered"
    recovered = fout.record("a+recovered")
    assert recovered.status == "done", \
        f"recovered tenant never finished: {recovered.status}"
    # The peer is untouched — bit-identical to its solo run even though a
    # neighbour died and drained mid-flight.
    peer = fout.record("b")
    assert peer.status == "done"
    assert bit_identical(peer.result.outputs, solo["b"].outputs), \
        "fault drain perturbed the surviving tenant's outputs"
    # The recovered incarnation computes the same function on one device.
    binding_a = bind_programs(graphs["a"], specs["a"])
    err = float(jnp.max(jnp.abs(jnp.asarray(recovered.result.outputs)
                                - jnp.asarray(binding_a.reference()))))
    assert err <= binding_a.atol, f"recovered numerics diverged: {err}"
    fault_conservation = fout.conservation

    # Kill attribution: the ledger still sums exactly, the cancelled bytes
    # and restore sweeps land on tenant a's lineage, and the surviving
    # peer is charged exactly zero fault cost.
    fcrit = analyze(ftracer, sweeps=fout.sweeps)
    fledger = build_ledger(fserver, crit=fcrit)
    assert_ledger_consistent(fledger, fserver, crit=fcrit,
                             registry=substrate_metrics(fserver))
    assert_peers_uncharged(fledger, ["a"])
    fby = fledger.by_lineage()
    assert fby["a"]["cancelled_bytes"] > 0
    assert fby["a"]["restore_sweeps"] > 0

    sweep_time = net_config.sweep_time_s
    duration_s = out.sweeps * sweep_time
    per_tenant = {}
    for rec in out.records:
        per_tenant[rec.name] = {
            "weight": rec.tenant.slo.weight,
            "latency_s": out.latency_s(rec.name, sweep_time),
            "link_bytes": conservation["per_tenant_link_bytes"][rec.name],
            "goodput_Bps":
                conservation["per_tenant_link_bytes"][rec.name] / duration_s,
        }
    print(f"co-run: {out.sweeps} sweeps, conservation exact, "
          f"{len(contended)} contended links")
    for n, row in per_tenant.items():
        print(f"  tenant {n}: latency {row['latency_s']:.2e}s, "
              f"goodput {row['goodput_Bps']:.3e} B/s")
    print(f"fault run: killed at sweep {killed.killed_at}, recovered as "
          f"{killed.recovered_as} in {fout.sweeps} sweeps, parity {err:.1e}")
    print(f"attrib: ledger exact on both runs; kill charged "
          f"a lineage {fby['a']['cancelled_bytes']} cancelled bytes + "
          f"{fby['a']['restore_sweeps']} restore sweeps, peer b zero")
    print(f"slo: {len(monitor.alerts)} alert(s) over the clean co-run")

    if args.trace:
        doc = write_chrome_trace(tracer, args.trace)
        print(f"wrote Chrome trace ({len(doc['traceEvents'])} events) "
              f"to {args.trace}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({
            "fabric": fabric.describe(),
            "sweeps": out.sweeps,
            "tenants": per_tenant,
            "conservation": conservation,
            "fault": {
                "kill_sweep": args.kill_sweep,
                "killed": killed.name,
                "recovered_as": killed.recovered_as,
                "recovered_parity_err": err,
                "sweeps": fout.sweeps,
                "conservation": fault_conservation,
                "attrib": fledger.to_json(),
            },
            "attrib": ledger.to_json(),
            "slo": slo_summary,
        }, f, indent=2, default=float)
        f.write("\n")
    print(f"SERVE_SMOKE_OK: wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
