"""Chaos smoke run (CI): the reduced fault matrix on one app.

One drop-rate tier, one finite link outage, one permanent link death
(route repair), and one kill/restore cell, all on the stencil app over a
4-FPGA emulated ring.  Every cell asserts bit-identity against the
fault-free baseline, full measured-vs-predicted agreement (including the
repair-aware goodput conservation), seeded replayability, and the
barrier-bounded restore cost.  Two **per-tenant** cells then co-run two
weighted tenants over one shared ring — a lossy fabric and a device kill
— asserting the cost ledger sums bit-exactly and the kill charges the
victim's lineage only.  Writes the fault-matrix JSON artifact.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.chaos.smoke \
        [--app stencil] [--full] [--out results/chaos_smoke.json] \
        [--trace results/chaos_trace.json]

``--full`` runs the complete :func:`repro.chaos.default_matrix` over all
four paper apps (the BENCH path; several minutes).  ``--trace`` re-runs
the drop-tier cell on the stencil 4-ring with a recording tracer, writes
its Chrome trace, and asserts the critical-path analysis attributes at
least one sweep to ARQ retransmits on the faulted link.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
# ^ MUST precede any jax import: device count locks on first init.

import argparse
import json


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="stencil",
                    choices=["stencil", "pagerank", "knn", "cnn"])
    ap.add_argument("--full", action="store_true",
                    help="full matrix over all four apps")
    ap.add_argument("--out", default="results/chaos_smoke.json")
    ap.add_argument("--trace", default=None,
                    help="write the traced drop-tier cell's Chrome trace")
    args = ap.parse_args()

    import jax


    from .runner import run_matrix, run_scenario
    from .scenario import ChaosScenario, default_matrix
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    print(f"devices: {jax.devices()}")
    if args.full:
        apps = ("stencil", "cnn", "knn", "pagerank")
        scenarios = default_matrix()
    else:
        apps = (args.app,)
        scenarios = (
            ChaosScenario("drop-mid", drop=0.05, corrupt=0.02,
                          reorder=0.03, seed=5),
            ChaosScenario("down-window", down={5: ((0, 6),)}, seed=11),
            ChaosScenario("link-death", down={5: ((0, None),)},
                          fail_threshold=4, seed=13),
            ChaosScenario("kill-restore", kill_sweep=6, barrier=4,
                          seed=17),
        )
    matrix = run_matrix(apps, scenarios, verbose=True)
    assert matrix["ok"]

    # Per-tenant chaos cells: the attribution tentpole under faults — a
    # lossy shared fabric (ledger sums bit-exactly, both tenants charged)
    # and a clean-link device kill (victim's lineage pays, peer pays zero).
    from .runner import run_tenant_cell
    tenant_cells = []
    for sc in (ChaosScenario("tenant-drop", drop=0.05, corrupt=0.02,
                             seed=5),
               ChaosScenario("tenant-kill", kill_sweep=2, seed=17)):
        cell = run_tenant_cell(sc)
        tenant_cells.append(cell)
        print(f"  [tenants × {sc.name}] sweeps {cell['sweeps']} "
              f"(clean {cell['clean_sweeps']}), ledger exact")
    matrix["tenant_cells"] = tenant_cells
    assert all(c["ok"] for c in tenant_cells)

    if args.trace:
        # The observability acceptance cell: trace the drop-tier scenario
        # on the stencil 4-ring and prove the critical-path analysis pins
        # recovery sweeps on the ARQ traffic of the faulted link.
        from ..obs.critpath import analyze
        from ..obs.trace import Tracer, write_chrome_trace
        drop = ChaosScenario("drop-mid", drop=0.05, corrupt=0.02,
                             reorder=0.03, seed=5)
        tracer = Tracer()
        cell = run_scenario("stencil", drop, tracer=tracer)
        crit = analyze(tracer, sweeps=cell["sweeps"])
        faulted = {e[2] for e in tracer.iter_kind("retransmit")}
        assert faulted, "drop-tier cell produced no retransmits"
        assert any(crit.fault_link_sweeps.get(li, 0) >= 1
                   for li in faulted), \
            "no fault sweep attributed to the faulted links"
        assert sum(t.fault for t in crit.tasks) >= 1, \
            "critpath attributed no task sweep to ARQ recovery"
        doc = write_chrome_trace(tracer, args.trace)
        matrix["traced_cell"] = {
            "scenario": drop.name,
            "trace_events": len(doc["traceEvents"]),
            "fault_link_sweeps": {str(k): v for k, v in
                                  crit.fault_link_sweeps.items()},
            "fault_task_sweeps": sum(t.fault for t in crit.tasks),
        }
        print(f"wrote Chrome trace ({len(doc['traceEvents'])} events) "
              f"to {args.trace}; fault sweeps on faulted links "
              f"{matrix['traced_cell']['fault_link_sweeps']}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(matrix, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")
    print(f"CHAOS_SMOKE_OK cells={len(matrix['cells'])} "
          f"apps={len(matrix['apps'])}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
