"""Observability smoke run (CI): trace, metrics, and critpath end to end.

For each paper app compiled onto a contended ``--ndev``-FPGA ring (real
fabric + congestion_feedback, so the network transport genuinely carries
the traffic), runs the design twice — once untraced (``NULL_TRACER``) and
once recording — and asserts the observability contract:

* **transparency** — the traced run is bit-identical to the untraced one,
  with identical sweep counts and identical report counters (the tracer
  observes, never perturbs);
* **byte agreement** — summed trace-event bytes equal the per-link goodput
  and per-bank counters exactly (integers, no tolerance), and the
  ``MetricsRegistry`` view reconciles with every legacy report field;
* **attribution** — the critical-path decomposition of every app sums to
  the measured makespan exactly, and the predicted-vs-measured table
  prints the §5 schedule error as a number.

With ``--metrics`` it also writes an ``obs-metrics/v1`` document — one
``MetricsRegistry.to_json()`` per app — the candidate side of the CI
regression gate (``scripts/obs_diff.py`` diffs it against the committed
``results/obs_baseline.json``).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.obs.smoke [--ndev 4] \
        [--out results/obs_smoke.json] [--trace results/obs_trace.json] \
        [--metrics results/obs_metrics.json]
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
# ^ MUST precede any jax import: device count locks on first init.

import argparse
import json

APPS_UNDER_TEST = ("stencil", "cnn", "knn", "pagerank")


def _compile(app: str, ndev: int):
    from ..apps import APPS
    from ..compiler import CompileOptions, compile as tapa_compile
    from ..core import fpga_ring_cluster
    from ..net import cluster_fabric
    cluster = fpga_ring_cluster(ndev)
    graph = APPS[app].build_graph(ndev)
    design = tapa_compile(graph, cluster, CompileOptions(
        balance_kind="LUT", balance_tol=0.8, exact_limit=1500,
        fabric=cluster_fabric(cluster),
        passes=("normalize_units", "partition", "congestion_feedback",
                "pipeline_interconnect", "schedule")))
    return graph, design


def _counters(report):
    """Every measured counter the tracer must not perturb."""
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ndev", type=int, default=4)
    ap.add_argument("--out", default="results/obs_smoke.json")
    ap.add_argument("--trace", default=None,
                    help="write the stencil run's Chrome trace JSON here")
    ap.add_argument("--metrics", default=None,
                    help="write the per-app obs-metrics/v1 registry "
                         "document here (the diff-gate candidate)")
    args = ap.parse_args()

    from ..exec import bind_programs, execute
    from ..tenants import bit_identical
    from .critpath import analyze, format_table, makespan_row
    from .metrics import (assert_registry_consistent,
                          assert_trace_report_consistent, from_report)
    from .trace import Tracer, write_chrome_trace
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows = []
    app_records = {}
    app_registries = {}
    stencil_tracer = None
    for app in APPS_UNDER_TEST:
        graph, design = _compile(app, args.ndev)
        base = execute(design, bind_programs(graph))
        tracer = Tracer()
        res = execute(design, bind_programs(graph), tracer=tracer)

        # Transparency: identical numerics and identical counters.
        assert bit_identical(base.outputs, res.outputs), \
            f"{app}: tracer perturbed the outputs"
        assert _counters(base.report) == _counters(res.report), \
            f"{app}: tracer perturbed the report counters"
        assert res.report.trace is tracer and base.report.trace is None

        # Byte agreement: trace events == report counters, exactly.
        assert_trace_report_consistent(tracer, res.report)
        reg = from_report(res.report)
        assert_registry_consistent(reg, res.report)
        app_registries[app] = reg

        # Attribution: exact decomposition (asserted inside makespan_row).
        crit = analyze(tracer, sweeps=res.report.sweeps)
        rows.append(makespan_row(app, design, res.report, crit))
        app_records[app] = {
            "events": len(tracer),
            "sweeps": res.report.sweeps,
            "critpath": crit.to_json(),
        }
        if app == "stencil":
            stencil_tracer = tracer
        print(f"[{app}] {len(tracer)} events over {res.report.sweeps} "
              f"sweeps; critical task {crit.critical().task}; "
              f"trace/report byte agreement exact")

    # The contended ring genuinely exercised the network path.
    assert any(r["network"] + r["compute"] > 0 for r in rows)
    assert sum(a["events"] for a in app_records.values()) > 0

    print()
    print(format_table(rows))

    if args.trace:
        doc = write_chrome_trace(stencil_tracer, args.trace)
        print(f"wrote Chrome trace ({len(doc['traceEvents'])} events) "
              f"to {args.trace}")

    if args.metrics:
        from .diff import METRICS_FORMAT
        os.makedirs(os.path.dirname(args.metrics) or ".", exist_ok=True)
        with open(args.metrics, "w") as f:
            json.dump({"format": METRICS_FORMAT, "ndev": args.ndev,
                       "apps": {a: r.to_json()
                                for a, r in app_registries.items()}},
                      f, indent=2, default=float)
            f.write("\n")
        print(f"wrote metrics document to {args.metrics}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"format": "obs-smoke/v1", "ndev": args.ndev,
                   "rows": rows, "apps": app_records},
                  f, indent=2, default=float)
        f.write("\n")
    print(f"OBS_SMOKE_OK: wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
