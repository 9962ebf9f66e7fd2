"""Network-fabric smoke run (CI): one app on a 2×2 mesh of emulated devices.

Compiles the stencil app onto a 2×2 mesh cluster with an explicit fabric
(so the congestion_feedback pass runs), executes it twice — through the
fabric and on the ideal transfer path — and asserts:

* numerics are **bit-identical** between the two paths (and match the
  single-device reference within the binding's atol);
* the fabric accounting conserves bytes (every submitted byte delivered;
  per-link totals sum exactly to the hop-weighted cut-set traffic);
* the λ route costing reproduces the partitioner's Eq. 2 objective.

Writes the per-link utilization JSON (the CI artifact):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.net.smoke [--rows 2 --cols 2] \
        [--app stencil] [--out results/net_smoke.json] \
        [--trace results/net_trace.json]
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
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--cols", type=int, default=2)
    ap.add_argument("--out", default="results/net_smoke.json")
    ap.add_argument("--trace", default=None,
                    help="write the fabric run's Chrome trace JSON here")
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp

    from ..apps import APPS
    from ..compiler import CompileOptions, compile as tapa_compile
    from ..core import ALVEO_U55C, Cluster, Mesh2D
    from ..exec import bind_programs, execute
    from ..obs.trace import Tracer, write_chrome_trace
    from . import cluster_fabric
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    ndev = args.rows * args.cols
    print(f"devices: {jax.devices()}")
    cluster = Cluster(ALVEO_U55C, Mesh2D(args.rows, args.cols))
    fabric = cluster_fabric(cluster)
    graph = APPS[args.app].build_graph(ndev)
    design = tapa_compile(graph, cluster, CompileOptions(
        balance_kind="LUT", balance_tol=0.8, exact_limit=1500,
        fabric=fabric,
        passes=("normalize_units", "partition", "congestion_feedback",
                "pipeline_interconnect", "schedule")))
    binding = bind_programs(graph)
    tracer = Tracer() if args.trace else None
    result = execute(design, binding, tracer=tracer)
    ideal = execute(design, bind_programs(graph), fabric=None)

    got, got_ideal = result.outputs, ideal.outputs
    expected = binding.reference()
    if isinstance(got, tuple):           # knn returns (dists, idx)
        got, got_ideal, expected = got[0], got_ideal[0], expected[0]
    assert bool(jnp.all(got == got_ideal)), \
        "fabric path numerics diverged from the ideal path"
    err = float(jnp.max(jnp.abs(got - expected)))
    assert err <= binding.atol, f"numerics diverged: {err}"
    report = result.report
    agree = report.agreement()
    assert all(agree.values()), f"accounting mismatch: {agree}"

    cong = report.congestion
    print(f"[{graph.name}] mesh {args.rows}x{args.cols}, "
          f"{len(fabric.links)} links, parity err {err:.2e}, "
          f"agreement {agree}")
    print(f"link bytes {report.net_link_bytes:.0f} == "
          f"hop-weighted {report.net_hop_weighted_bytes} "
          f"(max util {cong.max_utilization:.3f}, "
          f"sweeps {report.sweeps})")

    if tracer is not None:
        doc = write_chrome_trace(tracer, args.trace)
        print(f"wrote Chrome trace ({len(doc['traceEvents'])} events) "
              f"to {args.trace}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({
            "app": args.app,
            "mesh": [args.rows, args.cols],
            "parity_max_err": err,
            "atol": binding.atol,
            "agreement": agree,
            "fabric": fabric.describe(),
            "congestion": cong.summary(),
            "feedback": dict(
                design.pass_record("congestion_feedback").detail),
        }, f, indent=2, default=float)
        f.write("\n")
    print(f"NET_SMOKE_OK: wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
