"""Executor smoke run (CI): one app on a host-emulated ring.

Compiles the stencil app onto an ``--ndev``-FPGA ring (CI: 4), executes it
on emulated host devices, asserts numerics parity against the app's
single-device reference and the measured-vs-predicted comm agreement,
and writes the ExecutionReport JSON for the CI artifact.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.exec.smoke [--app stencil] \
        [--ndev 4] [--out results/exec_smoke.json] \
        [--trace results/exec_trace.json]
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
    ap.add_argument("--ndev", type=int, default=4)
    ap.add_argument("--out", default="results/exec_smoke.json")
    ap.add_argument("--trace", default=None,
                    help="write the run's Chrome trace JSON here")
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp

    from ..apps import APPS
    from ..compiler import CompileOptions, compile as tapa_compile
    from ..core import fpga_ring_cluster
    from ..obs.trace import Tracer, write_chrome_trace
    from . import bind_programs, execute
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    print(f"devices: {jax.devices()}")
    graph = APPS[args.app].build_graph(args.ndev)
    design = tapa_compile(graph, fpga_ring_cluster(args.ndev),
                          CompileOptions(balance_kind="LUT",
                                         balance_tol=0.8,
                                         floorplan_devices=(0,),
                                         exact_limit=1500))
    # One binding for both the run and the reference (same inputs).
    binding = bind_programs(graph)
    tracer = Tracer() if args.trace else None
    result = execute(design, binding, tracer=tracer)

    expected = binding.reference()
    got = result.outputs
    if isinstance(got, tuple):           # knn returns (dists, idx)
        got, expected = got[0], expected[0]
    err = float(jnp.max(jnp.abs(got - expected)))
    agree = result.report.agreement()
    print(f"[{graph.name}] parity err {err:.2e} (atol {binding.atol}), "
          f"agreement {agree}, sweeps {result.report.sweeps}, "
          f"measured inter-device bytes "
          f"{result.report.measured_inter_bytes}")
    assert err <= binding.atol, f"numerics diverged: {err}"
    assert all(agree.values()), f"comm accounting mismatch: {agree}"
    assert not result.report.starvation_events, \
        f"unexpected starvation: {result.report.starvation_events}"

    if tracer is not None:
        doc = write_chrome_trace(tracer, args.trace)
        print(f"wrote Chrome trace ({len(doc['traceEvents'])} events) "
              f"to {args.trace}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"parity_max_err": err, "atol": binding.atol,
                   "report": result.report.summary()},
                  f, indent=2, default=float)
        f.write("\n")
    print(f"EXEC_SMOKE_OK: wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
