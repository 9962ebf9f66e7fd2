"""HBM bank-model smoke run (CI): a memory-bound app on emulated devices.

Compiles one of the memory-bound apps (axpy by default) onto a ring
cluster with an explicit :class:`MemConfig` (so the memory_feedback pass
runs), executes it twice — through the bank model and on the ideal memory
path — and asserts:

* numerics are **bit-identical** between the two paths AND to the
  monolithic Pallas reference (the apps' atol is 0.0 — exact);
* the bank accounting conserves bytes (every issued request consumed;
  Σ per-bank bytes == Σ memory-channel delivered bytes exactly);
* the measured per-bank utilizations are ≤ 1 (achieved, not offered).

Writes the per-bank utilization JSON (the CI artifact):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.mem.smoke [--ndev 4] \
        [--app axpy] [--out results/mem_smoke.json] \
        [--trace results/mem_trace.json]
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
# ^ MUST precede any jax import: device count locks on first init.

import argparse
import json


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="axpy",
                    choices=["axpy", "dot", "gemv", "axpydot"])
    ap.add_argument("--ndev", type=int, default=4)
    ap.add_argument("--out", default="results/mem_smoke.json")
    ap.add_argument("--trace", default=None,
                    help="write the bank-modeled run's Chrome trace here")
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp

    from ..apps import APPS
    from ..compiler import CompileOptions, compile as tapa_compile
    from ..core import fpga_ring_cluster
    from ..exec import bind_programs, execute
    from ..obs.trace import Tracer, write_chrome_trace
    from .banks import MemConfig
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    print(f"devices: {jax.devices()}")
    cluster = fpga_ring_cluster(args.ndev)
    # Small banks so the CI shapes genuinely queue (several sweeps per
    # request) without slowing the run.
    config = MemConfig(banks_per_device=4, bank_bandwidth_Bps=2e9,
                       credits=4, burst_bytes=512)
    graph = APPS[args.app].build_graph(args.ndev)
    design = tapa_compile(graph, cluster, CompileOptions(
        balance_kind="LUT", balance_tol=0.8, exact_limit=1500,
        mem=config,
        passes=("normalize_units", "partition", "memory_feedback",
                "pipeline_interconnect", "schedule")))
    binding = bind_programs(graph)
    tracer = Tracer() if args.trace else None
    result = execute(design, binding, tracer=tracer)
    ideal = execute(design, bind_programs(graph), mem=None)

    expected = binding.reference()
    assert bool(jnp.all(result.outputs == ideal.outputs)), \
        "bank-modeled numerics diverged from the ideal path"
    assert bool(jnp.all(result.outputs == expected)), \
        "numerics diverged from the Pallas reference (bit-tight contract)"
    report = result.report
    agree = report.agreement()
    assert all(agree.values()), f"accounting mismatch: {agree}"
    mem = report.mem_contention
    assert mem is not None and mem.max_utilization <= 1.0 + 1e-12

    print(f"[{graph.name}] ring {args.ndev}, "
          f"{len(report.mem_channels)} memory channels, agreement {agree}")
    print(f"bank bytes {report.mem_bank_bytes:.0f} == "
          f"delivered {report.mem_delivered_bytes} "
          f"(max measured util {mem.max_utilization:.3f}, "
          f"mem waits {sum(report.task_mem_waits.values())}, "
          f"sweeps {report.sweeps} vs ideal {ideal.report.sweeps})")

    if tracer is not None:
        doc = write_chrome_trace(tracer, args.trace)
        print(f"wrote Chrome trace ({len(doc['traceEvents'])} events) "
              f"to {args.trace}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({
            "app": args.app,
            "ndev": args.ndev,
            "agreement": agree,
            "bit_identical": True,
            "sweeps": report.sweeps,
            "ideal_sweeps": ideal.report.sweeps,
            "mem_waits": dict(report.task_mem_waits),
            "config": {"banks_per_device": config.banks_per_device,
                       "bank_bandwidth_Bps": config.bank_bandwidth_Bps,
                       "credits": config.credits,
                       "burst_bytes": config.burst_bytes},
            "bank_map": dict(design.bank_map or {}),
            "measured": mem.summary(),
            "projected": design.mem_contention.summary(),
            "feedback": dict(design.pass_record("memory_feedback").detail),
        }, f, indent=2, default=float)
        f.write("\n")
    print(f"MEM_SMOKE_OK: wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
