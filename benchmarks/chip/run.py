#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload dilate-1chip --seed 7 \\
        --seconds 10 --trace 0

A cell names a configuration (``configs/<name>.json``, whose ``app`` names
the module beside it that builds the design and holds the plain
reference), a traffic mix (``mixes/<name>.json``: what one ``execute()``
carries) and a chip count; logical device d of the design runs on chip
d mod chips.  Each metric is read by ``metrics/<name>.py``.
Everything is found by name, so a new cell, mix or metric is new files and
new entries, never an edit.

One run is a closed loop on the paper's path:

1. set-up: ``repro.compiler.compile`` places the design on a 4-FPGA ring,
   the app's ``bind_programs`` makes its data from ``--seed``, and one
   ``execute()`` compiles every program the window will run;
2. the window: ``execute()`` back to back for ``--seconds`` (with
   ``--trace 1``, at most ``TRACE_SECONDS`` under the profiler), each call
   ending in ``block_until_ready`` on its outputs;
3. the check: a sample of the window's outputs, drawn from the seed, and
   the last, against the config's plain reference, and every task's
   outputs on the chip its logical device maps to.

Earlier lines report set-up, the window, the device and, when traced, each
chip's busy time and top operations; the check's
numbers close standard error; the last line of standard output is the
result.  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import peaks  # noqa: E402
import trace_reduce  # noqa: E402

# JAX's persistent compilation cache: a fixed directory in the checkout.
CACHE_DIR = ROOT / ".jax_cache"
# Longest traced window: the trace is read back inside the run's time.
TRACE_SECONDS = 4.0
# Window outputs checked besides the last, drawn from the seed.
CHECK_SAMPLES = 2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


def say(tag: str, record: dict) -> None:
    print(f"{tag}: {json.dumps(record, sort_keys=True)}", flush=True)


def chip_name(device) -> str:
    return f"{device.platform}:{device.id}"


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# -- the cell, resolved by name ------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    mix: Dict[str, Any]
    app: Any
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, Callable]


def load_spec(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _lists(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: Dict[str, Any], workload: str,
            root: pathlib.Path = ROOT) -> Cell:
    """Find the cell's config, app module, mix and metric readers."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    app = load_module(HERE / "configs" / f"{config['app']}.py",
                      f"chipbench_app_{config['app']}")
    mix = json.loads((HERE / "mixes" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _lists(m, workload)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    readers = {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py",
                                      f"chipbench_metric_{m['name']}").read
               for m in e2e + layer}
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config, mix=mix, app=app,
                end_to_end=e2e, per_layer=layer, readers=readers)


# -- what the metric readers read ----------------------------------------

@dataclasses.dataclass
class Readings:
    chips: int
    setup_s: float
    compile_design_s: float
    xla_compile_s: float
    walls: List[float]              # each execute() in the window, seconds
    window_s: float
    least_exec_s: float             # least time of one execute() on a chip
    trace: Optional[trace_reduce.TraceSummary] = None


class CompileMeter:
    """XLA compile seconds and persistent-cache hits and misses, summed
    from JAX's own monitoring events (a cache hit records its retrieval
    time as the compile time)."""

    def __init__(self):
        self.compile_s, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.compile_s, "cache_hits": self.hits,
                "cache_misses": self.misses}

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from ``rng``."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.items: List[Any] = []

    def offer(self, item: Any) -> None:
        if self.seen < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def _finite(x: float):
    return x if math.isfinite(x) else str(x)


def peak_bytes(devices) -> Dict[str, int]:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out[chip_name(d)] = int(stats["peak_bytes_in_use"])
    return out


# -- one run --------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             *, interpret: Optional[bool] = None,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """Set up, run the window, check it; return the result line's dict."""
    from repro.compiler import compile as tapa_compile
    from repro.core import fpga_ring_cluster
    from repro.exec import bind_programs, execute

    t_start = time.perf_counter() if t_start is None else t_start
    start_s = time.perf_counter() - t_start   # imports, chip and cache
    kind = devices[0].device_kind
    pk = peaks.peak(kind) if devices[0].platform == "tpu" else None
    cfg, mix, app = cell.config, cell.mix, cell.app
    meter = CompileMeter()
    try:
        graph = app.build_graph(cfg)
        t0 = time.perf_counter()
        design = tapa_compile(graph, fpga_ring_cluster(cfg["fpgas"]))
        compile_design_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        binding = bind_programs(graph, app.bind_spec(cfg, mix, seed,
                                                     interpret))
        bind_s = time.perf_counter() - t0
        # Logical device d runs on chip d mod chips.
        device_map = [d % cell.chips for d in range(cfg["fpgas"])]
        assign = design.partition.assignment
        expected = {t: [chip_name(devices[device_map[assign[t]]])]
                    for t in graph.tasks}

        def one_call():
            with jax.profiler.TraceAnnotation("bench.execute"):
                result = execute(design, binding, devices=devices,
                                 device_map=device_map)
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(result.outputs)
            return result

        t0 = time.perf_counter()
        one_call()                                   # the warm-up
        warmup_s = time.perf_counter() - t0
        before = meter.snapshot()
        say("setup", {"workload": cell.name, "config": cell.config_name,
                      "seed": seed, "chips": cell.chips,
                      "device_map": device_map, "start_s": start_s,
                      "compile_design_s": compile_design_s, "bind_s": bind_s,
                      "warmup_s": warmup_s, **before,
                      "compile_cache_dir":
                          jax.config.jax_compilation_cache_dir})

        window = min(seconds, TRACE_SECONDS) if trace else seconds
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if trace else None
        try:
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            sample = Reservoir(CHECK_SAMPLES, np.random.default_rng(seed))
            walls: List[float] = []
            setup_s = time.perf_counter() - t_start
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                w0 = time.perf_counter()
                w0_unix = time.time()
                deadline = w0 + window
                while True:
                    t0 = time.perf_counter()
                    result = one_call()
                    t1 = time.perf_counter()
                    walls.append(t1 - t0)
                    if len(walls) > 1:
                        sample.offer(last)
                    last = (len(walls) - 1, result.outputs,
                            result.report.task_devices)
                    if t1 >= deadline:
                        break
            window_s = t1 - w0
            summary = None
            if trace:
                jax.profiler.stop_trace()
                profile = trace_reduce.load(trace_reduce.find_xplane(
                    trace_dir))
                summary = trace_reduce.reduce(profile,
                                              [d.id for d in devices])
                del profile
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        after = meter.snapshot()
    finally:
        meter.close()

    memory = peak_bytes(devices)
    say("device", {"device_kind": kind, "chips": len(devices),
                   "peak_bytes_in_use": memory})
    if summary is not None:
        say("trace", {"window_s": summary.window_s,
                      "per_chip": summary.per_chip()})
    slowest = int(np.argmax(walls))
    median_s = float(np.median(walls))
    starts = np.concatenate([[0.0], np.cumsum(walls)[:-1]])
    # Calls over 1.5 times the median: index, start in the window, wall.
    slow = [[int(i), float(starts[i]), walls[i]]
            for i in np.flatnonzero(np.asarray(walls) > 1.5 * median_s)]
    say("window", {"seconds": window_s, "execs": len(walls),
                   "p95_samples_beyond": int(len(walls) * 0.05),
                   "first_s": walls[0], "median_s": median_s,
                   "max_s": walls[slowest], "max_call": slowest,
                   "max_starts_at_s": float(starts[slowest]),
                   "slow_calls": slow[:10],
                   "slow_calls_s": float(sum(w for _, _, w in slow)),
                   "start_unix": w0_unix,
                   "traced": bool(trace),
                   "compiles_in_window": after["cache_hits"]
                   + after["cache_misses"] - before["cache_hits"]
                   - before["cache_misses"],
                   "compile_s_in_window": after["compile_s"]
                   - before["compile_s"]})

    # The check, once the program's state is freed.
    checked = sample.items + [last]
    del design, binding, result, one_call
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.reference"):
        want = app.reference(cfg, mix, seed)
        numbers: Dict[str, float] = {}
        failed = 0
        for _, outputs, task_devices in checked:
            got = jax.device_put(outputs, want.devices().pop())
            nums = app.compare(got, want)
            # A task whose outputs are no arrays (a routed dict) has no
            # recorded chip; every recorded one must be where it was put.
            nums["misplaced_tasks"] = sum(
                bool(task_devices.get(t)) and task_devices[t] != want_dev
                for t, want_dev in expected.items())
            if any(not (v <= cfg["limits"][k]) for k, v in nums.items()):
                failed += 1
            for k, v in nums.items():
                numbers[k] = max(numbers.get(k, v), v)
    reference_s = time.perf_counter() - t0
    check = {k: {"value": _finite(v), "limit": cfg["limits"][k]}
             for k, v in numbers.items()}
    say("check", {"calls_checked": [i for i, _, _ in checked],
                  "reference_s": reference_s})

    readings = Readings(
        chips=len(devices), setup_s=setup_s,
        compile_design_s=compile_design_s,
        xla_compile_s=before["compile_s"], walls=walls, window_s=window_s,
        least_exec_s=(peaks.least_time_s(app.work(cfg, mix), pk)
                      if pk else math.nan),
        trace=summary)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.readers[m["name"]](readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(memory.values(), default=0)}
    out = {"correct": failed == 0, "attempted": len(walls),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_mean_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["check"] = check
    return out


def chips_for(cell: Cell):
    """The cell's TPU chips, with the benchmark's compile cache switched
    on; None, with the reason on standard error, where there are none."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"{cell.name}: needs a TPU, JAX found {devices[0].platform} "
              f"devices", file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"{cell.name}: needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    peaks.peak(devices[0].device_kind)       # an unknown chip is an error

    from repro.runtime.compile_cache import ENV_VAR, enable_compile_cache
    os.environ[ENV_VAR] = str(CACHE_DIR)
    if enable_compile_cache() != str(CACHE_DIR):
        raise RuntimeError("the program did not take the benchmark's "
                           "compile cache directory")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices[:cell.chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = resolve(load_spec(), args.workload)
    devices = chips_for(cell)
    if devices is None:
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   t_start=_T0)
    for name, c in out["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
