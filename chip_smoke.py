#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: dataflow + LM serving
    python chip_smoke.py --chips 4   # four chips: the dataflow phase only

Phases:

* ``dataflow`` — the paper's path.  ``repro.compiler.compile`` places the
  Dilate stencil (4 stages, 64 iterations: Table 4's memory-bound point) on
  a 4-FPGA ring, and ``repro.exec.execute`` streams 4 images of the paper's
  4096² f32 grid through it, each stage running 16 iterations of the Pallas
  kernel.  One chip runs every stage (``device_map=[0, 0, 0, 0]``); four
  chips run one stage each (``[0, 1, 2, 3]``).  The output must equal the
  plain jnp dilation bit for bit, and every stage's output must sit on the
  chip it was placed on.
* ``serve`` — qwen3-4b at its published widths in bf16 (random weights
  from ``--seed``) behind ``ServingEngine``: 4 prompts of 32 tokens, 16
  greedy tokens each.  Tokens must be in range, two identical calls must
  agree, and the decode path's logits at the last prompt position must
  match a full-sequence forward of the same parameters.

Every phase runs in this one process, on JAX's TPU devices; the script
exits non-zero, printing no result, when the first device is not a TPU.
Earlier lines report wall and compile seconds, parity errors, placement
and peak device memory; the last line is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.exec.executor import device_name  # noqa: E402
from repro.runtime.compile_cache import (CompileMeter,  # noqa: E402
                                         enable_compile_cache)

# Table 4's memory-bound point: 64 iterations over 4 stages.
DILATE_ITERS = 64
STAGES = 4
# Scale-normalised bound on |decode logits - forward logits|: bf16 keeps 8
# significant bits (2^-8 ≈ 0.4%), and the two paths round differently
# through 36 layers.
LOGIT_TOL = 5e-2


class SmokeFailure(RuntimeError):
    """What a phase produced is wrong."""


def _require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _print(phase: str, record: dict) -> None:
    print(f"{phase}: {json.dumps(record, sort_keys=True, default=float)}",
          flush=True)


def peak_bytes(devices) -> dict:
    """Peak device memory in use so far, per device, where reported."""
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out[device_name(d)] = int(stats["peak_bytes_in_use"])
    return out


def dataflow_phase(devices, device_map, *, h: int, w: int, streams: int,
                   seed: int, interpret: bool, meter: CompileMeter) -> dict:
    """Compile the Dilate chain, execute it twice, check it bit for bit."""
    from repro.apps import stencil
    from repro.compiler import compile as tapa_compile
    from repro.core import fpga_ring_cluster
    from repro.exec import bind_programs, execute

    before = meter.snapshot()
    t0 = time.perf_counter()
    graph = stencil.build_graph(STAGES, iters=DILATE_ITERS)
    design = tapa_compile(graph, fpga_ring_cluster(STAGES))
    design_s = time.perf_counter() - t0
    binding = bind_programs(graph, {
        "h": h, "w": w, "streams": streams, "seed": seed,
        "stage_iters": DILATE_ITERS // STAGES, "interpret": interpret})

    runs = []
    for _ in range(2):         # the first run compiles the stage kernel
        result = execute(design, binding, devices=devices,
                         device_map=device_map)
        runs.append(result.report.wall_time_s)
    report = result.report

    t0 = time.perf_counter()
    expected = binding.reference()
    expected.block_until_ready()
    reference_s = time.perf_counter() - t0
    got = np.asarray(result.outputs)
    want = np.asarray(expected)
    _require(got.shape == (streams, h, w) and got.dtype == np.float32,
             f"dilation output {got.shape} {got.dtype}")
    _require(np.all(np.isfinite(got)), "non-finite dilation output")
    max_err = float(np.max(np.abs(got - want)))
    _require(np.array_equal(got, want),
             f"dilation differs from the reference: max err {max_err}")
    agree = report.agreement()
    _require(all(agree.values()), f"comm accounting mismatch: {agree}")

    # Each stage's output arrays must be on the chip its logical device
    # was mapped to.
    assign = design.partition.assignment
    stage_chip = {}
    for stage in graph.tasks:
        want_dev = device_name(devices[device_map[assign[stage]]])
        seen = report.task_devices[stage]
        _require(seen == [want_dev], f"{stage} ran on {seen}, not {want_dev}")
        stage_chip[stage] = want_dev
    if len(set(device_map)) == STAGES:
        _require(len(set(stage_chip.values())) == STAGES,
                 f"stages share a chip: {stage_chip}")

    return {"grid": [h, w], "images": streams, "iterations": DILATE_ITERS,
            "compile_design_s": design_s,
            "execute_wall_s": runs, "reference_wall_s": reference_s,
            "max_abs_err": max_err, "bit_identical": True,
            "sweeps": report.sweeps, "placement": report.placement,
            "stage_chip": stage_chip,
            "agreement": agree, **meter.since(before),
            "peak_bytes_in_use": peak_bytes(devices)}


def forward_logits(params, cfg, tokens):
    """Full-sequence forward of the train path: logits at every position."""
    from repro.models import layers
    from repro.models import transformer as T
    x = T._embed_inputs(params, cfg, {"tokens": tokens})
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x, _ = T._run_stack(params, cfg, x, pos)
    x = layers.rmsnorm(params["final_norm"], x,
                       zero_centered=cfg.zero_centered_norm)
    return layers.unembed(T._unembed_table(params, cfg), x)


def serve_phase(cfg, *, slots: int, max_len: int, prompt_len: int,
                new_tokens: int, seed: int, meter: CompileMeter) -> dict:
    """Serve seeded prompts; check range, repeatability and parity."""
    from repro.models import init_params
    from repro.serving.engine import ServeConfig, ServingEngine

    before = meter.snapshot()
    t0 = time.perf_counter()
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

    engine = ServingEngine(params, cfg, ServeConfig(batch_slots=slots,
                                                    max_len=max_len))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (slots, prompt_len), 0, cfg.vocab,
        dtype=jnp.int32))

    outs, walls = [], []
    for _ in range(2):         # the first call compiles serve_step
        t0 = time.perf_counter()
        outs.append(engine.generate(prompts, max_new=new_tokens))
        walls.append(time.perf_counter() - t0)
    _require(outs[0].shape == (slots, new_tokens),
             f"generated tokens shaped {outs[0].shape}")
    _require(np.all((outs[0] >= 0) & (outs[0] < cfg.vocab)),
             "token out of range")
    _require(np.array_equal(outs[0], outs[1]), "generate is not repeatable")

    decode_last, _ = engine.prefill(prompts)
    full = jax.jit(lambda p, t: forward_logits(p, cfg, t))(
        params, jnp.asarray(prompts))
    full_last = np.asarray(full[:, -1], np.float32)
    decode_last = np.asarray(decode_last, np.float32)
    _require(np.all(np.isfinite(decode_last)), "non-finite logits")
    scale = float(np.max(np.abs(full_last)))
    logit_err = float(np.max(np.abs(decode_last - full_last))) / scale
    _require(logit_err <= LOGIT_TOL,
             f"decode/forward logits differ by {logit_err} of their scale")
    argmax_agree = float(np.mean(np.argmax(decode_last, -1)
                                 == np.argmax(full_last, -1)))

    return {"model": cfg.name, "params": n_params,
            "dtype": jnp.dtype(cfg.dtype).name, "slots": slots,
            "max_len": max_len, "prompt_len": prompt_len,
            "new_tokens": new_tokens, "init_s": init_s,
            "generate_wall_s": walls,
            "logit_err_scaled": logit_err, "logit_tol": LOGIT_TOL,
            "logit_scale": scale, "argmax_agree": argmax_agree,
            **meter.since(before),
            "peak_bytes_in_use": peak_bytes(jax.devices()[:1])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the dataflow phase across four chips only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform} "
              f"devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    _print("setup", {"compile_cache_dir": cache_dir,
                     "devices": [device_name(d) for d in devices],
                     "device_kind": devices[0].device_kind})

    t0 = time.perf_counter()
    device_map = list(range(STAGES)) if args.chips == 4 else [0] * STAGES
    rec = dataflow_phase(devices[:args.chips], device_map, h=4096, w=4096,
                         streams=4, seed=args.seed, interpret=False,
                         meter=meter)
    rec["phase_wall_s"] = time.perf_counter() - t0
    _print("dataflow", rec)

    if args.chips == 1:
        from repro.configs import get_arch
        t0 = time.perf_counter()
        rec = serve_phase(get_arch("qwen3-4b").full(), slots=4, max_len=256,
                          prompt_len=32, new_tokens=16, seed=args.seed,
                          meter=meter)
        rec["phase_wall_s"] = time.perf_counter() - t0
        _print("serve", rec)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
