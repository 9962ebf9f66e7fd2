#!/usr/bin/env python3
"""Read the two ends that a cell's correctness limits are set between.

    python3 benchmarks/chip/control.py --workload dilate-1chip \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 13 14 15

* Program readings: for each of ``--seeds``, a short run of the cell as
  ``run.py`` makes it (set-up, a ``--seconds`` window, the check) and the
  numbers its check compares.  Their largest is the lower reading.
* Control readings: for each of ``--control-seeds``, the plain reference
  computed in bfloat16, the precision below the config's float32, put in
  the program's place and compared with the float32 reference by the same
  numbers.  Their smallest is the upper reading.

Everything runs in this one process on the cell's chips; the last line is
a JSON object with both ends of each number and every reading.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys

import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent


def _run_module():
    name = "chipbench_run"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, HERE / "run.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def control_numbers(cell, seed):
    """The check's numbers for the bfloat16 reference in the program's
    place."""
    cfg, mix, app = cell.config, cell.mix, cell.app
    want = app.reference(cfg, mix, seed)
    return app.compare(app.reference(cfg, mix, seed, jnp.bfloat16), want)


def readings(cell, seeds, control_seeds, seconds, devices, **run_kw):
    run = _run_module()
    program = {s: {k: c["value"] for k, c in
                   run.run_cell(cell, s, seconds, False, devices,
                                **run_kw)["check"].items()}
               for s in seeds}
    control = {s: control_numbers(cell, s) for s in control_seeds}
    ends = {}
    for k in cell.config["limits"]:
        lows = [r[k] for r in program.values()]
        highs = [r[k] for r in control.values() if k in r]
        ends[k] = {"lower": max(lows), "upper": min(highs, default=None),
                   "limit": cell.config["limits"][k]}
    return {"workload": cell.name, "ends": ends, "program": program,
            "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    run = _run_module()
    cell = run.resolve(run.load_spec(), args.workload)
    devices = run.chips_for(cell)
    if devices is None:
        return 2
    out = readings(cell, args.seeds, args.control_seeds, args.seconds,
                   devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
