"""The check that decides ``correct``, on the CPU at a small size.

A whole run is driven past the harness's look for a chip: set-up, the
window and the check, on the CPU with the Pallas interpreter.  A sound
program comes out correct; the same run with the timed path broken
underneath comes out not correct, once for each fault the cell can have:

* a step that hands back its input unchanged;
* half of the batch left out;
* the exchange between chips left out (four emulated CPU devices, in a
  child process, since the device count is fixed when JAX starts);
* one answer altered where it is produced.

The control, the plain reference computed in bfloat16, fails the same
limits that the program's own readings meet: read by ``control.py``, and
put in the program's place under a whole run, on one device and on four.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SMALL = {"dilate-1chip": {"grid": [16, 128]},
         "dilate-4chip": {"grid": [16, 128]}}
SEED = 2**31 + 17


def _load(name, path):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="module")
def run():
    return _load("chipbench_run", HERE / "run.py")


def small_cell(run, workload):
    cell = run.resolve(run.load_spec(), workload)
    cell.config = dict(cell.config, **SMALL[workload])
    return cell


def drive(run, workload, seed=SEED):
    cell = small_cell(run, workload)
    return run.run_cell(cell, seed, 0.2, False, jax.devices()[:1],
                        interpret=True)


def broken(monkeypatch, app, task_fault):
    """Wrap the app's ``bind_programs`` so that ``task_fault(binding)``
    breaks the programs it binds."""
    from repro import apps
    mod = apps.APPS[app]
    bind = mod.bind_programs

    def bind_broken(graph, spec=None):
        binding = bind(graph, spec)
        task_fault(binding)
        return binding
    monkeypatch.setattr(mod, "bind_programs", bind_broken)


def _wrap(binding, task, fault):
    body = binding.programs[task]
    programs = dict(binding.programs)
    programs[task] = lambda inputs: fault(inputs, body)
    binding.programs = programs


@pytest.mark.parametrize("workload", ["dilate-1chip"])
def test_sound_run_is_correct(run, workload):
    out = drive(run, workload)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "check"
    cell = run.resolve(run.load_spec(), workload)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"exec_ms", "setup_s"} <= set(out["metrics"])
    for c in out["check"].values():
        assert c["value"] <= c["limit"]


# -- Dilate ---------------------------------------------------------------

def _stage_passthrough(binding):
    # The last stage hands its input on untouched.
    _wrap(binding, "stage3", lambda inputs, body: inputs["stage2"])


def _half_batch(binding):
    # Half of the images skip the last stage.
    count = {"n": 0}

    def fault(inputs, body):
        count["n"] += 1
        return body(inputs) if count["n"] % 2 else inputs["stage2"]
    _wrap(binding, "stage3", fault)


def _dilate_altered(binding):
    _wrap(binding, "stage3",
          lambda inputs, body: body(inputs).at[3, 5].add(1.0))


@pytest.mark.parametrize("fault", [_stage_passthrough, _half_batch,
                                   _dilate_altered])
def test_dilate_fault_is_not_correct(run, monkeypatch, fault):
    broken(monkeypatch, "stencil", fault)
    out = drive(run, "dilate-1chip")
    assert not out["correct"] and out["failed"] >= 1
    assert out["check"]["max_abs_err"]["value"] > 0


# -- the exchange between chips ------------------------------------------

_FOUR_CHIPS = textwrap.dedent("""
    import importlib.util, json, sys
    spec = importlib.util.spec_from_file_location("chipbench_run", sys.argv[1])
    run = importlib.util.module_from_spec(spec)
    sys.modules["chipbench_run"] = run
    spec.loader.exec_module(run)
    import jax, jax.numpy as jnp
    import repro.exec
    seed = int(sys.argv[3])
    cell = run.resolve(run.load_spec(), "dilate-4chip")
    cell.config = dict(cell.config, grid=[16, 128])
    if sys.argv[2] == "drop":
        # The crossing is left out: tokens stay on the producer's chip.
        from repro.exec import channels, executor
        channels._put = executor._put = lambda token, device: token
    elif sys.argv[2] == "control":
        # The bfloat16 reference in the program's place.
        want = cell.app.reference(cell.config, cell.mix, seed, jnp.bfloat16)
        real = repro.exec.execute

        def execute(*args, **kwargs):
            result = real(*args, **kwargs)
            result.outputs = want
            return result
        repro.exec.execute = execute
    out = run.run_cell(cell, seed, 0.2, False, jax.devices()[:4],
                       interpret=True)
    print(json.dumps(out))
""")


@pytest.mark.parametrize("mode", ["sound", "drop", "control"])
def test_exchange_between_chips(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIPS, str(HERE / "run.py"), mode,
         str(SEED)], env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    if mode == "sound":
        assert out["correct"]
        assert out["check"]["misplaced_tasks"]["value"] == 0
    elif mode == "drop":
        assert not out["correct"]
        assert out["check"]["misplaced_tasks"]["value"] >= 1
    else:
        assert not out["correct"] and out["failed"] >= 1
        c = out["check"]["max_abs_err"]
        assert c["value"] > c["limit"]


# -- the control ----------------------------------------------------------

def control_in_place(monkeypatch, cell, seed):
    """Put the bfloat16 reference in the place of what ``execute()``
    returns: the timed path runs as it is, its answers are the control's."""
    import repro.exec
    want = cell.app.reference(cell.config, cell.mix, seed, jnp.bfloat16)
    real = repro.exec.execute

    def execute(*args, **kwargs):
        result = real(*args, **kwargs)
        result.outputs = want
        return result
    monkeypatch.setattr(repro.exec, "execute", execute)


@pytest.mark.parametrize("workload", ["dilate-1chip"])
def test_control_in_the_programs_place_is_not_correct(run, monkeypatch,
                                                      workload):
    cell = small_cell(run, workload)
    control_in_place(monkeypatch, cell, SEED)
    out = run.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                       interpret=True)
    assert not out["correct"] and out["failed"] >= 1
    c = out["check"]["max_abs_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", ["dilate-1chip"])
def test_control_fails_where_the_program_passes(run, workload):
    control = _load("chipbench_control", HERE / "control.py")
    cell = small_cell(run, workload)
    out = control.readings(cell, [SEED, SEED + 1], [SEED + 2, SEED + 3],
                           0.2, jax.devices()[:1], interpret=True)
    failed = [k for k, e in out["ends"].items()
              if e["upper"] is not None and e["upper"] > e["limit"]]
    assert failed
    for k, e in out["ends"].items():
        assert e["lower"] <= e["limit"]
