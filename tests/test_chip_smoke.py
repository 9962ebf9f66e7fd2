"""``chip_smoke.py``'s phases, rehearsed on CPU at a tiny size.

The script refuses to run without a TPU; its phase functions are the code
the chip runs, driven here with the Pallas interpreter, a small grid and
a smoke-size model.
"""
import importlib.util
import pathlib

import jax
import pytest

from repro.configs import get_arch

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""                       # no result line
    assert "needs a TPU" in out.err


def _dataflow(chip_smoke, devices, device_map):
    return chip_smoke.dataflow_phase(
        devices, device_map, h=64, w=128, streams=2, seed=0,
        interpret=True, meter=chip_smoke.CompileMeter())


def test_dataflow_phase_one_device(chip_smoke):
    rec = _dataflow(chip_smoke, jax.devices()[:1], [0, 0, 0, 0])
    assert rec["bit_identical"] and rec["max_abs_err"] == 0.0
    assert rec["iterations"] == 64
    dev = jax.devices()[0]
    name = f"{dev.platform}:{dev.id}"
    assert set(rec["stage_chip"].values()) == {name}
    assert set(rec["placement"].values()) == {name}


def test_dataflow_phase_refuses_missing_devices(chip_smoke):
    pool = jax.devices()[:1] * 2               # a pool of two entries
    with pytest.raises(ValueError, match="only 2 devices"):
        _dataflow(chip_smoke, pool, [0, 1, 2, 3])


def test_serve_phase_smoke_model(chip_smoke):
    rec = chip_smoke.serve_phase(
        get_arch("qwen3-4b").smoke(), slots=2, max_len=32, prompt_len=8,
        new_tokens=4, seed=0, meter=chip_smoke.CompileMeter())
    assert rec["logit_err_scaled"] <= rec["logit_tol"]
    assert rec["argmax_agree"] == 1.0
    assert rec["new_tokens"] == 4 and rec["slots"] == 2
