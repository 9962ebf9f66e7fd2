"""Firings queue on their devices: a run waits once, on the sinks' outputs.

The Dilate chain runs with its four logical devices on one device and over
four emulated ones (in a child process: the device count is fixed when JAX
starts), through ``execute()`` and as the one tenant of a
``TenantServer``.  Each run must match the plain reference bit for bit,
leave every task's outputs on the device its logical device maps to, and
count at most ``firings - 1`` queued firings.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import stencil
from repro.compiler import compile as tapa_compile
from repro.core import fpga_ring_cluster
from repro.exec import bind_programs, execute, executor
from repro.net import cluster_fabric
from repro.tenants import Tenant, TenantServer

HERE = pathlib.Path(__file__).resolve().parent
NDEV = 4
SPEC = {"h": 16, "w": 128, "streams": 3, "stage_iters": 2}
PATHS = ("execute", "tenant")

_CHILD = """
import json, sys
sys.path.insert(0, sys.argv[1])
import test_exec_queued as t
print(json.dumps({path: t.run_case(path) for path in t.PATHS}))
"""


def run_case(path):
    """One run of the chain over ``jax.devices()``, logical device d on
    device d mod the pool's size; what the tests hold it to."""
    graph = stencil.build_graph(NDEV, iters=64)
    design = tapa_compile(graph, fpga_ring_cluster(NDEV))
    binding = bind_programs(graph, SPEC)
    pool = jax.devices()
    device_map = [d % len(pool) for d in range(NDEV)]
    if path == "execute":
        result = execute(design, binding, device_map=device_map)
    else:
        tenant = Tenant("t", design, device_map=device_map, inputs=SPEC)
        served = TenantServer(cluster_fabric(fpga_ring_cluster(NDEV)),
                              [tenant]).run()
        result = served.record("t").result
    report = result.report
    assign = design.partition.assignment
    return {
        "bit_exact": bool(np.array_equal(np.asarray(result.outputs),
                                         np.asarray(binding.reference()))),
        "task_devices": report.task_devices,
        "expected": {t: [executor.device_name(pool[device_map[assign[t]]])]
                     for t in graph.tasks},
        "queued": report.queued_firings,
        "summary_queued": report.summary()["queued_firings"],
        "firings": sum(report.device_fired.values()),
    }


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parent / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", _CHILD, str(HERE)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("path", PATHS)
def test_queued_run_matches_reference(path, devices, request):
    rec = (run_case(path) if devices == 1
           else request.getfixturevalue("four_devices")[path])
    assert rec["bit_exact"]
    assert rec["task_devices"] == rec["expected"]
    if devices == 4:
        assert len({d for names in rec["task_devices"].values()
                    for d in names}) == 4
    assert rec["firings"] == NDEV * SPEC["streams"]
    assert 0 <= rec["queued"] <= rec["firings"] - 1
    assert rec["summary_queued"] == rec["queued"]


@pytest.mark.parametrize("ready", [False, True])
def test_queued_firings_counts_firings_behind_a_busy_device(monkeypatch,
                                                            ready):
    # Every logical device on one device: each firing but the first finds
    # the previous one still computing, or none does.
    monkeypatch.setattr(executor, "_ready", lambda token: ready)
    rec = run_case("execute")
    assert rec["bit_exact"]
    assert rec["queued"] == (0 if ready else rec["firings"] - 1)


def test_a_donated_output_counts_as_computed():
    donated = jnp.ones(8)
    kept = jax.jit(lambda x: x + 1, donate_argnums=0)(donated)
    assert donated.is_deleted()
    assert executor._ready({"a": donated, "b": kept.block_until_ready(),
                            "c": 1.0})
