"""KNN benchmark — paper §3 + §5.4 (CHIP-KNN [44]).

Topology (Fig. 4): blue distance modules streaming the dataset from HBM,
yellow top-K sorters, one green aggregator.  All FPGAs except the aggregator
run completely independently on their data shard (§5.4), and inter-FPGA
volume depends only on K — constant over the search space.

Mechanisms:
* Routability gate (§3): single FPGA routes only 256-bit ports / 32 KB
  buffers ⇒ 51.2% per-bank saturation; the 512-bit/128 KB config fails
  routing on one device but routes when spread over ≥2.
* Distance phase is memory-bound (N·D·4 bytes streamed), sort phase is
  O(N·K) compute, aggregation O(ndev·K).
* Frequencies (§5.4): Vitis 165, TAPA 198, TAPA-CS 220 MHz.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import ResourceProfile, Task, TaskGraph

FREQS = {"F1-V": 165e6, "F1-T": 198e6, "FCS": 220e6}
K = 10
# Blue-module scaling (§5.4): 27 modules on one FPGA; 36/54/72 on 2/3/4.
BLUE = {1: 27, 2: 36, 3: 54, 4: 72, 8: 144}
SORT_CPP = 1.0      # sort cycles per point (O(N·K/PEs) with K folded in)


def hbm_eff(port_bits: int) -> float:
    return min(port_bits / 500.0, 1.0)


def design(ndev: int) -> dict:
    return {"blue": BLUE.get(ndev, 18 * ndev),
            "port": 256 if ndev == 1 else 512,
            "buffer_kb": 32 if ndev == 1 else 128}


def build_graph(ndev: int, n_points: int = 4_000_000, dim: int = 16
                ) -> TaskGraph:
    d = design(ndev)
    g = TaskGraph(f"knn-N{n_points}-D{dim}-x{ndev}")
    per_blue = n_points / d["blue"]
    for b in range(d["blue"]):
        g.add_task(Task(f"dist{b}", ResourceProfile(
            {"LUT": 22000, "DSP": 96, "BRAM": 40}),
            hbm_bytes=per_blue * dim * 4,
            meta={"cycles": per_blue * dim / 8,
                  "ops": 3 * per_blue * dim}))
    n_sort = max(1, d["blue"] // 3)
    for s in range(n_sort):
        g.add_task(Task(f"sort{s}", ResourceProfile(
            {"LUT": 15000, "DSP": 10, "BRAM": 30}),
            meta={"cycles": SORT_CPP * n_points / n_sort,
                  "ops": K * n_points / n_sort}))
    g.add_task(Task("agg", ResourceProfile({"LUT": 8000, "BRAM": 10}),
                    meta={"cycles": 1000.0 * ndev, "ops": K * 100}))
    for b in range(d["blue"]):
        s = b % n_sort
        g.add_channel(f"dist{b}", f"sort{s}", width_bits=512,
                      bytes_per_step=per_blue * 8)
    for s in range(n_sort):
        # Only K survivors cross to the aggregator — the paper's insight.
        g.add_channel(f"sort{s}", "agg", width_bits=64,
                      bytes_per_step=K * 8)
    return g


def modeled_latency(ndev: int, freq: float, n_points: int = 4_000_000,
                    dim: int = 16, devices_per_node: int = 4) -> float:
    d = design(ndev)
    shard = n_points / ndev
    # Distance phase: memory-bound stream of the shard, port-gated.
    dist_m = shard * dim * 4 / (460e9 * hbm_eff(d["port"]))
    dist_c = (shard * dim / 8) / ((d["blue"] / ndev) * freq)
    # Sort phase overlaps distance streaming (dataflow); aggregator adds a
    # small serial tail + K-sized transfers (constant in N, D).
    phase = max(dist_m, dist_c, SORT_CPP * shard / freq / (d["blue"] / 3))
    agg = 1e-4 + (ndev - 1) * (K * 8 / 12.5e9 + 1e-6)
    return phase + agg


def speedup_table(n_list=(1_000_000, 4_000_000, 8_000_000),
                  d_list=(2, 16, 128)) -> Dict[str, float]:
    out = {"F1-T": [], "F2": [], "F3": [], "F4": []}
    for n in n_list:
        for dim in d_list:
            base = modeled_latency(1, FREQS["F1-V"], n, dim)
            out["F1-T"].append(
                base / modeled_latency(1, FREQS["F1-T"], n, dim))
            for nd, key in ((2, "F2"), (3, "F3"), (4, "F4")):
                out[key].append(
                    base / modeled_latency(nd, FREQS["FCS"], n, dim))
    return {k: float(np.mean(v)) for k, v in out.items()}


# -- runnable numerics --------------------------------------------------------

def run_numeric(n: int = 2048, dim: int = 16, q: int = 32, k: int = K,
                seed: int = 0):
    """Runnable reduced-scale KNN on the fused Pallas kernel."""
    from ..kernels import knn_op
    rng = jax.random.PRNGKey(seed)
    data = jax.random.normal(rng, (n, dim), jnp.float32)
    queries = jax.random.normal(jax.random.fold_in(rng, 1), (q, dim),
                                jnp.float32)
    return knn_op(queries, data, k=k, block_q=min(32, q),
                  block_n=min(512, n))


@functools.partial(jax.jit,
                   static_argnames=("k", "n_valid", "block_n", "interpret"))
def knn_shard(queries, shard, offset, *, k: int, n_valid: int, block_n: int,
              interpret: Optional[bool] = None):
    """One blue module: the fused distance + top-k kernel over a shard
    already padded to a multiple of ``block_n`` (its first ``n_valid``
    rows are points), with local indices made global by ``offset``."""
    from ..kernels import knn_op
    d, i = knn_op(queries, shard, k=k, block_n=block_n, n_valid=n_valid,
                  interpret=interpret)
    return d, i + offset


@functools.partial(jax.jit, static_argnames="k")
def knn_merge(parts, k: int):
    """A sorter or the aggregator: the ``k`` smallest of its inputs'
    ``(dists, global_idx)`` candidates, ascending."""
    d = jnp.concatenate([p[0] for p in parts], axis=1)
    gi = jnp.concatenate([p[1] for p in parts], axis=1)
    d, gi = jax.lax.sort((d, gi), dimension=1, num_keys=1)
    return d[:, :k], gi[:, :k]


def bind_programs(graph: TaskGraph, spec=None):
    """Executable bodies for the CHIP-KNN graph (repro.exec hook).

    Each blue ``dist{b}`` module owns a contiguous dataset shard (the
    ranges of ``np.array_split``) and emits its local top-k candidates on
    the fused Pallas kernel (paper Fig. 4: only K survivors per module
    cross a channel); ``sort{s}`` merges its blues, ``agg`` merges the
    sorters, both in the one jitted ``knn_merge`` — the distributed merge
    of per-shard top-k equals the global top-k.  Shards are sliced and
    padded to a multiple of the kernel's block once, here, on the host,
    and a blue's shard is placed on the chip it first fires on: no firing
    pads, gathers or moves it between chips, and no device holds the
    dataset beside its shards.  ``reference()`` regenerates the dataset
    from the seed and runs the plain jnp search in float32 (``knn_ref``,
    products at ``HIGHEST``), one query batch at a time.

    ``spec`` sets the numeric scale independently of the graph's modeled
    §5.4 scale: ``n`` points (default 1024; the paper's 4M), ``dim`` (8;
    16), ``q`` queries a batch (8), ``k`` (``K``), ``streams`` (query
    batches, 2), ``seed`` and ``interpret`` (the kernel's Pallas interpret
    mode; None interprets on CPU only).  Every shard must hold ``k``
    points.
    """
    from ..exec.programs import SOURCE_KEY, ProgramBinding
    from ..kernels.knn.kernel import DEFAULT_BLOCK_N
    from ..kernels.knn.ref import knn_ref

    spec = dict(spec or {})
    n = spec.get("n", 1024)
    dim = spec.get("dim", 8)
    q = spec.get("q", 8)
    k = spec.get("k", K)
    streams = spec.get("streams", 2)
    seed = spec.get("seed", 0)
    interpret = spec.get("interpret")
    blues = sorted((t for t in graph.tasks if t.startswith("dist")),
                   key=lambda t: int(t[len("dist"):]))
    sorters = sorted((t for t in graph.tasks if t.startswith("sort")),
                     key=lambda t: int(t[len("sort"):]))
    if n < k * len(blues):
        raise ValueError(f"{n} points over {len(blues)} blues leave a shard "
                         f"with fewer than k={k}")

    rng = jax.random.PRNGKey(seed)

    def points():
        return jax.random.normal(rng, (n, dim), jnp.float32)

    host = np.asarray(points())
    queries = [jax.random.normal(jax.random.fold_in(rng, 1 + t), (q, dim),
                                 jnp.float32) for t in range(streams)]
    sizes = [len(s) for s in np.array_split(np.arange(n), len(blues))]
    bounds = np.cumsum([0] + sizes).tolist()
    block_n = min(DEFAULT_BLOCK_N, -(-max(sizes) // 8) * 8)
    rows = -(-max(sizes) // block_n) * block_n

    def dist_body(lo, hi):
        shard = np.pad(host[lo:hi], ((0, rows - (hi - lo)), (0, 0)))
        placed = {}

        def body(inputs):
            qs = inputs[SOURCE_KEY]
            dev = next(iter(qs.devices()))
            if dev not in placed:
                placed[dev] = jax.device_put((shard, jnp.int32(lo)), dev)
            x, offset = placed[dev]
            return knn_shard(qs, x, offset, k=k, n_valid=hi - lo,
                             block_n=block_n, interpret=interpret)
        return body

    def merge_body(inputs):
        return knn_merge(tuple(inputs.values()), k)

    programs = {name: dist_body(bounds[b], bounds[b + 1])
                for b, name in enumerate(blues)}
    programs.update({name: merge_body for name in sorters + ["agg"]})

    def reference():
        data = points()
        outs = [knn_ref(qs, data, k) for qs in queries]
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))

    def finalize(sinks):
        return (jnp.stack([d for d, _ in sinks["agg"]]),
                jnp.stack([i for _, i in sinks["agg"]]))

    return ProgramBinding(
        graph=graph, programs=programs, iterations=streams,
        source_inputs={b: queries for b in blues},
        finalize=finalize, reference=reference, atol=1e-4)
