"""Vectorized kl_refine vs the pure-Python reference: capacity safety,
pin immobility, and accepted-move quality (PR 3 satellite)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis or skip

from repro.core.ilp import kl_refine, kl_refine_reference


def ring_pair_cost(ndev):
    return np.array([[min(abs(i - j), ndev - abs(i - j))
                      for j in range(ndev)] for i in range(ndev)],
                    dtype=float)


def objective(assign, edges, pair_cost):
    return sum(w * pair_cost[assign[u], assign[v]] for u, v, w in edges)


def random_instance(data, min_nodes=4, max_nodes=40):
    ndev = data.draw(st.integers(2, 6))
    nv = data.draw(st.integers(min_nodes, max_nodes))
    nodes = [f"n{i}" for i in range(nv)]
    assign = {n: data.draw(st.integers(0, ndev - 1)) for n in nodes}
    ne = data.draw(st.integers(0, nv * 3))
    edges = [(nodes[data.draw(st.integers(0, nv - 1))],
              nodes[data.draw(st.integers(0, nv - 1))],
              float(data.draw(st.integers(1, 128))))
             for _ in range(ne)]
    nk = data.draw(st.integers(1, 3))
    area = {n: np.array([data.draw(st.floats(0.5, 8.0))
                         for _ in range(nk)]) for n in nodes}
    # Loose enough that refinement has room, tight enough to bind sometimes.
    caps = np.full((ndev, nk), float(nv * 8 // ndev + 10))
    # The refiner keeps a feasible start feasible; it does not repair an
    # infeasible one, so raise a device's cap to what the start put there.
    start = np.zeros((ndev, nk))
    for n, d in assign.items():
        start[d] += area[n]
    caps = np.maximum(caps, start)
    return assign, edges, ring_pair_cost(ndev), area, caps, ndev, nk


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_capacity_never_violated(data):
    assign, edges, pc, area, caps, ndev, nk = random_instance(data)
    out = kl_refine(assign, edges, pc, area, caps)
    usage = np.zeros((ndev, nk))
    for v, d in out.items():
        usage[d] += area[v]
    assert np.all(usage <= caps + 1e-6)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_pinned_tasks_never_moved(data):
    assign, edges, pc, area, caps, ndev, nk = random_instance(data)
    nodes = list(assign)
    pinned = nodes[::3]
    out = kl_refine(assign, edges, pc, area, caps, pinned=pinned)
    for n in pinned:
        assert out[n] == assign[n]


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_vectorized_no_worse_than_reference(data):
    assign, edges, pc, area, caps, ndev, nk = random_instance(data)
    ref = kl_refine_reference(assign, edges, pc, area, caps)
    vec = kl_refine(assign, edges, pc, area, caps)
    assert (objective(vec, edges, pc)
            <= objective(ref, edges, pc) + 1e-6)


def test_identical_decisions_on_integer_costs():
    """On integer-valued widths/distances the two refiners make the exact
    same greedy move sequence, not just equal-quality ones."""
    rng = np.random.default_rng(3)
    ndev, nv = 5, 64
    nodes = [f"n{i}" for i in range(nv)]
    assign = {n: int(rng.integers(0, ndev)) for n in nodes}
    edges = [(nodes[int(rng.integers(nv))], nodes[int(rng.integers(nv))],
              float(rng.integers(1, 64))) for _ in range(nv * 3)]
    area = {n: rng.integers(1, 6, 2).astype(float) for n in nodes}
    caps = np.full((ndev, 2), float(nv * 6 // ndev + 8))
    pc = ring_pair_cost(ndev)
    assert (kl_refine(assign, edges, pc, area, caps)
            == kl_refine_reference(assign, edges, pc, area, caps))


def test_self_loops_and_empty_inputs():
    pc = ring_pair_cost(3)
    area = {"a": np.array([1.0]), "b": np.array([1.0])}
    caps = np.full((3, 1), 10.0)
    # Self-loop edges are ignored (cost is device-local either way).
    out = kl_refine({"a": 0, "b": 2}, [("a", "a", 9.0), ("a", "b", 4.0)],
                    pc, area, caps)
    assert objective(out, [("a", "b", 4.0)], pc) == 0.0
    assert kl_refine({}, [], pc, {}, caps) == {}
