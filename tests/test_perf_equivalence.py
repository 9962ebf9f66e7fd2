"""Golden equivalence of the PR 3 fast path vs the legacy solver path.

The vectorized COO model build + halved linearization + vectorized KL must
produce the same Eq. 2 partition objective as the legacy dict-row build +
pure-Python KL (``use_reference=True``) on the paper app graphs.  The
8-device cases run at ``exact_limit=1500``: pagerank and knn then take the
recursive-bisect path, whose ``use_reference`` branch only they reach."""
import numpy as np
import pytest

from repro.apps import cnn, knn, pagerank, stencil
from repro.core import fpga_ring_cluster
from repro.core.ilp import ILPError, Model
from repro.core.partitioner import partition


def _case(mod, ndev, exact_limit=None):
    name = f"{mod.__name__.split('.')[-1]}-{ndev}"
    if exact_limit is not None:
        name += f"-limit{exact_limit}"
    return pytest.param(mod, ndev, exact_limit, id=name)


@pytest.mark.parametrize("mod,ndev,exact_limit", [
    _case(stencil, 2), _case(stencil, 4),
    _case(pagerank, 2), _case(pagerank, 4),
    _case(cnn, 2),
    _case(knn, 2),
    _case(stencil, 8, 1500), _case(pagerank, 8, 1500), _case(knn, 8, 1500),
])
def test_partition_objective_matches_legacy(mod, ndev, exact_limit):
    cl = fpga_ring_cluster(ndev)
    limit = {} if exact_limit is None else {"exact_limit": exact_limit}
    p_new = partition(mod.build_graph(ndev), cl,
                      balance_kind="LUT", balance_tol=0.8, **limit)
    p_ref = partition(mod.build_graph(ndev), cl,
                      balance_kind="LUT", balance_tol=0.8,
                      use_reference=True, **limit)
    assert p_new.comm_cost == pytest.approx(p_ref.comm_cost, rel=1e-6)
    # The drift invariant holds and Eq. 1 holds on the fast path.
    assert p_new.stats.objective == p_new.comm_cost
    caps = np.array([[cl.capacity(k) for k in p_new.kinds]
                     for _ in range(ndev)])
    assert np.all(p_new.usage <= caps + 1e-6)
    assert set(p_new.assignment) == set(p_ref.assignment)


def test_unpinned_kl_polish_matches_legacy_without_balance():
    """No balance band → the KL polish actually runs in both paths."""
    cl = fpga_ring_cluster(4)
    g_new, g_ref = stencil.build_graph(4), stencil.build_graph(4)
    p_new = partition(g_new, cl)
    p_ref = partition(g_ref, cl, use_reference=True)
    assert p_new.comm_cost == pytest.approx(p_ref.comm_cost, rel=1e-6)


def test_time_limit_degrades_to_feasible_instead_of_raising():
    """A branch-and-cut time limit too small to prove optimality now falls
    back to the HiGHS incumbent or the KL warm start (PR 3); the seed
    behaviour was an ILPError."""
    g = knn.build_graph(4)
    cl = fpga_ring_cluster(4)
    p = partition(g, cl, balance_kind="LUT", balance_tol=0.8,
                  time_limit=1e-3)
    assert set(p.assignment) == set(g.task_names())
    kinds = p.kinds
    caps = np.array([[cl.capacity(k) for k in kinds] for _ in range(4)])
    assert np.all(p.usage <= caps + 1e-6)
    assert p.stats.method.startswith("milp-exact")


def test_bulk_row_apis_match_dict_api():
    """Same tiny ILP emitted via dict rows and via the bulk COO APIs must
    produce identical solutions."""

    def build(bulk: bool) -> Model:
        m = Model("t")
        if bulk:
            x = m.add_vars(4, 0.0, 1.0, integer=True,
                           obj=np.array([1.0, 2.0, 3.0, 4.0]))
            cols = np.arange(x, x + 4)
            m.add_eq_rows(cols[None, :], np.ones((1, 4)), 2.0)
            m.add_ge_rows(np.array([[0, 1], [2, 3]]),
                          np.ones((2, 2)), 1.0)
            m.add_le_rows(np.array([[0, 3]]), np.ones((1, 2)), 1.0)
        else:
            x = [m.add_binary(obj=c) for c in (1.0, 2.0, 3.0, 4.0)]
            m.add_eq({v: 1.0 for v in x}, 2.0)
            m.add_ge({x[0]: 1.0, x[1]: 1.0}, 1.0)
            m.add_ge({x[2]: 1.0, x[3]: 1.0}, 1.0)
            m.add_le({x[0]: 1.0, x[3]: 1.0}, 1.0)
        return m

    s_dict = build(bulk=False).solve()
    s_bulk = build(bulk=True).solve()
    assert np.allclose(s_dict, s_bulk)
    assert np.allclose(s_bulk, [1.0, 0.0, 1.0, 0.0])


def test_warm_start_fallback_is_validated():
    """solve() only returns a warm start that actually satisfies the model;
    an infeasible model with a bogus warm start still raises."""
    m = Model("infeasible")
    v = m.add_binary()
    m.add_ge({v: 1.0}, 2.0)          # impossible for a binary
    with pytest.raises(ILPError):
        m.solve(warm_start=np.array([1.0]))
