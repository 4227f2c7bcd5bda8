"""The OPT-alpha numbers against the system's solver and broken weights."""
import numpy as np
import pytest

from chipbench import faults, opt_alpha_ref
from repro.core import opt_alpha


def _channel(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 12))
    adj = np.triu(rng.random((n, n)) < 0.4, 1)
    p = rng.uniform(0.05, 0.95, n)
    if seed % 3 == 0:
        p[rng.integers(n)] = 0.0
    if seed % 4 == 0:
        p[rng.integers(n)] = 1.0
    return p, adj | adj.T


def _numbers(A, p, adj, active=None):
    return opt_alpha_ref.solve_numbers([{"A": A, "p": p, "adj": adj, "active": active}])


@pytest.mark.parametrize("seed", range(8))
def test_a_converged_solve_reads_optimal_and_its_start_does_not(seed):
    p, adj = _channel(seed)
    best = opt_alpha.optimize(p, adj, sweeps=500, tol=1e-15, method="exact").A
    got = _numbers(best, p, adj)
    assert got["alpha_unbiased"] < 1e-12 and got["alpha_off_support"] == 0.0
    assert got["alpha_excess"] < 1e-6
    # the gap bounds the excess over the least variance from above
    start = opt_alpha.initial_weights(p, adj)
    sup = opt_alpha_ref.support(p, adj)
    excess = opt_alpha_ref.variance(p, start) - opt_alpha_ref.variance(p, best)
    assert opt_alpha_ref.optimality_gap(p, start, sup) >= excess - 1e-12


def test_an_unconverged_solve_reads_far_from_optimal():
    p = np.array([0.2, 0.9, 0.5, 0.7, 0.3])
    adj = np.ones((5, 5), bool) & ~np.eye(5, dtype=bool)
    start = opt_alpha.initial_weights(p, adj)
    assert _numbers(start, p, adj)["alpha_excess"] > 0.5


def test_biased_and_misplaced_weights_are_read():
    p, adj = _channel(5)
    A = opt_alpha.optimize(p, adj, method="exact").A
    assert _numbers(1.1 * A, p, adj)["alpha_unbiased"] == pytest.approx(0.1)
    off = np.argwhere(~opt_alpha_ref.support(p, adj))[0]
    bad = A.copy()
    bad[tuple(off)] = 0.25
    assert _numbers(bad, p, adj)["alpha_off_support"] == pytest.approx(0.25)


def test_a_cohort_solve_is_held_on_its_block():
    p, adj = _channel(6)
    active = np.zeros(len(p), bool)
    active[: len(p) // 2 + 1] = True
    A = opt_alpha.optimize_masked(p, adj, active, method="exact", sweeps=500, tol=1e-15).A
    got = _numbers(A, p, adj, active)
    assert got["alpha_off_support"] == 0.0 and got["alpha_unbiased"] < 1e-12
    assert got["alpha_excess"] < 1e-6


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    from chipbench.tests.tiny import tiny_root

    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS if f.startswith("alpha_")])
def test_run_with_the_solve_broken_is_not_correct(tiny, fault):
    from chipbench.tests.test_chipbench_faults import run_with

    result = run_with(tiny, fault)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["step1"]["value"] < 1e-4  # the rounds agree: the solve fails
