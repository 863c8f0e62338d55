"""Reference proximal-gradient solver behaviour (core/prox.py)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal CPU image — deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.core import graphs
from repro.core.objective import full_objective_cov
from repro.core.prox import fit_reference


@pytest.fixture(scope="module")
def chain_problem():
    return graphs.make_problem("chain", p=48, n=150, seed=1)


def test_cov_obs_converge_to_same_solution(chain_problem):
    p = chain_problem
    r1 = fit_reference(jnp.asarray(p.s), 0.15, 0.05, tol=1e-6,
                       max_iters=300)
    r2 = fit_reference(jnp.asarray(p.x), 0.15, 0.05, variant="obs",
                       tol=1e-6, max_iters=300)
    assert bool(r1.converged) and bool(r2.converged)
    np.testing.assert_allclose(np.asarray(r1.omega), np.asarray(r2.omega),
                               atol=2e-3)


def test_objective_decreases(chain_problem):
    """F(Omega_hat) must be below F(Omega_0) = F(I)."""
    p = chain_problem
    r = fit_reference(jnp.asarray(p.s), 0.2, 0.05, tol=1e-6)
    f0 = full_objective_cov(jnp.eye(p.s.shape[0]), jnp.asarray(p.s),
                            0.2, 0.05)
    fhat = full_objective_cov(r.omega, jnp.asarray(p.s), 0.2, 0.05)
    assert float(fhat) < float(f0)


def test_solution_is_fixed_point(chain_problem):
    """prox step at the solution returns (approximately) the solution."""
    from repro.core.objective import gradient_from_w, prox_l1_offdiag
    p = chain_problem
    lam1, lam2 = 0.2, 0.05
    r = fit_reference(jnp.asarray(p.s), lam1, lam2, tol=1e-7, max_iters=500)
    om = r.omega
    grad = gradient_from_w(om, om @ jnp.asarray(p.s), lam2)
    tau = 1e-3
    step = prox_l1_offdiag(om - tau * grad, tau * lam1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(om), atol=5e-4)


def test_diagonal_stays_positive(chain_problem):
    p = chain_problem
    r = fit_reference(jnp.asarray(p.s), 0.15, 0.0, tol=1e-6)
    assert np.all(np.diag(np.asarray(r.omega)) > 0)


def test_symmetry_preserved(chain_problem):
    p = chain_problem
    r = fit_reference(jnp.asarray(p.s), 0.15, 0.05, tol=1e-6)
    a = np.asarray(r.omega)
    np.testing.assert_allclose(a, a.T, atol=1e-5)


@given(st.floats(0.1, 0.6))
@settings(max_examples=8, deadline=None)
def test_sparsity_monotone_in_lam1(lam1):
    """Larger lam1 => no more edges (path monotonicity, statistical
    sanity of the estimator)."""
    p = graphs.make_problem("chain", p=32, n=100, seed=3)
    r1 = fit_reference(jnp.asarray(p.s), lam1, 0.05, tol=1e-5)
    r2 = fit_reference(jnp.asarray(p.s), lam1 + 0.2, 0.05, tol=1e-5)
    assert graphs.edge_count(np.asarray(r2.omega)) <= \
        graphs.edge_count(np.asarray(r1.omega)) + 2  # small slack


def test_support_recovery_chain():
    """On an easy chain problem the estimator finds mostly true edges
    (qualitative Table-1 check)."""
    p = graphs.make_problem("chain", p=64, n=400, seed=5)
    r = fit_reference(jnp.asarray(p.s), 0.22, 0.02, tol=1e-6, max_iters=400)
    ppv, fdr = graphs.ppv_fdr(np.asarray(r.omega), p.omega0)
    assert ppv > 0.85, f"PPV too low: {ppv}"


def test_warm_start_tau_reduces_ls_trials():
    p = graphs.make_problem("chain", p=48, n=150, seed=2)
    r0 = fit_reference(jnp.asarray(p.s), 0.15, 0.05, tol=1e-6)
    r1 = fit_reference(jnp.asarray(p.s), 0.15, 0.05, tol=1e-6,
                       warm_start_tau=True)
    # same solution
    np.testing.assert_allclose(np.asarray(r0.omega), np.asarray(r1.omega),
                               atol=2e-3)


def test_nongaussian_data_still_recovers():
    """CONCORD's pseudolikelihood makes no Gaussianity assumption."""
    p = graphs.make_problem("chain", p=48, n=400, seed=7, gaussian=False)
    r = fit_reference(jnp.asarray(p.s), 0.35, 0.02, tol=1e-5, max_iters=300)
    ppv, _ = graphs.ppv_fdr(np.asarray(r.omega), p.omega0)
    assert ppv > 0.7


@pytest.mark.parametrize("variant", ["cov", "obs"])
def test_use_pallas_runs_the_kernel_prox_without_sparse_matmul(
        chain_problem, variant):
    """use_pallas=True with the dense product route still runs the fused
    prox kernel (a pallas_call in the solve) and matches the jnp prox."""
    import jax

    from repro.core import prox
    from repro.core.penalty import PenaltySpec
    p = chain_problem
    data = jnp.asarray(p.s if variant == "cov" else p.x)
    spec = PenaltySpec("l1", 0.15, 0.05)

    def solve(use_pallas):
        return prox._solve_reference(
            data, spec, None, variant=variant, tol=1e-6, max_iters=300,
            max_ls=30, warm_start_tau=False, sparse_matmul=None,
            use_pallas=use_pallas)

    jaxpr = str(jax.make_jaxpr(lambda: solve(True))())
    assert "pallas_call" in jaxpr
    r_k, r_j = solve(True), solve(False)
    assert bool(r_k.converged) and bool(r_j.converged)
    np.testing.assert_allclose(np.asarray(r_k.omega), np.asarray(r_j.omega),
                               rtol=0, atol=1e-5)
