"""SVEN vs coordinate descent: the paper's central correctness claim.

"Throughout all experiments and all settings of lambda2 and t we find that
glmnet and SVEN obtain identical results up to the tolerance level."
Our glmnet stand-in is the independently KKT-validated CD baseline.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines import elastic_net_cd, elastic_net_fista
from repro.core import sven, sven_path, SvenConfig
from repro.core.elastic_net import kkt_violation, lambda1_max
from repro.data.synthetic import make_regression, prostate_like

ATOL = 1e-8


def _cd_then_sven(n, p, lam2, l1_frac, seed=0, **cfg_kw):
    X, y, _ = make_regression(n, p, k_true=min(10, p // 2), rho=0.3, seed=seed)
    l1 = l1_frac * float(lambda1_max(X, y))
    beta_cd = elastic_net_cd(X, y, l1, lam2).beta
    t = float(jnp.sum(jnp.abs(beta_cd)))
    if t <= 0:
        pytest.skip("degenerate: CD selected nothing")
    sol = sven(X, y, t, lam2, SvenConfig(**cfg_kw))
    return beta_cd, sol


@pytest.mark.parametrize("n,p", [(30, 150), (40, 400), (25, 64)])
@pytest.mark.parametrize("lam2", [0.1, 1.0, 10.0])
def test_pggn_dual_matches_cd(n, p, lam2):
    beta_cd, sol = _cd_then_sven(n, p, lam2, 0.3)
    assert sol.mode == "primal"  # 2p > n -> primal per Algorithm 1
    np.testing.assert_allclose(sol.beta, beta_cd, atol=ATOL)


@pytest.mark.parametrize("n,p", [(200, 30), (500, 50), (128, 12)])
@pytest.mark.parametrize("lam2", [0.1, 1.0, 10.0])
def test_nggp_matches_cd(n, p, lam2):
    beta_cd, sol = _cd_then_sven(n, p, lam2, 0.3)
    assert sol.mode == "dual"
    np.testing.assert_allclose(sol.beta, beta_cd, atol=ATOL)


@pytest.mark.parametrize("mode", ["primal", "dual"])
@pytest.mark.parametrize("matrix_free", [True, False])
def test_modes_and_materialization_agree(mode, matrix_free):
    """Forced primal/dual and explicit/matrix-free all give the same beta."""
    beta_cd, sol = _cd_then_sven(60, 80, 1.0, 0.4, mode=mode, matrix_free=matrix_free)
    np.testing.assert_allclose(sol.beta, beta_cd, atol=ATOL)


def test_dual_fista_matches_newton():
    beta_cd, sol_fista = _cd_then_sven(200, 30, 1.0, 0.3, solver="fista", tol=1e-10)
    np.testing.assert_allclose(sol_fista.beta, beta_cd, atol=1e-6)


def test_lasso_limit():
    """lambda2 -> 0 recovers the Lasso (paper: C -> inf, hard-margin link)."""
    X, y, _ = make_regression(50, 100, k_true=6, rho=0.2, seed=2)
    lam2 = 1e-7
    l1 = 0.4 * float(lambda1_max(X, y))
    beta_cd = elastic_net_cd(X, y, l1, lam2).beta
    t = float(jnp.sum(jnp.abs(beta_cd)))
    sol = sven(X, y, t, lam2, SvenConfig(tol=1e-10))
    np.testing.assert_allclose(sol.beta, beta_cd, atol=1e-5)


def test_sparsity_pattern_is_support_vectors():
    """Selected features <-> support vectors (paper §'Feature selection')."""
    beta_cd, sol = _cd_then_sven(40, 200, 1.0, 0.3)
    p = 200
    sv = (sol.alpha[:p] + sol.alpha[p:]) > 1e-9
    selected = jnp.abs(sol.beta) > 1e-9
    assert bool(jnp.all(selected == sv))


def test_regularization_path_matches_cd_path():
    """Fig. 1: paths coincide point-for-point along the t grid."""
    X, y, _ = prostate_like()
    lam2 = 0.5
    l1max = float(lambda1_max(X, y))
    l1s = l1max * np.geomspace(0.9, 0.05, 8)
    ts, betas_cd = [], []
    for l1 in l1s:
        b = elastic_net_cd(X, y, float(l1), lam2).beta
        ts.append(float(jnp.sum(jnp.abs(b))))
        betas_cd.append(b)
    betas_sven = sven_path(X, y, ts, lam2)
    np.testing.assert_allclose(betas_sven, jnp.stack(betas_cd), atol=1e-7)


def test_kkt_of_sven_solution():
    _, sol = _cd_then_sven(35, 120, 2.0, 0.35)
    assert float(sol.kkt) < 1e-8


def test_fista_baseline_agrees_with_cd():
    X, y, _ = make_regression(100, 40, seed=5)
    l1 = 0.3 * float(lambda1_max(X, y))
    b_cd = elastic_net_cd(X, y, l1, 1.0).beta
    b_f = elastic_net_fista(X, y, l1, 1.0).beta
    np.testing.assert_allclose(b_f, b_cd, atol=1e-7)


@pytest.mark.parametrize("mode", ["primal", "dual"])
def test_pallas_backend_matches_xla(mode):
    """End-to-end SVEN with Pallas kernels (interpret mode on CPU) agrees with
    the XLA path. f32 kernels => looser tolerance than the f64 XLA tests."""
    X, y, _ = make_regression(60, 80, k_true=8, rho=0.3, seed=11)
    l1 = 0.35 * float(lambda1_max(X, y))
    beta_cd = elastic_net_cd(X, y, l1, 1.0).beta
    t = float(jnp.sum(jnp.abs(beta_cd)))
    sol = sven(X, y, t, 1.0, SvenConfig(mode=mode, backend="auto", tol=1e-6))
    np.testing.assert_allclose(sol.beta, beta_cd, atol=5e-4 * max(1.0, float(jnp.abs(beta_cd).max())))
