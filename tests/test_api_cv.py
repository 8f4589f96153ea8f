"""glmnet-parity front-end (core/api.py, core/cv.py): scaling conversions,
standardization round-trip, penalized<->constrained mapping (t = |beta*|_1,
nu = lambda1 KKT), screening-fused path scans, batched CV vs the sequential
per-fold reference, keep-mask wiring, and the engine's penalized requests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines import elastic_net_cd
from repro.baselines.coordinate_descent import cd_path
from repro.core import (ElasticNet, ElasticNetCV, api, cross_validate,
                        cross_validate_reference, enet, enet_path,
                        gap_safe_screen, lambda_grid, penalized_from_glmnet,
                        penalized_from_sklearn, penalized_to_glmnet,
                        reset_trace_counts, sven, sven_batch, trace_counts)
from repro.core.elastic_net import kkt_multiplier, lambda1_max
from repro.data.synthetic import make_regression


# ---------------------------------------------------------------------------
# scaling conventions
# ---------------------------------------------------------------------------

def test_lambda_conversions_roundtrip():
    n = 73
    for lam, alpha in [(0.3, 0.5), (1.7, 0.9), (0.05, 0.1)]:
        l1, l2 = penalized_from_glmnet(lam, alpha, n)
        assert l1 == 2.0 * n * lam * alpha and l2 == n * lam * (1 - alpha)
        lam_back, alpha_back = penalized_to_glmnet(l1, l2, n)
        assert abs(lam_back - lam) < 1e-12 and abs(alpha_back - alpha) < 1e-12
    # sklearn's (alpha, l1_ratio) is glmnet's (lambda, alpha)
    assert penalized_from_sklearn(0.3, 0.5, n) == penalized_from_glmnet(0.3, 0.5, n)


def test_conversion_argmin_invariance():
    """Minimizing the paper objective at the converted (lambda1, lambda2)
    reproduces the glmnet-objective minimizer (same argmin, checked via CD on
    the explicitly rescaled problem)."""
    X, y, _ = make_regression(50, 20, k_true=5, seed=2)
    n = X.shape[0]
    lam, alpha = 0.02, 0.7
    l1, l2 = penalized_from_glmnet(lam, alpha, n)
    beta = elastic_net_cd(X, y, l1, l2).beta
    # glmnet stationarity: 1/n x_j^T r = lam*alpha*sign + lam*(1-alpha)*b_j
    r = y - X @ beta
    act = np.asarray(jnp.abs(beta) > 1e-10)
    lhs = np.asarray((X.T @ r) / n - lam * (1 - alpha) * beta)
    rhs = lam * alpha * np.sign(np.asarray(beta))
    np.testing.assert_allclose(lhs[act], rhs[act], atol=1e-8)


# ---------------------------------------------------------------------------
# penalized -> constrained mapping
# ---------------------------------------------------------------------------

def test_enet_matches_cd_and_kkt():
    """Single penalized solves match CD to 1e-5 (dual-mode shape), and the
    mapping invariants hold: t = |beta*|_1 and the constrained-form
    multiplier at beta* equals lambda1."""
    X, y, _ = make_regression(80, 25, k_true=6, rho=0.3, seed=0)
    l1max = float(lambda1_max(X, y))
    for frac, lam2 in [(0.5, 1.0), (0.2, 0.5), (0.05, 2.0)]:
        lam1 = frac * l1max
        beta_cd = elastic_net_cd(X, y, lam1, lam2).beta
        res = enet(X, y, lam1, lam2)
        np.testing.assert_allclose(np.asarray(res.beta), np.asarray(beta_cd),
                                   atol=1e-5)
        assert abs(float(res.t) - float(jnp.abs(beta_cd).sum())) < 1e-6
        assert abs(float(res.nu) - lam1) / l1max < 1e-7
        nu_kkt = float(kkt_multiplier(X, y, res.beta, lam2))
        assert abs(nu_kkt - lam1) / l1max < 1e-6


def test_enet_path_matches_cd_40_points():
    """Acceptance: the screening-fused scan path matches warm-started CD to
    1e-5 across a 40-point lambda grid (primal-mode shape), in one trace."""
    X, y, _ = make_regression(60, 40, k_true=8, rho=0.4, seed=1)
    grid = lambda_grid(X, y, n_lambdas=40)
    reset_trace_counts()
    path = enet_path(X, y, lambda1s=grid, lambda2=1.0)
    betas_cd = cd_path(X, y, grid, 1.0)
    np.testing.assert_allclose(np.asarray(path.betas), np.asarray(betas_cd),
                               atol=1e-5)
    # top of the path: beta identically zero at lambda1_max
    assert float(jnp.abs(path.betas[0]).max()) == 0.0
    # budgets increase down the path and equal |beta|_1
    np.testing.assert_allclose(np.asarray(path.ts),
                               np.abs(np.asarray(path.betas)).sum(1), atol=1e-12)
    # one executable for the whole grid; new grid values must not retrace
    enet_path(X, y, lambda1s=grid * 0.999, lambda2=1.0)
    assert trace_counts().get("enet_path_scan") == 1


def test_enet_path_screen_on_off_identical():
    X, y, _ = make_regression(40, 90, k_true=6, rho=0.3, seed=4)
    grid = lambda_grid(X, y, n_lambdas=12)
    on = enet_path(X, y, lambda1s=grid, lambda2=0.7)
    off = enet_path(X, y, lambda1s=grid, lambda2=0.7,
                    config=api.PathConfig(screen=False))
    np.testing.assert_allclose(np.asarray(on.betas), np.asarray(off.betas),
                               atol=1e-7)
    assert int(on.n_kept.min()) < 90          # the screen actually fired
    assert int(off.n_kept.min()) == 90


# ---------------------------------------------------------------------------
# standardization / intercept round trip
# ---------------------------------------------------------------------------

def _raw_problem(seed=3):
    """Un-standardized data: scaled/shifted columns, offset response."""
    rng = np.random.default_rng(seed)
    Xs, ys, _ = make_regression(70, 15, k_true=5, seed=seed)
    scales = rng.uniform(0.5, 8.0, 15)
    shifts = rng.uniform(-3.0, 3.0, 15)
    X = np.asarray(Xs) * scales + shifts
    y = np.asarray(ys) + 4.2
    return jnp.asarray(X), jnp.asarray(y)


def test_standardize_intercept_roundtrip():
    """Fitting with standardize+intercept equals solving the manually
    standardized problem with CD and un-scaling by hand — exact round trip."""
    X, y = _raw_problem()
    lam2 = 1.0
    Xs, ys, scaler = api.standardize_fit(X, y)
    lam1 = 0.3 * float(lambda1_max(Xs, ys))

    model = ElasticNet(lam1, lam2).fit(X, y)
    beta_std = elastic_net_cd(Xs, ys, lam1, lam2).beta
    beta_ref, b0_ref = api.unscale_coef(beta_std, scaler)
    np.testing.assert_allclose(np.asarray(model.coef_), np.asarray(beta_ref),
                               atol=1e-6)
    assert abs(float(model.intercept_) - float(b0_ref)) < 1e-6
    # prediction identity: original-scale predict == standardized-space predict
    pred = model.predict(X)
    pred_std = Xs @ beta_std + scaler.y_mean
    np.testing.assert_allclose(np.asarray(pred), np.asarray(pred_std), atol=1e-6)
    # centered design => residuals are mean-zero (the intercept is unpenalized)
    assert abs(float(jnp.mean(y - pred))) < 1e-8


def test_standardize_fit_statistics():
    X, y = _raw_problem(seed=9)
    Xs, ys, scaler = api.standardize_fit(X, y)
    np.testing.assert_allclose(np.asarray(Xs.mean(0)), 0.0, atol=1e-10)
    np.testing.assert_allclose(np.asarray(jnp.sqrt(jnp.mean(Xs * Xs, 0))), 1.0,
                               atol=1e-10)
    assert abs(float(ys.mean())) < 1e-10
    # no-op mode returns the data untouched
    X2, y2, s2 = api.standardize_fit(X, y, standardize=False, fit_intercept=False)
    assert (np.asarray(X2) == np.asarray(X)).all()
    np.testing.assert_allclose(np.asarray(s2.x_scale), 1.0)


# ---------------------------------------------------------------------------
# keep-mask wiring through sven / sven_batch
# ---------------------------------------------------------------------------

def test_sven_keep_mask_matches_full_solve():
    X, y, _ = make_regression(36, 100, k_true=6, seed=7)
    lam2 = 1.0
    lam1 = 0.35 * float(lambda1_max(X, y))
    beta_star = elastic_net_cd(X, y, lam1, lam2).beta
    t = float(jnp.sum(jnp.abs(beta_star)))
    keep = gap_safe_screen(X, y, beta_star, lam1, lam2).keep
    assert 0 < int(keep.sum()) < 100
    masked = sven(X, y, t, lam2, keep=keep)
    full = sven(X, y, t, lam2)
    np.testing.assert_allclose(np.asarray(masked.beta), np.asarray(full.beta),
                               atol=1e-6)
    assert (np.asarray(masked.beta)[~np.asarray(keep)] == 0.0).all()


def test_sven_batch_keep_mask():
    """Batched keep (B, p) masks each stacked problem independently."""
    X, y, _ = make_regression(80, 24, k_true=5, seed=8)
    lam2 = 1.0
    fracs = [0.5, 0.3, 0.2]
    ts, keeps = [], []
    for f in fracs:
        lam1 = f * float(lambda1_max(X, y))
        b = elastic_net_cd(X, y, lam1, lam2).beta
        ts.append(float(jnp.abs(b).sum()))
        keeps.append(gap_safe_screen(X, y, b, lam1, lam2).keep)
    keep_b = jnp.stack(keeps)
    sol = sven_batch(X, y, jnp.asarray(ts), lam2, keep=keep_b)
    for i, t in enumerate(ts):
        ref = sven(X, y, t, lam2).beta
        np.testing.assert_allclose(np.asarray(sol.beta[i]), np.asarray(ref),
                                   atol=1e-6)
        assert (np.asarray(sol.beta[i])[~np.asarray(keep_b[i])] == 0.0).all()


# ---------------------------------------------------------------------------
# batched cross-validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,p,k_true,rho,seed,n_lambdas", [
    (84, 30, 6, 0.3, 5, 40),
    (120, 32, 8, 0.4, 11, 8),
])
def test_cv_matches_sequential_reference_and_trace_budget(n, p, k_true, rho,
                                                          seed, n_lambdas):
    """Acceptance: the batched CV surface equals the sequential per-fold loop,
    lambda selection agrees, the refit matches CD to 1e-5, and the whole
    screening-fused CV driver costs at most 2 traces (scan + refit)."""
    X, y, _ = make_regression(n, p, k_true=k_true, rho=rho, seed=seed)
    kw = dict(k=4, n_lambdas=n_lambdas, lambda2=1.0,
              standardize=False, fit_intercept=False)
    reset_trace_counts()
    res = cross_validate(X, y, **kw)
    counts = trace_counts()
    assert counts.get("enet_cv_scan", 0) == 1
    assert counts.get("enet_cv_scan", 0) + counts.get("enet", 0) <= 2

    lam1s, mse_ref = cross_validate_reference(X, y, **kw)
    np.testing.assert_allclose(np.asarray(res.mse_path), np.asarray(mse_ref),
                               atol=1e-10)
    assert res.index_min == int(jnp.argmin(mse_ref.mean(1)))

    beta_cd = elastic_net_cd(X, y, res.lambda_min, 1.0).beta
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(beta_cd),
                               atol=1e-5)


def test_elastic_net_cv_estimator():
    X, y = _raw_problem(seed=6)
    cv = ElasticNetCV(k=4, n_lambdas=12, lambda2=1.0).fit(X, y)
    assert cv.mse_path_.shape == (12, 4)
    assert float(cv.mean_mse_.min()) == float(cv.mean_mse_[int(jnp.argmin(cv.mean_mse_))])
    assert cv.lambda_min_ == float(cv.lambda1s_[int(jnp.argmin(cv.mean_mse_))])
    # predictions at lambda_min beat the null model on the training data
    mse_fit = float(jnp.mean((cv.predict(X) - y) ** 2))
    assert mse_fit < float(jnp.var(y))


# ---------------------------------------------------------------------------
# serving: penalized-form requests
# ---------------------------------------------------------------------------

def test_engine_penalized_requests():
    from repro.serve import ElasticNetEngine

    engine = ElasticNetEngine()
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(3):
        n = int(rng.integers(24, 60))
        p = int(rng.integers(10, 40))
        X, y, _ = make_regression(n, p, k_true=5, seed=i)
        lam1 = 0.3 * float(lambda1_max(X, y))
        reqs.append((X, y, lam1, 1.0))
    # mix forms in one drain: penalized and constrained bucket separately
    ids_pen = [engine.submit_penalized(*r) for r in reqs]
    X0, y0, lam10, _ = reqs[0]
    id_con = engine.submit(X0, y0, 1.0, 1.0)
    out = engine.drain()
    for (X, y, lam1, lam2), rid in zip(reqs, ids_pen):
        beta_cd = elastic_net_cd(X, y, lam1, lam2).beta
        got = np.asarray(out[rid].beta)
        np.testing.assert_allclose(got, np.asarray(beta_cd), atol=1e-5)
        assert got.shape == (X.shape[1],)      # unpadded back to the request p
    ref = sven(X0, y0, 1.0, 1.0).beta
    np.testing.assert_allclose(np.asarray(out[id_con].beta), np.asarray(ref),
                               atol=1e-6)
