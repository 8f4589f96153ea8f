"""The solver's own counters and stop causes (core/svm, core/sven.py,
core/api.py), the path log they feed (obs/solve.py), the cold re-bracket of
a collapsed root-find, and host spans in a jax profile (obs/trace.py)."""
import importlib
import inspect
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, cross_validate, enet, enet_batch, enet_path
from repro.core.reduction import SvenOperator, gram_blocks, svm_C
from repro.core.sven import SvenConfig
from repro.core.svm import primal_newton, solve_dual_newton, solve_primal_newton
from repro.data.synthetic import make_regression
from repro.obs import Tracer, default_solve_log, enable_tracing, get_tracer
from repro.obs.metrics import default_registry
from repro.obs.solve import STOP_CAUSES

ROOT = Path(__file__).resolve().parents[1]
# `repro.core.sven` the attribute is the function; the module holds the constant
sven_mod = importlib.import_module("repro.core.sven")

#: the loop bodies of `_cg` and `_masked_cg`
_CG_BODIES = {"_cg.<locals>.body", "_masked_cg.<locals>.body"}


class _CgCount:
    """Counts, in Python, the calls a CG loop body makes to a wrapped
    mat-vec: one per CG iteration, with jit disabled (every lax loop is then
    a Python loop)."""

    def __init__(self):
        self.n = 0

    def wrap(self, fn):
        def counted(*a):
            f = sys._getframe(1)
            while f is not None:
                if f.f_code.co_qualname in _CG_BODIES:
                    self.n += 1
                    break
                f = f.f_back
            return fn(*a)
        return counted


@pytest.mark.parametrize("mode", ["primal", "dual"])
def test_cg_steps_equal_python_loop_count_in_the_solvers(mode):
    n, p = (20, 40) if mode == "primal" else (60, 8)
    X, y, _ = make_regression(n, p, k_true=4, seed=3)
    t = 0.5 * float(jnp.sum(jnp.abs(jnp.linalg.lstsq(X, y)[0])))
    op = SvenOperator(X=X, y=y, t=jnp.asarray(t, X.dtype))
    C = svm_C(1.0)
    count = _CgCount()
    with jax.disable_jit():
        if mode == "primal":
            yhat = jnp.concatenate([jnp.ones((p,)), -jnp.ones((p,))])
            res = solve_primal_newton(count.wrap(op.xhat_matvec),
                                      op.xhat_rmatvec, yhat, C, n, tol=1e-10)
        else:
            K = gram_blocks(X, y, t)
            res = solve_dual_newton(count.wrap(lambda v: K @ v), 2 * p, C,
                                    tol=1e-10)
    assert int(res.iters) >= 2
    assert count.n > int(res.iters)
    assert int(res.cg_steps) == count.n


@pytest.mark.parametrize("mode", ["primal", "primal_matrix_free", "dual"])
def test_path_cg_steps_equal_python_loop_count(mode, monkeypatch):
    """Through `_sven_core` and the Illinois loop: a path's cg_steps sum to
    the CG iterations its solves ran. At n = 20 the primal solve runs CG on
    the explicit Hessian, so the count is of the CG body's calls to the
    operator `_cg` is handed (H @ v); `primal_matrix_free` lowers the
    explicit form's limit below n and counts Xhat products as before."""
    n, p = (60, 8) if mode == "dual" else (20, 40)
    X, y, _ = make_regression(n, p, k_true=4, seed=4)
    count = _CgCount()
    if mode == "primal":
        cg = primal_newton._cg
        monkeypatch.setattr(primal_newton, "_cg",
                            lambda mv, *a: cg(count.wrap(mv), *a))
    else:
        if mode == "primal_matrix_free":
            monkeypatch.setattr(sven_mod, "EXPLICIT_HESSIAN_MAX_N", n - 1)
        name = "kernel_matvec" if mode == "dual" else "xhat_matvec"
        monkeypatch.setattr(SvenOperator, name,
                            count.wrap(getattr(SvenOperator, name)))
    forms = default_registry().counter("sven_hessian_form_total",
                                       labelnames=("form",))
    explicit0 = forms.value(form="explicit")
    config = api.PathConfig(solver=SvenConfig(tol=1e-10, cache_kernel="never"))
    with jax.disable_jit():
        path = enet_path(X, y, n_lambdas=3, config=config)
    assert (forms.value(form="explicit") > explicit0) == (mode == "primal")
    cg = np.asarray(path.cg_steps)
    assert cg[0] == 0 and np.all(cg[1:] > 0)
    assert int(cg.sum()) == count.n


def test_fista_solver_reads_zero_cg_steps():
    X, y, _ = make_regression(60, 8, k_true=4, seed=5)
    lam1 = 0.3 * float(api.en.lambda1_max(X, y))
    res = enet(X, y, lam1, 1.0, config=api.PathConfig(
        solver=SvenConfig(tol=1e-8, solver="fista")))
    assert int(res.evals) > 0 and int(res.cg_steps) == 0


def test_stop_codes_top_of_path_and_converged_path():
    X, y, _ = make_regression(40, 60, k_true=5, seed=6)
    path = enet_path(X, y, n_lambdas=6, eps=0.05)
    stop = np.asarray(path.stop)
    assert stop.dtype == np.int8
    assert stop[0] == api.STOP_NO_ROOT
    assert np.all(stop[1:] == api.STOP_ROOT)
    lmax = float(api.en.lambda1_max(X, y))
    assert int(enet(X, y, 1.5 * lmax, 1.0).stop) == api.STOP_NO_ROOT
    assert int(enet(X, y, 0.2 * lmax, 1.0).stop) == api.STOP_ROOT
    assert STOP_CAUSES[api.STOP_ROOT] == "root"
    assert STOP_CAUSES[api.STOP_BRACKET] == "bracket"
    assert STOP_CAUSES[api.STOP_EVALS] == "max_evals"


def test_cv_and_batch_results_carry_the_counters():
    X, y, _ = make_regression(60, 30, k_true=5, seed=9)
    cv = cross_validate(X, y, k=3, n_lambdas=4, mesh=None)
    assert cv.cg_steps.shape == cv.stop.shape == cv.evals.shape == (4, 3)
    assert np.all(np.asarray(cv.stop)[1:] == api.STOP_ROOT)
    assert np.all(np.asarray(cv.cg_steps)[1:] > 0)
    lmax = float(api.en.lambda1_max(X, y))
    pts = enet_batch(X, y, jnp.asarray([1.5, 0.5, 0.1]) * lmax, 1.0)
    np.testing.assert_array_equal(pts.stop, [api.STOP_NO_ROOT,
                                             api.STOP_ROOT, api.STOP_ROOT])
    cg = np.asarray(pts.cg_steps)
    assert cg[0] == 0 and np.all(cg[1:] > 0)


def test_collapsed_warm_bracket_reopens_cold():
    """A warm lower endpoint on the wrong side of the root (the previous
    point's nu claimed above lambda1 at a budget past t*) closes the
    bracket away from the root; the point re-brackets cold and certifies,
    matching the cold solve."""
    X, y, _ = make_regression(60, 8, k_true=4, seed=7)
    lam1 = jnp.asarray(0.3 * float(api.en.lambda1_max(X, y)))
    solve = jax.jit(api._enet_point, static_argnames="config")
    config = api.PathConfig()
    _, cold = solve(X, y, lam1, 1.0, api.cold_carry(X, y), config)
    assert int(cold.stop) == api.STOP_ROOT
    t_ridge = float(api._ridge_l1(X, y, 1.0))
    bad = api.cold_carry(X, y)._replace(
        t=jnp.asarray(0.5 * (float(cold.t) + t_ridge)), nu=2.0 * lam1)
    _, pt = solve(X, y, lam1, 1.0, bad, config)
    assert int(pt.stop) == api.STOP_ROOT
    assert int(pt.evals) > int(cold.evals)
    np.testing.assert_allclose(pt.beta, cold.beta, atol=1e-9)
    assert abs(float(pt.nu) / float(lam1) - 1.0) < 1e-8
    # under vmap (CV folds, served batches) only the collapsed lane re-opens
    lanes = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                         api.cold_carry(X, y), bad)
    _, both = jax.jit(jax.vmap(
        lambda c: api._enet_point(X, y, lam1, 1.0, c, config)))(lanes)
    np.testing.assert_array_equal(both.stop, [api.STOP_ROOT] * 2)
    np.testing.assert_array_equal(both.evals, [cold.evals, pt.evals])
    np.testing.assert_allclose(both.beta[1], cold.beta, atol=1e-9)


def test_bracket_closed_near_the_root_is_left_closed():
    """A wrong-side endpoint just past the root closes the bracket within
    REBRACKET_RTOL of lambda1: the point is reported unresolved
    (STOP_BRACKET), not re-opened."""
    X, y, _ = make_regression(60, 8, k_true=4, seed=7)
    lmax = float(api.en.lambda1_max(X, y))
    lam1 = jnp.asarray(0.3 * lmax)
    solve = jax.jit(api._enet_point, static_argnames="config")
    config = api.PathConfig()
    _, cold = solve(X, y, lam1, 1.0, api.cold_carry(X, y), config)
    near = api.cold_carry(X, y)._replace(
        t=jnp.asarray(float(cold.t) * (1 + 1e-5)), nu=2.0 * lam1)
    _, pt = solve(X, y, lam1, 1.0, near, config)
    f = abs(float(pt.nu) - float(lam1))
    assert int(pt.stop) == api.STOP_BRACKET
    assert config.f_rtol * lmax < f < api.REBRACKET_RTOL * float(lam1)
    assert int(pt.evals) < config.max_evals


@pytest.mark.parametrize("seed", [4, 8, 12345])
def test_msd_shape_path_certifies_every_point(seed):
    """YearPredictionMSD's benchmark shape (231,858 x 90, f64, 10-point grid
    to 1e-4 lambda_max): on these problems the warm root-find once closed
    its bracket at the last point with |nu - lambda1| ~ 0.7 lambda1 and a
    coefficient gap of ~1e-4. Every point now certifies."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import data, reference
    from bench.jobs.enet_path import glmnet_grid

    X, y = data.make_regression(231858, 90, data_seed=seed, dtype="float64",
                                k_true=10, rho=0.3, noise=0.1)
    grid = glmnet_grid(X, y, {"lambda_grid": {"min_ratio": 1e-4}}, 10)
    path = enet_path(X, y, lambda1s=jnp.asarray(grid), lambda2=1.0)
    ref = reference.enet_path_reference(np.asarray(X), np.asarray(y), grid,
                                        1.0)
    assert float(np.max(reference.point_gaps(np.asarray(path.betas), ref))) \
        < 1e-10
    assert np.all(np.asarray(path.stop)[1:] == api.STOP_ROOT)


def test_path_log_summary_matches_the_paths():
    log = default_solve_log()
    log.clear()
    X, y, _ = make_regression(40, 60, k_true=5, seed=8)
    paths = [enet_path(X, y, n_lambdas=4, eps=0.05),
             enet_path(X, 2.0 * y, n_lambdas=5, eps=0.05)]
    recs = log.path_records()
    assert len(recs) == 2 and log.records() == []
    for rec, path in zip(recs, paths):
        for field in ("evals", "sven_iters", "cg_steps", "stalls", "stop"):
            np.testing.assert_array_equal(getattr(rec, field),
                                          np.asarray(getattr(path, field)))
    stop = np.concatenate([np.asarray(p.stop) for p in paths])
    cg = np.concatenate([np.asarray(p.cg_steps) for p in paths])
    stalls = np.concatenate([np.asarray(p.stalls) for p in paths])
    summary = log.summary()
    assert summary["paths"] == 2 and summary["points"] == 9
    assert summary["by_stop"] == {
        name: int(np.sum(stop == code)) for code, name in enumerate(STOP_CAUSES)}
    assert summary["cg_steps_per_point"] == pytest.approx(float(np.mean(cg)))
    assert summary["stalls_per_point"] == pytest.approx(
        float(np.mean(stalls)))
    assert log.residual_report()["n_records"] == 0
    log.clear()


@pytest.fixture
def fresh_traces():
    """Clears JAX's trace caches around a test that patches a constant
    read at trace time, so that no test shares its executables."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("mode", ["primal", "dual"])
def test_stalls_reach_the_point_the_path_and_the_log(mode, monkeypatch,
                                                     fresh_traces):
    """With every halving line search counted a stall, the primal's stall
    stops reach `EnetPoint`, `EnetResult`, `EnetPath` and the path log's
    `stalls_per_point`; the dual Newton never stalls."""
    monkeypatch.setattr(primal_newton, "STALL_RTOL", np.inf)
    n, p = (20, 40) if mode == "primal" else (60, 8)
    X, y, _ = make_regression(n, p, k_true=4, seed=11)
    lmax = float(api.en.lambda1_max(X, y))
    lam1s = jnp.asarray([0.5, 0.2, 0.05]) * lmax
    log = default_solve_log()
    log.clear()
    path = enet_path(X, y, lambda1s=lam1s)
    assert log.summary()["stalls_per_point"] == pytest.approx(
        float(np.mean(path.stalls)))
    # enet_batch's points and enet's result each solve from a cold start
    for res in (path, enet_batch(X, y, lam1s, 1.0),
                enet(X, y, float(lam1s[2]), 1.0)):
        stalls = np.asarray(res.stalls)
        assert np.all((0 <= stalls) & (stalls <= np.asarray(res.evals)))
        assert (int(stalls.sum()) > 0) == (mode == "primal")
    log.clear()


def test_span_reaches_profile_while_tracer_disabled(tmp_path):
    tracer = get_tracer()
    was = tracer.enabled
    tracer.disable()
    try:
        name = "repro.test.span_in_profile"
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracer.span(name):
                jnp.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        assert name not in {s[1] for s in tracer.spans()}
        planes = list(tmp_path.rglob("*.xplane.pb"))
        assert planes and any(name.encode() in p.read_bytes()
                              for p in planes)
    finally:
        if was:
            enable_tracing()


def test_tracer_has_no_annotate_switch():
    assert list(inspect.signature(Tracer.enable).parameters) == ["self"]
    assert not inspect.signature(enable_tracing).parameters
    span = Tracer().span("off")
    assert isinstance(span, jax.profiler.TraceAnnotation)
    assert span.args is None
