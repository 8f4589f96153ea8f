"""The primal Newton Hessian's two forms (core/svm/primal_newton.py): the
explicit n x n H built once per Newton step from
`SvenOperator.xhat_weighted_gram`, and the matrix-free product inside CG;
which one `_sven_core` takes, read from ``sven_hessian_form_total``."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.reduction import SvenOperator, svm_C
from repro.core.sven import SvenConfig, _sven_core
from repro.core.svm import solve_primal_newton
from repro.data.synthetic import make_regression
from repro.obs.metrics import default_registry

# `repro.core.sven` the attribute is the function; the module holds the constant
sven_mod = importlib.import_module("repro.core.sven")


def _form_counts() -> dict:
    c = default_registry().counter("sven_hessian_form_total",
                                   labelnames=("form",))
    return {f: c.value(form=f) for f in ("explicit", "matrix_free")}


def _operator(n, p, seed, screened):
    X, y, _ = make_regression(n, p, k_true=4, seed=seed)
    if screened:
        X = X.at[:, ::3].set(0.0)
    t = 0.5 * float(jnp.sum(jnp.abs(jnp.linalg.lstsq(X, y)[0])))
    return SvenOperator(X=X, y=y, t=jnp.asarray(t, X.dtype))


def _columns(op, c):
    """Xhat^T diag(c) Xhat column by column from the matrix-free products."""
    return jax.vmap(lambda e: op.xhat_rmatvec(c * op.xhat_matvec(e)))(
        jnp.eye(op.n, dtype=op.X.dtype)).T


@pytest.mark.parametrize("case", ["dense", "screened", "vmap"])
def test_weighted_gram_matches_operator_products(case):
    rng = np.random.default_rng(11)
    if case == "vmap":
        ops = [_operator(12, 50, s, screened=s == 1) for s in (0, 1, 2)]
        Xs = jnp.stack([o.X for o in ops])
        ys = jnp.stack([o.y for o in ops])
        ts = jnp.stack([o.t for o in ops])
        cs = jnp.asarray(rng.random((3, 100)) < 0.6, Xs.dtype)
        got = jax.vmap(lambda X, y, t, c: SvenOperator(X=X, y=y, t=t)
                       .xhat_weighted_gram(c))(Xs, ys, ts, cs)
        want = jnp.stack([_columns(o, c) for o, c in zip(ops, cs)])
    else:
        op = _operator(15, 60, 3, screened=case == "screened")
        c = jnp.asarray(rng.random(120), op.X.dtype)
        got, want = op.xhat_weighted_gram(c), _columns(op, c)
    err = jnp.max(jnp.abs(got - want), axis=(-2, -1))
    assert np.all(np.asarray(err / jnp.max(jnp.abs(want), axis=(-2, -1)))
                  <= 1e-12)


def _trace_core(shape, config, batch=None):
    n, p = shape
    X = jax.ShapeDtypeStruct(shape if batch is None else (batch, n, p),
                             jnp.float64)
    y = jax.ShapeDtypeStruct((n,) if batch is None else (batch, n),
                             jnp.float64)
    core = lambda X, y: _sven_core(X, y, 0.5, 1.0, None, None, config)
    jax.eval_shape(core if batch is None else jax.vmap(core), X, y)


@pytest.mark.parametrize("n_over,config,batch,form", [
    (0, SvenConfig(), None, "explicit"),
    (1, SvenConfig(), None, "matrix_free"),
    (0, SvenConfig(), 3, "explicit"),
    (0, SvenConfig(matrix_free=False), None, "matrix_free"),
    (0, SvenConfig(backend="tpu_interpret"), None, "matrix_free"),
], ids=["at_max", "above_max", "vmap", "explicit_xhat", "pallas"])
def test_primal_solve_takes_the_form_its_shape_selects(n_over, config,
                                                       batch, form):
    n = sven_mod.EXPLICIT_HESSIAN_MAX_N + n_over
    before = _form_counts()
    _trace_core((n, n), config, batch)
    after = _form_counts()
    other = "matrix_free" if form == "explicit" else "explicit"
    assert after[form] == before[form] + 1
    assert after[other] == before[other]


def test_dual_solve_counts_no_hessian_form():
    before = _form_counts()
    _trace_core((64, 8), SvenConfig())
    assert _form_counts() == before


@pytest.mark.parametrize("screened", [False, True])
def test_explicit_and_matrix_free_forms_agree(screened, monkeypatch):
    n, p = 30, 160
    op = _operator(n, p, 5, screened=False)
    keep = jnp.arange(p) % 4 != 0 if screened else None
    config = SvenConfig(tol=1e-10)

    def solve(max_n):
        monkeypatch.setattr(sven_mod, "EXPLICIT_HESSIAN_MAX_N", max_n)
        # a fresh function object: a fresh trace under the patched constant
        return jax.jit(lambda X, y, t, k: _sven_core(
            X, y, t, 1.0, None, None, config, k))(op.X, op.y, op.t, keep)

    before = _form_counts()
    explicit, free = solve(n), solve(n - 1)
    after = _form_counts()
    assert after["explicit"] == before["explicit"] + 1
    assert after["matrix_free"] == before["matrix_free"] + 1
    scale = float(jnp.max(jnp.abs(free.beta)))
    assert scale > 0
    np.testing.assert_allclose(explicit.beta, free.beta, rtol=0,
                               atol=1e-10 * scale)
    np.testing.assert_allclose(explicit.w, free.w, rtol=0,
                               atol=1e-10 * float(jnp.max(jnp.abs(free.w))))
    assert abs(int(explicit.iters) - int(free.iters)) <= 1
    assert int(explicit.cg_steps) > 0 and int(free.cg_steps) > 0


def test_solver_forms_agree_from_a_warm_start():
    op = _operator(25, 120, 6, screened=False)
    yhat = jnp.concatenate([jnp.ones((120,)), -jnp.ones((120,))])
    C = svm_C(0.5)
    w0 = 0.1 * jnp.ones((25,))
    free = solve_primal_newton(op.xhat_matvec, op.xhat_rmatvec, yhat, C, 25,
                               tol=1e-10, w0=w0)
    explicit = solve_primal_newton(op.xhat_matvec, op.xhat_rmatvec, yhat, C,
                                   25, tol=1e-10, w0=w0,
                                   weighted_gram=op.xhat_weighted_gram)
    np.testing.assert_allclose(explicit.w, free.w, rtol=0,
                               atol=1e-10 * float(jnp.max(jnp.abs(free.w))))
    assert abs(int(explicit.iters) - int(free.iters)) <= 1
