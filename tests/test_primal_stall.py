"""The primal Newton's stall stop (`core/svm/primal_newton.py`): a solve
whose line search halves on rounding noise ends there, with the answer the
`max_newton` cap would have returned, and a solve that never stalls runs
exactly as on the gradient test alone.

The problems are GLI-85's benchmark shape (85 x 22,283, f64) on their
3-point glmnet grid, made by the benchmark's own generator. On CPU f64
data seed 0 stalls: two of its solves ran to the cap before the stop. Data
seed 1 does not stall."""
import sys
from pathlib import Path

import jax
import numpy as np

from repro.core import api
from repro.core.svm import primal_newton

ROOT = Path(__file__).resolve().parents[1]
CONFIG = api.PathConfig()
MAX_NEWTON = CONFIG.solver.max_newton
TOL = CONFIG.solver.tol
_SVEN_CORE = api._sven_core


def _problem(data_seed):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import data
    from bench.jobs.enet_path import glmnet_grid

    X, y = data.make_regression(85, 22283, data_seed=data_seed,
                                dtype="float64")
    return X, y, glmnet_grid(X, y, {"lambda_grid": {"min_ratio": 0.01}}, 3)


def _path(X, y, grid, monkeypatch, stall_rtol=None):
    """The path's points, traced afresh, and each root-find evaluation's
    (Newton steps, stalled, final max |grad|) in the order they ran.
    `stall_rtol=-inf` leaves the gradient test as the only stop."""
    evals = []

    def recording_core(*args):
        arrs = _SVEN_CORE(*args)
        jax.debug.callback(
            lambda i, s, g: evals.append((int(i), bool(s), float(g))),
            arrs.iters, arrs.stalled, arrs.opt_residual)
        return arrs

    monkeypatch.setattr(api, "_sven_core", recording_core)
    if stall_rtol is not None:
        monkeypatch.setattr(primal_newton, "STALL_RTOL", stall_rtol)

    @jax.jit
    def run(X, y, grid):
        def body(carry, lam1):
            return api._enet_point(X, y, lam1, 1.0, carry, CONFIG)
        return jax.lax.scan(body, api.cold_carry(X, y), grid)[1]

    pts = jax.block_until_ready(run(X, y, grid))
    jax.effects_barrier()
    return pts, evals


def test_stalled_solves_stop_before_the_cap_at_the_reference(monkeypatch):
    from bench import reference

    X, y, grid = _problem(0)
    pts, evals = _path(X, y, grid, monkeypatch)
    assert len(evals) == int(np.sum(pts.evals)) > 0
    assert all(i < MAX_NEWTON for i, _, _ in evals)
    stalls = np.asarray(pts.stalls)
    assert int(stalls.sum()) >= 1
    assert int(stalls.sum()) == sum(s for _, s, _ in evals)
    # the stall is recorded apart from the tolerance: a stalled solve ended
    # with its gradient above tol
    assert all(g > TOL for _, s, g in evals if s)
    ref = reference.enet_path_reference(np.asarray(X), np.asarray(y), grid,
                                        1.0)
    assert float(np.max(reference.point_gaps(np.asarray(pts.beta), ref))) \
        <= 1e-10
    # the first point is lambda_max, beta = 0, where `kkt` (the constrained
    # form's residual) reads no multiplier
    assert np.all(np.asarray(pts.stop)[1:] == api.STOP_ROOT)
    assert float(np.max(pts.kkt[1:])) <= 1e-9

    # without the stop, those solves halve on to the cap
    _, capped = _path(X, y, grid, monkeypatch, stall_rtol=-np.inf)
    assert max(i for i, _, _ in capped) == MAX_NEWTON
    assert not any(s for _, s, _ in capped)


def test_solves_that_never_stall_run_as_on_the_gradient_test(monkeypatch):
    X, y, grid = _problem(1)
    pts, evals = _path(X, y, grid, monkeypatch)
    alone, evals_alone = _path(X, y, grid, monkeypatch, stall_rtol=-np.inf)
    assert int(np.sum(pts.stalls)) == 0
    assert evals == evals_alone
    np.testing.assert_array_equal(pts.sven_iters, alone.sven_iters)
    np.testing.assert_array_equal(pts.beta, alone.beta)
