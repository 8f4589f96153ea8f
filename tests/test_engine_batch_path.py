"""The jit-native engine's contracts (DESIGN.md §6):

- `sven_path` (lax.scan) == `sven_path_reference` (host loop) to 1e-6, in
  both dispatch modes, with the warm w AND alpha genuinely carried;
- the scan compiles exactly once for a 40-point path and never retraces on
  new grid values (trace-count instrumentation);
- `sven()` itself never retraces across (t, lambda2) sweeps at fixed shape;
- `sven_batch` == per-problem `sven` loops for every stacking pattern
  (multi-response, (t, lambda2) grid, stacked CV folds);
- ElasticNetEngine padded/bucketed solves == direct unpadded solves, and
  steady-state traffic adds no new executables.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (cv_folds, en_grid, reset_trace_counts, sven,
                        sven_batch, sven_path, sven_path_reference,
                        trace_counts)
from repro.core.elastic_net import lambda1_max
from repro.core.svm import (Hyper, dual_newton_machine, make_hyper,
                            primal_newton_machine)
from repro.data.synthetic import make_regression
from repro.serve import ElasticNetEngine

PATH_ATOL = 1e-6


def _problem(n, p, seed=0):
    X, y, _ = make_regression(n, p, k_true=max(3, p // 6), rho=0.3, seed=seed)
    t_scale = 0.2 * float(jnp.sum(jnp.abs(X.T @ y))) / n
    return X, y, t_scale


# ---------------------------------------------------------------------------
# scan path vs reference loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,p", [(80, 24), (30, 70)])  # dual and primal modes
def test_scan_path_matches_reference_loop(n, p):
    X, y, t_scale = _problem(n, p, seed=1)
    ts = jnp.linspace(0.2, 1.5, 9) * t_scale
    betas_scan = sven_path(X, y, ts, 1.0)
    betas_loop = sven_path_reference(X, y, ts, 1.0)
    np.testing.assert_allclose(betas_scan, betas_loop, atol=PATH_ATOL)
    # and each point agrees with an independent cold solve
    sol_mid = sven(X, y, float(ts[4]), 1.0)
    np.testing.assert_allclose(betas_scan[4], sol_mid.beta, atol=PATH_ATOL)


def test_path_warm_start_carries_w_and_alpha():
    """The reference loop must feed BOTH warm starts back (the seed repo's
    `warm_w` was dead); regression-test via solution-identity at every point
    and via the solver doing less work warm than cold."""
    X, y, t_scale = _problem(26, 60, seed=2)  # primal mode: w is the carry
    ts = jnp.linspace(0.3, 1.2, 6) * t_scale
    betas = sven_path_reference(X, y, ts, 1.0)
    cold_iters, warm_iters = [], []
    warm_a = warm_w = None
    for i, t in enumerate(ts):
        cold = sven(X, y, float(t), 1.0)
        warm = sven(X, y, float(t), 1.0, warm_alpha=warm_a, warm_w=warm_w)
        cold_iters.append(int(cold.iters))
        warm_iters.append(int(warm.iters))
        warm_a, warm_w = warm.alpha, warm.w
        np.testing.assert_allclose(betas[i], cold.beta, atol=PATH_ATOL)
    assert sum(warm_iters) <= sum(cold_iters), (warm_iters, cold_iters)


def test_path_compiles_once_for_40_points():
    X, y, t_scale = _problem(40, 10, seed=3)  # small dual problem: fast scan
    ts40 = jnp.linspace(0.25, 1.25, 40) * t_scale
    reset_trace_counts()
    betas = sven_path(X, y, ts40, 1.0)
    assert betas.shape == (40, 10)
    assert trace_counts().get("sven_path_scan", 0) == 1
    # new grid VALUES and new lambda2, same shapes: zero additional traces
    sven_path(X, y, ts40 * 0.93, 2.0)
    assert trace_counts().get("sven_path_scan", 0) == 1
    # a different grid LENGTH is a new shape, hence one (and only one) more
    sven_path(X, y, ts40[:16], 1.0)
    assert trace_counts().get("sven_path_scan", 0) == 2


def test_sven_never_retraces_across_regularization_sweeps():
    X, y, t_scale = _problem(33, 21, seed=4)
    reset_trace_counts()
    for i, (t, lam2) in enumerate([(1.0, 1.0), (0.7, 2.0), (0.4, 0.25), (1.3, 5.0)]):
        sven(X, y, t * t_scale, lam2)
        assert trace_counts().get("sven", 0) == 1, f"retraced at sweep point {i}"


# ---------------------------------------------------------------------------
# solver machines: traced hyperparameters
# ---------------------------------------------------------------------------

def test_solver_machines_accept_traced_hyperparameters():
    """init/step/run jit with (C, tol) as operands — changing them must not
    retrace, and results must match the eager wrappers."""
    X, y, _ = _problem(50, 8, seed=5)
    from repro.core.reduction import gram_blocks
    K = gram_blocks(X, y, 1.0)
    machine = dual_newton_machine(lambda v: K @ v, m=K.shape[0], dtype=X.dtype)

    n_traces = [0]

    @jax.jit
    def run(C, tol):
        n_traces[0] += 1
        return machine.run(Hyper(C=C, tol=tol))

    s1 = run(jnp.asarray(0.5, X.dtype), jnp.asarray(1e-8, X.dtype))
    s2 = run(jnp.asarray(5.0, X.dtype), jnp.asarray(1e-10, X.dtype))
    assert n_traces[0] == 1
    assert bool(s1.converged) and bool(s2.converged)
    assert not np.allclose(np.asarray(s1.x), np.asarray(s2.x))  # C really traced

    eager = machine.run(make_hyper(5.0, 1e-10, X.dtype))
    np.testing.assert_allclose(s2.x, eager.x, atol=1e-9)


def test_primal_machine_state_protocol():
    X, y, _ = _problem(20, 40, seed=6)
    from repro.core.reduction import SvenOperator
    op = SvenOperator(X=X, y=y, t=jnp.asarray(1.0, X.dtype))
    p = X.shape[1]
    yhat = jnp.concatenate([jnp.ones((p,), X.dtype), -jnp.ones((p,), X.dtype)])
    machine = primal_newton_machine(op.xhat_matvec, op.xhat_rmatvec, yhat, X.shape[0])
    hyper = make_hyper(0.5, 1e-8, X.dtype)
    state = machine.init(hyper)
    assert not bool(state.converged) and int(state.iters) == 0
    stepped = machine.step(state, hyper)
    assert int(stepped.iters) == 1
    final = machine.run(hyper)
    assert bool(final.converged)
    assert float(final.residual) <= 1e-8


# ---------------------------------------------------------------------------
# sven_batch stacking patterns
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,p,t_grid,lam2s,seed", [
    (60, 16, (0.4, 1.2, 3), (0.5, 1.0, 4.0), 7),
    (120, 24, (0.45, 1.5, 2), (0.5, 2.0), 3),
])
def test_batch_grid_matches_sequential(n, p, t_grid, lam2s, seed):
    X, y, t_scale = _problem(n, p, seed=seed)
    ts, l2s = en_grid(jnp.linspace(*t_grid) * t_scale, jnp.array(lam2s))
    sol = sven_batch(X, y, ts, l2s)
    assert sol.beta.shape == (t_grid[2] * len(lam2s), p)
    for i in range(ts.shape[0]):
        ref = sven(X, y, float(ts[i]), float(l2s[i]))
        np.testing.assert_allclose(sol.beta[i], ref.beta, atol=PATH_ATOL)
        np.testing.assert_allclose(sol.kkt[i], ref.kkt, atol=1e-6)


def test_batch_multi_response_and_stacked_X():
    X, y, t_scale = _problem(48, 12, seed=8)
    # multi-response: shared X, stacked y
    Y = jnp.stack([y, -y, y * 0.5 + 0.1])
    sol = sven_batch(X, Y, t_scale, 1.0)
    for i in range(3):
        ref = sven(X, Y[i], t_scale, 1.0)
        np.testing.assert_allclose(sol.beta[i], ref.beta, atol=PATH_ATOL)
    # stacked CV folds: batched X AND y
    Xtr, ytr, Xva, yva = cv_folds(X, y, 4)
    assert Xtr.shape == (4, 36, 12) and Xva.shape == (4, 12, 12)
    solf = sven_batch(Xtr, ytr, t_scale, 1.0)
    for i in range(4):
        ref = sven(Xtr[i], ytr[i], t_scale, 1.0)
        np.testing.assert_allclose(solf.beta[i], ref.beta, atol=PATH_ATOL)


def test_batch_input_validation():
    X, y, t_scale = _problem(30, 10, seed=9)
    with pytest.raises(ValueError, match="no batched operand"):
        sven_batch(X, y, t_scale, 1.0)
    with pytest.raises(ValueError, match="inconsistent batch sizes"):
        sven_batch(X, jnp.stack([y, y]), jnp.ones((3,)) * t_scale, 1.0)


def test_batch_compiles_once_per_stacking_pattern():
    X, y, t_scale = _problem(44, 14, seed=10)
    ts = jnp.linspace(0.5, 1.0, 4) * t_scale
    reset_trace_counts()
    sven_batch(X, y, ts, 1.0)
    sven_batch(X, y, ts * 0.8, 3.0)          # new values, same pattern
    assert trace_counts().get("sven_batch", 0) == 1


# ---------------------------------------------------------------------------
# ElasticNetEngine: bucketing, padding exactness, executable reuse
# ---------------------------------------------------------------------------

def test_engine_padded_solves_match_direct():
    engine = ElasticNetEngine(max_batch=8)
    reqs, ids = [], []
    for seed, (n, p) in enumerate([(23, 11), (30, 9), (19, 14), (40, 20)]):
        X, y, t_scale = _problem(n, p, seed=20 + seed)
        reqs.append((X, y, t_scale, 1.0 + seed))
        ids.append(engine.submit(X, y, t_scale, 1.0 + seed))
    out = engine.drain()
    assert engine._queue == []
    for rid, (X, y, t, lam2) in zip(ids, reqs):
        res = out[rid]
        ref = sven(X, y, t, lam2)
        assert res.beta.shape == (X.shape[1],)
        np.testing.assert_allclose(res.beta, ref.beta, atol=PATH_ATOL)
        # bucket really padded: executable shape >= request shape, pow2-ish
        assert res.bucket[0] >= X.shape[0] and res.bucket[1] >= X.shape[1]


def test_engine_reuses_executables_across_waves():
    engine = ElasticNetEngine(max_batch=8)

    def wave(seed0):
        ids = []
        for s in range(4):
            X, y, t_scale = _problem(20 + s, 10 + s, seed=40 + seed0 + s)
            ids.append(engine.submit(X, y, t_scale, 1.0))
        return engine.drain()

    wave(0)
    compiled_after_first = engine.stats.bucket_shapes
    wave(100)   # same shape distribution, new data/values
    assert engine.stats.bucket_shapes == compiled_after_first
    assert engine.stats.requests == 8


def test_engine_solve_convenience_and_validation():
    X, y, t_scale = _problem(25, 7, seed=60)
    engine = ElasticNetEngine()
    res = engine.solve(X, y, t_scale, 1.0)
    np.testing.assert_allclose(res.beta, sven(X, y, t_scale, 1.0).beta,
                               atol=PATH_ATOL)
    with pytest.raises(ValueError, match="bad shapes"):
        engine.submit(X, y[:-1], t_scale, 1.0)
    with pytest.raises(ValueError, match="t > 0"):
        engine.submit(X, y, -1.0, 1.0)


def test_solver_exits_promptly_on_nan():
    """A diverged (NaN) residual is terminal: the machine must stop, not spin
    to max_iters re-iterating on a NaN iterate."""
    for n, p, seed in [(40, 10, 70), (20, 40, 71)]:   # dual and primal
        X, y, t_scale = _problem(n, p, seed=seed)
        X = X.at[0, 0].set(jnp.nan)
        sol = sven(X, y, t_scale, 1.0)
        assert bool(jnp.isnan(sol.opt_residual))
        assert int(sol.iters) <= 2, f"spun {int(sol.iters)} iters on NaN input"


def test_engine_drain_failure_preserves_queue(monkeypatch):
    """Both drain paths — the runtime scheduler's async dispatch and the
    synchronous drain_reference — must re-queue on a failed launch."""
    X1, y1, t1 = _problem(21, 8, seed=71)
    engine = ElasticNetEngine()
    rid = engine.submit(X1, y1, t1, 1.0)

    def boom(*a, **k):
        raise RuntimeError("boom")

    monkeypatch.setattr(engine.scheduler, "_dispatch", boom)
    with pytest.raises(RuntimeError, match="boom"):
        engine.drain()
    assert [r.req_id for r in engine._queue] == [rid]  # nothing lost
    monkeypatch.undo()

    monkeypatch.setattr(engine, "_drain_chunk", boom)
    with pytest.raises(RuntimeError, match="boom"):
        engine.drain_reference()
    assert [r.req_id for r in engine._queue] == [rid]  # nothing lost
    monkeypatch.undo()

    out = engine.drain()   # and the request is still solvable afterwards
    np.testing.assert_allclose(out[rid].beta, sven(X1, y1, t1, 1.0).beta,
                               atol=PATH_ATOL)


def test_engine_rejects_degenerate_bucket_floors():
    with pytest.raises(ValueError, match="must be >= 1"):
        ElasticNetEngine(min_n=0)


def test_engine_solve_does_not_lose_pending_requests():
    """A solve() that drains ride-along requests must hold their results for
    the next drain(), not drop them."""
    X1, y1, t1 = _problem(22, 9, seed=61)
    X2, y2, t2 = _problem(31, 13, seed=62)
    engine = ElasticNetEngine()
    rid = engine.submit(X1, y1, t1, 1.0)
    res2 = engine.solve(X2, y2, t2, 2.0)
    np.testing.assert_allclose(res2.beta, sven(X2, y2, t2, 2.0).beta,
                               atol=PATH_ATOL)
    held = engine.drain()
    assert set(held) == {rid}
    np.testing.assert_allclose(held[rid].beta, sven(X1, y1, t1, 1.0).beta,
                               atol=PATH_ATOL)
