"""glmnet-parity penalized front-end for the SVEN engine (DESIGN.md §7).

The paper's headline comparison is against glmnet, which solves the
*penalized* Elastic Net along a lambda grid; the SVEN reduction solves the
*constrained* form (t, lambda2). This module closes that gap so the
comparison is actually reproducible:

  - `lambda_grid` builds the standard glmnet grid: `n_lambdas` points
    geometrically spaced from lambda1_max (smallest lambda with beta = 0)
    down to eps * lambda1_max.
  - `penalized_from_glmnet` / `penalized_from_sklearn` convert those
    libraries' (lambda, alpha) / (alpha, l1_ratio) parameters into this
    repo's paper-scaled (lambda1, lambda2) — see the conventions table in
    DESIGN.md §7.
  - `standardize_fit` / `unscale_coef` handle glmnet-style column
    standardization and intercept centering with exact round-trip
    un-scaling (the penalty never touches the intercept).
  - `enet` / `enet_path` map each penalized (lambda1, lambda2) onto the
    constrained engine through the `t = |beta*|_1` equivalence
    (`core/elastic_net.py`): at the constrained optimum the L1 multiplier
    nu(t) = max_j |g_j(beta(t))| is piecewise linear and decreasing in t,
    so the t* with nu(t*) = lambda1 is found by a guarded Illinois
    (modified regula falsi) iteration whose every evaluation is one
    warm-started `_sven_core` solve. The bracket is analytic — nu(0) =
    lambda1_max and nu(|beta_ridge|_1) = 0 — so no extra solves are spent
    bracketing, and on the piecewise-linear nu the secant step is exact as
    soon as both endpoints share a segment.
  - `gap_safe_screen` (core/screening.py) is fused into every point as a
    fixed-size (p,) keep mask carried into `_sven_core` — columns that are
    provably inactive at the *current* lambda1 are zeroed and their
    coefficients scattered back as exact zeros, preserving compile-once.
  - `enet_path` runs the whole grid as ONE jitted `lax.scan` carrying
    (beta, alpha, w, t, nu) warm state; `trace_counts()["enet_path_scan"]`
    asserts the single-trace property. `enet_batch` vmaps the same point
    solver over stacked problems for the serving layer.
  - `ElasticNet` is the thin sklearn-style fit/predict wrapper
    (`core/cv.py` adds `ElasticNetCV`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import elastic_net as en
from repro.core.screening import gap_safe_screen
from repro.core.sven import SvenConfig, _bump_trace, _sven_core, resolve_backend
from repro.obs.solve import PathRecord, default_solve_log
from repro.obs.trace import get_tracer


# ---------------------------------------------------------------------------
# Scaling conventions (DESIGN.md §7): paper <-> glmnet <-> sklearn
# ---------------------------------------------------------------------------

def penalized_from_glmnet(lam, alpha, n: int) -> Tuple[float, float]:
    """glmnet (lambda, alpha) -> paper-scaled (lambda1, lambda2).

    glmnet minimizes 1/(2n) ||y - X b||^2 + lam * (alpha |b|_1
    + (1-alpha)/2 ||b||^2); multiplying by 2n (argmin-invariant) gives the
    paper objective with lambda1 = 2 n lam alpha, lambda2 = n lam (1-alpha).
    """
    return 2.0 * n * lam * alpha, n * lam * (1.0 - alpha)


def penalized_to_glmnet(lambda1, lambda2, n: int) -> Tuple[float, float]:
    """Inverse of `penalized_from_glmnet` (lambda1 + lambda2 must be > 0)."""
    la, lr = lambda1 / (2.0 * n), lambda2 / n
    lam = la + lr
    return lam, la / lam


def penalized_from_sklearn(alpha, l1_ratio, n: int) -> Tuple[float, float]:
    """sklearn ElasticNet (alpha, l1_ratio) -> paper-scaled (lambda1, lambda2).

    sklearn's objective is glmnet's with lambda = alpha, alpha = l1_ratio.
    """
    return penalized_from_glmnet(alpha, l1_ratio, n)


def lambda_grid(X: jax.Array, y: jax.Array, n_lambdas: int = 40,
                eps: Optional[float] = None) -> jax.Array:
    """The standard glmnet grid: geometric from lambda1_max to eps*lambda1_max.

    eps defaults to glmnet's: 1e-2 when p > n, else 1e-4. The first point is
    exactly lambda1_max, where the solution is identically zero.
    """
    n, p = X.shape
    if eps is None:
        eps = 1e-2 if p > n else 1e-4
    l1max = en.lambda1_max(X, y)
    return l1max * jnp.geomspace(1.0, eps, n_lambdas).astype(X.dtype)


# ---------------------------------------------------------------------------
# Standardization / intercept round trip
# ---------------------------------------------------------------------------

class Scaler(NamedTuple):
    """Column/response statistics needed to un-scale a standardized fit."""

    x_mean: jax.Array   # (p,)
    x_scale: jax.Array  # (p,)
    y_mean: jax.Array   # ()


def standardize_fit(X: jax.Array, y: jax.Array, *, standardize: bool = True,
                    fit_intercept: bool = True):
    """Center/scale (X, y) glmnet-style; returns (Xs, ys, Scaler).

    With fit_intercept, columns and the response are mean-centered so the
    (unpenalized) intercept drops out of the optimization entirely; with
    standardize, columns are scaled to unit 1/n-variance (constant columns
    keep scale 1). The solvers then see (Xs, ys); `unscale_coef` maps the
    standardized coefficients back.
    """
    dtype = X.dtype
    p = X.shape[1]
    if fit_intercept:
        x_mean = jnp.mean(X, axis=0)
        y_mean = jnp.mean(y)
    else:
        x_mean = jnp.zeros((p,), dtype)
        y_mean = jnp.zeros((), dtype)
    Xc = X - x_mean
    if standardize:
        sd = jnp.sqrt(jnp.mean(Xc * Xc, axis=0))
        x_scale = jnp.where(sd > 0, sd, 1.0)
    else:
        x_scale = jnp.ones((p,), dtype)
    return Xc / x_scale, y - y_mean, Scaler(x_mean, x_scale, y_mean)


def unscale_coef(beta_std: jax.Array, scaler: Scaler):
    """Standardized-space coefficients -> original-scale (beta, intercept).

    Works for a single (p,) vector or a stacked (L, p) path.
    """
    beta = beta_std / scaler.x_scale
    intercept = scaler.y_mean - beta @ scaler.x_mean
    return beta, intercept


# ---------------------------------------------------------------------------
# The penalized point solver: multiplier root-find over the constrained engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PathConfig:
    """Static configuration of the penalized front-end (hashable: jit key)."""

    solver: SvenConfig = SvenConfig(tol=1e-10)
    screen: bool = True        # fuse gap_safe_screen keep-masks into each point
    max_evals: int = 30        # Illinois iterations == SVEN solves per point
    t_floor_rel: float = 1e-7  # smallest bracketed t, relative to |ridge|_1
    f_rtol: float = 1e-9       # |nu - lambda1| stop, relative to lambda1_max


def resolve_path_config(config: PathConfig, *arrays) -> PathConfig:
    """Pin the nested SvenConfig's Pallas interpret choice before tracing
    (see `core.sven.resolve_backend`); a no-op for the XLA backend."""
    solver = resolve_backend(config.solver, *arrays)
    if solver is config.solver:
        return config
    return dataclasses.replace(config, solver=solver)


class EnetCarry(NamedTuple):
    """Warm state threaded across lambda points (and across CV-fold vmaps)."""

    beta: jax.Array   # (p,)  last solution (screening warm point)
    alpha: jax.Array  # (2p,) dual warm start
    w: jax.Array      # (n,)  primal warm start
    t: jax.Array      # ()    L1 budget of the last solution
    nu: jax.Array     # ()    multiplier measured at (t, beta)


class EnetPoint(NamedTuple):
    """Per-lambda solve result (standardized space), stackable under scan."""

    beta: jax.Array       # (p,)
    t: jax.Array          # |beta|_1 — the constrained budget this maps to
    nu: jax.Array         # measured L1 multiplier (== lambda1 at the root)
    kkt: jax.Array        # Elastic Net KKT violation at beta
    keep: jax.Array       # (p,) gap-safe mask used for this point
    n_kept: jax.Array     # surviving columns
    gap: jax.Array        # duality gap at the screening warm point
    evals: jax.Array      # Illinois iterations spent (== SVEN solves)
    sven_iters: jax.Array # total inner solver iterations across evals
    cg_steps: jax.Array   # CG iterations across evals (0 with FISTA: no CG)
    stalls: jax.Array     # evals whose primal solve ended on its stall stop
    stop: jax.Array       # int8 root-find stop cause, one of the STOP_* codes


#: `EnetPoint.stop`: why the multiplier root-find ended. Only STOP_ROOT and
#: STOP_NO_ROOT are certified points; the other two mark a point whose
#: |nu - lambda1| is still above the stop tolerance.
STOP_NO_ROOT = 0   # lambda1 >= lambda1_max: beta = 0, nothing to solve
STOP_ROOT = 1      # |nu - lambda1| <= ftol
STOP_BRACKET = 2   # bracket narrower than wtol, |nu - lambda1| > ftol
STOP_EVALS = 3     # max_evals reached, |nu - lambda1| > ftol

#: A bracket closed at |nu - lambda1| above this share of lambda1 is wrong,
#: not merely unresolved, and is re-opened cold (`_enet_point`). A wrong-side
#: endpoint left nu 0.7 lambda1 off on YearPredictionMSD-shaped paths; where
#: emulated f64 (TPU v5e) cannot resolve nu to `f_rtol`, a bracket closes
#: within 5e-5 lambda1 of the root, which a cold re-bracket would only
#: polish at the cost of a dozen more solves.
REBRACKET_RTOL = 1e-3


class _Illinois(NamedTuple):
    t_lo: jax.Array
    f_lo: jax.Array
    t_hi: jax.Array
    f_hi: jax.Array
    side: jax.Array       # +1: last eval replaced lo, -1: hi, 0: fresh
    beta: jax.Array
    alpha: jax.Array
    w: jax.Array
    nu: jax.Array         # nu at the last evaluated point
    f: jax.Array          # nu - lambda1 at the last evaluated point
    evals: jax.Array
    iters: jax.Array
    cg: jax.Array         # CG iterations summed over the evaluations
    stalls: jax.Array     # evaluations ended on the primal's stall stop
    restarted: jax.Array  # bool: the bracket was re-opened cold once


def cold_carry(X: jax.Array, y: jax.Array) -> EnetCarry:
    """Zero warm state; nu(0) = lambda1_max is the exact multiplier at 0."""
    n, p = X.shape
    dtype = X.dtype
    return EnetCarry(beta=jnp.zeros((p,), dtype), alpha=jnp.zeros((2 * p,), dtype),
                     w=jnp.zeros((n,), dtype), t=jnp.zeros((), dtype),
                     nu=jnp.asarray(en.lambda1_max(X, y), dtype))


def _ridge_l1(X: jax.Array, y: jax.Array, lambda2) -> jax.Array:
    """|beta_ridge(lambda2)|_1 — the analytic top of the t bracket.

    For t >= this, the L1 constraint is slack so nu(t) = 0. Solved in the
    cheaper of the (p, p) primal or (n, n) dual normal equations; lambda2 is
    floored so the Lasso limit returns the min-norm least-squares point.
    Both systems are SPD and are solved through their eigendecomposition:
    the TPU compiler has no f64 LU (`jnp.linalg.solve`), and its f64
    Cholesky does not compile inside `shard_map` (the fold-parallel CV and
    the serving fan-out run this point solver there).
    """
    n, p = X.shape
    dtype = X.dtype
    lam = jnp.maximum(jnp.asarray(lambda2, dtype), 1e-8)

    def spd_solve(A, b):
        w, V = jnp.linalg.eigh(A)
        return V @ ((V.T @ b) / w)

    if p <= n:
        b = spd_solve(X.T @ X + lam * jnp.eye(p, dtype=dtype), X.T @ y)
    else:
        b = X.T @ spd_solve(X @ X.T + lam * jnp.eye(n, dtype=dtype), y)
    return jnp.sum(jnp.abs(b))


def _enet_point(X: jax.Array, y: jax.Array, lambda1, lambda2,
                carry: EnetCarry, config: PathConfig):
    """Solve one penalized (lambda1, lambda2) point on the constrained engine.

    Pure traced function: lambda1/lambda2/warm state are operands, config is
    static — usable directly under jit, lax.scan (paths) and vmap (CV folds,
    serving batches). Returns (next_carry, EnetPoint).

    A bracket that closes below `wtol` with nu still more than
    `REBRACKET_RTOL` * lambda1 from lambda1 (an evaluation's inexact warm
    solve put an endpoint on the wrong side of the root) is re-opened once,
    cold: [0, t_ridge] with zero warm starts, inside the same while_loop,
    so a vmapped lane never runs both branches of a cond. Every other point
    runs exactly as before. `EnetPoint.stop` records how the loop ended.
    """
    n, p = X.shape
    dtype = X.dtype
    lambda1 = jnp.asarray(lambda1, dtype)
    lambda2 = jnp.asarray(lambda2, dtype)

    if config.screen:
        with jax.named_scope("enet.screen"):
            scr = gap_safe_screen(X, y, carry.beta, lambda1, lambda2)
        keep, gap = scr.keep, scr.gap
    else:
        keep = jnp.ones((p,), bool)
        gap = jnp.zeros((), dtype)
    with jax.named_scope("enet.mask"):
        keepf = keep.astype(dtype)
        Xm = X * keepf[None, :]

    with jax.named_scope("enet.bracket"):
        l1max_m = 2.0 * jnp.max(jnp.abs(Xm.T @ y))
        t_ridge = _ridge_l1(Xm, y, lambda2)
    t_floor = config.t_floor_rel * t_ridge + jnp.asarray(1e-30, dtype)
    ftol = config.f_rtol * jnp.maximum(l1max_m, 1e-30)
    wtol = 1e-12 * t_ridge
    has_root = l1max_m > lambda1          # else beta* = 0 (top of the path)

    # Bracket f(t) = nu(t) - lambda1: analytic endpoints nu(0) = l1max_m and
    # nu(t_ridge) = 0; the warm (t, nu) from the previous (larger) lambda is a
    # tighter lower endpoint whenever it is on the correct side.
    f_warm = carry.nu - lambda1
    f_cold = l1max_m - lambda1
    warm_ok = (f_warm > 0) & (carry.t > 0) & (carry.t < t_ridge)
    state0 = _Illinois(
        t_lo=jnp.where(warm_ok, carry.t, 0.0),
        f_lo=jnp.where(warm_ok, f_warm, f_cold),
        t_hi=t_ridge,
        f_hi=-lambda1,
        side=jnp.zeros((), jnp.int32),
        beta=carry.beta * keepf,
        alpha=carry.alpha * jnp.concatenate([keepf, keepf]),
        w=carry.w,
        nu=carry.nu,
        f=jnp.where(warm_ok, f_warm, f_cold),
        evals=jnp.zeros((), jnp.int32),
        iters=jnp.zeros((), jnp.int32),
        cg=jnp.zeros((), jnp.int32),
        stalls=jnp.zeros((), jnp.int32),
        restarted=jnp.zeros((), bool),
    )

    def closed(s: _Illinois):
        return s.t_hi - s.t_lo <= wtol

    def cond(s: _Illinois):
        far = jnp.abs(s.f) > REBRACKET_RTOL * lambda1
        return ((s.evals < config.max_evals) & has_root
                & (jnp.abs(s.f) > ftol)
                & (~closed(s) | (far & ~s.restarted)))

    def body(s: _Illinois):
        # cond lets a closed bracket through only to re-open it (docstring)
        cold = closed(s)
        s = s._replace(
            t_lo=jnp.where(cold, 0.0, s.t_lo),
            f_lo=jnp.where(cold, f_cold, s.f_lo),
            t_hi=jnp.where(cold, t_ridge, s.t_hi),
            f_hi=jnp.where(cold, -lambda1, s.f_hi),
            side=jnp.where(cold, 0, s.side),
            alpha=jnp.where(cold, 0.0, s.alpha),
            w=jnp.where(cold, 0.0, s.w),
            restarted=s.restarted | cold)
        frac = s.f_lo / jnp.maximum(s.f_lo - s.f_hi, 1e-30)
        frac = jnp.clip(frac, 0.05, 0.95)   # never stall on an endpoint
        t_c = jnp.maximum(s.t_lo + frac * (s.t_hi - s.t_lo), t_floor)
        arrs = _sven_core(Xm, y, t_c, lambda2, s.alpha, s.w, config.solver)
        with jax.named_scope("enet.grad"):
            g = en.smooth_grad(Xm, y, arrs.beta, lambda2)
            nu_c = jnp.max(jnp.abs(g) * keepf)
        f_c = nu_c - lambda1
        went_lo = f_c >= 0
        # Illinois: replacing the same endpoint twice halves the stale side's
        # f, forcing the secant off that endpoint (superlinear on kinks).
        f_hi = jnp.where(went_lo,
                         jnp.where(s.side == 1, 0.5 * s.f_hi, s.f_hi), f_c)
        t_hi = jnp.where(went_lo, s.t_hi, t_c)
        f_lo = jnp.where(went_lo, f_c,
                         jnp.where(s.side == -1, 0.5 * s.f_lo, s.f_lo))
        t_lo = jnp.where(went_lo, t_c, s.t_lo)
        side = jnp.where(went_lo, 1, -1).astype(jnp.int32)
        return _Illinois(t_lo, f_lo, t_hi, f_hi, side, arrs.beta, arrs.alpha,
                         arrs.w, nu_c, f_c, s.evals + 1,
                         s.iters + arrs.iters.astype(jnp.int32),
                         s.cg + arrs.cg_steps.astype(jnp.int32),
                         s.stalls + arrs.stalled.astype(jnp.int32),
                         s.restarted)

    s = jax.lax.while_loop(cond, body, state0)
    stop = jnp.where(~has_root, STOP_NO_ROOT,
                     jnp.where(jnp.abs(s.f) <= ftol, STOP_ROOT,
                               jnp.where(closed(s), STOP_BRACKET,
                                         STOP_EVALS))).astype(jnp.int8)

    ok = has_root.astype(dtype)
    beta = s.beta * keepf * ok
    t_out = jnp.sum(jnp.abs(beta))
    nu_out = jnp.where(has_root, s.nu, l1max_m)
    next_carry = EnetCarry(beta=beta, alpha=s.alpha * ok, w=s.w * ok,
                           t=t_out, nu=nu_out)
    with jax.named_scope("enet.kkt"):
        kkt = en.kkt_violation(X, y, beta, lambda2)
    point = EnetPoint(beta=beta, t=t_out, nu=nu_out, kkt=kkt,
                      keep=keep, n_kept=jnp.sum(keep), gap=gap,
                      evals=s.evals, sven_iters=s.iters, cg_steps=s.cg,
                      stalls=s.stalls, stop=stop)
    return next_carry, point


# ---------------------------------------------------------------------------
# jitted entry points: single solve, scan path, vmapped batch
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",))
def _enet_jit(X, y, lambda1, lambda2, carry, config: PathConfig):
    _bump_trace("enet")
    return _enet_point(X, y, lambda1, lambda2, carry, config)


@partial(jax.jit, static_argnames=("config",))
def _enet_path_scan(X, y, lambda1s, lambda2, config: PathConfig) -> EnetPoint:
    _bump_trace("enet_path_scan")

    def body(carry, lam1):
        return _enet_point(X, y, lam1, lambda2, carry, config)

    _, points = jax.lax.scan(body, cold_carry(X, y), lambda1s)
    return points


def _enet_solve_one(config: PathConfig):
    def one(X_, y_, l1_, l2_, warm_, hw_):
        carry = cold_carry(X_, y_)
        if warm_ is not None:
            # hw_ selects per problem: a cache hit rides its stored warm
            # state, a miss stays exactly cold — one executable either way.
            carry = jax.tree.map(
                lambda w, c: jnp.where(hw_, w.astype(c.dtype), c), warm_, carry)
        return _enet_point(X_, y_, l1_, l2_, carry, config)
    return one


@partial(jax.jit, static_argnames=("config", "axes"))
def _enet_batch_jit(X, y, lambda1, lambda2, warm, has_warm,
                    config: PathConfig, axes) -> EnetPoint:
    from repro.core.batch import solve_lanes

    _bump_trace("enet_batch")
    return solve_lanes(_enet_solve_one(config),
                       (X, y, lambda1, lambda2, warm, has_warm), axes)


@partial(jax.jit, static_argnames=("config", "axes", "mesh"))
def _enet_batch_sharded_jit(X, y, lambda1, lambda2, warm, has_warm,
                            config: PathConfig, axes, mesh) -> EnetPoint:
    """Penalized stack over the batch axis via `batch.shard_map_lanes`:
    each device runs its local lanes' whole multiplier root-find with ZERO
    collectives — solver while_loops never synchronize across devices."""
    from repro.core.batch import shard_map_lanes, solve_lanes

    _bump_trace("enet_batch")

    def local(*ops):
        return solve_lanes(_enet_solve_one(config), ops, axes)

    return shard_map_lanes(mesh, axes, local,
                           (X, y, lambda1, lambda2, warm, has_warm))


def enet_batch(X, y, lambda1s, lambda2s,
               config: PathConfig = PathConfig(), *,
               warm: Optional[EnetCarry] = None,
               has_warm: Optional[jax.Array] = None,
               return_carry: bool = False,
               route: str = "auto"):
    """Stacked penalized solves in one vmapped executable (serving layer).

    Batch axes by rank, as in `core.batch.sven_batch`: X (B, n, p) or (n, p)
    shared; y (B, n) or (n,); lambda1/lambda2 (B,) or scalar. Every field of
    the returned EnetPoint carries a leading (B,) axis. Under an active
    `repro.dist.mesh_context` the stacked operands take the rule table's
    "batch" axis placement when the `core.routing` cost model prefers the
    fan-out for this shape, exactly as `sven_batch` does; `route=` pins the
    layout ("batch" / "single").

    `warm` is an optional stacked EnetCarry (every field with a leading (B,)
    axis) and `has_warm` a (B,) bool selecting, per problem, the warm state
    over a cold start — the serving runtime's cache feeds adjacent-lambda
    solutions back through this without splitting the executable. With
    `return_carry` the final stacked EnetCarry comes back alongside the
    points (the state the runtime stores for the NEXT adjacent request);
    default is points only.
    """
    from repro.core.batch import _maybe_shard_batch, batch_mesh

    tracer = get_tracer()
    with tracer.span("enet_path.prepare"):
        X = jnp.asarray(X)
        dtype = X.dtype
        y = jnp.asarray(y, dtype)
        lambda1s = jnp.asarray(lambda1s, dtype)
        lambda2s = jnp.asarray(lambda2s, dtype)
        axes = (0 if X.ndim == 3 else None,
                0 if y.ndim == 2 else None,
                0 if lambda1s.ndim == 1 else None,
                0 if lambda2s.ndim == 1 else None,
                0 if warm is not None else None,
                0 if warm is not None else None)
        sizes = {op.shape[0]
                 for op, ax in zip((X, y, lambda1s, lambda2s), axes) if ax == 0}
        if not sizes:
            raise ValueError("enet_batch: no batched operand (use enet())")
        if (warm is None) != (has_warm is None):
            raise ValueError(
                "enet_batch: warm and has_warm must be given together")
        if has_warm is not None:
            has_warm = jnp.asarray(has_warm, bool)
            sizes.update(jnp.asarray(f).shape[0] for f in warm)
            sizes.add(has_warm.shape[0])
        if len(sizes) != 1:
            raise ValueError(
                f"enet_batch: inconsistent batch sizes {sorted(sizes)}")
        # route BEFORE placing (see sven_batch): the penalized lane runs the
        # whole multiplier root-find, priced via form="penalized".
        mesh = batch_mesh(next(iter(sizes)), X.shape[-2], X.shape[-1],
                          form="penalized", route=route)
        if mesh is not None:
            X, y, lambda1s, lambda2s = (
                _maybe_shard_batch(op, ax == 0)
                for op, ax in zip((X, y, lambda1s, lambda2s), axes[:4]))
            if warm is not None:
                warm = EnetCarry(*(_maybe_shard_batch(jnp.asarray(f), True)
                                   for f in warm))
                has_warm = _maybe_shard_batch(has_warm, True)
        config = resolve_path_config(config, X, y)
    with tracer.span("enet_path.dispatch"):
        if mesh is not None:
            carry, points = _enet_batch_sharded_jit(
                X, y, lambda1s, lambda2s, warm, has_warm, config, axes, mesh)
        else:
            carry, points = _enet_batch_jit(X, y, lambda1s, lambda2s, warm,
                                            has_warm, config, axes)
    return (points, carry) if return_carry else points


# ---------------------------------------------------------------------------
# Public penalized API (original scale)
# ---------------------------------------------------------------------------

class EnetResult(NamedTuple):
    beta: jax.Array        # (p,) original-scale coefficients
    intercept: jax.Array   # ()
    lambda1: float
    lambda2: float
    t: jax.Array           # |beta_std|_1 — the constrained-form budget
    nu: jax.Array          # measured multiplier (== lambda1 at convergence)
    n_kept: jax.Array      # columns surviving the gap-safe screen
    evals: jax.Array       # SVEN solves spent on the multiplier root-find
    sven_iters: jax.Array
    cg_steps: jax.Array    # CG iterations (0 with the FISTA solver)
    stalls: jax.Array      # evals ended on the primal's stall stop
    stop: jax.Array        # root-find stop code (STOP_*)


class EnetPath(NamedTuple):
    lambda1s: jax.Array    # (L,) descending grid
    lambda2: float
    betas: jax.Array       # (L, p) original-scale coefficients
    intercepts: jax.Array  # (L,)
    ts: jax.Array          # (L,) constrained budgets |beta*|_1
    nus: jax.Array         # (L,) measured multipliers
    kkts: jax.Array        # (L,) Elastic Net KKT violations
    n_kept: jax.Array      # (L,) columns surviving the screen
    evals: jax.Array       # (L,) SVEN solves per point
    sven_iters: jax.Array  # (L,)
    cg_steps: jax.Array    # (L,) CG iterations (0 with the FISTA solver)
    stalls: jax.Array      # (L,) evals ended on the primal's stall stop
    stop: jax.Array        # (L,) int8 root-find stop codes (STOP_*)


def enet(X, y, lambda1, lambda2, *, standardize: bool = False,
         fit_intercept: bool = False,
         config: PathConfig = PathConfig()) -> EnetResult:
    """Solve one penalized Elastic Net (paper scaling) via the SVEN engine."""
    tracer = get_tracer()
    with tracer.span("enet_path.prepare"):
        X = jnp.asarray(X)
        y = jnp.asarray(y, X.dtype)
        Xs, ys, scaler = standardize_fit(X, y, standardize=standardize,
                                         fit_intercept=fit_intercept)
        config = resolve_path_config(config, Xs, ys)
    with tracer.span("enet_path.dispatch"):
        _, pt = _enet_jit(Xs, ys, jnp.asarray(lambda1, X.dtype),
                          jnp.asarray(lambda2, X.dtype), cold_carry(Xs, ys),
                          config)
    with tracer.span("enet_path.unscale"):
        beta, intercept = unscale_coef(pt.beta, scaler)
    return EnetResult(beta=beta, intercept=intercept, lambda1=float(lambda1),
                      lambda2=float(lambda2), t=pt.t, nu=pt.nu,
                      n_kept=pt.n_kept, evals=pt.evals,
                      sven_iters=pt.sven_iters, cg_steps=pt.cg_steps,
                      stalls=pt.stalls, stop=pt.stop)


def enet_path(X, y, *, lambda1s=None, n_lambdas: int = 40,
              eps: Optional[float] = None, lambda2=1.0,
              standardize: bool = False, fit_intercept: bool = False,
              config: PathConfig = PathConfig()) -> EnetPath:
    """glmnet-style regularization path: ONE jitted scan over the lambda grid.

    The grid is computed on the standardized problem (as glmnet does); the
    whole path — screening, bracketing and every warm-started SVEN solve —
    compiles to a single executable per (shape, grid length, config), so
    re-solving with new data or a rescaled grid never retraces
    (`trace_counts()["enet_path_scan"]`).

    Under `repro.dist.mesh_context(mesh)`, a design whose one-chip plan
    does not fit a chip (`core.routing.route_path`) takes the row layout:
    the standardized rows are zero-padded to a multiple of the mesh size
    and placed over the mesh (`core.distributed.shard_rows`; arrays already
    placed so stay where they are), and XLA's partitioner runs every pass
    over X on each chip's rows, ending each reduction over rows in an
    all-reduce. Zero rows change no answer, and the scaler reads the rows
    as given, before padding. Arrays a caller placed with padding of its
    own hold that padding as rows of the problem: pass them only with
    `standardize=False, fit_intercept=False`.

    Besides the coefficients, every point reports its root-find
    evaluations, inner iterations, CG steps (0 with the FISTA solver, which
    runs no CG), the evaluations whose primal solve ended on its stall stop
    (`core.svm.primal_newton`; 0 in the dual) and root-find stop code
    (`STOP_*`); each call appends them as a `PathRecord` to
    `repro.obs.default_solve_log()`, without a sync.
    """
    from repro import dist
    from repro.core.distributed import shard_rows
    from repro.core.routing import route_path

    tracer = get_tracer()
    with tracer.span("enet_path.prepare"):
        X = jnp.asarray(X)
        y = jnp.asarray(y, X.dtype)
        Xs, ys, scaler = standardize_fit(X, y, standardize=standardize,
                                         fit_intercept=fit_intercept)
        if lambda1s is None:
            lambda1s = lambda_grid(Xs, ys, n_lambdas=n_lambdas, eps=eps)
        lambda1s = jnp.asarray(lambda1s, X.dtype)
        ctx = dist.current_context()
        mesh = ctx[0] if ctx is not None else None
        layout = route_path(*Xs.shape, Xs.dtype.itemsize, mesh)
        _layout_counter().inc(layout=layout)
        if layout == "rows":
            with tracer.span("enet_path.place"):
                Xs, ys = shard_rows(mesh, Xs, ys)
        config = resolve_path_config(config, Xs, ys)
    with tracer.span("enet_path.dispatch"):
        pts = _enet_path_scan(Xs, ys, lambda1s, jnp.asarray(lambda2, X.dtype),
                              config)
    default_solve_log().add(PathRecord(evals=pts.evals,
                                       sven_iters=pts.sven_iters,
                                       cg_steps=pts.cg_steps,
                                       stalls=pts.stalls, stop=pts.stop))
    with tracer.span("enet_path.unscale"):
        betas, intercepts = unscale_coef(pts.beta, scaler)
    return EnetPath(lambda1s=lambda1s, lambda2=float(lambda2), betas=betas,
                    intercepts=intercepts, ts=pts.t, nus=pts.nu, kkts=pts.kkt,
                    n_kept=pts.n_kept, evals=pts.evals,
                    sven_iters=pts.sven_iters, cg_steps=pts.cg_steps,
                    stalls=pts.stalls, stop=pts.stop)


def _layout_counter():
    """``enet_path_layout_total{layout="single"|"rows"}``: `enet_path`
    calls by the layout `core.routing.route_path` chose."""
    from repro.obs.metrics import default_registry
    return default_registry().counter(
        "enet_path_layout_total", "enet_path calls by row layout",
        ("layout",))


class ElasticNet:
    """sklearn-style estimator over the penalized SVEN front-end.

    Parameters are in the paper's scaling (no 1/2, no 1/n — see DESIGN.md §7
    for conversions from glmnet/sklearn). After `fit`: `coef_`, `intercept_`,
    `t_` (the constrained budget the fit mapped to), `n_kept_`.
    """

    def __init__(self, lambda1: float, lambda2: float = 1.0, *,
                 standardize: bool = True, fit_intercept: bool = True,
                 config: PathConfig = PathConfig()):
        self.lambda1 = lambda1
        self.lambda2 = lambda2
        self.standardize = standardize
        self.fit_intercept = fit_intercept
        self.config = config

    def fit(self, X, y):
        res = enet(X, y, self.lambda1, self.lambda2,
                   standardize=self.standardize,
                   fit_intercept=self.fit_intercept, config=self.config)
        self.coef_ = res.beta
        self.intercept_ = res.intercept
        self.t_ = res.t
        self.nu_ = res.nu
        self.n_kept_ = res.n_kept
        return self

    def predict(self, X):
        return jnp.asarray(X) @ self.coef_ + self.intercept_
