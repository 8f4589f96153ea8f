"""SVEN driver — the paper's Algorithm 1 as a jit-native JAX engine.

Dispatch (paper §3, "Implementation details"):
    2p > n  -> primal solver over w in R^n   (cost driven by n)
    else    -> dual solver over alpha in R^{2p}, kernel cached when it fits

The primal solver's Newton Hessian is formed explicitly (n x n, once per
Newton step) when n <= EXPLICIT_HESSIAN_MAX_N on the XLA backend, and
applied matrix-free inside CG otherwise.

`matrix_free=True` (default) uses the SvenOperator O(np) products and never
materializes the (2p, n) constructed dataset — the TPU-native path.
`matrix_free=False` is the paper-faithful baseline (explicit Xnew, as the
MATLAB listing does). Both return identical solutions (tested).

Engine architecture (DESIGN.md §6): `t` and `lambda2` are *traced* scalars,
so `sven()` compiles exactly once per (shape, dtype, warm-start structure,
config) — sweeping the regularization surface never retraces. `sven_path`
is a single jitted `lax.scan` over the t-grid that carries the warm dual
alpha AND primal w through the scan; `sven_path_reference` keeps the
host-side Python loop as the testable reference. `core/batch.py` vmaps the
same core over stacked problems and `serve/engine.py` buckets live request
queues onto these compiled executables. Trace counts are observable via
`trace_counts()` — tests assert the compile-once property.

Gap-safe screening (`core/screening.py`) plugs in through the optional
`keep` mask: a (p,) boolean operand that zeroes provably-inactive columns
and scatters their coefficients back as exact zeros — fixed shapes, so the
compile-once property survives. The glmnet-parity penalized front-end
(`core/api.py`: lambda grids, `enet_path`, estimators; `core/cv.py`:
batched `ElasticNetCV`) drives this core through the `t = |beta*|_1`
penalized<->constrained equivalence (DESIGN.md §7).

The returned diagnostics make the solve auditable at scale: iteration counts,
final KKT residuals of the *original* Elastic Net problem, and the objective.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import elastic_net as en
from repro.core import reduction as red
from repro.core.svm import solve_dual_fista, solve_dual_newton, solve_primal_newton
from repro.kernels import registry

# ---------------------------------------------------------------------------
# Trace instrumentation: each jit-wrapped entry point bumps its counter ONCE
# per trace (the bump runs at trace time, not at execution time). Tests
# assert e.g. a 40-point path costs exactly one trace. The counts
# live on the process-wide obs registry (``solver_traces_total{entry=...}``,
# DESIGN.md §12.2) so they export beside router decisions; a `trace:<entry>`
# instant marks WHEN each (re)trace happened on the timeline — a nonzero
# steady-state count is the regression the zero-retrace CI gate catches.
# ---------------------------------------------------------------------------

def _trace_counter():
    from repro.obs.metrics import default_registry
    return default_registry().counter(
        "solver_traces_total", "jit traces per solver entry point", ("entry",))


def _bump_trace(name: str) -> None:
    _trace_counter().inc(entry=name)
    from repro.obs.trace import get_tracer
    get_tracer().instant(f"trace:{name}")


def trace_counts() -> dict:
    """Snapshot of {entry_point: times_traced} since the last reset."""
    return {entry: int(v)
            for (entry,), v in _trace_counter().series().items()}


def reset_trace_counts() -> None:
    from repro.obs.metrics import default_registry
    default_registry().reset_instrument("solver_traces_total")


#: Largest primal dimension d = n at which the XLA primal solve forms the
#: Newton Hessian H = I + 2C Xhat^T diag(act) Xhat (n x n) once per Newton
#: step and runs CG on H @ v, instead of two passes over X per CG step.
#: Forming H costs one n x p x n GEMM; each CG step it saves costs two
#: n x p GEMVs, which the TPU runs far below its GEMM rate (f64 emulation
#: splits X anew on every CG step). On a TPU v5e at p = 22,283 in f64 one
#: Newton step took 5.0x less time explicit at n = 85, 10.7x at 256, 10.5x
#: at 512, 16.8x at 1024 and 14.3x at 2048 (PERF.md); 2048 is the largest n
#: measured, so above it, where H's n^2 storage grows, CG stays matrix-free.
EXPLICIT_HESSIAN_MAX_N = 2048


def _bump_hessian_form(form: str) -> None:
    """Count, at trace time, which Hessian form a primal solve took
    (``sven_hessian_form_total{form="explicit"|"matrix_free"}``)."""
    from repro.obs.metrics import default_registry
    default_registry().counter(
        "sven_hessian_form_total", "traced primal solves by Hessian form",
        ("form",)).inc(form=form)


class SvenArrays(NamedTuple):
    """Arrays-only solve result — the jit/scan/vmap-safe core payload."""

    beta: jax.Array
    alpha: jax.Array
    w: jax.Array              # primal iterate (dual mode: w = Zhat @ alpha)
    iters: jax.Array
    opt_residual: jax.Array
    kkt: jax.Array
    cg_steps: jax.Array       # CG iterations over all Newton steps (0: FISTA)
    stalled: jax.Array        # bool: primal stall stop (dual: False)


class SvenSolution(NamedTuple):
    beta: jax.Array
    alpha: jax.Array
    mode: str                 # "primal" | "dual"
    iters: jax.Array
    opt_residual: jax.Array   # solver's own optimality measure
    kkt: jax.Array            # Elastic Net KKT violation at beta
    w: jax.Array              # primal SVM iterate — warm-start carrier


#: every accepted SvenConfig.backend value: "xla" = no kernels module at
#: all (pure-jnp matrix-free reduction); "auto" = kernel registry, body
#: resolved from the operands' platform; the rest are RESOLVED kernel
#: backends (kernels/registry.py: body + execution mode).
BACKENDS = ("xla", "auto") + registry.RESOLVED_BACKENDS


@dataclasses.dataclass(frozen=True)
class SvenConfig:
    mode: str = "auto"            # "auto" | "primal" | "dual"
    matrix_free: bool = True      # SvenOperator vs explicit Xnew
    cache_kernel: str = "auto"    # "auto" | "blocks" | "never" (dual only)
    solver: str = "newton"        # "newton" | "fista" (dual only)
    backend: str = "xla"          # one of BACKENDS (DESIGN.md §10)
    # kernel MAC/storage precision: "f32" | "bf16". Applies to the
    # registry-backed kernel paths only ("xla" and the ref oracle always
    # compute at full input precision); bf16 dual solves get one
    # full-precision iterative-refinement re-solve (DESIGN.md §10.3) so the
    # <= 1e-10 parity gates still hold.
    precision: str = "f32"
    tol: float = 1e-8
    max_newton: int = 60
    cg_iters: int = 300
    kernel_cache_max_m: int = 8192   # cache K when 2p <= this
    lambda2_floor: float = red.LAMBDA2_FLOOR  # Lasso limit: C capped at 1/(2*floor)

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"SvenConfig.backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")
        if self.precision not in registry.PRECISIONS:
            raise ValueError(f"SvenConfig.precision must be one of "
                             f"{registry.PRECISIONS}, got "
                             f"{self.precision!r}")


def _pick_mode(n: int, p: int, cfg: SvenConfig) -> str:
    if cfg.mode != "auto":
        return cfg.mode
    return "primal" if 2 * p > n else "dual"


def resolve_backend(config: SvenConfig, *arrays) -> SvenConfig:
    """Pin the kernel backend enum into the (static, jit-keyed) config.

    Resolution happens BEFORE tracing, against the devices the concrete
    input arrays are committed to (`kernels.registry.resolve_kernel_backend`
    — never the process default backend at trace time, DESIGN.md §9.3), so
    the compiled executable matches where the data actually lives; two
    placements that need different kernel bodies get different jit keys,
    and "auto" and the backend it resolves to share one. A no-op (same
    object) for the "xla" backend and for already-resolved configs, which
    `api.resolve_path_config` relies on.
    """
    if config.backend == "xla":
        return config
    resolved = registry.resolve_kernel_backend(config.backend, *arrays)
    if resolved == config.backend:
        return config
    return dataclasses.replace(config, backend=resolved)


def _sven_core(
    X: jax.Array,
    y: jax.Array,
    t: jax.Array,
    lambda2: jax.Array,
    warm_alpha: Optional[jax.Array],
    warm_w: Optional[jax.Array],
    config: SvenConfig,
    keep: Optional[jax.Array] = None,
) -> SvenArrays:
    """Pure traced core: t/lambda2/warm starts are operands, config is static.

    `keep` is an optional (p,) screening mask (e.g. from `gap_safe_screen`):
    masked columns are zeroed — a fixed-shape form of feature screening that
    survives jit/scan/vmap — and the returned beta is scattered back to exact
    zeros on the discarded coordinates. Because a zero column provably carries
    beta_j = 0 through the reduction (see serve/engine.py padding argument),
    a *safe* mask leaves the solution unchanged.
    """
    n, p = X.shape
    dtype = X.dtype
    t = jnp.asarray(t, dtype)
    lambda2 = jnp.asarray(lambda2, dtype)
    X_full = X    # KKT diagnostics stay on the ORIGINAL problem: an unsafe
    keepf = None  # mask must show up as a large kkt, not pass trivially
    if keep is not None:
        keepf = keep.astype(dtype)
        X = X * keepf[None, :]
        if warm_alpha is not None:
            # symmetrize masked duplicate pairs so dual warm starts can't
            # leave stale asymmetric mass on screened-out samples
            warm_alpha = warm_alpha * jnp.concatenate([keepf, keepf])
    C = red.svm_C(lambda2, floor=config.lambda2_floor).astype(dtype)
    mode = _pick_mode(n, p, config)
    op = red.SvenOperator(X=X, y=y, t=t)

    if mode == "primal":
        if config.matrix_free:
            matvec, rmatvec = op.xhat_matvec, op.xhat_rmatvec
        else:
            Xhat, _ = red.build_svm_dataset(X, y, t)
            matvec = lambda w: Xhat @ w
            rmatvec = lambda v: Xhat.T @ v
        yhat = jnp.concatenate([jnp.ones((p,), dtype), -jnp.ones((p,), dtype)])
        hess_matvec = None
        if config.backend != "xla":
            from repro.kernels.ops import hinge_hessian_matvec

            def hess_matvec(v, act, C_traced):  # noqa: F811 — Pallas fused H v
                hv = hinge_hessian_matvec(
                    X.astype(jnp.float32), y.astype(jnp.float32),
                    jnp.asarray(t, jnp.float32), jnp.asarray(C_traced, jnp.float32),
                    act[:p].astype(jnp.float32), act[p:].astype(jnp.float32),
                    v.astype(jnp.float32), backend=config.backend,
                    precision=config.precision)
                return hv.astype(dtype)

        weighted_gram = None
        if (config.matrix_free and hess_matvec is None
                and n <= EXPLICIT_HESSIAN_MAX_N):
            weighted_gram = op.xhat_weighted_gram
        _bump_hessian_form("matrix_free" if weighted_gram is None
                           else "explicit")
        res = solve_primal_newton(
            matvec, rmatvec, yhat, C, n,
            tol=config.tol, max_newton=config.max_newton, cg_iters=config.cg_iters,
            w0=warm_w, hess_matvec=hess_matvec, weighted_gram=weighted_gram,
        )
        alpha = C * jnp.maximum(1.0 - yhat * matvec(res.w), 0.0)  # Alg.1 line 7
        beta = red.recover_beta(alpha, t)
        if keepf is not None:
            beta = beta * keepf
        return SvenArrays(beta=beta, alpha=alpha, w=res.w, iters=res.iters,
                          opt_residual=res.grad_norm,
                          kkt=en.kkt_violation(X_full, y, beta, lambda2),
                          cg_steps=res.cg_steps, stalled=res.stalled)

    # --- dual ---
    m = 2 * p
    cache = config.cache_kernel
    if cache == "auto":
        cache = "blocks" if m <= config.kernel_cache_max_m else "never"
    refine = False
    if cache == "blocks":
        if config.backend != "xla":
            from repro.kernels.ops import shifted_gram
            K = shifted_gram(X.astype(jnp.float32), y.astype(jnp.float32),
                             jnp.asarray(t, jnp.float32),
                             backend=config.backend,
                             precision=config.precision).astype(dtype)
            refine = config.precision != "f32"
        elif config.matrix_free:
            K = red.gram_blocks(X, y, t)
        else:
            K = red.gram_reference(X, y, t)
        kernel_matvec = lambda v: K @ v
    else:
        kernel_matvec = op.kernel_matvec

    solver = solve_dual_newton if config.solver == "newton" else solve_dual_fista
    res = solver(kernel_matvec, m, C, dtype=dtype, tol=config.tol, alpha0=warm_alpha)
    cg_steps = res.cg_steps
    if refine:
        # one step of iterative refinement (DESIGN.md §10.3): the bf16
        # kernel bought the O(np^2) Gram pass cheap; re-solving MATRIX-FREE
        # at full input precision, warm-started from the low-precision
        # alpha, re-evaluates every Newton residual against exact Gram
        # statistics at O(np) per iteration and converges in a handful of
        # steps — restoring <= 1e-10 parity with the full-precision solve.
        res = solver(op.kernel_matvec, m, C, dtype=dtype, tol=config.tol,
                     alpha0=res.alpha)
        cg_steps = cg_steps + res.cg_steps
    beta = red.recover_beta(res.alpha, t)
    if keepf is not None:
        beta = beta * keepf
    # w = Zhat @ alpha: the primal iterate this dual solution induces — carried
    # so a following primal-mode solve (or the scan) can warm-start from it.
    w = op.zhat_matvec(res.alpha)
    return SvenArrays(beta=beta, alpha=res.alpha, w=w, iters=res.iters,
                      opt_residual=res.pg_norm,
                      kkt=en.kkt_violation(X_full, y, beta, lambda2),
                      cg_steps=cg_steps, stalled=jnp.zeros((), bool))


@partial(jax.jit, static_argnames=("config",))
def _sven_jit(X, y, t, lambda2, warm_alpha, warm_w, keep, config: SvenConfig) -> SvenArrays:
    _bump_trace("sven")
    return _sven_core(X, y, t, lambda2, warm_alpha, warm_w, config, keep)


def sven(
    X: jax.Array,
    y: jax.Array,
    t,
    lambda2,
    config: SvenConfig = SvenConfig(),
    *,
    warm_alpha: Optional[jax.Array] = None,
    warm_w: Optional[jax.Array] = None,
    keep: Optional[jax.Array] = None,
) -> SvenSolution:
    """Solve the Elastic Net (paper eq. 1) via the SVM reduction.

    `t` and `lambda2` are jit operands: repeated calls at new regularization
    settings on the same-shape problem reuse one compiled executable
    (assertable via `trace_counts()["sven"]`).

    `keep` is an optional (p,) safe screening mask (see `core/screening.py`
    and the penalized front-end in `core/api.py`): screened-out columns are
    zeroed and their coefficients scattered back as exact zeros, without
    changing the compiled shape.
    """
    config = resolve_backend(config, X, y)
    arrs = _sven_jit(X, y, jnp.asarray(t, X.dtype), jnp.asarray(lambda2, X.dtype),
                     warm_alpha, warm_w, keep, config)
    mode = _pick_mode(X.shape[0], X.shape[1], config)
    return SvenSolution(beta=arrs.beta, alpha=arrs.alpha, mode=mode,
                        iters=arrs.iters, opt_residual=arrs.opt_residual,
                        kkt=arrs.kkt, w=arrs.w)


@partial(jax.jit, static_argnames=("config",))
def _sven_path_scan(X, y, ts, lambda2, config: SvenConfig) -> jax.Array:
    _bump_trace("sven_path_scan")
    n, p = X.shape
    dtype = X.dtype

    def body(carry, t):
        warm_a, warm_w = carry
        arrs = _sven_core(X, y, t, lambda2, warm_a, warm_w, config)
        return (arrs.alpha, arrs.w), arrs.beta

    carry0 = (jnp.zeros((2 * p,), dtype), jnp.zeros((n,), dtype))
    _, betas = jax.lax.scan(body, carry0, ts)
    return betas


def sven_path(
    X: jax.Array,
    y: jax.Array,
    ts,
    lambda2,
    config: SvenConfig = SvenConfig(),
) -> jax.Array:
    """Regularization path over a grid of L1 budgets (Fig. 1), scan-compiled.

    One `lax.scan` over the t-grid: the whole path is a single trace / single
    executable (per grid *length*, not per grid *values*), and both warm
    starts — the dual alpha and the primal w — are genuinely carried from
    point to point. Warm-starting across the grid is a beyond-paper
    optimization (the paper solves each (t, lambda2) cold); it typically cuts
    total Newton iterations 2-4x along a 40-point path, and the scan removes
    the per-point dispatch/retrace cost on top.

    `sven_path_reference` is the host-side loop with identical warm-start
    semantics; the two are tested equal to 1e-6.
    """
    ts = jnp.asarray(ts, X.dtype)
    config = resolve_backend(config, X, y)
    return _sven_path_scan(X, y, ts, jnp.asarray(lambda2, X.dtype), config)


def sven_path_reference(
    X: jax.Array,
    y: jax.Array,
    ts,
    lambda2,
    config: SvenConfig = SvenConfig(),
) -> jax.Array:
    """Reference Python-loop path, warm-started like the scan (alpha AND w)."""
    betas = []
    warm_a, warm_w = None, None
    for t in list(ts):
        sol = sven(X, y, float(t), lambda2, config, warm_alpha=warm_a, warm_w=warm_w)
        betas.append(sol.beta)
        warm_a, warm_w = sol.alpha, sol.w
    return jnp.stack(betas)
