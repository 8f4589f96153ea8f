"""Squared-hinge linear SVM, primal Newton-CG (Chapelle 2007), no bias.

    min_w f(w) = 1/2 ||w||^2 + C sum_i max(0, 1 - yhat_i w^T xhat_i)^2

Newton system at the current support-vector set SV = {i : margin_i < 1}:

    H = I + 2C Xhat_SV^T Xhat_SV
    H d = grad,   grad = w + 2C Xhat^T (act * (Xhat w - yhat))

solved with conjugate gradients, followed by a backtracking line search.
For a fixed SV set f is quadratic, so the method takes full steps near the
solution and terminates in a handful of iterations.

CG runs on H in one of two forms, chosen by the caller from the shape:

- explicit (`weighted_gram` given): H (d x d) is formed once per Newton
  step, under the scope `sven.hessian`, from one GEMM with X
  (`SvenOperator.xhat_weighted_gram`), and each CG step is H @ v.
  `core/sven.py` takes this form at small d, where the d x d matrix is
  cheap and a pass over X per CG step is not.
- matrix-free: each CG step applies H v = v + 2C Xhat^T (act * Xhat v),
  two passes over X, or the caller's `hess_matvec` override (the fused
  Pallas body, the feature-sharded mat-vec of `core/distributed.py`).

The gradient, the line search and the stop test are matrix-free in both
forms, so the fixed point does not depend on the form.

The machine's `aux` is the int32 count of CG iterations spent so far
(`PrimalResult.cg_steps`): `_cg` returns the iteration count its loop
already carries, and each Newton step adds it.

A solve stops when max |grad| <= tol, or on the stall stop: a Newton step
whose line search had to halve (s < 1) while the predicted decrease
gd = grad . d was at most `STALL_RTOL` * max(|f|, 1) is the solve's last
step. It is still applied. At that size gd is rounding: the Armijo test
then compares f values that differ by noise alone, the search halves until
noise lets a step through, w moves by rounding, and every further step
repeats the search from the same w until `max_newton`. So the stop returns
the answer the cap would have returned, to rounding, without those steps.
A step taken at s = 1 never stalls, so the last full steps near the
optimum still run to the gradient test. `PrimalResult.stalled` records a
stall stop apart from the tolerance: the machine's `converged` is the stop
flag, and `grad_norm` stays above `tol` on a stalled solve.

The solver is a `SolverState` init/step/run machine (state.py, DESIGN.md
§6): hyperparameters (C, tol) are traced scalars, the carry is fixed-shape,
and everything is jax.lax control flow — so one trace serves a whole
(t, lambda2) grid under `lax.scan` and stacked problems under `vmap`, and
the mat-vec callables may close over pjit-sharded arrays or shard_map
collectives.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.svm.state import (Hyper, SolverMachine, SolverState,
                                  initial_state, make_hyper, run_machine)


#: The stall stop's threshold on gd, a share of |f| (module docstring). On
#: GLI-85-shaped paths in f64, stalled steps read gd <= 4e-15 |f| on a CPU
#: and up to 5e-9 |f| on a TPU v5e, whose emulated f64 gradient carries
#: more rounding; productive halving steps read gd >= 3e-3 |f| on both
#: (PERF.md). A multiple of eps(dtype) wide enough for the TPU would
#: exceed |f| in float32 and stop every halving step there; 1e-7 is below
#: one float32 rounding of f, where float32 solves stall.
STALL_RTOL = 1e-7


class PrimalResult(NamedTuple):
    w: jax.Array
    iters: jax.Array
    grad_norm: jax.Array
    objective: jax.Array
    cg_steps: jax.Array     # CG iterations summed over the Newton steps
    stalled: jax.Array      # bool: ended on the stall stop, not on tol


def _cg(matvec: Callable, b: jax.Array, maxiter: int, tol):
    """Plain CG on SPD `matvec`; fixed-shape while_loop, early exit on tol.
    Returns (x, iterations run)."""

    def body(state):
        x, r, pvec, rs, it = state
        with jax.named_scope("sven.hess_mv"):
            Ap = matvec(pvec)
        denom = pvec @ Ap
        alpha = rs / jnp.where(denom > 0, denom, 1.0)
        x = x + alpha * pvec
        r = r - alpha * Ap
        rs_new = r @ r
        beta = rs_new / jnp.where(rs > 0, rs, 1.0)
        pvec = r + beta * pvec
        return x, r, pvec, rs_new, it + 1

    def cond(state):
        _, _, _, rs, it = state
        return (rs > tol * tol) & (it < maxiter)

    x0 = jnp.zeros_like(b)
    state = (x0, b, b, b @ b, jnp.zeros((), jnp.int32))
    with jax.named_scope("sven.cg"):
        x, _, _, _, it = jax.lax.while_loop(cond, body, state)
    return x, it


def _primal_obj(matvec: Callable, yhat: jax.Array, w: jax.Array, C) -> jax.Array:
    """f(w) = 1/2 ||w||^2 + C sum_i max(0, 1 - yhat_i (Xhat w)_i)^2."""
    o = matvec(w)
    act = (yhat * o) < 1.0
    xi = jnp.where(act, 1.0 - yhat * o, 0.0)
    return 0.5 * (w @ w) + C * (xi @ xi)


def primal_newton_machine(
    matvec: Callable[[jax.Array], jax.Array],     # w (d,) -> Xhat @ w (m,)
    rmatvec: Callable[[jax.Array], jax.Array],    # v (m,) -> Xhat^T v (d,)
    yhat: jax.Array,                              # (m,) labels in {+1,-1}
    d: int,
    *,
    max_newton: int = 50,
    cg_iters: int = 250,
    hess_matvec: Callable | None = None,          # (v, act, C) -> H v override (Pallas)
    weighted_gram: Callable | None = None,        # c (m,) -> Xhat^T diag(c) Xhat (d, d)
) -> SolverMachine:
    """Newton-CG as a SolverState machine; `hyper.C`/`hyper.tol` are traced.

    With `weighted_gram`, CG runs on the explicit H formed once per Newton
    step (and `hess_matvec` is not used); otherwise on `hess_matvec` or the
    matrix-free product."""
    dtype = yhat.dtype

    def f_value(w, C):
        return _primal_obj(matvec, yhat, w, C)

    def init(hyper: Hyper, x0: jax.Array | None = None) -> SolverState:
        del hyper
        w0 = jnp.zeros((d,), dtype) if x0 is None else x0.astype(dtype)
        return initial_state(w0, aux=jnp.zeros((), jnp.int32))

    @jax.named_scope("sven.newton")
    def step(state: SolverState, hyper: Hyper) -> SolverState:
        w, C = state.x, hyper.C
        o = matvec(w)
        act = ((yhat * o) < 1.0).astype(dtype)
        grad = w + 2.0 * C * rmatvec(act * (o - yhat))

        if weighted_gram is not None:
            with jax.named_scope("sven.hessian"):
                H = jnp.eye(d, dtype=dtype) + 2.0 * C * weighted_gram(act)

            def hess_mv(v):
                return H @ v
        elif hess_matvec is None:
            def hess_mv(v):
                return v + 2.0 * C * rmatvec(act * matvec(v))
        else:
            def hess_mv(v):
                return hess_matvec(v, act, C)

        dstep, cg_it = _cg(hess_mv, grad, cg_iters, hyper.tol * 1e-2)

        # Backtracking (Armijo) line search on f along -dstep, LINEARIZED:
        # matvec is linear, so Xhat (w - s d) = o - s (Xhat d) — one extra
        # matvec (od) per Newton step and every f evaluation becomes pure
        # replicated vector math. This hoists the per-evaluation matvec out
        # of the search loop: in the row-sharded primal machine each matvec
        # is a psum, so the old form paid one collective per backtracking
        # halving (plus one for f0) that the replicated operands make
        # redundant; on one device it saves the O(np) GEMV per halving.
        od = matvec(dstep)
        ww_ = w @ w
        wd = w @ dstep
        dd = dstep @ dstep

        def f_line(s):
            m = yhat * (o - s * od)
            xi = jnp.where(m < 1.0, 1.0 - m, 0.0)
            return (0.5 * (ww_ - 2.0 * s * wd + s * s * dd) + C * (xi @ xi))

        f0 = f_line(jnp.asarray(0.0, dtype))
        gd = grad @ dstep

        def ls_body(ls):
            s, _ = ls
            return s * 0.5, f_line(s * 0.5)

        def ls_cond(ls):
            s, fv = ls
            return (fv > f0 - 1e-4 * s * gd) & (s > 1e-10)

        s, _ = jax.lax.while_loop(
            ls_cond, ls_body, (jnp.asarray(1.0, dtype),
                               f_line(jnp.asarray(1.0, dtype))))
        gnorm = jnp.max(jnp.abs(grad))
        stalled = (s < 1.0) & (gd <= STALL_RTOL
                               * jnp.maximum(jnp.abs(f0), 1.0))
        # ~(> tol) rather than (<= tol): a NaN residual counts as terminal,
        # so a diverged solve exits instead of spinning to max_iters.
        return SolverState(x=w - s * dstep, aux=state.aux + cg_it,
                           iters=state.iters + 1, residual=gnorm,
                           converged=~(gnorm > hyper.tol) | stalled)

    def run(hyper: Hyper, x0: jax.Array | None = None) -> SolverState:
        return run_machine(step, init(hyper, x0), hyper, max_newton)

    return SolverMachine(init=init, step=step, run=run)


def solve_primal_newton(
    matvec: Callable[[jax.Array], jax.Array],
    rmatvec: Callable[[jax.Array], jax.Array],
    yhat: jax.Array,
    C,
    d: int,
    *,
    tol=1e-8,
    max_newton: int = 50,
    cg_iters: int = 250,
    w0: jax.Array | None = None,
    hess_matvec: Callable | None = None,
    weighted_gram: Callable | None = None,
) -> PrimalResult:
    """Classic-signature wrapper over the machine (C/tol may be traced)."""
    dtype = yhat.dtype
    machine = primal_newton_machine(matvec, rmatvec, yhat, d,
                                    max_newton=max_newton, cg_iters=cg_iters,
                                    hess_matvec=hess_matvec,
                                    weighted_gram=weighted_gram)
    hyper = make_hyper(C, tol, dtype)
    st = machine.run(hyper, w0)
    return PrimalResult(w=st.x, iters=st.iters, grad_norm=st.residual,
                        objective=_primal_obj(matvec, yhat, st.x, hyper.C),
                        cg_steps=st.aux,
                        # the stop flag raised with the gradient above tol
                        stalled=st.converged & (st.residual > hyper.tol))
