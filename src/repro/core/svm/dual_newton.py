"""Squared-hinge SVM dual: projected Newton with active sets.

    min_{alpha >= 0} D(alpha) = alpha^T K alpha + 1/(2C) ||alpha||^2
                                - 2 sum(alpha)                     (paper eq. 3)

with K = Zhat^T Zhat. grad = 2 K alpha + alpha/C - 2; the Hessian
H = 2K + I/C is constant and PD, so a projected Newton method with a
free/clamped split converges in finitely many outer iterations:

    F   = {i : alpha_i > 0  or  grad_i < 0}        (free set)
    solve (H d)_F = grad_F, d_{F^c} = 0 via masked CG
    alpha <- max(0, alpha - s d), backtracking on D

The kernel mat-vec is supplied as a callable: either `lambda v: K @ v` with a
cached kernel matrix (the paper's d >> m regime — "remaining running time
independent of the dimensionality") or the matrix-free O(np) SvenOperator
product. All compute is matmul/matvec-shaped for MXU/BLAS execution.

Expressed as a `SolverState` init/step/run machine (state.py, DESIGN.md §6)
with traced (C, tol) so one trace serves scan-compiled paths and vmapped
problem batches. The machine's `aux` is the int32 count of masked-CG
iterations spent so far (`DualResult.cg_steps`).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.svm.state import (Hyper, SolverMachine, SolverState,
                                  initial_state, make_hyper, run_machine)


class DualResult(NamedTuple):
    alpha: jax.Array
    iters: jax.Array
    pg_norm: jax.Array      # projected-gradient sup-norm
    objective: jax.Array
    cg_steps: jax.Array     # CG iterations over the Newton steps (0: FISTA)


def _masked_cg(matvec: Callable, b: jax.Array, mask: jax.Array, maxiter: int, tol):
    """CG restricted to coordinates where mask==1 (others pinned to 0).
    Returns (x, iterations run)."""

    def mv(v):
        with jax.named_scope("sven.hess_mv"):
            return mask * matvec(mask * v)

    b = mask * b

    def body(state):
        x, r, pvec, rs, it = state
        Ap = mv(pvec)
        denom = pvec @ Ap
        alpha = rs / jnp.where(denom > 0, denom, 1.0)
        x = x + alpha * pvec
        r = r - alpha * Ap
        rs_new = r @ r
        beta = rs_new / jnp.where(rs > 0, rs, 1.0)
        return x, r, r + beta * pvec, rs_new, it + 1

    def cond(state):
        _, _, _, rs, it = state
        return (rs > tol * tol) & (it < maxiter)

    x0 = jnp.zeros_like(b)
    with jax.named_scope("sven.cg"):
        x, _, _, _, it = jax.lax.while_loop(
            cond, body, (x0, b, b, b @ b, jnp.zeros((), jnp.int32)))
    return x, it


def _dual_obj(kernel_matvec, alpha, C):
    two = jnp.asarray(2.0, alpha.dtype)
    return (alpha @ kernel_matvec(alpha)
            + (alpha @ alpha) / (two * C) - two * jnp.sum(alpha))


def dual_newton_machine(
    kernel_matvec: Callable[[jax.Array], jax.Array],   # v (m,) -> K v (m,)
    m: int,
    *,
    dtype=jnp.float64,
    max_newton: int = 100,
    cg_iters: int = 250,
) -> SolverMachine:
    """Projected Newton as a SolverState machine; `hyper.C`/`hyper.tol` traced."""
    two = jnp.asarray(2.0, dtype)

    def grad_fn(alpha, C):
        return two * kernel_matvec(alpha) + alpha / C - two

    def init(hyper: Hyper, x0: jax.Array | None = None) -> SolverState:
        del hyper
        a0 = jnp.zeros((m,), dtype) if x0 is None else x0.astype(dtype)
        return initial_state(a0, aux=jnp.zeros((), jnp.int32))

    @jax.named_scope("sven.newton")
    def step(state: SolverState, hyper: Hyper) -> SolverState:
        alpha, C = state.x, hyper.C
        g = grad_fn(alpha, C)
        free = ((alpha > 0) | (g < 0)).astype(dtype)

        def hess_mv(v):
            return two * kernel_matvec(v) + v / C

        d, cg_it = _masked_cg(hess_mv, g, free, cg_iters, hyper.tol * 1e-2)

        f0 = _dual_obj(kernel_matvec, alpha, C)

        def proj(s):
            return jnp.maximum(alpha - s * d, 0.0)

        def ls_cond(ls):
            s, fv = ls
            return (fv > f0 - 1e-12 * jnp.abs(f0)) & (s > 1e-12)

        def ls_body(ls):
            s, _ = ls
            s = s * 0.5
            return s, _dual_obj(kernel_matvec, proj(s), C)

        s, _ = jax.lax.while_loop(
            ls_cond, ls_body,
            (jnp.asarray(1.0, dtype), _dual_obj(kernel_matvec, proj(1.0), C)))
        alpha_new = proj(s)
        # projected gradient: optimality measure for the bound-constrained QP
        g_new = grad_fn(alpha_new, C)
        pg = jnp.max(jnp.abs(jnp.where(alpha_new > 0, g_new, jnp.minimum(g_new, 0.0))))
        # ~(> tol): NaN residual is terminal (diverged), not "keep iterating"
        return SolverState(x=alpha_new, aux=state.aux + cg_it,
                           iters=state.iters + 1,
                           residual=pg, converged=~(pg > hyper.tol))

    def run(hyper: Hyper, x0: jax.Array | None = None) -> SolverState:
        return run_machine(step, init(hyper, x0), hyper, max_newton)

    return SolverMachine(init=init, step=step, run=run)


def solve_dual_newton(
    kernel_matvec: Callable[[jax.Array], jax.Array],
    m: int,
    C,
    *,
    dtype=jnp.float64,
    tol=1e-8,
    max_newton: int = 100,
    cg_iters: int = 250,
    alpha0: jax.Array | None = None,
) -> DualResult:
    """Classic-signature wrapper over the machine (C/tol may be traced)."""
    machine = dual_newton_machine(kernel_matvec, m, dtype=dtype,
                                  max_newton=max_newton, cg_iters=cg_iters)
    hyper = make_hyper(C, tol, dtype)
    st = machine.run(hyper, alpha0)
    return DualResult(alpha=st.x, iters=st.iters, pg_norm=st.residual,
                      objective=_dual_obj(kernel_matvec, st.x, hyper.C),
                      cg_steps=st.aux)
