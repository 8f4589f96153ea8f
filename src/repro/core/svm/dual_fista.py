"""Projected FISTA on the SVM dual — a robust first-order fallback.

Same bound-constrained QP as dual_newton; accelerated projected gradient with
step 1/L, L = lambda_max(2K + I/C) estimated by power iteration. Linear
convergence via strong convexity 1/C. Used (a) as an independent check of the
Newton solvers in tests, (b) as the solver of last resort for ill-conditioned
problems.

Expressed as a `SolverState` init/step/run machine (state.py, DESIGN.md §6):
the momentum pair (z, tk) and the 1/L step size live in `state.aux`, computed
once at init from the traced C.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.svm.dual_newton import DualResult, _dual_obj
from repro.core.svm.state import (Hyper, SolverMachine, SolverState,
                                  initial_state, make_hyper, run_machine)


def _power_iter_L(hess_mv: Callable, m: int, dtype, iters: int = 30) -> jax.Array:
    v = jnp.ones((m,), dtype) / jnp.sqrt(m)

    def body(_, v):
        w = hess_mv(v)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    return v @ hess_mv(v)


def dual_fista_machine(
    kernel_matvec: Callable[[jax.Array], jax.Array],
    m: int,
    *,
    dtype=jnp.float64,
    max_iters: int = 5000,
) -> SolverMachine:
    """Projected FISTA as a SolverState machine; aux = (z, tk, step)."""
    two = jnp.asarray(2.0, dtype)

    def grad_fn(a, C):
        return two * kernel_matvec(a) + a / C - two

    def init(hyper: Hyper, x0: jax.Array | None = None) -> SolverState:
        a0 = jnp.zeros((m,), dtype) if x0 is None else x0.astype(dtype)

        def hess_mv(v):
            return two * kernel_matvec(v) + v / hyper.C

        L = _power_iter_L(hess_mv, m, dtype) * 1.02
        aux = (a0, jnp.asarray(1.0, dtype), 1.0 / L)   # (z, tk, step)
        return initial_state(a0, aux=aux)

    def step(state: SolverState, hyper: Hyper) -> SolverState:
        a = state.x
        z, tk, stepsz = state.aux
        g = grad_fn(z, hyper.C)
        a_new = jnp.maximum(z - stepsz * g, 0.0)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        z_new = a_new + ((tk - 1.0) / t_new) * (a_new - a)
        g_new = grad_fn(a_new, hyper.C)
        pg = jnp.max(jnp.abs(jnp.where(a_new > 0, g_new, jnp.minimum(g_new, 0.0))))
        # ~(> tol): NaN residual is terminal (diverged), not "keep iterating"
        return SolverState(x=a_new, aux=(z_new, t_new, stepsz),
                           iters=state.iters + 1, residual=pg,
                           converged=~(pg > hyper.tol))

    def run(hyper: Hyper, x0: jax.Array | None = None) -> SolverState:
        return run_machine(step, init(hyper, x0), hyper, max_iters)

    return SolverMachine(init=init, step=step, run=run)


def solve_dual_fista(
    kernel_matvec: Callable[[jax.Array], jax.Array],
    m: int,
    C,
    *,
    dtype=jnp.float64,
    tol=1e-7,
    max_iters: int = 5000,
    alpha0: jax.Array | None = None,
) -> DualResult:
    """Classic-signature wrapper over the machine (C/tol may be traced)."""
    machine = dual_fista_machine(kernel_matvec, m, dtype=dtype, max_iters=max_iters)
    hyper = make_hyper(C, tol, dtype)
    st = machine.run(hyper, alpha0)
    return DualResult(alpha=st.x, iters=st.iters, pg_norm=st.residual,
                      objective=_dual_obj(kernel_matvec, st.x, hyper.C),
                      cg_steps=jnp.zeros((), jnp.int32))   # no CG in FISTA
