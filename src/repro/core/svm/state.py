"""The SolverState protocol — fixed-shape init/step/run state machines.

Every SVM solver in this package (primal Newton-CG, projected dual Newton,
projected dual FISTA) is expressed as the same three pure functions
(DESIGN.md §6):

    init(hyper, x0=None) -> SolverState     fixed-shape starting carry
    step(state, hyper)   -> SolverState     one outer iteration
    run(hyper, x0=None)  -> SolverState     while_loop(step) to convergence

with one common carry:

    SolverState(x, aux, iters, residual, converged)

`x` is the solver's iterate (primal w or dual alpha), `aux` holds any
solver-private fixed-shape extras (FISTA momentum; the Newton solvers'
running count of CG iterations), `residual` is the
solver's own optimality measure and `converged` its stop flag (the
tolerance reached, or the primal Newton's stall stop). Because
the carry is a fixed-shape pytree and the hyperparameters (`Hyper.C`,
`Hyper.tol`) enter as *traced scalars* — never Python floats baked into the
trace — a machine composes directly with `jax.jit`, `jax.lax.scan`
(regularization paths re-use one trace for the whole t-grid) and `jax.vmap`
(`core/batch.py` stacks whole problems). Loop bounds (`max_iters`,
`cg_iters`) stay static: they size the computation, not the trace inputs.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class Hyper(NamedTuple):
    """Traced solver hyperparameters (regular jnp scalars under jit/scan/vmap)."""

    C: jax.Array     # SVM cost 1/(2*lambda2), clamped (reduction.svm_C)
    tol: jax.Array   # outer-loop optimality tolerance


class SolverState(NamedTuple):
    """Common fixed-shape carry shared by all SVM solver machines."""

    x: jax.Array          # iterate: primal w (n,) or dual alpha (2p,)
    aux: Any              # solver-private extras (fixed-shape pytree)
    iters: jax.Array      # int32 outer-iteration count
    residual: jax.Array   # solver's optimality measure (sup-norm)
    converged: jax.Array  # bool: residual <= tol reached, or a stall stop

    def telemetry(self) -> dict:
        """Host-side scalar summary of where the solve ended (DESIGN.md
        §12.5): plain Python numbers for solve logs and event records.
        Call OUTSIDE jit only — it materializes device scalars."""
        return {"iters": int(self.iters),
                "residual": float(self.residual),
                "converged": bool(self.converged)}


class SolverMachine(NamedTuple):
    """An init/step/run triple closed over the problem operators."""

    init: Callable[..., SolverState]
    step: Callable[[SolverState, Hyper], SolverState]
    run: Callable[..., SolverState]


def make_hyper(C, tol, dtype) -> Hyper:
    """Coerce (possibly Python-float) hyperparameters to traced scalars."""
    return Hyper(C=jnp.asarray(C, dtype), tol=jnp.asarray(tol, dtype))


def initial_state(x0: jax.Array, aux: Any = ()) -> SolverState:
    return SolverState(
        x=x0,
        aux=aux,
        iters=jnp.zeros((), jnp.int32),
        residual=jnp.asarray(jnp.inf, x0.dtype),
        converged=jnp.zeros((), bool),
    )


def run_machine(step: Callable[[SolverState, Hyper], SolverState],
                state: SolverState, hyper: Hyper, max_iters: int) -> SolverState:
    """Drive `step` to convergence with a fixed-shape while_loop."""

    def cond(s: SolverState):
        return (~s.converged) & (s.iters < max_iters)

    return jax.lax.while_loop(cond, lambda s: step(s, hyper), state)
