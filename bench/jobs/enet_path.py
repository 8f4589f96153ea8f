"""Job kind `enet_path`: one regularization path through `repro.enet_path`.

A job solves one problem of the run, on that problem's glmnet grid cut to
the traffic's `n_lambdas` points, with the entry point's defaults
(`PathConfig()`, backend chosen by the program). All problems of a run
have one shape, so every job runs the same executable. A job's answers are
the path's coefficients, one (p,) vector per lambda point, and the
program's own counters for each point (root-find evaluations, inner solver
iterations, columns kept by screening).

The comparison that decides `correct`: every point of every compared job
against `bench.reference` on the same data and grid, as the widest gap
max |beta - beta_ref| / max |beta_ref| over the path (`beta_gap`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def glmnet_grid(X, y, config: dict, n_lambdas: int) -> np.ndarray:
    """The configuration's grid on (X, y), from lambda1_max down."""
    lmax = 2.0 * jnp.max(jnp.abs(
        jnp.dot(X.T, y, precision=jax.lax.Precision.HIGHEST)))
    return reference.glmnet_grid(float(lmax), n_lambdas,
                                 float(config["lambda_grid"]["min_ratio"]))


class Job:
    """Built once per run; `warm`, then `dispatch`/`block`/`fetch` per job.

    `problems` is a list of (X, y) on the device; `dispatch(i)` runs a job
    on problem i.
    """

    def __init__(self, config: dict, traffic: dict, problems: list,
                 grids=None):
        """`grids` overrides the grids worked out from the problems: the
        control runs the program on lower-precision copies of the data, on
        the grids of the data as generated."""
        from repro.core.api import enet_path

        self._enet_path = enet_path
        self.problems = problems
        self.lambda2 = float(config["lambda2"])
        self.n_lambdas = int(traffic["n_lambdas"])
        if grids is None:
            grids = [glmnet_grid(X, y, config, self.n_lambdas)
                     for X, y in problems]
        self.grids = [np.asarray(g, np.float64) for g in grids]
        self._grids_dev = [jnp.asarray(g, X.dtype)
                           for g, (X, _) in zip(self.grids, problems)]

    @property
    def points_per_job(self) -> int:
        return self.n_lambdas

    def _call(self, i, lambda1s):
        X, y = self.problems[i]
        return self._enet_path(X, y, lambda1s=lambda1s, lambda2=self.lambda2)

    def warm(self) -> None:
        """Compile every program a job runs, at a cost of almost nothing: a
        grid of n_lambdas copies of lambda_max has the timed grid's shape
        and dtype (one executable), and beta = 0 at every point."""
        flat = jnp.full((self.n_lambdas,), self.grids[0][0],
                        self._grids_dev[0].dtype)
        self.fetch(self.block(self._call(0, flat)))

    def dispatch(self, i: int):
        return self._call(i, self._grids_dev[i])

    @staticmethod
    def block(handle):
        return jax.block_until_ready(handle)

    @staticmethod
    def fetch(handle) -> dict:
        return {"betas": np.asarray(handle.betas),
                "evals": np.asarray(handle.evals),
                "sven_iters": np.asarray(handle.sven_iters),
                "n_kept": np.asarray(handle.n_kept)}

    def reference(self, i: int, X_host, y_host) -> np.ndarray:
        """The reference path of problem i, from host copies of its data
        as generated."""
        return reference.enet_path_reference(X_host, y_host, self.grids[i],
                                             self.lambda2)

    @staticmethod
    def compare(answers: list, refs: dict) -> dict:
        """{name: one reading per answered point, jobs concatenated};
        `answers` holds (problem, answer) pairs, `refs` the reference of
        each problem."""
        return {"beta_gap": np.concatenate(
            [reference.point_gaps(a["betas"], refs[i]) for i, a in answers])}
