"""Plain reference for the penalized Elastic Net, in NumPy float64 on the host.

    min_beta ||y - X beta||^2 + lambda2 ||beta||^2 + lambda1 |beta|_1

(the paper's scaling: no 1/2, no 1/n). It shares no code with the program.

Each point is solved by feature-sign search (Lee, Battle, Raina and Ng,
"Efficient sparse coding algorithms", NIPS 2006), warm-started down the
grid. With the support A and its signs s fixed, the problem is a quadratic
whose minimum solves

    (X_A^T X_A + lambda2 I) beta_A = X_A^T y - (lambda1 / 2) s;

the search moves towards that minimum, stopping where a coefficient would
change sign if the objective is lower there, and adds the zero coefficient
that breaks the optimality conditions most. Every step lowers the
objective, so it ends, and it ends only at a point that meets the KKT
conditions over all p columns, to float64 rounding:

    z = 2 X^T (y - X beta) - 2 lambda2 beta,
    z_j = lambda1 sign(beta_j) where beta_j != 0,  |z_j| <= lambda1 elsewhere.
"""
from __future__ import annotations

import numpy as np

#: relative slack of the optimality tests, against float64 rounding
RTOL = 1e-10
#: widest problem whose whole Gram X^T X is formed once; wider problems
#: form the Gram of the support at each step
GRAM_MAX_P = 4096


class NotCertified(RuntimeError):
    """The reference could not certify a point."""


def glmnet_grid(lambda_max: float, n_lambdas: int, min_ratio: float):
    """glmnet's grid: geometric from lambda_max to min_ratio * lambda_max."""
    return lambda_max * np.geomspace(1.0, min_ratio, n_lambdas)


class _Problem:
    def __init__(self, X, y, lambda2):
        self.X = np.asarray(X, np.float64)
        self.y = np.asarray(y, np.float64)
        self.lambda2 = float(lambda2)
        self.c = self.X.T @ self.y                                # X^T y
        self.G = (self.X.T @ self.X
                  if self.X.shape[1] <= GRAM_MAX_P else None)       # X^T X

    def gram(self, A):
        if self.G is not None:
            return self.G[np.ix_(A, A)]
        XA = self.X[:, A]
        return XA.T @ XA

    def score(self, beta):
        """z = 2 X^T (y - X beta) - 2 lambda2 beta (minus the gradient of
        the smooth part)."""
        A = np.flatnonzero(beta)
        if self.G is not None:
            xtxb = self.G[:, A] @ beta[A]
        else:
            xtxb = self.X.T @ (self.X[:, A] @ beta[A])
        return 2.0 * (self.c - xtxb) - 2.0 * self.lambda2 * beta


def _objective(M, cA, lam, b):
    """The objective restricted to the support, less ||y||^2:
    b^T M b - 2 c_A^T b + lambda1 |b|_1, with M = X_A^T X_A + lambda2 I."""
    return float(b @ (M @ b) - 2.0 * cA @ b + lam * np.sum(np.abs(b)))


def _feature_sign_step(prob, beta, theta, lam):
    """Move beta to the lowest objective on the segment towards the minimum
    with support and signs theta. Returns (beta, whether it reached that
    minimum with those signs)."""
    A = np.flatnonzero(theta)
    M = prob.gram(A) + prob.lambda2 * np.eye(len(A))
    cA = prob.c[A]
    cur = beta[A]
    new = np.linalg.solve(M, cA - 0.5 * lam * theta[A])
    candidates = [new]
    cross = np.flatnonzero((cur != 0) & (np.sign(new) != np.sign(cur)))
    for k in cross:
        t = cur[k] / (cur[k] - new[k])
        b = cur + t * (new - cur)
        b[k] = 0.0
        candidates.append(b)
    objs = [_objective(M, cA, lam, b) for b in candidates]
    best = int(np.argmin(objs))
    out = np.zeros_like(beta)
    out[A] = candidates[best]
    return out, best == 0 and bool(np.all(np.sign(new) == theta[A]))


def _point(prob, lam, beta, max_steps):
    """Feature-sign search from beta; the point's certified solution."""
    lam = float(lam)
    if lam >= np.max(np.abs(2.0 * prob.c)):
        return np.zeros_like(beta)
    slack = RTOL * lam
    theta = np.sign(beta)
    exact = not beta.any()
    for _ in range(max_steps):
        if exact:
            z = prob.score(beta)
            viol = np.where(beta != 0, 0.0, np.abs(z))
            i = int(np.argmax(viol))
            if viol[i] <= lam + slack:
                nz = beta != 0
                if np.all(np.abs(z[nz] - lam * theta[nz]) <= 100 * slack):
                    return beta
                raise NotCertified(f"active KKT residual at lambda1="
                                     f"{lam:.6g} beyond rounding")
            theta[i] = np.sign(z[i])
        beta, exact = _feature_sign_step(prob, beta, theta, lam)
        theta = np.sign(beta)
    raise NotCertified(f"no certified solution at lambda1={lam:.6g} "
                         f"in {max_steps} steps")


def enet_path_reference(X, y, lambda1s, lambda2, *,
                        max_steps: int = 20_000) -> np.ndarray:
    """(L, p) solutions at the descending grid `lambda1s`."""
    prob = _Problem(X, y, lambda2)
    beta = np.zeros(prob.X.shape[1])
    out = []
    for lam in lambda1s:
        beta = _point(prob, lam, beta, max_steps)
        out.append(beta)
    return np.stack(out)


def point_gaps(betas, ref) -> np.ndarray:
    """(L,) gaps between a path and the reference path, point by point,
    each relative to the reference path's largest coefficient:
    max_j |beta_j - ref_j| / max |ref|. A point with a non-finite
    coefficient, and every point of a path of the wrong shape, reads inf."""
    ref = np.asarray(ref, np.float64)
    betas = np.asarray(betas, np.float64)
    if betas.shape != ref.shape:
        return np.full(len(ref), np.inf)
    scale = max(float(np.max(np.abs(ref))), np.finfo(np.float64).tiny)
    gaps = np.max(np.abs(betas - ref), axis=1) / scale
    return np.where(np.all(np.isfinite(betas), axis=1), gaps, np.inf)
