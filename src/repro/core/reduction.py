"""The paper's reduction: Elastic Net -> squared-hinge SVM (Algorithm 1).

Given (X in R^{n x p}, y in R^n, t > 0, lambda2 > 0) construct a binary
classification problem with m = 2p samples in d = n dimensions:

    Xhat_1 = X - (1/t) y 1^T    (columns are the +1 class)
    Xhat_2 = X + (1/t) y 1^T    (columns are the -1 class)
    Xhat   = [Xhat_1, Xhat_2]   as columns; SVM sample i is the i-th column
    yhat   = [+1_p ; -1_p],  C  = 1 / (2 lambda2)

If alpha* solves the SVM dual (3), the Elastic Net solution is

    beta* = t * (alpha*[:p] - alpha*[p:]) / |alpha*|_1.

NOTE on the paper's MATLAB listing: line 3 uses "[A; B]'" (vertical concat)
which would produce a (p x 2n) matrix — inconsistent with the math (m = 2p
samples of dimension n). We follow the math: Xnew = [Xhat_1, Xhat_2]^T of
shape (2p, n), samples as rows.

This module provides BOTH an explicit construction (reference, used by tests
and the paper-faithful baseline) and matrix-free operators that never
materialize the (2p, n) matrix — the TPU-native path (see DESIGN.md §2): all
solver mat-vecs reduce to ops on the original (n, p) X plus rank-1 terms,
halving FLOPs and removing a full HBM materialization.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Explicit construction (paper-faithful)
# --------------------------------------------------------------------------

def build_svm_dataset(X: jax.Array, y: jax.Array, t: float) -> Tuple[jax.Array, jax.Array]:
    """Return (Xhat, yhat): Xhat (2p, n) rows = SVM samples, yhat (2p,) labels."""
    shift = (y / t)[None, :]          # (1, n) broadcast over the p columns
    Xt = X.T                          # (p, n): row j = original feature j
    Xhat = jnp.concatenate([Xt - shift, Xt + shift], axis=0)  # (2p, n)
    p = X.shape[1]
    yhat = jnp.concatenate([jnp.ones((p,), X.dtype), -jnp.ones((p,), X.dtype)])
    return Xhat, yhat


#: Default Lasso-limit floor on lambda2 (C capped at 1/(2*floor)). The single
#: source of truth for the clamp — SvenConfig.lambda2_floor defaults to it.
LAMBDA2_FLOOR = 1e-12


def svm_C(lambda2, floor: float = LAMBDA2_FLOOR) -> jax.Array:
    """C = 1/(2 lambda2); capped for the Lasso limit lambda2 -> 0.

    Accepts Python floats and traced scalars alike — the one clamping rule
    used by both the explicit reduction and the sven() driver.
    """
    lam2 = jnp.maximum(jnp.asarray(lambda2, jnp.result_type(float, lambda2)), floor)
    return 1.0 / (2.0 * lam2)


def recover_beta(alpha: jax.Array, t: float) -> jax.Array:
    """beta = t (alpha_top - alpha_bot) / sum(alpha); Algorithm 1 line 11."""
    p = alpha.shape[0] // 2
    s = jnp.sum(alpha)
    # Degenerate |alpha|_1 = 0 (no support vectors) is meaningless per the
    # paper's footnote 1; guard to avoid NaN and return beta = 0.
    safe = jnp.where(s > 0, s, 1.0)
    return jnp.where(s > 0, t * (alpha[:p] - alpha[p:]) / safe, jnp.zeros((p,), alpha.dtype))


def alpha_from_primal(Xhat: jax.Array, yhat: jax.Array, w: jax.Array, C: float) -> jax.Array:
    """Dual from primal solution: alpha_i = C max(0, 1 - yhat_i x_i^T w)."""
    return C * jnp.maximum(1.0 - yhat * (Xhat @ w), 0.0)


# --------------------------------------------------------------------------
# Matrix-free operators (TPU-native; beyond-paper optimization)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SvenOperator:
    """Matrix-free Xhat / Zhat operators built from the original (X, y, t).

    With a = X^T w (p,), b = y^T w / t (scalar):
        Xhat @ w          = [a - b ; a + b]
        Xhat^T @ v        = X (v_top + v_bot) + (y/t) (sum(v_bot) - sum(v_top))
        Zhat @ v          = X (v_top - v_bot) - (y/t) sum(v)          (n,)
        Zhat^T @ u        = [X^T u - (y^T u/t) 1 ; -X^T u - (y^T u/t) 1]
    where Zhat = [Xhat_1, -Xhat_2] (n x 2p) is the label-scaled data of the
    dual (3). Every product is O(np) on the original X — the (2p, n) matrix
    never exists.
    """

    X: jax.Array   # (n, p)
    y: jax.Array   # (n,)
    t: float

    @property
    def n(self) -> int:
        return self.X.shape[0]

    @property
    def p(self) -> int:
        return self.X.shape[1]

    @property
    def m(self) -> int:
        return 2 * self.X.shape[1]

    def xhat_matvec(self, w: jax.Array) -> jax.Array:
        a = self.X.T @ w
        b = (self.y @ w) / self.t
        return jnp.concatenate([a - b, a + b])

    def xhat_rmatvec(self, v: jax.Array) -> jax.Array:
        p = self.p
        vt, vb = v[:p], v[p:]
        return self.X @ (vt + vb) + (self.y / self.t) * (jnp.sum(vb) - jnp.sum(vt))

    def zhat_matvec(self, v: jax.Array) -> jax.Array:
        p = self.p
        vt, vb = v[:p], v[p:]
        return self.X @ (vt - vb) - (self.y / self.t) * jnp.sum(v)

    def zhat_rmatvec(self, u: jax.Array) -> jax.Array:
        a = self.X.T @ u
        b = (self.y @ u) / self.t
        return jnp.concatenate([a - b, -a - b])

    def kernel_matvec(self, v: jax.Array) -> jax.Array:
        """K v with K = Zhat^T Zhat (2p x 2p), in O(np)."""
        return self.zhat_rmatvec(self.zhat_matvec(v))

    def xhat_weighted_gram(self, c: jax.Array) -> jax.Array:
        """Xhat^T diag(c) Xhat (n x n) for sample weights c = [c_t ; c_b]:

            X diag(c_t + c_b) X^T - (u y^T + y u^T)/t + (sum(c)/t^2) y y^T,
            u = X (c_t - c_b)

        One n x p x n GEMM plus rank-2 terms; the (2p, n) matrix never
        exists. With c the hinge's active set this is the primal Newton
        Hessian's data term, formed once per Newton step at small n.
        """
        p = self.p
        ct, cb = c[:p], c[p:]
        u = self.X @ (ct - cb)
        yt = self.y / self.t
        G = (self.X * (ct + cb)[None, :]) @ self.X.T
        return (G - (u[:, None] * yt[None, :] + yt[:, None] * u[None, :])
                + jnp.sum(c) * (yt[:, None] * yt[None, :]))

    def margins(self, w: jax.Array) -> jax.Array:
        """yhat * (Xhat @ w) as used by the squared hinge."""
        p = self.p
        o = self.xhat_matvec(w)
        return jnp.concatenate([o[:p], -o[p:]])


def gram_from_stats(G: jax.Array, u: jax.Array, s) -> jax.Array:
    """K = Zhat^T Zhat (2p x 2p) from the sufficient statistics
    G = X^T X (p, p), u = X^T y / t (p,), s = y^T y / t^2 (scalar):

        K = [[ G - u1' - 1u' + s ,  -G - u1' + 1u' + s ],
             [ -G + u1' - 1u' + s,   G + u1' + 1u' + s ]]

    Split out from `gram_blocks` because the statistics are one-shot
    maintainable under streaming rows: a new (x, y) sample is a rank-1
    update G += x x^T, X^T y += y x, y^T y += y^2 — the serving runtime's
    online layer (`repro.runtime.online`) rebuilds K from the updated stats
    in O(p^2), never re-touching the n accumulated rows.
    """
    u1 = u[:, None]
    u2 = u[None, :]
    top = jnp.concatenate([G - u1 - u2 + s, -G - u1 + u2 + s], axis=1)
    bot = jnp.concatenate([-G + u1 - u2 + s, G + u1 + u2 + s], axis=1)
    return jnp.concatenate([top, bot], axis=0)


@jax.named_scope("sven.gram")
def gram_blocks(X: jax.Array, y: jax.Array, t: float) -> jax.Array:
    """Assemble K = Zhat^T Zhat (2p x 2p) from p x p blocks.

    Beyond-paper optimization: built via `gram_from_stats`, costing one
    p x p Gram (np^2 MACs) instead of the naive (2p)^2 n — a 4x FLOP
    reduction over materializing Zhat (what the MATLAB/GPU code pays).
    """
    return gram_from_stats(X.T @ X, (X.T @ y) / t, (y @ y) / (t * t))


@jax.named_scope("sven.gram")
def gram_reference(X: jax.Array, y: jax.Array, t: float) -> jax.Array:
    """Paper-faithful K: materialize Zhat then Zhat^T Zhat."""
    Xhat, yhat = build_svm_dataset(X, y, t)
    Zhat = (yhat[:, None] * Xhat).T   # (n, 2p)
    return Zhat.T @ Zhat
