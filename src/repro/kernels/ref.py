"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gram_blocks_ref(X: jax.Array, y: jax.Array, t: float) -> jax.Array:
    """Oracle for the fused shifted-Gram kernel.

    Returns K in block layout (2, 2, p, p) with
        K[a, b, i, j] = s_a s_b G_ij - s_a u_i - s_b u_j + s
    where s_0=+1, s_1=-1, G = X^T X, u = X^T y / t, s = y^T y / t^2.
    Flattened via K.transpose(0,2,1,3).reshape(2p, 2p) it equals
    Zhat^T Zhat of the paper's dual (eq. 3).
    """
    G = X.T @ X
    u = (X.T @ y) / t
    s = (y @ y) / (t * t)
    signs = jnp.array([1.0, -1.0], X.dtype)
    sa = signs[:, None, None, None]          # (2,1,1,1)
    sb = signs[None, :, None, None]          # (1,2,1,1)
    ui = u[None, None, :, None]
    uj = u[None, None, None, :]
    return sa * sb * G[None, None] - sa * ui - sb * uj + s


def flatten_gram(Kb: jax.Array) -> jax.Array:
    """(2,2,p,p) block layout -> (2p,2p) kernel matrix."""
    p = Kb.shape[-1]
    return Kb.transpose(0, 2, 1, 3).reshape(2 * p, 2 * p)


def hinge_xtv_ref(X: jax.Array, y: jax.Array, v: jax.Array, t: float,
                  act_top: jax.Array, act_bot: jax.Array):
    """Oracle for hinge pass 1: masked dual-side reduction of Xhat @ v.

    c   = X^T v                       (p,)
    byv = (y . v) / t                 scalar
    u_t = act_top * (c - byv);  u_b = act_bot * (c + byv)
    returns d = u_t + u_b (p,), e = sum(u_b) - sum(u_t) (scalar)
    """
    c = X.T @ v
    byv = (y @ v) / t
    u_t = act_top * (c - byv)
    u_b = act_bot * (c + byv)
    return u_t + u_b, jnp.sum(u_b) - jnp.sum(u_t)


def hinge_xd_ref(X: jax.Array, y: jax.Array, d: jax.Array, e: jax.Array,
                 v: jax.Array, t: float, C: float) -> jax.Array:
    """Oracle for hinge pass 2: H v = v + 2C (X d + (y/t) e)."""
    return v + 2.0 * C * (X @ d + (y / t) * e)


def hessian_matvec_ref(X, y, t, C, act_top, act_bot, v):
    """Full squared-hinge Hessian mat-vec (primal Newton-CG inner op)."""
    d, e = hinge_xtv_ref(X, y, v, t, act_top, act_bot)
    return hinge_xd_ref(X, y, d, e, v, t, C)


def hinge_stats_ref(X: jax.Array, y: jax.Array, t: float, w: jax.Array, C: float):
    """Oracle for the fused margins/loss/gradient kernel (Newton outer step).

    On the implicit SVEN dataset (m=2p rows [x_j -+ y/t], labels [+1;-1]):
        a      = X^T w                      (p,)
        byw    = (y . w) / t                scalar
        o      = [a - byw ; a + byw]        Xhat @ w
        margin = [o_top ; -o_bot]           yhat * o
        act    = margin < 1
        xi     = act * (1 - margin)
        loss   = 0.5 w.w + C xi.xi
        galpha = act * (o - yhat)  (2p,)    (grad = w + 2C Xhat^T galpha)
    Returns (margin, act, loss, galpha).
    """
    a = (X.T @ w).astype(w.dtype)
    byw = (y @ w) / t
    p = a.shape[0]
    dtype = a.dtype
    o = jnp.concatenate([a - byw, a + byw])
    margin = jnp.concatenate([o[:p], -o[p:]])
    act = (margin < 1.0).astype(dtype)
    xi = act * (1.0 - margin)
    loss = 0.5 * (w @ w) + C * (xi @ xi)
    yhat = jnp.concatenate([jnp.ones((p,), dtype), -jnp.ones((p,), dtype)])
    galpha = act * (o - yhat)
    return margin, act, loss, galpha
