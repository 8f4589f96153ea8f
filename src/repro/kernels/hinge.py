"""Pallas TPU kernels: fused squared-hinge Hessian mat-vec (primal Newton-CG).

The primal hot loop is H v = v + 2C Xhat^T (act . (Xhat v)) on the implicit
SVEN dataset. With c = X^T v, byv = y.v/t:

    u_t = act_top . (c - byv),  u_b = act_bot . (c + byv)
    H v = v + 2C ( X (u_t + u_b) + (y/t) (sum u_b - sum u_t) )

Two GEMV-shaped passes, each with its mask/shift epilogue fused into the
mat-vec tile (no (2p,)-sized intermediates in HBM beyond d itself):

  pass 1 (hinge_xtv): grid (p/bp, n/bk) — c-accumulate + hinge mask epilogue
  pass 2 (hinge_xd):  grid (n/bn, p/bk) — X d accumulate + rank-1/v epilogue
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# block index for "the first block": a Python 0 in an index map becomes an
# i64 under jax_enable_x64, and Mosaic cannot lower an i64 block index
_0 = np.int32(0)
#: MXU precision of every product: on a TPU the default runs f32 operands
#: through one bf16 pass (about 1e-3 relative error in H v); the
#: mat-vecs are memory-bound, so the full-precision passes cost nothing
HIGHEST = jax.lax.Precision.HIGHEST
#: per-tile scalar partials are written as one lane-aligned (8, 128) tile
#: each: Mosaic rejects a (1, 1) block of a larger array
PARTIAL_TILE = (8, 128)


# ---------------------------------------------------------------- pass 1 ---

def _xtv_kernel(x_ref, v_ref, y_ref, at_ref, ab_ref, invt_ref,
                d_ref, e_ref, acc_c, acc_byv):
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _init():
        acc_c[...] = jnp.zeros_like(acc_c)
        acc_byv[...] = jnp.zeros_like(acc_byv)

    xk = x_ref[...].astype(jnp.float32)          # (bk, bp)
    vk = v_ref[...].astype(jnp.float32)          # (bk, 1)
    yk = y_ref[...].astype(jnp.float32)          # (bk, 1)

    acc_c[...] += jax.lax.dot_general(
        xk, vk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST)
    acc_byv[...] += jax.lax.dot_general(
        yk, vk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST)

    @pl.when(k == nk - 1)
    def _epilogue():
        invt = invt_ref[0, 0].astype(jnp.float32)
        byv = acc_byv[0, 0] * invt
        c = acc_c[...]                            # (bp, 1)
        at = at_ref[...].astype(jnp.float32)      # (bp, 1)
        ab = ab_ref[...].astype(jnp.float32)
        u_t = at * (c - byv)
        u_b = ab * (c + byv)
        d_ref[...] = (u_t + u_b).astype(d_ref.dtype)
        e_ref[...] = jnp.full(e_ref.shape, jnp.sum(u_b - u_t), e_ref.dtype)


def hinge_xtv_raw(X, v2d, y2d, at2d, ab2d, invt, *, bp: int, bk: int,
                  interpret: bool = False):
    """Returns (d (p, 1), e partials (p // bp, 8, 128)); every element of
    partial tile i holds tile i's share of e."""
    n, p = X.shape
    assert n % bk == 0 and p % bp == 0
    grid = (p // bp, n // bk)
    return pl.pallas_call(
        _xtv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bp), lambda i, k: (k, i)),
            pl.BlockSpec((bk, 1), lambda i, k: (k, _0)),
            pl.BlockSpec((bk, 1), lambda i, k: (k, _0)),
            pl.BlockSpec((bp, 1), lambda i, k: (i, _0)),
            pl.BlockSpec((bp, 1), lambda i, k: (i, _0)),
            pl.BlockSpec((1, 1), lambda i, k: (_0, _0)),
        ],
        out_specs=[
            pl.BlockSpec((bp, 1), lambda i, k: (i, _0)),
            pl.BlockSpec((1, *PARTIAL_TILE), lambda i, k: (i, _0, _0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, 1), jnp.float32),
            jax.ShapeDtypeStruct((p // bp, *PARTIAL_TILE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bp, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(X, v2d, y2d, at2d, ab2d, invt)


# ---------------------------------------------------------------- pass 2 ---

def _xd_kernel(x_ref, d_ref, y_ref, v_ref, scal_ref, hv_ref, acc):
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xk = x_ref[...].astype(jnp.float32)          # (bn, bk)
    dk = d_ref[...].astype(jnp.float32)          # (bk, 1)
    acc[...] += jax.lax.dot_general(
        xk, dk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST)

    @pl.when(k == nk - 1)
    def _epilogue():
        invt = scal_ref[0, 0].astype(jnp.float32)
        e = scal_ref[1, 0].astype(jnp.float32)
        twoC = scal_ref[2, 0].astype(jnp.float32)
        yv = y_ref[...].astype(jnp.float32)       # (bn, 1)
        vv = v_ref[...].astype(jnp.float32)       # (bn, 1)
        hv = vv + twoC * (acc[...] + yv * invt * e)
        hv_ref[...] = hv.astype(hv_ref.dtype)


def hinge_xd_raw(X, d2d, y2d, v2d, scal, *, bn: int, bk: int,
                 interpret: bool = False):
    n, p = X.shape
    assert n % bn == 0 and p % bk == 0
    grid = (n // bn, p // bk)
    return pl.pallas_call(
        _xd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, 1), lambda i, k: (k, _0)),
            pl.BlockSpec((bn, 1), lambda i, k: (i, _0)),
            pl.BlockSpec((bn, 1), lambda i, k: (i, _0)),
            pl.BlockSpec((3, 1), lambda i, k: (_0, _0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, k: (i, _0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)],
        interpret=interpret,
    )(X, d2d, y2d, v2d, scal)
