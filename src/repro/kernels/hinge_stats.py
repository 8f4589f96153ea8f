"""Pallas TPU kernel: fused margins + squared-hinge loss + dual-gradient
(the primal Newton OUTER step, complementing hinge.py's CG inner mat-vec).

One pass over X computes, for the implicit SVEN dataset, everything the
Newton iteration needs between CG solves:
    a = X^T w, byw = y.w/t  ->  margins, active set, loss, galpha
where grad_w = w + 2C Xhat^T galpha (second pass via hinge_xd). The fused
epilogue means margins/act/xi/galpha never round-trip HBM as separate
elementwise passes — on the MATLAB path these are 4 extra BLAS-1 sweeps
over 2p-vectors.

Grid (p/bp, n/bk); fp32 accumulation; both +/- halves produced per tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hinge import HIGHEST, PARTIAL_TILE, _0


def _stats_kernel(x_ref, w_ref, y_ref, scal_ref,
                  mt_ref, mb_ref, gt_ref, gb_ref, loss_ref,
                  acc_a, acc_byw):
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _init():
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_byw[...] = jnp.zeros_like(acc_byw)

    xk = x_ref[...].astype(jnp.float32)           # (bk, bp)
    wk = w_ref[...].astype(jnp.float32)           # (bk, 1)
    yk = y_ref[...].astype(jnp.float32)           # (bk, 1)
    acc_a[...] += jax.lax.dot_general(
        xk, wk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST)
    acc_byw[...] += jax.lax.dot_general(
        yk, wk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST)

    @pl.when(k == nk - 1)
    def _epilogue():
        invt = scal_ref[0, 0].astype(jnp.float32)
        C = scal_ref[1, 0].astype(jnp.float32)
        a = acc_a[...]                             # (bp, 1)
        byw = acc_byw[0, 0] * invt
        o_top = a - byw
        o_bot = a + byw
        m_top = o_top                              # yhat=+1
        m_bot = -o_bot                             # yhat=-1
        act_t = (m_top < 1.0).astype(jnp.float32)
        act_b = (m_bot < 1.0).astype(jnp.float32)
        xi_t = act_t * (1.0 - m_top)
        xi_b = act_b * (1.0 - m_bot)
        mt_ref[...] = m_top.astype(mt_ref.dtype)
        mb_ref[...] = m_bot.astype(mb_ref.dtype)
        gt_ref[...] = (act_t * (o_top - 1.0)).astype(gt_ref.dtype)
        gb_ref[...] = (act_b * (o_bot + 1.0)).astype(gb_ref.dtype)
        loss = C * (jnp.sum(xi_t * xi_t) + jnp.sum(xi_b * xi_b))
        loss_ref[...] = jnp.full(loss_ref.shape, loss, loss_ref.dtype)


def hinge_stats_raw(X, w2d, y2d, scal, *, bp: int, bk: int,
                    interpret: bool = False):
    """Returns (mt, mb, gt, gb) each (p, 1), and the loss partials
    (p // bp, 8, 128) laid out as `hinge.hinge_xtv_raw`'s e partials."""
    n, p = X.shape
    assert n % bk == 0 and p % bp == 0
    grid = (p // bp, n // bk)
    out = [jax.ShapeDtypeStruct((p, 1), jnp.float32) for _ in range(4)]
    out.append(jax.ShapeDtypeStruct((p // bp, *PARTIAL_TILE), jnp.float32))
    vec = pl.BlockSpec((bp, 1), lambda i, k: (i, _0))
    return pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bp), lambda i, k: (k, i)),
            pl.BlockSpec((bk, 1), lambda i, k: (k, _0)),
            pl.BlockSpec((bk, 1), lambda i, k: (k, _0)),
            pl.BlockSpec((2, 1), lambda i, k: (_0, _0)),
        ],
        out_specs=[vec, vec, vec, vec,
                   pl.BlockSpec((1, *PARTIAL_TILE),
                                lambda i, k: (i, _0, _0))],
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((bp, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(X, w2d, y2d, scal)
