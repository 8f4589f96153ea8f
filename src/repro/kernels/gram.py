"""Pallas TPU kernel: fused shifted-Gram for the SVEN dual.

Computes the paper's dual kernel matrix K = Zhat^T Zhat (eq. 3) directly from
the ORIGINAL (n, p) design matrix — the (2p, n) constructed SVM dataset never
exists in HBM. Beyond the fusion, the kernel exploits the block identity

    K[a,b][i,j] = s_a s_b (X^T X)_ij - s_a u_i - s_b u_j + s,
    u = X^T y / t,  s = y^T y / t^2,  s_0 = +1, s_1 = -1,

so one p x p Gram pass yields all four (2p)^2 blocks: 4x fewer MACs and 2x
less HBM read traffic than the paper-faithful materialize-then-matmul.

Tiling: grid (p/bm, p/bn, n/bk), MXU-aligned 128-multiples, fp32 accumulation
in VMEM scratch; the rank-1 shift terms (u_i, u_j) and the scalar s are
accumulated in the same pass and applied in the final-k epilogue.
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


def _gram_kernel(xi_ref, xj_ref, y_ref, invt_ref, out_ref,
                 acc_p, acc_a, acc_b, acc_c, *, precision="f32"):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_p[...] = jnp.zeros_like(acc_p)
        acc_a[...] = jnp.zeros_like(acc_a)
        acc_b[...] = jnp.zeros_like(acc_b)
        acc_c[...] = jnp.zeros_like(acc_c)

    xi = xi_ref[...].astype(jnp.float32)          # (bk, bm)
    xj = xj_ref[...].astype(jnp.float32)          # (bk, bn)
    yk = y_ref[...].astype(jnp.float32)           # (bk, 1)

    # "f32" forces full-precision MACs; "bf16" allows the MXU's fast
    # low-precision passes — accumulation stays f32 either way, and one f32
    # refinement re-solve on top restores <= 1e-10 parity (DESIGN.md §10.3).
    prec = (jax.lax.Precision.HIGHEST if precision == "f32"
            else jax.lax.Precision.DEFAULT)
    acc_p[...] += jax.lax.dot_general(
        xi, xj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    acc_a[...] += jax.lax.dot_general(
        xi, yk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    acc_b[...] += jax.lax.dot_general(
        xj, yk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    acc_c[...] += jax.lax.dot_general(
        yk, yk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)

    @pl.when(k == nk - 1)
    def _epilogue():
        invt = invt_ref[0, 0].astype(jnp.float32)
        P = acc_p[...]
        a = acc_a[...] * invt                      # (bm, 1) broadcasts over cols
        b = (acc_b[...] * invt).T                  # (1, bn) broadcasts over rows
        s = acc_c[0, 0] * invt * invt
        dt = out_ref.dtype
        out_ref[0, 0] = (P - a - b + s).astype(dt)
        out_ref[0, 1] = (-P - a + b + s).astype(dt)
        out_ref[1, 0] = (-P + a - b + s).astype(dt)
        out_ref[1, 1] = (P + a + b + s).astype(dt)


def gram_pallas_raw(
    X: jax.Array,        # (n, p) with n % bk == 0, p % bm == p % bn == 0
    y2d: jax.Array,      # (n, 1)
    invt: jax.Array,     # (1, 1)
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=jnp.float32,
    precision: str = "f32",
    interpret: bool = False,
) -> jax.Array:
    """Unpadded core call. Returns K in block layout (2, 2, p, p)."""
    import functools

    n, p = X.shape
    assert n % bk == 0 and p % bm == 0 and p % bn == 0, (n, p, bm, bn, bk)
    grid = (p // bm, p // bn, n // bk)
    return pl.pallas_call(
        functools.partial(_gram_kernel, precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, 1), lambda i, j, k: (k, _0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (_0, _0)),
        ],
        out_specs=pl.BlockSpec((2, 2, bm, bn), lambda i, j, k: (_0, _0, i, j)),
        out_shape=jax.ShapeDtypeStruct((2, 2, p, p), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(X, X, y2d, invt)  # X passed twice: row-tile view (xi) and col-tile view (xj)
