"""Jitted public wrappers for the kernel bodies: backend resolution through
the per-backend registry (kernels/registry.py), tile selection through the
autotuner (kernels/autotune.py), padding, and dtype/precision handling.

One `backend` enum drives everything (DESIGN.md §10): a resolved value from
`registry.RESOLVED_BACKENDS` names both the kernel body ("tpu" Pallas, "ref"
jnp oracle) and how it executes (compiled vs interpret). `None`/"auto"
resolves from the OPERANDS' committed devices, never from the process
default backend at trace time (the §9.3 bugfix);
traced callers (`core/sven.py`, the bucket executables) thread an explicit
resolved value from `SvenConfig.backend`, pinned pre-trace by
`core.sven.resolve_backend`.

Precision (`"f32" | "bf16"`) selects the MAC path of the Gram kernel and
the storage dtype fed to the fused stats kernels; accumulation is f32 in
every cell of the matrix (README "Backends & precision"). The "ref" body
ignores it — the oracle always computes at full input precision.

Tiles: explicit `bm=`/`bn=`/`bk=`/`bp=` kwargs always win; unset tiles come
from `autotune.tiles_for`, which measures candidates once per (body,
shape-bucket) on compiled backends and uses static defaults elsewhere.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune, registry
from repro.kernels import gram as _gram
from repro.kernels import hinge as _hinge
from repro.kernels import hinge_stats as _hs
from repro.kernels import ref as _ref

# every body wires up here, once, at import time (re-import just
# overwrites the same keys)
registry.register("shifted_gram", "tpu")(_gram.gram_pallas_raw)
registry.register("shifted_gram", "ref")(_ref.gram_blocks_ref)
registry.register("hinge_stats", "tpu")(_hs.hinge_stats_raw)
registry.register("hinge_stats", "ref")(_ref.hinge_stats_ref)
registry.register("hinge_xtv", "tpu")(_hinge.hinge_xtv_raw)
registry.register("hinge_xtv", "ref")(_ref.hinge_xtv_ref)
registry.register("hinge_xd", "tpu")(_hinge.hinge_xd_raw)
registry.register("hinge_xd", "ref")(_ref.hinge_xd_ref)


def resolve_interpret(interpret, *arrays) -> bool:
    """Deprecated two-flag-era helper: the interpret bit of the resolved
    backend; new code should use `registry.resolve_kernel_backend`."""
    if interpret is not None:
        return bool(interpret)
    return registry.split_backend(
        registry.resolve_kernel_backend(None, *arrays))[1]


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _next_mult(sz: int, base: int = 128) -> int:
    """Largest power-of-two-ish tile not exceeding the padded size."""
    m = base
    while m > sz:
        m //= 2
    return max(m, 8)


def _tile_partials(part: jax.Array) -> jax.Array:
    """One scalar per grid tile from a kernel's (G, 8, 128) lane-aligned
    per-tile partials (every element of a tile holds the same value)."""
    return part.reshape(part.shape[0], -1)[:, 0]


def _storage(Xp: jax.Array, precision: str) -> jax.Array:
    """bf16 keeps reduced-precision STORAGE (the Rgtsvm recipe — kernels
    accumulate f32 regardless); f32 leaves the operand alone."""
    return Xp.astype(jnp.bfloat16) if precision == "bf16" else Xp


def _gram_tiles(backend: str, n: int, p: int, bm, bn, bk,
                precision: str) -> dict:
    if bm is not None and bn is not None and bk is not None:
        return {"bm": bm, "bn": bn, "bk": bk}
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    tiles = autotune.tiles_for("shifted_gram", backend, n, p, dtype)
    for k, v in (("bm", bm), ("bn", bn), ("bk", bk)):
        if v is not None:
            tiles[k] = v
    return tiles


# -- shifted Gram -----------------------------------------------------------

@jax.named_scope("sven.gram")
def shifted_gram(
    X: jax.Array,
    y: jax.Array,
    t: jax.Array | float,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    flatten: bool = True,
    backend: Optional[str] = None,
    precision: str = "f32",
) -> jax.Array:
    """K = Zhat^T Zhat of the SVEN dual, as (2p, 2p) (flatten) or (2,2,p,p).

    `backend=None`/"auto" resolves against X's committed devices (see
    `registry.resolve_kernel_backend`); traced call sites must pass an
    explicit resolved value.
    """
    resolved = registry.resolve_kernel_backend(backend, X, y)
    tiles = _gram_tiles(resolved, *X.shape, bm, bn, bk, precision)
    return _shifted_gram_jit(X, y, t, flatten=flatten, backend=resolved,
                             precision=precision, **tiles)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "flatten", "backend",
                                   "precision"))
def _shifted_gram_jit(
    X: jax.Array,
    y: jax.Array,
    t: jax.Array | float,
    *,
    bm: int,
    bn: int,
    bk: int,
    flatten: bool,
    backend: str,
    precision: str,
) -> jax.Array:
    n, p = X.shape
    impl, body, interp = registry.lookup("shifted_gram", backend)
    if body == "ref":
        Kb = _ref.gram_blocks_ref(X, y, t)
        return _ref.flatten_gram(Kb) if flatten else Kb
    Xp = _storage(_pad_to(_pad_to(X, 0, bk), 1, max(bm, bn)), precision)
    y2d = _storage(_pad_to(y[:, None], 0, bk).astype(X.dtype), precision)
    invt = (1.0 / jnp.asarray(t, jnp.float32)).reshape(1, 1)
    Kb = impl(Xp, y2d, invt, bm=bm, bn=bn, bk=bk, precision=precision,
              interpret=interp)
    Kb = Kb[:, :, :p, :p]
    return _ref.flatten_gram(Kb) if flatten else Kb


# -- hinge Hessian mat-vec --------------------------------------------------

def hinge_hessian_matvec(
    X: jax.Array,
    y: jax.Array,
    t: jax.Array | float,
    C: jax.Array | float,
    act_top: jax.Array,
    act_bot: jax.Array,
    v: jax.Array,
    *,
    bp: int = 512,
    bn: int = 512,
    bk: int = 512,
    backend: Optional[str] = None,
    precision: str = "f32",
) -> jax.Array:
    """H v = v + 2C Xhat^T(act . (Xhat v)) via two fused GEMV passes."""
    resolved = registry.resolve_kernel_backend(backend, X, v)
    return _hinge_hessian_matvec_jit(
        X, y, t, C, act_top, act_bot, v, bp=bp, bn=bn, bk=bk,
        backend=resolved, precision=precision)


@partial(jax.jit, static_argnames=("bp", "bn", "bk", "backend", "precision"))
def _hinge_hessian_matvec_jit(
    X: jax.Array,
    y: jax.Array,
    t: jax.Array | float,
    C: jax.Array | float,
    act_top: jax.Array,
    act_bot: jax.Array,
    v: jax.Array,
    *,
    bp: int,
    bn: int,
    bk: int,
    backend: str,
    precision: str,
) -> jax.Array:
    impl_xtv, body, interp = registry.lookup("hinge_xtv", backend)
    if body == "ref":
        return _ref.hessian_matvec_ref(X, y, t, C, act_top, act_bot, v)
    impl_xd, _, _ = registry.lookup("hinge_xd", backend)
    n, p = X.shape
    bp_ = min(bp, _next_mult(p))
    bk1 = min(bk, _next_mult(n))
    Xp1 = _storage(_pad_to(_pad_to(X, 0, bk1), 1, bp_), precision)
    v2d = _pad_to(v[:, None], 0, bk1).astype(jnp.float32)
    y2d = _pad_to(y[:, None], 0, bk1).astype(jnp.float32)
    at2d = _pad_to(act_top[:, None].astype(jnp.float32), 0, bp_)
    ab2d = _pad_to(act_bot[:, None].astype(jnp.float32), 0, bp_)
    invt = (1.0 / jnp.asarray(t, jnp.float32)).reshape(1, 1)
    d2d, e_part = impl_xtv(Xp1, v2d, y2d, at2d, ab2d, invt,
                           bp=bp_, bk=bk1, interpret=interp)
    e = jnp.sum(_tile_partials(e_part))

    bn_ = min(bn, _next_mult(n))
    bk2 = min(bk, _next_mult(p))
    Xp2 = _storage(_pad_to(_pad_to(X, 0, bn_), 1, bk2), precision)
    d2d = _pad_to(d2d[: p], 0, bk2)
    y2d2 = _pad_to(y[:, None], 0, bn_).astype(jnp.float32)
    v2d2 = _pad_to(v[:, None], 0, bn_).astype(jnp.float32)
    scal = jnp.stack([1.0 / jnp.asarray(t, jnp.float32),
                      e.astype(jnp.float32),
                      2.0 * jnp.asarray(C, jnp.float32)]).reshape(3, 1)
    hv = impl_xd(Xp2, d2d, y2d2, v2d2, scal, bn=bn_, bk=bk2,
                 interpret=interp)
    return hv[:n, 0].astype(v.dtype)


# -- fused Newton outer-step stats ------------------------------------------

def hinge_stats(
    X: jax.Array,
    y: jax.Array,
    t: jax.Array | float,
    w: jax.Array,
    C: jax.Array | float,
    *,
    bp: Optional[int] = None,
    bk: Optional[int] = None,
    backend: Optional[str] = None,
    precision: str = "f32",
):
    """Fused Newton outer-step stats: (margin (2p,), act (2p,), loss, galpha).

    Served by the TPU body or the ref oracle per the resolved backend.
    """
    resolved = registry.resolve_kernel_backend(backend, X, w)
    if bp is None or bk is None:
        tiles = autotune.tiles_for("hinge_stats", resolved, *X.shape)
        bp = bp if bp is not None else tiles["bp"]
        bk = bk if bk is not None else tiles["bk"]
    return _hinge_stats_jit(X, y, t, w, C, bp=bp, bk=bk, backend=resolved,
                            precision=precision)


@partial(jax.jit, static_argnames=("bp", "bk", "backend", "precision"))
def _hinge_stats_jit(
    X: jax.Array,
    y: jax.Array,
    t: jax.Array | float,
    w: jax.Array,
    C: jax.Array | float,
    *,
    bp: int,
    bk: int,
    backend: str,
    precision: str,
):
    impl, body, interp = registry.lookup("hinge_stats", backend)
    if body == "ref":
        return _ref.hinge_stats_ref(X, y, t, w, C)
    n, p = X.shape
    bp_ = min(bp, _next_mult(p))
    bk_ = min(bk, _next_mult(n))
    Xp = _storage(_pad_to(_pad_to(X, 0, bk_), 1, bp_), precision)
    w2d = _pad_to(w[:, None], 0, bk_).astype(jnp.float32)
    y2d = _pad_to(y[:, None], 0, bk_).astype(jnp.float32)
    scal = jnp.stack([1.0 / jnp.asarray(t, jnp.float32),
                      jnp.asarray(C, jnp.float32)]).reshape(2, 1)
    mt, mb, gt, gb, lp = impl(Xp, w2d, y2d, scal, bp=bp_, bk=bk_,
                              interpret=interp)
    # padded feature columns produce margin 1-eps... no: padded cols give a=0,
    # o=-+byw; slice them off before assembling
    margin = jnp.concatenate([mt[:p, 0], mb[:p, 0]]).astype(w.dtype)
    act = (margin < 1.0).astype(w.dtype)
    galpha = jnp.concatenate([gt[:p, 0], gb[:p, 0]]).astype(w.dtype)
    # loss partials include padded columns of the LAST block: recompute their
    # contribution exactly by masking is cheap: padded cols have a=0 =>
    # xi_top = act*(1-(-byw))... subtract analytically:
    pad = (-p) % bp_
    byw = (y @ w) / jnp.asarray(t, w.dtype)
    xi_pad = jnp.maximum(1.0 + byw, 0.0)   # padded cols: a=0 => both halves
    pad_loss = pad * jnp.asarray(C, jnp.float32) * 2.0 * xi_pad ** 2
    loss = 0.5 * (w @ w) + jnp.sum(_tile_partials(lp)) - pad_loss
    return margin, act, loss.astype(w.dtype), galpha


# -- sharded Gram -----------------------------------------------------------

def sharded_shifted_gram(
    mesh,
    X: jax.Array,
    y: jax.Array,
    t: jax.Array | float,
    *,
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    backend: Optional[str] = None,
    precision: str = "f32",
) -> jax.Array:
    """K = Zhat^T Zhat with the ROWS of X sharded over `mesh` (DESIGN.md §9).

    Each device runs the block-gram kernel for the RESOLVED backend on its
    local row shard and ONE psum over the flattened mesh assembles the full
    (2p, 2p) kernel: the quadrant identity is linear in the per-shard
    statistics (G, u, s), so partial block-grams sum exactly. The backend is
    resolved OUTSIDE the shard_map region — inside it the process default
    backend is unrelated to the kernel's actual placement, which is
    precisely why trace-time sniffing was a bug.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    resolved = registry.resolve_kernel_backend(backend, X, y)
    n_loc = X.shape[0] // mesh.size
    tiles = _gram_tiles(resolved, n_loc, X.shape[1], bm, bn, bk, precision)

    def local(X_loc, y_loc, t_op):
        Kb = _shifted_gram_jit(X_loc, y_loc, t_op, flatten=True,
                               backend=resolved, precision=precision,
                               **tiles)
        return jax.lax.psum(Kb, axes)

    fn = shard_map(local, mesh=mesh, in_specs=(P(axes, None), P(axes), P()),
                   out_specs=P(), check_vma=False)
    return fn(X, y, jnp.asarray(t, X.dtype))
