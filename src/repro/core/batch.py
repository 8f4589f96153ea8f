"""Batched multi-problem SVEN solves — vmap over the jit-native engine.

`sven_batch` stacks whole Elastic Net problems along a leading batch axis
and runs the same `_sven_core` trace for all of them at once (DESIGN.md §6).
Batching is where GPU/TPU SVM throughput actually comes from (cf. Rgtsvm,
Wang et al. 2017): one fat executable instead of B thin dispatches. The
three stacking patterns the serving layer needs all go through here:

    multi-response     X (n, p) shared,  y (B, n)
    (t, lambda2) grid  X, y shared,      t (B,), lambda2 (B,)   [en_grid]
    k-fold CV          X (B, n_tr, p), y (B, n_tr)              [cv_folds]

Any subset of {X, y, t, lambda2} may carry the batch axis; the rest
broadcast. Under an active `repro.dist.mesh_context` whose size divides the
batch, the solve runs as a shard_map over the batch axis (DESIGN.md §9.2):
each device vmaps its OWN local lanes with zero collectives — the same
rules that shard LM training batches shard solver workloads, without the
per-iteration while_loop synchronization a partitioner-sharded vmap would
pay. Any other mesh/batch combination falls back to the single-device
executable.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import dist
from repro.core.sven import (SvenArrays, SvenConfig, _bump_trace, _sven_core,
                             resolve_backend)


class SvenBatchSolution(NamedTuple):
    """Stacked per-problem solutions; every field has a leading (B,) axis."""

    beta: jax.Array           # (B, p)
    alpha: jax.Array          # (B, 2p)
    w: jax.Array              # (B, n)
    iters: jax.Array          # (B,)
    opt_residual: jax.Array   # (B,)
    kkt: jax.Array            # (B,)


def solve_lanes(solve_one, operands: tuple, axes: tuple):
    """Vmap `solve_one` over the stacked lanes of `operands` (pytrees; ax
    == 0 marks a batched operand). A width-1 stack skips vmap entirely —
    vmap rewrites every nested while_loop into its masked batched form,
    ~2.4x slower than the plain loops even at width 1. The ONE lane-solve
    implementation: both the constrained and the penalized batch entry
    points (and their shard_map bodies) route through here."""
    widths = {leaf.shape[0]
              for op, ax in zip(operands, axes) if ax == 0 and op is not None
              for leaf in jax.tree.leaves(op)}
    if widths == {1}:
        ops1 = tuple(jax.tree.map(lambda a: a[0], op) if ax == 0 else op
                     for op, ax in zip(operands, axes))
        return jax.tree.map(lambda a: jnp.expand_dims(a, 0),
                            solve_one(*ops1))
    return jax.vmap(solve_one, in_axes=axes)(*operands)


def shard_map_lanes(mesh, axes: tuple, local, operands: tuple):
    """shard_map a stacked solve over the batch axis (DESIGN.md §9.2).

    Problems are independent, so each device runs `local` on ITS OWN lane
    block with ZERO collectives — crucially the solver while_loops stay
    per-device (a batch-sharded vmap under the partitioner turns every
    while_loop condition into a cross-device all-reduce per iteration,
    orders of magnitude slower). Batched operands (ax == 0) shard dim 0
    over every mesh axis, the rest replicate; every output carries the
    leading batch axis.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    data_axes = tuple(mesh.axis_names)
    in_specs = tuple(P(data_axes) if ax == 0 else P() for ax in axes)
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=P(data_axes), check_vma=False)(*operands)


def _sven_solve_one(config: SvenConfig):
    def solve_one(X_, y_, t_, l2_, keep_, wa_, ww_):
        return _sven_core(X_, y_, t_, l2_, wa_, ww_, config, keep_)
    return solve_one


@partial(jax.jit, static_argnames=("config", "axes"))
def _sven_batch_jit(X, y, t, lambda2, keep, warm_alpha, warm_w,
                    config: SvenConfig, axes) -> SvenArrays:
    _bump_trace("sven_batch")
    return solve_lanes(_sven_solve_one(config),
                       (X, y, t, lambda2, keep, warm_alpha, warm_w), axes)


@partial(jax.jit, static_argnames=("config", "axes", "mesh"))
def _sven_batch_sharded_jit(X, y, t, lambda2, keep, warm_alpha, warm_w,
                            config: SvenConfig, axes, mesh) -> SvenArrays:
    _bump_trace("sven_batch")

    def local(*ops):
        return solve_lanes(_sven_solve_one(config), ops, axes)

    return shard_map_lanes(mesh, axes, local,
                           (X, y, t, lambda2, keep, warm_alpha, warm_w))


def batch_mesh(batch_size: int, n: Optional[int] = None,
               p: Optional[int] = None, *, form: str = "constrained",
               route: str = "auto"):
    """The mesh a stacked launch should fan its batch axis over, or None.

    Structural vetoes first (no context, 1-device mesh, mesh does not
    divide `batch_size` -> None: graceful single-device fallback), then the
    COST MODEL: with the problem shape (`n`, `p`) given, `core.routing`
    prices the fan-out against a single-device vmap on the calibrated mesh
    and returns None when single wins — an active mesh_context is an
    OFFER of devices, not an obligation to use them (the PR 6 regression
    fix). `route="batch"` pins the fan-out, `route="single"` pins one
    device; without a shape the offer is taken as-is (legacy behavior,
    the caller knows no better and neither do we).
    """
    ctx = dist.current_context()
    if ctx is None or route == "single":
        return None
    mesh = ctx[0]
    if mesh.size <= 1 or batch_size % mesh.size != 0:
        return None
    if route == "batch" or n is None or p is None:
        return mesh
    from repro.core import routing
    decision = routing.route_batch(n, p, batch_size, mesh, form=form,
                                   route=route)
    return mesh if decision.path == "batch" else None


def _maybe_shard_batch(arr: jax.Array, batched: bool, ctx=None) -> jax.Array:
    """Place a stacked operand with the rule table's "batch" axis (dim 0).

    `ctx` is an explicit (mesh, rules) pair; default is the innermost
    `dist.mesh_context` (no context, no placement). The one implementation
    of batch-axis placement — CV fold placement routes through here too.
    """
    if ctx is None:
        ctx = dist.current_context()
    if ctx is None or not batched:
        return arr
    mesh, rules = ctx
    names = ("batch",) + (None,) * (arr.ndim - 1)
    spec = dist.resolve_spec(names, arr.shape, mesh, rules)
    return jax.device_put(arr, NamedSharding(mesh, spec))


def sven_batch(
    X: jax.Array,
    y: jax.Array,
    t,
    lambda2,
    config: SvenConfig = SvenConfig(),
    *,
    keep: jax.Array | None = None,
    warm_alpha: jax.Array | None = None,
    warm_w: jax.Array | None = None,
    route: str = "auto",
) -> SvenBatchSolution:
    """Solve a stack of Elastic Net problems in one vmapped executable.

    Batch-axis detection by rank: X (B, n, p) vs (n, p); y (B, n) vs (n,);
    t / lambda2 (B,) vs scalar; optional screening mask keep (B, p) vs (p,)
    (see `sven`'s keep). At least one operand must be batched; all batched
    operands must agree on B. Results match a Python loop of per-problem
    `sven` calls to solver tolerance (tested).

    `warm_alpha` (B, 2p) / `warm_w` (B, n) warm-start every problem in the
    stack — the serving runtime's cache hands back neighbouring solutions
    through these (zero rows are exactly a cold start, so a mixed
    hit/miss batch stays a single executable).

    Under an active `dist.mesh_context` the batch axis fans out over the
    mesh only when the `core.routing` cost model says the mesh wins for
    this shape (see `batch_mesh`); `route="batch"`/`route="single"` pins
    the layout. Results are identical either way (tested to <= 1e-10).
    """
    X = jnp.asarray(X)
    dtype = X.dtype
    y = jnp.asarray(y, dtype)
    t = jnp.asarray(t, dtype)
    lambda2 = jnp.asarray(lambda2, dtype)
    if keep is not None:
        keep = jnp.asarray(keep)
    if warm_alpha is not None:
        warm_alpha = jnp.asarray(warm_alpha, dtype)
    if warm_w is not None:
        warm_w = jnp.asarray(warm_w, dtype)

    axes = (0 if X.ndim == 3 else None,
            0 if y.ndim == 2 else None,
            0 if t.ndim == 1 else None,
            0 if lambda2.ndim == 1 else None,
            0 if keep is not None and keep.ndim == 2 else None,
            0 if warm_alpha is not None else None,
            0 if warm_w is not None else None)
    operands = (X, y, t, lambda2, keep, warm_alpha, warm_w)
    sizes = {op.shape[0] for op, ax in zip(operands, axes) if ax == 0}
    if not sizes:
        raise ValueError("sven_batch: no batched operand (add a leading batch "
                         "axis to X, y, t or lambda2, or call sven())")
    if len(sizes) != 1:
        raise ValueError(f"sven_batch: inconsistent batch sizes {sorted(sizes)}")

    # route BEFORE placing: once operands are batch-sharded, a vmapped
    # executable would run under the partitioner with a per-iteration
    # all-reduce on every while_loop — placement must follow the routing
    # decision, never precede it.
    pn, pp = X.shape[-2], X.shape[-1]
    mesh = batch_mesh(next(iter(sizes)), pn, pp, route=route)
    if mesh is not None:
        X, y, t, lambda2, keep, warm_alpha, warm_w = (
            _maybe_shard_batch(op, ax == 0) if op is not None else None
            for op, ax in zip(operands, axes))
    config = resolve_backend(config, X, y)
    if mesh is not None:
        arrs = _sven_batch_sharded_jit(X, y, t, lambda2, keep, warm_alpha,
                                       warm_w, config, axes, mesh)
    else:
        arrs = _sven_batch_jit(X, y, t, lambda2, keep, warm_alpha, warm_w,
                               config, axes)
    return SvenBatchSolution(beta=arrs.beta, alpha=arrs.alpha, w=arrs.w,
                             iters=arrs.iters, opt_residual=arrs.opt_residual,
                             kkt=arrs.kkt)


def en_grid(ts, lambda2s) -> Tuple[jax.Array, jax.Array]:
    """Flatten a (t, lambda2) product grid into batched (B,) operand pairs."""
    T, L = jnp.meshgrid(jnp.asarray(ts), jnp.asarray(lambda2s), indexing="ij")
    return T.ravel(), L.ravel()


def cv_folds(X: jax.Array, y: jax.Array, k: int):
    """Stack k leave-one-fold-out problems for `sven_batch` (equal-size folds).

    Uses the first k*(n//k) rows so every fold — and therefore every stacked
    training problem — has the same shape (a vmap requirement). Returns
    (X_train (k, n-f, p), y_train (k, n-f), X_val (k, f, p), y_val (k, f)).
    """
    n = X.shape[0]
    if k < 2 or k > n:
        raise ValueError(f"cv_folds: need 2 <= k <= n, got k={k}, n={n}")
    fold = n // k
    n_use = fold * k
    X, y = X[:n_use], y[:n_use]
    idx = jnp.arange(n_use)
    val_idx = idx.reshape(k, fold)
    train_idx = jnp.stack([
        jnp.concatenate([idx[: i * fold], idx[(i + 1) * fold:]]) for i in range(k)
    ])
    return X[train_idx], y[train_idx], X[val_idx], y[val_idx]
