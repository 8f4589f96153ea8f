"""Batched K-fold cross-validation for the penalized Elastic Net (DESIGN.md §7).

glmnet's `cv.glmnet` loops folds sequentially; here the K held-out training
problems are stacked through `core.batch.cv_folds` and the whole (lambda
grid x fold) surface runs as ONE jitted `lax.scan` over the grid whose body
vmaps the screening-fused penalized point solver (`core.api._enet_point`)
over the fold axis — K solver machines advance in lockstep, each carrying
its own warm (beta, alpha, w, t, nu) state down the path. Under an active
`repro.dist.mesh_context` the fold axis is exactly the "batch" axis the rule
table shards, so CV fans out across the data-parallel mesh like any other
batched workload.

`cross_validate` selects lambda by mean held-out MSE and refits on the full
data: the entire driver costs exactly two traces — `enet_cv_scan` (the CV
surface) + `enet` (the refit) — asserted via `trace_counts()` in tests.
`cross_validate_reference` keeps the glmnet-style sequential per-fold loop
as the testable reference (identical fold splits, identical grid).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import dist
from repro.core import api
from repro.core.batch import cv_folds
from repro.core.sven import _bump_trace


def _auto_fold_chunk(k: int, mesh) -> int:
    """Right-size the scan-of-vmap: how many folds advance in vmap lockstep.

    A vmapped `while_loop` costs the MAX trip count across lanes at every
    nesting level (Illinois evals x Newton iters x CG), so on a single CPU
    device the k-wide lockstep runs ~1.6x SLOWER than solving folds one
    after another (measured on a CPU host). chunk=1 keeps
    everything inside ONE executable — an outer `lax.scan` over folds, no
    per-fold dispatch — which is what beats the host-side per-fold loop on
    CPU.

    The decision keys on where the FOLDS ARE PLACED, not on process-global
    device counts: with a (>1)-device `mesh` carrying the fold axis, every
    device advances its own fold subset and the full-width vmap wins; the
    mere existence of extra devices the folds don't live on (the old
    heuristic) buys nothing. Non-CPU backends keep the full-width vmap
    even on one device (batch parallelism in the hardware).

    `mesh` is REQUIRED and must be the RESOLVED placement (the mesh the
    folds actually shard over, or None for single-device) — an optional
    default here once let a caller inside an outer `mesh_context` with a
    single-device resolution fall back to process-global state and pick the
    wrong lockstep width. Every call path resolves first, then asks.
    """
    if mesh is not None and mesh.size > 1:
        return k
    if jax.default_backend() != "cpu":
        return k
    return 1


def _resolve_cv_mesh(mesh, k: int, n_tr: Optional[int] = None,
                     p: Optional[int] = None, points: int = 1):
    """mesh="auto" -> the innermost dist context, else a device-spanning
    data mesh, else None; any mesh whose size does not divide k falls back
    to None (replicated folds would just pay collective overhead).

    An auto-resolved mesh is an OFFER, so with the fold-problem shape
    (`n_tr`, `p`, `points` grid points per lane) given it is also priced by
    the `core.routing` cost model and declined when a single device would
    finish the CV surface sooner. An EXPLICIT mesh pins the placement —
    the caller said where the folds live, routing does not second-guess it.
    """
    auto = mesh == "auto"
    if auto:
        ctx = dist.current_context()
        if ctx is not None:
            mesh = ctx[0]
        elif jax.device_count() > 1:
            mesh = dist.data_mesh()
        else:
            mesh = None
    if mesh is not None and (mesh.size <= 1 or k % mesh.size != 0):
        return None
    if auto and mesh is not None and n_tr is not None and p is not None:
        from repro.core import routing
        decision = routing.route_batch(n_tr, p, k, mesh, form="penalized",
                                       points=points)
        if decision.path != "batch":
            return None
    return mesh


def _place_folds(mesh, *arrays):
    """Shard the leading (fold) axis of each stacked array over `mesh` via
    the one batch-axis placement implementation (`_maybe_shard_batch`);
    rules come from the active context when it carries this mesh."""
    from repro.core.batch import _maybe_shard_batch

    ctx = dist.current_context()
    rules = (ctx[1] if ctx is not None and ctx[0] is mesh
             else dict(dist.DEFAULT_RULES))
    return tuple(_maybe_shard_batch(a, True, (mesh, rules)) for a in arrays)


@partial(jax.jit, static_argnames=("config", "fold_chunk", "mesh"))
def _enet_cv_scan_sharded(Xtr, ytr, Xva, yva, lambda1s, lambda2,
                          config: api.PathConfig, fold_chunk: int, mesh):
    """Device-parallel CV: the fold axis shard_mapped over the mesh.

    Each device runs `_enet_cv_scan` on ITS OWN fold block with zero
    collectives — in particular the solver while_loops never synchronize
    across devices (a fold-sharded vmap under the partitioner would
    all-reduce every loop condition, orders of magnitude slower).
    `fold_chunk` is the PER-DEVICE lockstep width; with one fold per device
    it is 1, which `_enet_cv_scan` special-cases to the plain un-vmapped
    loops: full device parallelism AND no masked-lockstep penalty.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)

    def local(Xt, yt, Xv, yv, l1, l2):
        return _enet_cv_scan(Xt, yt, Xv, yv, l1, l2, config, fold_chunk)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axes), P(axes), P(axes), P(axes), P(), P()),
                     out_specs=(P(None, axes),) * 5, check_vma=False)(
                         Xtr, ytr, Xva, yva, lambda1s, lambda2)


@partial(jax.jit, static_argnames=("config", "fold_chunk"))
def _enet_cv_scan(Xtr, ytr, Xva, yva, lambda1s, lambda2,
                  config: api.PathConfig, fold_chunk: Optional[int] = None):
    """(L,) grid scan of fold-chunked vmaps; returns per-point CV diagnostics.

    Folds are processed `fold_chunk` at a time (None = all k at once, the
    pure vmap): an outer scan over k/fold_chunk chunks, each chunk scanning
    the lambda grid with a fold_chunk-wide vmapped `_enet_point` body and
    its own warm state carried down the path. Results are identical for any
    chunking (tested); see `_auto_fold_chunk` for why the size matters.
    """
    _bump_trace("enet_cv_scan")
    k = Xtr.shape[0]
    c = k if fold_chunk is None else fold_chunk
    if k % c:
        raise ValueError(f"_enet_cv_scan: fold_chunk={c} must divide k={k}")
    chunked = jax.tree.map(lambda a: a.reshape(k // c, c, *a.shape[1:]),
                           (Xtr, ytr, Xva, yva))

    def chunk_body(_, xs):
        Xt, yt, Xv, yv = xs                            # (c, n_tr, p) ...
        if c == 1:
            # skip the inner vmap: even at width 1 it rewrites every nested
            # while_loop into its masked batched form, which runs ~2.4x
            # slower than the plain loops on CPU
            Xf, yf, Xv1, yv1 = Xt[0], yt[0], Xv[0], yv[0]

            def lam_body1(carry, lam1):
                carry2, pt = api._enet_point(Xf, yf, lam1, lambda2, carry,
                                             config)
                resid = Xv1 @ pt.beta - yv1
                return carry2, (jnp.mean(resid * resid)[None],
                                pt.n_kept[None], pt.evals[None],
                                pt.cg_steps[None], pt.stop[None])

            _, out = jax.lax.scan(lam_body1, api.cold_carry(Xf, yf), lambda1s)
            return None, out                           # each (L, 1)

        init = jax.vmap(api.cold_carry)(Xt, yt)

        def lam_body(carry, lam1):
            def one(Xf, yf, cf):
                return api._enet_point(Xf, yf, lam1, lambda2, cf, config)

            carry2, pts = jax.vmap(one)(Xt, yt, carry)
            resid = jnp.einsum("kif,kf->ki", Xv, pts.beta) - yv
            mse = jnp.mean(resid * resid, axis=1)      # (c,)
            return carry2, (mse, pts.n_kept, pts.evals, pts.cg_steps,
                            pts.stop)

        _, out = jax.lax.scan(lam_body, init, lambda1s)
        return None, out                               # each (L, c)

    _, out = jax.lax.scan(chunk_body, None, chunked)

    def reorder(a):                                    # (g, L, c) -> (L, k)
        return jnp.moveaxis(a, 0, 1).reshape(a.shape[1], k)

    return tuple(reorder(a) for a in out)


class CVResult(NamedTuple):
    lambda1s: jax.Array     # (L,) descending grid
    lambda2: float
    mse_path: jax.Array     # (L, k) held-out MSE per grid point and fold
    mean_mse: jax.Array     # (L,)
    lambda_min: float       # grid point minimizing mean CV MSE
    index_min: int
    beta: jax.Array         # (p,) full-data refit at lambda_min (orig scale)
    intercept: jax.Array
    n_kept: jax.Array       # (L, k) screened problem sizes
    evals: jax.Array        # (L, k) SVEN solves per (lambda, fold)
    cg_steps: jax.Array     # (L, k) CG iterations per (lambda, fold)
    stop: jax.Array         # (L, k) root-find stop codes (api.STOP_*)


def cross_validate(X, y, *, k: int = 5, lambda1s=None, n_lambdas: int = 40,
                   eps: Optional[float] = None, lambda2=1.0,
                   standardize: bool = True, fit_intercept: bool = True,
                   fold_chunk: Optional[int] = None, mesh="auto",
                   config: api.PathConfig = api.PathConfig()) -> CVResult:
    """K-fold CV over the lambda grid, batched across folds; refit at the min.

    Standardization statistics and the grid are computed once on the full
    data (so every fold sees the same grid, as cv.glmnet does); held-out MSE
    is measured in the centered space, which equals original-space MSE
    because the scaler is global.

    `fold_chunk` sets how many folds advance in vmap lockstep (must divide
    k); the default picks per PLACEMENT — all k when the folds are sharded
    over a multi-device mesh or on accelerator backends, 1 (a pure scan,
    still one executable) on a single CPU device, where lockstep loses
    (see `_auto_fold_chunk`). On the sharded path the knob applies PER
    DEVICE (each holds k/mesh.size folds); an explicit chunk the local
    fold block cannot honor exactly disables the mesh rather than being
    silently overridden.

    `mesh` places the stacked fold axis: "auto" resolves the innermost
    `dist.mesh_context`, else a data mesh over the visible devices, else
    single-device; a mesh whose size does not divide k falls back to
    single-device placement (results are identical either way — tested).
    """
    X = jnp.asarray(X)
    y = jnp.asarray(y, X.dtype)
    Xs, ys, scaler = api.standardize_fit(X, y, standardize=standardize,
                                         fit_intercept=fit_intercept)
    if lambda1s is None:
        lambda1s = api.lambda_grid(Xs, ys, n_lambdas=n_lambdas, eps=eps)
    lambda1s = jnp.asarray(lambda1s, X.dtype)
    lam2 = jnp.asarray(lambda2, X.dtype)

    n_tr = (Xs.shape[0] // k) * (k - 1)          # rows per training fold
    mesh = _resolve_cv_mesh(mesh, k, n_tr, Xs.shape[1],
                            points=int(lambda1s.shape[0]))
    explicit_chunk = fold_chunk is not None
    if fold_chunk is None:
        fold_chunk = _auto_fold_chunk(k, mesh)
    if k % fold_chunk:
        raise ValueError(f"cross_validate: fold_chunk={fold_chunk} must "
                         f"divide k={k}")
    chunk_local = fold_chunk
    if mesh is not None:
        # the lockstep knob applies PER DEVICE on the sharded path: each
        # device holds k/mesh.size folds, advanced `chunk_local` at a time.
        # An explicit chunk the local block cannot honor EXACTLY disables
        # the mesh (single-device placement) — never silently overridden;
        # the auto default simply takes the full local width (1 fold per
        # device => the plain un-vmapped loops).
        k_local = k // mesh.size
        if explicit_chunk:
            if fold_chunk <= k_local and k_local % fold_chunk == 0:
                chunk_local = fold_chunk
            else:
                mesh = None
        else:
            chunk_local = k_local
    config = api.resolve_path_config(config, Xs, ys)
    Xtr, ytr, Xva, yva = cv_folds(Xs, ys, k)
    if mesh is not None:
        Xtr, ytr, Xva, yva = _place_folds(mesh, Xtr, ytr, Xva, yva)
        mse, n_kept, evals, cg_steps, stop = _enet_cv_scan_sharded(
            Xtr, ytr, Xva, yva, lambda1s, lam2, config, chunk_local, mesh)
    else:
        mse, n_kept, evals, cg_steps, stop = _enet_cv_scan(
            Xtr, ytr, Xva, yva, lambda1s, lam2, config, fold_chunk)
    mean_mse = jnp.mean(mse, axis=1)
    i_min = int(jnp.argmin(mean_mse))
    lambda_min = float(lambda1s[i_min])

    _, pt = api._enet_jit(Xs, ys, jnp.asarray(lambda_min, X.dtype), lam2,
                          api.cold_carry(Xs, ys), config)
    beta, intercept = api.unscale_coef(pt.beta, scaler)
    return CVResult(lambda1s=lambda1s, lambda2=float(lambda2), mse_path=mse,
                    mean_mse=mean_mse, lambda_min=lambda_min, index_min=i_min,
                    beta=beta, intercept=intercept, n_kept=n_kept, evals=evals,
                    cg_steps=cg_steps, stop=stop)


def cross_validate_reference(X, y, *, k: int = 5, lambda1s=None,
                             n_lambdas: int = 40, eps: Optional[float] = None,
                             lambda2=1.0, standardize: bool = True,
                             fit_intercept: bool = True,
                             config: api.PathConfig = api.PathConfig()):
    """Sequential per-fold loop (cv.glmnet's shape): the batched CV's oracle.

    Same splits, same full-data grid and scaler; each fold runs its own
    `_enet_path_scan`. Returns (lambda1s, mse_path (L, k)).
    """
    X = jnp.asarray(X)
    y = jnp.asarray(y, X.dtype)
    Xs, ys, _ = api.standardize_fit(X, y, standardize=standardize,
                                    fit_intercept=fit_intercept)
    if lambda1s is None:
        lambda1s = api.lambda_grid(Xs, ys, n_lambdas=n_lambdas, eps=eps)
    lambda1s = jnp.asarray(lambda1s, X.dtype)
    lam2 = jnp.asarray(lambda2, X.dtype)

    Xtr, ytr, Xva, yva = cv_folds(Xs, ys, k)
    cols = []
    for i in range(k):
        pts = api._enet_path_scan(Xtr[i], ytr[i], lambda1s, lam2, config)
        resid = pts.beta @ Xva[i].T - yva[i][None, :]   # (L, fold)
        cols.append(jnp.mean(resid * resid, axis=1))
    return lambda1s, jnp.stack(cols, axis=1)


class ElasticNetCV:
    """sklearn-style K-fold CV estimator over the batched SVEN front-end.

    After `fit`: `coef_`, `intercept_`, `lambda_min_`, `lambda1s_`,
    `mse_path_` (L, k), `mean_mse_`.
    """

    def __init__(self, k: int = 5, n_lambdas: int = 40,
                 eps: Optional[float] = None, lambda2: float = 1.0, *,
                 standardize: bool = True, fit_intercept: bool = True,
                 mesh="auto", config: api.PathConfig = api.PathConfig()):
        self.k = k
        self.n_lambdas = n_lambdas
        self.eps = eps
        self.lambda2 = lambda2
        self.standardize = standardize
        self.fit_intercept = fit_intercept
        self.mesh = mesh
        self.config = config

    def fit(self, X, y):
        res = cross_validate(X, y, k=self.k, n_lambdas=self.n_lambdas,
                             eps=self.eps, lambda2=self.lambda2,
                             standardize=self.standardize,
                             fit_intercept=self.fit_intercept,
                             mesh=self.mesh, config=self.config)
        self.coef_ = res.beta
        self.intercept_ = res.intercept
        self.lambda_min_ = res.lambda_min
        self.lambda1s_ = res.lambda1s
        self.mse_path_ = res.mse_path
        self.mean_mse_ = res.mean_mse
        self.cv_result_ = res
        return self

    def predict(self, X):
        return jnp.asarray(X) @ self.coef_ + self.intercept_
