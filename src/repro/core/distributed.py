"""Distributed SVEN: the paper's solver on the production mesh.

The paper parallelizes the squared-hinge SVM on one GPU via BLAS; here the
same matrix-op structure shards over a TPU pod with shard_map:

  * features (the 2p constructed SVM samples <-> original p features) shard
    over the FLATTENED mesh (all axes) — at (16,16) that is 256-way feature
    parallelism;
  * the primal Newton-CG Hessian mat-vec needs, per iteration,
        c_loc = X_loc^T v        (local GEMV over the feature shard)
        d_loc = mask epilogue    (local)
        Hv    = psum(X_loc d_loc) + rank-1 terms   (ONE all-reduce of an
                n-vector per CG iteration)
  * the dual Gram build computes block-rows K_loc = Z_loc^T Z against an
    all-gathered Z panel (one all-gather of X per solve, amortized over all
    Newton iterations — the "kernel caching" regime of the paper).

Distribution-by-construction: every collective is explicit, so the dry-run
HLO for the sven_* cells shows exactly one psum per CG step + one gather per
Gram build (EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core.svm.primal_newton import solve_primal_newton


def _flat_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def feature_sharding(mesh: Mesh) -> NamedSharding:
    """X (n, p) with p sharded over every mesh axis."""
    return NamedSharding(mesh, P(None, _flat_axes(mesh)))


def dual_sample_sharding(mesh: Mesh) -> NamedSharding:
    """K (2p, 2p) row-sharded over the full mesh."""
    return NamedSharding(mesh, P(_flat_axes(mesh), None))


def distributed_gram(mesh: Mesh, X: jax.Array, y: jax.Array, t: float,
                     row_shard_out: bool = True) -> jax.Array:
    """K = Zhat^T Zhat (2p, 2p) with SAMPLES (n) sharded over the full mesh.

    The n >> p dual regime: each device reduces its sample shard
        G_loc = X_loc^T X_loc  (p,p),  u_loc = X_loc^T y_loc / t,
        s_loc = y_loc^T y_loc / t^2
    followed by ONE psum of (p^2 + p + 1) floats; the 4 block quadrants of K
    (the kernels/gram.py identity) assemble locally with zero additional
    communication. Contrast: the paper-faithful path would all-gather the
    (2p, n) constructed matrix — n/p times more wire bytes.
    """
    axes = _flat_axes(mesh)
    p = X.shape[1]

    def local(X_loc, y_loc):
        G = jax.lax.psum(X_loc.T @ X_loc, axes)                 # (p, p)
        u = jax.lax.psum((X_loc.T @ y_loc) / t, axes)           # (p,)
        s = jax.lax.psum((y_loc @ y_loc) / (t * t), axes)
        a = u[:, None]
        b = u[None, :]
        top = jnp.concatenate([G - a - b + s, -G - a + b + s], axis=1)
        bot = jnp.concatenate([-G + a - b + s, G + a + b + s], axis=1)
        K = jnp.concatenate([top, bot], axis=0)                 # (2p, 2p) replicated
        if row_shard_out:
            rank = jax.lax.axis_index(axes)
            n_dev = jax.lax.psum(1, axes)
            rows = (2 * p) // n_dev
            K = jax.lax.dynamic_slice_in_dim(K, rank * rows, rows, axis=0)
        return K

    out_spec = P(axes, None) if row_shard_out else P()
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes)),
        out_specs=out_spec,
        check_vma=False,
    )(X, y)


def distributed_gram_rs(mesh: Mesh, X: jax.Array, y: jax.Array, t: float) -> jax.Array:
    """Reduce-scatter Gram (§Perf iteration on distributed_gram).

    all-reduce(G) gives every device all of G (2(n-1)/n x p^2 wire) but a
    device only assembles its own K row block. psum_scatter hands device r
    just its p/n_dev G rows (half the wire, 1/n_dev the G memory); the K rows
    emitted are the feature-interleaved permutation [ +rows_r ; -rows_r ] —
    labels via interleaved_labels(), solvers are permutation-equivariant."""
    axes = _flat_axes(mesh)
    p = X.shape[1]

    def local(X_loc, y_loc):
        n_dev = jax.lax.psum(1, axes)
        G_part = X_loc.T @ X_loc                               # (p, p) partial
        G_rows = jax.lax.psum_scatter(G_part, axes, scatter_dimension=0,
                                      tiled=True)              # (p/n_dev, p)
        u = jax.lax.psum((X_loc.T @ y_loc) / t, axes)          # (p,)
        s = jax.lax.psum((y_loc @ y_loc) / (t * t), axes)
        rank = jax.lax.axis_index(axes)
        rows = p // n_dev
        u_loc = jax.lax.dynamic_slice_in_dim(u, rank * rows, rows)
        a = u_loc[:, None]
        b = u[None, :]
        top = jnp.concatenate([G_rows - a - b + s, -G_rows - a + b + s], axis=1)
        bot = jnp.concatenate([-G_rows + a - b + s, G_rows + a + b + s], axis=1)
        return jnp.concatenate([top, bot], axis=0)             # (2 p/n_dev, 2p)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axes, None), P(axes)),
                     out_specs=P(axes, None), check_vma=False)(X, y)


def distributed_gram_rs_syrk(mesh: Mesh, X: jax.Array, y: jax.Array, t: float) -> jax.Array:
    """distributed_gram_rs + level-1 SYRK blocking: G = X^T X is symmetric, so
    with X = [X1 X2] only (G11, G12, G22) are computed — 3/4 of the MACs; G21
    is a local transpose. (Recursive halving would approach 1/2.)"""
    axes = _flat_axes(mesh)
    p = X.shape[1]
    h = p // 2

    def local(X_loc, y_loc):
        n_dev = jax.lax.psum(1, axes)
        X1, X2 = X_loc[:, :h], X_loc[:, h:]
        G11 = X1.T @ X1
        G12 = X1.T @ X2
        G22 = X2.T @ X2
        G_part = jnp.concatenate([
            jnp.concatenate([G11, G12], axis=1),
            jnp.concatenate([G12.T, G22], axis=1)], axis=0)
        G_rows = jax.lax.psum_scatter(G_part, axes, scatter_dimension=0, tiled=True)
        u = jax.lax.psum((X_loc.T @ y_loc) / t, axes)
        s = jax.lax.psum((y_loc @ y_loc) / (t * t), axes)
        rank = jax.lax.axis_index(axes)
        rows = p // n_dev
        u_loc = jax.lax.dynamic_slice_in_dim(u, rank * rows, rows)
        a = u_loc[:, None]
        b = u[None, :]
        top = jnp.concatenate([G_rows - a - b + s, -G_rows - a + b + s], axis=1)
        bot = jnp.concatenate([-G_rows + a - b + s, G_rows + a + b + s], axis=1)
        return jnp.concatenate([top, bot], axis=0)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axes, None), P(axes)),
                     out_specs=P(axes, None), check_vma=False)(X, y)


def interleaved_labels(p: int, n_dev: int, dtype) -> jax.Array:
    """Labels matching distributed_gram_rs's row permutation."""
    rows = p // n_dev
    one = jnp.ones((rows,), dtype)
    return jnp.tile(jnp.concatenate([one, -one]), n_dev)


def distributed_gram_paper(mesh: Mesh, X: jax.Array, y: jax.Array, t: float) -> jax.Array:
    """PAPER-FAITHFUL baseline for the §Perf hillclimb: materialize the
    constructed (n_loc, 2p) matrix Zhat per sample shard (exactly what the
    MATLAB listing does before calling the SVM) and reduce K = psum(Z^T Z):
    4x the MACs and 2x the HBM reads of distributed_gram's block identity."""
    axes = _flat_axes(mesh)
    p = X.shape[1]

    def local(X_loc, y_loc):
        shift = (y_loc / t)[:, None]
        Z_loc = jnp.concatenate([X_loc - shift, -(X_loc + shift)], axis=1)  # (n_loc, 2p)
        return jax.lax.psum(Z_loc.T @ Z_loc, axes)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axes, None), P(axes)),
                     out_specs=P(), check_vma=False)(X, y)


def make_distributed_hessian_matvec(mesh: Mesh, X: jax.Array, y: jax.Array,
                                    t: float, C: float):
    """Primal-mode H v mat-vec with ONE psum per call.

    v (n,) replicated; features sharded. act masks (2p,) live feature-sharded
    as (act_top_loc, act_bot_loc). Returns a closure for solve_primal_newton's
    hess_matvec hook (act supplied per Newton iteration, replicated (2p,) in
    shard order)."""
    axes = _flat_axes(mesh)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    p = X.shape[1]
    p_loc = p // n_dev

    def local(X_loc, y_full, act, v, C_op):
        rank = jax.lax.axis_index(axes)
        a_t = jax.lax.dynamic_slice_in_dim(act, rank * p_loc, p_loc)
        a_b = jax.lax.dynamic_slice_in_dim(act, p + rank * p_loc, p_loc)
        c = X_loc.T @ v                                   # (p_loc,)
        byv = (y_full @ v) / t                            # scalar (replicated)
        u_t = a_t * (c - byv)
        u_b = a_b * (c + byv)
        d = u_t + u_b
        e_loc = jnp.sum(u_b) - jnp.sum(u_t)
        partial_hv = X_loc @ d + (y_full / t) * e_loc     # (n,)
        hv = jax.lax.psum(partial_hv, axes)               # ONE all-reduce
        return v + 2.0 * C_op * hv

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(None, axes), P(), P(), P(), P()),
                   out_specs=P(), check_vma=False)

    def hess_matvec(v, act, C_traced=None):
        C_op = C if C_traced is None else C_traced
        return fn(X, y, act.astype(v.dtype), v, jnp.asarray(C_op, v.dtype))

    return hess_matvec


# ---------------------------------------------------------------------------
# Data-parallel SVEN (DESIGN.md §9): rows of Zhat sharded over the mesh
# ---------------------------------------------------------------------------
#
# Zhat (n, 2p) is the label-scaled dual data matrix; its rows are the
# ORIGINAL samples, so row-sharding Zhat == row-sharding X — plain data
# parallelism. Every solver product then reduces to local O(n_loc p) work
# plus one small collective:
#
#     dual   K = Zhat^T Zhat       one psum of (G, u, s): p^2 + p + 1 floats
#                                  per SOLVE (kernel caching regime); the
#                                  projected-Newton solver runs replicated
#                                  on the assembled (2p, 2p) kernel.
#     primal Xhat @ w              one psum of (p + 1) floats per product
#            Xhat^T v              one all-gather of an n-vector
#
# Rows pad with ZEROS to a multiple of the mesh size — a zero sample with a
# zero response adds nothing to the Elastic Net objective, to any Gram
# statistic, or to any matvec (the serve/engine.py padding argument), so
# padded parity is exact, not approximate.


def pad_rows(X: jax.Array, y: jax.Array, n_dev: int):
    """Zero-row pad (X, y) to a row count divisible by `n_dev` (exact)."""
    rem = (-X.shape[0]) % n_dev
    if rem == 0:
        return X, y
    return jnp.pad(X, ((0, rem), (0, 0))), jnp.pad(y, ((0, rem),))


def shard_rows(mesh: Mesh, X: jax.Array, y: jax.Array):
    """Place (X, y) row-sharded over the flattened mesh (zero-row padded)."""
    axes = _flat_axes(mesh)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    Xp, yp = pad_rows(X, y, n_dev)
    Xs = jax.device_put(Xp, NamedSharding(mesh, P(axes, None)))
    ys = jax.device_put(yp, NamedSharding(mesh, P(axes)))
    return Xs, ys


@partial(jax.jit, static_argnames=("mesh",))
def sharded_stats(X, y, t, *, mesh: Mesh):
    """The ONE collective of the sharded dual solve, as its own executable:
    psum-reduced sufficient statistics (G = X^T X, u = X^T y / t,
    s = y^T y / t^2) of a row-sharded (X, y).

    Launched separately from the solve program ON PURPOSE: under JAX async
    dispatch the returned arrays are futures, so the device runs the
    all-reduce while the host traces/launches the (much larger) replicated
    Newton program that consumes them — the stats reduction overlaps the
    solver setup instead of serializing in front of it. Same op order per
    shard as `reduction.gram_blocks`'s inputs, so a 1-device mesh
    reproduces the single-device statistics bitwise.
    """
    from repro.core.sven import _bump_trace

    _bump_trace("sven_sharded_stats")
    axes = _flat_axes(mesh)

    def local(X_loc, y_loc, t_op):
        G = jax.lax.psum(X_loc.T @ X_loc, axes)
        u = jax.lax.psum(X_loc.T @ y_loc, axes) / t_op
        s = jax.lax.psum(y_loc @ y_loc, axes) / (t_op * t_op)
        return G, u, s

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axes, None), P(axes), P()),
                     out_specs=(P(), P(), P()), check_vma=False)(
                         X, y, jnp.asarray(t, X.dtype))


def sharded_gram_stats(mesh: Mesh, X: jax.Array, y: jax.Array, t) -> jax.Array:
    """K = Zhat^T Zhat from psum-reduced (G, u, s) statistics — the
    data-parallel twin of `reduction.gram_blocks` (same op order per shard,
    so a 1-device mesh reproduces the single-device kernel bitwise).

    Composition of the async `sharded_stats` launch and the replicated
    4-block assembly; callers that want the overlap harvest the stats
    futures inside their own program instead (`_sven_sharded_dual_jit`).
    """
    from repro.core import reduction as red

    G, u, s = sharded_stats(X, y, t, mesh=mesh)
    return red.gram_from_stats(G, u, s)


def _sven_sharded_primal(mesh: Mesh, X, y, t, C, warm_w, config):
    """Whole primal Newton-CG solve inside ONE shard_map region: w (n,)
    replicated, X rows sharded; each Xhat product costs one psum(p + 1),
    each Xhat^T product one all-gather of an n-vector."""
    from repro.core import reduction as red

    axes = _flat_axes(mesh)
    n, p = X.shape
    dtype = X.dtype
    yhat = jnp.concatenate([jnp.ones((p,), dtype), -jnp.ones((p,), dtype)])

    def local(X_loc, y_loc, t_op, C_op, w0):
        n_loc = X_loc.shape[0]
        rank = jax.lax.axis_index(axes)

        def matvec(w):                       # Xhat @ w -> (2p,) replicated
            w_loc = jax.lax.dynamic_slice_in_dim(w, rank * n_loc, n_loc)
            ab = jax.lax.psum(jnp.concatenate([X_loc.T @ w_loc,
                                               (y_loc @ w_loc)[None]]), axes)
            a, b = ab[:p], ab[p] / t_op
            return jnp.concatenate([a - b, a + b])

        def rmatvec(v):                      # Xhat^T v -> (n,) replicated
            vt, vb = v[:p], v[p:]
            out_loc = (X_loc @ (vt + vb)
                       + (y_loc / t_op) * (jnp.sum(vb) - jnp.sum(vt)))
            return jax.lax.all_gather(out_loc, axes, tiled=True)

        res = solve_primal_newton(matvec, rmatvec, yhat, C_op, n,
                                  tol=config.tol, max_newton=config.max_newton,
                                  cg_iters=config.cg_iters, w0=w0)
        alpha = C_op * jnp.maximum(1.0 - yhat * matvec(res.w), 0.0)
        beta = red.recover_beta(alpha, t_op)
        return (beta, alpha, res.w, res.iters, res.grad_norm, res.cg_steps,
                res.stalled)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axes, None), P(axes), P(), P(), P()),
                     out_specs=(P(),) * 7, check_vma=False)(
                         X, y, jnp.asarray(t, dtype), jnp.asarray(C, dtype),
                         warm_w)


@partial(jax.jit, static_argnames=("n_orig", "config"))
def _sven_sharded_dual_jit(stats, K, X, y, t, lambda2, warm_alpha, *,
                           n_orig: int, config):
    """Replicated dual solve consuming the async stats/K launch.

    Exactly one of `stats` (the (G, u, s) futures from `sharded_stats`) and
    `K` (the Pallas `sharded_shifted_gram` future) is non-None — harvested
    at first use, so by the time the device reaches the kernel assembly the
    overlapped reduction has usually already landed. Everything here is
    global ops: the partitioner keeps X's rows sharded for the w-recovery
    and KKT contractions (one all-reduce each), the Newton solve itself is
    replicated — no shard_map, hence no static mesh in the jit key.
    """
    from repro.core import elastic_net as en
    from repro.core import reduction as red
    from repro.core.svm import solve_dual_fista, solve_dual_newton
    from repro.core.sven import SvenArrays, _bump_trace

    _bump_trace("sven_sharded")
    p = X.shape[1]
    dtype = X.dtype
    C = red.svm_C(lambda2, floor=config.lambda2_floor).astype(dtype)
    kernel_K = K is not None          # static: pytree structure keys the jit
    if K is None:
        K = red.gram_from_stats(*stats)
    solver = (solve_dual_newton if config.solver == "newton"
              else solve_dual_fista)
    res = solver(lambda v: K @ v, 2 * p, C, dtype=dtype, tol=config.tol,
                 alpha0=warm_alpha)
    cg_steps = res.cg_steps
    if kernel_K and config.precision != "f32":
        # iterative refinement, sharded flavor (DESIGN.md §10.3): re-solve
        # matrix-free at full precision from the low-precision alpha. All
        # global ops — the partitioner keeps X's rows sharded and inserts
        # the same one-psum-per-product collectives as the stats path.
        res = solver(red.SvenOperator(X=X, y=y, t=t).kernel_matvec, 2 * p, C,
                     dtype=dtype, tol=config.tol, alpha0=res.alpha)
        cg_steps = cg_steps + res.cg_steps
    beta = red.recover_beta(res.alpha, t)
    # w = Zhat @ alpha on the row-sharded X: global ops, the partitioner
    # keeps the row dimension sharded and gathers the (n,) result.
    w = red.SvenOperator(X=X, y=y, t=t).zhat_matvec(res.alpha)
    kkt = en.kkt_violation(X, y, beta, lambda2)
    return SvenArrays(beta=beta, alpha=res.alpha, w=w[:n_orig],
                      iters=res.iters, opt_residual=res.pg_norm, kkt=kkt,
                      cg_steps=cg_steps, stalled=jnp.zeros((), bool))


@partial(jax.jit, static_argnames=("mesh", "n_orig", "config"))
def _sven_sharded_primal_jit(X, y, t, lambda2, warm_w, *, mesh: Mesh,
                             n_orig: int, config):
    from repro.core import elastic_net as en
    from repro.core import reduction as red
    from repro.core.sven import SvenArrays, _bump_trace

    _bump_trace("sven_sharded")
    dtype = X.dtype
    C = red.svm_C(lambda2, floor=config.lambda2_floor).astype(dtype)
    beta, alpha, w, iters, opt, cg_steps, stalled = _sven_sharded_primal(
        mesh, X, y, t, C, warm_w, config)
    # KKT diagnostics on the (padded == original) problem; rows stay sharded
    # under the partitioner, one all-reduce for the X^T r contraction.
    kkt = en.kkt_violation(X, y, beta, lambda2)
    return SvenArrays(beta=beta, alpha=alpha, w=w[:n_orig], iters=iters,
                      opt_residual=opt, kkt=kkt, cg_steps=cg_steps,
                      stalled=stalled)


def sven_sharded(X: jax.Array, y: jax.Array, t, lambda2, config=None, *,
                 mesh: Optional[Mesh] = None, warm_alpha=None, warm_w=None):
    """Data-parallel `sven()`: rows sharded over the mesh, same answers.

    The production multi-device solve path (DESIGN.md §9): X's rows (==
    Zhat's rows) are zero-padded to the mesh size and sharded over every
    mesh axis; the dual path assembles the kernel from one psum of its
    sufficient statistics, the primal path runs the whole Newton-CG machine
    inside one shard_map region with one psum + one all-gather per product.
    Parity with single-device `sven()` is exact to solver tolerance
    (<= 1e-10 tested on 8 forced host devices), and a 1-device mesh
    reproduces it bitwise.

    `mesh=None` resolves the innermost `dist.mesh_context`, then falls back
    to `dist.data_mesh()` over all visible devices — on a single-device
    process that is a 1-device mesh, i.e. the single-device path.

    This is the PINNED sharded layout: it always runs row-sharded on the
    resolved mesh. `core.routing.sven_routed` is the entry point that
    consults the cost model first and only comes here when sharding wins.
    """
    from repro import dist
    from repro.core.sven import (SvenConfig, SvenSolution, _pick_mode,
                                 resolve_backend)

    config = SvenConfig() if config is None else config
    X = jnp.asarray(X)
    y = jnp.asarray(y, X.dtype)
    n, p = X.shape
    if mesh is None:
        ctx = dist.current_context()
        mesh = ctx[0] if ctx is not None else dist.data_mesh()
    mode = _pick_mode(n, p, config)
    Xs, ys = shard_rows(mesh, X, y)
    config = resolve_backend(config, Xs, ys)
    dtype = X.dtype
    t_op = jnp.asarray(t, dtype)
    l2_op = jnp.asarray(lambda2, dtype)
    if mode == "dual":
        # Launch the one-psum stats reduction (or the Pallas Gram kernel)
        # as its OWN async program, then hand its output futures to the
        # replicated solve program — the device reduces while the host
        # traces/dispatches the Newton setup (collective/compute overlap).
        stats = K = None
        if config.backend != "xla":
            from repro.kernels.ops import sharded_shifted_gram
            K = sharded_shifted_gram(
                mesh, Xs.astype(jnp.float32), ys.astype(jnp.float32),
                jnp.asarray(t, jnp.float32), backend=config.backend,
                precision=config.precision).astype(dtype)
        else:
            stats = sharded_stats(Xs, ys, t_op, mesh=mesh)
        wa = (jnp.zeros((2 * p,), dtype) if warm_alpha is None
              else jnp.asarray(warm_alpha, dtype))
        arrs = _sven_sharded_dual_jit(stats, K, Xs, ys, t_op, l2_op, wa,
                                      n_orig=n, config=config)
    else:
        ww = (jnp.zeros((Xs.shape[0],), dtype) if warm_w is None
              else jnp.pad(jnp.asarray(warm_w, dtype),
                           ((0, Xs.shape[0] - n),)))
        arrs = _sven_sharded_primal_jit(Xs, ys, t_op, l2_op, ww, mesh=mesh,
                                        n_orig=n, config=config)
    return SvenSolution(beta=arrs.beta, alpha=arrs.alpha, mode=mode,
                        iters=arrs.iters, opt_residual=arrs.opt_residual,
                        kkt=arrs.kkt, w=arrs.w)


def sven_primal_distributed(mesh: Mesh, X: jax.Array, y: jax.Array, t: float,
                            lambda2: float, *, tol: float = 1e-8,
                            max_newton: int = 40, cg_iters: int = 200):
    """Full distributed primal SVEN solve; beta via Algorithm 1 recovery.

    Note: the act-mask layout here is the canonical [all +, all -] ordering —
    the gradient/margin path computes on the replicated implicit operator
    while the O(np) Hessian mat-vecs (the hot loop) run feature-sharded."""
    from repro.core.reduction import SvenOperator, recover_beta, svm_C

    n, p = X.shape
    C = svm_C(lambda2).astype(X.dtype)
    op = SvenOperator(X=X, y=y, t=t)
    yhat = jnp.concatenate([jnp.ones((p,), X.dtype), -jnp.ones((p,), X.dtype)])
    hess = make_distributed_hessian_matvec(mesh, X, y, t, C)
    res = solve_primal_newton(op.xhat_matvec, op.xhat_rmatvec, yhat, C, n,
                              tol=tol, max_newton=max_newton, cg_iters=cg_iters,
                              hess_matvec=hess)
    alpha = C * jnp.maximum(1.0 - yhat * op.xhat_matvec(res.w), 0.0)
    return recover_beta(alpha, t), res
