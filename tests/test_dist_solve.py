"""ISSUE 5 + ISSUE 6: the sharded solve path, cost-model routing, and
their bugfix satellites.

Single-process tests cover the 1-device-mesh bitwise-parity contract of
`sven_sharded` and `sven_routed`, the router's trivial/pinned semantics,
the CV fold-chunk keying on RESOLVED placement (the nested-context
regression), the explicit kernel backend threading (the `_on_cpu()`
trace-time sniffing regression), the SolutionCache lambda-edge
keying (lasso-only / pure-ridge repeat traffic) and the lambda1 = 0
screening guard. Real multi-device behavior — cross-device parity for
sven / sven_routed / enet_path / CV at <= 1e-10, the routing decision
table never pricing the chosen path above single-device, and the property
that bucket placement never reorders results across device counts 1/2/8 —
runs in subprocesses with forced host devices, so this test session keeps
its real device set.
"""
import json
import math
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subprocess import run_python
from repro import dist
from repro.core import cross_validate, sven, sven_routed, sven_sharded
from repro.core.api import enet
from repro.core.routing import route_batch, route_solve
from repro.core.screening import gap_safe_screen
from repro.core.sven import SvenConfig, resolve_backend, trace_counts
from repro.data.synthetic import make_regression
from repro.kernels.ops import resolve_interpret
from repro.runtime.cache import _log_distance
from repro.runtime.scheduler import ContinuousScheduler


# ---------------------------------------------------------------------------
# kernel backend-selection threading (bugfix: trace-time _on_cpu sniffing)
# ---------------------------------------------------------------------------

def test_resolve_interpret_explicit_wins():
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # explicit beats whatever the operands say
    x = jnp.ones((4,))
    assert resolve_interpret(False, x) is False


def test_resolve_interpret_from_committed_device():
    x = jax.device_put(jnp.ones((4,)), jax.devices("cpu")[0])
    assert resolve_interpret(None, x) is True
    # numpy operands carry no device: process default backend fallback
    assert resolve_interpret(None, np.ones(4)) == (
        jax.default_backend() == "cpu")


def test_resolve_backend_pins_enum_into_config():
    X, y, _ = make_regression(24, 10, seed=0)
    resolved = resolve_backend(SvenConfig(backend="auto"), X, y)
    # CPU-committed operands -> the TPU body under interpret mode, as ONE
    # resolved enum value, equal to the config that names it outright
    assert resolved.backend == "tpu_interpret"
    assert resolved == SvenConfig(backend="tpu_interpret")
    # xla configs are untouched (identity object: resolve_path_config
    # depends on the no-op returning the SAME config)
    plain = SvenConfig()
    assert resolve_backend(plain, X, y) is plain
    # already-resolved configs are identity too
    pinned = SvenConfig(backend="ref")
    assert resolve_backend(pinned, X, y) is pinned


def test_sven_pallas_threading_no_retrace_and_parity():
    """An "auto" config and the explicitly-resolved one must hit the SAME
    executable (the resolution happens before the jit key is formed), and
    agree with the xla backend."""
    X, y, _ = make_regression(96, 16, seed=1)  # dual regime (2p < n)
    X, y = X.astype(jnp.float64), y.astype(jnp.float64)
    base = sven(X, y, 1.1, 1.0)
    n0 = trace_counts().get("sven", 0)
    s_auto = sven(X, y, 1.1, 1.0, SvenConfig(backend="auto"))
    n1 = trace_counts().get("sven", 0)
    s_expl = sven(X, y, 1.1, 1.0, SvenConfig(backend="tpu_interpret"))
    n2 = trace_counts().get("sven", 0)
    assert n1 == n0 + 1
    assert n2 == n1, "explicit tpu_interpret retraced: resolution did not " \
                     "pin the choice into the jit key"
    # pallas gram runs in f32; parity at f32 tolerance
    np.testing.assert_allclose(np.asarray(s_auto.beta), np.asarray(base.beta),
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(s_expl.beta),
                               np.asarray(s_auto.beta), atol=0)


# ---------------------------------------------------------------------------
# SolutionCache lambda-edge keying (bugfix)
# ---------------------------------------------------------------------------

def test_log_distance_edges():
    assert _log_distance(0.0, 0.0) == 0.0
    assert _log_distance(0.0, 1e-3) == math.inf
    assert _log_distance(2.0, 0.0) == math.inf
    # exact on the positive axis — no eps-floor distortion: 1e-13 vs 1e-14
    # are an e-fold-sized decade apart, not "adjacent"
    assert abs(_log_distance(1e-13, 1e-14) - math.log(10.0)) < 1e-12
    assert _log_distance(3.0, 3.0) == 0.0


def test_cache_lasso_repeat_traffic_warm_hits():
    """Lasso-only (lambda2 = 0) repeat traffic must warm-start itself."""
    X, y, _ = make_regression(40, 12, seed=3)
    X, y = np.asarray(X), np.asarray(y)
    direct = enet(X, y, 0.5, 0.0).beta
    sched = ContinuousScheduler(max_batch=4, max_wait=None)
    first = [sched.submit(X, y, lambda1=0.5, lambda2=0.0) for _ in range(4)]
    sched.drain()
    assert sched.cache.hits == 0
    again = [sched.submit(X, y, lambda1=0.5, lambda2=0.0) for _ in range(4)]
    out = sched.drain()
    assert sched.cache.hits == len(again), "lasso repeats missed the cache"
    for rid in again:
        np.testing.assert_allclose(np.asarray(out[rid].beta[:12]),
                                   np.asarray(direct), atol=1e-8)
    # constrained-form lasso repeats hit too
    t = float(jnp.sum(jnp.abs(direct)))
    sched.submit(X, y, t=t, lambda2=0.0)
    sched.drain()
    rid = sched.submit(X, y, t=t, lambda2=0.0)
    out = sched.drain()
    assert sched.cache.hits > len(again)
    np.testing.assert_allclose(np.asarray(out[rid].beta[:12]),
                               np.asarray(sven(X, y, t, 0.0).beta), atol=1e-8)


def test_cache_pure_ridge_lambda1_zero():
    """lambda1 = 0 (pure ridge) is admissible, solves to the ridge solution
    and repeat traffic warm-hits — no log(0) anywhere in the key."""
    X, y, _ = make_regression(40, 12, seed=4)
    X, y = np.asarray(X), np.asarray(y)
    b_ridge = jnp.linalg.solve(X.T @ X + 1.5 * jnp.eye(12), X.T @ y)
    sched = ContinuousScheduler(max_batch=2, max_wait=None)
    sched.submit(X, y, lambda1=0.0, lambda2=1.5)
    sched.drain()
    rid = sched.submit(X, y, lambda1=0.0, lambda2=1.5)
    out = sched.drain()
    assert sched.cache.hits >= 1
    np.testing.assert_allclose(np.asarray(out[rid].beta[:12]),
                               np.asarray(b_ridge), atol=1e-5)
    # a ridge entry must NOT answer a nearby-but-penalized request's key as
    # "adjacent" purely through an eps floor; a positive lambda1 is a
    # different axis point with finite distance, lambda1=0 is its own point
    assert _log_distance(0.0, 1e-9) == math.inf


def test_screen_keeps_everything_at_lambda1_zero():
    X, y, _ = make_regression(30, 8, seed=5)
    scr = gap_safe_screen(X, y, jnp.zeros((8,)), 0.0, 1.0)
    assert bool(jnp.all(scr.keep)), "lambda1=0 screen must discard nothing"
    assert bool(jnp.isfinite(scr.gap))
    r = enet(X, y, 0.0, 1.5)
    b_ridge = jnp.linalg.solve(X.T @ X + 1.5 * jnp.eye(8), X.T @ y)
    np.testing.assert_allclose(np.asarray(r.beta), np.asarray(b_ridge),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# sharded solve path: 1-device-mesh contract (multi-device in subprocess)
# ---------------------------------------------------------------------------

def test_sven_sharded_one_device_mesh_matches_sven():
    X, y, _ = make_regression(100, 24, seed=0)    # dual; 100 % 1 == 0
    s0 = sven(X, y, 1.5, 1.0)
    s1 = sven_sharded(X, y, 1.5, 1.0, mesh=dist.data_mesh(1))
    np.testing.assert_allclose(np.asarray(s1.beta), np.asarray(s0.beta),
                               atol=1e-12)
    X2, y2, _ = make_regression(50, 64, seed=1)   # primal, row padding
    p0 = sven(X2, y2, 0.8, 0.7)
    p1 = sven_sharded(X2, y2, 0.8, 0.7, mesh=dist.data_mesh(1))
    assert p1.mode == p0.mode == "primal"
    np.testing.assert_allclose(np.asarray(p1.beta), np.asarray(p0.beta),
                               atol=1e-12)


def test_batch_mesh_graceful_fallback():
    from repro.core.batch import batch_mesh
    assert batch_mesh(8) is None                  # no context
    with dist.mesh_context(dist.data_mesh(1)):
        assert batch_mesh(8) is None              # 1-device mesh
    # a mesh that does not divide the batch falls back too (subprocess runs
    # exercise the >1-device divide case)
    mesh = dist.data_mesh(jax.device_count())
    if mesh.size > 1:
        with dist.mesh_context(mesh):
            assert batch_mesh(mesh.size + 1) is None


# ---------------------------------------------------------------------------
# cost-model routing (core/routing.py): in-process contracts; the >1-device
# decision table runs in subprocesses below
# ---------------------------------------------------------------------------

def test_route_one_device_trivial_and_validation():
    d = route_solve(100, 24, mesh=dist.data_mesh(1))
    assert d.path == "single" and d.costs == {"single": 0.0}
    d = route_batch(48, 12, 8, dist.data_mesh(1), form="penalized")
    assert d.path == "single"
    with pytest.raises(ValueError, match="route must be"):
        route_solve(100, 24, route="fastest")
    with pytest.raises(ValueError, match="route must be"):
        route_batch(100, 24, 8, route="sharded")


def test_sven_routed_one_device_matches_sven_bitwise():
    """On a 1-device mesh every route pin degenerates to plain `sven` (the
    same executable), so parity is bitwise, not approximate."""
    X, y, _ = make_regression(100, 24, seed=0)
    s0 = sven(X, y, 1.5, 1.0)
    for route in ("auto", "single", "sharded"):
        s1 = sven_routed(X, y, 1.5, 1.0, mesh=dist.data_mesh(1), route=route)
        np.testing.assert_array_equal(np.asarray(s1.beta),
                                      np.asarray(s0.beta))


def test_auto_fold_chunk_keys_on_resolved_placement():
    """Regression (ISSUE 6 satellite): the lockstep width keys on where the
    folds are PLACED, never on process-global device counts."""
    from repro.core.cv import _auto_fold_chunk
    if jax.default_backend() == "cpu":
        assert _auto_fold_chunk(8, None) == 1
        assert _auto_fold_chunk(8, dist.data_mesh(1)) == 1
    mesh = dist.data_mesh(jax.device_count())
    if mesh.size > 1:
        assert _auto_fold_chunk(8, mesh) == 8


def test_cv_auto_mesh_inside_one_device_context():
    """The nested-context case: an outer 1-device `mesh_context` with
    mesh="auto" must resolve to single-device placement (chunk keyed on the
    RESOLVED mesh, not on the context's existence) and match the
    no-context run exactly."""
    X, y, _ = make_regression(40, 8, seed=6)
    cv0 = cross_validate(X, y, k=4, n_lambdas=5, mesh=None)
    with dist.mesh_context(dist.data_mesh(1)):
        cv1 = cross_validate(X, y, k=4, n_lambdas=5, mesh="auto")
    np.testing.assert_allclose(np.asarray(cv1.mse_path),
                               np.asarray(cv0.mse_path), atol=1e-12)
    assert cv1.lambda_min == cv0.lambda_min


# ---------------------------------------------------------------------------
# real multi-device runs (subprocess with forced host devices)
# ---------------------------------------------------------------------------

_PARITY_8DEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_enable_x64", True)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import dist
    from repro.core import cross_validate, sven, sven_batch, sven_sharded
    from repro.core.api import enet_batch, enet_path
    from repro.core.sven import SvenConfig
    from repro.data.synthetic import make_regression

    TOL = 1e-10
    mesh = dist.data_mesh()
    assert mesh.size == 8

    # 1) sven_sharded parity, dual (with row padding) and primal regimes
    X, y, _ = make_regression(100, 24, seed=0)
    d = float(jnp.abs(sven_sharded(X, y, 1.5, 1.0, mesh=mesh).beta
                      - sven(X, y, 1.5, 1.0).beta).max())
    assert d <= TOL, f"dual sharded dev {d}"
    Xp, yp, _ = make_regression(50, 64, seed=1)
    d = float(jnp.abs(sven_sharded(Xp, yp, 0.8, 0.7, mesh=mesh).beta
                      - sven(Xp, yp, 0.8, 0.7).beta).max())
    assert d <= TOL, f"primal sharded dev {d}"
    # pallas-backed sharded gram (interpret pinned outside the shard_map)
    cfg = SvenConfig(backend="auto")
    s3 = sven_sharded(X, y, 1.5, 1.0, cfg, mesh=mesh)
    d = float(jnp.abs(s3.beta - sven(X, y, 1.5, 1.0).beta).max())
    assert d <= 5e-5, f"pallas sharded dev {d}"     # f32 kernel
    print("sven_sharded8 OK")

    # 2) batch-axis sharding: stacked solves, order MUST be preserved
    B = 8
    Xb = jnp.stack([make_regression(48, 12, seed=10 + i)[0] for i in range(B)])
    yb = jnp.stack([make_regression(48, 12, seed=10 + i)[1] for i in range(B)])
    tb = jnp.linspace(0.7, 1.8, B)
    l2b = jnp.linspace(0.5, 2.0, B)
    plain = sven_batch(Xb, yb, tb, l2b)
    with dist.mesh_context(mesh):
        sharded = sven_batch(Xb, yb, tb, l2b)
    d = float(jnp.abs(sharded.beta - plain.beta).max())
    assert d <= TOL, f"sven_batch sharded dev {d}"
    # the fan-out pinned, where the router may decline it on host devices
    with dist.mesh_context(mesh):
        pinned = sven_batch(Xb, yb, tb, l2b, route="batch")
    d = float(jnp.abs(pinned.beta - plain.beta).max())
    assert d <= TOL, f"sven_batch pinned fan-out dev {d}"
    lam1 = jnp.linspace(0.8, 0.2, B)
    pl = enet_batch(Xb, yb, lam1, l2b)
    with dist.mesh_context(mesh):
        sh = enet_batch(Xb, yb, lam1, l2b)
    d = float(jnp.abs(sh.beta - pl.beta).max())
    assert d <= TOL, f"enet_batch sharded dev {d}"
    print("batch8 OK")

    # 3) enet_path with row-sharded X (partitioner-driven data parallelism)
    Xe, ye, _ = make_regression(64, 16, seed=2)
    path0 = enet_path(Xe, ye, n_lambdas=8, lambda2=1.0)
    Xs = jax.device_put(Xe, NamedSharding(mesh, P("data", None)))
    ys = jax.device_put(ye, NamedSharding(mesh, P("data")))
    path1 = enet_path(Xs, ys, n_lambdas=8, lambda2=1.0)
    d = float(jnp.abs(path1.betas - path0.betas).max())
    assert d <= TOL, f"enet_path sharded dev {d}"
    print("enet_path8 OK")

    # 4) device-parallel CV (k = 8 folds -> one per device) vs single-device
    Xc, yc, _ = make_regression(64, 10, seed=3)
    cv1 = cross_validate(Xc, yc, k=8, n_lambdas=6, mesh=mesh)
    cv0 = cross_validate(Xc, yc, k=8, n_lambdas=6, mesh=None)
    d = float(jnp.abs(cv1.mse_path - cv0.mse_path).max())
    assert d <= TOL, f"cv sharded mse dev {d}"
    assert cv1.lambda_min == cv0.lambda_min
    print("cv8 OK")

    # 4b) nested context with k = 6 NOT divisible by the 8-device mesh:
    # auto resolution must decline the mesh (resolved placement = single
    # device) and return the no-context answer exactly
    with dist.mesh_context(mesh):
        cv6a = cross_validate(Xc, yc, k=6, n_lambdas=6, mesh="auto")
    cv6b = cross_validate(Xc, yc, k=6, n_lambdas=6, mesh=None)
    d = float(jnp.abs(cv6a.mse_path - cv6b.mse_path).max())
    assert d <= TOL, f"cv nested-context dev {d}"
    assert cv6a.lambda_min == cv6b.lambda_min
    print("cv_nested8 OK")

    # 5) routed solves (ISSUE 6): every route pin — including the forced
    # sharded layout — matches the single-device answer in both regimes
    from repro.core.routing import route_solve, sven_routed
    for route in ("auto", "single", "sharded"):
        d = float(jnp.abs(
            sven_routed(X, y, 1.5, 1.0, mesh=mesh, route=route).beta
            - sven(X, y, 1.5, 1.0).beta).max())
        assert d <= TOL, f"routed({route}) dual dev {d}"
        d = float(jnp.abs(
            sven_routed(Xp, yp, 0.8, 0.7, mesh=mesh, route=route).beta
            - sven(Xp, yp, 0.8, 0.7).beta).max())
        assert d <= TOL, f"routed({route}) primal dev {d}"
    dec = route_solve(100, 24, mesh=mesh)
    assert dec.costs[dec.path] <= dec.costs["single"] + 1e-12
    print("routed8 OK")
""")


def test_multidevice_parity_subprocess():
    r = run_python(snippet=_PARITY_8DEV, timeout=900)
    for tag in ("sven_sharded8", "batch8", "enet_path8", "cv8",
                "cv_nested8", "routed8"):
        assert f"{tag} OK" in r.stdout


_ROUTING_DECISIONS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(dc)d"
    import sys; sys.path.insert(0, "src")
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro import dist
    from repro.core.routing import calibrate, route_batch, route_solve

    mesh = dist.data_mesh()
    assert mesh.size == %(dc)d
    cal = calibrate(mesh)
    assert cal.flops_per_s > 0 and cal.psum_latency_s >= 0.0
    assert cal.fanout_speedup > 0 and cal.replicated_slowdown > 0

    EPS = 1e-12
    for n, p in [(64, 8), (256, 16), (768, 48), (4096, 16), (32768, 8),
                 (50, 64)]:
        d = route_solve(n, p, mesh=mesh)
        assert d.path in d.costs, (n, p, d)
        assert d.costs[d.path] <= d.costs["single"] + EPS, (n, p, d)
        # pins are honored while still reporting the model's prices
        assert route_solve(n, p, mesh=mesh, route="single").path == "single"
        s = route_solve(n, p, mesh=mesh, route="sharded")
        assert s.path == "sharded" and "sharded" in s.costs
    for n, p, B in [(48, 12, %(dc)d), (256, 16, 2 * %(dc)d), (64, 10, 64)]:
        d = route_batch(n, p, B, mesh, form="penalized", points=8)
        assert d.costs[d.path] <= d.costs["single"] + EPS, (n, p, B, d)
        assert route_batch(n, p, B, mesh, route="batch").path == "batch"
    # the regression shape: a tiny lone solve must stay single-device —
    # collective latency + multi-device dispatch can never pay for 64x8
    assert route_solve(64, 8, mesh=mesh).path == "single"
    print("ROUTING OK")
""")


def test_routing_decisions_never_price_worse_than_single():
    """Property (ISSUE 6 satellite): on 2 and 8 devices, across dual/primal
    shapes and batch sizes, the router never picks a path the calibrated
    cost model prices above single-device, pinned routes are honored, and
    the tiny-lone-solve regression shape always routes single. (The
    1-device table is trivial and covered in-process above.)"""
    for dc in (2, 8):
        r = run_python(snippet=_ROUTING_DECISIONS % {"dc": dc}, timeout=900)
        assert "ROUTING OK" in r.stdout, f"dc={dc}:\n{r.stdout}"


_BUCKET_ORDER = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(dc)d"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_enable_x64", True)
    from repro.runtime import ContinuousScheduler, LoadSpec, make_workload

    assert jax.device_count() == %(dc)d
    sched = ContinuousScheduler(max_batch=4, max_wait=None, cache=None)
    spec = LoadSpec(n_requests=12, n_datasets=2, shapes=((24, 10), (32, 14)),
                    penalized_fraction=0.5, seed=11)
    ids = []
    for item in make_workload(spec):
        kw = {"lambda1": item.lam} if item.form == "penalized" else {"t": item.lam}
        ids.append(sched.submit(item.X, item.y, lambda2=item.lambda2, **kw))
    out = sched.drain()
    assert sorted(out) == sorted(ids), "lost or reordered request ids"
    betas = [np.asarray(out[i].beta).tolist() for i in ids]
    print("BETAS=" + json.dumps(betas))
""")


def test_bucket_placement_order_invariant_across_device_counts():
    """Property: the SAME workload solved on 1 / 2 / 8 devices returns the
    SAME beta for every request id — mesh placement must never permute
    results within a bucket (slot order is the contract `_complete` unpads
    by)."""
    results = {}
    for dc in (1, 2, 8):
        r = run_python(snippet=_BUCKET_ORDER % {"dc": dc}, timeout=900)
        line = [l for l in r.stdout.splitlines()
                if l.startswith("BETAS=")][-1]
        results[dc] = json.loads(line.split("=", 1)[1])
    for dc in (2, 8):
        assert len(results[dc]) == len(results[1])
        for i, (a, b) in enumerate(zip(results[dc], results[1])):
            dev = float(np.abs(np.asarray(a) - np.asarray(b)).max())
            assert dev <= 1e-10, (f"request {i} differs between 1 and {dc} "
                                  f"devices by {dev}")
