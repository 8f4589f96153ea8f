"""Adaptive execution routing: which device layout should run this solve?

PR 5 made the row-sharded `sven_sharded` path available everywhere a mesh
was in scope — and a CPU-host benchmark promptly recorded the cost of using
it unconditionally: a lone (768, 48) solve ran 10x SLOWER sharded than on one
device (`dist_solve.solve_speedup = 0.10`), because every collective pays
mesh latency and the replicated Newton solve competes with its own shards
for the simulated host devices' shared cores. The paper's claim is "as fast
as the hardware allows" (Zhou et al., AAAI 2015); GPU-SVM practice (Rgtsvm)
shows that only holds when the problem SHAPE picks the execution strategy.

This module is that picker. It routes every solve to one of three layouts:

    "single"   one device, the jit-native `sven` executable;
    "sharded"  rows of X/Zhat sharded over the mesh (`sven_sharded`,
               DESIGN.md §9.1) — wins when per-device GEMM savings beat
               collective latency + the replicated-solver tax;
    "batch"    batch-axis fan-out (`shard_map_lanes`, DESIGN.md §9.2) —
               each device vmaps its own lanes with zero collectives; wins
               whenever the per-device lane compute amortizes dispatch.

Decisions come from a COST MODEL, not hardcoded thresholds: a one-time
calibration microbenchmark (`calibrate`) measures, on the actual mesh,

    flops_per_s          single-device dense GEMM throughput,
    psum_latency_s       wall time of a small all-reduce (the per-collective
                         floor every sharded iteration pays),
    psum_per_byte_s      marginal cost per reduced byte (interconnect BW),
    fanout_speedup       measured speedup of shard_map'ing N independent
                         GEMMs vs one device doing all N (captures how much
                         of the mesh is REAL parallel hardware — simulated
                         host devices on shared cores score ~1, separate
                         chips score ~N),
    replicated_slowdown  the same GEMM run replicated on every device vs on
                         one (the oversubscription tax the sharded path's
                         replicated Newton solve pays on host-sim meshes),

    kernel_backend /     the RESOLVED kernel backend (kernels/registry.py)
    gram_flops_per_s     serving the dual Gram pass, and its measured
                         throughput — the data-pass term prices the real
                         kernel, not an assumed XLA GEMM,

and the router prices each layout's FLOPs + collectives with those numbers.
Calibration is cached per (backend, device-count) in-process AND persisted
to `<utils.cache_dir()>/calibration.json` keyed (platform, device count,
jax version), so repeat processes skip the microbenchmark entirely — the
knob: `calibrate(mesh, force=True)` re-measures (and overwrites the disk
entry), `clear_calibration()` drops the in-process caches (both exported;
see README "Multi-device").

Escape hatch: every routed entry point takes `route=` ("auto" | a pinned
path name) — `route="sharded"` forces the row-sharded layout regardless of
the model, which is also what the parity tests and benchmarks use to keep
exercising every path.
"""
from __future__ import annotations

import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# -- effective iteration counts for pricing a solve ------------------------
#
# The model prices RELATIVE layout costs, not absolute runtimes, so these
# only need to be the right order of magnitude (typical counts observed on
# the paper-scaled problems; tol=1e-8 Newton converges in ~10 outer steps).
DUAL_NEWTON_ITERS = 12      # projected-Newton outer steps (dual mode)
DUAL_CG_ITERS = 25          # masked-CG steps per outer step
PRIMAL_NEWTON_ITERS = 10    # Newton-CG outer steps (primal mode)
PRIMAL_CG_ITERS = 30        # CG steps per outer step
PENALIZED_EVALS = 8         # Illinois root-find SVEN evals per enet point

#: Fixed host-side overhead of launching any multi-device executable
#: (shard_map dispatch, sharded donation/placement) — keeps the router off
#: the mesh for solves too small for the timings above to even register.
MULTI_DEVICE_DISPATCH_S = 2e-4


class Calibration(NamedTuple):
    """Measured machine numbers the cost model prices layouts with."""

    devices: int
    backend: str
    flops_per_s: float
    psum_latency_s: float
    psum_per_byte_s: float
    fanout_speedup: float
    replicated_slowdown: float
    # the RESOLVED kernel backend the dual Gram pass will actually run on
    # (kernels/registry.py) and its measured throughput — the data-pass
    # term of the dual cost must price the real kernel, not assume an XLA
    # GEMM. On interpret/ref backends the kernel rate falls back to the
    # GEMM rate (interpret timings are pathological and the ref body IS an
    # XLA GEMM).
    kernel_backend: str = "ref"
    gram_flops_per_s: float = 0.0


class RouteDecision(NamedTuple):
    """One routing verdict: the chosen path and the model's price list."""

    path: str                 # "single" | "sharded" | "batch"
    costs: dict               # {path: predicted seconds} for every candidate
    calibration: Calibration
    reason: str


#: calibration cache, keyed on (backend, device_count) — mesh OBJECTS come
#: and go (tests build fresh ones constantly) but the hardware they name
#: does not, so the microbenchmark runs once per distinct device set.
_CALIBRATIONS: dict = {}
#: decision cache: routing must cost microseconds on the serving hot path,
#: so verdicts key on the (shape, mesh-size, backend) tuple that determined
#: them. Cleared with the calibrations.
_DECISIONS: dict = {}

_SINGLE_DEVICE = Calibration(devices=1, backend="any", flops_per_s=1e9,
                             psum_latency_s=0.0, psum_per_byte_s=0.0,
                             fanout_speedup=1.0, replicated_slowdown=1.0)


def clear_calibration() -> None:
    """Drop all in-process calibrations AND routing decisions (re-read the
    disk cache / re-measure next use) — the test/bench hook. To also force
    fresh MEASUREMENTS across processes, call `calibrate(mesh, force=True)`
    (which overwrites the disk entry) or delete
    `<utils.cache_dir()>/calibration.json`."""
    _CALIBRATIONS.clear()
    _DECISIONS.clear()


def _disk_key(backend: str, ndev: int) -> str:
    import jax as _jax
    return f"{backend}|{ndev}dev|jax{_jax.__version__}"


def _load_disk_calibration(backend: str, ndev: int):
    from repro import utils

    entry = utils.disk_cache_load("calibration").get(_disk_key(backend, ndev))
    if not isinstance(entry, dict) or set(entry) != set(Calibration._fields):
        return None
    try:
        return Calibration(**entry)
    except TypeError:
        return None


def _store_disk_calibration(cal: Calibration) -> None:
    from repro import utils

    utils.disk_cache_update(
        "calibration", {_disk_key(cal.backend, cal.devices): cal._asdict()})


def _gram_kernel_rate(flops_per_s: float) -> tuple[str, float]:
    """(resolved kernel backend, measured Gram-pass FLOPs/s) for this
    process's default platform. Compiled backends get a real measurement of
    `kernels.shifted_gram`, and a kernel that fails to compile raises;
    interpret/ref backends keep the GEMM rate."""
    from repro.kernels import ops as kops
    from repro.kernels import registry

    kb = registry.resolve_kernel_backend(None)
    body, interpret = registry.split_backend(kb)
    if interpret or body == "ref":
        return kb, flops_per_s
    n, p = 2048, 256
    X = jnp.ones((n, p), jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    t = _best_of(lambda: kops.shifted_gram(X, y, 1.0, backend=kb))
    return kb, (2.0 * n * p * p) / max(t, 1e-9)


def _best_of(fn, reps: int = 3) -> float:
    jax.block_until_ready(fn())                  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate(mesh: Optional[Mesh], *, force: bool = False) -> Calibration:
    """Measure the mesh once; cached per (backend, device count).

    `mesh=None` or a 1-device mesh is the trivial calibration: no
    collectives exist, so only GEMM throughput is measured. The
    microbenchmark uses small fixed shapes (~1 MFLOP GEMMs, ~100 KB
    reductions) — enough to resolve latency-vs-bandwidth without the
    calibration itself costing more than the solves it routes.
    """
    from jax import shard_map

    ndev = mesh.size if mesh is not None else 1
    backend = jax.default_backend()
    key = (backend, ndev)
    if not force and key in _CALIBRATIONS:
        return _CALIBRATIONS[key]
    if not force:
        # the repeat-process fast path: a prior run on this (platform,
        # device count, jax version) already paid for the microbenchmark —
        # BENCH showed the calibration overhead alone dragging routed
        # solves to 0.93x on the bit-identical "single" path.
        cal = _load_disk_calibration(backend, ndev)
        if cal is not None:
            _CALIBRATIONS[key] = cal
            return cal

    m = 192                                       # GEMM probe: 2*m^3 FLOPs
    A = jnp.ones((m, m), jnp.float32)
    gemm = jax.jit(lambda a: a @ a)
    t_gemm = _best_of(lambda: gemm(A))
    flops_per_s = (2.0 * m ** 3) / max(t_gemm, 1e-9)
    kernel_backend, gram_flops_per_s = _gram_kernel_rate(flops_per_s)

    if ndev <= 1:
        cal = Calibration(devices=ndev, backend=backend,
                          flops_per_s=flops_per_s, psum_latency_s=0.0,
                          psum_per_byte_s=0.0, fanout_speedup=1.0,
                          replicated_slowdown=1.0,
                          kernel_backend=kernel_backend,
                          gram_flops_per_s=gram_flops_per_s)
        _CALIBRATIONS[key] = cal
        _store_disk_calibration(cal)
        return cal

    axes = tuple(mesh.axis_names)

    def _psum_bench(rows: int):
        x = jax.device_put(jnp.ones((ndev, rows), jnp.float32),
                           NamedSharding(mesh, P(axes, None)))
        f = jax.jit(shard_map(lambda v: jax.lax.psum(v, axes), mesh=mesh,
                              in_specs=P(axes, None), out_specs=P(),
                              check_vma=False))
        return _best_of(lambda: f(x))

    t_small = _psum_bench(16)                     # latency-bound
    t_big = _psum_bench(32768)                    # bandwidth-bound (128 KB)
    psum_latency_s = t_small
    psum_per_byte_s = max(t_big - t_small, 0.0) / (32768 * 4)

    # fan-out probe: ndev independent GEMMs, shard_map'd one per device,
    # against a single device grinding through all of them as one batched
    # GEMM. On real parallel hardware this approaches ndev; on simulated
    # host devices sharing the same cores it hovers near 1 (or below).
    Ab = jnp.ones((ndev, m, m), jnp.float32)
    batched = jax.jit(lambda a: jnp.einsum("bij,bjk->bik", a, a))
    t_seq = _best_of(lambda: batched(Ab))
    Abs_ = jax.device_put(Ab, NamedSharding(mesh, P(axes, None, None)))
    fan = jax.jit(shard_map(lambda a: jnp.einsum("bij,bjk->bik", a, a),
                            mesh=mesh, in_specs=P(axes, None, None),
                            out_specs=P(axes, None, None), check_vma=False))
    t_fan = _best_of(lambda: fan(Abs_))
    fanout_speedup = max(t_seq / max(t_fan, 1e-9), 1e-3)

    # replication probe: the SAME GEMM executed by every device at once vs
    # by one — prices the sharded path's replicated Newton solve, which on
    # an oversubscribed host-sim mesh is several times slower than it looks.
    rep = jax.jit(shard_map(lambda a: a @ a, mesh=mesh, in_specs=P(),
                            out_specs=P(), check_vma=False))
    t_rep = _best_of(lambda: rep(A))
    replicated_slowdown = max(t_rep / max(t_gemm, 1e-9), 1.0)

    cal = Calibration(devices=ndev, backend=backend, flops_per_s=flops_per_s,
                      psum_latency_s=psum_latency_s,
                      psum_per_byte_s=psum_per_byte_s,
                      fanout_speedup=fanout_speedup,
                      replicated_slowdown=replicated_slowdown,
                      kernel_backend=kernel_backend,
                      gram_flops_per_s=gram_flops_per_s)
    _CALIBRATIONS[key] = cal
    _store_disk_calibration(cal)
    _DECISIONS.clear()
    return cal


# -- the cost model ---------------------------------------------------------

def _psum_cost(cal: Calibration, floats: float) -> float:
    return cal.psum_latency_s + floats * 8.0 * cal.psum_per_byte_s


def _solve_flops(n: int, p: int, mode: str) -> tuple:
    """(data-pass FLOPs over X, solver-iteration FLOPs) for one SVEN solve.

    dual: one Gram pass 2np^2 then Newton on the (2p, 2p) kernel — each
    outer step's masked CG does a K matvec, 2(2p)^2 FLOPs. primal: every
    Newton-CG product is a matvec + rmatvec pair over X, ~8np each.
    """
    if mode == "dual":
        data = 2.0 * n * p * p
        iters = DUAL_NEWTON_ITERS * (DUAL_CG_ITERS + 3) * 2.0 * (2 * p) ** 2
    else:
        data = 0.0
        iters = (PRIMAL_NEWTON_ITERS * (PRIMAL_CG_ITERS + 3)) * 8.0 * n * p
    return data, iters


def _solve_costs(n: int, p: int, mode: str, cal: Calibration) -> dict:
    """Predicted seconds for one solve under each layout."""
    F = cal.flops_per_s
    # the dual data pass runs on the RESOLVED kernel backend (Pallas Gram
    # on tpu, XLA GEMM otherwise) — price it at that kernel's measured
    # rate, not the generic GEMM rate
    G = cal.gram_flops_per_s or F
    data, iters = _solve_flops(n, p, mode)
    costs = {"single": data / G + iters / F}
    if cal.devices > 1:
        if mode == "dual":
            # data pass shards perfectly (one psum of G/u/s closes it); the
            # projected Newton runs REPLICATED on the assembled kernel, so
            # it pays the replication tax, not a 1/ndev discount.
            sharded = (data / (G * cal.fanout_speedup * cal.devices)
                       + _psum_cost(cal, p * p + p + 1)
                       + iters * cal.replicated_slowdown / F
                       + 2.0 * cal.psum_latency_s      # w recovery + kkt
                       + MULTI_DEVICE_DISPATCH_S)
        else:
            # every Newton-CG product: local O(np/ndev) work + one
            # psum(p + 1) + one all-gather of the n-vector.
            products = PRIMAL_NEWTON_ITERS * (PRIMAL_CG_ITERS + 3)
            per_product = (8.0 * n * p
                           / (F * cal.fanout_speedup * cal.devices)
                           + _psum_cost(cal, p + 1)
                           + _psum_cost(cal, n))
            sharded = products * per_product + MULTI_DEVICE_DISPATCH_S
        costs["sharded"] = sharded
    return costs


def _batch_costs(n: int, p: int, B: int, mode: str, cal: Calibration,
                 points: int) -> dict:
    """Predicted seconds for a B-problem stack: vmap on one device vs
    batch-axis fan-out (each device vmaps B/ndev lanes, zero collectives)."""
    data, iters = _solve_flops(n, p, mode)
    lane = points * (data + iters) / cal.flops_per_s
    costs = {"single": B * lane}
    if cal.devices > 1:
        costs["batch"] = (B * lane / cal.fanout_speedup
                          + MULTI_DEVICE_DISPATCH_S)
    return costs


def _decide(costs: dict, cal: Calibration, pinned: Optional[str]) -> RouteDecision:
    if pinned is not None:
        decision = RouteDecision(path=pinned, costs=costs, calibration=cal,
                                 reason=f"pinned route={pinned!r}")
    else:
        path = min(costs, key=costs.get)
        others = {k: v for k, v in costs.items() if k != path}
        margin = (min(others.values()) / max(costs[path], 1e-12)
                  if others else float("inf"))
        decision = RouteDecision(path=path, costs=costs, calibration=cal,
                                 reason=f"cost model: {path} wins {margin:.2f}x")
    # telemetry (DESIGN.md §12): each FRESH verdict (cached ones replay the
    # same decision) counts on the process registry and drops a trace
    # instant carrying the full price table the model compared.
    from repro.obs.metrics import default_registry
    from repro.obs.trace import get_tracer

    default_registry().counter(
        "route_decisions_total", "cost-model routing verdicts",
        ("path",)).inc(path=decision.path)
    get_tracer().instant("route", path=decision.path, costs=dict(costs),
                         reason=decision.reason)
    return decision


def _resolve_route_mesh(mesh):
    """None -> innermost dist context, else the process data mesh (matches
    `sven_sharded`'s resolution so routed and pinned calls agree)."""
    from repro import dist

    if mesh is None:
        ctx = dist.current_context()
        mesh = ctx[0] if ctx is not None else dist.data_mesh()
    return mesh


def route_solve(n: int, p: int, *, mesh: Optional[Mesh] = None,
                config=None, route: str = "auto") -> RouteDecision:
    """Price one (n, p) solve on `mesh` and pick single-device vs sharded.

    `route` pins the verdict ("single" / "sharded") while still reporting
    the model's prices — the escape hatch and the introspection hook.
    """
    if route not in ("auto", "single", "sharded"):
        raise ValueError(f"route_solve: route must be auto|single|sharded, "
                         f"got {route!r}")
    from repro.core.sven import SvenConfig, _pick_mode

    cfg = SvenConfig() if config is None else config
    mesh = _resolve_route_mesh(mesh)
    ndev = mesh.size if mesh is not None else 1
    mode = _pick_mode(n, p, cfg)
    if ndev <= 1:
        return RouteDecision(path="single",
                             costs={"single": 0.0},
                             calibration=_SINGLE_DEVICE,
                             reason="one device: nothing to route")
    cal = calibrate(mesh)
    key = ("solve", n, p, ndev, cal.backend, mode, route)
    if key not in _DECISIONS:
        _DECISIONS[key] = _decide(_solve_costs(n, p, mode, cal), cal,
                                  None if route == "auto" else route)
    return _DECISIONS[key]


#: Bytes the penalized path's one-chip program plans per byte of an f64 X:
#: TPU v5e emulates f64, and the compiler's memory analysis of
#: `api._enet_path_scan` (10 points, arguments + outputs + temporaries)
#: reads 22.66 GB at 463,716 x 90, 67.9 times X's 334 MB, mostly X split
#: into f32 pieces for each pass (PERF.md). Dtypes the chip runs natively
#: plan less, so for them the estimate errs towards the row layout.
PATH_PLAN_X_BYTES = 68


def chip_memory_bytes(device) -> Optional[int]:
    """The device's memory for one program (`bytes_limit`), or None where
    the platform reports none (the host CPU)."""
    return (device.memory_stats() or {}).get("bytes_limit")


def route_path(n: int, p: int, itemsize: int, mesh: Optional[Mesh]) -> str:
    """Layout of an `enet_path` over an (n, p) design: "rows" or "single".

    Only in a mesh context (`mesh` given, more than one device), and only
    for a tall design (2p <= n, the dual regime): there each pass over X
    splits over the chips' rows and each reduction over rows ends in an
    all-reduce of at most p^2 floats. A wide design (2p > n) has few rows
    to split. A tall design takes rows when its one-chip plan
    (`PATH_PLAN_X_BYTES` per byte of X) does not fit one chip's memory;
    where it fits, one chip runs it with no collectives.
    """
    if mesh is None or mesh.size <= 1 or 2 * p > n:
        return "single"
    limit = chip_memory_bytes(mesh.devices.flat[0])
    plan = PATH_PLAN_X_BYTES * n * p * itemsize
    return "rows" if limit is not None and plan > limit else "single"


def route_batch(n: int, p: int, batch_size: int, mesh: Optional[Mesh] = None,
                *, form: str = "constrained", points: int = 1,
                route: str = "auto") -> RouteDecision:
    """Price a stacked B-problem launch: single-device vmap vs batch-axis
    fan-out. `form="penalized"` scales each lane by the Illinois root-find's
    solve count; `points` further scales per-lane work (CV/path scans run
    `points` grid points per lane). Divisibility of B by the mesh is the
    CALLER's concern (`batch.batch_mesh` checks it) — the router prices
    layouts, it does not validate placements.
    """
    if route not in ("auto", "single", "batch"):
        raise ValueError(f"route_batch: route must be auto|single|batch, "
                         f"got {route!r}")
    from repro.core.sven import SvenConfig, _pick_mode

    mesh = _resolve_route_mesh(mesh)
    ndev = mesh.size if mesh is not None else 1
    mode = _pick_mode(n, p, SvenConfig())
    if ndev <= 1:
        return RouteDecision(path="single", costs={"single": 0.0},
                             calibration=_SINGLE_DEVICE,
                             reason="one device: nothing to route")
    cal = calibrate(mesh)
    pts = points * (PENALIZED_EVALS if form == "penalized" else 1)
    key = ("batch", n, p, batch_size, pts, ndev, cal.backend, mode, route)
    if key not in _DECISIONS:
        _DECISIONS[key] = _decide(_batch_costs(n, p, batch_size, mode, cal,
                                               pts), cal,
                                  None if route == "auto" else route)
    return _DECISIONS[key]


def estimate_batch_seconds(n: int, p: int, batch_size: int, *,
                           form: str = "constrained") -> float:
    """Modeled single-host seconds for a stacked B-problem (n, p) solve.

    The multi-host coordinator's placement signal: it needs RELATIVE prices
    (a (256, 128) x 8 batch must cost more than a (32, 16) x 2 one), not
    wall-clock accuracy, and it must never trigger a calibration
    microbenchmark on the admission path. So this prices the "single"
    layout with whatever calibration is already known — the in-process
    cache, then the disk cache, then the shape-only default — and never
    measures.
    """
    backend = jax.default_backend()
    cal = (_CALIBRATIONS.get((backend, 1))
           or _load_disk_calibration(backend, 1) or _SINGLE_DEVICE)
    from repro.core.sven import SvenConfig, _pick_mode

    mode = _pick_mode(n, p, SvenConfig())
    pts = PENALIZED_EVALS if form == "penalized" else 1
    return _batch_costs(n, p, batch_size, mode, cal, pts)["single"]


def sven_routed(X, y, t, lambda2, config=None, *, mesh: Optional[Mesh] = None,
                route: str = "auto", warm_alpha=None, warm_w=None):
    """`sven` with automatic layout choice — THE multi-device entry point.

    Routes through the cost model to single-device `sven` or row-sharded
    `sven_sharded` (results match to <= 1e-10 either way, tested);
    `route="single"`/`route="sharded"` pins the path. Mesh resolution
    matches `sven_sharded`: explicit mesh, else the innermost
    `dist.mesh_context`, else the process data mesh.
    """
    from repro.core.distributed import sven_sharded
    from repro.core.sven import SvenConfig, sven

    cfg = SvenConfig() if config is None else config
    # shape only — array conversion is the chosen entry point's job, and
    # an eager asarray here would tax every routed call
    n, p = jnp.shape(X)
    mesh = _resolve_route_mesh(mesh)
    decision = route_solve(n, p, mesh=mesh, config=cfg, route=route)
    if decision.path == "single":
        return sven(X, y, t, lambda2, cfg,
                    warm_alpha=warm_alpha, warm_w=warm_w)
    return sven_sharded(X, y, t, lambda2, cfg, mesh=mesh,
                        warm_alpha=warm_alpha, warm_w=warm_w)
