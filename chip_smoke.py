"""Bring-up smoke: the Elastic Net path, CV, a tall solve and serving, end to
end on one TPU, through the entry points a user calls.

    python chip_smoke.py              # one TPU chip: phases path, tall, serve
    python chip_smoke.py --chips 4    # four TPU chips: the mesh paths only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                      # tiny sizes on the CPU, kernels in
                                      # Pallas interpret mode; never "ok"

Phases on one chip, each run twice: with the default config
(`backend="xla"`) and with the Pallas kernels (`backend="tpu"`). Data is
f64, as at every entry point (`jax_enable_x64`):

  path   GLI-85's published shape, 85 x 22,283 (scikit-feature; the paper's
         p >> n suite): `enet_path` over the first PATH_POINTS points of a
         10-point glmnet-default lambda grid, with screening, then
         CV_FOLDS-fold `cross_validate` over the same points. Primal
         Newton-CG; the kernel run uses the hinge Hessian mat-vec.
  tall   UCI YearPredictionMSD's training shape, 463,715 x 90: `sven` at 3
         values of t. Dual path; the kernel run uses the shifted-Gram kernel.
  serve  a `ContinuousScheduler` answers 12 requests through `submit` and
         `drain`: an 8-point lambda crawl on the GLI-85-shaped design plus 4
         one-off 4,096 x 90 problems. Every request must end in "ok".

The path and the crawl are shorter than a deployment's (glmnet runs 100
points and 10 folds): f64 is emulated on the TPU, and a primal solve at
GLI-85's width costs seconds there, so the full depth does not fit the
smoke's time limit. Widths are never cut.

`--chips 4` runs only the mesh paths on a 4-device mesh and compares each
with the single-device answer in the same process: row-sharded `sven`
(route="sharded") on the tall design, `sven_batch` of 4 one-off problems
under `dist.mesh_context`, and `cross_validate` with k = 4 and an explicit
mesh on a one-off design. Each must have produced its result on all 4
devices. Then `enet_path` over the tall design's 10-point glmnet grid under
`dist.mesh_context`: its one-chip plan does not fit a chip, so it must take
the row layout (`enet_path_layout_total`) and match coordinate descent at
2 points; a profile of a second call counts the all-reduces each chip ran
beside the path's root-find evaluations (reported, not judged). The CPU rehearsal reports no device memory, so it is handed a
1 MiB chip for this check.

Data comes from `data.synthetic.make_regression` with `--seed`. Results are
checked against a plain reference: coordinate descent
(`baselines/coordinate_descent.py`) at 2-3 lambda points per phase, run on
the host CPU so that it shares no code path with the solve under test, or a
direct `enet`/`sven` call for each served request. Each phase prints one
JSON line; the last line, `{"ok": true, "device": {...}}`, is printed only
when every phase passed on a TPU. With no TPU the script exits non-zero and
does not fall back to the CPU. It starts no child process: a TPU belongs to
one process. Progress goes to stderr.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

#: largest admitted deviation max|b - b_ref| / max|b_ref|. Everything the
#: XLA backend computes is f64, and so is the reference: a solve computed
#: in f32 misses this bound by orders of magnitude.
TOL_F64 = 1e-6
#: the kernel runs compute the dual Gram in f32 (`SvenConfig.precision` =
#: "f32", no refinement), so the dual solution carries f32 rounding of K.
#: The primal kernel run keeps TOL_F64: only its Hessian products are f32,
#: and Newton's fixed point is set by the f64 gradient.
TOL_F32_GRAM = 1e-3

#: (n, p) per design; the rehearsal cuts both to CPU size
SHAPES = {"gli85": (85, 22_283), "msd": (463_715, 90), "oneoff": (4_096, 90)}
REHEARSAL_SHAPES = {"gli85": (40, 600), "msd": (4_000, 20),
                    "oneoff": (512, 20)}
N_LAMBDAS = 10        # glmnet-default grid length for p > n (eps = 0.01)
PATH_POINTS = 4       # the leading grid points the path and CV solve
CV_FOLDS = 3
CRAWL = 8             # penalized requests crawling down the lambda grid
ONEOFFS = 4
LAMBDA2 = 1.0

T0 = time.perf_counter()


def _log(msg: str) -> None:
    print(f"chip_smoke [{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 2


class _CompileClock:
    """Seconds of XLA compilation (JAX's `backend_compile_duration`
    events, which never nest), so a phase's wall time splits into compile
    seconds and run seconds (execution, tracing, dispatch, tile probes)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, monitoring):
        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.total += duration


class Smoke:
    def __init__(self, args, jax, jnp, np):
        self.args, self.jax, self.jnp, self.np = args, jax, jnp, np
        self.shapes = REHEARSAL_SHAPES if args.rehearse else SHAPES
        self.kernel_backend = "tpu_interpret" if args.rehearse else "tpu"
        self.cpu = jax.devices("cpu")[0]
        self.clock = _CompileClock(jax.monitoring)
        self._data = {}
        self._refs = {}

    # -- data and references -----------------------------------------------

    def data(self, name: str, seed_offset: int = 0):
        key = (name, seed_offset)
        if key not in self._data:
            from repro.data.synthetic import make_regression
            n, p = self.shapes[name]
            X, y, _ = make_regression(n, p, seed=self.args.seed + seed_offset)
            self._data[key] = (X, y)
        return self._data[key]

    def cd_reference(self, name: str, lambda1s):
        """Coordinate descent on the host CPU, warm-started down the list;
        cached, so both backends compare with the same reference."""
        from repro.baselines import elastic_net_cd
        X, y = self.data(name)
        Xc = self.jax.device_put(X, self.cpu)
        yc = self.jax.device_put(y, self.cpu)
        betas, beta = [], None
        for lam in lambda1s:
            key = (name, lam)
            if key not in self._refs:
                _log(f"reference: coordinate descent at lambda1={lam:.6g}")
                res = elastic_net_cd(Xc, yc, lam, LAMBDA2, beta0=beta)
                self._refs[key] = self.np.asarray(res.beta)
            beta = self.jax.device_put(self._refs[key], self.cpu)
            betas.append(self._refs[key])
        return betas

    def rel_dev(self, got, ref) -> float:
        got, ref = self.np.asarray(got), self.np.asarray(ref)
        return float(self.np.max(self.np.abs(got - ref))
                     / max(float(self.np.max(self.np.abs(ref))), 1e-300))

    def path_kkt(self, path) -> float:
        """Largest KKT violation over the grid points with a nonzero
        solution (at beta = 0 the constrained form's multiplier is
        undefined, and so is its KKT residual)."""
        nz = self.np.any(self.np.asarray(path.betas) != 0, axis=1)
        return float(self.np.max(self.np.asarray(path.kkts)[nz]))

    def kernel_compiled(self, lowered) -> bool:
        """Whether the compiled program holds a Pallas TPU kernel. The
        program was compiled by the run just made, so this is a cache hit."""
        return "tpu_custom_call" in lowered.compile().as_text()

    def timed(self, name: str, fn):
        """(result, compile seconds, run seconds) of one call of fn()."""
        _log(f"{name}: start")
        c0 = self.clock.total
        t0 = time.perf_counter()
        out = self.jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        compile_s = self.clock.total - c0
        _log(f"{name}: done in {wall:.1f}s ({compile_s:.1f}s compiling)")
        return out, compile_s, wall - compile_s

    def configs(self, backend: str):
        from repro.core import PathConfig, SvenConfig
        cfg = SvenConfig(backend=backend)
        return cfg, PathConfig(solver=SvenConfig(tol=1e-10, backend=backend))

    def grid(self):
        """The leading PATH_POINTS points of the glmnet-default grid."""
        from repro.core import api
        X, y = self.data("gli85")
        return api.lambda_grid(X, y, n_lambdas=N_LAMBDAS)[:PATH_POINTS]

    # -- one-chip phases ---------------------------------------------------

    def phase_path(self, backend: str) -> dict:
        from repro.core import api, cross_validate, enet_path
        X, y = self.data("gli85")
        _, pc = self.configs(backend)
        grid = self.grid()

        def run():
            path = enet_path(X, y, lambda1s=grid, lambda2=LAMBDA2, config=pc)
            cv = cross_validate(X, y, k=CV_FOLDS, lambda1s=grid,
                                lambda2=LAMBDA2, config=pc)
            return path, cv

        (path, cv), compile_s, run_s = self.timed(f"path/{backend}", run)
        rpc = api.resolve_path_config(pc, X, y)
        idx = (1, PATH_POINTS - 1)
        refs = self.cd_reference("gli85", [float(grid[i]) for i in idx])
        devs = [self.rel_dev(path.betas[i], r) for i, r in zip(idx, refs)]
        # cv.beta is the full-data refit at lambda_min, a grid point
        i_min = int(self.np.argmin(self.np.abs(
            self.np.asarray(grid) - cv.lambda_min)))
        devs.append(self.rel_dev(cv.beta, path.betas[i_min]))
        out = dict(backend=rpc.solver.backend,
                   tiles=("static (hinge mat-vec tiles are fixed in "
                          "kernels.ops)") if backend != "xla" else "none (xla)",
                   compile_s=compile_s, run_s=run_s, max_dev=max(devs),
                   tol=TOL_F64, kkt=self.path_kkt(path),
                   points=PATH_POINTS, folds=CV_FOLDS,
                   lambda_min=cv.lambda_min)
        if rpc.solver.backend == "tpu":
            out["kernel_in_compiled"] = self.kernel_compiled(
                api._enet_path_scan.lower(X, y, grid,
                                          self.jnp.asarray(LAMBDA2, X.dtype),
                                          rpc))
        return out

    def phase_tall(self, backend: str) -> dict:
        from repro.core import sven
        from repro.core.elastic_net import lambda1_max
        from repro.core.sven import _sven_jit, resolve_backend
        from repro.kernels import autotune
        X, y = self.data("msd")
        cfg, _ = self.configs(backend)
        rcfg = resolve_backend(cfg, X, y)
        l1max = float(lambda1_max(X, y))
        refs = self.cd_reference(
            "msd", [l1max * f for f in (0.3, 0.05, 0.005)])
        ts = [float(self.np.abs(r).sum()) for r in refs]
        tiles = "none (xla)"
        if backend != "xla":
            chosen, source = autotune.resolve_tiles(
                "shifted_gram", rcfg.backend, *X.shape, self.jnp.float32)
            tiles = f"{source} {chosen}"

        def run():
            return [sven(X, y, t, LAMBDA2, cfg) for t in ts]

        sols, compile_s, run_s = self.timed(f"tall/{backend}", run)
        devs = [self.rel_dev(s.beta, r) for s, r in zip(sols, refs)]
        out = dict(backend=rcfg.backend, tiles=tiles, compile_s=compile_s,
                   run_s=run_s, max_dev=max(devs),
                   tol=TOL_F64 if backend == "xla" else TOL_F32_GRAM,
                   kkt=max(float(s.kkt) for s in sols), mode=sols[0].mode)
        if rcfg.backend == "tpu":
            dt = X.dtype
            out["kernel_in_compiled"] = self.kernel_compiled(
                _sven_jit.lower(X, y, self.jnp.asarray(ts[0], dt),
                                self.jnp.asarray(LAMBDA2, dt), None, None,
                                None, config=rcfg))
        return out

    def phase_serve(self, backend: str) -> dict:
        from repro.core import api, enet, sven
        from repro.core.sven import resolve_backend
        from repro.kernels import autotune
        from repro.runtime.scheduler import ContinuousScheduler
        cfg, pc = self.configs(backend)
        X, y = self.data("gli85")
        grid = self.np.asarray(self.grid())
        crawl = [float(v) for v in self.np.geomspace(
            0.9 * grid[0], grid[-1], CRAWL)]
        oneoffs = [self.data("oneoff", 1 + i) for i in range(ONEOFFS)]
        # half the ridge L1 norm keeps each budget binding (for a larger t
        # the constrained problem is plain ridge, outside the reduction)
        t_oneoff = [0.5 * float(api._ridge_l1(a, b, LAMBDA2))
                    for a, b in oneoffs]
        sched = ContinuousScheduler(cfg, path_config=pc, max_batch=4,
                                    max_wait=None)
        rcfg = resolve_backend(cfg, X, y)
        tiles = "none (xla)"
        if backend != "xla":
            chosen, source = autotune.resolve_tiles(
                "shifted_gram", rcfg.backend,
                *sched.bucket_of(*self.shapes["oneoff"]), self.jnp.float32)
            tiles = f"{source} {chosen} (one-off bucket)"
        Xh, yh = self.np.asarray(X), self.np.asarray(y)

        def run():
            ids = [("pen", lam, sched.submit(Xh, yh, lambda1=lam,
                                             lambda2=LAMBDA2))
                   for lam in crawl]
            ids += [("con", i, sched.submit(self.np.asarray(a),
                                            self.np.asarray(b),
                                            t=t_oneoff[i], lambda2=LAMBDA2))
                    for i, (a, b) in enumerate(oneoffs)]
            return ids, sched.drain()

        (ids, results), compile_s, run_s = self.timed(f"serve/{backend}", run)
        statuses = [results[rid].status for _, _, rid in ids]
        _log(f"serve/{backend}: direct reference solves")
        devs, kkts = [], []
        for kind, arg, rid in ids:
            res = results[rid]
            if res.status != "ok":
                continue
            if kind == "pen":
                ref = enet(X, y, arg, LAMBDA2).beta
                tol = TOL_F64
            else:
                ref = sven(*oneoffs[arg], t_oneoff[arg], LAMBDA2).beta
                tol = TOL_F64 if backend == "xla" else TOL_F32_GRAM
            devs.append(self.rel_dev(res.beta, ref) / tol)
            kkts.append(float(res.kkt))
        return dict(backend=rcfg.backend, tiles=tiles, compile_s=compile_s,
                    run_s=run_s, requests=len(ids),
                    ok_requests=statuses.count("ok"),
                    max_dev_over_tol=max(devs) if devs else float("inf"),
                    kkt=max(kkts) if kkts else float("inf"))

    # -- four-chip phase ---------------------------------------------------

    def phase_mesh(self, backend: str) -> dict:
        from repro import dist
        from repro.core import api, cross_validate, sven, sven_batch
        from repro.core.routing import sven_routed
        jnp = self.jnp
        ndev = 4
        mesh = dist.data_mesh(ndev)
        cfg, pc = self.configs(backend)

        def on_all(arr) -> bool:
            return len(arr.sharding.device_set) == ndev

        out, devs = {}, {}
        Xt, yt = self.data("msd")
        t = 0.5 * float(api._ridge_l1(Xt, yt, LAMBDA2))
        (single, sharded), c, r = self.timed("mesh/sharded", lambda: (
            sven(Xt, yt, t, LAMBDA2, cfg),
            sven_routed(Xt, yt, t, LAMBDA2, cfg, mesh=mesh, route="sharded")))
        devs["sharded"] = self.rel_dev(sharded.beta, single.beta)
        out["sharded_on_all"] = on_all(sharded.beta)

        # the stacked problems and the CV folds are tall one-off designs:
        # dual solves, cheap enough for a four-chip call
        probs = [self.data("oneoff", 1 + i) for i in range(ndev)]
        Xb = jnp.stack([a for a, _ in probs])
        yb = jnp.stack([b for _, b in probs])
        ts = jnp.asarray([0.5 * float(api._ridge_l1(a, b, LAMBDA2))
                          for a, b in probs], Xb.dtype)

        def batches():
            single_b = sven_batch(Xb, yb, ts, LAMBDA2, cfg, route="single")
            with dist.mesh_context(mesh):
                fan = sven_batch(Xb, yb, ts, LAMBDA2, cfg, route="batch")
            return single_b, fan

        (single_b, fan), _, _ = self.timed("mesh/batch", batches)
        devs["batch"] = self.rel_dev(fan.beta, single_b.beta)
        out["batch_on_all"] = on_all(fan.beta)

        X, y = probs[0]
        grid = api.lambda_grid(X, y, n_lambdas=N_LAMBDAS)[:2]
        (cv_single, cv_mesh), _, _ = self.timed("mesh/cv", lambda: tuple(
            cross_validate(X, y, k=ndev, lambda1s=grid, lambda2=LAMBDA2,
                           mesh=m, config=pc) for m in (None, mesh)))
        devs["cv_mse"] = self.rel_dev(cv_mesh.mse_path, cv_single.mse_path)
        devs["cv_beta"] = self.rel_dev(cv_mesh.beta, cv_single.beta)
        out["cv_on_all"] = on_all(cv_mesh.mse_path)
        out.update(self.rows_path(mesh, pc, devs))
        out.update(backend=backend, devs=devs, max_dev=max(devs.values()),
                   tol=TOL_F64, sharded_compile_s=c, sharded_run_s=r,
                   kkt=max(float(sharded.kkt), float(jnp.max(fan.kkt))))
        return out

    def rows_path(self, mesh, pc, devs: dict) -> dict:
        """`enet_path` on the tall design under the mesh: the row layout,
        its answers at two grid points, and its all-reduces."""
        from repro import dist
        from repro.core import api, routing
        from repro.obs.metrics import default_registry
        np = self.np
        if self.args.rehearse:
            routing.chip_memory_bytes = lambda device: 1 << 20
        X, y = self.data("msd")
        grid = api.lambda_grid(X, y, n_lambdas=N_LAMBDAS)
        layouts = default_registry().counter(
            "enet_path_layout_total", "enet_path calls by row layout",
            ("layout",))
        before = layouts.series().get(("rows",), 0)

        def call():
            with dist.mesh_context(mesh):
                return api.enet_path(X, y, lambda1s=grid, lambda2=LAMBDA2,
                                     config=pc)

        path, c, r = self.timed("mesh/rows", call)
        idx = (1, 3)
        refs = self.cd_reference("msd", [float(grid[i]) for i in idx])
        devs["rows"] = max(self.rel_dev(path.betas[i], ref)
                           for i, ref in zip(idx, refs))
        reduces = self.all_reduces(call)
        return dict(rows_layout=layouts.series().get(("rows",), 0)
                    == before + 2,
                    rows_on_all=len(path.betas.sharding.device_set)
                    == mesh.size,
                    rows_compile_s=c, rows_run_s=r,
                    rows_all_reduces_per_chip=reduces,
                    rows_evals=int(np.sum(np.asarray(path.evals))))

    def all_reduces(self, fn):
        """All-reduce operations each device ran in a call of fn(), from a
        profile of it (the "XLA Ops" line of each device plane)."""
        import glob
        import shutil
        import tempfile

        from jax.profiler import ProfileData
        jax = self.jax
        d = tempfile.mkdtemp(prefix="chip-smoke-trace-")
        try:
            jax.profiler.start_trace(d)
            try:
                jax.block_until_ready(fn())
            finally:
                jax.profiler.stop_trace()
            path = max(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
            counts = []
            for plane in ProfileData.from_file(path).planes:
                if (plane.name.startswith("/device:")
                        and "CPU" not in plane.name):
                    counts.append(sum(
                        1 for line in plane.lines if line.name == "XLA Ops"
                        for e in line.events
                        if "all-reduce" in e.name.partition(" = ")[0]
                        and "done" not in e.name.partition(" = ")[0]))
            return counts
        finally:
            shutil.rmtree(d, ignore_errors=True)


def _passed(phase: str, backend: str, rec: dict) -> bool:
    if "kernel_in_compiled" in rec and not rec["kernel_in_compiled"]:
        return False
    if backend == "tpu" and rec["backend"] != "tpu":
        return False
    if phase == "serve":
        return (rec["ok_requests"] == rec["requests"]
                and rec["max_dev_over_tol"] <= 1.0)
    if phase == "mesh":
        return (rec["sharded_on_all"] and rec["batch_on_all"]
                and rec["cv_on_all"] and rec["rows_layout"]
                and rec["rows_on_all"] and rec["max_dev"] <= rec["tol"])
    return rec["max_dev"] <= rec["tol"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh paths, on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds, kernels in "
                         "interpret mode; never prints the ok line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        return _fail("the repro package (src/repro) is not next to this "
                     "script: run it from a checkout of the repository")
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_enable_x64", True)  # the entry points' setting
    from repro import utils
    _log(f"compile cache: {utils.enable_compile_cache()}")

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        return _fail(f"JAX found no TPU (platform {platform!r}); this smoke "
                     f"does not fall back to the CPU")
    if len(devices) < args.chips:
        return _fail(f"--chips {args.chips} needs {args.chips} devices, "
                     f"JAX found {len(devices)}")

    smoke = Smoke(args, jax, jnp, np)
    phases = ("mesh",) if args.chips == 4 else ("path", "tall", "serve")
    backends = ("xla",) if args.chips == 4 else ("xla", smoke.kernel_backend)
    ok = True
    for phase in phases:
        for backend in backends:
            try:
                rec = getattr(smoke, f"phase_{phase}")(backend)
                passed = _passed(phase, backend, rec)
            except Exception:  # noqa: BLE001 — report, run the other phases
                traceback.print_exc()
                rec, passed = {"error": "see stderr"}, False
            ok &= passed
            dtype = str(smoke.data("gli85")[0].dtype)
            print(json.dumps({"phase": phase, "requested": backend,
                              "dtype": dtype, **rec, "passed": passed},
                             default=str), flush=True)
    if not ok:
        return _fail("a phase failed")
    if args.rehearse:
        _log("rehearsal passed (no ok line off the chip)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
