"""Benchmark harness — one module per paper table/figure plus framework
micro-benches. Prints ``name,us_per_call,derived`` CSV lines and writes the
path-engine artifact ``BENCH_path.json`` (scan-vs-loop wall clock, trace
counts, batch-vs-sequential speedup, CV throughput, serving runtime
latency/throughput, per-backend kernel timings/parity, telemetry overhead
and accounting) whenever the ``path``/``batch``/``cv``/``serve``/
``dist_solve``/``kernels``/``multihost``/``obs`` benches run — CI
validates the artifact schema on CPU via
``benchmarks/validate_artifact.py``.

    PYTHONPATH=src python -m benchmarks.run [--quick] \
        [--only path,batch,cv,serve]
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

ARTIFACT = "BENCH_path.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer path points")
    ap.add_argument("--only", default="", help="comma list of module suffixes")
    ap.add_argument("--artifact", default=ARTIFACT,
                    help="where to write the path/batch JSON artifact")
    args = ap.parse_args()

    from repro import utils
    utils.enable_compile_cache()

    from benchmarks import (bench_batch, bench_crossover, bench_cv,
                            bench_dist_solve, bench_distributed,
                            bench_kernels, bench_lm_smoke, bench_nggp,
                            bench_obs, bench_path, bench_pggn,
                            bench_reduction_ops, bench_serve)

    mods = {
        "path": (lambda: bench_path.run(points=6)) if args.quick else bench_path.run,
        "batch": (lambda: bench_batch.run(B=4)) if args.quick else bench_batch.run,
        "cv": (lambda: bench_cv.run(k=4, n_lambdas=8)) if args.quick else bench_cv.run,
        # quick serve uses 32 requests / best-of-3: at 24/2 the sustained
        # ratio sits too close to the 2x gate once the LatencyRecorder fix
        # sped the synchronous reference up — more warm requests amortize
        # the runtime's fixed per-pass costs and de-flake the gate.
        "serve": ((lambda: bench_serve.run(requests=32, reps=3))
                  if args.quick else bench_serve.run),
        "multihost": ((lambda: bench_serve.run_multihost(requests=16))
                      if args.quick else bench_serve.run_multihost),
        # quick obs keeps the full 32-request / best-of-7 measurement: the
        # extra passes are ~20ms each and the 1.10x overhead gate jitters
        # on fewer reps; only the multihost leg is trimmed.
        "obs": ((lambda: bench_obs.run(requests=32, mh_requests=6))
                if args.quick else bench_obs.run),
        "dist_solve": ((lambda: bench_dist_solve.run(n=384, p=32, reps=2))
                       if args.quick else bench_dist_solve.run),
        "kernels": ((lambda: bench_kernels.run(n=384, p=32, reps=2))
                    if args.quick else bench_kernels.run),
        "reduction_ops": bench_reduction_ops.run,
        "crossover": bench_crossover.run,
        "pggn": (lambda: bench_pggn.run(points=2)) if args.quick else bench_pggn.run,
        "nggp": (lambda: bench_nggp.run(points=2)) if args.quick else bench_nggp.run,
        "distributed": bench_distributed.run,
        "lm_smoke": bench_lm_smoke.run,
    }
    picked = [s for s in args.only.split(",") if s] or list(mods)
    print("name,us_per_call,derived")
    failures = 0
    artifact: dict = {}
    for name in picked:
        try:
            out = mods[name]()
            if (name in ("path", "batch", "cv", "serve", "dist_solve",
                         "kernels", "multihost", "obs")
                    and isinstance(out, dict)):
                artifact[name] = out
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR", flush=True)
            traceback.print_exc()
    if artifact:
        with open(args.artifact, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print(f"# wrote {args.artifact}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
