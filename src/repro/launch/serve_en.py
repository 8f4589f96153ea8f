"""Elastic Net serving launcher, now on the continuous-batching runtime:
drive a `ContinuousScheduler` with a reproducible open-loop request stream
(mixed constrained + glmnet-form, adjacent-lambda pattern) and report
runtime-vs-reference throughput, warm-start cache behaviour, executable
reuse, and exactness against direct per-request solves. The synchronous
seed path survives as `ElasticNetEngine.drain_reference()` and is timed as
the baseline every wave.

    PYTHONPATH=src python -m repro.launch.serve_en --requests 24 --waves 3
"""
from __future__ import annotations

import argparse
import time

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

from repro import utils
from repro.baselines import elastic_net_cd
from repro.core import SvenConfig, enet, sven
from repro.runtime import (CONSTRAINED, PENALIZED, ContinuousScheduler,
                           LoadSpec, make_workload, run_open_loop)
from repro.serve import ElasticNetEngine


def _direct_solve(item, cfg: SvenConfig):
    if item.form == PENALIZED:
        return enet(item.X, item.y, item.lam, item.lambda2).beta
    return sven(item.X, item.y, item.lam, item.lambda2, cfg).beta


def _serve_metrics(registry, port: int):
    """Live Prometheus text exposition on a daemon thread (stdlib only).

    Scrape target for the duration of the run: ``GET /metrics`` renders
    `registry.to_prometheus()` at request time, so a scraper polling while
    waves are in flight sees counters move.
    """
    import http.server
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path not in ("/metrics", "/"):
                self.send_error(404)
                return
            body = registry.to_prometheus().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):   # keep the wave report readable
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24, help="requests per wave")
    ap.add_argument("--waves", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", type=int, default=4,
                    help="requests per wave cross-checked against direct "
                         "sven()/enet() solves")
    ap.add_argument("--penalized", type=int, default=2,
                    help="glmnet-form requests per wave (verified against "
                         "coordinate descent)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait", type=float, default=2e-3,
                    help="coalescing window (s) before a deadline launch")
    ap.add_argument("--cache-dir", type=str, default=None,
                    help="persistent warm-start spill directory: solutions "
                         "survive restarts and are shareable across hosts "
                         "(DESIGN.md §11.2)")
    ap.add_argument("--speculate", action="store_true",
                    help="pre-solve predicted next lambda-crawl points in "
                         "idle batch slots (DESIGN.md §11.3)")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="enable structured tracing and export a Chrome-trace "
                         "JSON here on exit (chrome://tracing / Perfetto)")
    ap.add_argument("--metrics-json", type=str, default=None,
                    help="write the final metrics-registry snapshot (JSON)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live Prometheus text exposition on this port "
                         "for the duration of the run (GET /metrics)")
    ap.add_argument("--events-out", type=str, default=None,
                    help="dump the structured event ring as JSONL on exit")
    args = ap.parse_args(argv)
    utils.enable_compile_cache()

    if args.trace_out is not None:
        from repro.obs import enable_tracing

        enable_tracing()

    cfg = SvenConfig()
    total = args.requests + args.penalized
    cache = "default"
    if args.cache_dir is not None:
        from repro.runtime import TieredSolutionCache

        cache = TieredSolutionCache(spill_dir=args.cache_dir)
    sched = ContinuousScheduler(cfg, max_batch=args.max_batch,
                                max_wait=args.max_wait, cache=cache,
                                speculate=args.speculate)
    reference = ElasticNetEngine(cfg, max_batch=args.max_batch, cache=None)

    metrics_server = None
    if args.metrics_port is not None:
        metrics_server = _serve_metrics(sched.registry, args.metrics_port)
        print(f"[serve_en] Prometheus exposition on "
              f"http://127.0.0.1:{metrics_server.server_address[1]}/metrics")

    new_execs_last_wave = 0
    for wave in range(args.waves):
        execs0 = sched.stats.bucket_shapes
        batches0 = sched.stats.batches
        padded0 = sched.stats.padded_slots
        # data_seed pins the datasets: every wave revisits the same problems
        # at freshly drawn adjacent lambdas — steady-state serving traffic,
        # which is what exercises both executable reuse and the warm cache.
        spec = LoadSpec(n_requests=total,
                        penalized_fraction=args.penalized / max(total, 1),
                        seed=args.seed + wave, data_seed=args.seed)
        workload = make_workload(spec)

        out = run_open_loop(sched, workload)
        results, ids = out["results"], out["ids"]

        # synchronous baseline: the seed engine's cold blocking drain over
        # the SAME wave (its own executables; first wave pays compile)
        ref_ids = []
        for item in workload:
            if item.form == PENALIZED:
                ref_ids.append(reference.submit_penalized(
                    item.X, item.y, item.lam, item.lambda2))
            else:
                ref_ids.append(reference.submit(
                    item.X, item.y, item.lam, item.lambda2))
        t0 = time.perf_counter()
        ref_results = reference.drain_reference()
        reference_s = time.perf_counter() - t0

        max_dev = ref_dev = pen_dev = 0.0
        n_verified = 0
        for item, rid, ref_rid in zip(workload, ids, ref_ids):
            ref_dev = max(ref_dev, float(jnp.abs(
                results[rid].beta - ref_results[ref_rid].beta).max()))
            if n_verified < args.verify:
                direct = _direct_solve(item, cfg)
                max_dev = max(max_dev,
                              float(jnp.abs(results[rid].beta - direct).max()))
                n_verified += 1
            if item.form == PENALIZED:
                beta_cd = elastic_net_cd(item.X, item.y, item.lam,
                                         item.lambda2).beta
                pen_dev = max(pen_dev, float(jnp.abs(
                    results[rid].beta - beta_cd).max()))

        new_execs_last_wave = sched.stats.bucket_shapes - execs0
        print(f"[serve_en] wave {wave}: {total} reqs "
              f"({args.penalized} pen) in {sched.stats.batches - batches0} "
              f"batches | runtime {out['wall_seconds']*1e3:7.1f} ms  "
              f"reference {reference_s*1e3:7.1f} ms "
              f"({reference_s/max(out['wall_seconds'],1e-9):4.1f}x) | "
              f"p50 {out['p50_latency_s']*1e3:6.1f} ms "
              f"p99 {out['p99_latency_s']*1e3:6.1f} ms | "
              f"new_executables={new_execs_last_wave} "
              f"padded_slots={sched.stats.padded_slots - padded0} "
              f"cache_hit_rate={sched.cache.hit_rate:.2f} | "
              f"max|beta-beta_direct|={max_dev:.2e} "
              f"ref_dev={ref_dev:.2e} pen_dev={pen_dev:.2e}")
        assert max_dev < 1e-6, "runtime diverged from direct solves"
        assert ref_dev < 1e-6, "runtime diverged from drain_reference()"
        assert pen_dev < 1e-5, "penalized path diverged from coordinate descent"

    steady = ("last wave added none" if new_execs_last_wave == 0
              else f"last wave still added {new_execs_last_wave}")
    print(f"[serve_en] done: {sched.stats.requests} runtime requests, "
          f"{sched.stats.bucket_shapes} compiled executables ({steady}); "
          f"launches: {sched.stats.launched_full} full / "
          f"{sched.stats.launched_deadline} deadline / "
          f"{sched.stats.launched_flush} flush; "
          f"warm-start hits {sched.cache.hits}/"
          f"{sched.cache.hits + sched.cache.misses}.")

    if args.trace_out is not None:
        from repro.obs import get_tracer

        get_tracer().export(args.trace_out)
        print(f"[serve_en] trace -> {args.trace_out} "
              f"({len(get_tracer().spans())} events)")
    if args.metrics_json is not None:
        import json

        with open(args.metrics_json, "w") as fh:
            json.dump(sched.registry.snapshot(), fh, indent=2, sort_keys=True)
        print(f"[serve_en] metrics snapshot -> {args.metrics_json}")
    if args.events_out is not None:
        from repro.obs import default_events

        default_events().dump(args.events_out)
        print(f"[serve_en] events -> {args.events_out}")
    if metrics_server is not None:
        metrics_server.shutdown()


if __name__ == "__main__":
    run()
