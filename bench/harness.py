"""The benchmark harness: builds one cell from `BENCHMARK.json` and the files
it names, runs its set-up, its measured window and its correctness check,
and returns the result line.

Nothing about a cell lives here. A cell is found by name:

- `BENCHMARK.json` -> the workload entry (config, traffic, chips) and the
  metrics it reports;
- the configuration's `file` (`bench/configs/<config>.json`): shape, dtype,
  lambda2, grid rule, generator settings;
- `bench/traffic/<traffic>.json`: the job kind, its parameters, the data
  seeds of the run's fixed problems (`problems`), and the limit of every
  number the check compares;
- `bench/jobs/<job>.py`: the job kind's code (a `Job` class);
- `bench/metrics/<metric>.py`: one reader per metric, `read(rec)`, which
  returns a number or None (nothing to read: the metric is left out).

A run's data: the traffic's fixed problems, the same in every run, and one
more made from `--seed` (the run's own problem). Jobs run back to back
from one client (a closed loop) in cycles: one job on each fixed problem,
in an order drawn from `--seed`. Every run thus does the same work in
another order, and a time per point averages over several problems, not
the rounding luck of one. Another cycle starts only while the last one's
time still fits before `seconds` is up (the first always runs); the window
runs from the first job's dispatch until the answers of the last cycle are
on the host. After the window, one untimed job of the same executable
solves the run's own problem, so that `correct` sees a new problem in
every run, besides every point of every timed job.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: seconds of the window the traced run traces, from its start: a device
#: trace of a solver loop holds an event per operation, hundreds of
#: thousands a second, so a whole window would not fit a run's time
TRACE_S = 2.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str, t0: float) -> None:
    print(f"bench [{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, spec_path: Path = ROOT / "BENCHMARK.json"):
    """(spec, workload entry, configuration, traffic) for a cell's name."""
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {spec_path.name}; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((spec_path.parent / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, config, traffic


def cell_metrics(spec: dict, workload: str, kind: str) -> list:
    """The `end_to_end` or `per_layer` entries this cell reports."""
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


class _CompileLog:
    """Host times at which JAX's backend compile events ended: a program
    compiled, or loaded from the persistent compilation cache."""

    def __init__(self, monitoring):
        self.ends = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.ends.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.ends)


class _TraceSlice:
    """A profiler trace of the window's first TRACE_S seconds, stopped from
    a timer thread so that it can end inside a job."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.timer = threading.Timer(TRACE_S, self._stop)

    def start(self):
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.timer.start()

    def _stop(self):
        with self.jax.profiler.TraceAnnotation("bench.trace_end"):
            pass
        self.jax.profiler.stop_trace()

    def reduce(self):
        """The reduced trace (`trace_reduce.reduce_trace`), or None."""
        from bench import trace_reduce

        self.timer.join()
        try:
            return trace_reduce.reduce_trace(
                trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_info(jax, devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(max(peaks))}


def peak_table(kind: str) -> dict:
    """The published peaks of one chip of `kind` (`bench/peaks.json`); a
    device that is not in the table is an error, not a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where JAX_COMPILATION_CACHE_DIR says; every program is kept, so a
    second run of a cell compiles nothing. The program's own helper
    (`repro.utils.enable_compile_cache`) follows the same rule; the
    benchmark keeps its own so that a change to the program cannot move
    where, or whether, the benchmark's programs are cached."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _problems(jax, data, config: dict, n: int, p: int, seeds) -> list:
    """(X, y) on the device for each data seed."""
    return [jax.block_until_ready(data.make_regression(
        n, p, data_seed=s, dtype=config["dtype"], **config["generator"]))
        for s in seeds]


def _cycle_order(seed: int, k: int) -> list:
    """The order in which a run's cycles visit the k fixed problems."""
    return [int(i) for i in np.random.default_rng(seed).permutation(k)]


def _run_job(jax, job, i: int) -> dict:
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        handle = job.dispatch(i)
    with jax.profiler.TraceAnnotation("bench.block"):
        job.block(handle)
    with jax.profiler.TraceAnnotation("bench.fetch"):
        return job.fetch(handle)


def _readings(job, answers, refs, limits, checks, suffix=""):
    """Compare answers with the references; adds each number's widest
    reading and its limit to `checks`, returns the points above a limit."""
    bad = 0
    for name, values in job.compare(answers, refs).items():
        values = np.asarray(values, np.float64)
        bad += int(np.sum(~(values <= limits[name])))
        checks[name + suffix] = {
            "value": float(np.max(values)) if values.size else float("inf"),
            "limit": limits[name]}
    return bad


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, require_chip: bool = True, rehearse: bool = False,
             spec_path: Path = ROOT / "BENCHMARK.json") -> dict:
    """Run one cell; returns the result line as a dict.

    `require_chip=False` skips the look for an accelerator (tests);
    `rehearse` takes the configuration's `rehearse` shape instead of its
    own (CPU rehearsals and tests; never a measurement).
    """
    import jax

    from bench import data

    spec, cell, config, traffic = load_cell(workload, spec_path)
    if config["dtype"] == "float64":
        jax.config.update("jax_enable_x64", True)
    devices = jax.devices()[:cell["chips"]]
    if require_chip:
        if devices[0].platform == "cpu":
            raise NoChip("JAX found no accelerator (platform cpu)")
        if len(devices) < cell["chips"]:
            raise NoChip(f"the cell asks for {cell['chips']} chips, JAX "
                         f"found {len(devices)}")
        peak_table(devices[0].device_kind)
    compiles = _CompileLog(jax.monitoring)
    shape = config["rehearse"] if rehearse else config
    n, p = int(shape["n"]), int(shape["p"])
    pool = [int(s) for s in traffic["problems"]]
    log(f"{workload}: data {len(pool)} + 1 problems of {n} x {p} "
        f"{config['dtype']} (seeds {pool} and {seed})", t0)
    problems = _problems(jax, data, config, n, p, pool + [seed])
    own = len(pool)
    job = load_module("jobs", traffic["job"]).Job(config, traffic, problems)
    job.warm()
    setup_s = time.perf_counter() - t0
    log(f"set-up done ({setup_s:.2f}s, {len(compiles.ends)} programs "
        f"compiled or loaded)", t0)

    order = _cycle_order(seed, len(pool))
    answers, tracer = [], _TraceSlice(jax) if trace else None
    if tracer:
        tracer.start()
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        c0 = time.perf_counter()
        for i in order:
            answers.append((i, _run_job(jax, job, i)))
        now = time.perf_counter()
        if now + (now - c0) > deadline:
            break
    end = time.perf_counter()
    points = len(answers) * job.points_per_job
    log(f"window: {len(answers) // len(order)} cycles of {order}, "
        f"{points} points in {end - start:.3f}s", t0)
    device = device_info(jax, devices)
    log(f"memory peak {device['memory_peak_bytes']} bytes", t0)

    breakdown = trace_rec = None
    if tracer:
        trace_rec = tracer.reduce()
        if trace_rec is not None:
            log(f"trace: {trace_rec['window_s']:.3f}s traced, "
                f"{trace_rec['busy_s']:.3f}s busy, device planes "
                f"{trace_rec['devices']}", t0)
            device.update(busy_s=trace_rec["busy_s"],
                          window_s=trace_rec["window_s"])
            breakdown = {"device_ops": trace_rec["device_ops"],
                         "idle_gaps": trace_rec["idle_gaps"]}

    log("the run's own problem, untimed", t0)
    checked = [(own, job.fetch(job.block(job.dispatch(own))))]
    log("reference", t0)
    refs = {}
    for i in sorted({i for i, _ in answers} | {own}):
        X, y = problems[i]
        refs[i] = job.reference(i, np.asarray(X), np.asarray(y))
    limits, checks = traffic["limits"], {}
    failed = _readings(job, answers, refs, limits, checks)
    failed += _readings(job, checked, refs, limits, checks, "_own")

    rec = {"answers": [a for _, a in answers], "points": points,
           "window_s": end - start, "setup_s": setup_s, "trace": trace_rec,
           "compiles_in_window": compiles.between(start, end)}
    metrics = {}
    for m in cell_metrics(spec, workload,
                          "per_layer" if trace else "end_to_end"):
        value = load_module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted = points + len(checked) * job.points_per_job
    result = {"correct": points > 0 and failed == 0, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
