"""Unified telemetry layer (ISSUE 9, DESIGN.md §12).

Pillar-by-pillar: the metrics registry (labeled instruments, exponential
histograms, the consuming delta protocol), the structured tracer (span
taxonomy, Chrome-trace round trip, zero recording when disabled), the
bounded event ring, and their integration into the serving runtime — the
stats/cache shims stay equal to the registry they now read through, every
admitted request lands in exactly one terminal-status counter, and the
fleet-merged worker counters survive a SIGKILL without double counting.
The timing-discipline lint (reprolint rule TIM001, formerly
tools/check_timing.py) runs as a test so a bare ``time.time()`` in
runtime/ fails here before it fails CI.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.data.synthetic import make_regression
from repro.obs import (EventLog, MetricsRegistry, SolveLog, SolveRecord,
                       Tracer, default_registry, disable_tracing,
                       enable_tracing, get_tracer)
from repro.obs.metrics import ExponentialHistogram
from repro.runtime import ContinuousScheduler, LoadSpec, make_workload, \
    run_open_loop


def _problem(n, p, seed=0):
    X, y, _ = make_regression(n, p, k_true=max(3, p // 6), rho=0.3, seed=seed)
    import jax.numpy as jnp
    t_scale = 0.2 * float(jnp.sum(jnp.abs(X.T @ y))) / n
    return X, y, t_scale


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    c = reg.counter("requests_terminal_total", "t", ("status",))
    c.inc(status="ok")
    c.inc(2, status="ok")
    c.inc(status="aborted")
    assert c.value(status="ok") == 3
    assert c.total() == 4
    assert c.series() == {("ok",): 3.0, ("aborted",): 1.0}
    with pytest.raises(ValueError):
        c.inc(wrong="label")
    with pytest.raises(ValueError):          # same name, different labels
        reg.counter("requests_terminal_total", "t", ("reason",))
    with pytest.raises(ValueError):          # same name, different kind
        reg.gauge("requests_terminal_total")


def test_exponential_histogram_quantiles():
    h = ExponentialHistogram()
    vals = [10 ** (-6 + 5 * i / 999) for i in range(1000)]   # 1us .. 100ms
    for v in vals:
        h.observe(v)
    ref = sorted(vals)
    for q in (50, 90, 99):
        exact = ref[int(q / 100 * (len(ref) - 1))]
        assert abs(h.quantile(q) - exact) / exact < 0.09, (q, h.quantile(q))
    assert h.count == 1000
    assert h.quantile(0) == h.min and h.quantile(100) == h.max


def test_histogram_merge_matches_union():
    a, b = ExponentialHistogram(), ExponentialHistogram()
    for i in range(100):
        a.observe(1e-4 * (i + 1))
        b.observe(1e-2 * (i + 1))
    union = ExponentialHistogram()
    for i in range(100):
        union.observe(1e-4 * (i + 1))
        union.observe(1e-2 * (i + 1))
    a.merge(b)
    assert a.count == union.count and a.max == union.max
    assert a.quantile(50) == union.quantile(50)


def test_counter_deltas_consume_and_merge():
    """The multihost piggyback protocol: deltas are consumed by the snapshot
    (second call empty), merge reconstructs totals, and a reset clears the
    watermark so no negative delta is ever shipped."""
    reg = MetricsRegistry()
    c = reg.counter("runtime_requests_total", "r")
    c.inc(5)
    d1 = reg.counter_deltas()
    assert d1["runtime_requests_total"]["deltas"] == [[[], 5.0]]
    assert reg.counter_deltas() == {}            # consumed
    c.inc(2)
    fleet = MetricsRegistry()
    fleet.merge_counter_deltas(d1)
    fleet.merge_counter_deltas(reg.counter_deltas())
    assert fleet.counter("runtime_requests_total").total() == 7
    reg.reset_instrument("runtime_requests_total")
    c.inc(1)
    d3 = reg.counter_deltas()                    # post-reset: +1, never -6
    assert d3["runtime_requests_total"]["deltas"] == [[[], 1.0]]


def test_snapshot_and_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("launches_total", "n", ("reason",)).inc(reason="full")
    reg.histogram("latency_seconds", "lat").observe(0.25)
    snap = reg.snapshot()
    assert snap["launches_total"]["values"]['reason="full"'] == 1.0
    assert snap["latency_seconds"]["values"]["_"]["count"] == 1
    json.dumps(snap)                             # plain-JSON by construction
    text = reg.to_prometheus()
    assert '# TYPE launches_total counter' in text
    assert 'launches_total{reason="full"} 1' in text
    assert 'latency_seconds_bucket{le="+Inf"} 1' in text
    assert text.count("latency_seconds_sum") == 1


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_spans_nest_and_export(tmp_path):
    tr = Tracer()
    tr.enabled = True
    with tr.span("outer", bucket=(64, 32)):
        with tr.span("inner"):
            pass
    tr.instant("mark", k=1)
    assert tr.counts() == {"outer": 1, "inner": 1, "mark": 1}
    out = tmp_path / "trace.json"
    tr.export(str(out))
    doc = json.loads(out.read_text())
    events = {e["name"]: e for e in doc["traceEvents"]}
    assert events["outer"]["ph"] == "X" and events["outer"]["dur"] >= 0
    assert events["mark"]["ph"] == "i"
    # nesting: inner starts at/after outer and ends at/before outer's end
    o, i = events["outer"], events["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert i["args"]["parent"] == "outer"
    assert o["args"]["bucket"] == [64, 32]


def test_tracer_disabled_records_nothing():
    tr = Tracer()
    assert not tr.enabled
    with tr.span("ghost"):
        tr.instant("ghost2")
    assert tr.spans() == [] and tr.counts() == {}


def test_tracer_ring_is_bounded():
    tr = Tracer(capacity=8)
    tr.enabled = True
    for i in range(50):
        tr.instant(f"e{i}")
    assert len(tr.spans()) == 8
    assert tr.spans()[-1][1] == "e49"            # newest survive
    assert sum(tr.counts().values()) == 50       # counts keep the true total


# ---------------------------------------------------------------------------
# event ring
# ---------------------------------------------------------------------------

def test_event_ring_bounded_and_jsonl(tmp_path):
    ev = EventLog(capacity=4)
    for i in range(9):
        ev.emit("requeue", host=i)
    recs = ev.records()
    assert len(recs) == 4 and recs[-1]["host"] == 8
    assert ev.counts() == {"requeue": 9}         # rolled-off still counted
    assert ev.emitted == 9
    out = tmp_path / "events.jsonl"
    ev.dump(str(out))
    lines = out.read_text().splitlines()
    assert len(lines) == 4
    for line in lines:
        rec = json.loads(line)
        assert "ts" in rec and rec["kind"] == "requeue"


# ---------------------------------------------------------------------------
# solve log
# ---------------------------------------------------------------------------

def test_solve_log_residual_report():
    log = SolveLog()
    for i in range(4):
        log.add(SolveRecord(bucket=(64, 32), form="constrained", batch=4,
                            b_real=3, route_path="single", modeled_s=0.01,
                            actual_s=0.02, blocked_s=0.001, iters_max=7,
                            iters_mean=5.0, kkt_max=1e-8, keep_fraction=0.4))
    log.add(SolveRecord(bucket=(64, 32), form="constrained", batch=4,
                        b_real=4, route_path="batch", modeled_s=0.0,
                        actual_s=0.05, blocked_s=0.0, iters_max=3,
                        iters_mean=3.0, kkt_max=0.0, keep_fraction=1.0))
    rep = log.residual_report()
    assert rep["n_records"] == 5 and rep["n_unmodeled"] == 1
    single = rep["by_path"]["single"]
    assert single["n"] == 4
    assert abs(single["log10_ratio_mean"] - np.log10(2.0)) < 1e-12


# ---------------------------------------------------------------------------
# runtime integration: shims, span taxonomy, terminal accounting
# ---------------------------------------------------------------------------

def test_scheduler_shims_read_registry_and_spans_cover_lifecycle(tmp_path):
    X, y, t = _problem(32, 16)
    sched = ContinuousScheduler(max_batch=2, max_wait=None)
    tracer = get_tracer()
    n0 = len(tracer.spans())
    enable_tracing()
    try:
        for i in range(4):
            sched.submit(X, y, t=t * (1 + 0.05 * i), lambda2=1.0)
        out = sched.drain()
    finally:
        disable_tracing()
    assert len(out) == 4

    # shim == registry: the legacy attributes are views, not copies
    reg = sched.registry
    assert sched.stats.requests == 4
    assert sched.stats.requests == int(
        reg.counter("runtime_requests_total").total())
    assert sched.cache.hits + sched.cache.misses == int(
        reg.counter("cache_lookups_total", labelnames=("result",)).total())
    term = reg.counter("requests_terminal_total", labelnames=("status",))
    assert term.value(status="ok") == 4          # exactly one terminal each

    # span taxonomy: the full request lifecycle appears in the trace
    names = {s[1] for s in tracer.spans()[n0:]}
    for expected in ("admit", "launch", "warm_start", "harvest.block",
                     "complete"):
        assert expected in names, (expected, names)
    # and the process tracer exports them as a valid Chrome trace
    tracer.export(str(tmp_path / "trace.json"))
    events = json.loads((tmp_path / "trace.json").read_text())["traceEvents"]
    assert events and all(ev.get("ph") in ("X", "i") and "ts" in ev
                          and "name" in ev for ev in events)

    # pillar 3: every dispatch priced and logged
    rep = sched.solve_log.residual_report()
    assert rep["n_records"] >= 2 and rep["n_unmodeled"] == 0
    assert "single" in rep["by_path"]


def test_trace_counts_reads_default_registry():
    from repro.core import reset_trace_counts, trace_counts
    reset_trace_counts()
    assert trace_counts() == {}
    default_registry().counter(
        "solver_traces_total", labelnames=("entry",)).inc(entry="sven")
    assert trace_counts() == {"sven": 1}
    reset_trace_counts()
    assert trace_counts() == {}


# ---------------------------------------------------------------------------
# multihost: fleet merge under host kill — no double counting
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multihost_metric_merge_survives_kill():
    """SIGKILL one worker mid-drain: the coordinator's books must stay
    balanced (each admitted request in exactly one terminal-status series),
    the fleet merge must show the re-solve work WITHOUT double-counting
    delivered requests, and host death must appear in coordinator
    counters + the structured event ring."""
    from repro.obs import default_events
    from repro.runtime.multihost import MultiHostCoordinator

    rng = np.random.default_rng(3)
    X, y = rng.normal(size=(40, 20)), rng.normal(size=40)
    deaths0 = default_events().counts().get("host_death", 0)
    coord = MultiHostCoordinator(n_hosts=2, max_batch=4)
    try:
        ids = [coord.submit(X + 0.01 * k, y, t=1.0) for k in range(8)]
        coord.flush()
        coord.kill_host(0)
        out = coord.drain()
        assert sorted(out) == sorted(ids)
        assert {r.status for r in out.values()} == {"ok"}

        acct = coord.accounting()
        assert acct["admitted"] == 8
        assert acct["terminals"] == {"ok": 8}    # one terminal per request
        assert acct["balanced"] and acct["outstanding"] == 0

        # fleet merge: every DELIVERED result rode in with its host's
        # deltas, so the fleet saw at least the admitted requests. The dead
        # host's unshipped deltas are dropped (never salvaged twice), so
        # the total exceeds admitted only if it shipped before dying —
        # which is exactly the no-double-counting property: requeues
        # change who solved, not how many results were delivered.
        fleet_reqs = int(coord.fleet.counter("runtime_requests_total",
                                             labelnames=()).total())
        assert fleet_reqs >= 8
        assert coord.requeued_batches >= 1

        assert coord.hosts_lost == 1
        assert int(coord.registry.counter("hosts_lost_total").total()) == 1
        snap = coord.metrics_snapshot()
        assert set(snap) == {"coordinator", "fleet", "hosts"}
        assert default_events().counts().get("host_death", 0) == deaths0 + 1
    finally:
        coord.shutdown()


# ---------------------------------------------------------------------------
# overhead guard (gated: timing assertions flake on loaded CI machines)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.skipif(not os.environ.get("REPRO_OVERHEAD_GUARD"),
                    reason="wall-clock gate; set REPRO_OVERHEAD_GUARD=1")
def test_tracing_overhead_within_budget():
    spec = LoadSpec(n_requests=16, n_datasets=2, penalized_fraction=0.0,
                    pattern="adjacent", seed=5)
    workload = make_workload(spec)
    sched = ContinuousScheduler(max_batch=8, max_wait=None)
    run_open_loop(sched, workload)               # compile + warm
    best = {False: float("inf"), True: float("inf")}
    p99 = {False: float("inf"), True: float("inf")}
    try:
        for _ in range(3):
            for enabled in (False, True):
                (enable_tracing if enabled else disable_tracing)()
                out = run_open_loop(sched, workload)
                if out["wall_seconds"] < best[enabled]:
                    best[enabled] = out["wall_seconds"]
                    p99[enabled] = out["p99_latency_s"]
    finally:
        disable_tracing()
    assert best[True] <= 1.10 * best[False], (best, p99)
    assert p99[True] <= 1.10 * p99[False], (best, p99)


# ---------------------------------------------------------------------------
# timing-discipline lint as a test
# ---------------------------------------------------------------------------

def test_runtime_has_no_bare_clock_reads():
    from pathlib import Path

    from tools.reprolint import load_config, run_paths
    root = Path(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    res = run_paths(root, ["src/repro/runtime"], load_config(root),
                    select=("TIM001",))
    assert res.findings == [], (
        "bare time.time()/time.perf_counter() in src/repro/runtime/ — "
        f"route clock reads through repro.obs.clock: {res.findings}")
