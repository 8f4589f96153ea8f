"""Contracts of the continuous-batching serving runtime (DESIGN.md §8):

- scheduler admission: full buckets launch immediately, expired deadlines
  launch partial buckets, priorities order overflow, failed dispatches
  re-queue;
- runtime drain == drain_reference == direct solves, for both problem forms;
- warm-start cache: neighborhood hit/miss semantics, eviction bounds, and
  the serving property that a warm re-solve returns the same solution in
  fewer solver iterations;
- warm operands on the batch entry points (`sven_batch` warm_alpha/warm_w,
  `enet_batch` warm/has_warm) leave solutions unchanged;
- penalized-form padding invariance (ISSUE 4 satellite): zero-row/zero-
  column padding through `submit_penalized` returns the exact unpadded
  `enet` solution — the penalized mirror of the constrained padding test;
- online rank-1 updates == from-scratch solves on the accumulated rows;
- metrics percentiles and loadgen reproducibility; a repeated stream is
  served warm, and with launches a pure function of the stream (no
  deadlines) it adds no traces.
"""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import enet, sven, sven_batch, trace_counts
from repro.core.api import EnetCarry, enet_batch
from repro.core.elastic_net import lambda1_max
from repro.data.synthetic import make_regression
from repro.runtime import (ContinuousScheduler, LoadSpec, OnlineElasticNet,
                           SolutionCache, WarmEntry, fingerprint_problem,
                           make_workload, percentile, run_open_loop)
from repro.runtime.cache import CONSTRAINED
from repro.serve import ElasticNetEngine

ATOL = 1e-6


def _problem(n, p, seed=0):
    X, y, _ = make_regression(n, p, k_true=max(3, p // 6), rho=0.3, seed=seed)
    t_scale = 0.2 * float(jnp.sum(jnp.abs(X.T @ y))) / n
    return X, y, t_scale


# ---------------------------------------------------------------------------
# scheduler admission / launch policy
# ---------------------------------------------------------------------------

def test_full_bucket_launches_on_submit():
    sched = ContinuousScheduler(max_batch=4, max_wait=None, min_n=16, min_p=8)
    X, y, t = _problem(20, 10, seed=0)
    for i in range(4):
        sched.submit(X, y, t=t * (1 + 0.01 * i), lambda2=1.0)
    assert sched.stats.launched_full == 1      # 4th submit filled the bucket
    assert sched.pending_requests == []
    assert sched.in_flight_count + len(sched.harvest(block=True)) >= 4


def test_deadline_launch_on_poll():
    sched = ContinuousScheduler(max_batch=64, max_wait=0.01)
    X, y, t = _problem(20, 10, seed=1)
    sched.submit(X, y, t=t, lambda2=1.0)
    assert sched.stats.launched_deadline == 0  # window still open
    time.sleep(0.02)
    sched.poll()
    assert sched.stats.launched_deadline == 1  # expired -> partial launch
    out = sched.harvest(block=True)
    assert len(out) == 1


def test_priority_orders_overflowing_bucket():
    sched = ContinuousScheduler(max_batch=2, max_wait=None)
    X, y, t = _problem(20, 10, seed=2)
    low = sched.submit(X, y, t=t, lambda2=1.0, priority=0)
    mid = sched.submit(X, y, t=t * 1.1, lambda2=1.0, priority=1)
    # bucket is full (max_batch=2): the two highest-priority requests must
    # have launched together, leaving nothing pending before the third
    hi = sched.submit(X, y, t=t * 1.2, lambda2=1.0, priority=5)
    pending = [r.req_id for r in sched.pending_requests]
    assert pending == [hi]
    out = sched.drain()
    assert set(out) == {low, mid, hi}


def test_expired_low_priority_request_not_stranded_by_overflow():
    """A deadline pop whose request gets priority-bumped out of the launch
    chunk must re-arm, so the remainder launches on the same poll — the
    request can't be stranded with no heap entry until a manual flush."""
    sched = ContinuousScheduler(max_batch=2, max_wait=0.01,
                                auto_launch_full=False)
    X, y, t = _problem(20, 10, seed=20)
    low = sched.submit(X, y, t=t, lambda2=1.0, priority=0)
    sched.submit(X, y, t=t * 1.1, lambda2=1.0, priority=5)
    sched.submit(X, y, t=t * 1.2, lambda2=1.0, priority=5)
    time.sleep(0.02)
    sched.poll()
    assert sched.pending_requests == []        # low launched too, same poll
    assert low in sched.harvest(block=True)


def test_metrics_survive_reset_with_preexisting_requests():
    """run_open_loop resets the recorder; requests submitted BEFORE the run
    must still drain (untracked, not KeyError)."""
    from repro.runtime import make_workload as mw
    sched = ContinuousScheduler(max_batch=4, max_wait=None)
    X, y, t = _problem(20, 10, seed=21)
    old = sched.submit(X, y, t=t, lambda2=1.0)
    spec = LoadSpec(n_requests=4, n_datasets=1, shapes=((20, 10),), seed=5)
    out = run_open_loop(sched, mw(spec))
    assert out["n_completed"] == 4             # reset scoped to the run
    assert old in out["results"]               # old request drained fine


def test_dispatch_failure_requeues(monkeypatch):
    sched = ContinuousScheduler(max_batch=8, max_wait=None)
    X, y, t = _problem(20, 10, seed=3)
    rid = sched.submit(X, y, t=t, lambda2=1.0)

    def boom(*a, **k):
        raise RuntimeError("boom")

    monkeypatch.setattr(sched, "_dispatch", boom)
    with pytest.raises(RuntimeError, match="boom"):
        sched.drain()
    assert [r.req_id for r in sched.pending_requests] == [rid]
    monkeypatch.undo()
    out = sched.drain()
    np.testing.assert_allclose(out[rid].beta, sven(X, y, t, 1.0).beta,
                               atol=ATOL)


def test_requeue_rechecks_deadline(monkeypatch):
    """Regression (ISSUE 6 satellite): requeue-on-failure used to re-admit
    without re-checking the deadline, so an expired request went straight
    back into the launch that just failed — a tight retry loop. An expired
    request must instead complete terminally with status="deadline_exceeded"
    (beta None), while unexpired requests are re-admitted and still solve."""
    fake_now = [0.0]
    sched = ContinuousScheduler(max_batch=8, max_wait=0.5,
                                clock=lambda: fake_now[0])
    X, y, t = _problem(20, 10, seed=22)
    rid_live = sched.submit(X, y, t=t, lambda2=1.0)
    rid_dead = sched.submit(X, y, t=t * 1.1, lambda2=1.0, deadline=1.0)

    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("boom")

    monkeypatch.setattr(sched, "_dispatch", boom)
    fake_now[0] = 2.0   # both deadlines (0 + max_wait = 0.5, and 1.0) passed
    with pytest.raises(RuntimeError, match="boom"):
        sched.flush()
    monkeypatch.undo()
    assert sched.pending_requests == []
    res_dead = sched.result(rid_dead)
    assert res_dead.status == "deadline_exceeded" and res_dead.beta is None
    res_live = sched.result(rid_live)
    assert res_live.status == "deadline_exceeded" and res_live.beta is None

    # unexpired arm: deadline far in the (fake) future survives the failed
    # dispatch, stays pending, and solves once dispatch works again
    rid2 = sched.submit(X, y, t=t, lambda2=1.0, deadline=100.0)
    monkeypatch.setattr(sched, "_dispatch", boom)
    with pytest.raises(RuntimeError, match="boom"):
        sched.flush()
    monkeypatch.undo()
    assert [r.req_id for r in sched.pending_requests] == [rid2]
    out = sched.drain()
    assert out[rid2].status == "ok"
    np.testing.assert_allclose(out[rid2].beta, sven(X, y, t, 1.0).beta,
                               atol=ATOL)


def test_submit_validation():
    sched = ContinuousScheduler()
    X, y, t = _problem(20, 10, seed=4)
    with pytest.raises(ValueError, match="exactly one"):
        sched.submit(X, y, t=t, lambda1=1.0)
    with pytest.raises(ValueError, match="exactly one"):
        sched.submit(X, y)
    with pytest.raises(ValueError, match="bad shapes"):
        sched.submit(X, y[:-1], t=t)
    with pytest.raises(ValueError, match="lambda1 >= 0"):
        sched.submit(X, y, lambda1=-1.0)
    with pytest.raises(ValueError, match="lambda2 >= 0"):
        sched.submit(X, y, lambda1=1.0, lambda2=-1.0)


def test_result_blocks_for_one_request_only():
    sched = ContinuousScheduler(max_batch=8, max_wait=None)
    Xa, ya, ta = _problem(20, 10, seed=5)
    Xb, yb, tb = _problem(40, 20, seed=6)     # different bucket
    other = sched.submit(Xa, ya, t=ta, lambda2=1.0)
    mine = sched.submit(Xb, yb, t=tb, lambda2=2.0)
    res = sched.result(mine)
    np.testing.assert_allclose(res.beta, sven(Xb, yb, tb, 2.0).beta, atol=ATOL)
    # the other bucket was left alone and still drains
    assert [r.req_id for r in sched.pending_requests] == [other]
    assert set(sched.drain()) == {other}


# ---------------------------------------------------------------------------
# runtime drain == reference drain == direct solves
# ---------------------------------------------------------------------------

def test_drain_matches_reference_and_direct_mixed_forms():
    engine = ElasticNetEngine(max_batch=8)
    reference = ElasticNetEngine(max_batch=8, cache=None)
    items = []
    for s, (n, p) in enumerate([(26, 12), (26, 12), (33, 17), (40, 9)]):
        X, y, t = _problem(n, p, seed=30 + s)
        lam1 = 0.35 * float(lambda1_max(X, y))
        items.append((X, y, t, lam1, 0.5 + s))
    ids, ref_ids = [], []
    for X, y, t, lam1, lam2 in items:
        ids.append((engine.submit(X, y, t, lam2),
                    engine.submit_penalized(X, y, lam1, lam2)))
        ref_ids.append((reference.submit(X, y, t, lam2),
                        reference.submit_penalized(X, y, lam1, lam2)))
    out = engine.drain()
    ref_out = reference.drain_reference()
    for (X, y, t, lam1, lam2), (cid, pid), (rc, rp) in zip(items, ids, ref_ids):
        np.testing.assert_allclose(out[cid].beta, sven(X, y, t, lam2).beta,
                                   atol=ATOL)
        np.testing.assert_allclose(out[pid].beta, enet(X, y, lam1, lam2).beta,
                                   atol=ATOL)
        np.testing.assert_allclose(out[cid].beta, ref_out[rc].beta, atol=ATOL)
        np.testing.assert_allclose(out[pid].beta, ref_out[rp].beta, atol=ATOL)


# ---------------------------------------------------------------------------
# warm-start cache
# ---------------------------------------------------------------------------

def test_cache_neighborhood_and_eviction():
    cache = SolutionCache(max_problems=2, per_problem=2, neighborhood=0.5)
    z = np.zeros(4)

    def entry(lam):
        return WarmEntry(lam=lam, lambda2=1.0, alpha=z, w=z, beta=z,
                         t=lam, nu=0.0)

    cache.insert("fpA", CONSTRAINED, entry(1.0))
    assert cache.lookup("fpA", CONSTRAINED, 1.2, 1.0).lam == 1.0   # near hit
    assert cache.lookup("fpA", CONSTRAINED, 3.0, 1.0) is None      # too far
    assert cache.lookup("fpA", CONSTRAINED, 1.0, 10.0) is None     # l2 far
    assert cache.lookup("fpB", CONSTRAINED, 1.0, 1.0) is None      # no data
    assert (cache.hits, cache.misses) == (1, 3)
    # per-problem bound: 3 distinct lambdas keep only the latest 2
    cache.insert("fpA", CONSTRAINED, entry(2.0))
    cache.insert("fpA", CONSTRAINED, entry(4.0))
    assert len(cache) == 2
    assert cache.lookup("fpA", CONSTRAINED, 1.0, 1.0) is None      # evicted
    # same-lambda re-insert replaces, never grows
    cache.insert("fpA", CONSTRAINED, entry(4.0))
    assert len(cache) == 2
    # LRU problem bound
    cache.insert("fpB", CONSTRAINED, entry(1.0))
    cache.insert("fpC", CONSTRAINED, entry(1.0))
    assert len(cache._store) == 2


def test_fingerprint_sensitivity():
    X, y, _ = _problem(20, 10, seed=7)
    fp1 = fingerprint_problem(X, y)
    assert fp1 == fingerprint_problem(np.asarray(X), np.asarray(y))
    X2 = np.asarray(X).copy()
    X2[0, 0] += 1e-12
    assert fp1 != fingerprint_problem(X2, y)


def test_warm_resolve_same_solution_fewer_iters():
    """The serving property: adjacent-lambda traffic re-solves warm to the
    SAME answer with less solver work."""
    X, y, t = _problem(48, 16, seed=8)
    cold = ContinuousScheduler(max_batch=4, max_wait=None, cache=None)
    warm = ContinuousScheduler(max_batch=4, max_wait=None)
    lams = [t, t * 1.05, t * 0.95, t * 1.02]
    cold_ids = [cold.submit(X, y, t=l, lambda2=1.0) for l in lams]
    cold_out = cold.drain()
    warm_first = warm.submit(X, y, t=t, lambda2=1.0)
    warm.drain()                                   # seeds the cache
    warm_ids = [warm.submit(X, y, t=l, lambda2=1.0) for l in lams[1:]]
    warm_out = warm.drain()
    assert warm.cache.hits >= 3
    cold_iters = warm_iters = 0
    for wid, cid, lam in zip(warm_ids, cold_ids[1:], lams[1:]):
        np.testing.assert_allclose(warm_out[wid].beta, cold_out[cid].beta,
                                   atol=ATOL)
        np.testing.assert_allclose(warm_out[wid].beta,
                                   sven(X, y, lam, 1.0).beta, atol=ATOL)
        cold_iters += int(cold_out[cid].iters)
        warm_iters += int(warm_out[wid].iters)
    assert warm_iters <= cold_iters, (warm_iters, cold_iters)


def test_speculative_presolve_rides_the_crawl():
    """DESIGN.md §11.3: a geometric lambda crawl (the glmnet grid shape)
    gets its NEXT point pre-solved in a padding slot, so by the time the
    client asks for it the exact point is already cached — and speculation
    never changes the answer or the client-facing hit accounting."""
    X, y, t = _problem(40, 12, seed=12)
    fp = fingerprint_problem(X, y)
    sched = ContinuousScheduler(max_batch=4, max_wait=None, speculate=True)
    lams = [t, 0.8 * t, 0.8 * 0.8 * t]     # exact ratio-0.8 crawl

    sched.submit(X, y, t=lams[0], lambda2=1.0)
    sched.drain()
    assert sched.stats.speculative_slots == 0, "one point is not a crawl"
    assert sched.cache.hits + sched.cache.misses == 1, (
        "speculative probes must not touch the client hit/miss counters")

    sched.submit(X, y, t=lams[1], lambda2=1.0)
    sched.drain()
    assert sched.stats.speculative_slots >= 1, (
        "two crawl points must trigger a padding-slot pre-solve")
    assert sched.cache.hits + sched.cache.misses == 2
    # the geometric continuation last*(last/prev) = 0.64*t is solved ALREADY
    assert sched.cache.probe(fp, CONSTRAINED, lams[2], 1.0)

    hits_before = sched.cache.hits
    r2 = sched.submit(X, y, t=lams[2], lambda2=1.0)
    out = sched.drain()
    assert sched.cache.hits == hits_before + 1, (
        "the crawl's next request must warm-start off the speculation")
    np.testing.assert_allclose(out[r2].beta, sven(X, y, lams[2], 1.0).beta,
                               atol=ATOL)


def test_batch_warm_operands_leave_solution_unchanged():
    X, y, t = _problem(30, 10, seed=9)
    ts = jnp.asarray([t, t * 1.1])
    base = sven_batch(X, y, ts, 1.0)
    warm = sven_batch(X, y, ts, 1.0, warm_alpha=base.alpha, warm_w=base.w)
    np.testing.assert_allclose(warm.beta, base.beta, atol=ATOL)

    lam1s = 0.4 * float(lambda1_max(X, y)) * jnp.asarray([1.0, 0.9])
    pts, carry = enet_batch(X, y, lam1s, 1.0, return_carry=True)
    # has_warm=False must be EXACTLY the cold path
    zeros = EnetCarry(*(jnp.zeros_like(f) for f in carry))
    pts_cold = enet_batch(X, y, lam1s, 1.0, warm=zeros,
                          has_warm=jnp.zeros(2, bool))
    np.testing.assert_allclose(pts_cold.beta, pts.beta, atol=0)
    pts_warm = enet_batch(X, y, lam1s, 1.0, warm=carry,
                          has_warm=jnp.ones(2, bool))
    np.testing.assert_allclose(pts_warm.beta, pts.beta, atol=ATOL)
    with pytest.raises(ValueError, match="given together"):
        enet_batch(X, y, lam1s, 1.0, warm=carry)


# ---------------------------------------------------------------------------
# penalized-form padding invariance (satellite): zero rows/columns through
# submit_penalized leave the solution exactly the unpadded enet solution
# ---------------------------------------------------------------------------

def _assert_penalized_padding_exact(n, p, seed, lam_frac, lam2):
    X, y, _ = make_regression(n, p, k_true=max(2, p // 4), rho=0.3, seed=seed)
    lam1 = lam_frac * float(lambda1_max(X, y))
    engine = ElasticNetEngine(min_n=16, min_p=8, cache=None)
    rid = engine.submit_penalized(X, y, lam1, lam2)
    res = engine.drain()[rid]
    bn, bp = res.bucket
    assert bn > n or bp > p or (bn, bp) == (n, p)  # really padded (or exact)
    ref = enet(X, y, lam1, lam2)
    assert res.beta.shape == (p,)
    np.testing.assert_allclose(res.beta, ref.beta, atol=ATOL)
    # screened-out coordinates survive the padding as EXACT zeros
    np.testing.assert_array_equal(np.asarray(res.beta) == 0.0,
                                  np.asarray(ref.beta) == 0.0)


@pytest.mark.parametrize("n,p,lam_frac,lam2",
                         [(19, 7, 0.5, 1.0),    # pads rows and columns
                          (23, 11, 0.25, 0.5),  # pads both, light penalty
                          (32, 8, 0.6, 2.0)])   # exact-n bucket, pads p only
def test_penalized_padding_invariance(n, p, lam_frac, lam2):
    _assert_penalized_padding_exact(n, p, seed=50 + n, lam_frac=lam_frac,
                                    lam2=lam2)


@settings(max_examples=6, deadline=None)
@given(st.integers(10, 40), st.integers(4, 20), st.integers(0, 99),
       st.floats(0.15, 0.7), st.floats(0.1, 3.0))
def test_penalized_padding_invariance_property(n, p, seed, lam_frac, lam2):
    _assert_penalized_padding_exact(n, p, seed, lam_frac, lam2)


# ---------------------------------------------------------------------------
# online rank-1 updates
# ---------------------------------------------------------------------------

def test_online_matches_from_scratch_solves():
    X, y, t = _problem(60, 12, seed=10)
    online = OnlineElasticNet(p=12)
    online.update(X[:40], y[:40])
    s1 = online.solve(t, 1.0)
    np.testing.assert_allclose(s1.beta, sven(X[:40], y[:40], t, 1.0).beta,
                               atol=ATOL)
    np.testing.assert_allclose(s1.kkt, sven(X[:40], y[:40], t, 1.0).kkt,
                               atol=1e-6)
    for i in range(40, 60):                      # rank-1 row arrivals
        online.update(X[i], y[i])
    assert online.n == 60
    s2 = online.solve(t, 1.0)
    ref = sven(X, y, t, 1.0)
    np.testing.assert_allclose(s2.beta, ref.beta, atol=ATOL)
    # warm re-solve at a nearby budget: same answer as cold, fewer iters
    s3 = online.solve(t * 1.03, 1.0)
    cold = sven(X, y, t * 1.03, 1.0)
    np.testing.assert_allclose(s3.beta, cold.beta, atol=ATOL)
    assert int(s3.iters) <= int(cold.iters)


def test_online_validation():
    online = OnlineElasticNet(p=5)
    with pytest.raises(ValueError, match="no rows"):
        online.solve(1.0)
    with pytest.raises(ValueError, match="bad shapes"):
        online.update(np.zeros((3, 4)), np.zeros(3))


# ---------------------------------------------------------------------------
# metrics + loadgen
# ---------------------------------------------------------------------------

def test_percentile_interpolation():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 4.0
    assert percentile(xs, 50) == 2.5
    with pytest.raises(ValueError):
        percentile([], 50)


LOADGEN_CASES = {
    # partial buckets launch at their deadline
    "deadline": (dict(n_requests=10, n_datasets=2, penalized_fraction=0.3,
                      shapes=((20, 10), (30, 14)), seed=3),
                 dict(max_batch=4, max_wait=0.002)),
    # buckets launch when full or at the closing flush: the launches are a
    # pure function of the stream
    "full_buckets": (dict(n_requests=32, n_datasets=3,
                          penalized_fraction=0.25, seed=7),
                     dict(max_batch=8, max_wait=None)),
}


@pytest.mark.parametrize("case", sorted(LOADGEN_CASES))
def test_loadgen_reproducible_and_complete(case):
    spec_kw, sched_kw = LOADGEN_CASES[case]
    spec = LoadSpec(**spec_kw)
    w1, w2 = make_workload(spec), make_workload(spec)
    assert [i.lam for i in w1] == [i.lam for i in w2]
    assert all((a.X == b.X).all() for a, b in zip(w1, w2))
    # data_seed pins datasets while the lambda stream moves
    w3 = make_workload(dataclasses.replace(spec, seed=spec.seed + 1,
                                           data_seed=spec.seed))
    fp1 = {fingerprint_problem(i.X, i.y) for i in w1}
    fp3 = {fingerprint_problem(i.X, i.y) for i in w3}
    assert fp3 <= fp1 and [i.lam for i in w3] != [i.lam for i in w1]

    sched = ContinuousScheduler(**sched_kw)
    out = run_open_loop(sched, w1)
    n = spec.n_requests
    assert out["n_completed"] == n and len(out["results"]) == n
    assert out["p99_latency_s"] >= out["p50_latency_s"] > 0
    for item, rid in zip(w1, out["ids"]):
        ref = (enet(item.X, item.y, item.lam, item.lambda2).beta
               if item.form == "penalized"
               else sven(item.X, item.y, item.lam, item.lambda2).beta)
        np.testing.assert_allclose(out["results"][rid].beta, ref, atol=ATOL)

    # the same stream again: the warm-start cache serves it and, where the
    # launches do not depend on the clock, no entry point retraces
    traces, hits = trace_counts(), sched.cache.hits
    again = run_open_loop(sched, w1)
    assert again["n_completed"] == n and sched.cache.hits > hits
    if sched_kw["max_wait"] is None:
        assert trace_counts() == traces
