"""Fault injection, end to end (ISSUE 8).

Training side (the seed tests): the launcher's supervisor survives an
injected node failure and restart-resumes exactly.

Serving side: the multi-host runtime's failure contract — a worker host
SIGKILLed mid-drain loses ZERO admitted requests (each ends in a terminal
`EnResult.status`: re-solved to "ok", or "deadline_exceeded" /" aborted"
explicitly — never silence), a corrupt or truncated spilled cache entry
degrades to a miss instead of an exception on the serving path, and a
restarted engine recovers its warm-start hit rate from the persistent
spill tier (DESIGN.md §11).
"""
import numpy as np
import pytest

from _subprocess import run_python


def _run_train(tmp, extra):
    import os
    return run_python(
        ["-m", "repro.launch.train", "--arch", "internlm2-1.8b", "--smoke",
         "--batch", "4", "--seq", "64", "--ckpt-dir",
         os.path.join(tmp, "ckpt"), "--ckpt-every", "5", "--log-every", "5"]
        + extra, timeout=900)


@pytest.mark.slow
def test_supervisor_recovers_from_injected_failure(tmp_path):
    r = _run_train(str(tmp_path), ["--steps", "15", "--inject-fault-at", "8"])
    assert "[supervisor] step 8 failed" in r.stdout
    assert "done at step 15" in r.stdout


@pytest.mark.slow
def test_restart_resumes_from_checkpoint(tmp_path):
    r1 = _run_train(str(tmp_path), ["--steps", "10"])
    assert r1.returncode == 0
    r2 = _run_train(str(tmp_path), ["--steps", "20"])
    assert "resumed from step 10" in r2.stdout
    assert "done at step 20" in r2.stdout

    # determinism: an uninterrupted 20-step run lands on the same loss
    r3 = _run_train(str(tmp_path) + "_b", ["--steps", "20"])
    loss_resumed = r2.stdout.strip().splitlines()[-1].split("loss")[-1].strip()
    loss_straight = r3.stdout.strip().splitlines()[-1].split("loss")[-1].strip()
    assert abs(float(loss_resumed) - float(loss_straight)) < 1e-4, (
        loss_resumed, loss_straight, r2.stdout, r3.stdout)


# ---------------------------------------------------------------------------
# serving: multi-host coordinator fault injection
# ---------------------------------------------------------------------------

TERMINAL = {"ok", "deadline_exceeded", "aborted"}


def _problem(seed=0, n=40, p=20):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, p)), rng.normal(size=n)


@pytest.fixture
def coordinator_factory():
    """Build MultiHostCoordinators and guarantee their worker processes are
    reaped even when the test body fails mid-flight."""
    from repro.runtime.multihost import MultiHostCoordinator

    coords = []

    def make(**kw):
        c = MultiHostCoordinator(**kw)
        coords.append(c)
        return c

    yield make
    for c in coords:
        c.shutdown()


@pytest.mark.slow
@pytest.mark.parametrize("spill", [False, True])
def test_kill_host_mid_drain_loses_nothing(coordinator_factory, tmp_path,
                                           spill):
    """The headline contract: SIGKILL a worker while it holds dispatched
    batches; every admitted request must still complete "ok" (no deadlines
    here, so the requeue path re-solves the dead host's work), with or
    without a shared spill tier, at 1e-10 of a direct solve."""
    from repro.core import sven
    X, y = _problem(0)
    coord = coordinator_factory(
        n_hosts=2, max_batch=4,
        cache_dir=str(tmp_path / "spill") if spill else None)
    ids = [coord.submit(X + 0.01 * k, y, t=1.0) for k in range(8)]
    coord.flush()                      # both hosts now hold in-flight work
    coord.kill_host(0)
    out = coord.drain()
    assert sorted(out) == sorted(ids), "silent request drop on host kill"
    assert {r.status for r in out.values()} == {"ok"}
    assert coord.hosts_lost == 1
    assert coord.requeued_batches >= 1, "kill was not detected as a failure"
    for k, rid in enumerate(ids):      # re-solved results are real solutions
        direct = sven(X + 0.01 * k, y, 1.0, 1.0).beta
        np.testing.assert_allclose(np.asarray(out[rid].beta),
                                   np.asarray(direct), atol=1e-10)


@pytest.mark.slow
def test_kill_host_with_deadlines_terminal_statuses(coordinator_factory):
    """With deadlines armed, a killed host's requeued work whose deadline
    already passed must terminate explicitly as deadline_exceeded — the
    PR 6 contract, now across processes. Either way: a terminal status for
    every admitted request, solutions only for status == "ok"."""
    X, y = _problem(1)
    coord = coordinator_factory(n_hosts=2, max_batch=4, max_wait=1e-3)
    ids = [coord.submit(X + 0.01 * k, y, t=1.0) for k in range(8)]
    coord.flush()
    coord.kill_host(0)
    out = coord.drain()
    assert sorted(out) == sorted(ids), "silent request drop on host kill"
    statuses = {rid: out[rid].status for rid in ids}
    assert set(statuses.values()) <= {"ok", "deadline_exceeded"}, statuses
    for rid in ids:
        if out[rid].status == "ok":
            assert np.all(np.isfinite(np.asarray(out[rid].beta)))
        else:
            assert out[rid].beta is None


@pytest.mark.slow
def test_all_hosts_dead_aborts_explicitly(coordinator_factory):
    """When NO host survives, pending requests must terminate as "aborted"
    (and drain must return, not hang)."""
    X, y = _problem(2)
    coord = coordinator_factory(n_hosts=1, max_batch=4)
    ids = [coord.submit(X, y + 0.1 * k, t=1.0) for k in range(4)]
    coord.kill_host(0)
    out = coord.drain(timeout=60.0)
    assert sorted(out) == sorted(ids)
    assert {r.status for r in out.values()} == {"aborted"}
    assert all(out[rid].beta is None for rid in ids)


@pytest.mark.slow
def test_multihost_shared_spill_survives_host_loss(coordinator_factory,
                                                   tmp_path):
    """Work a dead host completed before dying must warm-start the
    survivors through the shared persistent spill tier."""
    X, y = _problem(3)
    coord = coordinator_factory(n_hosts=2, max_batch=4,
                                cache_dir=str(tmp_path / "spill"))
    first = [coord.submit(X, y, t=0.8 + 0.05 * k) for k in range(8)]
    out = coord.drain()
    assert {out[r].status for r in first} == {"ok"}
    # no fault yet: the fleet-merged worker counters saw exactly what the
    # coordinator admitted, and its books balance
    acct = coord.accounting()
    assert acct["balanced"] and acct["admitted"] == 8
    assert int(coord.fleet.counter("runtime_requests_total",
                                   labelnames=()).total()) == 8
    coord.kill_host(0)                 # the half that solved some of wave 1
    again = [coord.submit(X, y, t=0.8 + 0.05 * k) for k in range(8)]
    out = coord.drain()
    assert sorted(out) == sorted(again)
    assert {out[r].status for r in again} == {"ok"}
    stats = coord.shutdown()
    # only the survivor reports; repeat traffic must have warm-started,
    # including from points the dead host spilled
    assert sum(s["cache_hits"] for s in stats) > 0


def test_corrupt_spill_entry_degrades_to_miss(tmp_path):
    """Flip bytes / truncate / garbage a spilled entry: lookups report a
    miss, the bad file is removed, nothing raises."""
    from repro.runtime.cache import TieredSolutionCache, WarmEntry

    def entry(lam):
        return WarmEntry(lam=lam, lambda2=1.0, alpha=np.ones(8),
                         w=np.ones(6), beta=np.ones(4), t=lam, nu=0.1)

    root = tmp_path / "spill"
    cache = TieredSolutionCache(spill_dir=root)
    cache.insert("fp0", "constrained", entry(1.0))
    cache.insert("fp1", "constrained", entry(2.0))
    files = sorted(root.glob("*.npz"))
    assert len(files) == 2

    files[0].write_bytes(b"\x00garbage, not a zipfile")   # corrupt
    with open(files[1], "r+b") as f:                       # truncate
        f.truncate(8)

    fresh = TieredSolutionCache(spill_dir=root)            # empty memory tier
    assert fresh.lookup("fp0", "constrained", 1.0, 1.0) is None
    assert fresh.lookup("fp1", "constrained", 2.0, 1.0) is None
    assert fresh.spill.corrupt_dropped == 2
    assert list(root.glob("*.npz")) == [], "bad entries must be removed"
    # and the tier still works after dropping the corruption
    fresh.insert("fp0", "constrained", entry(1.0))
    assert fresh.lookup("fp0", "constrained", 1.0, 1.0) is not None


def test_wrong_fingerprint_spill_never_served(tmp_path):
    """A spilled file renamed onto another problem's key (the on-disk
    analogue of a hash collision / tampering) must NOT be served: the
    stored fingerprint is verified against the query."""
    from repro.runtime.cache import PersistentCacheTier, WarmEntry

    tier = PersistentCacheTier(tmp_path / "spill")
    e = WarmEntry(lam=1.0, lambda2=1.0, alpha=np.ones(8), w=np.ones(6),
                  beta=np.ones(4), t=1.0, nu=0.0)
    assert tier.insert("aaaa", "constrained", e)
    (path,) = tier.root.glob("aaaa.*.npz")
    stolen = tier.root / path.name.replace("aaaa", "bbbb")
    path.rename(stolen)
    assert tier.lookup("bbbb", "constrained", 1.0, 1.0) is None
    assert tier.corrupt_dropped == 1
    assert not stolen.exists()


@pytest.mark.slow
def test_engine_restart_recovers_warm_hit_rate(tmp_path):
    """An engine restarted onto the same cache_dir must serve warm starts
    from the persistent tier: hit rate >= 0.5 on repeat traffic (ISSUE 8
    acceptance), with solutions unchanged."""
    from repro.serve import ElasticNetEngine

    X, y = _problem(4, n=24, p=10)
    lams = [0.6 + 0.1 * k for k in range(6)]
    spill = str(tmp_path / "warm")

    # max_batch=8 keeps each session to ONE batch: every lookup happens
    # before any insert, so the first session is provably all-miss
    engine1 = ElasticNetEngine(max_batch=8, cache_dir=spill)
    ids1 = [engine1.submit(X, y, t=lam, lambda2=1.0) for lam in lams]
    out1 = engine1.drain()
    assert engine1.cache.hits == 0     # cold process, cold disk

    del engine1                        # restart: fresh process state
    engine2 = ElasticNetEngine(max_batch=8, cache_dir=spill)
    assert len(engine2.cache) == 0, "memory tier must start empty"
    ids2 = [engine2.submit(X, y, t=lam, lambda2=1.0) for lam in lams]
    out2 = engine2.drain()
    cache = engine2.cache
    rate = cache.hits / max(cache.hits + cache.misses, 1)
    assert rate >= 0.5, (cache.hits, cache.misses)
    assert cache.spill_hits > 0, "hits must come from the persistent tier"
    for r1, r2 in zip(ids1, ids2):
        np.testing.assert_allclose(np.asarray(out2[r2].beta),
                                   np.asarray(out1[r1].beta), atol=1e-8)
