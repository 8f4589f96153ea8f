"""Per-solve telemetry: what each dispatched batch actually cost vs what
the cost model priced it at (DESIGN.md §12.5).

Every harvested batch appends one `SolveRecord`: the bucket geometry,
route decision, solver effort (iterations, final KKT violation), the
screening keep-fraction (nonzero share of the solution — the quantity
gap-safe screening trades against), and modeled-vs-actual seconds. The
modeled price is `core.routing.estimate_batch_seconds` taken AT DISPATCH
(so it reflects the calibration the router actually used), the actual is
dispatch -> harvest wall time with the blocking wait broken out.

`SolveLog.residual_report()` folds the records into the cost-model
residual summary: per route path, the distribution of
log10(actual/modeled). A drifting residual is the signal to re-run `core.routing.calibrate(force=True)` — this is the
data needed to validate and later recalibrate the router, closing the PR 6
loop.

Every `core.api.enet_path` call also appends one `PathRecord` to the
process-wide `default_solve_log()`: its per-point root-find evaluations,
inner solver iterations, CG steps, primal stall stops and root-find stop
causes, as the device arrays the call returned. Recording never syncs; the
arrays come to the host only when the log is read. This is where an
operator finds uncertified points: `SolveLog.summary()` counts the points
by stop cause, and any under "bracket" or "max_evals" ended with
|nu - lambda1| above the root-find's tolerance (`core.api.STOP_*`);
`path_records()` says which call and which point.
"""
from __future__ import annotations

import collections
import math
from typing import Any, List, NamedTuple

import numpy as np

__all__ = ["SolveRecord", "PathRecord", "SolveLog", "STOP_CAUSES",
           "default_solve_log"]

#: names of the root-find stop codes (`EnetPoint.stop`), by code
STOP_CAUSES = ("no_root", "root", "bracket", "max_evals")


class SolveRecord(NamedTuple):
    """One dispatched-and-harvested stacked solve."""

    bucket: tuple           # (bn, bp)
    form: str               # constrained | penalized
    batch: int              # padded batch B the executable ran at
    b_real: int             # real (non-padding) requests in the batch
    route_path: str         # router decision: single | sharded | batch
    modeled_s: float        # cost-model price at dispatch (0.0 = unmodeled)
    actual_s: float         # dispatch -> harvest wall seconds
    blocked_s: float        # host seconds inside block_until_ready
    iters_max: int          # max solver iterations across the batch
    iters_mean: float
    kkt_max: float          # worst EN KKT violation across real slots
    keep_fraction: float    # nonzero share of the solution (screening keep)


class PathRecord(NamedTuple):
    """One `enet_path` call: (L,) per-point counters, device or host arrays."""

    evals: Any        # root-find evaluations (SVEN solves)
    sven_iters: Any   # inner solver iterations
    cg_steps: Any     # CG iterations
    stalls: Any       # evaluations ended on the primal's stall stop
    stop: Any         # root-find stop code, indexes STOP_CAUSES

    def host(self) -> "PathRecord":
        """The same record with every field as a NumPy array (syncs)."""
        return PathRecord(*(np.asarray(f) for f in self))


class SolveLog:
    """Bounded log of `SolveRecord`s (served batches) and `PathRecord`s
    (path calls), with a cost-model residual report and a path summary."""

    def __init__(self, *, capacity: int = 4096) -> None:
        self._records: collections.deque = collections.deque(maxlen=capacity)
        self.recorded = 0

    def add(self, record) -> None:
        self._records.append(record)
        self.recorded += 1

    def __len__(self) -> int:
        return len(self._records)

    def records(self) -> List[SolveRecord]:
        return [r for r in self._records if isinstance(r, SolveRecord)]

    def path_records(self) -> List[PathRecord]:
        """The retained `PathRecord`s, oldest first, on the host."""
        return [r.host() for r in self._records if isinstance(r, PathRecord)]

    def summary(self) -> dict:
        """Points of the retained path calls by stop cause, and CG steps
        and stall stops per point."""
        recs = self.path_records()

        def per_point(field):
            return (np.concatenate([getattr(r, field) for r in recs]) if recs
                    else np.zeros(0))

        stop, cg, stalls = (per_point(f) for f in ("stop", "cg_steps", "stalls"))
        return {"paths": len(recs), "points": int(stop.size),
                "by_stop": {name: int(np.sum(stop == code))
                            for code, name in enumerate(STOP_CAUSES)},
                "cg_steps_per_point": (float(np.mean(cg)) if cg.size
                                       else None),
                "stalls_per_point": (float(np.mean(stalls)) if stalls.size
                                     else None)}

    def residual_report(self) -> dict:
        """Modeled-vs-actual summary per route path.

        ``log10_ratio`` statistics are over log10(actual/modeled): 0 means
        the calibration prices this path perfectly, +1 means solves run 10x
        slower than modeled (recalibrate), negative means the model is
        pessimistic (routing may be leaving fan-out wins on the table).
        Records without a model price (pinned meshes, unpriced forms) are
        counted but excluded from the ratio stats.
        """
        by_path: dict = {}
        unmodeled = 0
        records = self.records()
        for r in records:
            if r.modeled_s <= 0.0 or r.actual_s <= 0.0:
                unmodeled += 1
                continue
            by_path.setdefault(r.route_path, []).append(r)
        paths = {}
        for path, recs in sorted(by_path.items()):
            ratios = sorted(math.log10(r.actual_s / r.modeled_s)
                            for r in recs)
            n = len(ratios)
            paths[path] = {
                "n": n,
                "modeled_s_mean": sum(r.modeled_s for r in recs) / n,
                "actual_s_mean": sum(r.actual_s for r in recs) / n,
                "log10_ratio_mean": sum(ratios) / n,
                "log10_ratio_p50": ratios[n // 2],
                "log10_ratio_max_abs": max(abs(ratios[0]), abs(ratios[-1])),
            }
        return {"n_records": len(records), "n_unmodeled": unmodeled,
                "by_path": paths}

    def clear(self) -> None:
        self._records.clear()
        self.recorded = 0


_DEFAULT = SolveLog()


def default_solve_log() -> SolveLog:
    """Process-wide log that `core.api.enet_path` appends a `PathRecord`
    to per call — per-scheduler batch records live on their own log."""
    return _DEFAULT
