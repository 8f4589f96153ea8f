"""`enet_path`'s row layout on 4 forced host devices: a tall design under
`dist.mesh_context` whose one-chip plan does not fit a chip takes rows,
padded with zero rows to a multiple of the mesh, and answers as the
single-device path and the float64 reference (`bench/reference.py`) do;
a primal-regime design stays on one device.

Tolerances: 1e-10 against the single-device path, whose program is the same
but for the order of each reduction over rows (partial sums per device,
then an all-reduce): f64 rounding, carried through solves that stop at
1e-10. 1e-8 against the reference, as `beta_gap` (max |beta - ref| over
max |ref|): the root-find stops at |nu - lambda1| <= 1e-9 lambda1_max and
the reference certifies its KKT conditions to 1e-10 relative.
"""
import json
import textwrap

import pytest

from _subprocess import run_python

_ROWS_4DEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    sys.path[:0] = ["src", "."]
    import jax, jax.numpy as jnp, numpy as np
    jax.config.update("jax_enable_x64", True)
    from bench import reference
    from repro import dist
    from repro.core import api, routing
    from repro.core.distributed import shard_rows
    from repro.data.synthetic import make_regression
    from repro.obs.metrics import default_registry
    from repro.obs.trace import enable_tracing

    mesh = dist.data_mesh(4)
    counter = default_registry().counter(
        "enet_path_layout_total", "", ("layout",))
    tracer = enable_tracing()

    def layouts():
        return {k[0]: int(v) for k, v in counter.series().items()}

    def case(name, n, p, seed, standardize=False, fit_intercept=False):
        kw = dict(standardize=standardize, fit_intercept=fit_intercept)
        X, y, _ = make_regression(n, p, seed=seed)
        single = api.enet_path(X, y, n_lambdas=8, **kw)
        before = layouts()
        tracer.reset()
        with dist.mesh_context(mesh):
            path = api.enet_path(X, y, n_lambdas=8, **kw)
        after = layouts()
        took = [k for k in after if after[k] != before.get(k, 0)]
        Xs, ys, sc = api.standardize_fit(X, y, **kw)
        ref = reference.enet_path_reference(
            np.asarray(Xs), np.asarray(ys), np.asarray(path.lambda1s), 1.0)
        ref = ref / np.asarray(sc.x_scale)
        print(json.dumps({
            "case": name, "layout": took,
            "dev_single": float(jnp.max(jnp.abs(path.betas - single.betas))),
            "dev_intercept": float(jnp.max(jnp.abs(path.intercepts
                                                   - single.intercepts))),
            "beta_gap": float(np.max(reference.point_gaps(path.betas, ref))),
            "evals": np.asarray(path.evals).tolist(),
            "evals_single": np.asarray(single.evals).tolist(),
            "iters": np.asarray(path.sven_iters).tolist(),
            "iters_single": np.asarray(single.sven_iters).tolist(),
            "place_span": "enet_path.place" in {s[1] for s in tracer.spans()},
        }), flush=True)

    # the host CPU reports no memory: every plan fits, the layout is single
    case("fits", 1003, 12, 0)
    # a chip of 1 MiB: the 1,003 x 12 plan (6.5 MB) does not fit
    routing.chip_memory_bytes = lambda device: 1 << 20
    case("rows", 1003, 12, 0)
    case("rows_standardized", 1003, 12, 0, standardize=True,
         fit_intercept=True)
    case("primal", 30, 40, 1)
    # placed once by the caller, then used as it is
    X, y, _ = make_regression(1003, 12, seed=0)
    Xp, yp = shard_rows(mesh, X, y)
    with dist.mesh_context(mesh):
        a = api.enet_path(Xp, yp, n_lambdas=8)
    b = api.enet_path(X, y, n_lambdas=8)
    print(json.dumps({"case": "preplaced", "rows": int(Xp.shape[0]),
                      "devices": len(Xp.sharding.device_set),
                      "dev_single": float(jnp.max(jnp.abs(a.betas
                                                          - b.betas)))}))
""")


@pytest.fixture(scope="module")
def cases():
    out = run_python(snippet=_ROWS_4DEV, timeout=600).stdout
    rows = [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]
    return {r["case"]: r for r in rows}


@pytest.mark.parametrize("name", ["rows", "rows_standardized"])
def test_tall_design_takes_rows_and_answers_as_one_device(cases, name):
    r = cases[name]
    assert r["layout"] == ["rows"] and r["place_span"]
    assert r["dev_single"] <= 1e-10 and r["dev_intercept"] <= 1e-10, r
    assert r["beta_gap"] <= 1e-8, r


@pytest.mark.parametrize("name", ["fits", "primal"])
def test_design_stays_single(cases, name):
    """A plan that fits one chip, and the primal regime (2p > n) whatever
    its plan, run on one device: the same program as without a mesh."""
    r = cases[name]
    assert r["layout"] == ["single"] and not r["place_span"]
    assert r["dev_single"] == 0.0 and r["dev_intercept"] == 0.0, r
    assert r["beta_gap"] <= 1e-8, r


@pytest.mark.parametrize("name", ["rows", "rows_standardized"])
def test_row_layout_takes_the_steps_of_one_device(cases, name):
    """Only the order of each reduction over rows changes: per point, the
    same root-find evaluations and Newton steps as on one device."""
    r = cases[name]
    assert r["evals"] == r["evals_single"] and sum(r["evals"]) > 0
    assert r["iters"] == r["iters_single"]


def test_rows_placed_by_the_caller_are_used(cases):
    r = cases["preplaced"]
    assert r["rows"] == 1004 and r["devices"] == 4
    assert r["dev_single"] <= 1e-10, r


class _Chip:
    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return None if self.limit is None else {"bytes_limit": self.limit}


def _mesh(size, limit):
    import types

    import numpy as np
    return types.SimpleNamespace(size=size,
                                 devices=np.asarray([_Chip(limit)] * size))


V5E = 16_900_000_000      # a v5e chip's bytes_limit, about


@pytest.mark.parametrize("n, p, mesh, layout", [
    (463_716, 90, None, "single"),                 # no mesh context
    (463_716, 90, _mesh(1, V5E), "single"),        # nothing to shard over
    (463_716, 90, _mesh(4, V5E), "rows"),          # 22.7 GB plan
    (231_858, 90, _mesh(4, V5E), "single"),        # 11.3 GB: fits a chip
    (463_716, 90, _mesh(4, None), "single"),       # no memory reported
    (85, 22_283, _mesh(4, 1), "single"),           # primal regime
])
def test_route_path(n, p, mesh, layout):
    from repro.core.routing import route_path

    assert route_path(n, p, 8, mesh) == layout
