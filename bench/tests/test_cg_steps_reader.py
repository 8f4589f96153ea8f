"""`cg_steps_per_point` on the CPU at rehearsal sizes: it reads the window's
records of the program's path log, refuses records that are not the
window's, and stays silent for a program without the log.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def _reader():
    from bench import harness

    return harness.load_module("metrics", "cg_steps_per_point")


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_cg_steps(cell):
    from bench import harness
    from repro.obs import default_solve_log

    res = harness.run_cell(cell, 20261017, 0.3, True,
                           t0=time.perf_counter(), require_chip=False,
                           rehearse=True)
    assert res["correct"]
    value = res["metrics"]["cg_steps_per_point"]
    assert value["unit"] == "steps/point" and value["value"] > 0
    # warm-up, the window's jobs, then the own problem: the window's
    # records are the ones before the last
    recs = default_solve_log().path_records()
    n_jobs = res["attempted"] // len(recs[-1].evals) - 1
    window = recs[-n_jobs - 1:-1]
    assert value["value"] == pytest.approx(
        float(np.mean(np.concatenate([r.cg_steps for r in window]))))


def test_mismatched_record_raises():
    from repro.core.api import enet_path
    from repro.data.synthetic import make_regression

    X, y, _ = make_regression(30, 40, k_true=4, seed=1)
    paths = [enet_path(X, y, n_lambdas=3, eps=0.05) for _ in range(3)]
    answers = [{"evals": np.asarray(p.evals)} for p in paths[1:2]]
    assert _reader().read({"answers": answers}) > 0
    answers[0]["evals"] = answers[0]["evals"] + 1
    with pytest.raises(ValueError, match="does not match"):
        _reader().read({"answers": answers})


def test_program_without_the_log_reads_nothing(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.solve",
                        types.ModuleType("repro.obs.solve"))
    assert _reader().read({"answers": [{"evals": np.zeros(3)}]}) is None
