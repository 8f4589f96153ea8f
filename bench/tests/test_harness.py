"""The harness on the CPU at rehearsal sizes: every cell runs end to end and
checks out correct; a cell is added as data alone; the run refuses to go on
without a chip or without the program; an answer broken underneath the
timed path, or the program's lower-precision path, reads not correct.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(workload, *, seed=20260917, seconds=0.3, trace=False,
         spec_path=ROOT / "BENCHMARK.json"):
    from bench import harness

    return harness.run_cell(workload, seed, seconds, trace,
                            t0=time.perf_counter(), require_chip=False,
                            rehearse=True, spec_path=spec_path)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell):
    res = _run(cell, seed=2**40 + 12345)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in SPEC["end_to_end"]
             if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == names
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"beta_gap", "beta_gap_own"}
    assert res["device"]["count"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_counters(cell):
    res = _run(cell, trace=True)
    assert res["correct"]
    for name in ("evals_per_point", "newton_iters_per_point",
                 "compiles_in_window"):
        assert name in res["metrics"], res["metrics"]
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    # a CPU trace has no device plane: the device readers stay silent
    assert "device_idle_share" not in res["metrics"]


def test_data_from_data_seed_alone():
    import jax

    from bench import data

    jax.config.update("jax_enable_x64", True)
    a = data.make_regression(20, 50, data_seed=2**33 + 5)
    b = data.make_regression(20, 50, data_seed=2**33 + 5)
    c = data.make_regression(20, 50, data_seed=5)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert np.allclose(np.mean(np.asarray(a[0]), axis=0), 0, atol=1e-12)
    assert abs(float(np.mean(np.asarray(a[1])))) < 1e-12


@pytest.mark.parametrize("cell", CELLS)
def test_seed_does_not_change_the_work(cell):
    """Two seeds: the same timed problems, so the same counters and window
    readings; another problem of the run's own."""
    one, two = (_run(cell, seed=s, trace=True) for s in (3, 2**40 + 9))
    for name in ("evals_per_point", "newton_iters_per_point"):
        assert one["metrics"][name] == two["metrics"][name]
    assert one["checks"]["beta_gap"] == two["checks"]["beta_gap"]
    assert one["checks"]["beta_gap_own"] != two["checks"]["beta_gap_own"]


@pytest.mark.parametrize("seed", [0, 7, 2**40 + 9])
def test_cycle_order_from_seed(seed):
    """Each cycle visits every fixed problem once, in the seed's order."""
    from bench import harness

    order = harness._cycle_order(seed, 5)
    assert sorted(order) == list(range(5))
    assert order == harness._cycle_order(seed, 5)
    orders = {tuple(harness._cycle_order(s, 5)) for s in range(20)}
    assert len(orders) > 1


@pytest.mark.parametrize("cell", CELLS)
def test_window_holds_whole_cycles(cell):
    """Every timed problem is solved equally often, and the run's own
    problem once more after the window."""
    from bench import harness

    _, _, _, traffic = harness.load_cell(cell)
    per_cycle = len(traffic["problems"]) * traffic["n_lambdas"]
    res = _run(cell, seed=11)
    timed = res["attempted"] - traffic["n_lambdas"]
    assert timed > 0 and timed % per_cycle == 0


@pytest.mark.parametrize("cell", CELLS)
def test_own_problem_is_the_seeds(cell):
    """The run's own reading is that of one job on the seed's problem, as
    `calibrate` reads it."""
    from bench import calibrate

    res = _run(cell, seed=2**35 + 3)
    alone = calibrate.readings(cell, control=False, seed=2**35 + 3,
                               rehearse=True)
    assert res["checks"]["beta_gap_own"]["value"] == alone["beta_gap"]


def _broken_enet_path(monkeypatch, change):
    """Patch the program's entry point underneath the job: every path it
    returns passes through `change` first."""
    from repro.core import api

    real = api.enet_path

    def broken(*a, **k):
        path = real(*a, **k)
        return path._replace(betas=change(path.betas))

    monkeypatch.setattr(api, "enet_path", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, monkeypatch):
    """One coefficient of the last point moved by 1e-4 of the path's
    largest: too little for a user to see, far above every limit."""
    def change(betas):
        scale = float(np.max(np.abs(np.asarray(betas))))
        return betas.at[-1, 0].add(1e-4 * scale)

    _broken_enet_path(monkeypatch, change)
    res = _run(cell)
    assert not res["correct"]
    assert res["failed"] > 0
    assert res["checks"]["beta_gap"]["value"] > res["checks"]["beta_gap"][
        "limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_above_every_limit(cell):
    """The control, the program run on a float32 copy of the data, fails
    the cell's limit; the program itself, on the same seed, passes it."""
    from bench import calibrate, harness

    _, _, _, traffic = harness.load_cell(cell)
    limit = traffic["limits"]["beta_gap"]
    sound = calibrate.readings(cell, control=False, seed=5, rehearse=True)
    control = calibrate.readings(cell, control=True, seed=5, rehearse=True)
    assert sound["beta_gap"] <= limit < control["beta_gap"], (sound, control)


def _copy_bench(dst: Path, with_program: bool):
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    if with_program:
        (dst / "src").symlink_to(ROOT / "src")


def test_cell_added_as_data(tmp_path):
    """A new configuration, traffic mix and per-layer metric, added as new
    files and entries only, run through the unchanged harness."""
    _copy_bench(tmp_path, with_program=True)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "fake", "source": "https://example.org/fake",
        "file": "bench/configs/fake.json", "reduced": [], "why": "test"})
    spec["workloads"].append({
        "name": "fake.path", "config": "fake", "traffic": "fake_2pt",
        "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "kept_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "solver: screening",
        "moves": "point_s", "workloads": ["fake.path"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench/configs/fake.json").write_text(json.dumps({
        "n": 25, "p": 60, "dtype": "float64", "lambda2": 0.5,
        "lambda_grid": {"min_ratio": 0.05},
        "generator": {"k_true": 3, "rho": 0.0, "noise": 0.2},
        "rehearse": {"n": 25, "p": 60}}))
    (tmp_path / "bench/traffic/fake_2pt.json").write_text(json.dumps({
        "job": "enet_path", "n_lambdas": 2, "problems": [4, 9],
        "limits": {"beta_gap": 1e-7}}))
    (tmp_path / "bench/metrics/kept_share.py").write_text(
        "import numpy as np\n\n\n"
        "def read(rec):\n"
        "    kept = np.concatenate([a['n_kept'] for a in rec['answers']])\n"
        "    return 100.0 * float(np.mean(kept)) / 60\n")
    script = (
        "import json, sys, time\n"
        f"sys.path[:0] = [{str(tmp_path)!r}, {str(tmp_path / 'src')!r}]\n"
        "from bench import harness\n"
        "for trace in (False, True):\n"
        "    print(json.dumps(harness.run_cell('fake.path', 3, 0.2, trace,"
        " t0=time.perf_counter(), require_chip=False, rehearse=True)))\n")
    out = subprocess.run([sys.executable, "-c", script], env=ENV,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    plain, traced = (json.loads(line) for line in
                     out.stdout.strip().splitlines()[-2:])
    assert plain["correct"] and traced["correct"]
    assert plain["attempted"] % 4 == 2       # whole cycles, then its own
    assert set(plain["metrics"]) == {"point_s", "setup_s"}
    assert 0 < traced["metrics"]["kept_share"]["value"] <= 100
    assert "evals_per_point" not in traced["metrics"]
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


@pytest.mark.parametrize("with_program", [True, False])
def test_refuses_without_chip_or_program(tmp_path, with_program):
    """No accelerator (the CPU here), or a directory holding only
    BENCHMARK.json and bench/: exit non-zero, print no result."""
    _copy_bench(tmp_path, with_program)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=ENV,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
