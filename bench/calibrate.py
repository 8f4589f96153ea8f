"""Readings that set a cell's limits: the program's compared numbers, and
its control's, on problems made from given seeds.

    python3 bench/calibrate.py --workload gli85.path --seeds 1 2 3 \
        --control-seeds 1 2 3

For each seed it builds the problem a run with that `--seed` solves after
its window (the run's own problem, at the cell's own size), runs one job
through the timed path (`Job.dispatch`, the call the window makes) and
compares it with the reference, as a run does. The control is the
program's own lower-precision path: the same job on a float32 copy of the
data, on the same grid, compared with the same float64 reference. A sound
limit lies above the program's readings (the lower one: their largest)
and below the control's (the upper one: their smallest). One line per
reading, then a summary line.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

#: the precision below each configured one: the step that would tempt a
#: later change
LOWER = {"float64": "float32"}


def readings(workload: str, control: bool, seed: int, *, rehearse=False,
             spec_path=ROOT / "BENCHMARK.json") -> dict:
    """{name: widest reading} of one job on the seed's problem."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import data, harness

    _, cell, config, traffic = harness.load_cell(workload, spec_path)
    if config["dtype"] == "float64":
        jax.config.update("jax_enable_x64", True)
    shape = config["rehearse"] if rehearse else config
    X, y = data.make_regression(int(shape["n"]), int(shape["p"]),
                                data_seed=seed, dtype=config["dtype"],
                                **config["generator"])
    Job = harness.load_module("jobs", traffic["job"]).Job
    job = Job(config, traffic, [(X, y)])
    if control:
        low = jnp.dtype(LOWER[config["dtype"]])
        job = Job(config, traffic, [(X.astype(low), y.astype(low))],
                  grids=job.grids)
    job.warm()
    answers = [(0, job.fetch(job.block(job.dispatch(0))))]
    refs = {0: job.reference(0, np.asarray(X), np.asarray(y))}
    return {name: float(np.max(v))
            for name, v in job.compare(answers, refs).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness

    if not args.rehearse:
        harness.enable_compile_cache(jax)
    summary = {"workload": args.workload, "lower": {}, "upper": {}}
    for key, control, seeds in (("lower", False, args.seeds),
                                ("upper", True, args.control_seeds)):
        for seed in seeds:
            r = readings(args.workload, control, seed, rehearse=args.rehearse)
            print(json.dumps({"workload": args.workload, "control": control,
                              "seed": seed, "readings": r,
                              "at_s": time.perf_counter() - T0}), flush=True)
            pick = max if key == "lower" else min
            for name, v in r.items():
                summary[key][name] = pick(summary[key].get(name, v), v)
    summary["at_s"] = time.perf_counter() - T0
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
