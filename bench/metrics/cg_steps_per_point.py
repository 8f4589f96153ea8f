"""CG iterations per lambda point (summed over a point's Newton steps and
root-find evaluations): the mean of the program's per-point `cg_steps` over
every point the window answered.

Read from the program's path log (`repro.obs.default_solve_log()`), which
holds one record per `enet_path` call. A run calls the path once to warm
up, then once per window job, then once for its own problem, so the
window's records are the `len(rec["answers"])` before the last one. Each is
matched to its answer by the root-find evaluations of every point: a
mismatch means the records are not the window's, and raises. A program
without the log gives nothing to read.
"""
import numpy as np


def read(rec):
    try:
        from repro.obs.solve import default_solve_log
    except ImportError:
        return None
    answers = rec["answers"]
    records = default_solve_log().path_records()
    if not answers or len(records) < len(answers) + 1:
        return None
    window = records[-len(answers) - 1:-1]
    for i, (r, a) in enumerate(zip(window, answers)):
        if not np.array_equal(np.asarray(r.evals), np.asarray(a["evals"])):
            raise ValueError(f"path log record {i} of the window does not "
                             f"match its answer: evals {r.evals.tolist()} "
                             f"against {np.asarray(a['evals']).tolist()}")
    return float(np.mean(np.concatenate([r.cg_steps for r in window])))
