"""Illinois root-find evaluations (SVEN solves) per lambda point: the mean
of the program's `EnetPath.evals` over every point the window answered."""
import numpy as np


def read(rec):
    evals = [a["evals"] for a in rec["answers"] if "evals" in a]
    return float(np.mean(np.concatenate(evals))) if evals else None
