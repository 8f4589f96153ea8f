"""Inner solver iterations (Newton / Newton-CG steps, summed over a point's
root-find evaluations) per lambda point: the mean of the program's
`EnetPath.sven_iters` over every point the window answered."""
import numpy as np


def read(rec):
    iters = [a["sven_iters"] for a in rec["answers"] if "sven_iters" in a]
    return float(np.mean(np.concatenate(iters))) if iters else None
