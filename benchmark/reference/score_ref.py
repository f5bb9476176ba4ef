"""Node choice for ONE pending pod on a known cluster state, in plain NumPy.

The semantics fix this choice (PARITY.md deviation 1): among the feasible
nodes, the one with the highest score, lowest index among ties.  The score
is the sum of three plugins at the weights the configuration's conf gives
them (all 1 under the benchmark's conf), written here from the published
formulas, not from the program's code:

  binpack          10 * mean over cpu, memory of (used + request) / allocatable
  least-requested  10 * mean over cpu, memory of (allocatable - used - request)
                   / allocatable
  balanced         10 * (1 - |cpu fraction - memory fraction|), fractions of
                   (used + request) / allocatable

This file imports nothing of the program and takes nothing it made.
"""

from __future__ import annotations

import numpy as np

MAX_PRIORITY = 10.0
# Two scores closer than this are a tie (float64 sums of a few terms of
# magnitude <= 10 carry errors near 1e-15; the program states float32, whose
# errors at this magnitude are ~2e-6).
TIE = 1e-5


def feasible(alloc, used, req):
    """Nodes that can take the pod: cpu (milli), memory (bytes) and pod count
    within allocatable.  ``alloc``/``used`` are [N, 3] integer arrays with
    columns cpu, memory, pods; ``req`` is (cpu, memory)."""
    after = used + np.array([req[0], req[1], 1], dtype=used.dtype)
    return np.all(after <= alloc, axis=1)


def scores(alloc, used, req, dtype=np.float64):
    """Per-node score of placing the pod, every term computed in ``dtype``
    (float64 is the reference; a lower ``dtype`` is the control)."""
    cap = alloc[:, :2].astype(dtype)
    after = (used[:, :2].astype(dtype)
             + np.array(req, dtype=np.float64).astype(dtype))
    frac = after / cap
    # Constants in ``dtype`` too, so that every product and sum stays in it
    # (ml_dtypes' bfloat16 promotes to float32 against a Python float).
    one, two, ten = (np.asarray(v).astype(dtype) for v in (1.0, 2.0, MAX_PRIORITY))
    binpack = (frac[:, 0] + frac[:, 1]) / two * ten
    free = (cap - after) / cap
    least = (free[:, 0] + free[:, 1]) / two * ten
    balanced = (one - np.abs(frac[:, 0] - frac[:, 1])) * ten
    total = binpack + least + balanced
    if total.dtype != np.dtype(dtype):
        raise TypeError(f"score left {np.dtype(dtype)}: {total.dtype}")
    return total


def choose(alloc, used, req, dtype=np.float64) -> int:
    """Index of the node the pod must go to, or -1 when none is feasible."""
    ok = feasible(alloc, used, req)
    if not ok.any():
        return -1
    s = scores(alloc, used, req, dtype).astype(np.float64)
    s = np.where(ok, s, -np.inf)
    return int(np.flatnonzero(s >= s.max() - TIE)[0])


def top_two_gap(alloc, used, req) -> float:
    """Gap between the best and the best *different* score among feasible
    nodes (inf with fewer than two distinct scores): how much room a
    lower-precision score plane has before it flips the choice."""
    ok = feasible(alloc, used, req)
    s = np.unique(np.round(scores(alloc, used, req)[ok] / TIE) * TIE)
    return float(s[-1] - s[-2]) if len(s) > 1 else float("inf")
