"""Fair share between weighted queues, in plain NumPy float64.

What a fair-share deployment (``configs/drf-5k.json``) promises beyond the
guarantees of ``harness/validate.py``: how much of the cluster each queue
deserves, when a queue is refused, and which gang goes next.  Written here
from the published formulas of the reference scheduler (volcano, the
``proportion`` and ``drf`` plugins and the ``allocate`` action), not from the
program's code:

  deserved        proportion.go:117-173, the water-fill at session open
  share           proportion.go:208-215 with helpers.Share (helpers.go:46-59)
  overused        proportion.go:217-229, Resource.LessEqual
                  (resource_info.go:286-320) with its epsilon
  dominant_share  drf.go:317-329 (calcShare), the job order key
  allocate        allocate.go:107-250, the <queue, job> walk

A resource vector is ``[R]``, column 0 cpu in milli-cores, column 1 memory in
bytes (more columns are scalar resources in milli-units).  This file imports
nothing of the program and takes nothing it made.

Departures from the published text, each on purpose:

1. ``allocate`` walks *gangs* on the cluster's *aggregate* capacity: a gang is
   taken whole when its request fits what is left of the cluster, where
   allocate.go places it task by task on nodes and discards it when fewer
   than ``minAvailable`` tasks found one.  With gangs far smaller than a node
   set that is the same answer up to packing loss; the tests that use it
   choose requests that tile the nodes.
2. One namespace, so allocate.go's namespace round-robin is left out.
3. Job order is DRF's dominant share, then creation, then name: every gang
   here has one priority and none is part-ready, so the ``priority`` and
   ``gang`` comparators before it (allocate.go's JobOrderFn chain) tie.
4. A gang that does not fit is dropped for this walk and its queue goes on
   with its next gang (allocate.go pops the job and pushes it back only
   when it became ready with tasks left), so one walk is one session.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Set

import numpy as np

# Resource quanta of the reference (resource_info.go:30-34): differences
# below these do not count.  cpu milli, memory bytes, scalars milli.
MIN_MILLI_CPU = 10.0
MIN_MEMORY = 10.0 * 1024 * 1024
MIN_MILLI_SCALAR = 10.0


def epsilon(r: int) -> np.ndarray:
    """The ``[r]`` vector of quanta: cpu, memory, then scalars."""
    eps = np.full(r, MIN_MILLI_SCALAR, dtype=np.float64)
    eps[:2] = (MIN_MILLI_CPU, MIN_MEMORY)[:r]
    return eps


def _vec(x) -> np.ndarray:
    return np.asarray(x, dtype=np.float64)


def deserved(total, weights, requests) -> np.ndarray:
    """Each queue's deserved share ``[Q, R]`` of ``total`` ``[R]``.

    Rounds of: what remains is dealt by weight among the queues not yet met;
    a queue whose request is below what it now holds in *every* dimension
    (Resource.Less, strict) is clipped to its request and is met; what the
    clipping gave back remains for the next round.  Ends when no queue is
    left or nothing (less than a quantum in every dimension) remains."""
    total = _vec(total)
    weights = _vec(weights)
    requests = _vec(requests)
    q, r = requests.shape
    eps = epsilon(r)
    out = np.zeros((q, r), dtype=np.float64)
    met = np.zeros(q, dtype=bool)
    remaining = total.copy()
    while True:
        total_weight = weights[~met].sum()
        if total_weight == 0:
            break
        given = np.zeros(r, dtype=np.float64)
        for i in np.flatnonzero(~met):
            old = out[i].copy()
            out[i] = old + remaining * (weights[i] / total_weight)
            if np.all(requests[i] < out[i]):
                out[i] = np.minimum(out[i], requests[i])
                met[i] = True
            given += out[i] - old
        remaining = remaining - given
        if np.all(remaining < eps):
            break
    return out


def _ratio(l: np.ndarray, r: np.ndarray) -> np.ndarray:
    """helpers.Share elementwise: 0/0 is 0, x/0 is 1."""
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(r == 0, np.where(l == 0, 0.0, 1.0), l / np.where(r == 0, 1.0, r))


def share(allocated, deserved_) -> float:
    """A queue's share: the largest ratio of allocated to deserved."""
    return float(_ratio(_vec(allocated), _vec(deserved_)).max())


def less_equal(l, r) -> bool:
    """Resource.LessEqual: in every dimension ``l < r`` or within a quantum."""
    l, r = _vec(l), _vec(r)
    return bool(np.all((l < r) | (np.abs(l - r) < epsilon(len(l)))))


def overused(allocated, deserved_) -> bool:
    """A queue is refused further gangs when its allocation is not
    ``LessEqual`` its deserved share."""
    return not less_equal(allocated, deserved_)


def dominant_share(job_allocated, total) -> float:
    """DRF's share of a job: its largest ratio of allocated to the cluster."""
    return float(_ratio(_vec(job_allocated), _vec(total)).max())


class Gang(NamedTuple):
    name: str
    queue: int            # index into the queues
    request: tuple        # [R], the whole gang's (min_member pods)
    created: float


class Walk(NamedTuple):
    deserved: np.ndarray  # [Q, R]
    allocated: np.ndarray  # [Q, R] after the walk
    admitted: Set[str]    # names of the gangs taken


def allocate(gangs: Sequence[Gang], weights, capacity) -> Walk:
    """One session's walk over pending ``gangs`` on an empty cluster of
    aggregate ``capacity`` ``[R]``, the queues given in the order of their
    creation: until no queue is left, take the queue of least share that is
    not overused (ties: creation), take its gang of least dominant share
    (ties: creation, then name), allocate it whole if it fits what is left,
    and update the queue's allocation *before the next pick*."""
    capacity = _vec(capacity)
    q, r = len(weights), len(capacity)
    allocated = np.zeros((q, r))
    requests = np.zeros((q, r))
    for g in gangs:
        requests[g.queue] += _vec(g.request)
    des = deserved(capacity, weights, requests)
    left = capacity.copy()
    # A pending gang holds nothing: its dominant share is
    # dominant_share(0, capacity) = 0 for every one, so the job order within
    # a queue is the tie-break, creation then name (departure 3).
    pending: Dict[int, List[Gang]] = {}
    for g in sorted(gangs, key=lambda g: (g.created, g.name), reverse=True):
        pending.setdefault(g.queue, []).append(g)     # pop() takes the first
    admitted: Set[str] = set()
    while pending:
        live = [i for i in pending if not overused(allocated[i], des[i])]
        if not live:
            break
        i = min(live, key=lambda i: (share(allocated[i], des[i]), i))
        g = pending[i].pop()
        if not pending[i]:
            del pending[i]
        req = _vec(g.request)
        if less_equal(req, left):
            allocated[i] += req
            left = left - req
            admitted.add(g.name)
    return Walk(des, allocated, admitted)
