"""The probe: node choice against the plain reference, where the semantics
fix it, on the object that was timed and at the timed size.

Bind-for-bind equality of a whole backlog with a sequential reference is not
available: the wave solver scores a wave against the state at the wave's
start (``ops/wave.py``'s documented deviation).  What is fixed is the choice
for a *single* pending pod: the best-scoring feasible node, lowest index
among ties (PARITY.md deviation 1).  So, once the window has closed and
outside every timed span, the run goes on with the same ``Driver`` (the same
store, mirror, ``Scheduler`` and compiled programs, every node of the
configuration):

1. one more batch of the cell's own size is submitted and scheduled and not
   completed, so the cluster stands as the timed solve leaves it (a burst
   cell: the whole backlog bound; a churn cell: the residents and a batch);
2. ``before_drain`` one-pod gangs are submitted, one per cycle, on that
   state;
3. the client lets the oldest jobs finish until ``keep_pods`` pods remain
   (the mix's own completion step; nothing where fewer are there): the
   program rebuilds its object model at the first event after every commit,
   2.3 s a probe cycle at 101,000 pods, and the probe needs some dozens of
   cycles before the control fails on every seed (PERF.md section 2);
4. the rest of the ``probes`` one-pod gangs, one per cycle.

For each of those the cluster's state is the integer ledger that
``validate`` keeps from the binder's record, over all N nodes, and the node
the program chose must be the one ``reference/score_ref.py`` names.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Sequence, Tuple

import numpy as np

from ..reference import score_ref
from . import validate


@dataclass
class ProbeVerdict:
    probes: int = 0
    misses: int = 0
    worst_shortfall: float = 0.0        # reference's best score - chosen node's
    smallest_gap: float = float("inf")  # between the two best distinct scores
    most_tied: int = 0                  # nodes within TIE of the best, at most
    missed: List[bool] = field(default_factory=list)   # probe by probe
    examples: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.probes > 0 and self.misses == 0

    def lines(self) -> List[str]:
        out = [f"probe: {self.misses} of {self.probes} one-pod choices differ "
               "from the float64 reference over every node (limit 0)",
               f"probe: the chosen node's score lies at most "
               f"{self.worst_shortfall:.9f} below the reference's best "
               f"(a tie is within {score_ref.TIE})",
               f"probe: smallest gap between the two best scores "
               f"{self.smallest_gap:.6f}; at most {self.most_tied} nodes tied "
               "for the best (float32 resolves ~2e-6 here, bfloat16 0.125)"]
        out.extend(f"probe: e.g. {e}" for e in self.examples[:5])
        return out


def drive(driver, gen, batch_pods: int, par: dict) -> int:
    """Go on with the timed ``driver`` after the window: the fill, the
    one-pod gangs before the drain, the drain, the rest of them (``par`` is
    the configuration's ``probe`` block; where the traffic names the
    batches' priority class, ``gen`` deals it to these too).  Returns the
    index in ``driver.rounds`` of the first probe round."""
    probes, before = int(par["probes"]), int(par.get("before_drain", 0))
    keep = int(par.get("keep_pods", 0))

    def drain(plan) -> int:
        """Pods to complete after ``plan`` is bound so that ``keep`` remain."""
        return max(0, driver.pods_alive + plan.n_pods - keep) if keep else 0

    fill = gen.plan(batch_pods, "probefill")
    driver.round(fill, 0 if before else drain(fill))
    first = len(driver.rounds)
    for k in range(probes):
        plan = gen.plan(1, f"probe{k:03d}", gang_size=1)
        driver.round(plan, drain(plan) if k + 1 == before else 0)
    return first


def check(node_names: Sequence[str], alloc: np.ndarray,
          events: Sequence[validate.RoundEvents], first_probe: int,
          control_dtype=None, live_keys=None
          ) -> Tuple[validate.Verdict, ProbeVerdict]:
    """The guarantees over every round, and every probe round's node held to
    the float64 reference's answer, computed from the ledger as it stands
    before that round.  With ``control_dtype`` the control stands in the
    program's place: at each probe the node "chosen" is the one the same
    reference names when computed in that lower precision (it has to
    miss); the ledger still follows the program's binds.  ``live_keys``:
    the keys the store held after the last round, for conservation."""
    ledger = validate.Ledger(node_names, alloc)
    out = ProbeVerdict()
    for i, ev in enumerate(events):
        if i >= first_probe:
            _compare(ledger, ev, control_dtype, out)
        ledger.apply(ev)
    ledger.close(live_keys)
    return ledger.verdict, out


def _compare(ledger, ev, control_dtype, out: ProbeVerdict) -> None:
    plan = ev.plan
    req = (int(plan.cpu_milli[0]), int(plan.mem_bytes[0]))
    want = score_ref.choose(ledger.alloc, ledger.used, req)
    ok = score_ref.feasible(ledger.alloc, ledger.used, req)
    s = np.where(ok, score_ref.scores(ledger.alloc, ledger.used, req), -np.inf)
    out.smallest_gap = min(out.smallest_gap, score_ref.top_two_gap(
        ledger.alloc, ledger.used, req))
    out.most_tied = max(out.most_tied,
                        int((s >= s.max() - score_ref.TIE).sum()))
    got = [h for _t, _k, hosts in ev.arrivals for h in hosts]
    got_i = ledger.node_index.get(got[0], -2) if len(got) == 1 else -2
    if control_dtype is not None:
        got_i = score_ref.choose(ledger.alloc, ledger.used, req, control_dtype)
        got = [f"node {got_i} (the control)"]
    out.probes += 1
    short = float(s.max() - s[got_i]) if got_i >= 0 else float("inf")
    out.worst_shortfall = max(out.worst_shortfall, short)
    out.missed.append(got_i != want)
    if got_i != want:
        out.misses += 1
        out.examples.append(
            f"{plan.names[0]} (cpu {req[0]}m, mem {req[1]}) bound to "
            f"{got or 'nothing'} ({short:.6f} below the best score), "
            f"reference says node {want}")
