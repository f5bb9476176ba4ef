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
   where the configuration's ``probe`` block says ``fill_pods``, that many
   more in the configuration's gang shape and pod size, which the client
   places itself (``Driver.place``: they arrive bound, as pods whatever the
   traffic's ``entry``), one at a time on the node that holds the fewest
   pods, lowest index among ties, by the ledger of the run so far, and
   which are not completed either.  The control can miss only where no
   node stands nearly empty: a 1 cpu / 1 Gi pod on a 64 cpu / 256 Gi node
   scores 20 - 30 k / 256 as the node's k-th pod, the emptiest node wins,
   and bfloat16 tells a node's 1st, 2nd, 3rd and 4th pod apart and scores
   its 4th and 5th alike (19.5).  A cell whose batch is small against its
   cluster gets to 3 and 4 pods a node by this fill alone, and not through
   the scheduler: the wave solver gives a burst's k-th pod the k-th best
   node scaled by what the best still holds, so a burst of one pod size
   lands 64 a node and leaves the other nodes empty (PERF.md section 2);
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

import heapq
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

from ..reference import score_ref
from . import generate, validate


@dataclass
class ProbeVerdict:
    probes: int = 0
    misses: int = 0
    worst_shortfall: float = 0.0        # reference's best score - chosen node's
    smallest_gap: float = float("inf")  # between the two best distinct scores
    most_tied: int = 0                  # nodes within TIE of the best, at most
    missed: List[bool] = field(default_factory=list)   # probe by probe
    # at the first probe: pods a node holds -> nodes that hold so many
    nodes_by_pods: Dict[int, int] = field(default_factory=dict)
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
    batches' priority class, ``gen`` deals it to these too).  With
    ``fill_pods`` the fill is two rounds: the batch-sized one through the
    cell's own entry and the scheduler, then that many pods more which the
    client places.  Returns the index in ``driver.rounds`` of the first
    probe round."""
    probes, before = int(par["probes"]), int(par.get("before_drain", 0))
    keep = int(par.get("keep_pods", 0))

    def drain(plan) -> int:
        """Pods to complete after ``plan`` is bound so that ``keep`` remain."""
        return max(0, driver.pods_alive + plan.n_pods - keep) if keep else 0

    fill = gen.plan(batch_pods, "probefill")
    driver.round(fill, 0 if before else drain(fill))
    if par.get("fill_pods"):
        # After the batch-sized fill: as Jobs, that one is the controllers'
        # to make, and a job controller that walks every pod of the store
        # for each (PERF.md section 7 (5)) walks the fewer the earlier.
        own = gen.plan(int(par["fill_pods"]), "probefill-own")
        driver.place(own, _deal(driver, own))
    first = len(driver.rounds)
    for k in range(probes):
        plan = gen.plan(1, f"probe{k:03d}", gang_size=1)
        driver.round(plan, drain(plan) if k + 1 == before else 0)
    return first


def _deal(driver, plan) -> List[str]:
    """The node of each pod of ``plan``, dealt one at a time to the node
    that holds the fewest pods and has room for it, lowest index among
    ties, on the cluster as the ledger of the driver's rounds so far leaves
    it: the nodes end level to within one pod, and those that hold one more
    are the first of them."""
    names = generate.node_names(driver.config)
    ledger = validate.Ledger(names, generate.node_alloc(driver.config))
    for r in driver.rounds:
        ledger.apply(r.events())
    alloc, used = ledger.alloc, ledger.used
    heap = [(held, i) for i, held in enumerate(used[:, 2].tolist())]
    heapq.heapify(heap)
    hosts = []
    for cpu, mem in zip(plan.cpu_milli.tolist(), plan.mem_bytes.tolist()):
        full = []
        while True:
            if not heap:
                raise RuntimeError(f"the probe's own fill: no node has room "
                                   f"for pod {len(hosts)} of {plan.n_pods}")
            held, i = heapq.heappop(heap)
            if np.all(used[i] + (cpu, mem, 1) <= alloc[i]):
                break
            full.append((held, i))
        used[i] += (cpu, mem, 1)
        hosts.append(names[i])
        for entry in full + [(held + 1, i)]:
            heapq.heappush(heap, entry)
    return hosts


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
        if i == first_probe:
            held, n = np.unique(ledger.used[:, 2], return_counts=True)
            out.nodes_by_pods = dict(zip(held.tolist(), n.tolist()))
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
