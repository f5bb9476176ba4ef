"""The guarantees, checked from the plans the benchmark generated and the
binds its binder saw: an integer ledger of its own, nothing of the program's
accounting.  (From ``chip_smoke.py``'s ``_check_placement``, extended to a
cluster that lives through many rounds.)

Checked, at full size, for every round of a run (set-up rounds too):

- every bind names a pod of this run that is alive and a node of this
  cluster (else *unknown*);
- no pod is bound twice in one life (*double*);
- every pod submitted in the round is bound by the round's end (*unbound*):
  every configuration keeps demand under capacity, so none may wait;
- after the round's binds no node holds more than its allocatable cpu
  (milli), memory (bytes) or pod count (*oversubscribed* nodes);
- every gang of the round has 0 or at least ``min_member`` pods bound
  (*split* gangs).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence

import numpy as np


@dataclass
class RoundEvents:
    """What happened in one round, as plain data."""

    plan: object                       # generate.Plan submitted this round
    arrivals: Sequence                 # [(t_ns, keys, hosts)] seen this round
    deleted: Sequence[str] = ()        # keys the client deleted at its end


@dataclass
class Verdict:
    rounds: int = 0
    submitted: int = 0
    bound: int = 0
    unknown: int = 0
    double: int = 0
    unbound: int = 0
    oversubscribed: int = 0
    split: int = 0
    worst_fill: float = 0.0            # highest used / allocatable seen
    examples: List[str] = field(default_factory=list)

    def example(self, text: str) -> None:
        if len(self.examples) < 5:
            self.examples.append(text)

    @property
    def failed(self) -> int:
        """Pods that missed a guarantee (a pod of a split gang or on an
        oversubscribed node is counted once, by the check that caught it)."""
        return self.unknown + self.double + self.unbound \
            + self.oversubscribed + self.split

    @property
    def ok(self) -> bool:
        return self.failed == 0

    def lines(self) -> List[str]:
        out = [f"validate: {self.rounds} rounds, {self.submitted} pods "
               f"submitted, {self.bound} binds seen"]
        for name in ("unknown", "double", "unbound", "oversubscribed", "split"):
            out.append(f"validate: {name} = {getattr(self, name)} (limit 0)")
        out.append(f"validate: fullest node at {self.worst_fill:.6f} of its "
                   "allocatable (limit 1.0)")
        out.extend(f"validate: e.g. {e}" for e in self.examples)
        return out


class Ledger:
    """Per-node integer usage, rebuilt from binds alone."""

    def __init__(self, node_names: Sequence[str], alloc: np.ndarray):
        self.node_index: Dict[str, int] = {n: i for i, n in enumerate(node_names)}
        self.alloc = np.asarray(alloc, dtype=np.int64)
        self.used = np.zeros_like(self.alloc)
        # key -> [cpu, mem, node index or -1] for pods alive now
        self.alive: Dict[str, list] = {}
        self.verdict = Verdict()

    def apply(self, ev: RoundEvents) -> None:
        v = self.verdict
        plan = ev.plan
        keys = plan.keys()
        v.rounds += 1
        v.submitted += len(keys)
        for key, cpu, mem in zip(keys, plan.cpu_milli.tolist(),
                                 plan.mem_bytes.tolist()):
            self.alive[key] = [cpu, mem, -1]
        add_node, add_cpu, add_mem = [], [], []
        for _t, bkeys, hosts in ev.arrivals:
            for key, host in zip(bkeys, hosts):
                pod = self.alive.get(key)
                node = self.node_index.get(host)
                if pod is None or node is None:
                    v.unknown += 1
                    v.example(f"bind of {key} to {host}: not a live pod of "
                              "this run or not a node")
                    continue
                if pod[2] >= 0:
                    v.double += 1
                    v.example(f"{key} bound twice")
                    continue
                pod[2] = node
                v.bound += 1
                add_node.append(node)
                add_cpu.append(pod[0])
                add_mem.append(pod[1])
        if add_node:
            idx = np.asarray(add_node, dtype=np.int64)
            np.add.at(self.used[:, 0], idx, np.asarray(add_cpu, np.int64))
            np.add.at(self.used[:, 1], idx, np.asarray(add_mem, np.int64))
            np.add.at(self.used[:, 2], idx, 1)
        # Every pod of the round bound; gangs whole.
        bound_in_gang = np.zeros(len(plan.gang_names), dtype=np.int64)
        for key, g in zip(keys, plan.gang.tolist()):
            if self.alive[key][2] >= 0:
                bound_in_gang[g] += 1
            else:
                v.unbound += 1
                v.example(f"{key} not bound within the round")
        bad = (bound_in_gang > 0) & (bound_in_gang < plan.gang_min_member)
        if bad.any():
            v.split += int(bound_in_gang[bad].sum())
            v.example(f"{int(bad.sum())} gangs split, e.g. "
                      f"{plan.gang_names[int(np.flatnonzero(bad)[0])]}")
        # No node over its allocatable.
        over = np.any(self.used > self.alloc, axis=1)
        if over.any():
            v.oversubscribed += int(self.used[over, 2].sum())
            i = int(np.flatnonzero(over)[0])
            v.example(f"node {i} holds {self.used[i].tolist()} of "
                      f"{self.alloc[i].tolist()} (cpu milli, memory bytes, "
                      "pods)")
        v.worst_fill = max(v.worst_fill,
                           float((self.used / self.alloc).max()))
        # The client's deletions free what the ledger says the pods held.
        for key in ev.deleted:
            cpu, mem, node = self.alive.pop(key)
            if node >= 0:
                self.used[node] -= (cpu, mem, 1)


def check(node_names, alloc, events: Sequence[RoundEvents]) -> Verdict:
    ledger = Ledger(node_names, alloc)
    for ev in events:
        ledger.apply(ev)
    return ledger.verdict
