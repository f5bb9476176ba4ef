"""The guarantees, checked from the plans the benchmark generated, the binds
its binder saw and the evictions its evictor saw: an integer ledger of its
own, nothing of the program's accounting.  (From ``chip_smoke.py``'s
``_check_placement``, extended to a cluster that lives through many rounds.)

Checked, at full size, for every round of a run (set-up rounds too):

- every bind names a pod of this run that is alive and a node of this
  cluster (else *unknown*);
- no pod is bound twice in one life (*double*); a key bound again after its
  termination ended is a new life;
- every pod submitted in the round that may not wait is bound by the round's
  end (*unbound*);
- no node holds more than its allocatable cpu (milli), memory (bytes) or pod
  count (*oversubscribed*): after the round's binds, and in a round with
  evictions, terminations or cycles after its completions, after every batch
  of binds in the order of the stamps, so that a bind stamped before the
  termination or completion that made its room is counted;
- every gang of the round has 0 or at least ``min_member`` pods bound
  (*split* gangs);
- every eviction names a live, bound pod of this run (*evicted_unknown*) that
  is not terminating already (*evicted_twice*), a wave of evictions leaves
  each victim's gang with 0 or at least ``min_member`` pods that run, unless
  ``min_member`` is 1 (*gang_broken*, the gang plugin's floor), and every
  eviction has its termination ended by the round's end (*never_terminated*);
- after the run, the keys the ledger holds alive are the keys the store
  holds (*lost*: the ledger's alone, *ghost*: the store's alone);
- in a round whose gangs entered as Jobs (``RoundEvents.jobs``), and only
  there: the pod records the job controller made for the batch are the
  plan's, each once and none more under a Job of the batch
  (*pods_not_as_planned*: per Job, the pods missing or the pods more,
  whichever is more, and every second copy); a Job of which at least
  ``min_available`` pods were bound, and so reported Running, reads Running
  once the round has reconciled (*jobs_not_running*); a Job that completed
  and was deleted has left no pod, PodGroup or Job record in the store when
  its round ends (*jobs_left_behind*).

Pods that may wait and victims waiting for room again are counted and
printed with no limit.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

LIMITED = ("unknown", "double", "unbound", "oversubscribed", "split",
           "evicted_unknown", "evicted_twice", "never_terminated",
           "gang_broken", "lost", "ghost")
OF_JOBS = ("pods_not_as_planned", "jobs_not_running", "jobs_left_behind")
# alive[key] = [cpu, mem, node or -1, terminating, lives, plan, index in plan]
NODE, TERMINATING, LIVES, PLAN, INDEX = 2, 3, 4, 5, 6


@dataclass
class JobEvents:
    """What a round whose gangs entered as Jobs saw of them, as plain data."""

    created: Sequence = ()             # [(pod key, its owner's job key)] made
    not_running: Sequence[str] = ()    # job keys not Running once reconciled
    left_behind: Sequence[str] = ()    # completed, deleted, and a record left


@dataclass
class RoundEvents:
    """What happened in one round, as plain data."""

    plan: object                       # generate.Plan submitted this round
    arrivals: Sequence                 # [(t_ns, keys, hosts)] seen this round
    deleted: Sequence[str] = ()        # keys the client deleted at its end
    evictions: Sequence = ()           # [(t_ns, key)] the evictor saw
    terminations: Sequence = ()        # [(t_ns, key)] the kubelet's side ended
    # When the client's deletions were done; given where something of the
    # round is stamped after it or was evicted, so that order matters.
    t_deleted: Optional[int] = None
    jobs: Optional[JobEvents] = None   # given under ``entry: jobs`` alone


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
    evicted_unknown: int = 0
    evicted_twice: int = 0
    never_terminated: int = 0
    gang_broken: int = 0
    lost: int = 0
    ghost: int = 0
    evictions: int = 0
    terminations: int = 0
    may_wait: int = 0                  # submitted pods that need not bind
    waiting: int = 0                   # of them, not bound at the run's end
    waiting_victims: int = 0           # terminated, not bound again at its end
    worst_fill: float = 0.0            # highest used / allocatable seen
    of_jobs: Dict[str, int] = field(default_factory=dict)  # entry: jobs alone
    extra: Dict[str, int] = field(default_factory=dict)   # a config's checks
    examples: List[str] = field(default_factory=list)

    def example(self, text: str) -> None:
        if len(self.examples) < 5:
            self.examples.append(text)

    @property
    def failed(self) -> int:
        """Pods that missed a guarantee (a pod of a split gang or on an
        oversubscribed node is counted once, by the check that caught it)."""
        return sum(self.compared().values())

    @property
    def ok(self) -> bool:
        return self.failed == 0

    def compared(self) -> Dict[str, int]:
        """Every count that has a limit (0), by name."""
        return {**{name: getattr(self, name) for name in LIMITED},
                **self.of_jobs, **self.extra}

    def lines(self) -> List[str]:
        out = [f"validate: {self.rounds} rounds, {self.submitted} pods "
               f"submitted, {self.bound} binds seen, {self.evictions} "
               f"evictions seen, {self.terminations} terminations ended"]
        for name, count in self.compared().items():
            out.append(f"validate: {name} = {count} (limit 0)")
        out.append(f"validate: {self.may_wait} pods may wait, {self.waiting} "
                   f"of them and {self.waiting_victims} victims wait at the "
                   "run's end (no limit)")
        out.append(f"validate: fullest node at {self.worst_fill:.6f} of its "
                   "allocatable (limit 1.0)")
        out.extend(f"validate: e.g. {e}" for e in self.examples)
        return out


class Ledger:
    """Per-node integer usage, rebuilt from binds, terminations and the
    client's deletions alone."""

    def __init__(self, node_names: Sequence[str], alloc: np.ndarray):
        self.node_index: Dict[str, int] = {n: i for i, n in enumerate(node_names)}
        self.alloc = np.asarray(alloc, dtype=np.int64)
        self.used = np.zeros_like(self.alloc)
        self.alive: Dict[str, list] = {}
        self.terminating: Dict[str, None] = {}     # keys evicted, not yet ended
        self._gone: Dict[str, list] = {}   # deleted inside the round replayed
        self._wave: Dict[tuple, object] = {}       # gangs of a wave's victims
        self.verdict = Verdict()

    def apply(self, ev: RoundEvents) -> None:
        v = self.verdict
        plan = ev.plan
        keys = plan.keys()
        v.rounds += 1
        v.submitted += len(keys)
        may_wait = bool(getattr(plan, "may_wait", False))
        if may_wait:
            v.may_wait += len(keys)
        for i, (key, cpu, mem) in enumerate(zip(keys, plan.cpu_milli.tolist(),
                                                plan.mem_bytes.tolist())):
            self.alive[key] = [cpu, mem, -1, False, 0, plan, i]
        self._gone.clear()
        if ev.evictions or ev.terminations or ev.t_deleted is not None:
            self._replay(ev)
            deleted = ()
        else:
            self._bind(ev.arrivals)
            # No node over its allocatable.
            over = np.any(self.used > self.alloc, axis=1)
            if over.any():
                v.oversubscribed += int(self.used[over, 2].sum())
                self._over_example(int(np.flatnonzero(over)[0]))
            deleted = ev.deleted
        # Every pod of the round that may not wait bound; gangs whole.
        bound_in_gang = np.zeros(len(plan.gang_names), dtype=np.int64)
        for key, g in zip(keys, plan.gang.tolist()):
            pod = self.alive.get(key) or self._gone.get(key)
            if pod is not None and pod[NODE] >= 0:
                bound_in_gang[g] += 1
            elif not may_wait:
                v.unbound += 1
                v.example(f"{key} not bound within the round")
        bad = (bound_in_gang > 0) & (bound_in_gang < plan.gang_min_member)
        if bad.any():
            v.split += int(bound_in_gang[bad].sum())
            v.example(f"{int(bad.sum())} gangs split, e.g. "
                      f"{plan.gang_names[int(np.flatnonzero(bad)[0])]}")
        v.worst_fill = max(v.worst_fill,
                           float((self.used / self.alloc).max()))
        if self.terminating:
            v.never_terminated += len(self.terminating)
            v.example(f"{len(self.terminating)} evictions not ended by the "
                      f"round's end, e.g. {next(iter(self.terminating))}")
            self.terminating.clear()       # counted once
        if ev.jobs is not None:
            self._hold_jobs(plan, keys, ev.jobs, bound_in_gang)
        # The client's deletions free what the ledger says the pods held.
        self._delete(deleted)

    # ---- the steps ---------------------------------------------------------

    def _bind(self, arrivals) -> List[int]:
        """Apply batches of binds; the node indices touched."""
        v = self.verdict
        add_node, add_cpu, add_mem = [], [], []
        for _t, bkeys, hosts in arrivals:
            for key, host in zip(bkeys, hosts):
                pod = self.alive.get(key)
                node = self.node_index.get(host)
                if pod is None or node is None:
                    v.unknown += 1
                    v.example(f"bind of {key} to {host}: not a live pod of "
                              "this run or not a node")
                    continue
                if pod[NODE] >= 0:
                    v.double += 1
                    v.example(f"{key} bound twice")
                    continue
                pod[NODE] = node
                v.bound += 1
                add_node.append(node)
                add_cpu.append(pod[0])
                add_mem.append(pod[1])
        if add_node:
            idx = np.asarray(add_node, dtype=np.int64)
            np.add.at(self.used[:, 0], idx, np.asarray(add_cpu, np.int64))
            np.add.at(self.used[:, 1], idx, np.asarray(add_mem, np.int64))
            np.add.at(self.used[:, 2], idx, 1)
        return add_node

    def _delete(self, keys, remember: bool = False) -> None:
        for key in keys:
            pod = self.alive.pop(key, None)
            if pod is None:
                continue
            self.terminating.pop(key, None)
            if pod[NODE] >= 0:
                self.used[pod[NODE]] -= (pod[0], pod[1], 1)
            if remember:
                self._gone[key] = pod

    def _hold_jobs(self, plan, keys, jobs: JobEvents, bound_in_gang) -> None:
        """The three counts of a round whose gangs entered as Jobs."""
        v = self.verdict
        if not v.of_jobs:
            v.of_jobs.update(dict.fromkeys(OF_JOBS, 0))
        counts = v.of_jobs
        owners = plan.job_keys()
        planned: Dict[str, set] = {owner: set() for owner in owners}
        for key, g in zip(keys, plan.gang.tolist()):
            planned[owners[g]].add(key)
        held: Dict[str, Counter] = {owner: Counter() for owner in owners}
        for key, owner in jobs.created:
            if owner in held:
                held[owner][key] += 1
        for owner, want in planned.items():
            got = held[owner]
            off = sum(n - 1 for n in got.values() if n > 1) \
                + max(len(want - got.keys()), len(got.keys() - want))
            if off:
                counts["pods_not_as_planned"] += off
                v.example(f"job {owner}: the plan's pods {sorted(want - got.keys())[:3]} "
                          f"are not in the store, {sorted(got.keys() - want)[:3]} "
                          "are and are no pod of the plan")
        floor = dict(zip(owners, (bound_in_gang >= plan.gang_min_member).tolist()))
        for owner in jobs.not_running:
            if floor.get(owner):
                counts["jobs_not_running"] += 1
                v.example(f"job {owner} has min_available pods bound and "
                          "reported Running and does not read Running")
        counts["jobs_left_behind"] += len(jobs.left_behind)
        if jobs.left_behind:
            v.example(f"{len(jobs.left_behind)} completed jobs left a record "
                      f"in the store, e.g. {jobs.left_behind[0]}")

    def _over_example(self, i: int) -> None:
        self.verdict.example(
            f"node {i} holds {self.used[i].tolist()} of "
            f"{self.alloc[i].tolist()} (cpu milli, memory bytes, pods)")

    def _replay(self, ev: RoundEvents) -> None:
        """The round's binds, evictions, terminations and the client's
        deletions in the order of their stamps; capacity is held after
        every batch of binds."""
        v = self.verdict
        steps = [(t, 0, (t, k, h)) for t, k, h in ev.arrivals]
        steps += [(t, 1, key) for t, key in ev.evictions]
        steps += [(t, 2, key) for t, key in ev.terminations]
        if ev.t_deleted is not None:
            steps.append((ev.t_deleted, 3, None))
        steps.sort(key=lambda s: (s[0], s[1]))
        deleted = False
        for _t, kind, what in steps:
            if kind != 1 and self._wave:
                self._hold_floors()
            if kind == 0:
                touched = self._bind([what])
                if touched:
                    idx = np.asarray(touched, dtype=np.int64)
                    v.worst_fill = max(v.worst_fill, float(
                        (self.used[idx] / self.alloc[idx]).max()))
                    over = np.any(self.used[idx] > self.alloc[idx], axis=1)
                    if over.any():
                        v.oversubscribed += int(over.sum())
                        self._over_example(int(idx[np.flatnonzero(over)[0]]))
            elif kind == 1:
                self._evict(what)
            elif kind == 2:
                self._terminate(what)
            else:
                self._delete(ev.deleted, remember=True)
                deleted = True
        if self._wave:
            self._hold_floors()
        if not deleted:
            self._delete(ev.deleted, remember=True)

    def _evict(self, key: str) -> None:
        v = self.verdict
        v.evictions += 1
        pod = self.alive.get(key)
        if pod is None or pod[NODE] < 0:
            v.evicted_unknown += 1
            v.example(f"eviction of {key}: not a live, bound pod of this run")
            return
        if pod[TERMINATING]:
            v.evicted_twice += 1
            v.example(f"{key} evicted twice in one life")
            return
        pod[TERMINATING] = True
        self.terminating[key] = None
        plan = pod[PLAN]
        self._wave[(id(plan), int(plan.gang[pod[INDEX]]))] = plan

    def _hold_floors(self) -> None:
        """The gang plugin's floor, over the gangs of the victims of one wave
        (the evictions no other stamp lies between) as they run after it."""
        v = self.verdict
        keys: Dict[int, list] = {}          # of each plan, made once a wave
        for (plan_id, g), plan in self._wave.items():
            floor = int(plan.gang_min_member[g])
            if floor == 1:
                continue
            names = keys.get(plan_id) or keys.setdefault(plan_id, plan.keys())
            running = 0
            for m in np.flatnonzero(plan.gang == g).tolist():
                mate = self.alive.get(names[m])
                if mate is not None and mate[PLAN] is plan \
                        and mate[NODE] >= 0 and not mate[TERMINATING]:
                    running += 1
            if 0 < running < floor:
                v.gang_broken += 1
                v.example(f"a wave of evictions leaves {plan.gang_names[g]} "
                          f"with {running} of min_member {floor} running")
        self._wave.clear()

    def _terminate(self, key: str) -> None:
        v = self.verdict
        v.terminations += 1
        pod = self.alive.get(key)
        if pod is None or not pod[TERMINATING]:
            v.evicted_unknown += 1
            v.example(f"termination of {key}, which no eviction named")
            return
        self.terminating.pop(key, None)
        if pod[NODE] >= 0:
            self.used[pod[NODE]] -= (pod[0], pod[1], 1)
        # A new life: Pending again, for as long as the program restores it.
        pod[NODE], pod[TERMINATING] = -1, False
        pod[LIVES] += 1

    def close(self, live_keys: Optional[Sequence[str]] = None) -> None:
        """After the last round: who waits, and conservation against the
        keys the store holds, handed over as plain data."""
        v = self.verdict
        for pod in self.alive.values():
            if pod[NODE] < 0:
                if pod[LIVES]:
                    v.waiting_victims += 1
                elif getattr(pod[PLAN], "may_wait", False):
                    v.waiting += 1
        if live_keys is None:
            return
        held = Counter(live_keys)
        for key in self.alive:
            if held[key] > 0:
                held[key] -= 1
            else:
                v.lost += 1
                v.example(f"{key} is alive by the ledger and not in the store")
        ghosts = [key for key, n in held.items() if n > 0]
        v.ghost += sum(held[key] for key in ghosts)
        if ghosts:
            v.example(f"{ghosts[0]} is in the store and not alive by the "
                      "ledger")


def check(node_names, alloc, events: Sequence[RoundEvents],
          live_keys: Optional[Sequence[str]] = None) -> Verdict:
    ledger = Ledger(node_names, alloc)
    for ev in events:
        ledger.apply(ev)
    ledger.close(live_keys)
    return ledger.verdict
