"""The one loop every cell runs: rounds of submit, schedule, complete, settle.

Single-threaded apart from the program's own bind dispatcher.  The program
is reached through its public entry points only: the store's event API
(``add_node``, ``add_queue``, ``add_priority_class``, ``add_pod_group``,
``add_pod``, ``update_pod``, ``delete_pod``, ``delete_pod_group``), its public
``pods`` map, ``flush_binds``, ``Scheduler(store, conf_str=...).run_once()``
and the binder and evictor slots.  No knob of the program is set and nothing
in it is patched.

The kubelet's side of a cycle.  The harness plays the node agents, and no
controller: after a cycle whose hand-over is done, a bound pod runs (where
the traffic says ``pods_run``: the program takes its victims among Running
pods only) and an eviction whose termination is due ends
(``store.delete_pod`` of the record the evictor was handed,
``termination_cycles`` cycles after the cycle that evicted it).  What stands
for the victim afterwards is whatever the program put into ``store.pods`` by
then under the victim's namespace and name (its ``MigrationLedger`` restores
the pod as a new Pending record), or nothing.  A cell in which nothing is
evicted and no pod is said to run pays one comparison a cycle for all this.

``entry: jobs`` (the traffic's key; absent or ``pods``: all of the above, as
it was).  The same closed round with the user one layer further out: a gang
is a ``Job`` handed to ``AdmittedStore.add_batch_job``, and
``ControllerManager`` stands between the client and the store
(``harness/jobs.py``).  *submit* is the admissions, each stamped (that stamp
is the submit time of the gang's pods in every end-to-end metric), and one
pump; *schedule* the cycles, with a pump after each cycle's hand-over and
kubelet's side (the first cycle admits the PodGroups, which have no pods
yet; the pump creates the pods; the second binds); *reconcile*, a phase of
its own inside the round, the kubelet's Running reports and pumps until
every Job of the batch reads Running; *complete* the Jobs' own end (pods
Succeeded, a pump, ``delete_batch_job``, a pump, the terminations ended).
Each wait on the controllers is at most the traffic's ``max_pumps`` pumps.
"""

from __future__ import annotations

import contextlib
import copy
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import generate
from .binder import RecordingBinder
from .evictor import RecordingEvictor
from .jobs import JobEntry
from .validate import JobEvents, RoundEvents

POLL_S = 0.01      # how often a wait asks whether the hand-over is done


def default_scheduler(store, conf_str):
    from volcano_tpu.scheduler import Scheduler

    return Scheduler(store, conf_str=conf_str)


@dataclass
class Round:
    """One round as observed.  Times are ``perf_counter_ns`` readings."""

    plan: generate.Plan
    submit_ns: np.ndarray               # each pod's own add_pod call (under
    #                                     entry: jobs its gang's add_batch_job)
    t_start: int
    t_submitted: int                    # the backlog stands
    t_scheduled: int                    # every bind seen (or cycles used up)
    t_reconciled: int                   # t_scheduled; under entry: jobs, every
    #                                     Job Running (or its pumps used up)
    t_completed: int                    # completions done
    t_end: int                          # settled
    cycles: int                         # of the schedule phase
    run_once_s: float                   # every run_once() of the round
    arrivals: list
    deleted: List[str]
    settle_cycles: int = 0
    evictions: list = field(default_factory=list)       # [(t, key)]
    terminations: list = field(default_factory=list)    # [(t, key)]
    wait_s: float = 0.0                 # longest wait for a cycle's hand-over
    waits_timed_out: int = 0            # waits that reached bind_wait_s
    lanes: Dict[str, float] = field(default_factory=dict)
    records: List[dict] = field(default_factory=list)   # traced run: per cycle
    # Under ``entry: jobs`` alone; else ``jobs`` is None and nothing reads them.
    jobs: Optional[JobEvents] = None
    t_admitted: int = 0                 # every add_batch_job call made
    pumps: Dict[str, int] = field(default_factory=dict)      # by phase
    pump_s: Dict[str, float] = field(default_factory=dict)   # their seconds
    pumps_used_up: List[str] = field(default_factory=list)   # phases out of them

    def events(self) -> RoundEvents:
        ordered = self.settle_cycles or self.evictions or self.terminations
        return RoundEvents(self.plan, self.arrivals, self.deleted,
                           self.evictions, self.terminations,
                           self.t_completed if ordered else None, self.jobs)

    def spans(self) -> Dict[str, float]:
        """Seconds by phase.  Under ``entry: jobs`` also ``admit`` (the
        ``add_batch_job`` calls, part of ``submit``), ``reconcile`` (between
        ``schedule`` and ``complete``) and ``pump`` (every pump of the
        round, whatever phase it lies in)."""
        out = {
            "submit": (self.t_submitted - self.t_start) / 1e9,
            "schedule": (self.t_scheduled - self.t_submitted) / 1e9,
            "complete": (self.t_completed - self.t_reconciled) / 1e9,
            "settle": (self.t_end - self.t_completed) / 1e9,
            "round": (self.t_end - self.t_start) / 1e9,
            "run_once": self.run_once_s,
        }
        if self.jobs is not None:
            out.update(
                admit=(self.t_admitted - self.t_start) / 1e9,
                reconcile=(self.t_reconciled - self.t_scheduled) / 1e9,
                pump=sum(self.pump_s.values()))
        return out


class Driver:
    """A store, a scheduler, the benchmark's binder and evictor, and the
    steps of a round on them."""

    def __init__(self, config: dict, max_cycles: int = 4,
                 make_scheduler: Callable = default_scheduler,
                 read_lanes: bool = False, bind_wait_s: float = 10.0,
                 annotate: Optional[Callable] = None,
                 termination_cycles: int = 0, settle_cycles: int = 0,
                 pods_run: bool = False, entry: str = "pods",
                 max_pumps: int = 4):
        from volcano_tpu.api import GROUP_NAME_ANNOTATION
        from volcano_tpu.cache import ClusterStore

        self._group_key = GROUP_NAME_ANNOTATION
        self.config = config
        self.binder = RecordingBinder()
        self.evictor = RecordingEvictor()
        self.store = ClusterStore(binder=self.binder, evictor=self.evictor)
        # Async bind dispatch, as in production (chip_smoke.py, bench.py).
        self.store.async_bind = True
        # The controllers watch the store from before its first event.
        self.jobs = JobEntry(self.store, max_pumps) if entry == "jobs" else None
        add_queue = self.store.add_queue if self.jobs is None \
            else self.jobs.admitted.add_queue
        for queue in generate.to_queues(config):
            add_queue(queue)
        for pc in generate.to_priority_classes(config):
            self.store.add_priority_class(pc)
        self.priority_values = {c["name"]: int(c["value"])
                                for c in config.get("priority_classes", [])}
        for node in generate.to_nodes(config):
            self.store.add_node(node)
        self.sched = make_scheduler(self.store, config["scheduler_conf"])
        self.max_cycles = int(max_cycles)
        self.bind_wait_s = float(bind_wait_s)
        self.termination_cycles = int(termination_cycles)
        self.settle_cycles = int(settle_cycles)
        self.pods_run = bool(pods_run)
        self.read_lanes = read_lanes
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.stamps = itertools.count(1)
        self.fifo: deque = deque()      # gangs still in the cluster, oldest first
        self.rounds: List[Round] = []
        self._seen = 0                  # arrivals already given to a round
        # The kubelet's side.
        self.terminations: list = []    # [(t, key)], every one ended so far
        self._cycle_no = 0              # run_once() calls so far
        self._evicted_in: List[int] = []    # cycle of each eviction seen
        self._ended = 0                 # evictions gone through, in order
        self._terminating: Dict[str, str] = {}   # uid -> key, not yet ended
        self._ran = 0                   # arrivals whose pods were said to run
        self._restored_uid: Dict[str, str] = {}  # key -> uid of its new life
        self._gangs: Optional[Dict[str, tuple]] = None   # name -> fifo entry
        self._mixed = False             # binds of other rounds' pods may come
        self._wait_s = 0.0              # of the round under way: longest wait
        self._waits_timed_out = 0       # and waits that reached bind_wait_s

    def close(self) -> None:
        self.store.close()

    @property
    def pods_alive(self) -> int:
        """Pods submitted and not yet completed."""
        return sum(len(pods) for _pg, pods in self.fifo)

    def live_keys(self) -> List[str]:
        """The keys of the store's pod records, as plain data."""
        return [f"{p.namespace}/{p.name}" for p in self.store.pods.values()]

    def round(self, plan: generate.Plan, complete_pods: int) -> Round:
        """Submit ``plan``, schedule until every bind of it was seen (a plan
        that may wait: one cycle), delete the oldest ``complete_pods`` pods,
        then run the traffic's ``settle_cycles``.  The gangs' objects are
        built before the round's clock starts."""
        jobs = self.jobs
        if jobs is None:
            gangs = generate.to_objects(plan, self.stamps, self.priority_values)
        else:
            gangs = generate.to_jobs(plan, self.stamps, self.config.get("job"))
            jobs.begin_round()
        store = self.store
        n = plan.n_pods
        submit_ns = np.empty(n, dtype=np.int64)
        lacks = 0 if plan.may_wait else n
        want = self.binder.count + lacks
        now = time.perf_counter_ns
        self._mixed = self._mixed or plan.may_wait
        self._wait_s, self._waits_timed_out = 0.0, 0
        evictions0, terminations0 = self.evictor.count, len(self.terminations)
        own_keys = None

        t_start = now()
        t_admitted = 0
        with self.annotate("bench:submit"):
            if jobs is not None:
                jobs.submit(gangs, submit_ns, now)
                t_admitted = now()
                jobs.pump("submit")
            else:
                i = 0
                for pg, pods in gangs:
                    store.add_pod_group(pg)
                    for pod in pods:
                        submit_ns[i] = now()
                        store.add_pod(pod)
                        i += 1
        t_submitted = now()

        cycles = 0
        run_once_s = 0.0
        with self.annotate("bench:schedule"):
            while cycles < self.max_cycles:
                run_once_s += self._cycle()
                cycles += 1
                if jobs is not None and not jobs.created:
                    # no pod of the batch is made yet: no bind to wait for
                    self._await_hand_over()
                    done = False
                elif lacks:
                    done = self._await(want)
                else:                   # a plan that may wait: one cycle
                    self._await_hand_over()
                    done = True
                if done and self._mixed:
                    if own_keys is None:
                        own_keys = set(plan.keys())
                    want, done = self._batch_bound(own_keys, lacks)
                if done:
                    break
                self._kubelet()
                if jobs is not None:
                    jobs.pump("schedule")
        t_scheduled = now()

        t_reconciled, not_running = t_scheduled, ()
        if jobs is not None:
            with self.annotate("bench:reconcile"):
                self._kubelet()
                not_running = jobs.reconcile(gangs)
            t_reconciled = now()

        self.fifo.extend(gangs)
        if self._gangs is not None:
            self._gangs.update((pg.name, (pg, pods)) for pg, pods in gangs)
        deleted: List[str] = []
        with self.annotate("bench:complete"):
            left = int(complete_pods)
            finishing = []              # entry: jobs, the Jobs that finish
            while left > 0 and self.fifo:
                pg, pods = self.fifo.popleft()
                left -= len(pods)
                if jobs is not None and isinstance(pods[0], str):
                    finishing.append((pg, pods))    # a Job, its pods' keys
                    continue
                if self._terminating or self._gangs is not None:
                    self._complete_with_victims(pg, pods)
                else:
                    for pod in pods:
                        store.delete_pod(pod)
                store.delete_pod_group(pg.uid)
                deleted.extend(f"{p.namespace}/{p.name}" for p in pods)
            if finishing:
                jobs.complete(finishing)
                deleted.extend(key for _job, keys in finishing for key in keys)
        t_completed = now()

        settled = 0
        if self.settle_cycles or self._mixed or self._kubelet_has_work():
            with self.annotate("bench:settle"):
                if self._mixed:
                    # binds beside the batch's may still be on their way
                    self._await_hand_over()
                self._kubelet()
                while settled < self.settle_cycles:
                    run_once_s += self._cycle()
                    settled += 1
                    self._await_hand_over()
                    self._kubelet()
                # the round's end is every termination's last day
                self._kubelet(everything=True)
        t_end = now()

        with self.binder.cond:
            arrivals = self.binder.arrivals[self._seen:]
            self._seen = len(self.binder.arrivals)
        rec = Round(plan, submit_ns, t_start, t_submitted, t_scheduled,
                    t_reconciled, t_completed, t_end, cycles, run_once_s,
                    arrivals, deleted,
                    settle_cycles=settled,
                    evictions=[(t, key) for t, key, _pod
                               in self.evictor.evictions[evictions0:]],
                    terminations=self.terminations[terminations0:],
                    wait_s=self._wait_s,
                    waits_timed_out=self._waits_timed_out)
        if jobs is not None:
            rec.jobs = JobEvents(jobs.created, not_running, jobs.left_behind())
            rec.t_admitted = t_admitted
            rec.pumps, rec.pump_s = jobs.pumps, jobs.pump_s
            rec.pumps_used_up = jobs.used_up
        if self.read_lanes:
            rec.lanes, rec.records = self._records(cycles + settled)
        self.rounds.append(rec)
        return rec

    def place(self, plan: generate.Plan, hosts: List[str]) -> Round:
        """A round of pods that arrive bound: the client names each pod's
        node in its ``add_pod`` (and, where the traffic says ``pods_run``,
        reports it Running there), as a scheduler finds the pods of a
        cluster it takes over.  They enter as pods whatever the traffic's
        ``entry`` and belong to no Job; no cycle, pump or completion is the
        round's, and the binder sees nothing of it: its one arrival is the
        client's own placement, which ``validate`` holds like a bind.  The
        probe's own fill (``probe.fill_pods``) and nothing of a window."""
        from volcano_tpu.api import PodPhase

        gangs = generate.to_objects(plan, self.stamps, self.priority_values)
        store, now = self.store, time.perf_counter_ns
        submit_ns = np.empty(plan.n_pods, dtype=np.int64)
        t_start = now()
        i = 0
        for pg, pods in gangs:
            store.add_pod_group(pg)
            for pod in pods:
                pod.node_name = hosts[i]
                if self.pods_run:
                    pod.phase = PodPhase.Running
                submit_ns[i] = now()
                store.add_pod(pod)
                i += 1
        t = now()
        self.fifo.extend(gangs)
        if self._gangs is not None:
            self._gangs.update((pg.name, (pg, pods)) for pg, pods in gangs)
        rec = Round(plan, submit_ns, t_start, t, t, t, t, t, cycles=0,
                    run_once_s=0.0, arrivals=[(t, plan.keys(), list(hosts))],
                    deleted=[])
        self.rounds.append(rec)
        return rec

    # ---- waiting for a cycle's hand-over ------------------------------------

    def _cycle(self) -> float:
        """One ``run_once()``; its seconds by the benchmark's clock."""
        t0 = time.perf_counter_ns()
        self.sched.run_once()
        self._cycle_no += 1
        return (time.perf_counter_ns() - t0) / 1e9

    def _await(self, want: int) -> bool:
        """True as soon as the binder has seen ``want`` binds in all: its
        condition wakes this thread at the arrival that makes the count.
        False once the dispatcher has handed over everything the cycle gave
        it and the count is still short: no time-out in the normal case."""
        t0 = time.monotonic()
        while True:
            if self.binder.wait_for(want, POLL_S):
                return True
            handed_over = self.store.flush_binds(0)
            waited = time.monotonic() - t0
            self._wait_s = max(self._wait_s, waited)
            if handed_over:
                return self.binder.count >= want
            if waited >= self.bind_wait_s:
                self._waits_timed_out += 1
                return False

    def _await_hand_over(self) -> None:
        """Until the dispatcher has handed over all it was given."""
        t0 = time.monotonic()
        if not self.store.flush_binds(self.bind_wait_s):
            self._waits_timed_out += 1
        self._wait_s = max(self._wait_s, time.monotonic() - t0)

    def _batch_bound(self, keys: set, lacks: int):
        """In a run where pods of other rounds may be bound beside the
        batch's (pods that may wait, victims restored), the count alone does
        not say that the batch (``keys``) is bound: reckon it, over the
        arrivals no round has been given yet.  Returns the count to wait
        for next and whether the batch is bound."""
        while True:
            with self.binder.cond:
                count = self.binder.count
                arrivals = self.binder.arrivals[self._seen:]
            own = sum(1 for _t, ks, _h in arrivals for k in ks if k in keys)
            if own >= lacks:
                return count, True
            want = count + lacks - own
            if not self._await(want):
                return want, False

    # ---- the kubelet's side --------------------------------------------------

    def _kubelet_has_work(self) -> bool:
        return self.evictor.count != self._ended or self.pods_run

    def _kubelet(self, everything: bool = False) -> None:
        """After a cycle's hand-over: bound pods run, and the evictions whose
        termination is due (``everything``: all that still terminate) end.
        Where no pod is said to run and nothing was evicted, it returns at
        its first comparison."""
        if self.pods_run:
            self._run_bound_pods()
        ev = self.evictor
        if ev.count == self._ended:
            return
        if self.jobs is not None:
            raise RuntimeError("an eviction under entry: jobs: the kubelet's "
                               "side holds no Job's victim yet")
        self._mixed = True
        seen = ev.count
        # An eviction is of the cycle after which it was first seen here.
        noted = len(self._evicted_in)
        for _t, key, pod in ev.evictions[noted:seen]:
            self._terminating[pod.uid] = key
        self._evicted_in.extend([self._cycle_no] * (seen - noted))
        if self._gangs is None:
            self._gangs = {pg.name: (pg, pods) for pg, pods in self.fifo}
        while self._ended < seen:
            i = self._ended
            if not everything and \
                    self._cycle_no - self._evicted_in[i] < self.termination_cycles:
                break
            self._ended += 1
            _t, key, pod = ev.evictions[i]
            if self._terminating.pop(pod.uid, None) is None:
                continue                # its gang completed meanwhile
            self._end_termination(key, pod)

    def _end_termination(self, key: str, pod) -> None:
        """The victim's record leaves the store; what the program puts in
        its place, if anything, stands for the key in its gang from now."""
        restored = self._delete_victim(pod)
        self.terminations.append((time.perf_counter_ns(), key))
        entry = self._gangs.get(pod.annotations.get(self._group_key, ""))
        if entry is None:
            return
        pods = entry[1]
        for i, own in enumerate(pods):
            if own.uid == pod.uid:
                if restored is not None:
                    pods[i] = restored
                    self._restored_uid[key] = restored.uid
                else:
                    del pods[i]
                    self._restored_uid.pop(key, None)
                break

    def _delete_victim(self, pod):
        """``delete_pod`` of an evicted record; returns the record the
        program added under the same namespace and name while it ran (the
        newest of ``store.pods``), or None."""
        self.store.delete_pod(pod)
        pods = self.store.pods
        if pods:
            newest = pods[next(reversed(pods))]
            if newest.uid != pod.uid and newest.name == pod.name \
                    and newest.namespace == pod.namespace \
                    and newest.node_name is None:
                return newest
        return None

    def _complete_with_victims(self, pg, pods) -> None:
        """A gang finishes in a run that has seen evictions: a pod of it
        that still terminates ends here, with whatever was restored for
        it, and its eviction is stamped as ended."""
        for pod in pods:
            key = self._terminating.pop(pod.uid, None)
            if key is None:
                self.store.delete_pod(pod)
                continue
            restored = self._delete_victim(pod)
            self.terminations.append((time.perf_counter_ns(), key))
            if restored is not None:
                self.store.delete_pod(restored)
        self._gangs.pop(pg.name, None)
        for pod in pods:
            self._restored_uid.pop(f"{pod.namespace}/{pod.name}", None)

    def _run_bound_pods(self) -> None:
        """The pods of the binds seen since the last call are reported
        Running, as their kubelets would: the store's own record, copied,
        with the phase and the node the binder was given."""
        from volcano_tpu.api import PodPhase

        with self.binder.cond:
            arrivals = self.binder.arrivals[self._ran:]
            self._ran = len(self.binder.arrivals)
        pods = self.store.pods
        # the record's uid: the client's own, or under jobs the controller's
        uid_of = self.jobs.uid_of.get if self.jobs is not None \
            else lambda key: f"bench-{key.split('/', 1)[1]}"
        for _t, keys, hosts in arrivals:
            for key, host in zip(keys, hosts):
                uid = self._restored_uid.get(key) or uid_of(key)
                pod = pods.get(uid)
                if pod is None or pod.deleting or pod.phase != PodPhase.Pending:
                    continue
                pod = copy.copy(pod)
                pod.phase, pod.node_name = PodPhase.Running, host
                self.store.update_pod(pod)

    # ---- the program's own record, traced run only ---------------------------

    def _records(self, cycles: int):
        """From the program's flight recorder, for the round's ``cycles``
        cycles: seconds per lane summed over them (a cycle off the fast path
        or with an error is named under ``_off_fast_path``), and of each
        cycle its spans, its ``solve``, ``whatif`` and ``between`` blocks
        and its ``path`` as plain data."""
        lanes: Dict[str, float] = {}
        records: List[dict] = []
        for rec in self.store.flight.recent()[-cycles:]:
            for name, s in rec.lanes.items():
                lanes[name] = lanes.get(name, 0.0) + float(s)
            if rec.path != "fast" or rec.error is not None:
                lanes["_off_fast_path"] = lanes.get("_off_fast_path", 0.0) + 1.0
            records.append({
                "spans": [(s.name, s.dur_ns, s.span_id, s.parent_id)
                          for s in rec.spans],
                "solve": rec.solve, "whatif": rec.whatif,
                "between": rec.between, "path": rec.path})
        return lanes, records


def run_window(driver: Driver, gen: generate.Generator, batch_pods: int,
               seconds: float, on_round: Optional[Callable] = None) -> List[Round]:
    """Rounds until ``seconds`` have passed: a round that has begun is
    finished and counted, none begins after the deadline."""
    counted: List[Round] = []
    t0 = time.perf_counter()
    while True:
        if counted and time.perf_counter() - t0 >= seconds:
            break
        plan = gen.plan(batch_pods, f"w{len(counted):04d}")
        counted.append(driver.round(plan, batch_pods))
        if on_round is not None:
            on_round(counted[-1])
    return counted
