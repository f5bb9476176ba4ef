"""The one loop every cell runs: rounds of submit, schedule, complete.

Single-threaded apart from the program's own bind dispatcher.  The program
is reached through its public entry points only: the store's event API
(``add_node``, ``add_queue``, ``add_pod_group``, ``add_pod``, ``delete_pod``,
``delete_pod_group``), ``Scheduler(store, conf_str=...).run_once()`` and the
binder slot ``store.binder``.  No knob of the program is set and nothing in
it is patched.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import generate
from .binder import RecordingBinder
from .validate import RoundEvents


def default_scheduler(store, conf_str):
    from volcano_tpu.scheduler import Scheduler

    return Scheduler(store, conf_str=conf_str)


@dataclass
class Round:
    """One round as observed.  Times are ``perf_counter_ns`` readings."""

    plan: generate.Plan
    submit_ns: np.ndarray               # each pod's own add_pod call
    t_start: int
    t_submitted: int                    # the backlog stands
    t_scheduled: int                    # every bind seen (or cycles used up)
    t_end: int                          # completions done
    cycles: int
    run_once_s: float
    arrivals: list
    deleted: List[str]
    lanes: Dict[str, float] = field(default_factory=dict)

    def events(self) -> RoundEvents:
        return RoundEvents(self.plan, self.arrivals, self.deleted)

    def spans(self) -> Dict[str, float]:
        return {
            "submit": (self.t_submitted - self.t_start) / 1e9,
            "schedule": (self.t_scheduled - self.t_submitted) / 1e9,
            "complete": (self.t_end - self.t_scheduled) / 1e9,
            "round": (self.t_end - self.t_start) / 1e9,
            "run_once": self.run_once_s,
        }


class Driver:
    """A store, a scheduler and the benchmark's binder, and the three steps
    of a round on them."""

    def __init__(self, config: dict, max_cycles: int = 4,
                 make_scheduler: Callable = default_scheduler,
                 read_lanes: bool = False, bind_wait_s: float = 10.0,
                 annotate: Optional[Callable] = None):
        from volcano_tpu.cache import ClusterStore

        self.config = config
        self.binder = RecordingBinder()
        self.store = ClusterStore(binder=self.binder)
        # Async bind dispatch, as in production (chip_smoke.py, bench.py).
        self.store.async_bind = True
        for queue in generate.to_queues(config):
            self.store.add_queue(queue)
        for node in generate.to_nodes(config):
            self.store.add_node(node)
        self.sched = make_scheduler(self.store, config["scheduler_conf"])
        self.max_cycles = int(max_cycles)
        self.bind_wait_s = float(bind_wait_s)
        self.read_lanes = read_lanes
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.stamps = itertools.count(1)
        self.fifo: deque = deque()      # gangs still in the cluster, oldest first
        self.rounds: List[Round] = []
        self._seen = 0                  # arrivals already given to a round

    def close(self) -> None:
        self.store.close()

    @property
    def pods_alive(self) -> int:
        """Pods submitted and not yet completed."""
        return sum(len(pods) for _pg, pods in self.fifo)

    def round(self, plan: generate.Plan, complete_pods: int) -> Round:
        """Submit ``plan``, schedule until every bind was seen, then delete
        the oldest ``complete_pods`` pods.  The gangs' objects are built
        before the round's clock starts."""
        gangs = generate.to_objects(plan, self.stamps)
        store = self.store
        n = plan.n_pods
        submit_ns = np.empty(n, dtype=np.int64)
        want = self.binder.count + n
        now = time.perf_counter_ns

        t_start = now()
        with self.annotate("bench:submit"):
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
                t0 = now()
                self.sched.run_once()
                run_once_s += (now() - t0) / 1e9
                cycles += 1
                if self.binder.wait_for(want, self.bind_wait_s):
                    break
        t_scheduled = now()

        self.fifo.extend(gangs)
        deleted: List[str] = []
        with self.annotate("bench:complete"):
            left = int(complete_pods)
            while left > 0 and self.fifo:
                pg, pods = self.fifo.popleft()
                for pod in pods:
                    store.delete_pod(pod)
                store.delete_pod_group(pg.uid)
                deleted.extend(f"{p.namespace}/{p.name}" for p in pods)
                left -= len(pods)
        t_end = now()

        with self.binder.cond:
            arrivals = self.binder.arrivals[self._seen:]
            self._seen = len(self.binder.arrivals)
        rec = Round(plan, submit_ns, t_start, t_submitted, t_scheduled, t_end,
                    cycles, run_once_s, arrivals, deleted)
        if self.read_lanes:
            rec.lanes = self._lanes(cycles)
        self.rounds.append(rec)
        return rec

    def _lanes(self, cycles: int) -> Dict[str, float]:
        """Seconds per lane, summed over the round's cycles, from the
        program's flight recorder; a cycle off the fast path or with an
        error is named under ``_off_fast_path``."""
        lanes: Dict[str, float] = {}
        for rec in self.store.flight.recent()[-cycles:]:
            for name, s in rec.lanes.items():
                lanes[name] = lanes.get(name, 0.0) + float(s)
            if rec.path != "fast" or rec.error is not None:
                lanes["_off_fast_path"] = lanes.get("_off_fast_path", 0.0) + 1.0
        return lanes


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
