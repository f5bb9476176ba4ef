"""The user one layer further out (``entry: jobs``): a gang enters as a
``Job`` through admission, and the controllers stand between the client and
the store, as in a deployment.

``JobEntry`` holds what ``loop.Driver`` builds for it once, before the nodes
are added: ``AdmittedStore(store)`` (``add_batch_job`` = ``mutate_job`` +
``validate_job_create``; ``add_queue``) and ``ControllerManager(store)``,
whose three controllers ``store.watch`` from then on and so see every event
of the run.  Nothing else of ``Service`` is started: no HTTP, no thread, no
``ClusterSimulator``.  The harness pumps ``ControllerManager.process()``
itself, where ``Service._controller_loop`` would, and keeps playing the
kubelet: a bound pod is reported Running, a finishing Job's pods Succeeded,
and a pod the controller marked ``deleting`` has its termination ended
(``delete_pod``).

A pump has no time limit of its own, so every wait on the controllers is
counted in pumps: a phase that used up the traffic's ``max_pumps`` is named
in ``used_up``, its Jobs are counted by ``validate`` and the run goes on.

The store's records of the batch are the controller's to make and to name.
The plan states the names; this class learns each record's uid from the
tail of ``store.pods`` after a pump (a dict keeps the order of insertion)
and hands ``(pod key, owner's job key)`` to ``validate``, which holds them
to the plan.
"""

from __future__ import annotations

import copy
import time
from typing import Dict, List, Tuple

PHASES = ("submit", "schedule", "reconcile", "complete")


class JobEntry:
    def __init__(self, store, max_pumps: int):
        from volcano_tpu.api import PodPhase
        from volcano_tpu.controllers import ControllerManager, JobPhase
        from volcano_tpu.webhooks import AdmittedStore

        self.store = store
        self.admitted = AdmittedStore(store)
        self.manager = ControllerManager(store)
        self.max_pumps = int(max_pumps)
        self._running = JobPhase.Running.value
        self._completed = JobPhase.Completed.value
        self._succeeded = PodPhase.Succeeded
        self.uid_of: Dict[str, str] = {}    # pod key -> uid of the store's record
        self._finished: List[Tuple[str, List[str]]] = []   # (job key, pod uids)
        self.begin_round()

    def begin_round(self) -> None:
        self.pumps = dict.fromkeys(PHASES, 0)       # of the round under way
        self.pump_s = dict.fromkeys(PHASES, 0.0)
        self.created: List[Tuple[str, str]] = []    # (pod key, owner's job key)
        self.used_up: List[str] = []                # phases out of pumps

    # ---- the controllers ------------------------------------------------------

    def pump(self, phase: str) -> None:
        """One ``ControllerManager.process()``, its seconds on the
        benchmark's clock, and then the records it made."""
        t0 = time.perf_counter_ns()
        self.manager.process()
        self.pump_s[phase] += (time.perf_counter_ns() - t0) / 1e9
        self.pumps[phase] += 1
        self._note_created()

    def _note_created(self) -> None:
        """The records added to ``store.pods`` since the last look: its
        tail, back to the first record already known.  A record that names
        no owner entered as a pod (the probe's own fill): it is known from
        now on, so that the next look stops at it, and was made under no
        Job."""
        uid_of = self.uid_of
        fresh = []
        for pod in reversed(self.store.pods.values()):
            key = f"{pod.namespace}/{pod.name}"
            if uid_of.get(key) == pod.uid:
                break
            fresh.append((key, pod))
        for key, pod in reversed(fresh):
            uid_of[key] = pod.uid
            if pod.owner_job:
                self.created.append((key, pod.owner_job))

    def _pump_until(self, phase: str, keys: List[str], reads: str) -> List[str]:
        """Pumps, at most ``max_pumps``, until every Job of ``keys`` reads
        the phase ``reads``; the keys of those that still do not."""
        jobs = self.store.batch_jobs
        for _ in range(self.max_pumps):
            self.pump(phase)
            keys = [k for k in keys if k not in jobs
                    or jobs[k].status.state.phase != reads]
            if not keys:
                return keys
        self.used_up.append(phase)
        return keys

    # ---- the phases of a round ------------------------------------------------

    def submit(self, gangs, submit_ns, now) -> None:
        """The user's call, a gang: its stamp is the submit time of every
        pod of the gang."""
        add = self.admitted.add_batch_job
        i = 0
        for job, keys in gangs:
            n = len(keys)
            submit_ns[i:i + n] = now()
            add(job)
            i += n

    def reconcile(self, gangs) -> List[str]:
        """After the kubelet's Running reports: the Jobs of ``gangs`` that
        do not read Running when the pumps are done."""
        return self._pump_until("reconcile", [job.key for job, _keys in gangs],
                                self._running)

    def complete(self, gangs) -> None:
        """Jobs finish as Jobs do: their pods succeed, the controllers see
        it, the client deletes the Job, the controllers clean up, and the
        kubelet ends the terminations they asked for."""
        store, pods, uid_of = self.store, self.store.pods, self.uid_of
        for _job, keys in gangs:
            for key in keys:
                pod = pods.get(uid_of.get(key))
                if pod is None or pod.deleting:
                    continue
                pod = copy.copy(pod)
                pod.phase, pod.exit_code = self._succeeded, 0
                store.update_pod(pod)
        self._pump_until("complete", [job.key for job, _keys in gangs],
                         self._completed)
        for job, _keys in gangs:
            self.admitted.delete_batch_job(job.key)
        self.pump("complete")
        for job, keys in gangs:
            uids = [uid_of.pop(key, None) for key in keys]
            for uid in uids:
                pod = pods.get(uid)
                if pod is not None and pod.deleting:
                    store.delete_pod(pod)
            self._finished.append((job.key, uids))

    def left_behind(self) -> List[str]:
        """Of the Jobs that finished in this round, those of which a pod,
        the PodGroup or the Job record is still in the store."""
        store = self.store
        out = [key for key, uids in self._finished
               if key in store.batch_jobs or key in store.pod_groups
               or any(uid in store.pods for uid in uids)]
        self._finished = []
        return out
