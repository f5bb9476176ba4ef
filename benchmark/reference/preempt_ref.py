"""Who may be evicted, and for whom, in plain Python and NumPy float64.

What a deployment that preempts and reclaims (``configs/preempt-10k.json``)
promises beyond the guarantees of ``harness/validate.py``: that every victim
was one the published rules admit.  Written here from the published rules of
the reference scheduler (volcano, the ``preempt`` and ``reclaim`` actions and
the ``priority``, ``gang``, ``conformance`` and ``proportion`` plugins), not
from the program's code:

  preempt's filter     preempt.go:81-142: a Running task with a request, of
                       another job of the preemptor's own queue
  reclaim's filter     reclaim.go:84-143: a Running task of another queue that
                       is Reclaimable, for a task of a queue that is not
                       overused (proportion.go:217-229)
  priority rule        priority.go:85-104: the victim's *job* priority is
                       strictly under the preemptor's
  gang rule            gang.go:74-98: the victim's job keeps min_member ready
                       tasks after the loss, or min_member is 1; counted down
                       over the candidates of one call
  conformance rule     conformance.go:44-66: not of kube-system, not of a
                       system-cluster-critical or system-node-critical class
  proportion rule      proportion.go:190-215: the victim's queue holds its
                       deserved share in every dimension after the loss;
                       counted down per queue over the candidates of one call
  victim sets          session_plugins.go:110-193: within a tier the plugins'
                       victim sets are intersected

A resource vector is ``[R]``, column 0 cpu in milli-cores, column 1 memory in
bytes, as in ``fairshare_ref.py``, whose ``deserved``, ``share`` and
``overused`` this file uses.  It imports nothing of the program and takes
nothing it made.

Departures from the published text, each on purpose:

1. ``reclaimable`` holds every victim to the ``proportion`` rule.  By the
   published tier walk (session_plugins.go:110-193: the first tier that names
   victims wins) ``proportion`` stands in tier 2 of the benchmark's conf and
   is not asked once ``gang`` and ``conformance`` in tier 1 have named
   victims, so any queue that may be reclaimed from can be pushed under its
   share.  The program holds every victim to it (docs/preempt_reclaim.md,
   "never reclaimed below deserved"), and so does this file: the stricter
   rule is the one users are promised.
2. The ``drf`` plugin's preemptable rule (drf.go:121-200) is left out: the
   benchmark's conf has ``drf`` in tier 2, which the tier walk does not reach
   for ``preempt``, and the program does not ask it.
3. ``preempt.go:144-177``, a job's own tasks preempting each other, is left
   out: every pod of a gang has its job's priority here.

The ``proportion`` rule is the published one: proportion.go:209-211 compares
every dimension (``deserved.LessEqualStrict(allocated)`` after the loss,
resource_info.go:264-283: no quantum).  The
*program* reads it otherwise, and says so (docs/preempt_reclaim.md, "Victim
eligibility": "share = max over capped slots of allocated/deserved"): a queue
may be taken from while its *share*, the largest ratio of allocated to
deserved (proportion.go:208-215, the number the queue order and ``overused``
turn on), stays at or over 1.  The two differ wherever cpu and memory do not
stand in one proportion: the water-fill never clips a queue that is not met,
so a queue whose request exceeds its share in cpu alone is dealt all the
memory the others leave, stands under its deserved memory for ever, and by
the published comparison is overused, refused by allocate, and never taken
from.  ``share_rule`` is that reading, kept apart from the rules; ``check``
asks it only for a victim that ``reclaimable`` has refused, counts every
victim it admits so (``judged``'s ``by_share_alone``, printed with every
run) and does not hold them against the run: the departure is the program's,
it is documented, and a run says how far it went.

``check`` replays a run by the benchmark's own stamps.  It sees a bind when
the binder does and a queue's share when it asks, where the program read
both when its cycle opened; what that can move is bounded by the pods one
cycle binds and evicts, so ``check`` compares shares with a slack of one
gang's request (the largest gang of the configuration at its largest pod)
and says so where it does.
"""

from __future__ import annotations

import sys
from collections import Counter
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from . import fairshare_ref as fair

CRITICAL_CLASSES = ("system-cluster-critical", "system-node-critical")
CRITICAL_NAMESPACE = "kube-system"
COUNTS = ("victim_not_running", "victim_critical", "victim_unjustified",
          "gang_under_floor", "queue_under_deserved", "evicted_beyond_demand")


class Pod(NamedTuple):
    key: str
    queue: str
    value: int                  # its job's priority: the PodGroup's class
    gang: str
    request: tuple              # [R]
    running: bool
    critical: bool = False


class Queue(NamedTuple):
    weight: float
    reclaimable: bool
    allocated: tuple            # [R] held by its pods that are bound
    request: tuple              # [R] allocated and pending together


class Gang(NamedTuple):
    min_member: int
    running: int                # pods bound and not evicted


class State(NamedTuple):
    total: tuple                # [R] the cluster's capacity
    queues: Dict[str, Queue]
    gangs: Dict[str, Gang]


def critical(namespace: str, class_name: Optional[str]) -> bool:
    return namespace == CRITICAL_NAMESPACE or class_name in CRITICAL_CLASSES


def deserved_of(state: State) -> Dict[str, np.ndarray]:
    """Each queue's deserved share by the water-fill, by name."""
    names = list(state.queues)
    rows = fair.deserved(state.total,
                         [state.queues[n].weight for n in names],
                         [state.queues[n].request for n in names])
    return dict(zip(names, rows))


# ---- the rules: (claimant, candidates, state) -> the candidates admitted ----


def priority_rule(preemptor: Pod, candidates: Sequence[Pod],
                  state: State) -> List[Pod]:
    return [c for c in candidates if c.value < preemptor.value]


def gang_rule(claimant: Pod, candidates: Sequence[Pod],
              state: State) -> List[Pod]:
    left: Dict[str, int] = {}
    out = []
    for c in candidates:
        gang = state.gangs[c.gang]
        count = left.get(c.gang, gang.running)
        if gang.min_member <= count - 1 or gang.min_member == 1:
            left[c.gang] = count - 1
            out.append(c)
    return out


def conformance_rule(claimant: Pod, candidates: Sequence[Pod],
                     state: State) -> List[Pod]:
    return [c for c in candidates if not c.critical]


def _counted_down(candidates: Sequence[Pod], state: State, stays) -> List[Pod]:
    """proportion.go:190-215's loop: each candidate is taken off what its
    queue holds, one after another, and admitted if ``stays(held after the
    loss, deserved)``."""
    deserved = deserved_of(state)
    held: Dict[str, np.ndarray] = {}
    out = []
    for c in candidates:
        if c.queue not in held:
            held[c.queue] = np.asarray(state.queues[c.queue].allocated,
                                       dtype=np.float64)
        req = np.asarray(c.request, dtype=np.float64)
        if np.all(held[c.queue] < req):     # Resource.Less: holds nothing of it
            continue
        held[c.queue] = held[c.queue] - req
        if stays(held[c.queue], deserved[c.queue]):
            out.append(c)
    return out


def proportion_rule(reclaimer: Pod, candidates: Sequence[Pod], state: State,
                    slack=0.0) -> List[Pod]:
    """The published rule: after the loss the queue holds its deserved share
    in *every* dimension.  ``slack`` ``[R]`` is added to what a queue holds
    after the loss before it is compared (``check``'s tolerance; the rule
    itself has none)."""
    return _counted_down(candidates, state,
                         lambda held, des: bool(np.all(des <= held + slack)))


def share_rule(reclaimer: Pod, candidates: Sequence[Pod], state: State,
               slack=0.0) -> List[Pod]:
    """The program's reading of the same rule, no rule of the reference
    scheduler (see the head of this file): after the loss the queue's
    *share*, its largest ratio of allocated to deserved, is at or over 1."""
    return _counted_down(candidates, state,
                         lambda held, des: fair.share(held + slack, des) >= 1.0)


def _intersect(rules, claimant, candidates, state) -> List[Pod]:
    """Every rule is asked over all the candidates; a victim is one that
    every rule admits (session_plugins.go:110-193, one tier)."""
    keys = None
    for rule in rules:
        admitted = {c.key for c in rule(claimant, candidates, state)}
        keys = admitted if keys is None else keys & admitted
    return [c for c in candidates if c.key in keys]


def preemptable(preemptor: Pod, candidates: Sequence[Pod],
                state: State) -> List[Pod]:
    """The candidates that ``preemptor`` (a pending pod) may take by
    ``preempt``: the action's filter, then priority, gang and conformance."""
    offered = [c for c in candidates
               if c.running and any(c.request) and c.queue == preemptor.queue
               and c.gang != preemptor.gang]
    return _intersect((priority_rule, gang_rule, conformance_rule),
                      preemptor, offered, state)


def queue_overused(name: str, state: State, slack=0.0) -> bool:
    queue = state.queues[name]
    held = np.asarray(queue.allocated, dtype=np.float64) - slack
    return fair.overused(held, deserved_of(state)[name])


def reclaimable(reclaimer: Pod, candidates: Sequence[Pod], state: State,
                proportion=proportion_rule) -> List[Pod]:
    """The candidates that ``reclaimer`` (a pending pod) may take by
    ``reclaim``: none if its own queue is overused; else the action's filter,
    then gang, conformance and proportion (departure 1).  ``proportion``:
    ``share_rule`` gives what the program's reading admits."""
    if queue_overused(reclaimer.queue, state):
        return []
    offered = [c for c in candidates
               if c.running and c.queue != reclaimer.queue
               and state.queues[c.queue].reclaimable]
    return _intersect((gang_rule, conformance_rule, proportion),
                      reclaimer, offered, state)


# ---- a run, replayed ----------------------------------------------------------

# a pod of the replay: [queue, value, gang, request, bound, terminating, critical]
QUEUE, VALUE, GANG, REQUEST, BOUND, TERMINATING, CRITICAL = range(7)


class _Replay:
    """The run's pods, queues and gangs as the benchmark's stamps give them:
    a pod is pending from its submission (or from the end of its termination)
    to its bind, holds its request from its bind to its eviction (the
    published ``allocated`` leaves a Releasing task out), and runs from its
    bind: the harness reports a bound pod Running after the cycle that bound
    it, and an eviction is stamped after the cycle that took it."""

    def __init__(self, nodes: dict, config: dict):
        n = config["nodes"]
        self.total = np.array([int(n["cpu"]) * 1000.0,
                               float(int(n["memory_gi"]) << 30)]) * len(nodes["names"])
        q = config.get("queues", {})
        count = int(q.get("count", 1))
        self.queue_names = ["default"] + [f"queue-{i}" for i in range(1, count)]
        self.queue_index = {n: i for i, n in enumerate(self.queue_names)}
        weights = q.get("weights") or [1]
        may = q.get("reclaimable") or [True]
        self.weight = [float(weights[i % len(weights)]) for i in range(count)]
        self.may = [bool(may[i % len(may)]) for i in range(count)]
        self.value = {c["name"]: int(c["value"])
                      for c in config.get("priority_classes", [])}
        # One gang's request, the slack of every comparison of shares.
        shapes = [config["gang"]] + [c["gang"] for c in config.get(
            "priority_classes", []) if "gang" in c]
        largest = max(max(g.get("sizes", [g.get("size", 1)])) for g in shapes)
        self.slack = largest * np.array(
            [max(config["pods"]["cpu_choices"]) * 1000.0,
             float(max(config["pods"]["mem_gi_choices"]) << 30)])
        self.pods: Dict[str, list] = {}
        self.allocated = np.zeros((count, 2))
        self.pending = np.zeros((count, 2))
        # cpu pending by class value, a queue: who may claim a victim
        self.waiting = [Counter() for _ in range(count)]
        self.gangs: Dict[str, list] = {}        # name -> [min_member, running]
        self.counts = dict.fromkeys(COUNTS, 0)
        self.tally = dict.fromkeys(TALLY, 0)
        self._requests = None                   # of the wave under way
        # The round's demand, and what its victims so far have freed of it.
        self._claims: Optional[List[Counter]] = None
        self._pool = np.zeros(count)
        self._freed_own = np.zeros(count)
        self._freed_pool = np.zeros(count)

    # ---- one round -----------------------------------------------------------

    def round(self, ev) -> None:
        plan = ev.plan
        classes = plan.gang_priority
        self._claims = None
        for g, name in enumerate(plan.gang_names):
            self.gangs[name] = [int(plan.gang_min_member[g]), 0]
        for key, g, cpu, mem in zip(plan.keys(), plan.gang.tolist(),
                                    plan.cpu_milli.tolist(),
                                    plan.mem_bytes.tolist()):
            klass = classes[g] if classes else None
            qi = self.queue_index[plan.gang_queue[g]]
            pod = [qi, self.value.get(klass, 0), plan.gang_names[g],
                   (float(cpu), float(mem)), False, False,
                   critical(key.split("/", 1)[0], klass)]
            self.pods[key] = pod
            self._wait(pod, +1)
        self._open_demand()
        steps = [(t, 0, (keys, hosts)) for t, keys, hosts in ev.arrivals]
        steps += [(t, 1, key) for t, key in ev.evictions]
        steps += [(t, 2, key) for t, key in ev.terminations]
        if ev.t_deleted is not None:
            steps.append((ev.t_deleted, 3, None))
        steps.sort(key=lambda s: (s[0], s[1]))
        deleted = False
        for _t, kind, what in steps:
            if kind != 1:
                self._requests = None           # a wave ends at any other stamp
            if kind == 0:
                for key in what[0]:
                    self._bind(key)
            elif kind == 1:
                self._evict(what)
            elif kind == 2:
                self._terminate(what)
            else:
                self._delete(ev.deleted)
                deleted = True
        self._requests = None
        if not deleted:
            self._delete(ev.deleted)

    # ---- what a round's victims may be taken for -----------------------------

    def _open_demand(self) -> None:
        """The cpu that the round's victims can be for, as the round's
        submission leaves it (a pod that turns pending later in the round, a
        victim restored, is added when it does): in each queue the cpu
        pending by class, which ``preempt`` in that queue may claim, and the
        cpu pending in each queue that is not overused, which ``reclaim``
        from another queue may claim."""
        state = self._state()
        self._claims = [Counter({v: c for v, c in w.items() if c > 0})
                        for w in self.waiting]
        self._pool = np.array([
            0.0 if queue_overused(n, state, self.slack) else self.pending[i, 0]
            for i, n in enumerate(self.queue_names)])
        self._freed_own[:] = 0.0
        self._freed_pool[:] = 0.0

    def _beyond_demand(self, pod: list, how: str) -> bool:
        """Whether what the round's victims of this kind had freed before
        ``pod`` already covers all that may claim it; ``pod`` is then added
        to what was freed."""
        qi, cpu = pod[QUEUE], pod[REQUEST][0]
        if how == "preempt":
            demand = sum(c for v, c in self._claims[qi].items() if v > pod[VALUE])
            freed = self._freed_own
        else:
            demand = self._pool.sum() - self._pool[qi]
            freed = self._freed_pool
        beyond = freed[qi] >= demand
        freed[qi] += cpu
        return bool(beyond)

    # ---- the steps -----------------------------------------------------------

    def _wait(self, pod: list, sign: int) -> None:
        qi, cpu = pod[QUEUE], pod[REQUEST][0]
        self.pending[qi] += np.multiply(pod[REQUEST], sign)
        self.waiting[qi][pod[VALUE]] += sign * cpu
        if sign > 0 and self._claims is not None:
            self._claims[qi][pod[VALUE]] += cpu
            if not queue_overused(self.queue_names[qi], self._state(), self.slack):
                self._pool[qi] += cpu

    def _hold(self, pod: list, sign: int) -> None:
        self.allocated[pod[QUEUE]] += np.multiply(pod[REQUEST], sign)
        self.gangs[pod[GANG]][1] += sign

    def _bind(self, key: str) -> None:
        pod = self.pods.get(key)
        if pod is None or pod[BOUND] or pod[TERMINATING]:
            return                              # validate.py counts these
        pod[BOUND] = True
        self._wait(pod, -1)
        self._hold(pod, +1)

    def _terminate(self, key: str) -> None:
        pod = self.pods.get(key)
        if pod is None or not pod[TERMINATING]:
            return
        pod[TERMINATING] = False                # a new life, pending
        self._wait(pod, +1)

    def _delete(self, keys) -> None:
        for key in keys:
            pod = self.pods.pop(key, None)
            if pod is None:
                continue
            if pod[BOUND]:
                self._hold(pod, -1)
            elif not pod[TERMINATING]:
                self._wait(pod, -1)

    def _evict(self, key: str) -> None:
        """Judge one victim on the state its eviction finds, then take it
        out of that state."""
        pod = self.pods.get(key)
        if pod is None or not pod[BOUND] or pod[TERMINATING]:
            self.counts["victim_not_running"] += 1
            return
        how, refused = self._judge(key, pod)
        self.tally["victims"] += 1
        for name in refused:
            self.counts[name] += 1
        if how is not None:
            self.tally[HOW[how]] += 1
            # admitted: then it has to be for somebody still
            if self._beyond_demand(pod, how):
                self.counts["evicted_beyond_demand"] += 1
        pod[BOUND], pod[TERMINATING] = False, True
        self._hold(pod, -1)

    def _state(self, gang: Optional[str] = None) -> State:
        """The queues as the stamps stand (and ``gang``, where one is
        judged).  The shares of one wave are those its first eviction
        finds: the program reads them when the cycle opens, and a bind
        moves no queue's request."""
        requests = self._requests
        if requests is None:
            requests = self.allocated + self.pending
        return State(
            tuple(self.total),
            {n: Queue(self.weight[i], self.may[i], tuple(self.allocated[i]),
                      tuple(requests[i]))
             for i, n in enumerate(self.queue_names)},
            {gang: Gang(*self.gangs[gang])} if gang is not None else {})

    def _judge(self, key: str, pod: list):
        """``(how, refused)``: how ``key`` is admitted for some pod pending
        now (``"preempt"``, ``"reclaim"`` by the published rules, or
        ``"share"``: by ``reclaimable`` on the program's reading of
        ``proportion`` alone), or None and the counts it falls under."""
        if self._requests is None:
            self._requests = self.allocated + self.pending
        names = self.queue_names
        state = self._state(pod[GANG])
        victim = Pod(key, names[pod[QUEUE]], pod[VALUE], pod[GANG],
                     pod[REQUEST], True, pod[CRITICAL])
        # Of each queue, its pending pod of the highest class claims.
        claimants = [Pod("", n, max(+self.waiting[i]), "", (), False)
                     for i, n in enumerate(names) if +self.waiting[i]]
        for how, admits in (
                ("preempt", preemptable), ("reclaim", reclaimable),
                ("share", lambda c, v, s: reclaimable(c, v, s, share_rule))):
            if any(admits(c, [victim], state) for c in claimants):
                return how, []
        # Refused as the stamps stand: by which rule, and is it the replay's
        # own lag (a share within one gang's request of its mark)?
        refused = []
        if not conformance_rule(None, [victim], state):
            refused.append("victim_critical")
        if not gang_rule(None, [victim], state):
            refused.append("gang_under_floor")
        own = [c for c in claimants if c.queue == victim.queue]
        if own and priority_rule(own[0], [victim], state):
            return None, refused        # preempt's, but for those two rules
        others = [c for c in claimants if c.queue != victim.queue
                  and not queue_overused(c.queue, state, self.slack)]
        if not others or not state.queues[victim.queue].reclaimable:
            refused.append("victim_unjustified")
        elif not share_rule(others[0], [victim], state, self.slack):
            refused.append("queue_under_deserved")
        if refused:
            return None, refused
        by_rule = proportion_rule(others[0], [victim], state, self.slack)
        return ("reclaim" if by_rule else "share"), []


# how a victim was admitted -> its line of the tally
HOW = {"preempt": "by_preempt", "reclaim": "by_reclaim",
       "share": "by_share_alone"}
TALLY = ("victims",) + tuple(HOW.values())


def judged(events, nodes, config):
    """``(counts, tally)`` of a run's evictions: the counts that ``check``
    returns, and of the victims that were Running how many were admitted by
    ``preemptable``, by ``reclaimable``, and by ``reclaimable`` on the
    program's share reading alone (the published every-dimension comparison
    refuses them; see the head of this file).  No limit stands on the tally."""
    replay = _Replay(nodes, config)
    for ev in events:
        replay.round(ev)
    return dict(replay.counts), dict(replay.tally)


def check(events, nodes, config) -> Dict[str, int]:
    """The run's evictions, each judged where its stamp stands among the
    binds, terminations and deletions: every count has the limit 0.

      victim_not_running     not bound (and so not Running) when taken
      victim_critical        of kube-system or of a critical class
      victim_unjustified     no pod pending then could claim it: none of a
                             higher class in its own queue, and none in another
                             queue that is not overused while its own may be
                             reclaimed from
      gang_under_floor       its gang is left under min_member (> 1)
      queue_under_deserved   taken by reclaim alone, and its queue then stands
                             under its deserved share, on the program's own
                             reading of it too
      evicted_beyond_demand  admitted, but the round's victims before it had
                             freed all that could claim it: by preempt, the
                             cpu pending in its own queue in a class above
                             its own; by reclaim, the cpu pending in the
                             other queues that are not overused

    A victim that ``preemptable`` or ``reclaimable`` admits for some pending
    pod falls under none of the first five; one they refuse falls under the
    rule that refused it, with the slack of one gang's request on every
    share (see the head of this file).  The tally goes to standard error,
    one line a run."""
    counts, tally = judged(events, nodes, config)
    print("preempt_ref: {victims} victims were Running when taken: "
          "{by_preempt} admitted by preemptable, {by_reclaim} by reclaimable, "
          "{by_share_alone} by reclaimable on the program's share reading "
          "alone (the published every-dimension comparison refuses them)"
          .format(**tally), file=sys.stderr)
    return counts
