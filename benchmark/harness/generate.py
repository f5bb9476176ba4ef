"""Traffic generation: one general generator, driven by a configuration file
(node and pod shapes, gangs, queues) and a traffic file (how much, how often).

The draw is a copy of ``volcano_tpu/synth.py``'s ``synthetic_cluster`` (nodes
of one shape, zones round-robin, each gang one cpu and one memory size out of
the configuration's choices, optional affinity mix), kept here because later
PRs may change ``synth.py`` and may not change the yardstick.  Two departures,
both so that every seed does the same work in another order:

- gangs take the cpu x memory combinations in equal shares, dealt from a
  deck the seed shuffles (synth draws them independently);
- uids and creation timestamps are given (synth lets the API mint a uuid and
  read the clock per pod), which also keeps the client's own cost small.

A *plan* is plain data (names, integer requests, gang of each pod): the
validator and the reference read plans and never the program's objects.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

GI = 1 << 30
NAMESPACE = "default"
TASK_NAME = "worker"        # the one task of a gang that enters as a Job


@dataclass
class Plan:
    """One batch of gangs as plain data.  Pod ``i`` of the batch has name
    ``names[i]``, requests ``cpu_milli[i]`` / ``mem_bytes[i]`` and belongs to
    gang ``gang[i]`` (an index into the gang arrays)."""

    tag: str
    names: List[str]
    cpu_milli: np.ndarray
    mem_bytes: np.ndarray
    gang: np.ndarray
    gang_names: List[str]
    gang_min_member: np.ndarray
    gang_queue: List[str]
    gang_cpu: List[int] = field(default_factory=list)      # cores
    gang_mem_gi: List[int] = field(default_factory=list)
    gang_kind: List[str] = field(default_factory=list)     # "", affinity, ...
    # What follows is set only by a configuration with ``priority_classes``
    # or a traffic with ``waiting_fraction``; left as it is, a plan is what
    # it was before they came.
    gang_priority: List[str] = field(default_factory=list)  # class names
    gang_size: Optional[np.ndarray] = None     # pods, where min_member is less
    gang_max_unavailable: List[Optional[int]] = field(default_factory=list)
    may_wait: bool = False                     # need not bind within its round

    @property
    def n_pods(self) -> int:
        return len(self.names)

    def sizes(self) -> np.ndarray:
        """Pods in each gang (``gang_min_member`` unless a class said less)."""
        return self.gang_min_member if self.gang_size is None else self.gang_size

    def keys(self) -> List[str]:
        return [f"{NAMESPACE}/{n}" for n in self.names]

    def job_keys(self) -> List[str]:
        """Each gang's key as a Job (``entry: jobs``), in the gangs' order."""
        return [f"{NAMESPACE}/{g}" for g in self.gang_names]


def node_names(config) -> List[str]:
    return [f"node-{i:06d}" for i in range(int(config["nodes"]["count"]))]


def node_alloc(config) -> np.ndarray:
    """[N, 3] int64: allocatable cpu (milli), memory (bytes), pods."""
    n = config["nodes"]
    row = [int(n["cpu"]) * 1000, int(n["memory_gi"]) * GI, int(n["pods"])]
    return np.tile(np.array(row, dtype=np.int64), (int(n["count"]), 1))


def node_labels(config) -> List[dict]:
    """Each node's labels as plain data: zones dealt round-robin."""
    zones = int(config["nodes"].get("zones", 0))
    return [{"zone": f"zone-{i % zones}"} if zones > 0 else {}
            for i in range(int(config["nodes"]["count"]))]


def queue_names(config) -> List[str]:
    q = config.get("queues", {})
    return ["default"] + [f"queue-{i}" for i in range(1, int(q.get("count", 1)))]


class Generator:
    """Batches of gangs for one run, all drawn from ``seed``.  Under
    ``entry: jobs`` a gang's pods bear the names the job controller will
    give them (``<job>-worker-<i>``): the plan states them, and the store is
    held to the plan."""

    def __init__(self, config: dict, seed: int, entry: str = "pods"):
        self.config = config
        self._pod_of = "{}-" + TASK_NAME + "-{}" if entry == "jobs" else "{}-{}"
        self.rng = np.random.default_rng(seed)
        pods = config["pods"]
        self.combos = [(int(c), int(m)) for c in pods["cpu_choices"]
                       for m in pods["mem_gi_choices"]]
        gang = config["gang"]
        self.gang_sizes = [int(s) for s in gang.get("sizes", [gang.get("size", 1)])]
        self.queues = queue_names(config)
        mix = config.get("affinity_mix", {})
        self.mix = (float(mix.get("affinity", 0.0)),
                    float(mix.get("anti_affinity", 0.0)),
                    float(mix.get("spread", 0.0)))
        self.zones = int(config["nodes"].get("zones", 0))
        # Priority classes, optional: each may bring a gang shape of its own.
        self.classes = {c["name"]: c for c in config.get("priority_classes", [])}
        shares = np.array([float(c.get("share", 0.0))
                           for c in self.classes.values()])
        self._class_edges = np.cumsum(shares / shares.sum()) if shares.sum() else None
        self.batch_class: Optional[str] = None     # of a plan that names none
        # A class may name the queues its gangs are dealt to, in turn; one
        # that names none is dealt over all of them, as every gang was.
        self._class_queues = {name: list(c["queues"])
                              for name, c in self.classes.items() if "queues" in c}
        for name, own in self._class_queues.items():
            if not own or set(own) - set(self.queues):
                raise ValueError(f"priority class {name!r} names queues {own}; "
                                 f"the configuration has {self.queues}")
        self._gangs_made = 0
        self._deck: List[int] = []

    def plan(self, n_pods: int, tag: str, gang_size: Optional[int] = None,
             klass: Optional[str] = None, may_wait: bool = False) -> Plan:
        """``n_pods`` pods in gangs of the configuration's size(s) (or of
        ``gang_size``); the last gang is cut to what is left.  Where the
        configuration has priority classes every gang is of ``klass``, else
        of ``self.batch_class``, else of a class drawn by the classes'
        shares, and takes that class's ``gang`` where it states one."""
        sizes, classes = [], []
        left = int(n_pods)
        while left > 0:
            shape = self.gang_sizes
            if self.classes:
                classes.append(klass or self.batch_class or self._draw_class())
                own = self.classes[classes[-1]].get("gang")
                if own:
                    shape = [int(s) for s in own.get("sizes", [own.get("size", 1)])]
            size = gang_size or int(shape[
                int(self.rng.integers(len(shape))) if len(shape) > 1 else 0])
            size = min(size, left)
            sizes.append(size)
            left -= size
        g_n = len(sizes)
        combo_of = self._deal(g_n)
        kinds = self._kinds(g_n)
        names, cpu, mem, gang = [], [], [], []
        gang_names, gang_queue, gang_cpu, gang_mem = [], [], [], []
        for g, size in enumerate(sizes):
            c, m = self.combos[int(combo_of[g])]
            gname = f"{tag}-pg-{g:06d}"
            gang_names.append(gname)
            queues = self._class_queues.get(classes[g], self.queues) \
                if classes else self.queues
            gang_queue.append(queues[(self._gangs_made + g) % len(queues)])
            gang_cpu.append(c)
            gang_mem.append(m)
            for k in range(size):
                names.append(self._pod_of.format(gname, k))
                cpu.append(c * 1000)
                mem.append(m * GI)
                gang.append(g)
        self._gangs_made += g_n
        plan = Plan(tag, names, np.array(cpu, np.int64), np.array(mem, np.int64),
                    np.array(gang, np.int64), gang_names,
                    np.array(sizes, np.int64), gang_queue, gang_cpu, gang_mem,
                    kinds, may_wait=may_wait)
        if classes:
            self._shape_by_class(plan, classes)
        elif "min_member" in self.config["gang"]:
            # An elastic gang without classes: the configuration's own floor.
            floor = np.minimum(plan.gang_min_member,
                               int(self.config["gang"]["min_member"]))
            plan.gang_size, plan.gang_min_member = plan.gang_min_member, floor
        return plan

    def _draw_class(self) -> str:
        if self._class_edges is None:
            return next(iter(self.classes))
        i = int(np.searchsorted(self._class_edges, self.rng.random(), "right"))
        return list(self.classes)[min(i, len(self.classes) - 1)]

    def _shape_by_class(self, plan: Plan, classes: List[str]) -> None:
        """The gang plugin's floor and the ledger's budget, where a class
        states them: ``min_member`` under the size makes a gang elastic."""
        plan.gang_priority = classes
        sizes = plan.gang_min_member
        floor = sizes.copy()
        for g, name in enumerate(classes):
            own = self.classes[name].get("gang") or {}
            if "min_member" in own:
                floor[g] = min(int(own["min_member"]), int(sizes[g]))
            plan.gang_max_unavailable.append(own.get("max_unavailable"))
        if (floor != sizes).any():
            plan.gang_size, plan.gang_min_member = sizes, floor

    def _deal(self, g_n: int) -> np.ndarray:
        """Combination of each of the next ``g_n`` gangs: dealt from a deck
        of all cpu x memory combinations that is shuffled anew whenever it
        runs out, so that any stretch of gangs holds every combination in
        equal shares (to within one) whatever the seed."""
        out = []
        while len(out) < g_n:
            if not len(self._deck):
                self._deck = self.rng.permutation(len(self.combos)).tolist()
            out.append(self._deck.pop())
        return np.array(out, dtype=np.int64)

    def _kinds(self, g_n: int) -> List[str]:
        aff, anti, spread = self.mix
        if aff + anti + spread <= 0.0:
            return [""] * g_n
        r = self.rng.random(g_n)
        out = []
        for x in r:
            if self.zones > 0 and x < aff:
                out.append("affinity")
            elif aff <= x < aff + anti:
                out.append("anti_affinity")
            elif self.zones > 0 and aff + anti <= x < aff + anti + spread:
                out.append("spread")
            else:
                out.append("")
        return out


# --- the only part of this file that touches the program's API types -------


def to_nodes(config):
    from volcano_tpu.api import Node

    n = config["nodes"]
    alloc = {"cpu": str(n["cpu"]), "memory": f"{n['memory_gi']}Gi",
             "pods": int(n["pods"])}
    return [Node(name=name, allocatable=dict(alloc), labels=labels)
            for name, labels in zip(node_names(config), node_labels(config))]


def to_queues(config):
    from volcano_tpu.api import Queue

    q = config.get("queues", {})
    weights = q.get("weights") or [1]
    reclaimable = q.get("reclaimable")
    out = []
    for i, name in enumerate(queue_names(config)):
        # The store makes ``default`` itself (weight 1, reclaimable); it is
        # stated again only where the file says what it may give up.
        if i > 0 or reclaimable is not None:
            queue = Queue(name=name, weight=int(weights[i % len(weights)]))
            if reclaimable is not None:
                queue.reclaimable = bool(reclaimable[i % len(reclaimable)])
            out.append(queue)
    return out


def to_priority_classes(config):
    from volcano_tpu.api import PriorityClass

    return [PriorityClass(name=c["name"], value=int(c["value"]))
            for c in config.get("priority_classes", [])]


# Pod fields no gang of the benchmark sets: one empty list and one empty dict
# stand in all of them, for every pod.  The store treats pod specs as
# immutable, and a dozen fresh containers per pod would make the client's own
# allocation (and the collector's passes over it) a tenth of a round.
_NO_LIST: list = []
_NO_DICT: dict = {}
_UNSET = dict(
    init_containers=_NO_LIST, node_selector=_NO_DICT, tolerations=_NO_LIST,
    host_ports=_NO_LIST, affinity=_NO_LIST, anti_affinity=_NO_LIST,
    preferred_node_affinity=_NO_LIST, required_node_affinity=_NO_LIST,
    preferred_affinity=_NO_LIST, preferred_anti_affinity=_NO_LIST,
    topology_spread=_NO_LIST, env=_NO_DICT, volumes=_NO_LIST)


def to_objects(plan: Plan, stamps, priority_values=None):
    """The plan as API objects: one list of gangs, each ``(pod_group,
    [pods])``.  ``stamps`` is an iterator of rising creation timestamps (the
    run's own, so that job order does not depend on the clock).  Sub-objects
    a gang's pods share (annotations, labels, containers) are shared by
    reference, as ``synth.tier_cluster`` does: the store treats pod specs as
    immutable.  ``priority_values`` (class name -> value) is read only for a
    plan whose gangs carry classes."""
    from volcano_tpu.api import (GROUP_NAME_ANNOTATION, AffinityTerm, Pod,
                                 PodGroup)

    gangs = []
    start = 0
    sizes = plan.sizes()
    classes = plan.gang_priority
    for g, gname in enumerate(plan.gang_names):
        size = int(sizes[g])
        pg = PodGroup(name=gname, min_member=int(plan.gang_min_member[g]),
                      queue=plan.gang_queue[g],
                      creation_timestamp=float(next(stamps)))
        anno = {GROUP_NAME_ANNOTATION: gname}
        labels = {"app": gname}
        containers = [{"cpu": str(plan.gang_cpu[g]),
                       "memory": f"{plan.gang_mem_gi[g]}Gi"}]
        kind = plan.gang_kind[g] if plan.gang_kind else ""
        extra = dict(_UNSET)
        if kind == "affinity":
            extra["affinity"] = [AffinityTerm(match_labels=labels,
                                              topology_key="zone")]
        elif kind == "anti_affinity":
            extra["anti_affinity"] = [AffinityTerm(
                match_labels=labels, topology_key="kubernetes.io/hostname")]
        elif kind == "spread":
            extra["topology_spread"] = [("zone", 10)]
        if classes:
            # The job's rank is its PodGroup's class; a pod carries the
            # class and its value, as an admitted pod does.
            pg.priority_class = classes[g]
            pg.max_unavailable = plan.gang_max_unavailable[g]
            extra.update(priority_class=classes[g],
                         priority=priority_values[classes[g]])
        pods = []
        for k in range(size):
            name = plan.names[start + k]
            pods.append(Pod(name=name, uid=f"bench-{name}", labels=labels,
                            annotations=anno, containers=containers,
                            creation_timestamp=float(next(stamps)), **extra))
        start += size
        gangs.append((pg, pods))
    return gangs


def to_jobs(plan: Plan, stamps, block: Optional[dict] = None):
    """The plan as Jobs (``entry: jobs``): one list of gangs, each ``(job,
    [pod keys])``.  A gang is one ``Job`` of one task, ``worker``, whose
    replicas are the gang's pods and whose container is the gang's cpu and
    memory; ``min_available`` is the gang's ``min_member``; ``block`` is the
    configuration's ``job`` block (``plugins``, ``policies``, ``max_retry``,
    as ``examples/job.yaml`` has them), copied onto every Job.  The pods are
    the controller's to make; their keys are the plan's."""
    from volcano_tpu.controllers import Job, LifecyclePolicy, TaskSpec

    block = block or {}
    keys = plan.keys()
    gangs = []
    start = 0
    sizes = plan.sizes()
    classes = plan.gang_priority
    for g, gname in enumerate(plan.gang_names):
        size = int(sizes[g])
        task = TaskSpec(name=TASK_NAME, replicas=size, containers=[
            {"cpu": str(plan.gang_cpu[g]), "memory": f"{plan.gang_mem_gi[g]}Gi"}])
        job = Job(name=gname, namespace=NAMESPACE, uid=f"bench-{gname}",
                  min_available=int(plan.gang_min_member[g]), tasks=[task],
                  queue=plan.gang_queue[g],
                  priority_class=classes[g] if classes else "",
                  plugins={name: list(args)
                           for name, args in block.get("plugins", {}).items()},
                  policies=[LifecyclePolicy(event=p.get("event", ""),
                                            action=p["action"],
                                            exit_code=p.get("exit_code"))
                            for p in block.get("policies", [])],
                  creation_timestamp=float(next(stamps)))
        if "max_retry" in block:
            job.max_retry = int(block["max_retry"])
        gangs.append((job, keys[start:start + size]))
        start += size
    return gangs
