"""A stale object model stays stale until somebody reads it (ISSUE 30).

After a fast-path commit the store's ``JobInfo`` / ``NodeInfo`` model is
void.  The event handlers leave it alone while it is, keeping only what
``_rebuild_objects`` reads, and the first reader of ``store.jobs`` /
``store.nodes`` / ``snapshot()`` pays the one rebuild.  Held here: the
rebuilt model is the one an always-fresh store keeps by hand; the rule
itself, with its counters; the stale model pins no pod record; and the
object session binds after a stale stretch what ``oracle.py`` binds.

Small CPU shapes; tier-1.
"""

import copy
import gc
import weakref

import numpy as np
import pytest

from volcano_tpu.api import (GROUP_NAME_ANNOTATION, Node, Pod, PodGroup,
                             PodPhase, PriorityClass, Queue, TaskInfo)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.oracle import solve_oracle
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import solve_args_from_store, synthetic_cluster

pytestmark = pytest.mark.tier1


# ------------------------------------------------------------------ parity

N_NODES = 6


def _node(i, rng, gen=0):
    return Node(
        name=f"n{i}",
        allocatable={"cpu": str(64 + 8 * gen), "memory": "256Gi",
                     "pods": 110},
        labels={"zone": f"z{int(rng.integers(0, 3))}", "gen": str(gen)},
    )


def _events(seed, n=140):
    """A seeded sequence of store events as ``(kind, payload)``; every
    payload is a spec either store turns into objects of its own, so the
    two stores share no mutable record."""
    rng = np.random.default_rng(seed)
    out = []
    nodes = {}                  # index -> generation, live nodes
    groups = {}                 # name -> dict spec
    pods = {}                   # name -> dict spec
    serial = 0
    for i in range(N_NODES):
        nodes[i] = 0
        out.append(("add_node", _node(i, rng)))
    out.append(("add_queue", "q1"))
    out.append(("add_priority_class", ("high", 100)))

    def loaded(i):
        return any(p["node_name"] == f"n{i}" for p in pods.values())

    def group_spec(name):
        return dict(name=name, min_member=int(rng.integers(1, 4)),
                    queue=str(rng.choice(["default", "q1"])),
                    priority_class=str(rng.choice(["", "high"])),
                    creation_timestamp=float(len(out) + 1))

    for _ in range(n):
        r = rng.random()
        if r < 0.10 or not groups:
            name = f"g{len(out)}"
            groups[name] = group_spec(name)
            out.append(("add_pod_group", dict(groups[name])))
        elif r < 0.16:
            # A group keeps the priority class it came with: a job's
            # priority is sticky on a fresh model once its class is
            # gone, and 0 on a rebuilt one.
            name = str(rng.choice(sorted(groups)))
            groups[name].update(min_member=int(rng.integers(1, 4)),
                                queue=str(rng.choice(["default", "q1"])))
            out.append(("update_pod_group", dict(groups[name])))
        elif r < 0.20:
            # Only a group without pods goes, and its name is never
            # used again: the mirror keeps a job's row, and its place in
            # the order, for ever.
            idle = [g for g in sorted(groups)
                    if not any(p["group"] == g for p in pods.values())]
            if idle:
                name = str(rng.choice(idle))
                del groups[name]
                out.append(("delete_pod_group", name))
        elif r < 0.50:
            serial += 1
            # One pod in ten arrives before its group, and one in four
            # already bound (one of those to a node never announced).
            group = (f"late{serial}" if rng.random() < 0.1
                     else str(rng.choice(sorted(groups))))
            node_name = None
            if rng.random() < 0.25 and nodes:
                node_name = (f"n{int(rng.choice(sorted(nodes)))}"
                             if rng.random() < 0.9 else "ghost")
            spec = dict(
                name=f"p{serial}", uid=f"uid-{seed}-{serial}", group=group,
                cpu=int(rng.integers(1, 3)), node_name=node_name,
                phase=(PodPhase.Running if node_name else PodPhase.Pending),
                deleting=False, ts=float(serial))
            pods[spec["name"]] = spec
            out.append(("add_pod", dict(spec)))
        elif r < 0.62 and pods:
            name = str(rng.choice(sorted(pods)))
            spec = pods[name]
            if spec["node_name"] is None and nodes:
                spec["node_name"] = f"n{int(rng.choice(sorted(nodes)))}"
                spec["phase"] = PodPhase.Running
            elif spec["phase"] == PodPhase.Running:
                spec["phase"] = str(rng.choice(
                    [PodPhase.Succeeded, PodPhase.Failed, PodPhase.Running]))
                spec["deleting"] = bool(rng.random() < 0.3)
            out.append(("update_pod", dict(spec)))
        elif r < 0.74 and pods:
            name = str(rng.choice(sorted(pods)))
            del pods[name]
            out.append(("delete_pod", name))
        elif r < 0.79:
            pending = [p for p in sorted(pods)
                       if pods[p]["node_name"] is None]
            if pending and nodes:
                name = str(rng.choice(pending))
                host = f"n{int(rng.choice(sorted(nodes)))}"
                pods[name]["node_name"] = host
                out.append(("bind", (name, host)))
        elif r < 0.83:
            running = [p for p in sorted(pods)
                       if pods[p]["node_name"] is not None
                       and pods[p]["phase"] == PodPhase.Running
                       and not pods[p]["deleting"]]
            if running:
                name = str(rng.choice(running))
                pods[name]["deleting"] = True
                out.append(("evict", name))
        elif r < 0.88:
            bound = [p for p in sorted(pods)
                     if pods[p]["node_name"] is not None
                     and pods[p]["phase"] == PodPhase.Running]
            if bound:
                name = str(rng.choice(bound))
                pods[name]["node_name"] = None
                out.append(("bind_failure", name))
        elif r < 0.93 and nodes:
            i = int(rng.choice(sorted(nodes)))
            nodes[i] += 1
            out.append(("update_node", _node(i, rng, nodes[i])))
        elif r < 0.96:
            # A node goes only when it is empty, and a new node takes a
            # new name: a fresh model forgets the tasks of a node that
            # goes and comes back, and puts it last; a rebuilt one finds
            # them, and the node in the row it had.
            empty = [i for i in sorted(nodes) if not loaded(i)]
            if empty and len(nodes) > 2:
                i = int(rng.choice(empty))
                del nodes[i]
                out.append(("delete_node", f"n{i}"))
        else:
            i = max(list(nodes) + [N_NODES - 1]) + 1
            nodes[i] = 0
            out.append(("add_node", _node(i, rng)))
    return out


def _pod(spec):
    return Pod(
        name=spec["name"], uid=spec["uid"],
        annotations={GROUP_NAME_ANNOTATION: spec["group"]},
        containers=[{"cpu": str(spec["cpu"]), "memory": "1Gi"}],
        node_name=spec["node_name"], phase=spec["phase"],
        deleting=spec["deleting"], creation_timestamp=spec["ts"])


def _apply(store, kind, payload):
    if kind == "add_node":
        store.add_node(copy.deepcopy(payload))
    elif kind == "update_node":
        store.update_node(copy.deepcopy(payload))
    elif kind == "delete_node":
        store.delete_node(payload)
    elif kind == "add_queue":
        store.add_queue(Queue(name=payload, weight=2))
    elif kind == "add_priority_class":
        store.add_priority_class(PriorityClass(name=payload[0],
                                               value=payload[1]))
    elif kind in ("add_pod_group", "update_pod_group"):
        getattr(store, kind)(PodGroup(**payload))
    elif kind == "delete_pod_group":
        store.delete_pod_group(f"default/{payload}")
    elif kind in ("add_pod", "update_pod"):
        getattr(store, kind)(_pod(payload))
    else:
        by_name = {p.name: p for p in store.pods.values()}
        if kind == "delete_pod":
            store.delete_pod(by_name[payload])
        elif kind == "bind":
            store.bind(TaskInfo(by_name[payload[0]]), payload[1])
        elif kind == "evict":
            store.evict(TaskInfo(by_name[payload]), "test")
        elif kind == "bind_failure":
            pod = by_name[payload]
            store._on_bind_failures([(f"{pod.namespace}/{pod.name}", pod)])
            assert store.drain_bind_failures() == 1
        else:
            raise AssertionError(kind)


def _res(r):
    return (r.milli_cpu, r.memory, dict(r.scalars or {}))


def _task(t):
    return (t.uid, t.job, t.name, t.namespace, t.status, t.node_name,
            t.priority, _res(t.resreq), _res(t.init_resreq),
            t.pod.uid, t.pod.node_name, t.pod.phase, t.pod.deleting)


def _job(job):
    pg = job.pod_group
    return dict(
        uid=job.uid, name=job.name, namespace=job.namespace,
        queue=job.queue, priority=job.priority,
        min_available=job.min_available,
        creation_timestamp=job.creation_timestamp,
        pod_group=None if pg is None else (
            pg.uid, pg.min_member, pg.queue, pg.priority_class),
        tasks={uid: _task(t) for uid, t in job.tasks.items()},
        index={st: sorted(ts) for st, ts in job.task_status_index.items()},
        empty_pending=job._empty_pending,
        allocated=_res(job.allocated),
        total_request=_res(job.total_request))


def _node_info(node):
    return dict(
        name=node.name,
        spec=None if node.node is None else (
            node.node.name, dict(node.node.allocatable),
            dict(node.node.labels)),
        state=(node.state.phase, node.state.reason),
        idle=_res(node.idle), used=_res(node.used),
        releasing=_res(node.releasing), pipelined=_res(node.pipelined),
        allocatable=_res(node.allocatable),
        capability=_res(node.capability),
        tasks={key: _task(t) for key, t in node.tasks.items()})


def _model(jobs, nodes):
    """The object model field by field.  The order of the two dicts is
    part of it for what a session is handed in that order: jobs that
    have their group, nodes that have their spec.  Three things differ
    between a model kept by hand and a rebuilt one, before this change
    as after it, and compare as sets: a job whose group never came
    stands where its first pod arrived (rebuilt: behind every grouped
    job) and stays, empty, when its last pod has gone (rebuilt: it is
    not there); the placeholder of a node never announced does the same
    among the nodes.  Tasks within a job or a node compare as sets too."""
    grouped = [(uid, _job(job)) for uid, job in jobs.items()
               if job.pod_group is not None]
    ungrouped = {uid: _job(job) for uid, job in jobs.items()
                 if job.pod_group is None and job.tasks}
    announced = [(name, _node_info(node)) for name, node in nodes.items()
                 if node.node is not None]
    placeholders = {name: _node_info(node) for name, node in nodes.items()
                    if node.node is None and node.tasks}
    return grouped, ungrouped, announced, placeholders


def _snapshot(store):
    info = store.snapshot()
    return (_model(info.jobs, info.nodes),
            [(name, q.weight) for name, q in info.queues.items()],
            sorted((ns, ni.weight)
                   for ns, ni in info.namespace_info.items()))


@pytest.mark.parametrize("seed", range(12))
def test_a_model_left_stale_rebuilds_to_the_one_kept_fresh(seed):
    rng = np.random.default_rng(1000 + seed)
    fresh, lazy = ClusterStore(), ClusterStore()
    # ``lazy`` is stale from the start and goes stale again, as after a
    # commit, soon after any read; ``fresh`` is read after every event,
    # so its handlers always keep the model by hand.
    lazy.mark_objects_stale()
    for kind, payload in _events(seed):
        _apply(fresh, kind, payload)
        assert fresh.jobs is not None and not fresh._objects_stale
        _apply(lazy, kind, payload)
        r = rng.random()
        if r < 0.06:
            assert lazy.nodes is not None and not lazy._objects_stale
        elif r < 0.30:
            lazy.mark_objects_stale()
    # No cycle took the count: every event ``lazy`` took stale is in it.
    assert lazy._stale_events_cycle > 50 and fresh._stale_events_cycle == 0
    assert sorted(lazy.pods) == sorted(fresh.pods)
    assert _model(lazy.jobs, lazy.nodes) == _model(fresh.jobs, fresh.nodes)
    assert _snapshot(lazy) == _snapshot(fresh)
    # And the mirror rows the fast cycle reads are the same rows.
    ml, mf = lazy.mirror, fresh.mirror
    assert ml.n_name == mf.n_name and ml.j_uid == mf.j_uid
    assert ml.p_uid == mf.p_uid
    np.testing.assert_array_equal(ml.p_status[:ml.n_pods],
                                  mf.p_status[:mf.n_pods])
    np.testing.assert_array_equal(ml.p_node[:ml.n_pods],
                                  mf.p_node[:mf.n_pods])
    np.testing.assert_array_equal(ml.j_prio[:len(ml.j_uid)],
                                  mf.j_prio[:len(mf.j_uid)])
    np.testing.assert_array_equal(ml.j_alive[:len(ml.j_uid)],
                                  mf.j_alive[:len(mf.j_uid)])


# ---------------------------------------------------------------- the rule


class _Rebuilds:
    """Every ``store:rebuild_objects`` event so far: those sealed into
    cycle records, and those the tracer still held when asked."""

    def __init__(self, store):
        self.store = store
        self.drained = []

    def __call__(self):
        self.drained += self.store.tracer.drain()
        spans = [s for rec in self.store.flight.recent() for s in rec.spans]
        return [s for s in spans + self.drained
                if s.name == "store:rebuild_objects"]


def _late_gang(store, tag, size=3, cpu="1"):
    pg = PodGroup(name=f"late-{tag}", min_member=size)
    store.add_pod_group(pg)
    for k in range(size):
        store.add_pod(Pod(
            name=f"late-{tag}-{k}",
            annotations={GROUP_NAME_ANNOTATION: pg.name},
            containers=[{"cpu": cpu, "memory": "1Gi"}]))


def test_events_after_a_commit_rebuild_nothing_until_somebody_reads():
    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=5)
    rebuilds = _Rebuilds(store)
    Scheduler(store).run_once()
    assert len(store.binder.binds) == 32 and store._objects_stale
    n = 6
    for pod in list(store.pods.values())[:n]:
        store.delete_pod(pod)
    for k in range(n // 3):
        _late_gang(store, k)                    # 2 groups + 6 pods
    assert store._objects_stale
    assert store._jobs == {} and store._nodes == {}
    Scheduler(store).run_once()                 # drains the store track
    assert not rebuilds()
    rec = store.flight.recent()[-1]
    assert rec.object_model == {"stale": 1, "stale_events": 2 * n + 2}
    # The cycle took the count; the model's own runs on.
    store.delete_pod(next(iter(store.pods.values())))
    assert len(store.jobs) == 10                # the reader pays, once
    assert len(store.nodes) == 8 and store.snapshot().jobs
    assert not store._objects_stale and store._stale_events == 0
    assert [s.args for s in rebuilds()] == [
        {"pods": 31, "stale_events": 2 * n + 3}]
    # Fresh again: the handlers keep it by hand, as they always did.
    store.delete_pod(next(iter(store.pods.values())))
    assert sum(len(j.tasks) for j in store.jobs.values()) == 30
    assert store._stale_events == 0 and len(rebuilds()) == 1
    Scheduler(store).run_once()
    assert store.flight.recent()[-1].object_model == {
        "stale": 0, "stale_events": 1}


# --------------------------------------------------------- no stale holder


def test_the_model_a_commit_voids_holds_no_pod_record():
    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=9)
    assert sum(len(j.tasks) for j in store.jobs.values()) == 32
    assert not store._objects_stale             # fresh before the commit
    Scheduler(store).run_once()
    store.flush_binds()
    assert len(store.binder.binds) == 32 and store._objects_stale
    refs = [weakref.ref(pod) for pod in store.pods.values()]
    for pod in list(store.pods.values()):
        store.delete_pod(pod)
    del pod
    assert store._objects_stale and not store.pods
    gc.collect()
    alive = [r() for r in refs if r() is not None]
    assert not alive, [gc.get_referrers(p) for p in alive[:1]]


# ------------------------------------------------- the object session after

SEQ_CONF = """
actions: "enqueue, allocate"
configurations:
- name: allocate
  arguments:
    solver: seq
tiers:
- plugins:
  - name: gang
  - name: predicates
  - name: binpack
"""

SHAPE = dict(n_nodes=6, n_pods=24, gang_size=3)


@pytest.mark.parametrize("seed", range(4))
def test_the_object_session_after_a_stale_stretch_binds_what_the_oracle_binds(
        seed, monkeypatch):
    store = synthetic_cluster(seed=seed, **SHAPE)
    rebuilds = _Rebuilds(store)
    Scheduler(store).run_once()                 # the fast path commits
    first = dict(store.binder.binds)
    assert len(first) == 24 and store._objects_stale
    gone = {p.name for p in list(store.pods.values())[:6]}
    for pod in [p for p in store.pods.values() if p.name in gone]:
        store.delete_pod(pod)
    for k in range(4):
        _late_gang(store, k, cpu=str(1 + (seed + k) % 4))
    assert store._objects_stale and store._stale_events == 6 + 4 * 4

    # The oracle's cluster: the same seed, never stale, brought to the
    # same state through the handlers that keep the model by hand.
    twin = synthetic_cluster(seed=seed, **SHAPE)
    for pod in list(twin.pods.values()):
        bound = copy.copy(pod)
        bound.node_name = first[f"{pod.namespace}/{pod.name}"]
        twin.update_pod(bound)
    for pod in [p for p in twin.pods.values() if p.name in gone]:
        twin.delete_pod(pod)
    for k in range(4):
        _late_gang(twin, k, cpu=str(1 + (seed + k) % 4))
    assert twin._stale_events == 0 and not twin._objects_stale
    args, maps = solve_args_from_store(twin)
    want = solve_oracle(*args)
    oracle_binds = {
        f"{t.namespace}/{t.name}": maps.node_names[want.assigned[i]]
        for i, t in enumerate(maps.task_infos) if want.assigned[i] >= 0}
    assert len(oracle_binds) == 12

    monkeypatch.setenv("VOLCANO_TPU_FASTPATH", "0")
    Scheduler(store, conf_str=SEQ_CONF).run_once()
    got = {k: v for k, v in store.binder.binds.items() if k not in first}
    assert got == oracle_binds
    assert len(rebuilds()) == 1                 # the session's snapshot()
