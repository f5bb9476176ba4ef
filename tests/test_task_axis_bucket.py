"""The task axis takes the solve's one bucket rule (ISSUE 45): whole waves
of ``DEFAULT_WAVE``, at every row count.  (a) padded rows are inert and a
solve under one wave gives every real row the node the exact shape gives
it; (b) a trickle of differing pending counts lowers no program once the
store's shapes are warm; (c) the same on a full cluster that evicts, the
what-if solve and the victim kernel included.  CPU, small; tier-1.
"""

import numpy as np
import pytest

from test_fastpath_evict import CONF_PREEMPT
from volcano_tpu.api import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup
from volcano_tpu.api.spec import AffinityTerm
from volcano_tpu.cache import ClusterStore
from volcano_tpu.ops.wave import DEFAULT_WAVE, solve_wave
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.sim import ClusterSimulator
from volcano_tpu.synth import (preempt_cluster, solve_args_from_store,
                               synthetic_cluster)

ROWS = [1, 3, 8, 37, 150, 611, 1158, 2047, 2049, 4096]


@pytest.fixture(scope="module")
def lowered():
    """Names of the programs JAX lowers while this module's trickles run:
    the event ``benchmark/run.py``'s ``Compiles`` counts for
    ``compiles_in_window``.  The listener comes off with the module."""
    import jax.monitoring

    names = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            names.append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(on)
    yield names
    jax.monitoring.unregister_event_duration_listener(on)


def _cut(args, rows):
    """``synth`` pads the task axis itself; back to the real rows, as the
    fast path hands them over."""
    tasks, aff = args[1], args[7]
    real = np.asarray(tasks.real)
    assert real[:rows].all() and not real[rows:].any()
    tasks = type(tasks)(*[np.asarray(f)[:rows] for f in tasks])
    aff = aff._replace(**{
        name: np.asarray(getattr(aff, name))[:rows]
        for name in ("t_req_aff", "t_req_anti", "t_matches", "t_soft")})
    return (args[0], tasks, *args[2:7], aff)


def _hold_rules(args, res, rows):
    """The harness's rules on a solve's own arrays, over the real rows:
    capacity, whole gangs, and a queue's share made of real rows only."""
    nodes, tasks, jobs, queues = args[:4]
    assigned = np.asarray(res.assigned)[:rows]
    placed = assigned >= 0
    req = np.asarray(tasks.req)
    use = np.zeros_like(np.asarray(nodes.idle))
    np.add.at(use, assigned[placed], req[placed])
    assert (use <= np.asarray(nodes.idle) + 1e-3).all()
    job = np.asarray(tasks.job)
    per_job = np.bincount(job[placed], minlength=len(jobs.min_available))
    ready = np.asarray(jobs.ready_base) + per_job
    assert ((per_job == 0) | (ready >= np.asarray(jobs.min_available))).all()
    assert not per_job[np.asarray(res.never_ready)].any()
    share = np.array(queues.allocated, np.float64)
    np.add.at(share, np.asarray(jobs.queue)[job[placed]], req[placed])
    np.testing.assert_allclose(np.asarray(res.q_alloc), share, rtol=1e-5)
    return int(placed.sum())


@pytest.mark.parametrize("rows", ROWS)
def test_padded_rows_are_inert_at_every_row_count(rows):
    # short of room at 611 rows and from 2,047 on: some gangs wait
    node_cpu = 16 if rows < 1000 else 64
    store = synthetic_cluster(n_nodes=64, n_pods=rows, node_cpu=str(node_cpu),
                              gang_size=min(4, rows), n_queues=2, seed=rows)
    args = _cut(solve_args_from_store(store)[0], rows)
    res = solve_wave(*args)
    padded = -(-rows // DEFAULT_WAVE) * DEFAULT_WAVE
    for name in ("assigned", "pipelined"):
        arr = np.asarray(getattr(res, name))
        assert arr.shape == (padded,) and (arr[rows:] == -1).all(), name
    assert _hold_rules(args, res, rows) > 0
    if rows < DEFAULT_WAVE:
        # the exact shape, which ``wave`` still lets a caller ask for
        exact = solve_wave(*args, wave=rows)
        assert exact.assigned.shape == (rows,)
        np.testing.assert_array_equal(
            np.asarray(res.assigned)[:rows], np.asarray(exact.assigned))
        for name in ("never_ready", "fit_failed", "idle", "q_alloc"):
            np.testing.assert_array_equal(np.asarray(getattr(res, name)),
                                          np.asarray(getattr(exact, name)), name)
    # ... and through a cycle: a bind, a gang's count and a journey are
    # of real pods only
    try:
        Scheduler(store).run_once()
        store.flush_binds()
        binds = store.binder.binds
        assert len(store.binder.channel) == len(binds) > 0   # at most once
        assert set(binds) <= {f"{p.namespace}/{p.name}"
                              for p in store.pods.values()}
        cpu = {}
        gang = {}
        for p in store.pods.values():
            if p.node_name:
                cpu[p.node_name] = cpu.get(p.node_name, 0) + int(
                    p.containers[0]["cpu"])
                g = p.annotations[GROUP_NAME_ANNOTATION]
                gang[g] = gang.get(g, 0) + 1
        assert max(cpu.values()) <= node_cpu
        assert all(n >= store.pod_groups[f"default/{g}"].min_member
                   for g, n in gang.items())
        assert sum(gang.values()) == len(binds)
        journey = store.journey.stats()
        assert journey["pods"] == rows and journey["bound"] == len(binds)
        record = store.flight.recent()[-1]
        assert record.pods_bound == len(binds)
        assert record.pods_considered <= rows
    finally:
        store.close()


def _submit(store, tag, pods, term):
    """``pods`` pods in gangs of 8; with ``term`` the first gang's pods
    keep off each other's hosts (an inter-pod term of its own)."""
    for g in range(-(-pods // 8)):
        name = f"{tag}-{g}"
        size = min(8, pods - 8 * g)
        store.add_pod_group(PodGroup(name=name, min_member=size))
        anti = [AffinityTerm(match_labels={"app": name},
                             topology_key="kubernetes.io/hostname")]
        for k in range(size):
            store.add_pod(Pod(
                name=f"{name}-{k}", labels={"app": name},
                annotations={GROUP_NAME_ANNOTATION: name},
                containers=[{"cpu": "10m", "memory": "1Mi"}],
                anti_affinity=anti if term and g == 0 else []))


@pytest.mark.parametrize("term", [False, True], ids=["term-free", "term"])
def test_a_trickle_of_pending_rows_lowers_nothing(term, lowered):
    store = ClusterStore()
    for i in range(64):
        store.add_node(Node(
            name=f"n{i:02d}", labels={"kubernetes.io/hostname": f"n{i:02d}"},
            allocatable={"cpu": "64", "memory": "256Gi", "pods": 1024}))
    sched = Scheduler(store)
    by_count = {}
    try:
        # two warm cycles: over one wave (the store's marks take the
        # largest counts), then under one
        for cycle, pods in enumerate(
                [4000, 300,
                 1, 2049, 37, 611, 3, 4095, 150, 1158, 8, 2047, 2500, 23]):
            _submit(store, f"c{cycle}", pods, term)
            before = len(lowered)
            sched.run_once()
            store.flush_binds()
            by_count[pods] = lowered[before:]
            assert store.flight.recent()[-1].solve["rows"] == pods
    finally:
        store.close()
    assert all(p.node_name for p in store.pods.values())
    assert by_count.pop(4000) and by_count.pop(300)
    assert len(by_count) == 12 and not any(by_count.values()), by_count


def test_an_evicting_trickle_lowers_nothing(monkeypatch, lowered):
    """A full cluster; each round one gang of the high class, of another
    size, binds by evicting; the victims end, come back Pending and take
    the room the gang leaves when it finishes."""
    monkeypatch.delenv("VOLCANO_TPU_EVICT_DEVICE", raising=False)
    store = preempt_cluster(n_nodes=8, n_pending=0, seed=0)
    sched = Scheduler(store, conf_str=CONF_PREEMPT)
    sim = ClusterSimulator(store)
    by_round = []
    spans = set()

    def cycle():
        sched.run_once()
        store.flush_binds()
        sim.step()
        spans.update(s.name for s in store.flight.recent()[-1].spans)

    try:
        for rnd, size in enumerate([4, 3, 2, 4, 1, 3]):
            name = f"hi-{rnd}"
            store.add_pod_group(PodGroup(name=name, min_member=size,
                                         queue="premium"))
            for k in range(size):
                store.add_pod(Pod(
                    name=f"{name}-{k}",
                    annotations={GROUP_NAME_ANNOTATION: name},
                    containers=[{"cpu": "16", "memory": "48Gi"}],
                    priority_class="high", priority=10000))
            before = len(lowered)
            evicted = len(store.evictor.evicts)
            for _ in range(8):
                cycle()
                gang = [p for p in store.pods.values()
                        if p.name.startswith(name + "-")]
                if all(p.node_name for p in gang):
                    break
            assert all(p.node_name for p in gang), rnd
            assert len(store.evictor.evicts) == evicted + size
            # the gang finishes; its victims bind where it ran
            for p in gang:
                store.delete_pod(p)
            for _ in range(4):
                cycle()
            assert all(p.node_name for p in store.pods.values()), rnd
            by_round.append(lowered[before:])
    finally:
        store.close()
    assert "whatif_solve" in spans
    assert any("victim_scores" in n for n in by_round[0])
    assert by_round[0] and not any(by_round[2:]), by_round
