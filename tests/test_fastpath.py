"""Fast-path parity: the vectorized cycle must produce the same binds and
pod-group phases as the object-session path on identical stores."""

import os

import pytest

from volcano_tpu.framework import parse_scheduler_conf
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

CONF = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""


def _run(store, fast: bool):
    os.environ["VOLCANO_TPU_FASTPATH"] = "1" if fast else "0"
    try:
        Scheduler(store, conf_str=CONF).run_once()
    finally:
        os.environ.pop("VOLCANO_TPU_FASTPATH", None)
    return store


def _state(store):
    binds = dict(store.binder.binds)
    phases = {
        uid: pg.status.phase for uid, pg in sorted(store.pod_groups.items())
    }
    return binds, phases


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize(
    "kwargs",
    [
        dict(n_nodes=8, n_pods=40, gang_size=4),
        dict(n_nodes=12, n_pods=60, gang_size=3, n_queues=3,
             queue_weights=(1, 2, 4)),
        dict(n_nodes=6, n_pods=30, gang_size=5, zones=2,
             affinity_fraction=0.2, anti_affinity_fraction=0.1,
             spread_fraction=0.2),
    ],
)
def test_fast_matches_object_path(seed, kwargs):
    a = _run(synthetic_cluster(seed=seed, **kwargs), fast=False)
    b = _run(synthetic_cluster(seed=seed, **kwargs), fast=True)
    binds_a, phases_a = _state(a)
    binds_b, phases_b = _state(b)
    assert binds_b == binds_a
    assert phases_b == phases_a


def test_fast_path_used(monkeypatch):
    """The eligible default conf actually takes the fast path."""
    import volcano_tpu.fastpath as fp

    called = {}
    orig = fp.FastCycle.run

    def spy(self):
        called["yes"] = True
        return orig(self)

    monkeypatch.setattr(fp.FastCycle, "run", spy)
    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    Scheduler(store, conf_str=CONF).run_once()
    assert called.get("yes")


def test_object_model_rebuild_after_fast_cycle():
    store = synthetic_cluster(n_nodes=4, n_pods=12, gang_size=3)
    Scheduler(store, conf_str=CONF).run_once()
    # Accessing the object model after a fast commit rebuilds it from pods.
    total_bound = sum(
        1 for p in store.pods.values() if p.node_name
    )
    assert total_bound == len(store.binder.binds)
    node_tasks = sum(len(n.tasks) for n in store.nodes.values())
    assert node_tasks == total_bound
    # Node accounting balances.
    for node in store.nodes.values():
        assert node.idle.milli_cpu >= -1e-6


def test_chunked_solve_matches_unchunked(monkeypatch):
    """Forcing a tiny affinity budget splits the solve into job-aligned
    chunks with commits in between; the set of binds must match the
    single-call solve (later chunks seeing earlier placements is the
    sequential reference's own semantics)."""
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    kw = dict(n_nodes=16, n_pods=96, gang_size=4, zones=4,
              affinity_fraction=0.2, anti_affinity_fraction=0.1,
              spread_fraction=0.2, seed=3)
    a = synthetic_cluster(**kw)
    Scheduler(a).run_once()
    monkeypatch.setenv("VOLCANO_TPU_AFF_BUDGET_MB", "0.0001")
    b = synthetic_cluster(**kw)
    Scheduler(b).run_once()
    assert len(b.binder.binds) == len(a.binder.binds)
    assert set(b.binder.binds) == set(a.binder.binds)


def test_bind_failure_resyncs_tasks_to_pending():
    """A binder reporting partial failure (BindFailure) reverts exactly
    the failed tasks to Pending — the errTasks resync semantics
    (cache.go:627-649) — and the next cycle retries them."""
    from volcano_tpu.cache.interface import BindFailure
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=8, n_pods=24, gang_size=1)
    orig_bind_keys = store.binder.bind_keys
    state = {"fail_once": True}

    def flaky_bind_keys(keys, hosts):
        if state["fail_once"]:
            state["fail_once"] = False
            ok = [(k, h) for k, h in zip(keys, hosts)][: len(keys) // 2]
            orig_bind_keys([k for k, _ in ok], [h for _, h in ok])
            raise BindFailure([k for k in keys[len(keys) // 2:]])
        orig_bind_keys(keys, hosts)

    store.binder.bind_keys = flaky_bind_keys
    sched = Scheduler(store)
    sched.run_once()
    bound_1 = len(store.binder.binds)
    assert bound_1 == 12
    # Failed tasks are Pending again, not phantom-bound.
    pending = [p for p in store.pods.values() if p.node_name is None]
    assert len(pending) == 12
    # Next cycle rebinds them.
    sched.run_once()
    assert len(store.binder.binds) == 24
    assert all(p.node_name for p in store.pods.values())


def test_enqueue_phase_transition_persisted_despite_writeback_skip():
    """The close write-back skips unchanged PodGroups, but enqueue's
    in-place Pending -> Inqueue mutation must still persist + notify
    (the status updater is the API-server boundary)."""
    from volcano_tpu.api import Node, PodGroup
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.scheduler import Scheduler

    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "4",
                                                "memory": "8Gi"}))
    store.add_pod_group(PodGroup(name="g", min_member=1,
                                 min_resources={"cpu": "1"}))
    phases = []
    orig = store.status_updater.update_pod_group
    store.status_updater.update_pod_group = (
        lambda pg: (phases.append(pg.status.phase), orig(pg))[1]
    )
    Scheduler(store).run_once()
    assert "Inqueue" in phases, f"Inqueue not persisted: {phases}"


def test_enqueue_transition_survives_failed_cycle(monkeypatch):
    """A cycle that fails AFTER enqueue's in-place Inqueue mutation must
    not strand the transition: the next successful cycle still persists
    it (the dirty set lives on the store, cleared only after a
    successful write-back)."""
    import volcano_tpu.fastpath as fp
    from volcano_tpu.api import Node, PodGroup
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.scheduler import Scheduler

    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "4",
                                                "memory": "8Gi"}))
    store.add_pod_group(PodGroup(name="g", min_member=1,
                                 min_resources={"cpu": "1"}))
    phases = []
    orig_update = store.status_updater.update_pod_group
    store.status_updater.update_pod_group = (
        lambda pg: (phases.append(pg.status.phase), orig_update(pg))[1]
    )
    orig_alloc = fp.FastCycle._allocate
    calls = {"n": 0}

    def failing_alloc(self):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("device failure after enqueue")
        return orig_alloc(self)

    monkeypatch.setattr(fp.FastCycle, "_allocate", failing_alloc)
    # This test exercises the production fallback path by design.
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "auto")
    sched = Scheduler(store)
    sched.run_once()  # fast cycle fails post-enqueue; object path covers
    phases.clear()
    # Force the interesting path: a later FAST cycle must persist the
    # still-pending transition even though the phase compares equal.
    store._phase_dirty_uids.add("default/g")
    sched.run_once()
    assert "Inqueue" in phases or "Running" in phases, (
        f"stranded transition never persisted: {phases}"
    )
    assert not store._phase_dirty_uids


def test_enqueue_accept_all_eps_boundary_falls_back_to_walk():
    """When pending groups' MinResources total exactly consumes the
    overcommitted idle budget, the sequential walk (enqueue.go:98-101)
    accepts groups until idle goes empty and rejects everything after —
    including MinResources-nil groups that charge nothing.  The
    accept-all shortcut must not diverge at this eps boundary (it
    requires a non-empty residual before accepting, else falls through
    to the walk)."""
    from volcano_tpu.api import Node, PodGroup
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.scheduler import Scheduler

    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "10",
                                                "memory": "10Gi"}))
    # "a" consumes the whole 1.2x-overcommitted idle (12 cpu / 12Gi).
    store.add_pod_group(PodGroup(name="a", min_member=1,
                                 min_resources={"cpu": "12",
                                                "memory": "12Gi"}))
    store.add_pod_group(PodGroup(name="b", min_member=1))
    Scheduler(store).run_once()
    phases = {pg.name: pg.status.phase
              for pg in store.pod_groups.values()}
    assert phases["a"] == "Inqueue"
    # The walk broke once idle went empty, so "b" never got examined.
    assert phases["b"] == "Pending", phases


def test_fastpath_volume_gate_and_revert():
    """Fast-path commit runs claims through the volume binder before the
    pod bind dispatches: an existing claim binds with the pod; a missing
    claim reverts exactly that pod to Pending (statement.go allocate->
    AllocateVolumes, commit->BindVolumes semantics)."""
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.scheduler import Scheduler

    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "8",
                                                "memory": "16Gi"}))
    store.put_pvc("default", "good-claim", {"storage": "1Gi"})
    store.add_pod_group(PodGroup(name="g", min_member=1))
    store.add_pod_group(PodGroup(name="h", min_member=1))
    store.add_pod(Pod(
        name="with-claim",
        containers=[{"cpu": "1", "memory": "1Gi"}],
        annotations={GROUP_NAME_ANNOTATION: "g"},
        volumes=[("good-claim", "/data")],
    ))
    store.add_pod(Pod(
        name="no-claim",
        containers=[{"cpu": "1", "memory": "1Gi"}],
        annotations={GROUP_NAME_ANNOTATION: "h"},
        volumes=[("vanished", "/data")],
    ))
    Scheduler(store).run_once()

    by_name = {p.name: p for p in store.pods.values()}
    assert by_name["with-claim"].node_name == "n0"
    assert store.pvcs["default/good-claim"]["phase"] == "Bound"
    assert store.pvcs["default/good-claim"]["node"] == "n0"
    # The claimless pod reverted: not bound, not dispatched to the binder.
    assert by_name["no-claim"].node_name is None
    assert "default/no-claim" not in store.binder.binds
    evs = store.events_for("Pod/default/no-claim")
    assert any(e["reason"] == "FailedScheduling"
               and "vanished" in e["message"] for e in evs)
    # Node accounting reverted with it: only one pod's worth used.
    ni = store.nodes["n0"]
    assert int(ni.used.milli_cpu) == 1000


def test_cycle_lane_breakdown_published():
    """Each fast cycle publishes its per-lane wall-clock split on its
    flight record (CycleRecord.lanes) — what the benchmark's lane
    reader and /debug/cycles show."""
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=8, n_pods=24, gang_size=2)
    Scheduler(store).run_once()
    lanes = store.flight.recent()[-1].lanes
    for key in ("derive", "order", "encode", "device", "commit",
                "close", "enqueue"):
        assert key in lanes and lanes[key] >= 0.0, (key, lanes)
    # Sanity: lanes are a breakdown, not garbage — each under a minute.
    assert all(v < 60 for v in lanes.values())
