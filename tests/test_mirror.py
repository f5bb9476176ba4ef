"""Mirror maintenance: compaction, dynamic updates, fallback eligibility."""

import os

import numpy as np

from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster


def test_compaction_preserves_scheduling():
    """Deleting >half the pod table triggers compaction; scheduling after
    compaction matches a fresh store with the same surviving state."""
    store = ClusterStore()
    for i in range(4):
        store.add_node(Node(name=f"n{i}",
                            allocatable={"cpu": "8", "memory": "16Gi"}))
    # Churn: add and delete enough pods to cross the compaction threshold.
    dead = []
    for i in range(5000):
        p = Pod(name=f"tmp-{i}", containers=[{"cpu": "100m",
                                              "memory": "64Mi"}])
        store.add_pod(p)
        dead.append(p)
    for p in dead:
        store.delete_pod(p)
    assert store.mirror.n_dead == 0 or store.mirror.n_pods < 5000
    # Survivors scheduled after compaction.
    store.add_pod_group(PodGroup(name="g", min_member=3))
    for i in range(3):
        store.add_pod(Pod(name=f"w{i}",
                          containers=[{"cpu": "1", "memory": "1Gi"}],
                          annotations={GROUP_NAME_ANNOTATION: "g"}))
    Scheduler(store).run_once()
    assert len(store.binder.binds) == 3


def test_custom_plugin_conf_falls_back_to_object_path():
    """Non-built-in plugin names make the fast path ineligible; the object
    session handles the cycle and still binds."""
    conf = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: gang
  - name: priority
  - name: conformance
"""
    # Sanity: this conf IS eligible; now an unknown plugin is not.
    conf_custom = conf + "  - name: my-custom-plugin\n"
    import volcano_tpu.fastpath as fp
    from volcano_tpu.framework import parse_scheduler_conf

    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    assert fp.FastCycle(store, parse_scheduler_conf(conf)).eligible()
    parsed = parse_scheduler_conf(conf_custom)
    assert not fp.FastCycle(store, parsed).eligible()
    Scheduler(store, conf_str=conf_custom).run_once()
    assert len(store.binder.binds) == 8


def test_mirror_tracks_bind_and_evict_status():
    from volcano_tpu.api import TaskStatus

    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    Scheduler(store).run_once()
    m = store.mirror
    bound_rows = np.flatnonzero(
        m.p_status[:m.n_pods] == int(TaskStatus.Bound)
    )
    assert len(bound_rows) == 8
    # Evict one pod through the store; mirror follows.
    pod = next(iter(store.pods.values()))
    ti = store.jobs[pod.job_id()].tasks[pod.uid]
    store.evict(ti, "test")
    row = m.p_row[pod.uid]
    assert m.p_status[row] == int(TaskStatus.Releasing)


def test_checkpoint_then_schedule_more(tmp_path):
    """A restored store keeps scheduling new work (mirror rebuilt via the
    event API replay)."""
    from volcano_tpu.persistence import load_store, save_store

    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    Scheduler(store).run_once()
    path = str(tmp_path / "ckpt")
    save_store(store, path)
    b = load_store(path)
    b.add_pod_group(PodGroup(name="late", min_member=2))
    for i in range(2):
        b.add_pod(Pod(name=f"late-{i}",
                      containers=[{"cpu": "1", "memory": "1Gi"}],
                      annotations={GROUP_NAME_ANNOTATION: "late"}))
    Scheduler(b).run_once()
    assert any(k.endswith("late-0") for k in b.binder.binds)


def test_object_path_status_writes_refresh_mirror_columns():
    """update_job_status / record_job_condition (the object session's
    write-back) must re-sync the mirror's persistent j_phase_code /
    j_st_* / j_cond_sig columns, or the fast path's change detection
    works off stale 'last written' state after a slow-path cycle."""
    from volcano_tpu.api import PodGroup, PodGroupCondition
    from volcano_tpu.cache import ClusterStore

    store = ClusterStore()
    pg = PodGroup(name="g", min_member=2)
    store.add_pod_group(pg)
    m = store.mirror
    row = m.j_row[pg.uid]
    assert m.j_phase_code[row] == 1  # Pending

    # Object-path write-back: phase + counters via update_job_status.
    snap = store.snapshot()
    job = snap.jobs[pg.uid]
    job.pod_group.status.phase = "Running"
    job.pod_group.status.running = 2
    store.update_job_status(job)
    assert m.j_phase_code[row] == 3
    assert m.j_st_run[row] == 2

    # Condition write via record_job_condition refreshes the signature.
    cond = PodGroupCondition(
        type="Unschedulable", status="True", transition_id="t",
        reason="NotEnoughResources", message="0/2 ready",
    )
    store.record_job_condition(job, cond)
    assert m.j_cond_sig[row] == (
        hash(("NotEnoughResources", "0/2 ready")) & 0x7FFFFFFFFFFFFFFF
    )


def test_job_uid_rank_extends_its_uid_array_and_stays_the_full_sort():
    """The rank is a strict monotone map of the uid strings whether the
    string array was made in one go or extended round by round, with
    uids of growing width, and across a pod-table compaction (which
    carries the job table, and the cached array, over)."""
    store = ClusterStore()
    m = store.mirror
    assert len(m.job_uid_rank()) == 0
    rng = np.random.default_rng(5)

    def check():
        rank = m.job_uid_rank()
        uids = list(m.j_uid)
        want = np.empty(len(uids), np.int64)
        want[np.argsort(np.array(uids), kind="stable")] = np.arange(len(uids))
        np.testing.assert_array_equal(rank, want)
        assert [uids[i] for i in np.argsort(rank)] == sorted(uids)

    for step in range(12):
        for _ in range(int(rng.integers(1, 40))):
            name = "g" + "x" * step + str(int(rng.integers(0, 10 ** 6)))
            store.add_pod_group(PodGroup(name=name, min_member=1))
        check()
        assert len(m._j_uid_arr) == len(m.j_uid)
    first = m._j_uid_arr
    pods = [Pod(name=f"tmp-{i}", containers=[{"cpu": "100m"}])
            for i in range(4200)]
    for p in pods:
        store.add_pod(p)
    gen = m.compact_gen
    for p in pods:
        store.delete_pod(p)
    assert m.compact_gen > gen and m._j_uid_arr is first
    store.add_pod_group(PodGroup(name="after", min_member=1))
    check()
