"""Checkpoint/restore fidelity beyond the basics.

test_ha_persistence.py covers schedule-identical restores, claims,
policies, and leader election; these tests pin the remaining contract:
mid-flight batch jobs resume without duplicated side effects, commands
survive, saves are atomic under concurrent churn, and failure modes
(version mismatch, corrupt file) are loud.
"""

import copy
import pickle
import threading

import pytest

from volcano_tpu.api import (GROUP_NAME_ANNOTATION, Node, Pod, PodGroup,
                             PodGroupPhase, TaskInfo)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.controllers import ControllerManager, Job, TaskSpec
from volcano_tpu.controllers.apis import Command, VolumeSpec
from volcano_tpu.persistence import FORMAT_VERSION, load_store, save_store
from volcano_tpu.scheduler import Scheduler


def running_job_store():
    """A job initiated, admitted, with pods created and bound — the
    mid-flight state a restart must resume from."""
    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "16",
                                                "memory": "32Gi",
                                                "pods": 110}))
    cm = ControllerManager(store)
    job = Job(name="j1", min_available=2,
              tasks=[TaskSpec(name="w", replicas=2,
                              containers=[{"cpu": "1", "memory": "1Gi"}])],
              volumes=[VolumeSpec(mount_path="/d",
                                  volume_claim={"storage": "1Gi"})])
    store.add_batch_job(job)
    cm.process()
    pg = store.pod_groups["default/j1"]
    pg.status.phase = PodGroupPhase.Inqueue.value
    store.update_pod_group(pg)
    store._notify("PodGroup", "status", pg)
    cm.process()
    Scheduler(store).run_once()
    return store, cm, job


def test_midflight_job_resumes_without_duplicate_side_effects(tmp_path):
    store, _cm, job = running_job_store()
    path = str(tmp_path / "ckpt.bin")
    save_store(store, path)
    restored = load_store(path)
    cm2 = ControllerManager(restored)
    job2 = restored.batch_jobs["default/j1"]
    # Status machinery state survived.
    assert job2.status.controlled_resources == job.status.controlled_resources
    assert job2.finalizers == job.finalizers
    n_pvcs = len(restored.pvcs)
    n_pods = len(restored.pods)
    # Reconciling the restored store is a no-op: no duplicate pods,
    # claims, or PodGroups (plugin markers + existing records gate it).
    cm2.process()
    cm2.process()
    assert len(restored.pvcs) == n_pvcs
    assert len(restored.pods) == n_pods
    assert list(restored.pod_groups) == ["default/j1"]
    # And scheduling the restored store reaches the same placements.
    Scheduler(restored).run_once()
    bound = {p.name: p.node_name for p in restored.pods.values()}
    orig = {p.name: p.node_name for p in store.pods.values()}
    assert bound == orig


def test_commands_survive_restart(tmp_path):
    store = ClusterStore()
    store.add_command(Command(action="AbortJob", target_kind="Job",
                              target_name="j9", name="pending-cmd"))
    path = str(tmp_path / "ckpt.bin")
    save_store(store, path)
    restored = load_store(path)
    assert "pending-cmd" in restored.commands
    assert restored.commands["pending-cmd"].action == "AbortJob"


def test_save_is_atomic_under_concurrent_churn(tmp_path):
    """Saves taken while another thread churns pods always load to a
    consistent snapshot (the payload is serialized under the store
    lock; the file write is tmp+rename)."""
    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "64",
                                                "memory": "128Gi",
                                                "pods": 256}))
    store.add_pod_group(PodGroup(name="g", min_member=1))
    stop = threading.Event()
    errors = []

    def churn():
        i = 0
        try:
            while not stop.is_set() and i < 500:
                i += 1
                pod = Pod(name=f"p-{i}",
                          annotations={GROUP_NAME_ANNOTATION: "g"},
                          containers=[{"cpu": "1", "memory": "1Gi"}])
                store.add_pod(pod)
                if i % 2 == 0:
                    store.delete_pod(pod)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    t = threading.Thread(target=churn)
    t.start()
    try:
        for k in range(10):
            path = str(tmp_path / f"ckpt-{k}.bin")
            save_store(store, path)
            restored = load_store(path)
            # Consistency: every restored pod round-trips through the
            # event API and lands in the mirror at its indexed row.
            for pod in restored.pods.values():
                row = restored.mirror.p_row[pod.uid]
                assert restored.mirror.p_pod[row] is pod
    finally:
        stop.set()
        t.join(timeout=10)
    assert not errors


def test_version_mismatch_raises(tmp_path):
    store = ClusterStore()
    path = str(tmp_path / "ckpt.bin")
    save_store(store, path)
    blob = pickle.load(open(path, "rb"))
    blob["version"] = FORMAT_VERSION + 999
    with open(path, "wb") as f:
        pickle.dump(blob, f)
    with pytest.raises(ValueError, match="unsupported checkpoint"):
        load_store(path)


def test_corrupt_checkpoint_raises_loudly(tmp_path):
    path = str(tmp_path / "ckpt.bin")
    with open(path, "wb") as f:
        f.write(b"\x80\x04 garbage that is not a pickle")
    with pytest.raises(Exception):
        load_store(path)


def test_no_temp_files_left_behind(tmp_path):
    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "1",
                                                "memory": "1Gi"}))
    for k in range(5):
        save_store(store, str(tmp_path / "ckpt.bin"))
    leftovers = [p.name for p in tmp_path.iterdir()
                 if p.name.startswith(".vctpu-ckpt-")]
    assert leftovers == []


# --------------------------------- one record a pod spec (ISSUE 46) and both

def _spec_pods(n):
    return [Pod(name=f"s{i}", uid=f"s{i}",
                annotations={GROUP_NAME_ANNOTATION: "pg"},
                containers=[{"cpu": "500m", "memory": "1Gi"},
                            {"cpu": "1", "example.com/gpu": 2}],
                init_containers=[{"cpu": "3"}])
            for i in range(n)]


@pytest.mark.parametrize("which", [1, 2, 5])
def test_a_pod_handed_its_specs_record_answers_with_the_parsed_request(which):
    """The pods after a spec's first parse nothing, and still hand a
    ``TaskInfo`` the request a parse would give, as a copy of its own:
    the record's ``Resource`` is every such pod's."""
    store = ClusterStore()
    store.add_pod_group(PodGroup(name="pg", min_member=1))
    pods = _spec_pods(6)
    for pod in pods:
        store.add_pod(pod)
    assert store._between.specs_encoded == 1
    parsed = TaskInfo(_spec_pods(1)[0])         # never met a store
    feat = store.mirror.p_feat[store.mirror.p_row[pods[which].uid]]
    assert feat is store.mirror.p_feat[store.mirror.p_row[pods[0].uid]]
    assert pods[which].resource_request() is feat.req_res
    assert pods[which].init_resource_request() is feat.init_res
    ti = TaskInfo(pods[which])
    assert ti.resreq == parsed.resreq and ti.init_resreq == parsed.init_resreq
    assert (ti.resreq.milli_cpu, ti.resreq.memory, ti.resreq.scalars) == (
        1500.0, float(2 ** 30), {"example.com/gpu": 2000.0})
    assert ti.init_resreq.milli_cpu == 3000.0
    assert ti.resreq is not feat.req_res and ti.init_resreq is not feat.init_res
    ti.resreq.add(ti.resreq)                    # a task's own to change
    assert TaskInfo(pods[0]).resreq == parsed.resreq
    assert store.jobs["default/pg"].tasks[pods[which].uid].resreq == parsed.resreq


def test_a_restored_store_encodes_again_and_shares_no_record(tmp_path):
    store = ClusterStore()
    store.add_pod_group(PodGroup(name="pg", min_member=1))
    for pod in _spec_pods(5):
        store.add_pod(pod)
    path = str(tmp_path / "ckpt.bin")
    save_store(store, path)
    assert all(not hasattr(pod, "_mirror_feat") and
               not hasattr(pod, "_req_cache")
               for pod in pickle.load(open(path, "rb"))["pods"])
    restored = load_store(path)
    assert restored._between.specs_encoded == 1
    assert len(restored.mirror._spec_memo) == 1
    mine = {id(f) for f in store.mirror.p_feat}
    theirs = restored.mirror.p_feat
    assert len({id(f) for f in theirs}) == 1 and id(theirs[0]) not in mine
    assert theirs[0].profiles is restored.mirror.profiles
    assert theirs[0].req_res is not store.mirror.p_feat[0].req_res
    # A record is one mirror's: a pod that brings another store's along
    # (a copy, caches and all) is encoded by the store it is added to.
    other = ClusterStore()
    other.add_pod(Pod(name="first", containers=[{"cpu": "7"}]))
    guest = copy.copy(store.pods["s0"])
    other.add_pod(guest)
    assert guest._mirror_feat.profiles is other.mirror.profiles
    assert other.mirror.p_prof[other.mirror.p_row["s0"]] == 1
