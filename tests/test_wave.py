"""Wave-batched solver: invariants and agreement with the sequential solver.

The wave solver (ops/wave.py) trades exact per-task ordering for batched
device work; these tests pin down what it must still guarantee:

- no node oversubscription (epsilon-aware),
- gang atomicity (committed jobs meet min_available; discarded jobs leave
  no allocations behind),
- full placement parity with the sequential solver on feasible workloads,
- determinism,
- per-feature paths (selectors, taints, queues/overuse gating, gangs too
  big to fit) behave like the sequential solver's.
"""

import jax
import numpy as np
import pytest

from volcano_tpu.api import Node, Pod, PodGroup, Queue
from volcano_tpu.cache import ClusterStore
from volcano_tpu.ops.allocate import solve
from volcano_tpu.ops.wave import solve_wave
from volcano_tpu.synth import solve_args_from_store, synthetic_cluster


def _placed(res):
    return int((np.asarray(res.assigned) >= 0).sum())


def _check_invariants(args, res):
    nodes, tasks, jobs = args[0], args[1], args[2]
    assigned = np.asarray(res.assigned)
    idle0 = np.asarray(nodes.idle)
    req = np.asarray(tasks.req)
    use = np.zeros_like(idle0)
    for i, n in enumerate(assigned):
        if n >= 0:
            use[n] += req[i]
    assert (use <= idle0 + 1e-3).all(), "node oversubscription"

    job = np.asarray(tasks.job)
    real = np.asarray(tasks.real)
    minav = np.asarray(jobs.min_available)
    rb = np.asarray(jobs.ready_base)
    counts = {}
    for i in range(len(assigned)):
        if real[i] and assigned[i] >= 0:
            counts[job[i]] = counts.get(job[i], 0) + 1
    for j, c in counts.items():
        assert rb[j] + c >= minav[j], (
            f"gang violated: job {j} committed {c} < min {minav[j]}"
        )
    never = np.asarray(res.never_ready)
    for i in range(len(assigned)):
        if real[i] and never[job[i]]:
            assert assigned[i] == -1, "discarded job left an allocation"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_wave_invariants_randomized(seed):
    rng = np.random.RandomState(seed)
    store = synthetic_cluster(
        n_nodes=int(rng.randint(16, 64)),
        n_pods=int(rng.randint(64, 256)),
        gang_size=int(rng.randint(1, 6)),
        n_queues=int(rng.randint(1, 3)),
        seed=seed,
    )
    args, _ = solve_args_from_store(store)
    res = solve_wave(*args, wave=64)
    _check_invariants(args, res)


def test_wave_full_placement_matches_sequential():
    """On a feasible workload both solvers place every task."""
    store = synthetic_cluster(n_nodes=64, n_pods=512, gang_size=4,
                              n_queues=2)
    args, _ = solve_args_from_store(store)
    seq = solve(*args)
    wav = solve_wave(*args, wave=128)
    assert _placed(seq) == _placed(wav) == 512
    # Total consumed capacity agrees.
    assert np.allclose(
        np.asarray(seq.idle).sum(), np.asarray(wav.idle).sum(), rtol=1e-4
    )


def test_wave_deterministic():
    store = synthetic_cluster(n_nodes=32, n_pods=128, gang_size=4)
    args, _ = solve_args_from_store(store)
    a = np.asarray(solve_wave(*args, wave=64).assigned)
    b = np.asarray(solve_wave(*args, wave=64).assigned)
    assert np.array_equal(a, b)


from volcano_tpu.synth import GROUP_NAME_ANNOTATION


def _one_node_store(cpu="8", mem="16Gi"):
    store = ClusterStore()
    store.add_node(
        Node(name="n0", allocatable={"cpu": cpu, "memory": mem})
    )
    return store


def _add_gang(store, name, replicas, min_member, cpu="1", mem="1Gi",
              node_selector=None):
    pg = PodGroup(name=name, min_member=min_member, queue="default")
    store.add_pod_group(pg)
    for k in range(replicas):
        store.add_pod(Pod(
            name=f"{name}-{k}",
            annotations={GROUP_NAME_ANNOTATION: name},
            containers=[{"cpu": cpu, "memory": mem}],
            node_selector=node_selector or {},
        ))


def test_wave_gang_discard_when_gang_cannot_fit():
    """A gang larger than the cluster commits nothing (stmt.Discard)."""
    store = _one_node_store(cpu="4")
    _add_gang(store, "big", replicas=8, min_member=8)
    args, _ = solve_args_from_store(store)
    res = solve_wave(*args, wave=8)
    assert _placed(res) == 0
    assert bool(np.asarray(res.never_ready).any())
    # Capacity fully restored by the rollback.
    assert np.allclose(np.asarray(res.idle), np.asarray(args[0].idle))


def test_wave_partial_gang_commits_at_min_available():
    """min_available below replicas commits the partial gang (gang.go)."""
    store = _one_node_store(cpu="4")
    _add_gang(store, "elastic", replicas=8, min_member=2)
    args, _ = solve_args_from_store(store)
    res = solve_wave(*args, wave=8)
    assert _placed(res) == 4  # node fits 4 of 8; 4 >= min_available=2
    assert not bool(np.asarray(res.never_ready).any())


def test_wave_node_selector_respected():
    store = ClusterStore()
    store.add_node(
        Node(name="bad", allocatable={"cpu": "64", "memory": "64Gi"})
    )
    store.add_node(
        Node(name="good", allocatable={"cpu": "64", "memory": "64Gi"},
             labels={"zone": "a"})
    )
    _add_gang(store, "pinned", replicas=2, min_member=2,
              node_selector={"zone": "a"})
    args, maps = solve_args_from_store(store)
    res = solve_wave(*args, wave=8)
    assigned = np.asarray(res.assigned)
    good = maps.node_index["good"]
    real = np.asarray(args[1].real)
    assert all(assigned[i] == good for i in range(len(real)) if real[i])


def test_wave_matches_sequential_on_heterogeneous_mix():
    """Mixed profiles, queues, and gang sizes: same totals as sequential."""
    store = synthetic_cluster(n_nodes=48, n_pods=384, gang_size=3,
                              n_queues=3, seed=7)
    args, _ = solve_args_from_store(store)
    seq = solve(*args)
    wav = solve_wave(*args, wave=96)
    _check_invariants(args, wav)
    assert _placed(wav) == _placed(seq)


def test_sparse_cnt0_path_matches_dense(monkeypatch):
    """Forcing the sparse on-device cnt0 scatter (the hyperscale upload
    avoidance) must produce the same schedule as the dense upload,
    including resident counts and task-axis padding truncation."""
    import volcano_tpu.ops.wave as wave
    from volcano_tpu.api import Node, Pod, PodGroup, GROUP_NAME_ANNOTATION
    from volcano_tpu.api.spec import AffinityTerm
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.synth import solve_args_from_store

    def build():
        store = ClusterStore()
        for z in range(2):
            for i in range(3):
                store.add_node(Node(
                    name=f"z{z}-n{i}",
                    allocatable={"cpu": "8", "memory": "16Gi", "pods": 32},
                    labels={"zone": f"z{z}"},
                ))
        # Resident pod matching the term -> nonzero cnt0 entry.
        store.add_pod_group(PodGroup(name="res", min_member=1))
        res = Pod(name="res-0", labels={"app": "db"},
                  containers=[{"cpu": "1", "memory": "1Gi"}],
                  annotations={GROUP_NAME_ANNOTATION: "res"},
                  node_name="z1-n0", phase="Running")
        store.add_pod(res)
        term = AffinityTerm(match_labels={"app": "db"},
                            topology_key="zone")
        store.add_pod_group(PodGroup(name="g", min_member=3))
        for k in range(3):
            store.add_pod(Pod(
                name=f"g-{k}", labels={"app": "db"},
                containers=[{"cpu": "1", "memory": "1Gi"}],
                annotations={GROUP_NAME_ANNOTATION: "g"},
                affinity=[term],
            ))
        return store

    args, _ = solve_args_from_store(build())
    dense = np.asarray(wave.solve_wave(*args).assigned)
    monkeypatch.setattr(wave, "CNT0_SPARSE_MIN", 0)
    args2, _ = solve_args_from_store(build())
    sparse = np.asarray(wave.solve_wave(*args2).assigned)
    assert np.array_equal(dense, sparse)
    assert (sparse >= 0).sum() == 3
    # A table that arrives committed to ONE device (no mesh behind its
    # sharding) is rebuilt where it was placed.
    args3 = list(solve_args_from_store(build())[0])
    args3[7] = args3[7]._replace(cnt0=jax.device_put(np.asarray(args3[7].cnt0)))
    placed = np.asarray(wave.solve_wave(*args3).assigned)
    assert np.array_equal(dense, placed)


def test_sparse_profile_tables_match_dense(monkeypatch):
    """Forcing the sparse profile-term shipping path (PROF_SPARSE_MIN=0)
    must produce identical placements to the dense path — guards the
    flag bit-packing and the device-side scatter rebuild."""
    import volcano_tpu.ops.wave as wave
    from volcano_tpu.api import (
        GROUP_NAME_ANNOTATION,
        AffinityTerm,
        Node,
        Pod,
        PodGroup,
    )
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.synth import solve_args_from_store

    def build():
        store = ClusterStore()
        for z in ("z1", "z2"):
            for i in range(2):
                store.add_node(Node(
                    name=f"{z}-n{i}",
                    allocatable={"cpu": "8", "memory": "16Gi"},
                    labels={"zone": z},
                ))
        res = Pod(name="seed", labels={"app": "db"},
                  containers=[{"cpu": "1", "memory": "1Gi"}],
                  node_name="z1-n0", phase="Running")
        store.add_pod(res)
        aff_term = AffinityTerm(match_labels={"app": "db"},
                                topology_key="zone")
        anti_term = AffinityTerm(match_labels={"app": "lonely"},
                                 topology_key="kubernetes.io/hostname")
        store.add_pod_group(PodGroup(name="g", min_member=3))
        for k in range(3):
            store.add_pod(Pod(
                name=f"g-{k}", labels={"app": "db"},
                containers=[{"cpu": "1", "memory": "1Gi"}],
                annotations={GROUP_NAME_ANNOTATION: "g"},
                affinity=[aff_term],
            ))
        store.add_pod_group(PodGroup(name="solo", min_member=2))
        for k in range(2):
            store.add_pod(Pod(
                name=f"solo-{k}", labels={"app": "lonely"},
                containers=[{"cpu": "1", "memory": "1Gi"}],
                annotations={GROUP_NAME_ANNOTATION: "solo"},
                anti_affinity=[anti_term],
            ))
        return store

    args, _ = solve_args_from_store(build())
    dense = np.asarray(wave.solve_wave(*args).assigned)
    monkeypatch.setattr(wave, "PROF_SPARSE_MIN", 0)
    args2, _ = solve_args_from_store(build())
    sparse = np.asarray(wave.solve_wave(*args2).assigned)
    assert np.array_equal(dense, sparse)
    assert (sparse >= 0).sum() == 5  # the 3 aff + 2 anti pending pods
