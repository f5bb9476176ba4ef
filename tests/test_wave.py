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
    # whole waves come back: past the caller's rows is padding, never bound
    rows = np.asarray(tasks.real).shape[0]
    assigned = np.asarray(res.assigned)
    assert (assigned[rows:] == -1).all()
    assigned = assigned[:rows]
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


# ---- the hand-off of the inter-pod term data: entries against tables --------


def _term_snapshot(mesh=None):
    """A fast-path encode over required affinity, required anti-affinity,
    a soft spread whose (profile, term) pair recurs, plain gangs and
    residents that match two of the terms: ``(inputs, pid, profiles,
    node_classes, taint_any)`` as ``FastCycle._solve_inputs`` hands them
    to ``solve_wave``."""
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, AffinityTerm
    from volcano_tpu.fastpath import FastCycle
    from volcano_tpu.framework import parse_scheduler_conf

    store = ClusterStore()
    for z in range(4):
        for i in range(4):
            store.add_node(Node(
                name=f"z{z}-n{i}",
                allocatable={"cpu": "8", "memory": "16Gi", "pods": 32},
                labels={"zone": f"z{z}"},
            ))

    def gang(name, n, labels, phase="Inqueue", node=None, **terms):
        pg = PodGroup(name=name, min_member=n)
        pg.status.phase = phase
        store.add_pod_group(pg)
        for k in range(n):
            store.add_pod(Pod(
                name=f"{name}-{k}", labels=labels,
                containers=[{"cpu": "1", "memory": "1Gi"}],
                annotations={GROUP_NAME_ANNOTATION: name},
                **({"node_name": node, "phase": "Running"} if node else {}),
                **terms))

    db = AffinityTerm(match_labels={"app": "db"}, topology_key="zone")
    lonely = AffinityTerm(match_labels={"app": "lonely"},
                          topology_key="kubernetes.io/hostname")
    gang("res-db", 2, {"app": "db"}, phase="Running", node="z1-n0")
    gang("res-lonely", 1, {"app": "lonely"}, phase="Running", node="z2-n3")
    gang("aff", 3, {"app": "db"}, affinity=[db])
    gang("anti", 4, {"app": "lonely"}, anti_affinity=[lonely])
    gang("spread", 4, {"app": "web"},
         topology_spread=[("zone", 10), ("zone", 5)])
    for g in range(3):
        gang(f"plain{g}", 4, {"app": f"p{g}"})
    store.solve_mesh = mesh
    cyc = FastCycle(store, parse_scheduler_conf(
        "actions: allocate\ntiers:\n- plugins:\n  - name: gang\n"
        "  - name: predicates\n  - name: nodeorder\n"))
    with store._lock:
        cyc.derive()
        cyc._proportion()
        solve_jobs, task_rows = cyc._pending_rows(cyc._ordered_jobs())
        inputs, pid, profiles, ncls = cyc._solve_inputs(
            solve_jobs, task_rows, slim=True)
    return inputs, pid, profiles, ncls, cyc._taint_any


def _as_tables(inputs, profiles):
    """The dense hand-off of the same data, built here cell by cell: the
    ``[U, Ep]`` profile-term tables and the ``[Ep, D]`` count table."""
    from volcano_tpu.ops.wave import SolveProfiles

    t = profiles.terms
    tabs = [np.zeros(t.shape, bool) for _ in range(3)]
    soft = np.zeros(t.shape, np.float32)
    for u, e, f, w in zip(t.rows, t.cols, t.flags, t.soft):
        for bit in range(3):
            tabs[bit][u, e] = bool((f >> bit) & 1)
        soft[u, e] = w
    aff = inputs[7]
    cnt0 = np.zeros(aff.cnt0.shape, np.int32)
    for e, d, v in zip(aff.cnt0.rows, aff.cnt0.cols, aff.cnt0.vals):
        cnt0[e, d] = v
    return ((*inputs[:7], aff._replace(cnt0=cnt0), *inputs[8:]),
            SolveProfiles(*profiles[:9], *tabs, soft))


@pytest.mark.parametrize("wave_size", [2048, 8], ids=["one-wave", "waves-of-8"])
@pytest.mark.parametrize("placement", ["one-device", "mesh-4"])
@pytest.mark.parametrize("thresholds", ["device-scatter", "dense-upload"])
def test_term_entries_hand_off_matches_the_dense_one(
        monkeypatch, thresholds, placement, wave_size):
    """The fast path hands ``solve_wave`` the profile-term tables and the
    resident counts as entries; the same snapshot handed over as dense
    tables gives the same windows and the same result, element for
    element, whether the tables are then born on the device (thresholds
    lowered) or densified and uploaded, on one device and on a mesh."""
    import volcano_tpu.ops.wave as wave
    from volcano_tpu.arrays.affinity import CountEntries
    from volcano_tpu.parallel.mesh import make_mesh, sharded_solve_wave_cycle

    if placement == "mesh-4" and len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    if thresholds == "device-scatter":
        monkeypatch.setattr(wave, "CNT0_SPARSE_MIN", 0)
        monkeypatch.setattr(wave, "PROF_SPARSE_MIN", 0)
    mesh = make_mesh(4) if placement == "mesh-4" else None
    inputs, pid, profiles, ncls, taint_any = _term_snapshot(mesh)
    terms, cnt = profiles.terms, inputs[7].cnt0
    assert isinstance(profiles, wave.SparseProfiles)
    assert isinstance(cnt, CountEntries)
    # What the snapshot was built to hold: required terms of both kinds,
    # the spread's recurring pair summed into one entry, and residents.
    assert (terms.flags & 1).any() and (terms.flags & 2).any()
    assert sorted(terms.soft[terms.soft != 0].tolist()) == [-15.0]
    assert cnt.vals.tolist() == [2, 1]
    order = np.lexsort((terms.cols, terms.rows))
    assert np.array_equal(order, np.arange(len(order)))

    windows = []
    orig = wave._term_windows

    def spy(*a, **k):
        windows.append(orig(*a, **k))
        return windows[-1]

    monkeypatch.setattr(wave, "_term_windows", spy)

    def solve(args, profs):
        if mesh is not None:
            res = sharded_solve_wave_cycle(
                mesh, args, pid, profs, wave=wave_size,
                taint_any=taint_any, node_classes=ncls)
        else:
            res = wave.solve_wave(
                *args, pid=pid, profiles=profs, wave=wave_size,
                taint_any=taint_any, node_classes=ncls)
        return res, dict(wave.LAST_TWOPHASE["terms"])

    by_entries, told_e = solve(inputs, profiles)
    by_tables, told_t = solve(*_as_tables(inputs, profiles))
    for field in ("assigned", "never_ready", "fit_failed", "fb_affinity",
                  "fb_exhausted"):
        assert np.array_equal(np.asarray(getattr(by_entries, field)),
                              np.asarray(getattr(by_tables, field))), field
    assert (np.asarray(by_entries.assigned) >= 0).sum() == 23
    (wt_e, ew_e, dis_e), (wt_t, ew_t, dis_t) = windows
    assert np.array_equal(wt_e, wt_t) and ew_e == ew_t and dis_e == dis_t
    assert (wt_e < terms.shape[1]).any()
    # Both count the same entries; only the dense hand-off has dense
    # host tables to read, and only the dense upload builds any.
    for k in ("prof_entries", "cnt0_entries"):
        assert told_e[k] == told_t[k] > 0
    assert told_e["prof_entries"] == len(terms.rows)
    assert told_t["host_dense_bytes"] > told_e["host_dense_bytes"]
    assert (told_e["host_dense_bytes"] == 0) == (thresholds == "device-scatter")
