"""Device-lane incrementality (ISSUE 9): persistent static planes,
warm-started shortlists, and null-delta fast cycles.

The acceptance bar is BIT-FOR-BIT: with ``VOLCANO_TPU_DEVINCR=1``,
binds/phases/mirror state must equal the ``=0`` path across randomized
churn — including the mesh-sharded and remote-solver paths — and every
invalidation edge (class-set change, profile-set change, node-liveness
flip, compaction, dirty-cap overflow) must demonstrably fall back to a
full recompute.
"""

import itertools
import os
import random

import numpy as np
import pytest

from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    TaskStatus,
)
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

pytestmark = pytest.mark.tier1

ST_BOUND = int(TaskStatus.Bound)


def _reset_uid_counters():
    import volcano_tpu.api.spec as spec

    spec._uid_counter = itertools.count(1)
    spec._ts_counter = itertools.count(1)


def _partial_feed(node_rows):
    """Re-pend only rows bound to ``node_rows`` — a sparse steady-state
    dirty set, the warm path's home turf."""

    def feed(fc):
        m = fc.m
        rows = np.flatnonzero(
            (m.p_status[:fc.Pn] == ST_BOUND) & m.p_alive[:fc.Pn]
        )
        if len(rows):
            sel = rows[np.isin(m.p_node[rows], node_rows)]
            if len(sel):
                fc._unbind_rows(sel)

    return feed


def _mirror_state(store):
    m = store.mirror
    return tuple(
        (m.p_uid[r], int(m.p_status[r]), m.p_node_name[r])
        for r in range(m.n_pods) if m.p_uid[r] is not None
    )


def _churn(store, rng, step):
    """Randomized mutation batch (name-keyed — twin runs must see the
    identical op sequence)."""
    op = rng.choice(["add_gang", "delete_pod", "node_flap", "add_pods",
                     "nothing"])
    if op == "add_gang":
        name = f"churn-{step}"
        store.add_pod_group(PodGroup(name=name, min_member=2))
        for i in range(2):
            store.add_pod(Pod(
                name=f"{name}-{i}",
                annotations={GROUP_NAME_ANNOTATION: name},
                containers=[{"cpu": "1", "memory": "1Gi"}],
            ))
    elif op == "delete_pod":
        pods = sorted(store.pods.values(), key=lambda p: p.name)
        if pods:
            store.delete_pod(pods[rng.randrange(len(pods))])
    elif op == "node_flap":
        names = sorted(store.mirror.n_row)
        if names:
            name = names[rng.randrange(len(names))]
            if rng.random() < 0.5:
                store.delete_node(name)
            else:
                store.add_node(Node(
                    name=name,
                    allocatable={"cpu": "64", "memory": "256Gi",
                                 "pods": 256},
                ))
    elif op == "add_pods":
        name = f"solo-{step}"
        store.add_pod_group(PodGroup(name=name, min_member=1))
        store.add_pod(Pod(
            name=f"{name}-0",
            annotations={GROUP_NAME_ANNOTATION: name},
            containers=[{"cpu": "2", "memory": "2Gi"}],
        ))


def _twin_run(devincr: bool, monkeypatch, *, mesh=None, churn=True,
              cycles=10, seed=13, **cluster_kw):
    monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "1" if devincr else "0")
    _reset_uid_counters()
    kw = dict(n_nodes=24, n_pods=72, gang_size=4, seed=seed)
    kw.update(cluster_kw)
    store = synthetic_cluster(**kw)
    store.pipeline = True
    if mesh is not None:
        store.solve_mesh = mesh
    store.cycle_feed = _partial_feed([0, 1])
    sched = Scheduler(store)
    rng = random.Random(7)
    states = []
    for step in range(cycles):
        sched.run_once()
        states.append(_mirror_state(store))
        if churn and step % 2 == 1:
            _churn(store, rng, step)
    dv = getattr(store, "_devincr_cache", None)
    counts = dict(dv.counts) if dv is not None else {}
    store.flush_binds()
    binds = dict(store.binder.binds)
    phases = {uid: pg.status.phase
              for uid, pg in sorted(store.pod_groups.items())}
    store.close()
    return binds, phases, states, counts


def test_churn_parity_devincr_on_off(monkeypatch):
    """Randomized churn over a pipelined feed loop: binds, PodGroup
    phases, and the full per-cycle mirror-state sequence are bit-for-bit
    equal between incremental-on and DEVINCR=0 — and the on-run must
    actually take the warm path."""
    b1, p1, s1, c1 = _twin_run(True, monkeypatch)
    b0, p0, s0, c0 = _twin_run(False, monkeypatch)
    assert b1 == b0
    assert p1 == p0
    assert s1 == s0
    assert c1.get("warm", 0) >= 1, f"warm path never engaged: {c1}"
    assert c0 == {}, "DEVINCR=0 must not touch the lane"


def test_churn_parity_mesh_sharded(monkeypatch):
    """Same parity bar on the mesh path (virtual CPU mesh): the
    replicated devincr planes + warm kernel must not perturb the
    sharded solve."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    from volcano_tpu.parallel import make_mesh

    mesh = make_mesh(4)
    b1, p1, s1, c1 = _twin_run(True, monkeypatch, mesh=mesh, cycles=8,
                               n_nodes=16, n_pods=48)
    b0, p0, s0, c0 = _twin_run(False, monkeypatch, mesh=mesh, cycles=8,
                               n_nodes=16, n_pods=48)
    assert b1 == b0
    assert p1 == p0
    assert s1 == s0
    assert c1.get("warm", 0) >= 1, f"warm path never engaged: {c1}"


def test_affinity_churn_parity(monkeypatch):
    """Affinity workloads: the cnt0 content token invalidates warm
    reuse whenever resident term counts move, so parity must hold with
    inter-pod terms in play."""
    b1, p1, s1, c1 = _twin_run(
        True, monkeypatch, cycles=8, seed=5,
        affinity_fraction=0.3, anti_affinity_fraction=0.1,
        spread_fraction=0.2, zones=2,
    )
    b0, p0, s0, c0 = _twin_run(
        False, monkeypatch, cycles=8, seed=5,
        affinity_fraction=0.3, anti_affinity_fraction=0.1,
        spread_fraction=0.2, zones=2,
    )
    assert b1 == b0
    assert p1 == p0
    assert s1 == s0


# ------------------------------------------------- invalidation edges


def _steady_store(monkeypatch, n_nodes=16, n_pods=48):
    monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "1")
    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=n_nodes, n_pods=n_pods,
                              gang_size=4, seed=3)
    store.pipeline = True
    store.cycle_feed = _partial_feed([0])
    sched = Scheduler(store)
    # Warm the lane: fill + reach steady warm state.
    for _ in range(4):
        sched.run_once()
    dv = store._devincr_cache
    assert dv.last_mode == "warm", dv.counts
    return store, sched, dv


def _modes_after(sched, dv, n=2):
    modes = []
    for _ in range(n):
        sched.run_once()
        modes.append(dv.last_mode)
    return modes


def test_invalidation_node_relabel_falls_back(monkeypatch):
    """A node relabel changes the class-table signature (and epoch):
    the next solve must full-recompute, then warm again."""
    store, sched, dv = _steady_store(monkeypatch)
    store.add_node(Node(
        name=sorted(store.mirror.n_row)[2],
        allocatable={"cpu": "64", "memory": "256Gi", "pods": 256},
        labels={"relabelled": "yes"},
    ))
    modes = _modes_after(sched, dv, 3)
    assert modes[0] == "full", modes
    assert "warm" in modes[1:], modes
    store.close()


def test_invalidation_profile_set_change_falls_back(monkeypatch):
    """A new pending profile rebuilds the encode cache (profile
    generation bump): statics + warm candidates are stale -> full."""
    store, sched, dv = _steady_store(monkeypatch)
    builds0 = dv.static_builds
    store.add_pod_group(PodGroup(name="newprof", min_member=1))
    store.add_pod(Pod(
        name="newprof-0",
        annotations={GROUP_NAME_ANNOTATION: "newprof"},
        containers=[{"cpu": "3", "memory": "3Gi"}],  # distinct profile
    ))
    modes = _modes_after(sched, dv, 1)
    assert modes[0] == "full", modes
    assert dv.static_builds > builds0, "static planes not rebuilt"
    store.close()


def test_invalidation_node_liveness_flip_falls_back(monkeypatch):
    """A node deletion flips liveness (and epoch): full recompute."""
    store, sched, dv = _steady_store(monkeypatch)
    store.delete_node(sorted(store.mirror.n_row)[-1])
    modes = _modes_after(sched, dv, 1)
    assert modes[0] == "full", modes
    store.close()


def test_invalidation_compaction_falls_back(monkeypatch):
    """A pod-table compaction renumbers rows (compact_gen): the warm
    key breaks, the derive full-rebuilds (poisoning the dirty
    accumulator), and any in-flight solve voids -> full.  The gen bump
    is synthetic (real compaction needs 4096+ tombstoned rows —
    mechanics covered by test_mirror_compaction); the invalidation
    contract keys on the GENERATION, which is what this pins."""
    store, sched, dv = _steady_store(monkeypatch, n_pods=48)
    with store._lock:
        store.mirror.compact_gen += 1
    modes = _modes_after(sched, dv, 1)
    assert modes[0] == "full", modes
    # And the lane recovers to warm afterwards.
    assert "warm" in _modes_after(sched, dv, 2)
    store.close()


def test_invalidation_dirty_cap_overflow_falls_back(monkeypatch):
    """Past VOLCANO_TPU_DIRTY_CAP the dirty superset is unprovable:
    every solve takes the full re-rank (and stays correct)."""
    monkeypatch.setenv("VOLCANO_TPU_DIRTY_CAP", "1")
    monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "1")
    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=16, n_pods=48, gang_size=4,
                              seed=3)
    store.pipeline = True
    store.cycle_feed = _partial_feed([0])
    sched = Scheduler(store)
    for _ in range(5):
        sched.run_once()
    dv = store._devincr_cache
    assert dv.counts["warm"] == 0, dv.counts
    assert dv.counts["full"] >= 1, dv.counts
    store.flush_binds()
    assert len(store.binder.binds) >= 1
    store.close()


# --------------------------------------------------- null-delta cycles


@pytest.mark.parametrize("mode", ["on", "mesh", "off"])
def test_null_delta_skips_and_resumes(monkeypatch, mode):
    """An idle pipelined loop records skip-cycles in the flight
    recorder, dispatches zero solves, and resumes an ordinary solve on
    the first mutation — under a mesh too.  With DEVINCR=0 the same
    idle cycles each dispatch their solve."""
    devincr = mode != "off"
    monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "1" if devincr else "0")
    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=8, n_pods=24, gang_size=4, seed=5)
    store.pipeline = True
    if mode == "mesh":
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        from volcano_tpu.parallel import make_mesh

        store.solve_mesh = make_mesh(4)
    sched = Scheduler(store)
    for _ in range(2):
        sched.run_once()
    # A pending-but-unschedulable gang keeps the pending set non-empty
    # (otherwise the lane early-outs before the skip check matters).
    store.add_pod_group(PodGroup(name="big", min_member=1))
    store.add_pod(Pod(
        name="big-0", annotations={GROUP_NAME_ANNOTATION: "big"},
        containers=[{"cpu": "512", "memory": "512Gi"}],
    ))
    sched.run_once()   # dispatches the (failing) solve
    sched.run_once()   # commits the empty result
    seq0 = store._solve_seq
    if not devincr:
        for _ in range(3):
            sched.run_once()
        assert store._solve_seq == seq0 + 3, "an idle cycle skipped"
        assert not any("null-delta" in e
                       for r in store.flight.recent()[-3:]
                       for e in r.device_events)
        store.close()
        return
    dv = store._devincr_cache
    skips0 = dv.counts["skip"]
    for _ in range(3):
        sched.run_once()
    assert store._solve_seq == seq0, "idle cycles dispatched solves"
    assert dv.counts["skip"] == skips0 + 3, dv.counts
    recs = store.flight.recent()[-3:]
    for r in recs:
        assert any("null-delta" in e for e in r.device_events), \
            r.device_events
        assert r.dispatched_solve_id is None
    # First mutation resumes an ordinary solve and binds the new pod.
    store.add_pod_group(PodGroup(name="ok", min_member=1))
    store.add_pod(Pod(
        name="ok-0", annotations={GROUP_NAME_ANNOTATION: "ok"},
        containers=[{"cpu": "1", "memory": "1Gi"}],
    ))
    sched.run_once()
    assert store._solve_seq > seq0, "mutation did not resume dispatch"
    sched.run_once()
    store.flush_binds()
    assert any("ok-0" in k for k in store.binder.binds)
    store.close()


def test_null_delta_skip_counts_metric(monkeypatch):
    """The skip decisions land in
    volcano_device_incremental_solves_total{mode=skip}."""
    from volcano_tpu.metrics import metrics

    monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "1")
    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=8, n_pods=16, gang_size=4, seed=9)
    store.pipeline = True
    sched = Scheduler(store)
    for _ in range(2):
        sched.run_once()
    store.add_pod_group(PodGroup(name="big", min_member=1))
    store.add_pod(Pod(
        name="big-0", annotations={GROUP_NAME_ANNOTATION: "big"},
        containers=[{"cpu": "512", "memory": "512Gi"}],
    ))
    sched.run_once()
    sched.run_once()
    text0 = metrics.expose_text()
    sched.run_once()
    text1 = metrics.expose_text()

    def count(text):
        for line in text.splitlines():
            if ("device_incremental_solves_total" in line
                    and 'mode="skip"' in line):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    assert count(text1) == count(text0) + 1
    store.close()


def test_kill_switch_disables_everything(monkeypatch):
    """VOLCANO_TPU_DEVINCR=0: no skip, no warm, no static planes — and
    the lane's store slot stays untouched by the solve path."""
    monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "0")
    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=8, n_pods=24, gang_size=4, seed=5)
    store.pipeline = True
    sched = Scheduler(store)
    for _ in range(4):
        sched.run_once()
    dv = getattr(store, "_devincr_cache", None)
    assert dv is None or (dv.counts["warm"] == 0
                          and dv.counts["skip"] == 0)
    store.flush_binds()
    assert len(store.binder.binds) == 24
    store.close()


# ------------------------------------------------------- remote solver


def test_remote_solver_devincr_parity(monkeypatch):
    """The remote child keeps its own persistent planes keyed by the
    frame manifest's tokens: pipelined remote binds with DEVINCR=1 must
    equal the local DEVINCR=0 run, and the child must report a warm
    decision once steady."""
    import subprocess

    from test_remote_solver import _spawn_solver

    from volcano_tpu.solver_service import RemoteSolver

    proc, port = _spawn_solver()
    try:
        monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "1")
        _reset_uid_counters()
        store = synthetic_cluster(n_nodes=12, n_pods=36, gang_size=4,
                                  seed=21)
        store.pipeline = True
        store.remote_solver = RemoteSolver(f"127.0.0.1:{port}")
        store.cycle_feed = _partial_feed([0, 1])
        sched = Scheduler(store)
        states_r = []
        modes = []
        for _ in range(7):
            sched.run_once()
            states_r.append(_mirror_state(store))
            modes.append(store.remote_solver.last_devincr_mode)
        store.flush_binds()
        binds_r = dict(store.binder.binds)
        store.close()

        monkeypatch.setenv("VOLCANO_TPU_DEVINCR", "0")
        _reset_uid_counters()
        store = synthetic_cluster(n_nodes=12, n_pods=36, gang_size=4,
                                  seed=21)
        store.pipeline = True
        store.cycle_feed = _partial_feed([0, 1])
        sched = Scheduler(store)
        states_l = []
        for _ in range(7):
            sched.run_once()
            states_l.append(_mirror_state(store))
        store.flush_binds()
        binds_l = dict(store.binder.binds)
        store.close()

        assert binds_r == binds_l
        assert states_r == states_l
        assert "warm" in modes, f"child never went warm: {modes}"
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
