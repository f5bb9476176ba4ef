"""Pipelined scheduler cycles (double-buffered sessions): overlap
correctness.

The pipelined cycle dispatches the device solve WITHOUT blocking and
commits the result at the top of the next cycle (ISSUE 1).  These tests
pin the overlap contracts: placement parity with the synchronous loop
when nothing moves during the overlap, the staleness guard dropping
exactly the conflicting rows when something does (pod deletes, competing
binds, capacity theft), clean drain/abandon of the in-flight solve on
stop/restart, whole-result invalidation across a mirror compaction, and
the device-resident snapshot's delta-upload path.

All of it runs under JAX_PLATFORMS=cpu (conftest forces the virtual CPU
platform) — no TPU required; the tier1 marker records that these belong
to the tier-1 overlap-correctness gate.
"""

import numpy as np
import pytest

from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    TaskStatus,
)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

pytestmark = pytest.mark.tier1

ST_PENDING = int(TaskStatus.Pending)
ST_BOUND = int(TaskStatus.Bound)


def _placements(store):
    return {
        f"{p.namespace}/{p.name}": p.node_name
        for p in store.pods.values()
    }


def _assert_capacity_respected(store):
    """No node oversubscribed: sum of bound pods' cpu <= allocatable."""
    used = {}
    for p in store.pods.values():
        if p.node_name:
            req = p.resource_request()
            used[p.node_name] = used.get(p.node_name, 0) + req.milli_cpu
    for name, milli in used.items():
        node = next(n for n in store.mirror.node_objs
                    if n is not None and n.name == name)
        alloc = node.allocatable_resource()
        assert milli <= alloc.milli_cpu, f"{name} oversubscribed"


def _small(seed=7, **kw):
    kw.setdefault("n_nodes", 8)
    kw.setdefault("n_pods", 32)
    kw.setdefault("gang_size", 4)
    return synthetic_cluster(seed=seed, **kw)


# ------------------------------------------------------------- parity


def test_pipelined_matches_synchronous_without_mutations():
    """With no concurrent store mutations the pipelined loop lands the
    exact placements of the synchronous loop, one cycle later."""
    sync = _small()
    Scheduler(sync).run_once()
    sync.flush_binds()

    piped = _small()
    piped.pipeline = True
    sched = Scheduler(piped)
    sched.run_once()
    # Cycle 1 only dispatched: nothing bound yet, handle parked.
    assert piped._inflight_solve is not None
    assert len(piped.binder.binds) == 0
    sched.run_once()
    piped.flush_binds()
    assert piped._inflight_solve is None  # nothing left pending
    assert _placements(sync) == _placements(piped)
    assert len(piped.binder.binds) == len(sync.binder.binds)


def test_unmutated_overlap_skips_revalidation(monkeypatch):
    """mutation_seq equality at fetch proves nothing moved: the commit
    must take the fast path (no capacity re-validation)."""
    from volcano_tpu import fastpath

    store = _small()
    store.pipeline = True

    def boom(self, task_rows, assigned):
        raise AssertionError("revalidation ran on an unmutated overlap")

    monkeypatch.setattr(fastpath.FastCycle, "_revalidate_inflight", boom)
    sched = Scheduler(store)
    sched.run_once()
    sched.run_once()
    store.flush_binds()
    assert all(p.node_name for p in store.pods.values())


# ----------------------------------------------------- staleness guard


def _two_node_store(n_pods=4, node_cpu="2"):
    store = ClusterStore()
    for i in range(2):
        store.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": node_cpu, "memory": "8Gi", "pods": 64},
        ))
    pg = PodGroup(name="g", min_member=1)
    store.add_pod_group(pg)
    for k in range(n_pods):
        store.add_pod(Pod(
            name=f"p{k}",
            annotations={GROUP_NAME_ANNOTATION: pg.name},
            containers=[{"cpu": "1", "memory": "1Gi"}],
        ))
    return store


def test_overlap_delete_and_competing_bind_no_double_bind_no_lost_pod():
    """A pod deleted and a competing bind landing between dispatch N and
    fetch N: the deleted row and any row whose capacity was taken drop;
    every surviving pod binds exactly once; nothing is lost."""
    store = _two_node_store(n_pods=4, node_cpu="2")
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()  # dispatch over the 4 pending pods
    assert store._inflight_solve is not None

    # Overlap mutations: delete p0; a competing scheduler binds a brand
    # new pod onto n0, eating one of the cpus the in-flight solve was
    # promised (a fast-path/async-bind race in production).
    victim = next(p for p in store.pods.values() if p.name == "p0")
    store.delete_pod(victim)
    intruder = Pod(
        name="intruder",
        annotations={GROUP_NAME_ANNOTATION: "g"},
        containers=[{"cpu": "1", "memory": "1Gi"}],
        node_name="n0",
    )
    store.add_pod(intruder)

    sched.run_once()  # fetch + staleness-guarded commit, then redispatch
    sched.run_once()  # land the redispatch of any dropped rows
    sched.run_once()
    store.flush_binds()

    live = [p for p in store.pods.values()]
    assert len(live) == 4  # 3 survivors + intruder
    # No lost pod: every live schedulable pod ends up bound.
    assert all(p.node_name for p in live)
    # No double bind: the async binder saw each surviving pod at most
    # once per final placement, and no node is oversubscribed.
    _assert_capacity_respected(store)
    m = store.mirror
    rows = [m.p_row[p.uid] for p in live]
    assert all(m.p_status[r] == ST_BOUND for r in rows)
    # Mirror column agrees with the records (batched column write).
    assert [m.p_node_name[r] for r in rows] == [p.node_name for p in live]


def test_overlap_full_capacity_theft_drops_rows_then_replaces():
    """Every cpu the in-flight solve counted on is stolen during the
    overlap: the guard must drop ALL rows targeting the stuffed nodes
    (no divergence error, no oversubscription) and later cycles re-place
    what still fits."""
    store = _two_node_store(n_pods=2, node_cpu="1")
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()  # dispatch: p0 -> one node, p1 -> the other

    for i in range(2):
        store.add_pod(Pod(
            name=f"thief{i}",
            annotations={GROUP_NAME_ANNOTATION: "g"},
            containers=[{"cpu": "1", "memory": "1Gi"}],
            node_name=f"n{i}",
        ))
    sched.run_once()  # guard drops both rows; nothing commits
    store.flush_binds()
    originals = [p for p in store.pods.values()
                 if p.name.startswith("p")]
    assert all(p.node_name is None for p in originals)
    _assert_capacity_respected(store)
    m = store.mirror
    assert all(m.p_status[m.p_row[p.uid]] == ST_PENDING
               for p in originals)


def test_compaction_mid_flight_voids_whole_result():
    """Row renumbering (mirror compaction) between dispatch and fetch
    voids the in-flight result wholesale; the pods simply re-place."""
    store = _small(seed=9)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    assert store._inflight_solve is not None
    store.mirror.compact_gen += 1  # what maybe_compact() does
    sched.run_once()  # result dropped, fresh dispatch
    assert len(store.binder.binds) == 0
    sched.run_once()  # fresh result lands
    store.flush_binds()
    assert all(p.node_name for p in store.pods.values())


def test_node_relabel_mid_flight_drops_selector_rows():
    """Node labels changing during the overlap invalidate any in-flight
    row whose pod matched them via a nodeSelector: the solve saw stale
    planes, so the row drops (conservative) instead of committing a
    placement the synchronous loop could never produce."""
    store = ClusterStore()
    store.add_node(Node(
        name="gpu-node",
        allocatable={"cpu": "4", "memory": "8Gi", "pods": 16},
        labels={"gpu": "true"},
    ))
    store.add_node(Node(
        name="plain-node",
        allocatable={"cpu": "4", "memory": "8Gi", "pods": 16},
    ))
    store.add_pod_group(PodGroup(name="g", min_member=1))
    store.add_pod(Pod(
        name="needs-gpu",
        annotations={GROUP_NAME_ANNOTATION: "g"},
        containers=[{"cpu": "1", "memory": "1Gi"}],
        node_selector={"gpu": "true"},
    ))
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()  # dispatch: the solve places needs-gpu on gpu-node
    assert store._inflight_solve is not None

    # Overlap mutation: the gpu label disappears (epoch bump).
    store.add_node(Node(
        name="gpu-node",
        allocatable={"cpu": "4", "memory": "8Gi", "pods": 16},
    ))
    sched.run_once()  # guard drops the selector row; fresh solve sees
    sched.run_once()  # no matching node
    store.flush_binds()
    pod = next(p for p in store.pods.values())
    assert pod.node_name is None, (
        "stale selector placement committed onto a relabelled node"
    )
    m = store.mirror
    assert m.p_status[m.p_row[pod.uid]] == ST_PENDING


def test_fetch_device_crash_degrades_budget_and_replaces(monkeypatch):
    """Device memory exhaustion surfacing at the async fetch must
    route through the same chunk-budget degradation as a synchronous
    solve (not be swallowed), and the rows re-place."""
    import jax

    from volcano_tpu import pipeline as pl

    store = _small(seed=29)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    assert store._inflight_solve is not None

    real_fetch = pl.InflightSolve.fetch
    calls = {"n": 0}

    def crash_once(self):
        if calls["n"] == 0:
            calls["n"] += 1
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: Error allocating device buffer")
        return real_fetch(self)

    monkeypatch.setattr(pl.InflightSolve, "fetch", crash_once)
    sched.run_once()  # fetch crashes; budget halves; redispatch
    assert store._aff_budget_scale == 0.5
    sched.run_once()  # the redispatched solve lands
    store.flush_binds()
    assert all(p.node_name for p in store.pods.values())


def test_fetch_programming_error_propagates(monkeypatch):
    """A non-crash fetch error (local kind) is a programming error and
    must propagate, exactly as from a synchronous solve."""
    from volcano_tpu import pipeline as pl

    store = _small(seed=31)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    assert store._inflight_solve is not None

    def boom(self):
        raise ValueError("shape mismatch: solver returned garbage")

    monkeypatch.setattr(pl.InflightSolve, "fetch", boom)
    from volcano_tpu.fastpath import run_cycle_fast

    with pytest.raises(ValueError, match="shape mismatch"):
        run_cycle_fast(store, sched._load_conf())


def test_remote_garbage_replies_fail_cycle_after_cap(monkeypatch):
    """A solver child that keeps replying garbage never fails the
    send-side probe, so each cycle's fetch raises and used to be
    swallowed as a 'lost reply' forever — pods Pending, healthz green.
    Past REMOTE_FETCH_FAIL_CAP consecutive fetch failures the cycle
    must fail loudly (scheduler failure accounting takes over); one
    success resets the counter."""
    from volcano_tpu import pipeline as pl
    from volcano_tpu.fastpath import FastCycle, run_cycle_fast

    store = _small(seed=33)
    store.pipeline = True
    sched = Scheduler(store)
    conf = sched._load_conf()
    sched.run_once()
    assert store._inflight_solve is not None

    def garbage(self):
        raise ValueError("malformed snapshot frame")

    monkeypatch.setattr(pl.InflightSolve, "fetch", garbage)
    for _ in range(FastCycle.REMOTE_FETCH_FAIL_CAP - 1):
        # Present the parked handle as a remote dispatch; the failure
        # is swallowed and the cycle re-dispatches.
        store._inflight_solve.kind = "remote"
        run_cycle_fast(store, conf)
        assert store._inflight_solve is not None
    store._inflight_solve.kind = "remote"
    with pytest.raises(ValueError, match="malformed"):
        run_cycle_fast(store, conf)
    # Recovery: a successful fetch resets the consecutive counter (the
    # first cycle after the failure only re-dispatches; the fetch that
    # resets lands at the top of the one after).
    monkeypatch.undo()
    sched.run_once()
    sched.run_once()
    assert store._remote_fetch_fails == 0


# ------------------------------------------------------- stop / restart


def test_stop_mid_flight_abandons_dispatch_and_restart_places_all():
    store = _small(seed=11)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    assert store._inflight_solve is not None
    sched.stop()  # no loop thread: must still drain the dispatch
    assert store._inflight_solve is None

    # "Restarted" scheduler (fresh instance, same store): first cycles
    # re-place everything that was in flight.
    sched2 = Scheduler(store)
    sched2.run_once()
    sched2.run_once()
    store.flush_binds()
    assert all(p.node_name for p in store.pods.values())


def test_fallback_to_object_session_abandons_inflight(monkeypatch):
    """A cycle that leaves the fast path must not strand the in-flight
    handle where a later fast cycle would commit stale rows."""
    store = _small(seed=13)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    assert store._inflight_solve is not None

    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "always")
    from volcano_tpu import fastpath

    def explode(store_, conf):
        raise RuntimeError("fast path down")

    monkeypatch.setattr(fastpath, "run_cycle_fast", explode)
    import volcano_tpu.scheduler as sched_mod

    monkeypatch.setattr(sched_mod, "run_cycle_fast", explode,
                        raising=False)
    sched.run_once()  # falls back; must abandon the parked handle
    assert store._inflight_solve is None
    store.flush_binds()
    _assert_capacity_respected(store)


# ------------------------------------------------ device-resident planes


def test_devsnap_delta_upload_on_node_change():
    """A single-node mutation between cycles re-ships only the dirty
    rows (delta scatter), not the full plane set."""
    store = _small(seed=17, n_nodes=8, n_pods=16, gang_size=2)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    snap = store.device_snapshot
    assert snap.full_uploads >= 1
    full_before = snap.full_uploads

    # Node mutation: epoch bumps, one row dirty.
    store.add_node(Node(
        name="node-000000",
        allocatable={"cpu": "64", "memory": "256Gi", "pods": 256},
        labels={"freshly": "relabelled"},
    ))
    # New work so the next cycle actually solves.
    store.add_pod_group(PodGroup(name="late", min_member=1))
    store.add_pod(Pod(
        name="late-0",
        annotations={GROUP_NAME_ANNOTATION: "late"},
        containers=[{"cpu": "1", "memory": "1Gi"}],
    ))
    sched.run_once()
    sched.run_once()
    store.flush_binds()
    assert snap.delta_uploads >= 1
    assert snap.full_uploads == full_before
    assert all(p.node_name for p in store.pods.values())


def test_devsnap_steady_state_hits_without_node_changes():
    store = _small(seed=19)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    snap = store.device_snapshot
    # Re-pend half the pods (vectorized, via the mirror column) so the
    # next cycle solves again at an unchanged node epoch.
    m = store.mirror
    rows = np.flatnonzero(
        (m.p_status[:m.n_pods] == ST_BOUND) & m.p_alive[:m.n_pods]
    )
    sched.run_once()
    store.flush_binds()
    hits_before = snap.hits
    rows = np.flatnonzero(
        (m.p_status[:m.n_pods] == ST_BOUND) & m.p_alive[:m.n_pods]
    )
    m.p_status[rows] = ST_PENDING
    m.p_node[rows] = -1
    m.p_node_name[rows] = None
    m.mutation_seq += 1
    for p in store.pods.values():
        p.node_name = None
    store.mark_objects_stale()
    sched.run_once()
    assert snap.hits > hits_before
    assert snap.full_uploads == 1


# ------------------------------------------------------ remote pipeline


def test_remote_pipelined_two_process_parity():
    """--remote-solver pipelined sessions over two real OS processes:
    frame N+1 is sent while frame N's reply is outstanding, and the
    placements match the local synchronous loop (hack/run-e2e.sh runs
    this file as its pipelined-mode pass)."""
    from test_remote_solver import _spawn_solver

    from volcano_tpu.solver_service import RemoteSolver

    local = _small(seed=23)
    Scheduler(local).run_once()
    local.flush_binds()

    proc, port = _spawn_solver()
    try:
        remote = _small(seed=23)
        remote.pipeline = True
        client = RemoteSolver(f"127.0.0.1:{port}")
        remote.remote_solver = client
        sched = Scheduler(remote)
        sched.run_once()
        inflight = remote._inflight_solve
        assert inflight is not None and inflight.kind == "remote"
        sched.run_once()
        remote.flush_binds()
        assert _placements(local) == _placements(remote)
        assert client.ping()["solves"] >= 1  # the CHILD actually solved
        remote.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except Exception:
            proc.kill()


# ----------------------------------------------------------- plumbing


def test_dispatch_slot_is_exclusive_remote_contract():
    """The remote protocol allows one outstanding solve: a second
    dispatch without a fetch must fail loudly, and abandon must clear
    the slot."""
    from volcano_tpu.solver_service import (
        PendingSolve,
        RemoteSolver,
        _WireCache,
    )

    client = RemoteSolver.__new__(RemoteSolver)
    import threading

    client._lock = threading.Lock()
    client._sock = None
    client._wire = _WireCache()
    client._shm = None
    client.wire_fallbacks = {}
    client._pending = PendingSolve(client)
    with pytest.raises(RuntimeError):
        client._roundtrip(b"x")
    client._pending.abandon()
    assert client._pending is None
