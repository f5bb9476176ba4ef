"""Async bind dispatch + rate-limited bind-failure backoff + event trail
(the analog of cache.go:536-552 goroutine binds and 627-649 errTasks)."""

import threading
import time

import pytest

from volcano_tpu.cache.interface import BindFailure
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster


def _flaky(store, fail_times):
    """Wrap the store's binder: the first ``fail_times`` batches fail the
    second half of their keys."""
    orig = store.binder.bind_keys
    state = {"left": fail_times}

    def flaky(keys, hosts):
        if state["left"] > 0:
            state["left"] -= 1
            half = len(keys) // 2
            orig(list(keys[:half]), list(hosts[:half]))
            raise BindFailure(list(keys[half:]))
        orig(keys, hosts)

    store.binder.bind_keys = flaky
    return state


def test_async_bind_failure_reverts_with_backoff(monkeypatch):
    from volcano_tpu.cache import bindqueue

    monkeypatch.setattr(bindqueue, "BACKOFF_BASE", 0.05)
    store = synthetic_cluster(n_nodes=8, n_pods=24, gang_size=1)
    store.async_bind = True
    _flaky(store, fail_times=1)
    sched = Scheduler(store)
    sched.run_once()
    assert store.flush_binds(timeout=10)
    assert len(store.binder.binds) == 12

    # Next cycle drains the failures: tasks revert to Pending, carry a
    # backoff window, and are NOT re-solved within it.
    sched.run_once()
    assert store.flush_binds(timeout=10)
    assert len(store.bind_backoff) == 12
    assert len(store.binder.binds) == 12  # still inside backoff

    # FailedScheduling events are visible on the pods.
    failed_keys = list(store.bind_backoff)
    evs = store.events_for(f"Pod/{failed_keys[0]}")
    assert any(e["reason"] == "FailedScheduling" for e in evs)

    # After the backoff expires the tasks re-enter and bind.
    time.sleep(0.12)
    sched.run_once()
    assert store.flush_binds(timeout=10)
    assert len(store.binder.binds) == 24
    assert all(p.node_name for p in store.pods.values())
    # Successful rebind clears the backoff state at the next cycle's
    # drain (clears are queued for the cycle thread, which owns
    # bind_backoff — store._on_bind_success).
    sched.run_once()
    assert not store.bind_backoff


def test_async_bind_success_records_scheduled_events():
    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=1)
    store.async_bind = True
    Scheduler(store).run_once()
    assert store.flush_binds(timeout=10)
    pod = next(iter(store.pods.values()))
    evs = store.events_for(f"Pod/{pod.namespace}/{pod.name}")
    assert any(e["reason"] == "Scheduled" for e in evs)


def test_unschedulable_gang_records_podgroup_event():
    # A gang that cannot fit leaves an Unschedulable event on its group.
    store = synthetic_cluster(n_nodes=1, n_pods=4, gang_size=4,
                              pod_cpu_choices=("64",),
                              pod_mem_choices=("256Gi",))
    Scheduler(store).run_once()
    pgs = [pg for pg in store.pod_groups.values()]
    assert pgs
    hit = False
    for pg in pgs:
        evs = store.events_for(f"PodGroup/{pg.namespace}/{pg.name}")
        if any(e["reason"] == "Unschedulable" for e in evs):
            hit = True
    assert hit


def test_evict_records_event():
    from volcano_tpu.synth import preempt_cluster

    store = preempt_cluster(n_nodes=4, fill_per_node=4, n_pending=8,
                            gang_size=1)
    conf = """
actions: "enqueue, allocate, preempt, reclaim, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""
    Scheduler(store, conf_str=conf).run_once()
    evicted = getattr(store.evictor, "evicts", [])
    assert evicted
    key = evicted[0]
    evs = store.events_for(f"Pod/{key}")
    assert any(e["reason"] == "Evict" for e in evs)


def test_indeterminate_batch_exception_redrives_per_key():
    """A non-BindFailure exception from bind_keys must not fail the whole
    batch: binds that already landed would be re-queued and later re-bound
    (possibly to a different node).  The dispatcher re-drives per key
    instead (bindqueue.py worker)."""
    store = synthetic_cluster(n_nodes=8, n_pods=16, gang_size=1)
    store.async_bind = True
    orig = store.binder.bind_keys
    state = {"left": 1}

    def broken(keys, hosts):
        if state["left"] > 0:
            state["left"] -= 1
            half = len(keys) // 2
            orig(list(keys[:half]), list(hosts[:half]))
            raise RuntimeError("transport blew up mid-batch")
        orig(keys, hosts)

    store.binder.bind_keys = broken
    sched = Scheduler(store)
    sched.run_once()
    assert store.flush_binds(timeout=10)
    # Per-key re-drive landed every bind exactly where the solver put it:
    # no pod re-entered Pending, no backoff, all 16 bound.
    assert len(store.binder.binds) == 16
    sched.run_once()
    assert not store.bind_backoff
    assert all(p.node_name for p in store.pods.values())


def test_deleted_pod_prunes_backoff_entry(monkeypatch):
    from volcano_tpu.cache import bindqueue

    monkeypatch.setattr(bindqueue, "BACKOFF_BASE", 60.0)
    store = synthetic_cluster(n_nodes=8, n_pods=8, gang_size=1)
    store.async_bind = True
    _flaky(store, fail_times=1)
    sched = Scheduler(store)
    sched.run_once()
    assert store.flush_binds(timeout=10)
    sched.run_once()  # drain failures -> backoff entries
    assert store.bind_backoff
    key = next(iter(store.bind_backoff))
    ns, name = key.split("/", 1)
    pod = next(p for p in store.pods.values()
               if p.namespace == ns and p.name == name)
    store.delete_pod(pod)
    assert key not in store.bind_backoff


def test_bind_failure_releases_claim_pin(monkeypatch):
    """A claim provisioned for a pod whose bind then fails must return
    to Pending (unpinned) so the retry can place the pod — and the
    claim — on any node."""
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.cache import bindqueue

    monkeypatch.setattr(bindqueue, "BACKOFF_BASE", 0.05)
    store = ClusterStore()
    store.add_node(Node(name="n0", allocatable={"cpu": "8",
                                                "memory": "16Gi"}))
    store.add_node(Node(name="n1", allocatable={"cpu": "8",
                                                "memory": "16Gi"}))
    store.put_pvc("default", "claim", {"storage": "1Gi"})
    store.add_pod_group(PodGroup(name="g", min_member=1))
    store.add_pod(Pod(
        name="p0",
        containers=[{"cpu": "1", "memory": "1Gi"}],
        annotations={GROUP_NAME_ANNOTATION: "g"},
        volumes=[("claim", "/data")],
    ))
    store.async_bind = True
    _flaky(store, fail_times=1)  # fails the 2nd half => our only pod?
    # _flaky fails keys[half:]; with one key, half=0 -> all fail.
    sched = Scheduler(store)
    sched.run_once()
    assert store.flush_binds(timeout=10)
    sched.run_once()  # drain: pod back to Pending with backoff
    pod = next(iter(store.pods.values()))
    assert pod.node_name is None
    rec = store.pvcs["default/claim"]
    assert rec["phase"] == "Pending" and rec["node"] is None

    import time as _t
    _t.sleep(0.12)
    sched.run_once()
    assert store.flush_binds(timeout=10)
    pod = next(iter(store.pods.values()))
    assert pod.node_name is not None
    assert store.pvcs["default/claim"]["phase"] == "Bound"
    assert store.pvcs["default/claim"]["node"] == pod.node_name


# ------------------------------------------------- churn stress (r4)


def test_dispatcher_vs_store_churn_stress(monkeypatch):
    """Concurrent async-bind dispatch, bind failures, pod deletions and
    re-adds, and cycle-thread drains: no deadlock, no lost pods, and
    every surviving pod either binds or re-enters Pending with backoff.
    The bindqueue race surface VERDICT r3 called thin, exercised
    directly."""
    import threading

    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from volcano_tpu.cache import bindqueue

    monkeypatch.setattr(bindqueue, "BACKOFF_BASE", 0.02)
    store = synthetic_cluster(n_nodes=16, n_pods=64, gang_size=1, seed=5)
    store.async_bind = True
    # Every third batch fails its second half.
    orig = store.binder.bind_keys
    calls = {"n": 0}

    def flaky(keys, hosts):
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            half = len(keys) // 2
            orig(list(keys[:half]), list(hosts[:half]))
            raise BindFailure(list(keys[half:]))
        orig(keys, hosts)

    store.binder.bind_keys = flaky
    sched = Scheduler(store)
    stop = threading.Event()
    errors = []

    def churner():
        """Deletes and re-adds pods while cycles and binds run.
        Iteration-bounded, not wall-clock-bounded: surviving churn pods
        must stay well under cluster capacity or unschedulable pods
        (neither bound nor backed off) would flake the final assert on
        fast machines."""
        i = 0
        try:
            while not stop.is_set() and i < 400:
                i += 1
                name = f"churn-{i}"
                pg = PodGroup(name=name, min_member=1)
                store.add_pod_group(pg)
                pod = Pod(
                    name=f"{name}-0",
                    annotations={GROUP_NAME_ANNOTATION: name},
                    containers=[{"cpu": "1", "memory": "1Gi"}],
                )
                store.add_pod(pod)
                time.sleep(0.002)
                if i % 2 == 0:
                    store.delete_pod(pod)
                    store.delete_pod_group(f"default/{name}")
        except Exception as e:  # pragma: no cover - failure channel
            errors.append(e)

    t = threading.Thread(target=churner)
    t.start()
    try:
        deadline = time.time() + 4.0
        while time.time() < deadline:
            sched.run_once()
            time.sleep(0.01)
    finally:
        stop.set()
        t.join(timeout=10)
    assert not t.is_alive()
    assert not errors, errors
    assert store.flush_binds(timeout=30)
    # Converge: backoff windows expire, the remaining pods bind.
    time.sleep(0.1)
    for _ in range(6):
        sched.run_once()
        store.flush_binds(timeout=30)
        time.sleep(0.03)
    store.close()
    unbound = [
        f"{p.namespace}/{p.name}" for p in store.pods.values()
        if p.node_name is None and not p.deleting
    ]
    # Everything alive is either bound or still inside a backoff window.
    for key in unbound:
        assert key in store.bind_backoff, (
            f"{key} neither bound nor backed off "
            f"(backoff={list(store.bind_backoff)[:5]}...)"
        )
    # Binder-side state agrees with the pod records for bound pods.
    for p in store.pods.values():
        if p.node_name is not None:
            key = f"{p.namespace}/{p.name}"
            assert store.binder.binds.get(key) == p.node_name


def test_flush_timeout_returns_false_on_wedged_binder():
    """flush(timeout) must not hang when a binder stalls."""
    import threading

    from volcano_tpu.cache.bindqueue import BindDispatcher

    release = threading.Event()

    class Wedged:
        def bind_keys(self, keys, hosts):
            release.wait(10)

    d = BindDispatcher(Wedged(), lambda pairs: None)
    d.dispatch(["a/b"], ["n0"], [None])
    t0 = time.time()
    assert d.flush(timeout=0.2) is False
    assert time.time() - t0 < 5
    release.set()
    assert d.flush(timeout=10) is True
    d.stop()


def test_the_idle_slot_comes_once_the_queue_is_empty(monkeypatch):
    """``idle_slot`` (ISSUE 43): after a batch is delivered and let go
    of, and only when no other waits: behind the last bind, in front
    of none.  A slot that fails leaves the worker alive."""
    from volcano_tpu.cache.bindqueue import BindDispatcher

    release = threading.Event()
    seen = []

    class Held:
        def bind_keys(self, keys, hosts):
            assert release.wait(10)
            seen.append(("bound", len(keys)))

    def slot():
        seen.append(("idle", d.flush(0)))
        raise RuntimeError("a slot's failure is not the worker's")

    monkeypatch.setattr(BindDispatcher, "idle_slot", slot)
    d = BindDispatcher(Held(), lambda pairs: None)
    try:
        d.dispatch(["a/b"], ["n0"], [None])
        d.dispatch(["a/c", "a/d"], ["n0", "n1"], [None, None])
        release.set()
        assert d.flush(timeout=10)
        deadline = time.time() + 10
        while len(seen) < 3 and time.time() < deadline:
            time.sleep(0.005)
        # None between the two batches; nothing was in flight at the slot.
        assert seen == [("bound", 1), ("bound", 2), ("idle", True)]
        d.dispatch(["a/e"], ["n0"], [None])     # the worker lives on
        assert d.flush(timeout=10)
    finally:
        release.set()
        d.stop()


def test_deferred_record_walk_sets_node_name_post_cycle():
    """Async watcher-free cycles ship the bind batch as object arrays;
    the dispatcher worker applies the pod.node_name record walk
    post-cycle (the reference's API-server-side NodeName write,
    cache.go:536-552).  After flush, every bound pod record must carry
    its host and the binder must have seen every key."""
    store = synthetic_cluster(n_nodes=4, n_pods=32, gang_size=4, seed=5)
    store.async_bind = True
    Scheduler(store).run_once()
    assert store.flush_binds(timeout=30)
    assert len(store.binder.binds) == 32
    named = [p for p in store.pods.values() if p.node_name]
    assert len(named) == 32
    store.close()


def test_deferred_record_walk_applies_before_failure_resync():
    """A cycle that fails after commit must apply the deferred record
    walk before the mirror resync, or committed pods would read as
    unbound and double-schedule (fastpath.run() exception path)."""
    import pytest

    from volcano_tpu.fastpath import FastCycle

    store = synthetic_cluster(n_nodes=4, n_pods=32, gang_size=4, seed=6)
    store.async_bind = True
    orig = FastCycle._close

    def boom(self):
        raise RuntimeError("injected close failure")

    FastCycle._close = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            Scheduler(store).run_once()
    finally:
        FastCycle._close = orig
    # The exception path applied the record walk synchronously.
    named = [p for p in store.pods.values() if p.node_name]
    assert len(named) == 32
    store.flush_binds(timeout=30)
    store.close()


def test_apply_pending_bind_records_covers_undispatched_batches():
    """Deferred record walks register with the STORE at commit time, so
    a failure path can force them even when the dispatcher worker has
    not processed the batch yet (prior-cycle coverage)."""
    store = synthetic_cluster(n_nodes=4, n_pods=32, gang_size=4, seed=7)
    store.async_bind = True
    Scheduler(store).run_once()
    # Do NOT flush: force synchronously, racing (idempotently) with the
    # worker thread.
    store.apply_pending_bind_records()
    named = [p for p in store.pods.values() if p.node_name]
    assert len(named) == 32
    store.flush_binds(timeout=30)
    assert len(store.binder.binds) == 32
    store.close()


def test_materialize_bind_entry_removes_by_identity():
    """Regression (ISSUE 9 satellite): ``_materialize_bind_entry`` used
    ``list.remove``, whose == scan compares this entry against OTHER
    pending entries — and two entries holding numpy object arrays raise
    the ambiguous-truth ValueError mid-scan, which the old handler
    swallowed.  The materialized entry then stayed registered forever
    and ``apply_pending_bind_records`` (which loops until the list
    drains) never terminated.  Removal is now by identity."""
    import numpy as np

    from volcano_tpu.cache import ClusterStore

    class Rec:
        node_name = None

    store = ClusterStore()

    def batch(n, tag):
        keys = np.array([f"default/{tag}-{i}" for i in range(n)],
                        dtype=object)
        hosts = np.array([f"n{i}" for i in range(n)], dtype=object)
        pods = np.array([Rec() for _ in range(n)], dtype=object)
        return keys, hosts, pods

    e1 = store.defer_bind_records(*batch(3, "a"))
    e2 = store.defer_bind_records(*batch(3, "b"))
    # Materialize the SECOND entry first: the removal scan compares it
    # against e1 (numpy object arrays on both sides) before reaching
    # e2 — exactly the ambiguous-truth trap.
    keys, hosts, pods = store._materialize_bind_entry(e2)
    assert keys == ["default/b-0", "default/b-1", "default/b-2"]
    assert [p.node_name for p in pods] == ["n0", "n1", "n2"]
    # The entry must be GONE (by identity) — pre-fix it was stranded
    # with entry[3] already True, the unbounded-loop condition.
    assert not any(e is e2 for e in store._pending_record_walks)
    # And the drain loop terminates, applying the remaining batch.
    store.apply_pending_bind_records()
    assert store._pending_record_walks == []
    assert not any(e is e1 for e in store._pending_record_walks)
    assert e1[3] is True
    store.close()


# ------------------------------------- who holds a batch (ISSUE 28)
#
# The dispatcher owns a batch from dispatch() to the end of its delivery
# and not a moment longer (cache/bindqueue.py, "Who holds a batch").


class _Rec:
    """Stand-in for a pod record: weakly referenceable, takes the
    deferred walk's ``node_name``, and may log where it dies."""

    __slots__ = ("node_name", "log", "binder", "__weakref__")

    def __init__(self, log=None, binder=None):
        self.node_name = None
        self.log = log
        self.binder = binder

    def __del__(self):
        if self.log is not None:
            self.log.append((threading.current_thread().name,
                             len(self.binder.calls)))


class _RecordingBinder:
    """Keeps what ``bind_keys`` was handed (the dispatcher's copies),
    fails the second half of a batch when asked to, and notes how many
    finalisers had run on the bind thread when each call came in."""

    def __init__(self, fail_half=False, log=None):
        self.calls = []
        self.fail_half = fail_half
        self.log = log
        self.finalised_on_bind_thread_before = []

    def bind_keys(self, keys, hosts):
        if self.log is not None:
            self.finalised_on_bind_thread_before.append(
                sum(1 for t, _ in self.log if t == "vc-bind-dispatch"))
        self.calls.append((keys, hosts))
        if self.fail_half:
            raise BindFailure(list(keys[len(keys) // 2:]))


def _batch(n, tag, **rec_kw):
    keys = [f"default/{tag}-{i}" for i in range(n)]
    hosts = [f"n{i % 4}" for i in range(n)]
    pods = [_Rec(**rec_kw) for _ in range(n)]
    return keys, hosts, pods


def _dispatcher(binder, store=None, tracer=None):
    from volcano_tpu.cache.bindqueue import BindDispatcher

    failures, successes = [], []
    d = BindDispatcher(
        binder, failures.extend,
        on_success=lambda k, h: successes.append((k, h)),
        materialize=(store._materialize_bind_entry
                     if store is not None else None),
        tracer=tracer)
    return d, failures, successes


@pytest.mark.parametrize("kind", ["plain", "deferred", "failed-keys"])
def test_flush_means_the_dispatcher_holds_nothing_of_the_batch(kind):
    """After dispatch() + flush(), with NO second batch dispatched, the
    only holders of the batch are the test's own names: the lists'
    reference counts are what they were before dispatch(), and a pod of
    the batch dies the moment the test lets go of it."""
    import gc
    import sys
    import weakref

    import numpy as np

    from volcano_tpu.cache import ClusterStore

    n = 64
    keys, hosts, pods = _batch(n, kind)
    store = entry = None
    binder = _RecordingBinder(fail_half=(kind == "failed-keys"))
    if kind == "deferred":
        store = ClusterStore()
        entry = store.defer_bind_records(
            np.array(keys, dtype=object), np.array(hosts, dtype=object),
            np.array(pods, dtype=object))
    d, failures, successes = _dispatcher(binder, store)
    held = [entry] if entry is not None else [keys, hosts, pods]
    before = [sys.getrefcount(x) for x in held]
    if entry is not None:
        before[0] -= 1      # the store's pending list lets go on delivery
    gc.disable()    # reference counts alone must do it, not a collection
    try:
        if entry is not None:
            d.dispatch(None, None, None, entry=entry)
        else:
            d.dispatch(keys, hosts, pods)
        assert d.flush(timeout=30)
        # Delivered as before: the binder and the hooks got copies.
        assert binder.calls == [(keys, hosts)]
        assert binder.calls[0][0] is not keys
        half = n // 2 if kind == "failed-keys" else n
        assert successes == [(keys[:half], hosts[:half])]
        assert failures == list(zip(keys[half:], pods[half:]))
        if kind == "deferred":
            assert [p.node_name for p in pods] == hosts
            assert store._pending_record_walks == []
            # The materialized lists live in the entry; nobody else
            # holds them.
            assert [sys.getrefcount(entry[i]) for i in range(3)] == [2] * 3
        assert [sys.getrefcount(x) for x in held] == before
        first, last = weakref.ref(pods[0]), weakref.ref(pods[-1])
        # The caller drops its own references, as complete() does when
        # it deletes the pods from the store and the mirror.
        del pods, held, entry
        failures.clear()    # the failure hand-back holds (key, pod)
        assert first() is None and last() is None
    finally:
        gc.enable()
        d.stop()
        if store is not None:
            store.close()


def test_a_delivered_batch_is_never_freed_on_the_bind_thread():
    """The order backlog_to_bind_ms depends on, free of clocks: the pods
    of batch A are freed where they are deleted (here, as in complete(),
    on the caller's thread), not by the bind worker when it takes batch
    B, in front of B's bind."""
    import gc

    log = []
    binder = _RecordingBinder(log=log)
    d, _failures, _successes = _dispatcher(binder)
    gc.disable()
    try:
        keys, hosts, pods = _batch(256, "a", log=log, binder=binder)
        d.dispatch(keys, hosts, pods)
        assert d.flush(timeout=30)
        assert log == []
        del keys, hosts, pods       # complete(): the store lets go of A
        here = threading.current_thread().name
        assert log == [(here, 1)] * 256
        d.dispatch(*_batch(8, "b"))
        assert d.flush(timeout=30)
    finally:
        gc.enable()
        d.stop()
    assert len(binder.calls) == 2 and len(log) == 256
    assert not [t for t, _ in log if t == "vc-bind-dispatch"]
    # B's bind_keys was not preceded by a single finaliser on its thread.
    assert binder.finalised_on_bind_thread_before == [0, 0]


@pytest.mark.parametrize("kind", ["plain", "deferred", "failed-keys"])
def test_bind_release_is_the_last_event_of_a_batch(kind):
    """One ``bind:release`` a batch on the ``bind`` track with
    ``args["pods"]``, after ``bind:on_success``; the events of a batch
    are otherwise what they were."""
    import numpy as np

    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.obs.trace import Tracer

    tracer = Tracer(enabled=True)
    store = ClusterStore() if kind == "deferred" else None
    binder = _RecordingBinder(fail_half=(kind == "failed-keys"))
    d, _failures, _successes = _dispatcher(binder, store, tracer)
    try:
        for n in (8, 24):
            keys, hosts, pods = _batch(n, kind)
            if store is not None:
                entry = store.defer_bind_records(
                    np.array(keys, dtype=object),
                    np.array(hosts, dtype=object),
                    np.array(pods, dtype=object))
                d.dispatch(None, None, None, entry=entry)
            else:
                d.dispatch(keys, hosts, pods)
            assert d.flush(timeout=30)
    finally:
        d.stop()
        if store is not None:
            store.close()
    events = tracer.drain()
    names = ["bind:queue_wait", "bind:binder", "bind:on_success",
             "bind:release"]
    if kind == "deferred":
        names.insert(1, "bind:materialize")
    assert [e.name for e in events] == names * 2
    for batch, n in ((events[:len(names)], 8), (events[len(names):], 24)):
        for e in batch:
            assert e.tid == "bind" and e.cat == "bind"
            assert e.args == {"pods": n}
            assert e.parent_id == 0 and e.dur_ns >= 0
        ok, release = batch[-2], batch[-1]
        assert release.ts_ns >= ok.ts_ns + ok.dur_ns
    # flush() returned after the release, so it was all there to drain.
    assert tracer.drain() == []


def test_a_bound_pod_dies_when_the_store_deletes_it():
    """The whole path, a store and its scheduler: once a batch is bound
    and flushed, nothing of the program keeps its pod records alive
    beyond ``delete_pod`` — not the dispatcher, not the commit path's
    object-array cache on the store — so they are freed one by one
    where they are deleted and never in one cascade inside the next
    cycle.  Round two binds without deferral (round one's deletions
    left tombstoned rows), as every counted round of the benchmark."""
    import gc
    import weakref

    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup

    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=5)
    store.async_bind = True
    sched = Scheduler(store)
    try:
        for rnd in range(2):
            sched.run_once()
            assert store.flush_binds(timeout=30)
            pods = list(store.pods.values())
            assert len(pods) == 32 and all(p.node_name for p in pods)
            refs = [weakref.ref(p) for p in pods]
            gc.collect()
            gc.disable()
            try:
                while pods:
                    store.delete_pod(pods.pop())
                assert [r() for r in refs] == [None] * 32
            finally:
                gc.enable()
            for g in range(8):
                store.add_pod_group(PodGroup(name=f"r{rnd}-{g}",
                                             min_member=4))
                for i in range(4):
                    store.add_pod(Pod(
                        name=f"r{rnd}-{g}-{i}",
                        annotations={GROUP_NAME_ANNOTATION: f"r{rnd}-{g}"},
                        containers=[{"cpu": "1", "memory": "1Gi"}]))
    finally:
        store.close()
