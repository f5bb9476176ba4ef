"""The time between two cycles accounts for itself (ISSUE 35):
``CycleRecord.between`` holds what the store did since the previous
record was sealed: its event handlers' calls by kind (exact), their
seconds by kind and phase (one timed call in ``SAMPLE_STRIDE``), the
collector's passes (one ``gc.callbacks`` hook a process), the pod
table's compactions and the bind worker's busy time.  Nothing per pod.

All on the CPU; most through a bare ``CycleScope`` (what
``Scheduler.run_once()`` opens), two through the scheduler itself.
"""

import gc
import sys
import threading
import time
import weakref

import pytest

from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    PriorityClass,
    Queue,
    ResourceQuota,
)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.obs import export, trace
from volcano_tpu.obs.recorder import CycleRecord
from volcano_tpu.obs.trace import (
    EVENT_KINDS,
    SAMPLE_STRIDE,
    _busy_inside,
)
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

pytestmark = pytest.mark.tier1

POD_PHASES = {"lock_wait", "objects", "feat", "columns", "audit",
              "journey", "notify"}


class _Named:
    """A controller-plane record: a batch job has ``key``, a command
    ``name``."""

    def __init__(self, name):
        self.key = self.name = name


def _pod(name, group="pg"):
    return Pod(name=name, annotations={GROUP_NAME_ANNOTATION: group},
               containers=[{"cpu": "1", "memory": "1Gi"}])


def _node(name):
    return Node(name=name, allocatable={"cpu": "64", "memory": "256Gi",
                                        "pods": 256})


# kind -> the call that is one event of that kind, the i-th of a test.
HANDLERS = {
    "Pod/add": lambda s, i: s.add_pod(_pod(f"a{i}")),
    "Pod/update": lambda s, i: s.update_pod(_pod(f"u{i}")),
    "Pod/delete": lambda s, i: s.delete_pod(_pod(f"d{i}")),
    "PodGroup/add": lambda s, i: s.add_pod_group(PodGroup(name=f"g{i}")),
    "PodGroup/update": lambda s, i: s.update_pod_group(
        PodGroup(name=f"g{i}", min_member=2)),
    "PodGroup/delete": lambda s, i: s.delete_pod_group(f"default/g{i}"),
    "Node/add": lambda s, i: s.add_node(_node(f"n{i}")),
    "Node/update": lambda s, i: s.update_node(_node(f"n{i}")),
    "Node/delete": lambda s, i: s.delete_node(f"n{i}"),
    "Queue/add": lambda s, i: s.add_queue(Queue(name=f"q{i}")),
    "Queue/update": lambda s, i: s.update_queue(Queue(name=f"q{i}",
                                                      weight=2)),
    "Queue/delete": lambda s, i: s.delete_queue(f"q{i}"),
    "PriorityClass/add": lambda s, i: s.add_priority_class(
        PriorityClass(name=f"pc{i}", value=i)),
    "PriorityClass/delete": lambda s, i: s.delete_priority_class(f"pc{i}"),
    "ResourceQuota/add": lambda s, i: s.add_resource_quota(
        ResourceQuota(name=f"rq{i}")),
    "Job/add": lambda s, i: s.add_batch_job(_Named(f"j{i}")),
    "Job/update": lambda s, i: s.update_batch_job(_Named(f"j{i}")),
    "Job/delete": lambda s, i: s.delete_batch_job(f"j{i}"),
    "Command/add": lambda s, i: s.add_command(_Named(f"c{i}")),
    "Command/delete": lambda s, i: s.delete_command(f"c{i}"),
}


def _seal(store):
    """What ``run_once()`` does around a cycle, without the cycle: open
    the frame, hand it a record, seal.  Returns the sealed record."""
    with store.tracer.cycle(store.flight) as scope:
        scope.submit(CycleRecord(path="test"))
    return store.flight.recent()[-1]


def _store():
    """A store whose construction (the default queue) is already in a
    sealed record, so the next block starts empty."""
    store = ClusterStore()
    _seal(store)
    return store


class _Clock:
    """Every reading is 1,000 ns after the previous one, so a phase
    reads 1 us for each stamp that closes a stretch of it."""

    def __init__(self):
        self.t = 0
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.t += 1000
        return self.t


@pytest.fixture
def no_collector():
    """The collector off: a real pass inside a test with an injected
    clock would take real nanoseconds out of a timed call."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


@pytest.fixture
def clock(no_collector, monkeypatch):
    """One injected clock for the accounts' timed calls and for the
    process's collector hook."""
    clk = _Clock()
    monkeypatch.setattr(trace._collector, "clock", clk)
    return clk


def _clocked_store(clock, monkeypatch):
    store = _store()
    store._between.clock = clock
    # The hand-made passes below tell this store's account alone.
    monkeypatch.setattr(trace._collector, "_tracers",
                        (weakref.ref(store.tracer),))
    return store


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: the names opened,
    each with the names open above it."""

    def __init__(self):
        self.open = []
        self.seen = []

    def __call__(self, name):
        log = self

        class _Annotation:
            def __enter__(self):
                log.seen.append((name, tuple(log.open)))
                log.open.append(name)

            def __exit__(self, *exc):
                assert log.open.pop() == name

        return _Annotation()


def _paths(obj, prefix=()):
    """Every key path of a nested dict."""
    if not isinstance(obj, dict):
        return [prefix]
    return [p for k, v in sorted(obj.items())
            for p in _paths(v, prefix + (k,))]


# ------------------------------------------------ (1) exact counts by kind


def test_the_kinds_are_the_stores_public_event_handlers():
    names = {f"{verb}_{what}" for verb in ("add", "update", "delete")
             for what in ("pod", "pod_group", "node", "queue",
                          "priority_class", "resource_quota", "batch_job",
                          "command")}
    assert len(HANDLERS) == len(EVENT_KINDS) == 20
    assert set(HANDLERS) == set(EVENT_KINDS)
    assert sum(hasattr(ClusterStore, n) for n in names) == 20


@pytest.mark.parametrize("kind", EVENT_KINDS)
def test_a_kinds_calls_are_counted_exactly_and_once(kind):
    store = _store()
    n = 3 + EVENT_KINDS.index(kind)
    for i in range(n):
        HANDLERS[kind](store, i)
    rec = _seal(store)
    assert set(rec.between["events"]) == {kind}
    assert rec.between["events"][kind]["n"] == n
    assert rec.between["stride"] == SAMPLE_STRIDE
    # The record after holds none of them.
    assert _seal(store).between["events"] == {}


def test_a_scripted_sequence_lands_in_the_next_cycles_record_only():
    store = synthetic_cluster(seed=7, n_nodes=8, n_pods=32, gang_size=4)
    store.async_bind = True
    sched = Scheduler(store)
    sched.run_once()
    store.flush_binds()
    first = store.flight.recent()[-1].between
    # The cluster's own construction: 8 nodes, 8 gangs of 4, the
    # default queue (synthetic_cluster may add its own besides).
    assert first["events"]["Node/add"]["n"] == 8
    assert first["events"]["Pod/add"]["n"] == 32
    assert first["events"]["PodGroup/add"]["n"] == 8
    script = [("Node/add", 2), ("PodGroup/add", 3), ("Pod/add", 5),
              ("Pod/update", 2), ("Pod/delete", 4), ("PodGroup/delete", 1),
              ("Queue/add", 1), ("Queue/update", 1), ("Node/update", 2)]
    for kind, n in script:
        for i in range(n):
            HANDLERS[kind](store, 100 + i)
    sched.run_once()
    store.flush_binds()
    rec = store.flight.recent()[-1]
    assert rec.path == "fast"
    assert {k: v["n"] for k, v in rec.between["events"].items()} == dict(script)
    assert rec.to_dict()["between"] == rec.between
    assert rec.between["t0_ns"] < rec.between["t1_ns"]
    # The interval ends where the cycle's outer span begins.
    cycle = next(s for s in rec.spans if s.name == "cycle")
    assert rec.between["t1_ns"] == cycle.ts_ns
    sched.run_once()
    assert store.flight.recent()[-1].between["events"] == {}


def test_the_object_path_seals_the_block_too(monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_FASTPATH", "0")
    store = synthetic_cluster(seed=11, n_nodes=4, n_pods=8, gang_size=2)
    Scheduler(store).run_once()
    rec = store.flight.recent()[-1]
    assert rec.path == "object"
    assert rec.between["events"]["Pod/add"]["n"] == 8
    assert "between" not in rec.lanes      # no lane: the lanes rule stands


def test_handlers_called_from_many_threads_lose_no_count():
    store = _store()
    store.add_pod_group(PodGroup(name="pg"))
    threads, each = 8, 400
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [
            threading.Thread(target=lambda t=t: [
                store.add_pod(_pod(f"t{t}-{i}")) for i in range(each)])
            for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(was)
    ev = _seal(store).between["events"]["Pod/add"]
    assert ev["n"] == threads * each
    assert store._between._open is None


# ------------------------------------- (2) the block's size is the kinds'


def _burst(n_pods):
    store = _store()
    for g in range(n_pods // 4):
        store.add_pod_group(PodGroup(name=f"g{g}", min_member=4))
        for i in range(4):
            store.add_pod(_pod(f"g{g}-{i}", group=f"g{g}"))
    return _seal(store)


def test_a_10000_pod_interval_has_the_keys_and_size_of_a_100_pod_one():
    small, large = _burst(100), _burst(10_000)
    assert small.between["events"]["Pod/add"]["n"] == 100
    assert large.between["events"]["Pod/add"]["n"] == 10_000
    assert _paths(small.between) == _paths(large.between)
    assert (len(_paths(small.to_dict(include_spans=True)))
            == len(_paths(large.to_dict(include_spans=True))))
    # Beside the block only the collector's larger passes are recorded,
    # one event a pass of generation 1 or 2: nothing per pod.
    assert ([s.name for s in small.spans if s.cat != "gc"]
            == [s.name for s in large.spans if s.cat != "gc"] == ["cycle"])


# --------------------------- (3) sampled seconds, exact under a fake clock


def test_one_call_in_the_stride_is_timed_and_the_estimate_is_exact(
        clock, monkeypatch):
    store = _clocked_store(clock, monkeypatch)
    store.mark_objects_stale()
    store.add_pod_group(PodGroup(name="pg"))
    n = 200
    for i in range(n):
        store.add_pod(_pod(f"p{i}"))
    ev = _seal(store).between["events"]["Pod/add"]
    # The 1st, 62nd, 123rd and 184th call: one in 61, a prime, so that
    # the timed place moves through a gang.
    assert ev["n"] == n and ev["samples"] == 4
    assert SAMPLE_STRIDE == 61
    # A timed add: one stamp closes each of lock_wait, objects, feat,
    # audit, journey and notify, three close a stretch of columns.
    per_call_us = {"lock_wait": 1, "objects": 1, "feat": 1, "columns": 3,
                   "audit": 1, "journey": 1, "notify": 1}
    assert set(ev["phases"]) == POD_PHASES
    for name, us in per_call_us.items():
        assert ev["phases"][name]["sampled_s"] == pytest.approx(
            4 * us * 1e-6, abs=1e-12)
        assert ev["phases"][name]["est_s"] == pytest.approx(
            4 * us * 61e-6, abs=1e-12)
    # The phases of the timed calls sum to their whole.
    assert ev["sampled_s"] == pytest.approx(4 * 9e-6, abs=1e-12)
    assert ev["sampled_s"] == pytest.approx(
        sum(p["sampled_s"] for p in ev["phases"].values()), abs=1e-12)
    # The estimate is sum x stride: a timed call stands for 61.
    assert ev["est_s"] == pytest.approx(4 * 9e-6 * 61, abs=1e-12)


@pytest.mark.parametrize("kind,phases_us", [
    ("Pod/update", {"lock_wait": 1, "objects": 1, "feat": 1, "columns": 3,
                    "audit": 1, "journey": 1, "notify": 1}),
    ("Pod/delete", {"lock_wait": 1, "objects": 1, "notify": 1}),
    ("PodGroup/add", {"lock_wait": 1, "held": 1}),
    ("Node/add", {"lock_wait": 1, "held": 1}),
    ("Queue/delete", {"lock_wait": 1, "held": 1}),
])
def test_every_kind_is_timed_whole_and_by_its_own_phases(
        kind, phases_us, clock, monkeypatch):
    store = _clocked_store(clock, monkeypatch)
    n = 2 * SAMPLE_STRIDE + 1           # the 1st, 62nd and 123rd call
    for i in range(n):
        HANDLERS[kind](store, i)
    block = _seal(store).between
    ev = block["events"][kind]
    assert ev["n"] == n and ev["samples"] == 3
    assert {k: round(v["sampled_s"] * 1e6 / 3) for k, v in
            ev["phases"].items()} == phases_us
    whole_us = sum(phases_us.values())
    assert ev["est_s"] == pytest.approx(3 * whole_us * 61e-6, abs=1e-12)
    # The lock was held for everything but the wait for it.
    assert block["lock_held_s"] == pytest.approx(
        3 * (whole_us - 1) * 61e-6, abs=1e-12)


def test_a_delete_of_a_live_pod_is_timed_through_the_mirror(
        clock, monkeypatch):
    store = _clocked_store(clock, monkeypatch)
    store.add_pod_group(PodGroup(name="pg"))
    pod = _pod("p0")
    store.add_pod(pod)
    store.delete_pod(pod)               # the kind's first call: timed
    ev = _seal(store).between["events"]["Pod/delete"]
    assert ev["samples"] == 1
    assert {k: round(v["sampled_s"] * 1e6) for k, v in
            ev["phases"].items()} == {
        "lock_wait": 1, "objects": 1, "columns": 2, "audit": 1,
        "journey": 1, "notify": 1}


def test_a_handler_that_raises_leaves_no_timed_call_open(no_collector):
    store = _store()

    def boom(kind, event, obj):
        raise RuntimeError("watcher")

    store.watch(boom)
    with pytest.raises(RuntimeError, match="watcher"):
        store.add_pod(_pod("p0"))       # the kind's first call: timed
    with pytest.raises(RuntimeError, match="watcher"):
        store.add_node(_node("n0"))
    assert store._between._open is None
    ev = _seal(store).between["events"]
    assert ev["Pod/add"]["samples"] == 1 and ev["Node/add"]["samples"] == 1


# ------------------------------------------------------ (4) the collector


def _pass(gen, collected=0):
    """One pass of the collector, by hand."""
    trace._collector.hook("start", {"generation": gen, "collected": 0,
                                    "uncollectable": 0})
    trace._collector.hook("stop", {"generation": gen,
                                   "collected": collected,
                                   "uncollectable": 1})


def test_a_pass_inside_a_timed_call_is_taken_out_of_it(clock, monkeypatch):
    store = _clocked_store(clock, monkeypatch)
    store.add_pod_group(PodGroup(name="pg"))
    store.watch(lambda kind, event, obj: _pass(2, collected=5))
    store.add_pod(_pod("p0"))           # timed; the watcher runs in it
    block = _seal(store).between
    ev = block["events"]["Pod/add"]
    # notify: the stamp after the mirror, two readings by the hook, the
    # closing stamp: 3 us, of which the pass's 1 us is not the call's.
    assert ev["phases"]["notify"]["sampled_s"] == pytest.approx(2e-6)
    assert ev["sampled_s"] == pytest.approx(10e-6)
    assert block["gc"]["gen2"] == {"n": 1, "s": 1e-6, "collected": 5}
    assert block["gc"]["longest_s"] == 1e-6
    assert block["gc"]["gen0"]["n"] == block["gc"]["gen1"]["n"] == 0
    assert block["gc"]["in_cycle"]["n"] == 0


def test_a_pass_that_interrupts_a_locked_region_waits_for_no_lock(
        clock, monkeypatch):
    # Since 3.12 the collector runs wherever the interpreter checks
    # its breaker, inside ``with lock:`` too, and its hook runs on
    # that thread: a lock the hook took could be one the thread holds.
    store = _clocked_store(clock, monkeypatch)
    done = []

    def interrupted():
        with store._between._lock, trace._collector._lock:
            _pass(2, collected=1)
            done.append(True)

    worker = threading.Thread(target=interrupted, daemon=True)
    worker.start()
    worker.join(timeout=10)
    assert done and not hasattr(store.tracer, "_lock")
    assert _seal(store).between["gc"]["gen2"]["n"] == 1


@pytest.mark.parametrize("gen", [0, 1, 2])
def test_a_pass_inside_an_open_cycle_is_the_cycles(gen, clock, monkeypatch):
    store = _clocked_store(clock, monkeypatch)
    _pass(gen, collected=2)             # between two cycles
    with store.tracer.cycle(store.flight) as scope:
        _pass(gen, collected=3)         # inside the cycle
        _pass(0)
        scope.submit(CycleRecord(path="test"))
    g = store.flight.recent()[-1].between["gc"]
    assert g[f"gen{gen}"] == {"n": 1, "s": 1e-6, "collected": 2}
    assert g["in_cycle"] == {"n": 2, "s": 2e-6, "collected": 3}
    assert g["longest_s"] == 1e-6
    # And the next record has neither.
    g = _seal(store).between["gc"]
    assert g[f"gen{gen}"]["n"] == 0 and g["in_cycle"]["n"] == 0
    assert g["longest_s"] == 0.0


def test_full_passes_are_counted_by_who_started_them(clock, monkeypatch):
    """``gc.full_by`` (ISSUE 43): the scheduler's own full passes under
    their reason, any other under ``allocator``, seal to seal, so the
    one at the end of the cycle's ``gc`` lane is this record's too."""
    store = _clocked_store(clock, monkeypatch)
    with trace.scheduled_pass("growth"):
        _pass(1)                        # a young pass has no starter
        _pass(2, collected=4)           # the bind worker's idle slot
    _pass(2)                            # the allocator's count, a caller's collect()
    with store.tracer.cycle(store.flight) as scope:
        with trace.scheduled_pass("cycles"):
            _pass(2, collected=1)       # the end of run_once()'s gc lane
        scope.submit(CycleRecord(path="test"))
    rec = store.flight.recent()[-1]
    g = rec.between["gc"]
    assert g["full_by"] == {"cycles": 1, "growth": 1, "allocator": 1}
    assert g["gen2"] == {"n": 2, "s": 2e-6, "collected": 4}
    assert g["in_cycle"] == {"n": 1, "s": 1e-6, "collected": 1}
    assert [(s.name, s.args.get("reason")) for s in rec.spans
            if s.cat == "gc"] == [
        ("gc:gen1", None), ("gc:gen2", "growth"), ("gc:gen2", "allocator"),
        ("gc:gen2", "cycles")]
    assert trace._collector.reason is None
    # And the next record has none of them.
    assert _seal(store).between["gc"]["full_by"] == {
        "cycles": 0, "growth": 0, "allocator": 0}


def test_a_real_pass_is_counted_and_recorded_without_a_hand(monkeypatch):
    store = _store()
    gc.collect()
    block = _seal(store).between
    assert block["gc"]["gen2"]["n"] >= 1 and block["gc"]["gen2"]["s"] > 0
    assert block["gc"]["longest_s"] > 0


def test_a_compaction_is_counted_whole_and_taken_out_of_the_timed_delete():
    store = _store()
    store.add_pod_group(PodGroup(name="pg"))
    pods = [_pod(f"p{i}") for i in range(4200)]
    for pod in pods:
        store.add_pod(pod)
    _seal(store)
    for pod in pods[:2200]:             # past half of 4,096+ rows
        store.delete_pod(pod)
    block = _seal(store).between
    assert store.mirror.compact_gen >= 1
    assert block["compactions"] == store.mirror.compact_gen
    assert block["compact_s"] > 0
    assert store.mirror.between is store._between   # rode the swap
    # Counted once: the deletes' estimate does not hold it as well.
    ev = block["events"]["Pod/delete"]
    assert ev["est_s"] / ev["n"] < block["compact_s"] / 10


def _sized(name, cpu):
    pod = _pod(name)
    pod.containers = [{"cpu": str(cpu), "memory": "1Gi"}]
    return pod


@pytest.mark.parametrize("specs", [1, 9, 200])
def test_the_specs_encoded_are_counted_exactly_and_once(specs):
    """``specs_encoded`` is the pods whose spec the mirror had not met
    (``StoreMirror._feat``): the distinct specs of a first batch, none
    of a second batch of the same specs, and the one a new spec adds."""
    store = _store()
    store.add_pod_group(PodGroup(name="pg"))
    for i in range(1000):
        store.add_pod(_sized(f"a{i}", 1 + i % specs))
    block = _seal(store).between
    assert block["events"]["Pod/add"]["n"] == 1000
    assert block["specs_encoded"] == block["spec_rows"] == specs
    for i in range(1000):
        store.add_pod(_sized(f"b{i}", 1 + i % specs))
    store.update_pod(_sized("b0", 1))           # met: an update is none
    assert _seal(store).between["specs_encoded"] == 0
    store.add_pod(_sized("c0", 1000))
    store.update_pod(_sized("c0", 1001))        # not met: an update is one
    block = _seal(store).between
    assert (block["specs_encoded"], block["spec_rows"]) == (2, specs + 2)
    block = _seal(store).between                # a level: it stays
    assert (block["specs_encoded"], block["spec_rows"]) == (0, specs + 2)


def test_the_specs_met_before_a_compaction_are_met_after_it():
    store = _store()
    store.add_pod_group(PodGroup(name="pg"))
    pods = [_sized(f"p{i}", 1 + i % 7) for i in range(4200)]
    for pod in pods:
        store.add_pod(pod)
    assert _seal(store).between["specs_encoded"] == 7
    for pod in pods[:2200]:
        store.delete_pod(pod)
    assert store.mirror.compact_gen >= 1
    for i in range(100):
        store.add_pod(_sized(f"q{i}", 1 + i % 7))
    block = _seal(store).between
    assert block["compactions"] == 1 and block["specs_encoded"] == 0
    assert store._between.specs_encoded == 7    # a lifetime count
    assert block["spec_rows"] == 7


@pytest.mark.parametrize("own", [0, 40, 400])
def test_spec_rows_is_the_spec_tables_level_and_falls_at_a_compaction(own):
    """``spec_rows`` is the rows of the mirror's spec table as the
    cycle's snapshot finds them.  ``events.Pod/add.n`` less
    ``specs_encoded`` is the adds that wrote no ragged row: the columns
    the readers gather from grew by ``specs_encoded`` rows and no more.
    Pods that each brought a spec of their own take its row along when
    a compaction finds them gone."""
    store = _store()
    m = store.mirror
    store.add_pod_group(PodGroup(name="pg"))
    ragged = (m.c_req, m.c_init_req, m.c_sel, m.c_ports, m.c_ip_aff,
              m.c_ip_anti, m.c_ip_soft)
    loners = [_sized(f"l{i}", 100 + i) for i in range(own)]
    crowd = [_sized(f"p{i}", 1 + i % 3) for i in range(4300 - own)]
    for pod in loners + crowd:
        store.add_pod(pod)
    block = _seal(store).between
    assert block["events"]["Pod/add"]["n"] == 4300
    assert block["specs_encoded"] == block["spec_rows"] == own + 3
    assert {col._n for col in ragged} == {own + 3}
    for i in range(500):
        store.add_pod(_sized(f"q{i}", 1 + i % 3))
    block = _seal(store).between
    assert block["events"]["Pod/add"]["n"] - block["specs_encoded"] == 500
    assert block["spec_rows"] == own + 3
    assert {col._n for col in ragged} == {own + 3}
    for pod in loners + crowd[:2500]:
        store.delete_pod(pod)
    block = _seal(store).between
    assert block["compactions"] == 1
    assert block["spec_rows"] == len(m.s_feat) == 3
    assert {col._n for col in ragged} == {3}


# ------------------------------------------- (5) one hook, weak references


def test_fifty_stores_made_and_dropped_leave_one_hook_and_no_account():
    ClusterStore()                      # the process's first, if it is
    before = len(gc.callbacks)
    assert gc.callbacks.count(trace._collector.hook) == 1
    accounts = []
    for _ in range(50):
        store = ClusterStore()
        store.add_node(_node("n0"))
        accounts.append(weakref.ref(store._between))
        del store
    gc.collect()
    assert len(gc.callbacks) == before
    assert all(ref() is None for ref in accounts)
    keep = ClusterStore()               # registering prunes the dead
    assert all(r() is not None for r in trace._collector._tracers)
    assert any(r() is keep.tracer for r in trace._collector._tracers)


# ------------------------------------------------ (6) VOLCANO_TPU_TRACE=0


def test_with_tracing_off_the_counts_stay_and_nothing_is_timed(
        clock, monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_TRACE", "0")
    store = _clocked_store(clock, monkeypatch)
    log = store.tracer.annotate = _Annotations()
    reads = clock.reads
    store.add_pod_group(PodGroup(name="pg"))
    pods = [_pod(f"p{i}") for i in range(130)]
    for pod in pods:
        store.add_pod(pod)
    for pod in pods:
        store.delete_pod(pod)
    assert clock.reads == reads         # no stamp on the event path
    _pass(2, collected=9)
    rec = _seal(store)
    assert rec.between["events"] == {
        "PodGroup/add": {"n": 1}, "Pod/add": {"n": 130},
        "Pod/delete": {"n": 130}}
    # The one spec of the 130 pods was encoded once: counted, not timed.
    assert rec.between["specs_encoded"] == 1
    assert rec.between["spec_rows"] == 1
    assert set(rec.between) == {"t0_ns", "t1_ns", "stride", "events",
                                "specs_encoded", "spec_rows"}
    assert rec.spans == [] and not store.tracer._events
    assert [name for name, _ in log.seen] == ["vc:cycle"]  # no vc:gc2


# ------------------------------------- (7) gc:gen2 on the record and a track


def test_a_gen2_pass_reaches_the_records_spans_and_the_gc_track(
        clock, monkeypatch):
    store = _clocked_store(clock, monkeypatch)
    log = store.tracer.annotate = _Annotations()
    _pass(0)
    _pass(1, collected=3)
    _pass(2, collected=7)
    rec = _seal(store)
    assert [name for name, _ in log.seen if name.startswith("vc:gc")] == [
        "vc:gc1", "vc:gc2"]             # generation 0 opens none
    passes = [s for s in rec.spans if s.cat == "gc"]
    assert [s.name for s in passes] == ["gc:gen1", "gc:gen2"]
    gen2 = passes[1]
    assert gen2.tid == "gc" and gen2.dur_ns == 1000
    assert gen2.args == {"collected": 7, "uncollectable": 1,
                         "reason": "allocator"}
    # On the tracer's clock: the hook's fifth reading began it.
    assert gen2.ts_ns == store.tracer._anchor_ns + 5000
    events = export.trace_events([rec])
    tracks = {ev["args"]["name"]: ev["tid"] for ev in events
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    exported = next(ev for ev in events if ev["name"] == "gc:gen2")
    assert exported["tid"] == tracks["gc"] and exported["cat"] == "gc"
    assert exported["args"]["collected"] == 7
    # The block itself is one event on the store's track.
    block = next(ev for ev in events if ev["name"] == "between")
    assert block["tid"] == tracks["store"]
    assert block["ts"] == rec.between["t0_ns"] / 1e3
    assert block["args"]["gc"]["gen2"]["n"] == 1
    assert "t0_ns" not in block["args"]


# ------------------------------------------------------ (8) bind_busy_s


class _Ev:
    def __init__(self, name, ts_ns, dur_ns, tid="bind"):
        self.name, self.ts_ns, self.dur_ns, self.tid = (
            name, ts_ns, dur_ns, tid)


@pytest.mark.parametrize("events,busy_ns", [
    ([("bind:binder", 50, 100)], 50),                   # half inside, left
    ([("bind:on_success", 150, 100)], 50),              # half inside, right
    ([("bind:binder", 0, 50), ("bind:release", 250, 9)], 0),    # outside
    ([("bind:materialize", 110, 20), ("bind:binder", 120, 30),
      ("bind:on_success", 170, 10)], 50),               # a union, a gap
    ([("bind:queue_wait", 100, 100)], 0),               # waiting, not work
    ([("bind:binder", 50, 300)], 100),                  # over all of it
])
def test_busy_inside_is_the_union_cut_to_the_interval(events, busy_ns):
    spans = [_Ev(*e) for e in events] + [_Ev("bind:binder", 100, 100,
                                             tid="rpc")]
    assert _busy_inside(spans, 100, 200) == pytest.approx(busy_ns * 1e-9)


def test_bind_busy_is_the_inside_half_of_two_hand_made_events():
    store = _store()
    t0 = store._between._t0_ns          # where the previous record sealed
    ms = 1_000_000
    event = store.tracer.event
    event("bind:binder", "bind", t0 - ms, 2 * ms, tid="bind")
    event("bind:on_success", "bind", t0 + ms // 2, ms, tid="bind")
    event("bind:queue_wait", "bind", t0, 5 * ms, tid="bind")
    time.sleep(0.004)
    block = _seal(store).between
    assert block["t1_ns"] - block["t0_ns"] > 3 * ms
    # [t0 - 1, t0 + 1] cut to [t0, t0 + 1], joined with [t0 + .5, t0 + 1.5].
    assert block["bind_busy_s"] == pytest.approx(1.5e-3, abs=1e-12)
    # The same worker's batch through the real dispatcher.
    store = synthetic_cluster(seed=13, n_nodes=8, n_pods=32, gang_size=4)
    store.async_bind = True
    sched = Scheduler(store)
    sched.run_once()
    store.flush_binds()
    time.sleep(0.002)
    sched.run_once()
    recs = store.flight.recent()
    busy = sum(s.dur_ns for r in recs for s in r.spans
               if s.tid == "bind" and s.name in trace.BIND_BUSY)
    assert busy > 0
    assert 0 <= recs[-1].between["bind_busy_s"] <= busy * 1e-9 + 1e-12
