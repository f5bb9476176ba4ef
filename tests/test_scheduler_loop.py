"""Periodic scheduler-loop behaviors.

The cycle itself is covered everywhere; these tests pin the LOOP's
contracts: GC suspension during cycles, full passes of the collector
that the scheduler starts and the allocator does not (``_FullPasses``),
the leadership gate skipping cycles (and clearing stale failure
counts), and failure counting driving healthz.
"""

import gc
import sys
import threading
import time

import pytest

from volcano_tpu import scheduler as scheduler_mod
from volcano_tpu.api import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup
from volcano_tpu.cache import ClusterStore
from volcano_tpu.cache.bindqueue import BindDispatcher
from volcano_tpu.cache.interface import FakeBinder
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

OUT_OF_REACH = scheduler_mod._FullPasses.OUT_OF_REACH
NEVER = 10 ** 15


def small_store(binder=None):
    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    if binder is not None:
        store.binder = binder
    return store


def _let_go(policy):
    for ref in list(policy._holders.values()):
        policy.release(ref())


@pytest.fixture(autouse=True)
def policy(monkeypatch):
    """A collector policy of the test's own, in a process whose
    thresholds are CPython's: Schedulers that earlier tests of this
    worker left behind let go first, and the test's own at its end."""
    gc.collect()
    _let_go(scheduler_mod._full_passes)
    assert gc.get_threshold()[2] == 10 and BindDispatcher.idle_slot is None
    fresh = scheduler_mod._FullPasses()
    monkeypatch.setattr(scheduler_mod, "_full_passes", fresh)
    yield fresh
    _let_go(fresh)
    assert gc.get_threshold()[2] == 10 and BindDispatcher.idle_slot is None


@pytest.fixture
def passes():
    """Every pass of the collector while the test runs, as
    ``(generation, collected)``, through ``gc.callbacks``."""
    seen = []

    def hook(phase, info):
        if phase == "stop":
            seen.append((info["generation"], info["collected"]))

    gc.callbacks.append(hook)
    yield seen
    gc.callbacks.remove(hook)


def _full(passes):
    return sum(1 for gen, _ in passes if gen == 2)


def _until(cond, timeout=10.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.005)
    return cond()


def test_gc_suspended_during_cycle_and_restored_after():
    seen = {"during": None}
    store = small_store()
    sched = Scheduler(store)
    orig = sched._run_once_inner

    def probe():
        seen["during"] = gc.isenabled()
        return orig()

    sched._run_once_inner = probe
    assert gc.isenabled()
    sched.run_once()
    assert seen["during"] is False  # suspended inside the cycle
    assert gc.isenabled()           # restored after


def test_gc_stays_disabled_if_caller_disabled_it():
    """run_once must not re-enable GC behind a caller that turned it
    off deliberately (e.g. a benchmark harness)."""
    store = small_store()
    sched = Scheduler(store)
    gc.disable()
    try:
        sched.run_once()
        assert not gc.isenabled()
    finally:
        gc.enable()


# ----------------------------------------- who starts a full pass, and when


def _storm(passes):
    """Long-lived objects enough to make the allocator's own rule true:
    more than a quarter of what a full pass has just found alive, in
    more than ten passes of generation 1."""
    gc.collect()
    alive = len(gc.get_objects())
    del passes[:]
    return [[] for _ in range(max(alive // 2, 150_000))]


def test_the_allocator_starts_no_full_pass_after_a_cycle(policy, passes):
    sched = Scheduler(small_store())
    sched.run_once()
    assert gc.get_threshold() == (700, 10, OUT_OF_REACH)
    kept = _storm(passes)
    assert _full(passes) == 0 and gc.get_count()[2] > 10
    # Generations 0 and 1 are still the allocator's.
    assert sum(1 for gen, _ in passes if gen == 0) > 100
    assert sum(1 for gen, _ in passes if gen == 1) > 10
    # The control: once the scheduler has stopped, the same storm earns
    # one, so it is the policy that held it back and not the storm.
    sched.stop()
    assert gc.get_threshold() == (700, 10, 10)
    del kept
    kept = _storm(passes)
    assert _full(passes) >= 1


class _Gate(FakeBinder):
    """A binder that says what it saw and can be held: ``order`` takes
    ``bind`` on entry; a full pass shows as ``gen2`` beside it."""

    def __init__(self, order, hold=None, ask=None):
        super().__init__()
        self.order, self.hold, self.ask = order, hold, ask

    def bind_keys(self, keys, hosts):
        self.order.append("bind")
        if self.ask is not None:
            self.order.append(("asked", self.ask()))
        if self.hold is not None:
            assert self.hold.wait(10)
        super().bind_keys(keys, hosts)
        self.order.append("bound")


@pytest.fixture
def order():
    seen = []

    def hook(phase, info):
        if phase == "stop" and info["generation"] == 2:
            seen.append("gen2")

    gc.callbacks.append(hook)
    yield seen
    gc.callbacks.remove(hook)


def test_a_due_pass_waits_for_the_async_hand_over(policy, order,
                                                  monkeypatch):
    monkeypatch.setattr(Scheduler, "GC_FULL_EVERY", 1)
    monkeypatch.setattr(policy, "GROWTH_FLOOR", NEVER)
    hold = threading.Event()
    store = small_store(binder=_Gate(order, hold=hold))
    store.async_bind = True
    try:
        sched = Scheduler(store)
        sched.run_once()                # returns; the worker is held
        assert _until(lambda: "bind" in order)
        assert policy._due() == "cycles"
        assert not policy.run_if_due()  # whoever asks: a bind is on its way
        assert order == ["bind"]
        hold.set()
        # The worker delivers, lets go, finds its queue empty: its slot.
        assert _until(lambda: "gen2" in order)
        assert order == ["bind", "bound", "gen2"]
        assert policy._due() is None and not policy.run_if_due()
    finally:
        hold.set()
        store.close()


def test_a_due_pass_comes_after_the_sync_hand_over_in_the_gc_lane(
        policy, order, monkeypatch):
    monkeypatch.setattr(Scheduler, "GC_FULL_EVERY", 1)
    monkeypatch.setattr(policy, "GROWTH_FLOOR", NEVER)
    store = small_store(binder=_Gate(order, ask=lambda: policy.run_if_due()))
    sched = Scheduler(store)
    sched.run_once()                    # the first cycle: none was due in it
    assert order == ["bind", ("asked", False), "bound", "gen2"]
    rec = store.flight.recent()[-1]
    assert [s.name for s in rec.spans if s.cat == "gc"][-1] == "gc:gen2"
    gen2 = [s for s in rec.spans if s.name == "gc:gen2"][-1]
    lane = next(s for s in rec.spans if s.name == "gc" and s.lane == "gc")
    assert lane.ts_ns <= gen2.ts_ns
    assert gen2.ts_ns + gen2.dur_ns <= lane.ts_ns + lane.dur_ns
    assert gen2.args["reason"] == "cycles"
    assert rec.between["gc"]["full_by"] == {
        "cycles": 1, "growth": 0, "allocator": 0}


def _graph(n):
    """A graph of ``n`` objects that only a pass of the collector
    frees once it is let go of, as a job <-> task graph is."""
    ring = []
    ring.append(ring)
    ring.extend([] for _ in range(n))
    return ring


def test_growth_is_due_once_a_doubling_whatever_grows(policy, passes,
                                                      monkeypatch):
    """Rule (b) on both kinds of heap: cycles of the object session
    that each let go of a cyclic graph grown old stay under twice what
    the last pass left plus the floor (and the two graphs around), and
    a store that only grows is walked O(log) times, not once a cycle."""
    monkeypatch.setenv("VOLCANO_TPU_FASTPATH", "0")
    monkeypatch.setattr(policy, "GROWTH_FLOOR", 10_000)
    store = small_store()
    sched = Scheduler(store)
    inner = Scheduler._run_once_inner
    per_cycle, held = [0], []

    def dropping(self):
        inner(self)
        held[:] = [_graph(per_cycle[0])]    # the cycle before's goes

    monkeypatch.setattr(Scheduler, "_run_once_inner", dropping)
    sched.run_once()                    # the first pass: nothing to go by yet
    assert _full(passes) == 1 and store.flight.recent()[-1].path == "object"
    left = policy._live
    per_cycle[0] = left // 4
    most = 0
    for _ in range(16):                 # four times the heap, in garbage
        sched.run_once()
        most = max(most, sys.getallocatedblocks())
        left = max(left, policy._live)
        # What the events between two cycles do to it: the graph grows
        # old, and the young generations' passes free it no more.
        gc.collect(1)
    assert most <= 2 * left + policy.GROWTH_FLOOR + 2 * per_cycle[0]
    assert 2 <= _full(passes) - 1 <= 8
    assert sum(n for gen, n in passes if gen == 2) >= 10 * per_cycle[0]

    # A heap that only grows: every cycle finds an eighth of the first
    # heap more, alive; 32 cycles make it five times what it was.
    monkeypatch.setattr(Scheduler, "_run_once_inner", inner)
    del held[:]
    gc.collect()
    del passes[:]
    step, kept = policy._live // 8, []
    for _ in range(32):
        kept.append([[] for _ in range(step)])
        sched.run_once()
    assert 1 <= _full(passes) <= 3      # log2(5), not 32
    assert sum(n for gen, n in passes if gen == 2) < step


@pytest.mark.parametrize("setting", ["off", "third", "first_two"])
def test_a_callers_own_collector_settings_survive(setting, policy, passes,
                                                  monkeypatch):
    monkeypatch.setattr(Scheduler, "GC_FULL_EVERY", 1)
    store = small_store()
    sched = Scheduler(store)
    found = gc.get_threshold()
    try:
        if setting == "off":
            gc.disable()
        elif setting == "third":
            gc.set_threshold(700, 10, 50)
        else:
            gc.set_threshold(500, 8, 10)
        mine = gc.get_threshold()
        del passes[:]
        sched.run_once()
        if setting == "off":
            # Nothing installed, no pass of any generation started.
            assert not gc.isenabled() and gc.get_threshold() == mine
            assert passes == [] and not policy.run_if_due()
        elif setting == "third":
            assert gc.get_threshold() == mine
        else:
            assert gc.get_threshold() == (500, 8, OUT_OF_REACH)
        sched.stop()
        assert gc.get_threshold() == mine
    finally:
        gc.enable()
        gc.set_threshold(*found)


@pytest.mark.parametrize("stores", [1, 2])
@pytest.mark.parametrize("how", ["stop", "close", "drop"])
def test_the_last_scheduler_puts_the_threshold_back(how, stores, policy):
    a = small_store()
    b = a if stores == 1 else small_store()
    first, second = Scheduler(a), Scheduler(b)
    first.run_once()
    second.run_once()
    assert gc.get_threshold() == (700, 10, OUT_OF_REACH)
    assert BindDispatcher.idle_slot == policy.run_if_due
    if how == "stop":
        first.stop()
    elif how == "close":
        a.close()
    else:
        del first
    # One store closed lets go of every Scheduler on it.
    held = how != "close" or stores == 2
    assert (gc.get_threshold()[2] == OUT_OF_REACH) is held
    if how == "stop":
        second.stop()
    elif how == "close":
        b.close()
    else:
        del second
    assert gc.get_threshold() == (700, 10, 10)
    assert BindDispatcher.idle_slot is None
    if how != "drop":
        second.run_once()               # and the next cycle takes it up again
        assert gc.get_threshold() == (700, 10, OUT_OF_REACH)


def _repend_every_cycle(store):
    """Steady-state feed: re-pend whatever the commit just bound, so
    every cycle hands a batch over."""
    import numpy as np

    from volcano_tpu.api import TaskStatus

    st_bound = int(TaskStatus.Bound)

    def feed(fc):
        rows = np.flatnonzero(
            (fc.m.p_status[:fc.Pn] == st_bound) & fc.m.p_alive[:fc.Pn]
        )
        if len(rows):
            fc._unbind_rows(rows)

    store.cycle_feed = feed


def test_the_loop_and_the_idle_slot_share_one_due(policy, passes,
                                                  monkeypatch):
    monkeypatch.setattr(Scheduler, "GC_FULL_EVERY", 3)
    monkeypatch.setattr(policy, "GROWTH_FLOOR", NEVER)
    store = small_store()
    store.async_bind = True
    _repend_every_cycle(store)
    cycles = [0]
    inner = Scheduler._run_once_inner

    def counting(self):
        cycles[0] += 1
        return inner(self)

    monkeypatch.setattr(Scheduler, "_run_once_inner", counting)
    sched = Scheduler(store, schedule_period=0.005)
    sched.run()
    try:
        assert _until(lambda: _full(passes) >= 3)
    finally:
        sched.stop()
        store.close()
    # Every pass had its three cycles: the slack and the slot, whichever
    # came first, took the one that was due and left none for the other.
    assert 3 * _full(passes) <= cycles[0]
    assert len(store.binder.binds) >= 8


def test_loop_runs_full_collect_every_n_cycles(monkeypatch):
    collects = {"full": 0}
    real_collect = gc.collect

    def counting(generation=2):
        if generation == 2:
            collects["full"] += 1
        return real_collect(generation)

    monkeypatch.setattr(gc, "collect", counting)
    monkeypatch.setattr(Scheduler, "GC_FULL_EVERY", 3)
    store = small_store()
    sched = Scheduler(store, schedule_period=0.01)
    sched.run()
    try:
        deadline = time.time() + 5.0
        while collects["full"] < 2 and time.time() < deadline:
            time.sleep(0.02)
    finally:
        sched.stop()
    assert collects["full"] >= 2, "periodic full collect never ran"


def test_leadership_gate_skips_cycles_and_clears_failures():
    store = small_store()
    leading = threading.Event()
    sched = Scheduler(store, schedule_period=0.01,
                      gate=leading.is_set)
    # Simulate prior leader-era failures: standing by must clear them
    # (a standby's health check must not stay red).
    sched._consecutive_failures = sched.UNHEALTHY_AFTER
    assert not sched.healthy()
    sched.run()
    try:
        time.sleep(0.1)
        assert len(store.binder.binds) == 0  # no cycles while standby
        assert sched.healthy()               # failures cleared
        leading.set()
        deadline = time.time() + 5.0
        while len(store.binder.binds) < 8 and time.time() < deadline:
            time.sleep(0.02)
    finally:
        sched.stop()
    assert len(store.binder.binds) == 8


def test_stop_joins_thread_and_drains_inflight_dispatch():
    """stop() must leave the loop thread DEAD (not a timed-out join that
    silently leaks a scheduling thread behind a restart) and must drain
    the pipelined dispatch parked between cycles — the solved pods stay
    Pending and re-place after a restart."""
    store = small_store()
    store.pipeline = True
    # Every cycle dispatches a fresh solve, so an in-flight handle is
    # parked whenever the loop is between cycles.
    _repend_every_cycle(store)
    sched = Scheduler(store, schedule_period=0.01)
    sched.run()
    t = sched._thread
    assert t is not None
    deadline = time.time() + 10.0
    while (getattr(store, "_inflight_solve", None) is None
           and time.time() < deadline):
        time.sleep(0.005)
    assert store._inflight_solve is not None, "no dispatch ever parked"
    sched.stop()
    assert not t.is_alive()          # the loop thread is DEAD
    assert sched._thread is None     # and not retained for a re-join
    # The parked device future was abandoned, not leaked.
    assert getattr(store, "_inflight_solve", None) is None


def test_repeated_failures_flip_healthz(monkeypatch):
    store = small_store()
    sched = Scheduler(store, schedule_period=0.01)

    def boom():
        raise RuntimeError("cycle exploded")

    sched.run_once = boom
    assert sched.healthy()
    sched.run()
    try:
        deadline = time.time() + 5.0
        while sched.healthy() and time.time() < deadline:
            time.sleep(0.02)
    finally:
        sched.stop()
    assert not sched.healthy()
