"""Solver replica pool (ISSUE 15, volcano_tpu/solver_pool.py).

Pins the pool's acceptance contracts: multi-process parity vs the
single connection, hedged-dispatch first-wins determinism with the
slow reply drained, failover-within-one-cycle with zero lost pods,
what-if-offload overlap with unchanged commit semantics, pool-of-1
bitwise equality to today's path, and the kill switch.
"""

import os
import threading
import time

import numpy as np
import pytest

from volcano_tpu.api import TaskStatus
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.solver_pool import SolverPool, make_solver_client
from volcano_tpu.solver_service import RemoteSolver, SolverServer
from volcano_tpu.synth import synthetic_cluster

from test_remote_solver import _local_loop, _spawn_solver

ST_BOUND = int(TaskStatus.Bound)


@pytest.fixture()
def servers():
    """Two in-process solver servers (each connection gets its own
    thread + mirror + devincr context, exactly like separate
    processes for the wire's purposes)."""
    out = []
    for _ in range(2):
        s = SolverServer(port=0)
        threading.Thread(target=s.serve_forever, daemon=True).start()
        out.append(s)
    yield out
    for s in out:
        try:
            s.shutdown()
        except OSError:
            pass


def _pool_loop(pool, *, cycles=10, seed=31, churn=True,
               feed_nodes=(0, 1)):
    """Pipelined pool twin of test_remote_solver._wire_loop (same
    seeds, same churn sequence)."""
    import random

    from test_devincr import (
        _churn,
        _mirror_state,
        _partial_feed,
        _reset_uid_counters,
    )

    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=16, n_pods=48, gang_size=4,
                              seed=seed)
    store.pipeline = True
    store.remote_solver = pool
    store.cycle_feed = _partial_feed(list(feed_nodes))
    sched = Scheduler(store)
    rng = random.Random(7)
    states = []
    for step in range(cycles):
        sched.run_once()
        states.append(_mirror_state(store))
        if churn and step % 2 == 1:
            _churn(store, rng, step)
    store.flush_binds()
    binds = dict(store.binder.binds)
    store.close()
    return binds, states


def test_pool_two_process_churn_parity(monkeypatch):
    """A pool of two REAL solver child processes stays bind-for-bind
    and per-cycle-mirror-state equal to the in-process loop across a
    randomized-churn feed — any replica can serve any solve, and each
    replica's deltas re-engage after its first full frame."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    procs = []
    try:
        addrs = []
        for _ in range(2):
            proc, port = _spawn_solver()
            procs.append(proc)
            addrs.append(f"127.0.0.1:{port}")
        pool = SolverPool(addrs)
        binds_p, states_p = _pool_loop(pool, cycles=10, churn=True)
        frames = pool.per_replica_frames()
        pool.close()
        binds_l, states_l = _local_loop(cycles=10, churn=True)
        assert binds_p and binds_p == binds_l
        assert states_p == states_l
        # Both replicas served solves; whichever served more than one
        # frame re-engaged deltas after its first (always-full) frame.
        assert all(f["full"] >= 1 for f in frames), frames
        assert any(f["delta"] >= 1 for f in frames), frames
    finally:
        for proc in procs:
            proc.terminate()
            proc.wait(timeout=10)


def test_pool_of_one_bitwise_equal_to_single_client(servers,
                                                    monkeypatch):
    """Pool of 1 (the VOLCANO_TPU_SOLVER_POOL=1 default semantics) is
    bind-for-bind, mirror-state, frame-kind AND wire-byte identical to
    the plain single-connection RemoteSolver path."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    addr = f"127.0.0.1:{servers[0].port}"
    pool = SolverPool([addr], size=1)
    binds_p, states_p = _pool_loop(pool, cycles=8, churn=True)
    pool_frames = dict(pool.frame_counts)
    pool_bytes = dict(pool.frame_bytes)
    pool.close()

    from test_devincr import _partial_feed, _reset_uid_counters
    import random

    from test_devincr import _churn, _mirror_state

    _reset_uid_counters()
    client = RemoteSolver(addr)
    store = synthetic_cluster(n_nodes=16, n_pods=48, gang_size=4,
                              seed=31)
    store.pipeline = True
    store.remote_solver = client
    store.cycle_feed = _partial_feed([0, 1])
    sched = Scheduler(store)
    rng = random.Random(7)
    states_s = []
    for step in range(8):
        sched.run_once()
        states_s.append(_mirror_state(store))
        if step % 2 == 1:
            _churn(store, rng, step)
    store.flush_binds()
    binds_s = dict(store.binder.binds)
    single_frames = dict(client.frame_counts)
    single_bytes = dict(client.frame_bytes)
    store.close()
    client.close()

    assert binds_p and binds_p == binds_s
    assert states_p == states_s
    assert pool_frames == single_frames
    # Wire-byte identity: the pool of one adds no machinery to the
    # frames themselves.
    assert pool_bytes == single_bytes


class _ExactShapePool(SolverPool):
    """Asks the servers for the exact task shape (the manifest's ``wave``
    key), so a toy's solve on the CPU is a millisecond whatever the wave
    size and the timing below is the injected straggler's alone."""

    def solve_async(self, solve_args, pid, profiles, wave=None,
                    devincr=None):
        return super().solve_async(
            solve_args, pid, profiles,
            wave=max(1, len(solve_args[1].real)), devincr=devincr)


def test_hedged_dispatch_first_wins_and_drains(servers, monkeypatch):
    """A straggling primary past its rolling-p99 deadline re-dispatches
    the identical frame to the second replica; the first valid reply
    commits, the loser's reply is drained (its connection and mirror
    stay coherent — deltas continue afterwards), and the binds are
    deterministic (equal to an unhedged run)."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    monkeypatch.setenv("VOLCANO_TPU_POOL_HEDGE_P99_MULT", "2.0")
    monkeypatch.setenv("VOLCANO_TPU_POOL_HEDGE_MIN_MS", "20")
    for s in servers:
        s.solve_delay_fn = lambda i: 0.25 if i % 4 == 0 else 0.0
    pool = _ExactShapePool([f"127.0.0.1:{s.port}" for s in servers])
    binds_h, states_h = _pool_loop(pool, cycles=12, churn=False)
    snap = pool.health_snapshot()
    assert snap["hedge_dispatches"] >= 1, snap
    assert snap["hedge_wins"] >= 1, snap
    # The loser's reply is DRAINED (received + discarded), never
    # abandoned: no connection was torn down for a hedge (abandon /
    # reconnect would void the loser's wire cache), and a blocking
    # drain of whatever is still parked leaves every replica clean.
    assert pool.wire_fallbacks.get("abandon", 0) == 0
    for r in pool.replicas:
        pool._drain(r, block=True)
    snap = pool.health_snapshot()
    assert all(not r["draining"] for r in snap["replicas"]), snap
    pool.close()

    # Determinism: the same loop with hedging disabled lands the
    # identical binds and mirror states (first-wins is safe because
    # replies are deterministic for identical frames).
    monkeypatch.setenv("VOLCANO_TPU_POOL_HEDGE_P99_MULT", "0")
    for s in servers:
        s.solve_delay_fn = None
    pool2 = _ExactShapePool([f"127.0.0.1:{s.port}" for s in servers])
    binds_n, states_n = _pool_loop(pool2, cycles=12, churn=False)
    assert pool2.health_snapshot()["hedge_dispatches"] == 0
    pool2.close()
    assert binds_h and binds_h == binds_n
    assert states_h == states_n


def test_failover_within_one_cycle_zero_lost_pods(servers,
                                                  monkeypatch):
    """Killing the replica holding the in-flight solve costs exactly
    one cycle's lost-reply re-place: the fetch routes through the
    existing lost-reply machinery, the NEXT dispatch fails over to the
    healthy replica (full frame by construction), and no pod is lost."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    from test_devincr import _partial_feed, _reset_uid_counters

    _reset_uid_counters()
    pool = SolverPool([f"127.0.0.1:{s.port}" for s in servers])
    store = synthetic_cluster(n_nodes=16, n_pods=48, gang_size=4,
                              seed=37)
    store.pipeline = True
    store.remote_solver = pool
    store.cycle_feed = _partial_feed([0, 1])
    sched = Scheduler(store)
    for _ in range(5):
        sched.run_once()
    # Kill the replica with the in-flight solve: shut its server down
    # AND sever the live connection (a real child death does both).
    prim = pool.health_snapshot()["primary"]
    servers[prim].shutdown()
    victim = pool.replicas[prim].client
    with victim._lock:
        victim._close_locked("kill")
    other = 1 - prim
    # The kill cycle: lost reply counted, rows re-place, NO stall.
    sched.run_once()
    rec = store.flight.recent()[-1]
    assert rec.drop_reasons.get("lost-reply", 0) >= 1, rec.drop_reasons
    assert rec.error is None
    # Failover landed within the same cycle's dispatch: the healthy
    # replica took the frame (its first frame is full).
    snap = pool.health_snapshot()
    assert snap["failovers"] >= 1, snap
    assert snap["primary"] == other, snap
    assert pool.replicas[other].client.frame_counts["full"] >= 1
    # Drain: every pod lands Bound — zero lost pods.
    for _ in range(3):
        sched.run_once()
    store.cycle_feed = None
    for _ in range(3):
        sched.run_once()
    store.flush_binds()
    m = store.mirror
    not_bound = [
        m.p_uid[r] for r in range(m.n_pods)
        if m.p_uid[r] is not None and m.p_alive[r]
        and int(m.p_status[r]) != ST_BOUND
    ]
    assert not_bound == [], f"pods lost to the kill: {not_bound}"
    assert store.auditor.total_anomalies() == 0
    store.close()
    pool.close()


PREEMPT_CONF = """
actions: "enqueue, allocate, preempt, backfill"
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


def test_whatif_offload_overlap(servers, monkeypatch):
    """With a pool, the device-native preempt lane turns ON for remote
    stores: the plan-proving solve offloads to an idle NON-primary
    replica (overlapping the allocate lane's in-flight solve instead of
    contending for it) and the commit semantics are unchanged — the
    starved gang binds, victims restore through the ledger, zero lost
    pods."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    from volcano_tpu.cache import ClusterStore
    from volcano_tpu.cache.interface import FakeBinder, FakeEvictor
    from volcano_tpu.metrics import metrics
    from volcano_tpu.sim import ClusterSimulator

    def _whatif_dispatches():
        return sum(
            v for k, v in metrics.solver_pool_dispatch.data.items()
            if dict(k).get("kind") == "whatif"
        )

    before = _whatif_dispatches()
    pool = SolverPool([f"127.0.0.1:{s.port}" for s in servers])
    store = ClusterStore(evictor=FakeEvictor(), binder=FakeBinder())
    store.pipeline = True
    store.remote_solver = pool
    ClusterSimulator.priority_tier_workload(store, workers=4,
                                            serving_tasks=2)
    # Lock held for the read: the lockdep leg (VOLCANO_TPU_LOCKDEP=1)
    # holds test code to the same guarded-attribute contract.
    with store._lock:
        n_logical = len(store.pods)
    sched = Scheduler(store, conf_str=PREEMPT_CONF)
    sim = ClusterSimulator(store, grace_steps=2)
    bound = 0
    for _ in range(16):
        sched.run_once()
        sim.step()
        with store._lock:
            bound = sum(1 for p in store.pods.values()
                        if p.name.startswith("serving-") and p.node_name)
        if bound >= 2:
            break
    assert bound >= 2, "serving gang did not bind"
    # The plan solve actually offloaded (kind=whatif dispatches), and
    # it went to a replica other than the allocate primary.
    assert _whatif_dispatches() > before
    ledger = store.migrations
    assert ledger is not None and ledger.committed_plans >= 1
    # Commit semantics unchanged: zero lost pods (every victim
    # restored), budgets intact.
    with store._lock:
        assert len(store.pods) == n_logical
    assert store.auditor.total_anomalies() == 0
    store.close()
    pool.close()


def test_whatif_stays_off_without_offload_capacity(servers,
                                                   monkeypatch):
    """A single-connection remote store (no pool, or a pool of one)
    keeps the engine off exactly as before — the plan solve would
    contend for the one connection."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    from volcano_tpu import whatif
    from volcano_tpu.cache import ClusterStore

    store = ClusterStore()
    store.remote_solver = RemoteSolver(
        f"127.0.0.1:{servers[0].port}")
    assert not whatif.evict_device_on(store)
    store.remote_solver = SolverPool(
        [f"127.0.0.1:{servers[0].port}"], size=1)
    assert not whatif.evict_device_on(store)
    store.remote_solver = None
    assert whatif.evict_device_on(store)
    store.close()


def test_kill_switch_builds_plain_client(monkeypatch):
    """VOLCANO_TPU_SOLVER_POOL default (1) builds a plain RemoteSolver
    — no pool object at all, exactly today's path; >= 2 (or multiple
    addresses) builds the pool."""
    monkeypatch.delenv("VOLCANO_TPU_SOLVER_POOL", raising=False)
    c = make_solver_client("127.0.0.1:1")
    assert isinstance(c, RemoteSolver)
    monkeypatch.setenv("VOLCANO_TPU_SOLVER_POOL", "3")
    c = make_solver_client("127.0.0.1:1")
    assert isinstance(c, SolverPool) and c.size == 3
    monkeypatch.delenv("VOLCANO_TPU_SOLVER_POOL")
    c = make_solver_client("127.0.0.1:1,127.0.0.1:2")
    assert isinstance(c, SolverPool) and c.size == 2
    addrs = [(r.client.host, r.client.port) for r in c.replicas]
    assert addrs == [("127.0.0.1", 1), ("127.0.0.1", 2)]
