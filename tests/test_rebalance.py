"""Rebalance subsystem tests (ISSUE 5, docs/rebalance.md): planner
kernel <-> oracle parity, the plan-improves-or-noop invariant, per-group
disruption-budget ceilings (including the pipelined stale-void path),
the simulator's eviction grace window, and the fragmented-cluster e2e —
a 32-task gang unschedulable under allocate+backfill alone binds after
one rebalance cycle with zero lost pods."""

import numpy as np
import pytest

from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    PriorityClass,
)
from volcano_tpu.cache import ClusterStore, FakeBinder
from volcano_tpu.framework import (
    REBALANCE_SCHEDULER_CONF,
    parse_scheduler_conf,
)
from volcano_tpu.metrics import metrics
from volcano_tpu.oracle import oracle_rebalance
from volcano_tpu.ops.rebalance import frag_scores, select_drain_set
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.sim import ClusterSimulator

ALLOC_CONF = """
actions: "enqueue, allocate, backfill"
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


def make_pod(name, group, cpu="1", mem="1Gi", **kw):
    return Pod(
        name=name,
        namespace="default",
        annotations={GROUP_NAME_ANNOTATION: group},
        containers=[{"cpu": cpu, "memory": mem}],
        **kw,
    )


def make_node(name, cpu="4", mem="16Gi"):
    return Node(name=name,
                allocatable={"cpu": cpu, "memory": mem, "pods": 110})


def _rebalance_outcomes(store):
    """Flight-recorder rebalance outcomes, oldest first."""
    return [r.rebalance for r in store.flight.recent()
            if r.rebalance is not None]


def _plans_count(outcome):
    key = (("outcome", outcome),)
    return metrics.rebalance_plans.data.get(key, 0.0)


def _fragmented_cluster(workers, spill, budget=None, gang_priority=True):
    """``workers`` 4-cpu nodes each stranded by a 3-cpu filler plus
    ``spill`` empty 3-cpu nodes: no node fits a whole-node (4 cpu) gang
    task until fillers migrate to the spill nodes."""
    store = ClusterStore(binder=FakeBinder())
    if gang_priority:
        store.add_priority_class(PriorityClass(name="high", value=1000))
    for i in range(workers):
        store.add_node(make_node(f"w{i}", cpu="4"))
    for i in range(spill):
        store.add_node(make_node(f"s{i}", cpu="3"))
    for i in range(workers):
        store.add_pod_group(PodGroup(name=f"f{i}", min_member=1,
                                     max_unavailable=budget))
        store.add_pod(make_pod(f"fill{i}", f"f{i}", cpu="3"))
    return store


def _add_gang(store, size, cpu="4", priority_class="high"):
    store.add_pod_group(PodGroup(name="gang", min_member=size,
                                 priority_class=priority_class))
    for i in range(size):
        store.add_pod(make_pod(f"g{i}", "gang", cpu=cpu))


# --------------------------------------------------------------- parity


def test_oracle_parity_fixed_seeds():
    """frag/fit planes and the greedy drain selection agree exactly
    with the Go-shaped oracle on randomized fragmented snapshots."""
    import jax

    for seed in range(6):
        rng = np.random.RandomState(seed)
        N, R, U = 24, 3, 2
        alloc = rng.uniform(2.0, 8.0, size=(N, R)).astype(np.float32)
        idle = (alloc * rng.uniform(0.0, 1.0, size=(N, R))).astype(
            np.float32)
        ev = (idle * rng.uniform(0.0, 1.5, size=(N, R))).astype(
            np.float32)
        ready = rng.rand(N) > 0.1
        prof_req = rng.uniform(0.5, 6.0, size=(U, R)).astype(np.float32)
        # Some profiles request nothing on some slots.
        prof_req[rng.rand(U, R) < 0.3] = 0.0
        eps = np.full(R, 1e-3, np.float32)
        victims_by_node = [
            [n * 10 + k for k in range(int(rng.randint(0, 3)))]
            for n in range(N)
        ]
        victim_group = {
            r: f"g{r % 5}" for rows in victims_by_node for r in rows
        }
        budget_left = {f"g{i}": int(rng.randint(0, 4))
                       for i in range(5)}
        need = int(rng.randint(1, 6))
        cap = int(rng.randint(1, N))

        fs = frag_scores(idle, alloc, ready, ev, prof_req, eps)
        frag, fit_now, fit_freed = jax.device_get(
            (fs.frag, fs.fit_now, fs.fit_freed))
        nodes, blocked = select_drain_set(
            frag, fit_now, fit_freed, need, victims_by_node,
            victim_group, dict(budget_left), cap)

        ref = oracle_rebalance(idle, alloc, ready, ev, prof_req, eps,
                               need, victims_by_node, victim_group,
                               dict(budget_left), cap)
        np.testing.assert_allclose(frag, ref.frag, atol=1e-5,
                                   err_msg=f"seed {seed}")
        np.testing.assert_array_equal(fit_now, ref.fit_now)
        np.testing.assert_array_equal(fit_freed, ref.fit_freed)
        assert (list(nodes) == ref.drain_nodes.tolist()
                if ref.feasible else nodes == []), f"seed {seed}"
        assert blocked == ref.budget_blocked, f"seed {seed}"


# ------------------------------------------------- plan-improves-or-noop


def test_plan_improves_or_noop_fixed_seeds(monkeypatch):
    """On randomized fragmented clusters the lane either commits a plan
    that strictly improves binds — the gang fully binds and every
    evicted filler is re-bound (zero lost pods) — or commits nothing
    and mutates nothing."""
    committed_any = False
    for seed in range(3):
        rng = np.random.RandomState(100 + seed)
        workers = int(rng.randint(6, 12))
        spill = workers + int(rng.randint(0, 4))
        gang = max(2, workers // 2)
        monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", str(workers))
        store = _fragmented_cluster(workers, spill)
        sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
        sim = ClusterSimulator(store, grace_steps=1)
        sched.run_once()
        sim.step()
        _add_gang(store, gang)
        n_logical = len(store.pods)  # fillers + gang, all must survive
        sched.run_once()
        ledger = store.migrations
        if ledger is None or ledger.committed_plans == 0:
            # Noop: nothing evicted, nothing mutated.
            assert not any(p.deleting for p in store.pods.values()), \
                f"seed {seed}: evictions without a committed plan"
            continue
        committed_any = True
        for _ in range(12):
            sim.step()
            sched.run_once()
            if (sum(1 for p in store.pods.values()
                    if p.name.startswith("g") and p.node_name) >= gang
                    and not ledger.active(store)):
                break
        bound_gang = sum(1 for p in store.pods.values()
                         if p.name.startswith("g") and p.node_name)
        assert bound_gang >= gang, f"seed {seed}: gang did not bind"
        # Zero lost pods: every logical pod (original or its restored
        # successor) is present and placed.
        assert len(store.pods) == n_logical, f"seed {seed}: pod lost"
        unplaced = [p.name for p in store.pods.values()
                    if p.node_name is None]
        assert not unplaced, f"seed {seed}: unplaced after converge"
        store.close()
    assert committed_any, "no seed exercised the commit path"


# ----------------------------------------------------------------- budgets


def test_budget_zero_blocks_plan(monkeypatch):
    """max_unavailable=0 on every filler group makes the drain set
    unassemblable: the plan is rejected for budget, nothing is
    evicted."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")
    before = _plans_count("rejected-budget")
    store = _fragmented_cluster(4, 4, budget=0)
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store)
    sched.run_once()
    sim.step()
    _add_gang(store, 2)
    sched.run_once()
    assert store.migrations is None or not store.migrations.entries
    assert not any(p.deleting for p in store.pods.values())
    outcomes = _rebalance_outcomes(store)
    assert outcomes and outcomes[-1]["outcome"] == "rejected-budget"
    assert _plans_count("rejected-budget") == before + 1
    store.close()


def test_budget_ceiling_caps_wave_size(monkeypatch):
    """One shared filler group with max_unavailable=2 and a gang that
    needs only 2 drained nodes: the committed wave takes exactly the
    victims the budget allows, the group's disrupted count never
    exceeds the ceiling at any point of the migration, and the gang
    binds."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")
    store = ClusterStore(binder=FakeBinder())
    store.add_priority_class(PriorityClass(name="high", value=1000))
    for i in range(4):
        store.add_node(make_node(f"w{i}", cpu="4"))
    for i in range(4):
        store.add_node(make_node(f"s{i}", cpu="3"))
    store.add_pod_group(PodGroup(name="fillers", min_member=1,
                                 max_unavailable=2))
    for i in range(4):
        store.add_pod(make_pod(f"fill{i}", "fillers", cpu="3"))
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=1)
    sched.run_once()
    sim.step()
    _add_gang(store, 2)  # needs 2 of the 4 worker nodes drained
    max_seen = 0
    bound = 0
    for _ in range(16):
        sched.run_once()
        ledger = store.migrations
        if ledger is not None:
            max_seen = max(max_seen,
                           ledger.disrupted(store, "default/fillers"))
        sim.step()
        bound = sum(1 for p in store.pods.values()
                    if p.name.startswith("g") and p.node_name)
        if bound >= 2:
            break
    assert max_seen <= 2, f"budget exceeded: {max_seen} disrupted"
    assert max_seen > 0, "no migration happened"
    assert bound >= 2, "gang did not bind"
    ledger = store.migrations
    assert ledger is not None and ledger.committed_plans == 1
    outcomes = [o for o in _rebalance_outcomes(store)
                if o["outcome"] == "committed"]
    assert outcomes and outcomes[0]["victims"] == 2
    store.close()


def test_failed_evict_dispatch_cancels_migration(monkeypatch):
    """An evictor failure reverts the victim to Running AND cancels its
    ledger entry: the budget is not pinned, the lane is not wedged, and
    the pod's eventual ordinary deletion is not 'restored'."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")

    class FlakyEvictor:
        def __init__(self):
            self.fail = True

        def evict(self, pod):
            if self.fail:
                raise RuntimeError("evictor down")

    evictor = FlakyEvictor()
    store = _fragmented_cluster(4, 4)
    store.evictor = evictor
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=1)
    sched.run_once()
    sim.step()
    _add_gang(store, 2)
    sched.run_once()  # plan commits; every evict dispatch fails
    ledger = store.migrations
    assert ledger is not None and ledger.committed_plans == 1
    # All entries cancelled: nothing terminating, budgets unpinned,
    # the lane free to re-plan.
    assert not ledger.entries
    assert not ledger.active(store)
    assert not any(p.deleting for p in store.pods.values())
    assert all(p.phase == "Running" for p in store.pods.values()
               if p.name.startswith("fill"))
    # Evictor recovers: a later wave completes end to end (the
    # rejection backoff applies only to planning failures, not evictor
    # failures — but drive enough cycles either way).
    evictor.fail = False
    from volcano_tpu.fastpath import FastCycle

    for _ in range(FastCycle.REBALANCE_REJECT_BACKOFF + 10):
        sim.step()
        sched.run_once()
        if sum(1 for p in store.pods.values()
               if p.name.startswith("g") and p.node_name) >= 2:
            break
    assert sum(1 for p in store.pods.values()
               if p.name.startswith("g") and p.node_name) >= 2
    # Zero lost pods through the failure + retry.
    fillers = [p for p in store.pods.values()
               if p.name.startswith("fill")]
    assert len(fillers) == 4 and all(p.node_name for p in fillers)
    store.close()


def test_deliberate_delete_is_not_resurrected(monkeypatch):
    """Deleting a victim's workload mid-termination wins over the
    migration: the pod is NOT restored, and the drained ledger does not
    wedge the lane."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")
    store = _fragmented_cluster(4, 4)
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=3)
    sched.run_once()
    sim.step()
    _add_gang(store, 2)
    sched.run_once()  # plan commits; victims enter the grace window
    ledger = store.migrations
    assert ledger is not None and ledger.entries
    victims = [p for p in store.pods.values() if p.deleting]
    assert victims
    # The operator removes one victim's workload outright.
    gone = victims[0]
    group_uid = gone.annotations[GROUP_NAME_ANNOTATION]
    store.delete_pod_group(f"default/{group_uid}")
    store.delete_pod(gone)
    assert all("-mig" not in p.uid for p in store.pods.values()
               if p.name == gone.name), "deleted workload resurrected"
    assert gone.uid not in ledger.entries
    # The remaining victims migrate normally and the ledger drains —
    # the lane is not wedged by the removed workload.
    for _ in range(12):
        sim.step()
        sched.run_once()
        if not ledger.active(store):
            break
    assert not ledger.active(store)
    store.close()


def test_pipelined_stale_commit_voids_cleanly(monkeypatch):
    """Pipelined stores park the plan and commit next cycle; a store
    mutation during the overlap voids the whole plan (stale-voided) and
    nothing is evicted."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")
    before = _plans_count("stale-voided")
    store = _fragmented_cluster(4, 4)
    store.pipeline = True
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=1)
    sched.run_once()  # dispatches the fillers' solve
    sched.run_once()  # commits the filler binds
    sim.step()        # fillers start Running
    _add_gang(store, 2)
    # Pipelined starvation streak: the plan forms on the second
    # starved pass and parks on the store.
    sched.run_once()
    sched.run_once()
    parked = store._inflight_plan
    assert parked is not None, "plan did not park"
    # Concurrent mutation during the overlap window.
    store.add_pod(make_pod("intruder", "f0", cpu="1"))
    sched.run_once()
    # The stale plan was voided; the lane may already have parked a
    # FRESH plan against the post-mutation state — never the old one.
    assert store._inflight_plan is not parked
    outcomes = [o for o in _rebalance_outcomes(store)
                if o["outcome"] == "stale-voided"]
    assert outcomes, "stale plan did not void"
    assert _plans_count("stale-voided") >= before + 1
    assert not any(p.deleting for p in store.pods.values()), \
        "a voided plan must evict nothing"
    store.close()


def test_pipelined_plan_commits_when_fresh(monkeypatch):
    """Without concurrent mutations the parked plan commits next cycle
    and the migration completes end to end."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")
    store = _fragmented_cluster(4, 4)
    store.pipeline = True
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=1)
    sched.run_once()  # dispatches the fillers' solve
    sched.run_once()  # commits the filler binds
    sim.step()        # fillers start Running
    _add_gang(store, 2)
    for _ in range(16):
        sched.run_once()
        sim.step()
        if sum(1 for p in store.pods.values()
               if p.name.startswith("g") and p.node_name) >= 2:
            break
    assert sum(1 for p in store.pods.values()
               if p.name.startswith("g") and p.node_name) >= 2
    ledger = store.migrations
    assert ledger is not None and ledger.committed_plans >= 1
    store.close()


# ------------------------------------------------------------- sim grace


def test_sim_grace_period_holds_capacity():
    """A deleting pod passes through Terminating for grace_steps ticks;
    its capacity frees only when the delete lands."""
    store = ClusterStore(binder=FakeBinder())
    store.add_node(make_node("n0", cpu="4"))
    store.add_pod_group(PodGroup(name="pg", min_member=1))
    store.add_pod(make_pod("p0", "pg", cpu="4"))
    sched = Scheduler(store, conf_str=ALLOC_CONF)
    sim = ClusterSimulator(store, grace_steps=2)
    sched.run_once()
    sim.step()
    pod = next(p for p in store.pods.values() if p.name == "p0")
    assert pod.phase == "Running"
    pod.deleting = True
    r1 = sim.step()
    assert r1["terminating"] == 1 and r1["deleted"] == 0
    # Capacity still charged: a same-size pod cannot bind yet.
    store.add_pod_group(PodGroup(name="pg2", min_member=1))
    store.add_pod(make_pod("p1", "pg2", cpu="4"))
    sched.run_once()
    assert next(p for p in store.pods.values()
                if p.name == "p1").node_name is None
    r2 = sim.step()
    assert r2["terminating"] == 1 and r2["deleted"] == 0
    r3 = sim.step()
    assert r3["deleted"] == 1
    sched.run_once()
    assert next(p for p in store.pods.values()
                if p.name == "p1").node_name == "n0"
    store.close()


def test_sim_grace_zero_is_instant():
    store = ClusterStore(binder=FakeBinder())
    store.add_node(make_node("n0"))
    store.add_pod_group(PodGroup(name="pg", min_member=1))
    store.add_pod(make_pod("p0", "pg"))
    sim = ClusterSimulator(store)
    pod = next(iter(store.pods.values()))
    pod.deleting = True
    assert sim.step()["deleted"] == 1
    assert not store.pods
    store.close()


# ------------------------------------------------------------------- e2e


def test_fragmented_cluster_e2e_32_task_gang(monkeypatch):
    """Acceptance e2e: a 32-task whole-node gang is unschedulable under
    allocate+backfill alone, binds after ONE rebalance cycle (plus the
    eviction grace window), with zero lost pods and budgets never
    exceeded."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "32")
    workers, spill, gang = 32, 32, 32
    store = _fragmented_cluster(workers, spill)
    sched_alloc = Scheduler(store, conf_str=ALLOC_CONF)
    sim = ClusterSimulator(store, grace_steps=2)
    sched_alloc.run_once()
    sim.step()  # fillers start Running
    _add_gang(store, gang)
    n_logical = len(store.pods)

    # Unschedulable under allocate+backfill alone.
    sched_alloc.run_once()
    assert not any(p.node_name for p in store.pods.values()
                   if p.name.startswith("g"))
    conds = store.pod_groups["default/gang"].status.conditions
    assert any(c.type == "Unschedulable" for c in conds)

    # ONE rebalance cycle plans and commits the full migration wave.
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sched.run_once()
    ledger = store.migrations
    assert ledger is not None and ledger.committed_plans == 1
    assert len(ledger.entries) == workers  # every filler migrating
    outcomes = _rebalance_outcomes(store)
    assert outcomes[-1]["outcome"] == "committed"
    assert outcomes[-1]["victims"] == workers
    evicted = [p.name for p in store.pods.values() if p.deleting]
    assert len(evicted) == workers

    # Budgets (max_unavailable default 1 per single-member group): no
    # group ever has more than one member disrupted.
    for i in range(workers):
        assert ledger.disrupted(store, f"default/f{i}") <= 1

    # Drive the migration through the grace window to convergence.
    converged = False
    for _ in range(12):
        sim.step()
        sched.run_once()
        gang_bound = sum(1 for p in store.pods.values()
                         if p.name.startswith("g") and p.node_name)
        if gang_bound >= gang and not ledger.active(store):
            converged = True
            break
    assert converged, "migration did not converge"

    # The gang landed on the drained worker nodes; every filler
    # (original or restored) is bound; zero lost pods.
    assert len(store.pods) == n_logical
    gang_nodes = sorted(p.node_name for p in store.pods.values()
                        if p.name.startswith("g"))
    assert all(n and n.startswith("w") for n in gang_nodes)
    fillers = [p for p in store.pods.values()
               if p.name.startswith("fill")]
    assert len(fillers) == workers
    assert all(p.node_name for p in fillers)
    assert ledger.committed_plans == 1, "one wave sufficed"
    # The restored fillers all landed on spill nodes.
    restored = [p for p in fillers if "-mig" in p.uid]
    assert len(restored) == workers
    assert all(p.node_name.startswith("s") for p in restored)
    store.close()


def test_rebalance_disabled_by_env(monkeypatch):
    """VOLCANO_TPU_REBALANCE=0 turns the configured action into a
    no-op without a config change."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE", "0")
    store = _fragmented_cluster(4, 4)
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store)
    sched.run_once()
    sim.step()
    _add_gang(store, 2)
    sched.run_once()
    assert store.migrations is None
    assert not any(p.deleting for p in store.pods.values())
    store.close()


def test_object_path_rebalance_action_is_noop(monkeypatch):
    """A configuration that forces the object session still accepts the
    action name (registered no-op) instead of warning/failing."""
    monkeypatch.setenv("VOLCANO_TPU_FASTPATH", "0")
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "always")
    store = _fragmented_cluster(2, 2)
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sched.run_once()  # must not raise
    assert store.migrations is None
    store.close()


@pytest.mark.parametrize("seed", range(6))
def test_the_ledgers_one_pass_counts_what_disrupted_counts(seed):
    """``disrupted_by_group`` is ``disrupted`` of every group, from one
    prune and one pass: in-flight and restored-but-unbound entries count,
    finished ones (the restored pod bound, or deleted again) and those of
    a PodGroup that is gone are pruned, by either."""
    import copy
    from types import SimpleNamespace

    from volcano_tpu.actions.rebalance import MigrationLedger

    rng = np.random.RandomState(seed)
    groups = [f"default/g{i}" for i in range(8)]
    store = SimpleNamespace(
        pod_groups={g: object() for g in groups[2:] if rng.rand() > 0.2},
        pods={})          # g0 and g1 are gone, and some of the others
    ledger = MigrationLedger()
    states = ["in-flight", "restored-unbound", "bound", "deleted-again"]
    made = {s: 0 for s in states}
    for i in range(60):
        uid, state = f"default/v{i}", states[rng.randint(len(states))]
        ledger.register(uid, groups[rng.randint(len(groups))], "",
                        action=("preempt", "reclaim", "rebalance")[i % 3])
        made[state] += 1
        if state != "in-flight":
            ledger.entries[uid].restored_uid = f"{uid}-mig{i}"
        if state in ("restored-unbound", "bound"):
            store.pods[f"{uid}-mig{i}"] = SimpleNamespace(
                node_name="n0" if state == "bound" else None)
    assert all(made.values()) and len(store.pod_groups) < len(groups)
    one_by_one = copy.deepcopy(ledger)
    want = {g: one_by_one.disrupted(store, g) for g in groups}
    assert ledger.disrupted_by_group(store) == {g: n for g, n in want.items() if n}
    assert list(ledger.entries) == list(one_by_one.entries)
    assert 0 < len(ledger.entries) < 60 and sum(want.values()) == len(ledger.entries)
    assert all(want[g] == 0 for g in groups if g not in store.pod_groups)
