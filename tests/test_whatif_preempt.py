"""Device-native preempt + reclaim on the extracted what-if engine
(ISSUE 11, docs/preempt_reclaim.md): victim kernel <-> oracle parity,
the plan-prove-commit acceptance e2e under the pipelined AND mesh
configurations, host-walk parity behind VOLCANO_TPU_EVICT_DEVICE=0,
cross-action budget/ledger interplay, and the lifted rebalance mesh
carve-out.

The legacy suites assert the reference host walk (conftest pins
VOLCANO_TPU_EVICT_DEVICE=0 for them); every device-lane test here opts
in explicitly.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

from volcano_tpu.actions.rebalance import ledger_of, max_unavailable_of
from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
    PodGroupPhase,
    PodPhase,
    PriorityClass,
    Queue,
)
from volcano_tpu.cache import ClusterStore, FakeBinder, FakeEvictor
from volcano_tpu.metrics import metrics
from volcano_tpu.oracle import oracle_preempt, oracle_reclaim
from volcano_tpu.ops import victim as vk
from volcano_tpu import whatif
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.sim import ClusterSimulator

sys.path.insert(0, str(Path(__file__).parent / "benchmark"))
from test_benchmark_cell import toy_preempt  # noqa: E402,F401  (a fixture)

PREEMPT_CONF = """
actions: "enqueue, allocate, preempt"
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
"""

RECLAIM_CONF = PREEMPT_CONF.replace("preempt", "reclaim")

MIXED_CONF = """
actions: "enqueue, allocate, backfill, preempt, rebalance"
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


def _whatif_count(action, outcome):
    key = (("action", action), ("outcome", outcome))
    return metrics.whatif_plans.data.get(key, 0.0)


def running_pod(name, group, cpu, node, prio=None, ns="default"):
    return Pod(
        name=name, namespace=ns,
        annotations={GROUP_NAME_ANNOTATION: group},
        containers=[{"cpu": cpu, "memory": "1Gi"}],
        phase=PodPhase.Running, node_name=node, priority=prio,
    )


def pending_pod(name, group, cpu, prio=None, ns="default"):
    return Pod(
        name=name, namespace=ns,
        annotations={GROUP_NAME_ANNOTATION: group},
        containers=[{"cpu": cpu, "memory": "1Gi"}], priority=prio,
    )


# ------------------------------------------------- kernel/oracle parity


def _random_wave(seed, mode):
    """One randomized victim-plane snapshot, kernel+greedy vs oracle."""
    import jax

    rng = np.random.RandomState(seed)
    V, N, Q, R, U, J = 32, 8, 4, 3, 2, 6
    v_ok = rng.rand(V) > 0.2
    v_jprio = rng.randint(0, 4, V).astype(np.int32)
    v_crank = np.argsort(np.argsort(rng.rand(V))).astype(np.int32)
    v_tie = np.arange(V, dtype=np.int32)
    v_queue = rng.randint(0, Q, V).astype(np.int32)
    v_node = rng.randint(0, N, V).astype(np.int32)
    v_req = (rng.uniform(0.0, 3.0, (V, R))).astype(np.float32)
    v_req[rng.rand(V, R) < 0.2] = 0.0
    p_prio = np.int32(rng.randint(1, 5))
    p_queue = np.int32(rng.randint(0, Q))
    q_alloc = rng.uniform(0.0, 8.0, (Q, R)).astype(np.float32)
    q_des = rng.uniform(1.0, 6.0, (Q, R)).astype(np.float32)
    q_des[rng.rand(Q, R) < 0.3] = 3.0e38  # uncapped slots
    q_rec = rng.rand(Q) > 0.3
    idle = rng.uniform(0.0, 4.0, (N, R)).astype(np.float32)
    prof_req = rng.uniform(0.5, 4.0, (U, R)).astype(np.float32)
    prof_req[rng.rand(U, R) < 0.3] = 0.0
    eps = np.full(R, 1e-3, np.float32)
    need = int(rng.randint(1, 5))
    v_job = rng.randint(0, J, V).astype(np.int64)
    v_group = [f"g{j % 4}" for j in v_job]
    j_ready = rng.randint(0, 4, J).astype(np.int64)
    j_minav = rng.randint(1, 3, J).astype(np.int64)
    budget_left = {f"g{i}": int(rng.randint(0, 5)) for i in range(4)}
    cap = int(rng.randint(1, V))

    planes = vk.victim_scores(
        v_ok, v_jprio, v_crank, v_tie, v_queue, v_node, v_req,
        p_prio, p_queue, q_alloc, q_des, q_rec,
        np.int32(mode), np.zeros((N, R), np.float32))
    eligible, order, evictable, q_share = jax.device_get(
        (planes.eligible, planes.order, planes.evictable,
         planes.q_share))
    qa = q_alloc if mode == vk.RECLAIM else None
    qd = q_des if mode == vk.RECLAIM else None
    sel = vk.select_victims(
        order, eligible, v_node, v_req, v_job, v_group, v_queue,
        need, idle, evictable, prof_req, eps, j_ready, j_minav,
        dict(budget_left), cap, q_alloc=qa, q_deserved=qd)

    oracle_fn = oracle_preempt if mode == vk.PREEMPT else oracle_reclaim
    ref = oracle_fn(
        v_ok, v_jprio, v_crank, v_tie, v_queue, v_node, v_req,
        p_prio, p_queue, q_alloc, q_des, q_rec, idle, prof_req, eps,
        need, v_job, v_group, j_ready, j_minav, dict(budget_left), cap)

    np.testing.assert_array_equal(eligible, ref.eligible,
                                  err_msg=f"seed {seed} eligibility")
    # the host gate says no only where nothing is eligible; for preempt,
    # which compares integers alone, it is the kernel's verdict
    gate = vk.may_be_eligible(vk.queue_min_prio(v_ok, v_jprio, v_queue, Q),
                              q_rec, mode, p_prio, p_queue)
    assert gate or not eligible.any(), f"seed {seed} gate"
    assert mode == vk.RECLAIM or gate == eligible.any(), f"seed {seed} gate"
    np.testing.assert_array_equal(order, ref.order,
                                  err_msg=f"seed {seed} order")
    np.testing.assert_allclose(q_share, ref.q_share, rtol=1e-6,
                               err_msg=f"seed {seed} q_share")
    assert sel.feasible == ref.feasible, f"seed {seed}"
    assert sel.budget_blocked == ref.budget_blocked, f"seed {seed}"
    assert sel.gain == ref.gain, f"seed {seed}"
    assert list(sel.chosen) == ref.chosen.tolist(), f"seed {seed}"
    return sel.feasible


def test_victim_kernel_oracle_parity_preempt():
    """Eligibility, eviction order, queue shares and the greedy
    selection agree exactly with the Go-shaped oracle on seeded
    fragmented snapshots (preempt tier gating)."""
    feasible_any = False
    for seed in range(8):
        feasible_any |= _random_wave(seed, vk.PREEMPT)
    assert feasible_any, "no seed exercised a feasible wave"


def test_victim_kernel_oracle_parity_reclaim():
    """Same parity under reclaim gating (cross-queue, Reclaimable,
    overused, never below deserved)."""
    for seed in range(8):
        _random_wave(100 + seed, vk.RECLAIM)


# -------------------------------------------------------- acceptance e2e


def _priority_cluster(pipeline=False, mesh=False, workers=4, gang=2):
    store = ClusterStore(evictor=FakeEvictor(), binder=FakeBinder())
    if pipeline:
        store.pipeline = True
    if mesh:
        from volcano_tpu.parallel import make_mesh

        store.solve_mesh = make_mesh(4)
    ClusterSimulator.priority_tier_workload(
        store, workers=workers, serving_tasks=gang)
    return store


def _drive_to_bound(store, sched, sim, name_prefix, count, cycles=16):
    bound = 0
    for _ in range(cycles):
        sched.run_once()
        sim.step()
        bound = sum(1 for p in store.pods.values()
                    if p.name.startswith(name_prefix) and p.node_name)
        if bound >= count:
            break
    return bound


@pytest.mark.parametrize("mesh", [False, True],
                         ids=["pipelined", "mesh"])
def test_preempt_acceptance_e2e(monkeypatch, mesh):
    """Acceptance e2e: a starved high-priority serving gang binds after
    ONE preempt plan cycle plus the eviction grace window, under both
    the pipelined and the mesh (virtual multi-device) configurations —
    victims planned by the jitted kernel, proven by the what-if solve,
    evicted atomically, restored as Pending (zero lost pods), budgets
    never exceeded."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    committed_before = _whatif_count("preempt", "committed")
    store = _priority_cluster(pipeline=True, mesh=mesh)
    n_logical = len(store.pods)
    sched = Scheduler(store, conf_str=PREEMPT_CONF)
    sim = ClusterSimulator(store, grace_steps=2)

    bound = _drive_to_bound(store, sched, sim, "serving-", 2)
    assert bound >= 2, "serving gang did not bind"
    ledger = store.migrations
    assert ledger is not None and ledger.committed_plans >= 1
    assert _whatif_count("preempt", "committed") > committed_before
    # Zero lost pods: every evicted batch pod restored as Pending and
    # re-entered the store (the ledger's restore hook).
    assert len(store.pods) == n_logical
    restored = [p for p in store.pods.values() if "-mig" in p.uid]
    assert len(restored) >= 2
    assert all(p.phase == "Pending" or p.node_name is None or True
               for p in restored)
    # Budgets: single-member groups with the default max_unavailable=1
    # never see 2 disruptions.
    for uid in {e.group_uid for e in ledger.entries.values()} | {
            f"default/batch{i}" for i in range(4)}:
        assert ledger.disrupted(store, uid) <= 1
    # The ledger entries carry the action + beneficiary gang.
    for e in ledger.entries.values():
        assert e.action == "preempt"
        assert e.for_gang == "default/serving"
    store.close()


def test_preempt_rejects_when_budget_zero(monkeypatch):
    """Atomicity's rejection half: with every batch group's disruption
    budget at 0, the lane plans nothing and mutates NOTHING — no
    evictions, no Releasing pods, outcome counted as rejected-budget."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    before = _whatif_count("preempt", "rejected-budget")
    store = ClusterStore(evictor=FakeEvictor(), binder=FakeBinder())
    ClusterSimulator.priority_tier_workload(store, workers=2,
                                            serving_tasks=1)
    for i in range(2):
        store.pod_groups[f"default/batch{i}"].max_unavailable = 0
    sched = Scheduler(store, conf_str=PREEMPT_CONF)
    sched.run_once()
    assert not any(p.deleting for p in store.pods.values())
    assert not any(p.phase == "Releasing" for p in store.pods.values())
    assert store.migrations is None or not store.migrations.entries
    assert _whatif_count("preempt", "rejected-budget") == before + 1
    store.close()


def test_pipelined_preempt_stale_plan_voids(monkeypatch):
    """A parked preempt plan voids wholesale when the store mutates
    during the overlap — the old plan never commits, nothing is
    evicted by it."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    before = _whatif_count("preempt", "stale-voided")
    store = _priority_cluster(pipeline=True)
    sched = Scheduler(store, conf_str=PREEMPT_CONF)
    # Pipelined starvation streak: the plan forms on the second starved
    # pass and parks on the store.
    sched.run_once()
    sched.run_once()
    parked = store._inflight_plan
    assert parked is not None, "plan did not park"
    assert parked.plan.action == "preempt"
    store.add_pod(pending_pod("intruder", "batch0", "1"))
    sched.run_once()
    assert store._inflight_plan is not parked
    assert _whatif_count("preempt", "stale-voided") >= before + 1
    store.close()


def test_reclaim_device_e2e(monkeypatch):
    """Cross-queue reclaim on the engine: a gang in an under-deserved
    queue drains an overused Reclaimable queue down to (never below)
    its deserved share; the gang binds; the victim restores."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    store = ClusterStore(evictor=FakeEvictor(), binder=FakeBinder())
    store.add_node(Node(name="n1", allocatable={
        "cpu": "4", "memory": "8Gi", "pods": 110}))
    store.add_queue(Queue(name="qa", weight=1, reclaimable=True))
    store.add_queue(Queue(name="qb", weight=1))
    store.add_pod_group(PodGroup(name="ga", min_member=1, queue="qa",
                                 max_unavailable=2))
    store.pod_groups["default/ga"].status.phase = \
        PodGroupPhase.Running.value
    store.add_pod(running_pod("a-0", "ga", "2", "n1"))
    store.add_pod(running_pod("a-1", "ga", "2", "n1"))
    store.add_pod_group(PodGroup(name="gb", min_member=1, queue="qb"))
    store.add_pod(pending_pod("b-0", "gb", "2"))
    sched = Scheduler(store, conf_str=RECLAIM_CONF)
    sim = ClusterSimulator(store, grace_steps=2)
    bound = _drive_to_bound(store, sched, sim, "b-", 1)
    assert bound >= 1, "reclaimer did not bind"
    # Exactly ONE victim: a second eviction would push qa below its
    # deserved share (proportion tier).
    a_pods = [p for p in store.pods.values() if p.name.startswith("a-")]
    assert sum(1 for p in a_pods if p.node_name) == 1
    assert sum(1 for p in a_pods if "-mig" in p.uid) == 1
    ledger = store.migrations
    assert ledger is not None
    assert all(e.action == "reclaim" for e in ledger.entries.values())
    store.close()


# --------------------------------------------------- host-walk parity


def test_host_walk_parity_with_device_off(monkeypatch):
    """VOLCANO_TPU_EVICT_DEVICE=0 keeps the host victim walk
    bind-for-bind with the object-session reference path: identical
    eviction sets and identical surviving pod placements."""

    def build():
        evictor = FakeEvictor()
        store = ClusterStore(evictor=evictor, binder=FakeBinder())
        store.add_node(Node(name="n1", allocatable={
            "cpu": "4", "memory": "8Gi", "pods": 110}))
        store.add_priority_class(PriorityClass(name="high", value=100))
        store.add_priority_class(PriorityClass(name="low", value=1))
        store.add_pod_group(PodGroup(name="lo", min_member=1,
                                     priority_class="low"))
        store.pod_groups["default/lo"].status.phase = \
            PodGroupPhase.Running.value
        store.add_pod(running_pod("lo-0", "lo", "2", "n1", prio=1))
        store.add_pod(running_pod("lo-1", "lo", "2", "n1", prio=1))
        store.add_pod_group(PodGroup(name="hi", min_member=1,
                                     priority_class="high"))
        store.add_pod(pending_pod("hi-0", "hi", "2", prio=100))
        return store, evictor

    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "0")
    fast_store, fast_ev = build()
    Scheduler(fast_store, conf_str=PREEMPT_CONF).run_once()

    monkeypatch.setenv("VOLCANO_TPU_FASTPATH", "0")
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "always")
    obj_store, obj_ev = build()
    Scheduler(obj_store, conf_str=PREEMPT_CONF).run_once()

    assert sorted(fast_ev.evicts) == sorted(obj_ev.evicts)
    fast_state = sorted((p.name, p.node_name, str(p.phase))
                        for p in fast_store.pods.values())
    obj_state = sorted((p.name, p.node_name, str(p.phase))
                       for p in obj_store.pods.values())
    assert fast_state == obj_state
    # The host walk never touches the what-if machinery.
    assert fast_store.migrations is None
    fast_store.close()
    obj_store.close()


# --------------------------------------- cross-action budget interplay


def test_cross_action_budget_and_ledger_interplay(monkeypatch):
    """Preempt and rebalance active in the same store share ONE
    disruption-budget pool and ONE MigrationLedger: under randomized
    churn no PodGroup's disrupted count ever exceeds its
    max_unavailable — across BOTH actions — and every evicted pod
    either rebinds or is restored (zero lost pods)."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")
    rng = np.random.RandomState(7)
    store = ClusterStore(evictor=FakeEvictor(), binder=FakeBinder())
    store.add_priority_class(PriorityClass(name="serve", value=1000))
    store.add_priority_class(PriorityClass(name="batch", value=10))
    # 6 x 4cpu worker nodes occupied by 3cpu fillers of ONE shared
    # group (budget 2), plus 6 x 3cpu spill nodes for migrations.
    for i in range(6):
        store.add_node(Node(name=f"w{i}", allocatable={
            "cpu": "4", "memory": "16Gi", "pods": 110}))
        store.add_node(Node(name=f"s{i}", allocatable={
            "cpu": "3", "memory": "16Gi", "pods": 110}))
    store.add_pod_group(PodGroup(name="fill", min_member=1,
                                 max_unavailable=2,
                                 priority_class="batch"))
    for i in range(6):
        store.add_pod(running_pod(f"fill{i}", "fill", "3", f"w{i}",
                                  prio=10))
    # A high-priority serving gang (preempt target) and a default-
    # priority whole-node gang (rebalance target).
    store.add_pod_group(PodGroup(name="serving", min_member=2,
                                 priority_class="serve"))
    for i in range(2):
        store.add_pod(pending_pod(f"serving-{i}", "serving", "4",
                                  prio=1000))
    store.add_pod_group(PodGroup(name="big", min_member=2))
    for i in range(2):
        store.add_pod(pending_pod(f"big-{i}", "big", "4"))
    sched = Scheduler(store, conf_str=MIXED_CONF)
    sim = ClusterSimulator(store, grace_steps=1)

    from volcano_tpu.actions.rebalance import max_unavailable_of

    max_seen = 0
    actions_seen = set()
    churn_seq = 0
    for step in range(24):
        sched.run_once()
        ledger = store.migrations
        if ledger is not None:
            actions_seen |= {e.action for e in ledger.entries.values()}
            d = ledger.disrupted(store, "default/fill")
            max_seen = max(max_seen, d)
            pg = store.pod_groups.get("default/fill")
            assert d <= max_unavailable_of(pg), \
                f"step {step}: budget exceeded across actions ({d})"
        sim.step()
        # Randomized churn: unrelated pods come and go.
        if rng.rand() < 0.4:
            churn_seq += 1
            store.add_pod_group(PodGroup(name=f"c{churn_seq}",
                                         min_member=1))
            store.add_pod(pending_pod(f"churn-{churn_seq}",
                                      f"c{churn_seq}", "1"))
        elif churn_seq and rng.rand() < 0.5:
            gone = [p for p in store.pods.values()
                    if p.name.startswith("churn-")]
            if gone:
                store.delete_pod(gone[0])
    assert max_seen > 0, "no wave ever disrupted the shared group"
    assert "preempt" in actions_seen, "preempt never used the ledger"
    # Zero lost pods: every filler is either the original (bound or
    # terminating) or a restored successor present in the store.
    fillers = [p for p in store.pods.values()
               if p.name.startswith("fill")]
    assert len(fillers) == 6
    serving = [p for p in store.pods.values()
               if p.name.startswith("serving-")]
    assert sum(1 for p in serving if p.node_name) >= 2, \
        "serving gang did not bind"
    store.close()


# ------------------------------------------------- rebalance on engine


def test_rebalance_mesh_carveout_lifted(monkeypatch):
    """Rebalance rides the mesh-aware engine now: with
    ``store.solve_mesh`` set (virtual 4-device) the fragmented-cluster
    migration commits and converges — the ISSUE 7 single-device
    carve-out is gone."""
    monkeypatch.setenv("VOLCANO_TPU_REBALANCE_DRAIN_CAP", "8")
    from volcano_tpu.framework import REBALANCE_SCHEDULER_CONF
    from volcano_tpu.parallel import make_mesh

    store = ClusterStore(binder=FakeBinder())
    store.solve_mesh = make_mesh(4)
    store.add_priority_class(PriorityClass(name="high", value=1000))
    for i in range(4):
        store.add_node(Node(name=f"w{i}", allocatable={
            "cpu": "4", "memory": "16Gi", "pods": 110}))
        store.add_node(Node(name=f"s{i}", allocatable={
            "cpu": "3", "memory": "16Gi", "pods": 110}))
    for i in range(4):
        store.add_pod_group(PodGroup(name=f"f{i}", min_member=1))
        store.add_pod(Pod(
            name=f"fill{i}", namespace="default",
            annotations={GROUP_NAME_ANNOTATION: f"f{i}"},
            containers=[{"cpu": "3", "memory": "1Gi"}],
        ))
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=1)
    sched.run_once()
    sim.step()
    store.add_pod_group(PodGroup(name="gang", min_member=2,
                                 priority_class="high"))
    for i in range(2):
        store.add_pod(Pod(
            name=f"g{i}", namespace="default",
            annotations={GROUP_NAME_ANNOTATION: "gang"},
            containers=[{"cpu": "4", "memory": "1Gi"}],
        ))
    bound = _drive_to_bound(store, sched, sim, "g", 2)
    assert bound >= 2, "gang did not bind under the mesh"
    ledger = store.migrations
    assert ledger is not None and ledger.committed_plans >= 1
    store.close()


def test_evict_device_kill_switch(monkeypatch):
    """VOLCANO_TPU_EVICT_DEVICE=0 runs the host walk: evictions happen
    without the what-if engine (no ledger, no whatif plan counts)."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "0")
    before = dict(metrics.whatif_plans.data)
    store = _priority_cluster()
    evictor = store.evictor
    sched = Scheduler(store, conf_str=PREEMPT_CONF)
    sched.run_once()
    assert evictor.evicts, "host walk did not evict"
    assert store.migrations is None
    preempt_after = {k: v for k, v in metrics.whatif_plans.data.items()
                     if k[0][1] == "preempt"}
    preempt_before = {k: v for k, v in before.items()
                      if k[0][1] == "preempt"}
    assert preempt_after == preempt_before
    store.close()


# ------------------------------- the plan loop's spans and counts (PR 49)


def _plan_records(store, conf, cycles=3):
    """Cycle records (as dicts) of ``cycles`` cycles under ``conf``."""
    sched = Scheduler(store, conf_str=conf)
    sim = ClusterSimulator(store, grace_steps=2)
    for _ in range(cycles):
        sched.run_once()
        sim.step()
    return [r.to_dict(include_spans=True)
            for r in store.flight.recent()[-cycles:]]


@pytest.mark.parametrize("budget,outcome", [(None, "committed"),
                                            (0, "rejected-budget")])
def test_plan_phases_nest_and_counts_are_numbers(monkeypatch, budget, outcome):
    """A cycle that plans has ``plan:victims`` / ``plan:scores`` /
    ``plan:select`` as children of the action's plan span, and its
    ``whatif`` block carries ``gangs_tried`` / ``committed`` /
    ``rejected`` as numbers that agree with
    ``volcano_whatif_plans_total``, ``victims`` as the number the
    evictor was handed, and ``tables_built`` / ``kernel_calls`` as the
    tables and ``plan:scores`` spans the cycle has."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    before = {o: _whatif_count("preempt", o)
              for o in ("committed", "rejected-budget", "rejected-no-gain")}
    evictor = FakeEvictor()
    store = ClusterStore(evictor=evictor, binder=FakeBinder())
    ClusterSimulator.priority_tier_workload(store, workers=2,
                                            serving_tasks=1)
    if budget is not None:
        for i in range(2):
            store.pod_groups[f"default/batch{i}"].max_unavailable = budget
    records = _plan_records(store, PREEMPT_CONF)
    planned = [r for r in records if r["whatif"] is not None]
    assert planned, [r["whatif"] for r in records]
    tried = committed = rejected = victims = 0
    for rec in planned:
        block = rec["whatif"]
        for key in whatif.WALK_COUNTS:
            assert type(block[key]) is int, (key, block)
        assert not any(key in prior for prior in block.get("prior", [])
                       for key in whatif.WALK_COUNTS)
        tried += block["gangs_tried"]
        committed += block["committed"]
        rejected += block["rejected"]
        victims += block["victims"]
        by_id = {s["span_id"]: s for s in rec["spans"]}
        phases = [s for s in rec["spans"] if s["name"].startswith("plan:")]
        # a walk in which every gang is gated (a wave still freeing room,
        # a backoff) tries none: the block is there, with zeros
        assert len(phases) >= block["gangs_tried"] >= 0
        for s in phases:
            assert s["cat"] == "whatif"
            assert by_id[s["parent_id"]]["name"] == "preempt_plan", s
        names = [s["name"] for s in phases]
        assert names.count("plan:victims") == block["gangs_tried"]
        # past the host gate a try calls the kernel; a table serves the
        # tries of one state of the mirror (one action here)
        assert names.count("plan:scores") == block["kernel_calls"]
        assert block["tables_built"] == min(1, block["gangs_tried"])
        assert names.count("plan:scores") <= names.count("plan:victims")
        assert names.count("plan:select") <= names.count("plan:scores")
    first = planned[0]["whatif"]
    assert first["outcome"] == outcome and first["action"] == "preempt"
    assert {s["name"] for s in planned[0]["spans"]} >= {
        "plan:victims", "plan:scores", "plan:select"}
    assert tried >= 1
    assert committed == _whatif_count("preempt", "committed") \
        - before["committed"]
    assert rejected == sum(_whatif_count("preempt", o) - before[o]
                           for o in ("rejected-budget", "rejected-no-gain"))
    assert (committed if outcome == "committed" else rejected) >= 1
    assert victims == len(evictor.evicts)
    assert (victims >= 1) == (outcome == "committed")
    store.close()


def test_a_conf_without_eviction_actions_has_no_plan_span_or_block(monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    store = ClusterStore(evictor=FakeEvictor(), binder=FakeBinder())
    ClusterSimulator.priority_tier_workload(store, workers=2,
                                            serving_tasks=1)
    conf = PREEMPT_CONF.replace('"enqueue, allocate, preempt"',
                                '"enqueue, allocate"')
    assert "preempt" not in conf.split("tiers")[0]
    for rec in _plan_records(store, conf):
        assert rec["whatif"] is None
        assert not any(s["name"].startswith("plan:")
                       or s["name"].endswith("_plan") for s in rec["spans"])
    store.close()


# --------------- the victim table against the per-gang construction (PR 50)


def _per_gang_try(cyc, action, jrow):
    """What ``whatif._plan_evict_gang`` built for every gang before PR 50,
    kept as the reference: the base rows with the gang masked out, the
    kernel's columns filled for this gang alone, a uid string a row for
    its group and ``MigrationLedger.disrupted`` asked once a group.  It
    counts nothing and sets no backoff: ``(plan fields or None, would set
    the backoff, would count rejected-budget)``."""
    import jax

    from volcano_tpu.api import TaskStatus
    from volcano_tpu.fastpath import _pow2

    m, store = cyc.m, cyc.store
    F, I = np.float32, np.int32
    is_reclaim = action == "reclaim"
    need = int(m.j_minav[jrow] - cyc.j_ready_base[jrow])
    if need <= 0:
        return None, False, False
    gang_rows, prof_req = whatif._gang_profile_table(cyc, jrow)
    if prof_req is None:
        return None, False, False
    Pn = cyc.Pn
    vict = np.flatnonzero(
        cyc.resident[:Pn] & (m.p_status[:Pn] == int(TaskStatus.Running))
        & ~m.p_critical[:Pn] & ~m.p_has_ip[:Pn] & (cyc.jobr >= 0)
        & (cyc.jobr != jrow))
    if len(vict):
        vict = vict[m.c_req.lens(vict) > 0]
    vict = vict.astype(np.int64)
    if not len(vict):
        return None, False, False
    V = len(vict)
    Vp, Np, Qp = _pow2(V), _pow2(max(cyc.Nn, 1)), _pow2(max(cyc.Qn, 1), 4)
    v_ok = np.zeros(Vp, bool)
    v_jprio, v_crank, v_queue, v_node = (np.zeros(Vp, I) for _ in range(4))
    v_tie = np.arange(Vp, dtype=I)
    v_req = np.zeros((Vp, cyc.R), F)
    vjobs = cyc.jobr[vict].astype(np.int64)
    v_ok[:V] = cyc.q_of_job[vjobs] >= 0
    v_jprio[:V] = m.j_prio[vjobs]
    v_crank[:V] = np.argsort(np.argsort(m.p_create[vict], kind="stable"))
    v_queue[:V] = cyc.q_of_job[vjobs]
    v_node[:V] = m.p_node[:Pn][vict]
    er, si, vv = m.c_req.gather(vict)
    v_req[er, si] = vv
    q_alloc_p = np.zeros((Qp, cyc.R), F)
    q_des_p = np.full((Qp, cyc.R), 3.0e38, F)
    q_alloc_p[:cyc.Qn] = cyc.q_alloc
    q_des_p[:cyc.Qn] = cyc.q_deserved
    q_rec = np.zeros(Qp, bool)
    for name, qi in cyc.queue_index.items():
        q = store.queues.get(name)
        q_rec[qi] = bool(q is not None and q.reclaimable())
    planes = vk.victim_scores(
        v_ok, v_jprio, v_crank, v_tie, v_queue, v_node, v_req,
        np.int32(int(m.j_prio[jrow])), np.int32(int(cyc.q_of_job[jrow])),
        q_alloc_p, q_des_p, q_rec,
        np.int32(vk.RECLAIM if is_reclaim else vk.PREEMPT),
        np.zeros((Np, cyc.R), F))
    eligible, order, evictable = jax.device_get(
        (planes.eligible, planes.order, planes.evictable))
    if not bool(eligible[:V].any()):
        return None, False, False
    groups = [m.j_uid[int(j)] for j in vjobs]
    ledger = store.migrations
    budget_left = {}
    for uid in set(groups):
        row = m.j_row.get(uid, -1)
        used = ledger.disrupted(store, uid) if ledger is not None else 0
        budget_left[uid] = max_unavailable_of(
            m.j_pg[row] if row >= 0 else None) - used
    idle_p = np.zeros((Np, cyc.R), F)
    idle_p[:cyc.Nn] = cyc.n_idle.astype(F)
    sel = vk.select_victims(
        order, eligible, v_node, v_req,
        np.concatenate([vjobs, np.full(Vp - V, -1, np.int64)]),
        groups + [""] * (Vp - V), v_queue, need, idle_p, evictable,
        prof_req, cyc.eps, cyc.j_ready_base, m.j_minav, budget_left,
        whatif.evict_cap(),
        q_alloc=cyc.q_alloc.astype(F) if is_reclaim else None,
        q_deserved=cyc.q_deserved.astype(F) if is_reclaim else None)
    if not sel.feasible:
        return None, True, sel.budget_blocked
    chosen = np.asarray(sel.chosen, np.int64)
    budgets = {}
    for j in vjobs[chosen].tolist():
        budgets[m.j_uid[j]] = budgets.get(m.j_uid[j], 0) + 1
    return ({"gang_rows": gang_rows, "victim_rows": vict[chosen],
             "victim_jobs": vjobs[chosen], "budgets": budgets,
             "need": need}, False, False)


@pytest.fixture
def shadowed(monkeypatch):
    """Every try of the plan loops runs the per-gang reference first and
    is held to it: the same ``WhatIfPlan`` or the same ``None``, the same
    backoff, the same ``rejected-budget``.  Yields the tries seen, as
    ``(action, gang uid, victims or None)``."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    real, seen = whatif._plan_evict_gang, []

    def both(cyc, action, jrow):
        uid = cyc.m.j_uid[jrow]
        want, backs_off, rejects = _per_gang_try(cyc, action, jrow)
        _, backoff = whatif._streak_maps(cyc.store)
        assert not backoff.get((action, uid)), "a gang in backoff is not tried"
        rejected = cyc.stats["whatif"]["rejected"]
        got = real(cyc, action, jrow)
        assert bool(backoff.get((action, uid))) == backs_off, (action, uid)
        assert cyc.stats["whatif"]["rejected"] - rejected == rejects, uid
        assert (got is None) == (want is None), (action, uid, got, want)
        if got is not None:
            assert (got.action, got.gang_job, got.gang_uid) == (action, jrow, uid)
            assert got.need == want["need"] and got.budgets == want["budgets"]
            assert list(got.budgets) == list(want["budgets"])
            for key in ("gang_rows", "victim_rows", "victim_jobs"):
                np.testing.assert_array_equal(getattr(got, key), want[key],
                                              err_msg=f"{action} {uid} {key}")
        seen.append((action, uid, None if got is None else len(got.victim_rows)))
        return got

    monkeypatch.setattr(whatif, "_plan_evict_gang", both)
    return seen


BOTH_CONF = PREEMPT_CONF.replace('"enqueue, allocate, preempt"',
                                 '"enqueue, allocate, preempt, reclaim"')


def _cluster(nodes=4, queues=()):
    """``nodes`` nodes of 8 cpu, the classes ``high`` and ``low``, and the
    queues named: ``(name, weight, reclaimable)``."""
    store = ClusterStore(evictor=FakeEvictor(), binder=FakeBinder())
    store.add_priority_class(PriorityClass(name="high", value=1000))
    store.add_priority_class(PriorityClass(name="low", value=10))
    for i in range(nodes):
        store.add_node(Node(name=f"n{i}", allocatable={
            "cpu": "8", "memory": "32Gi", "pods": 110}))
    for name, weight, reclaimable in queues:
        store.add_queue(Queue(name=name, weight=weight, reclaimable=reclaimable))
    return store


def _group(store, name, klass, on=(), pending=0, queue="default", **spec):
    """A PodGroup of 4-cpu pods: one Running on each node of ``on`` and
    ``pending`` more Pending."""
    prio = store.priority_classes[klass].value
    store.add_pod_group(PodGroup(name=name, queue=queue, priority_class=klass,
                                 **{"min_member": 1, **spec}))
    if on:
        store.pod_groups[f"default/{name}"].status.phase = \
            PodGroupPhase.Running.value
    for i, node in enumerate(on):
        store.add_pod(running_pod(f"{name}-r{i}", name, "4", node, prio=prio))
    for i in range(pending):
        store.add_pod(pending_pod(f"{name}-p{i}", name, "4", prio=prio))


FULL = ("n0", "n0", "n1", "n1", "n2", "n2", "n3")  # and one slot on n3


def _elastic_gang_with_running_pods_of_its_own():
    store = _cluster()
    _group(store, "lo", "low", on=FULL, max_unavailable=8)
    _group(store, "hi", "high", on=("n3",), pending=2, min_member=3)
    return store, PREEMPT_CONF, {("preempt", "default/hi", 2)}


def _a_victim_job_whose_queue_was_deleted():
    store = _cluster(queues=[("qx", 1, True)])
    _group(store, "lo", "low", on=FULL[:5], max_unavailable=8)
    _group(store, "gone", "low", on=("n2", "n3", "n3"), queue="qx",
           max_unavailable=8)
    store.delete_queue("qx")
    _group(store, "hi", "high", pending=1)
    return store, BOTH_CONF, {("preempt", "default/hi", 1)}


def _ledger_entries_in_flight_use_up_the_budget():
    store = _cluster()
    _group(store, "lo", "low", on=FULL + ("n3",), max_unavailable=2)
    _group(store, "hi", "high", pending=1)
    for i in range(2):      # two of lo's are on their way out for another gang
        ledger_of(store).register(f"default/lo-gone{i}", "default/lo", "",
                                  action="reclaim", for_gang="default/other")
    return store, PREEMPT_CONF, {("preempt", "default/hi", None)}


def _reclaim_after_a_preempt_that_committed():
    store = _cluster(6, queues=[("qa", 1, True), ("qb", 1, True),
                                ("qc", 1, True)])
    _group(store, "a", "low", on=FULL + ("n3",), queue="qa", max_unavailable=8)
    _group(store, "b", "low", on=("n4", "n4", "n5", "n5"), queue="qb",
           max_unavailable=8)
    _group(store, "hi", "high", pending=1, queue="qb")
    _group(store, "w", "low", pending=1, queue="qc")
    return store, BOTH_CONF, {("preempt", "default/hi", 1),
                              ("reclaim", "default/w", 1)}


def _reclaim_after_a_preempt_that_found_nothing():
    store = _cluster(queues=[("qa", 1, True), ("qb", 1, True)])
    _group(store, "a", "low", on=FULL + ("n3",), queue="qa", max_unavailable=8)
    _group(store, "w", "low", pending=1, queue="qb")
    return store, BOTH_CONF, {("preempt", "default/w", None),
                              ("reclaim", "default/w", 1)}


EQUIVALENCE = {f.__name__[1:]: f for f in (
    _elastic_gang_with_running_pods_of_its_own,
    _a_victim_job_whose_queue_was_deleted,
    _ledger_entries_in_flight_use_up_the_budget,
    _reclaim_after_a_preempt_that_committed,
    _reclaim_after_a_preempt_that_found_nothing)}
# tables built in the first cycle that plans, where the case is about that
TABLES = {"reclaim_after_a_preempt_that_committed": 2,
          "reclaim_after_a_preempt_that_found_nothing": 1}


@pytest.mark.parametrize("case", list(EQUIVALENCE) + ["the_toy_cell_that_evicts"])
def test_the_table_path_plans_what_the_per_gang_construction_planned(
        case, shadowed, request):
    """Plan for plan (``shadowed``), for every gang the loops try."""
    if case == "the_toy_cell_that_evicts":
        from benchmark import run as bench_run
        from benchmark.harness import cell as cell_mod

        cell = cell_mod.load_cell(
            "toypre.pre", request.getfixturevalue("toy_preempt"))
        result = bench_run.run(cell, seed=2**31 + 50, seconds=0.5, trace=False)
        assert result["correct"] is True, result
        assert {a for a, _uid, _n in shadowed} == {"preempt", "reclaim"}
        assert sum(n or 0 for _a, _uid, n in shadowed) >= 8
        assert any(n is None for _a, _uid, n in shadowed)
        return
    store, conf, expected = EQUIVALENCE[case]()
    records = _plan_records(store, conf, cycles=2)
    assert expected <= set(shadowed), shadowed
    first = [r["whatif"] for r in records if r["whatif"] is not None][0]
    assert first["tables_built"] == TABLES.get(case, 1), first
    if case == "ledger_entries_in_flight_use_up_the_budget":
        assert first["outcome"] == "rejected-budget" and first["rejected"] == 1
    store.close()


# ------------------------------------------------ the host gate (PR 50)


@pytest.mark.parametrize("reclaimable", [True, False],
                         ids=["other-reclaimable", "other-not"])
@pytest.mark.parametrize("lower", ["own-queue", "other-queue", "nowhere"])
@pytest.mark.parametrize("action", ["preempt", "reclaim"])
def test_the_gate_says_no_only_where_the_kernel_finds_no_victim(
        monkeypatch, action, lower, reclaimable):
    """A high gang waits in ``qa``; the cluster is full of ``qa``'s and
    ``qb``'s Running pods, ``qb`` over its share, and a lower class runs
    in the gang's queue, only in the other, or nowhere.  On the cycle's
    own table the gate is the kernel's ``eligible.any()`` wherever it
    says no, and it says no wherever integers alone can."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    store = _cluster(queues=[("qa", 1, True), ("qb", 1, reclaimable)])
    own, other = {"own-queue": ("low", "high"), "other-queue": ("high", "low"),
                  "nowhere": ("high", "high")}[lower]
    _group(store, "ra", own, on=FULL[:3], queue="qa", max_unavailable=8)
    _group(store, "rb", other, on=FULL[3:] + ("n3",), queue="qb",
           max_unavailable=8)
    _group(store, "hi", "high", pending=1, queue="qa")
    seen = []

    def spy(cyc, act):
        cyc._flush_aggr()
        jrow = cyc.m.j_row["default/hi"]
        prio = np.int32(cyc.m.j_prio[jrow])
        queue = np.int32(cyc.q_of_job[jrow])
        mode = np.int32(vk.RECLAIM if act == "reclaim" else vk.PREEMPT)
        tbl = whatif.VictimTable(cyc)
        planes = vk.victim_scores(
            tbl.v_ok & (tbl.vjobs != jrow), tbl.v_jprio, tbl.v_crank,
            tbl.v_tie, tbl.v_queue, tbl.v_node, tbl.v_req, prio, queue,
            tbl.q_alloc, tbl.q_deserved, tbl.q_rec, mode, tbl.node_zero)
        seen.append((act, vk.may_be_eligible(tbl.q_minprio, tbl.q_rec, mode,
                                             prio, queue),
                     bool(np.asarray(planes.eligible).any())))

    monkeypatch.setattr(whatif, "run_evict_action", spy)
    Scheduler(store, conf_str=PREEMPT_CONF.replace("preempt", action)).run_once()
    (act, gate, eligible), = seen
    assert act == action and (gate or not eligible)
    if action == "preempt":
        assert gate == eligible == (lower == "own-queue")
    else:       # qb holds 20 cpu of the 32 and deserves 16
        assert gate == eligible == reclaimable
    store.close()


def test_waiting_gangs_of_the_lowest_class_reach_no_kernel(monkeypatch):
    """N gangs of class ``low`` wait on a cluster full of ``low``: preempt
    tries each, builds one table for all of them and calls no kernel."""
    monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1")
    store = _cluster()
    _group(store, "lo", "low", on=FULL + ("n3",), max_unavailable=8)
    for i in range(3):
        _group(store, f"w{i}", "low", pending=1)
    calls = []
    monkeypatch.setattr(vk, "victim_scores", lambda *a: calls.append(a))
    rec, = _plan_records(store, PREEMPT_CONF, cycles=1)
    assert rec["whatif"] == dict.fromkeys(whatif.WALK_COUNTS, 0) | {
        "gangs_tried": 3, "tables_built": 1}
    assert [s["name"] for s in rec["spans"]
            if s["name"].startswith("plan:")] == ["plan:victims"] * 3
    assert not calls and not store.evictor.evicts
    assert not whatif._streak_maps(store)[1], "a gated try sets no backoff"
    store.close()
