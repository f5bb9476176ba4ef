"""The traffic generator: the same seed gives the same batch, another seed
the same sizes in another order, and the parameters later mixes need
(queues and weights, gang-size sets, the affinity mix) are read from the
configuration file."""

import itertools
from collections import Counter

import numpy as np
import pytest

from benchmark.harness import generate

CONFIG = {
    "nodes": {"count": 8, "cpu": 64, "memory_gi": 256, "pods": 256, "zones": 4},
    "pods": {"cpu_choices": [1, 2, 4], "mem_gi_choices": [2, 4, 8]},
    "gang": {"size": 4},
}
BIG_SEED = 2**31 + 12345


def test_same_seed_same_plan():
    a = generate.Generator(CONFIG, BIG_SEED).plan(360, "x")
    b = generate.Generator(CONFIG, BIG_SEED).plan(360, "x")
    assert a.names == b.names
    assert np.array_equal(a.cpu_milli, b.cpu_milli)
    assert np.array_equal(a.mem_bytes, b.mem_bytes)


def test_other_seed_same_sizes_other_order():
    a = generate.Generator(CONFIG, 1).plan(360, "x")
    b = generate.Generator(CONFIG, 2).plan(360, "x")
    sizes = lambda p: Counter(zip(p.gang_cpu, p.gang_mem_gi))  # noqa: E731
    assert sizes(a) == sizes(b)
    assert len(sizes(a)) == 9 and set(sizes(a).values()) == {10}
    assert a.gang_cpu != b.gang_cpu


def test_queues_gang_sizes_and_affinity_mix_come_from_the_file():
    config = {**CONFIG, "gang": {"sizes": [2, 4, 8]},
              "queues": {"count": 3, "weights": [1, 2, 4]},
              "affinity_mix": {"affinity": 0.3, "anti_affinity": 0.3,
                               "spread": 0.3}}
    plan = generate.Generator(config, 5).plan(200, "m")
    assert plan.n_pods == 200
    assert set(plan.gang_min_member.tolist()) <= {2, 4, 8}
    assert set(plan.gang_queue) == {"default", "queue-1", "queue-2"}
    assert {"affinity", "anti_affinity", "spread"} <= set(plan.gang_kind)
    queues = generate.to_queues(config)
    assert [(q.name, q.weight) for q in queues] == [("queue-1", 2),
                                                    ("queue-2", 4)]
    gangs = generate.to_objects(plan, itertools.count(1))
    pods = [p for _pg, ps in gangs for p in ps]
    assert len(pods) == 200
    assert any(p.affinity for p in pods) and any(p.anti_affinity for p in pods)
    assert any(p.topology_spread for p in pods)
    stamps = [p.creation_timestamp for p in pods]
    assert stamps == sorted(stamps)


def test_nodes_carry_the_configurations_shape():
    nodes = generate.to_nodes(CONFIG)
    assert len(nodes) == 8 and nodes[5].labels == {"zone": "zone-1"}
    assert nodes[0].allocatable == {"cpu": "64", "memory": "256Gi", "pods": 256}
    alloc = generate.node_alloc(CONFIG)
    assert alloc[0].tolist() == [64000, 256 * 2**30, 256]


# ---- ISSUE 40: priorities, reclaimable queues, pods that may wait ----------

# sha256 over the plans of a cell's set-up, first window round and first probe
# (names, requests, gangs, queues, kinds) for seed 2**31 + 40, computed with
# the parent's generate.py (commit d4b4efd), first 16 hex digits.
PARENT_DIGEST = {
    "north-10k.burst": "5752b2e4e28006e6",
    "binpack-1k.burst": "dc82f6be543950e3",
    "north-10k.churn": "4aefe150372b5d4b",
    "drf-5k.burst": "4b9f7e76d3493d02",
    "affinity-10k.burst": "8883f58fbe26b3b5",
    "binpack-1k.churn": "25e21d0e29501fa3",
    "hyper-50k.burst": "8883f58fbe26b3b5",
}


def _digest(plan):
    import hashlib
    import json

    h = hashlib.sha256()
    for part in (plan.tag, plan.names, plan.cpu_milli.tolist(),
                 plan.mem_bytes.tolist(), plan.gang.tolist(), plan.gang_names,
                 plan.gang_min_member.tolist(), plan.gang_queue, plan.gang_cpu,
                 plan.gang_mem_gi, plan.gang_kind):
        h.update(json.dumps(part).encode())
    return h.hexdigest()


@pytest.mark.parametrize("cell_name", sorted(PARENT_DIGEST))
def test_the_seven_cells_plans_are_the_parents_byte_for_byte(cell_name):
    import hashlib

    from benchmark.harness import cell as cell_mod

    c = cell_mod.load_cell(cell_name)
    s = c.sizes()
    gen = generate.Generator(c.config, 2**31 + 40)
    plans = []
    if s["resident_pods"]:
        plans.append(gen.plan(s["resident_pods"], "resident",
                              klass=s["resident_class"]))
    for i in range(s["warmup_rounds"]):
        plans.append(gen.plan(s["batch_pods"], f"warm{i:02d}"))
    plans.append(gen.plan(s["batch_pods"], "w0000"))
    plans.append(gen.plan(1, "probe000", gang_size=1))
    h = hashlib.sha256()
    for plan in plans:
        h.update(_digest(plan).encode())
        # and nothing of what ISSUE 40 added is set
        assert (plan.gang_priority, plan.gang_size, plan.gang_max_unavailable,
                plan.may_wait) == ([], None, [], False)
        assert plan.sizes() is plan.gang_min_member
    assert h.hexdigest()[:16] == PARENT_DIGEST[cell_name]


TIERS = {**CONFIG, "gang": {"size": 4},
         "queues": {"count": 2, "weights": [1, 3], "reclaimable": [True, False]},
         "priority_classes": [
             {"name": "low", "value": 10, "share": 0.75,
              "gang": {"sizes": [1, 2], "min_member": 1, "max_unavailable": 2}},
             {"name": "high", "value": 1000, "share": 0.25}]}


def test_a_class_brings_its_rank_and_its_own_gang():
    gen = generate.Generator(TIERS, BIG_SEED)
    low = gen.plan(60, "res", klass="low")
    assert set(low.gang_priority) == {"low"} and not low.may_wait
    assert set(low.sizes().tolist()) == {1, 2}          # the class's own sizes
    assert set(low.gang_min_member.tolist()) == {1}     # elastic: floor 1
    assert low.gang_size is not None and low.sizes().sum() == low.n_pods == 60
    assert low.gang_max_unavailable == [2] * len(low.gang_names)
    high = gen.plan(40, "burst", klass="high", may_wait=True)
    assert set(high.gang_priority) == {"high"} and high.may_wait
    assert high.gang_size is None and set(high.sizes().tolist()) == {4}
    assert high.gang_max_unavailable == [None] * 10
    values = {"low": 10, "high": 1000}
    stamps = itertools.count(1)
    for plan, value in ((low, 10), (high, 1000)):
        gangs = generate.to_objects(plan, stamps, values)
        assert sum(len(pods) for _pg, pods in gangs) == plan.n_pods
        for g, (pg, pods) in enumerate(gangs):
            assert pg.priority_class == plan.gang_priority[g]
            assert pg.min_member == plan.gang_min_member[g] <= len(pods)
            assert len(pods) == plan.sizes()[g]
            assert pg.max_unavailable == plan.gang_max_unavailable[g]
            assert {(p.priority_class, p.priority) for p in pods} \
                == {(pg.priority_class, value)}
    # names and keys stay what the validator expects
    assert low.keys()[0] == "default/res-pg-000000-0"


def test_without_a_named_class_the_shares_deal_them_from_the_seed():
    a = generate.Generator(TIERS, 11).plan(400, "x")
    b = generate.Generator(TIERS, 11).plan(400, "x")
    assert a.gang_priority == b.gang_priority and a.names == b.names
    share = Counter(a.gang_priority)
    assert set(share) == {"low", "high"}
    assert 0.6 < share["low"] / len(a.gang_priority) < 0.9
    other = generate.Generator(TIERS, 12)
    assert other.plan(400, "x").gang_priority != a.gang_priority
    # the traffic's batch class, once set, is every unnamed plan's
    other.batch_class = "high"
    assert set(other.plan(40, "y").gang_priority) == {"high"}
    assert set(other.plan(40, "z", klass="low").gang_priority) == {"low"}


def test_queues_say_what_may_be_reclaimed_and_classes_reach_the_store():
    queues = generate.to_queues(TIERS)
    # the default queue is stated again, with its weight, where the file says
    # what may be reclaimed from it
    assert [(q.name, q.weight, q.reclaimable) for q in queues] \
        == [("default", 1, True), ("queue-1", 3, False)]
    plain = generate.to_queues({**TIERS, "queues": {"count": 2, "weights": [1, 3]}})
    assert [(q.name, q.weight, q.reclaimable) for q in plain] == [("queue-1", 3, True)]
    assert [(c.name, c.value) for c in generate.to_priority_classes(TIERS)] \
        == [("low", 10), ("high", 1000)]
    assert generate.to_priority_classes(CONFIG) == []
    assert generate.node_labels(CONFIG)[5] == {"zone": "zone-1"}


# ---- ISSUE 42: a class may name the queues it is dealt to ------------------

FOUR_QUEUES = {**TIERS, "queues": {"count": 4, "weights": [1, 2, 4, 8]}}


def _with_queues(names):
    low, high = TIERS["priority_classes"]
    return {**FOUR_QUEUES, "priority_classes": [low, {**high, "queues": names}]}


def test_a_class_that_names_its_queues_is_dealt_there_and_only_there():
    named = ["queue-1", "queue-2", "queue-3"]
    gen = generate.Generator(_with_queues(named), BIG_SEED)
    low = gen.plan(60, "res", klass="low")
    high = gen.plan(48, "burst", klass="high")
    # in turn over its own three; the class that names none over all four
    assert Counter(high.gang_queue) == {q: 4 for q in named}
    assert set(low.gang_queue) == {"default", *named}
    # the queues apart, both plans are what they are without the key
    plain = generate.Generator(FOUR_QUEUES, BIG_SEED)
    assert plain.plan(60, "res", klass="low").gang_queue == low.gang_queue
    same = plain.plan(48, "burst", klass="high")
    assert (same.names, same.gang_cpu, same.gang_priority) \
        == (high.names, high.gang_cpu, high.gang_priority)
    assert set(same.gang_queue) == {"default", *named}


@pytest.mark.parametrize("names", [["queue-1", "queue-4"], []])
def test_a_class_that_names_a_queue_the_configuration_lacks_raises(names):
    with pytest.raises(ValueError, match="names queues"):
        generate.Generator(_with_queues(names), BIG_SEED)


# ---- ISSUE 52: a gang that enters as a Job ----------------------------------

JOBS = {**CONFIG, "gang": {"size": 6, "min_member": 3},
        "queues": {"count": 2, "weights": [1, 3]},
        "job": {"plugins": {"ssh": [], "env": [], "svc": ["--disable-network-policy"]},
                "policies": [{"event": "PodEvicted", "action": "RestartJob"},
                             {"exit_code": 3, "action": "AbortJob"}],
                "max_retry": 5}}


def test_a_gangs_own_floor_makes_it_elastic_without_a_class():
    plan = generate.Generator(JOBS, BIG_SEED).plan(20, "x")
    assert plan.sizes().tolist() == [6, 6, 6, 2]
    assert plan.gang_min_member.tolist() == [3, 3, 3, 2]
    assert plan.gang_priority == [] and plan.gang_max_unavailable == []
    gangs = generate.to_objects(plan, itertools.count(1))
    assert [(pg.min_member, len(pods)) for pg, pods in gangs] \
        == [(3, 6), (3, 6), (3, 6), (2, 2)]


def test_under_jobs_the_plan_is_the_same_draw_with_the_controllers_names():
    pods = generate.Generator(JOBS, BIG_SEED).plan(20, "x")
    jobs = generate.Generator(JOBS, BIG_SEED, entry="jobs").plan(20, "x")
    assert pods.names[:2] == ["x-pg-000000-0", "x-pg-000000-1"]
    assert jobs.names[:2] == ["x-pg-000000-worker-0", "x-pg-000000-worker-1"]
    assert jobs.names[-1] == "x-pg-000003-worker-1"
    assert jobs.job_keys() == ["default/x-pg-00000%d" % g for g in range(4)]
    for name in ("cpu_milli", "mem_bytes", "gang", "gang_min_member"):
        assert np.array_equal(getattr(pods, name), getattr(jobs, name)), name
    assert (pods.gang_names, pods.gang_queue, pods.gang_cpu, pods.gang_mem_gi) \
        == (jobs.gang_names, jobs.gang_queue, jobs.gang_cpu, jobs.gang_mem_gi)


def test_a_gang_becomes_one_job_of_one_task_with_the_configurations_block():
    from volcano_tpu.controllers import JobController

    plan = generate.Generator(JOBS, BIG_SEED, entry="jobs").plan(20, "x")
    gangs = generate.to_jobs(plan, itertools.count(1), JOBS["job"])
    assert [keys for _job, keys in gangs] \
        == [plan.keys()[0:6], plan.keys()[6:12], plan.keys()[12:18],
            plan.keys()[18:20]]
    stamps = [job.creation_timestamp for job, _keys in gangs]
    assert stamps == sorted(stamps) == [1.0, 2.0, 3.0, 4.0]
    for g, (job, keys) in enumerate(gangs):
        assert (job.key, job.queue, job.priority_class) \
            == (plan.job_keys()[g], plan.gang_queue[g], "")
        assert job.min_available == int(plan.gang_min_member[g])
        (task,) = job.tasks
        assert (task.name, task.replicas) == ("worker", len(keys))
        assert task.containers == [{"cpu": str(plan.gang_cpu[g]),
                                    "memory": f"{plan.gang_mem_gi[g]}Gi"}]
        assert job.plugins == JOBS["job"]["plugins"]
        assert job.plugins is not JOBS["job"]["plugins"]
        assert [(p.event, p.exit_code, p.action) for p in job.policies] \
            == [("PodEvicted", None, "RestartJob"), ("", 3, "AbortJob")]
        assert job.max_retry == 5
        # the names the plan states are the ones the controller will give
        assert [f"default/{JobController._pod_name(None, job, task, i)}"
                for i in range(task.replicas)] == keys
    # no block: none of it
    (bare, _keys) = generate.to_jobs(plan, itertools.count(1))[0]
    assert (bare.plugins, bare.policies, bare.max_retry) == ({}, [], 3)
    # a class on the gang is the Job's
    tiers = generate.Generator(TIERS, BIG_SEED, entry="jobs").plan(8, "t", klass="high")
    assert {job.priority_class for job, _k in generate.to_jobs(
        tiers, itertools.count(1))} == {"high"}
