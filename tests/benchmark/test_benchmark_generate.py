"""The traffic generator: the same seed gives the same batch, another seed
the same sizes in another order, and the parameters later mixes need
(queues and weights, gang-size sets, the affinity mix) are read from the
configuration file."""

import itertools
from collections import Counter

import numpy as np

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
