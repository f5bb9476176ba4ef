"""The validator refuses what it is there to refuse, each from a hand-made
record: an oversubscribed node, a split gang, a missing bind, a double bind
and a bind nobody asked for."""

import numpy as np
import pytest

from benchmark.harness import generate, validate

CONFIG = {
    "nodes": {"count": 2, "cpu": 8, "memory_gi": 16, "pods": 4, "zones": 0},
    "pods": {"cpu_choices": [2], "mem_gi_choices": [4]},
    "gang": {"size": 2},
}
NODES = generate.node_names(CONFIG)
ALLOC = generate.node_alloc(CONFIG)


def _plan(n_pods=4, tag="r0"):
    return generate.Generator(CONFIG, seed=1).plan(n_pods, tag)


def _check(plan, binds, deleted=()):
    keys = [k for k, _ in binds]
    hosts = [h for _, h in binds]
    ev = validate.RoundEvents(plan, [(1, keys, hosts)], list(deleted))
    return validate.check(NODES, ALLOC, [ev])


def test_sound_record_passes():
    plan = _plan()
    k = plan.keys()
    v = _check(plan, [(k[0], NODES[0]), (k[1], NODES[0]),
                      (k[2], NODES[1]), (k[3], NODES[1])])
    assert v.ok and v.bound == 4 and v.failed == 0
    assert v.worst_fill == pytest.approx(0.5)


def test_oversubscribed_node():
    plan = _plan(6)  # 6 pods x 2 cpu on a node of 8 cpu and 4 pod slots
    v = _check(plan, [(k, NODES[0]) for k in plan.keys()])
    assert not v.ok and v.oversubscribed > 0
    assert v.unbound == 0 and v.split == 0


def test_split_gang():
    plan = _plan()
    k = plan.keys()
    v = _check(plan, [(k[0], NODES[0]), (k[2], NODES[1]), (k[3], NODES[1])])
    assert not v.ok
    assert v.split == 1 and v.unbound == 1


def test_missing_bind():
    plan = _plan()
    k = plan.keys()
    v = _check(plan, [(k[0], NODES[0]), (k[1], NODES[0])])
    assert not v.ok and v.unbound == 2 and v.split == 0


def test_double_bind():
    plan = _plan()
    k = plan.keys()
    v = _check(plan, [(k[0], NODES[0]), (k[1], NODES[0]), (k[2], NODES[1]),
                      (k[3], NODES[1]), (k[0], NODES[1])])
    assert not v.ok and v.double == 1


@pytest.mark.parametrize("key,host", [("default/nobody-0", NODES[0]),
                                      (None, "node-999999")])
def test_unknown_pod_or_node(key, host):
    plan = _plan()
    k = plan.keys()
    v = _check(plan, [(k[0], NODES[0]), (k[1], NODES[0]), (k[2], NODES[1]),
                      (key or k[3], host)])
    assert not v.ok and v.unknown == 1


def test_a_deleted_pod_cannot_be_bound_again_and_frees_its_node():
    plan = _plan(2)
    k = plan.keys()
    first = validate.RoundEvents(plan, [(1, k, [NODES[0]] * 2)], k)
    ledger = validate.Ledger(NODES, ALLOC)
    ledger.apply(first)
    assert ledger.verdict.ok and not ledger.used.any()
    late = validate.RoundEvents(_plan(2, "r1"), [(2, [k[0]], [NODES[0]])], [])
    ledger.apply(late)
    assert ledger.verdict.unknown == 1 and ledger.verdict.unbound == 2


def test_ledger_is_integer():
    assert ALLOC.dtype == np.int64
    assert _plan().mem_bytes.dtype == np.int64
