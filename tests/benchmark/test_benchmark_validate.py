"""The validator refuses what it is there to refuse, each from a hand-made
record: an oversubscribed node, a split gang, a missing bind, a double bind
and a bind nobody asked for; and, since a round can hold an eviction, a victim
nobody knows, a victim evicted twice, a termination that never ended, a gang
broken under its floor, a bind stamped before the termination that made its
room, a pod lost and a pod nobody submitted."""

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


# ---- ISSUE 40: victims ------------------------------------------------------


def _full(n_pods=8, tag="res"):
    """Both nodes full: 4 pods of 2 cpu on each (8 cpu, 4 pod slots)."""
    plan = _plan(n_pods, tag)
    k = plan.keys()
    hosts = [NODES[0]] * 4 + [NODES[1]] * 4
    return plan, k, validate.RoundEvents(plan, [(1, k, hosts)])


def _burst(binds, evictions=(), terminations=(), may_wait=False, t_deleted=None,
           deleted=()):
    plan = _plan(2, "hi")
    plan.may_wait = may_wait
    arrivals = [(t, [plan.keys()[i]], [NODES[0]]) for t, i in binds]
    return plan, validate.RoundEvents(plan, arrivals, list(deleted),
                                      list(evictions), list(terminations),
                                      t_deleted)


def _run(*events, live_keys=None):
    return validate.check(NODES, ALLOC, list(events), live_keys)


def test_an_eviction_ended_makes_room_and_a_new_life():
    _res, k, first = _full()
    # two victims on node 0 evicted at 10, ended at 20; the burst bound at 30
    _hi, second = _burst([(30, 0), (31, 1)], [(10, k[0]), (10, k[1])],
                         [(20, k[0]), (21, k[1])])
    v = _run(first, second)
    assert v.ok and (v.evictions, v.terminations) == (2, 2)
    assert v.worst_fill == pytest.approx(1.0)
    # the victims wait (no limit) and are bound again later: a new life
    assert v.waiting_victims == 2
    third = validate.RoundEvents(_plan(0, "t"), [(50, [k[0]], [NODES[1]])],
                                 [k[4]], t_deleted=40)
    v = _run(first, second, third)
    assert v.ok and v.double == 0 and v.waiting_victims == 1


def test_a_bind_stamped_before_the_termination_that_made_its_room():
    _res, k, first = _full()
    _hi, second = _burst([(15, 0), (30, 1)], [(10, k[0]), (10, k[1])],
                         [(20, k[0]), (21, k[1])])
    v = _run(first, second)
    assert not v.ok and v.oversubscribed == 1 and v.unbound == 0


def test_the_same_binds_with_no_order_would_pass():
    """What the ordered replay is for: without stamps on the frees the
    round's end looks sound."""
    _res, k, first = _full()
    plan, second = _burst([(15, 0), (30, 1)])
    second.deleted = [k[0], k[1]]
    ledger = validate.Ledger(NODES, ALLOC)
    ledger.apply(first)
    ledger._delete([k[0], k[1]])            # freed before anything was bound
    ledger.apply(validate.RoundEvents(plan, second.arrivals))
    assert ledger.verdict.ok


def test_a_settle_bind_takes_the_room_the_completions_freed():
    _res, k, first = _full()
    # a pod that may wait is submitted onto the full cluster ...
    wait = _plan(1, "wt")
    wait.may_wait = True
    second = validate.RoundEvents(wait, [])
    # ... and bound in a later round's settle cycle, after that round's
    # completions (t_deleted 20) freed a slot
    third = validate.RoundEvents(_plan(0, "t"), [(30, wait.keys(), [NODES[0]])],
                                 [k[0]], t_deleted=20)
    v = _run(first, second, third)
    assert v.ok and (v.may_wait, v.waiting, v.unbound) == (1, 0, 0)
    # stamped before the completions it is one pod too many
    early = validate.RoundEvents(_plan(0, "t"), [(10, wait.keys(), [NODES[0]])],
                                 [k[0]], t_deleted=20)
    v = _run(first, second, early)
    assert not v.ok and v.oversubscribed == 1
    # never bound, it waits: counted, no limit
    v = _run(first, second)
    assert v.ok and v.waiting == 1


def test_evicted_unknown_twice_and_never_terminated():
    _res, k, first = _full()
    _hi, ev = _burst([], [(10, "default/nobody-0")], may_wait=True)
    assert _run(first, ev).evicted_unknown == 1
    # a pending pod is no victim either
    lone = _plan(1, "p")
    lone.may_wait = True
    v = _run(first, validate.RoundEvents(lone, [], (), [(10, lone.keys()[0])]))
    assert v.evicted_unknown == 1
    _hi, ev = _burst([], [(10, k[0]), (11, k[0])], [(20, k[0])], may_wait=True)
    v = _run(first, ev)
    assert (v.evicted_twice, v.never_terminated, v.evicted_unknown) == (1, 0, 0)
    _hi, ev = _burst([], [(10, k[0]), (10, k[1])], [(20, k[0])], may_wait=True)
    v = _run(first, ev)
    assert not v.ok and (v.never_terminated, v.evicted_twice) == (1, 0)
    # a termination no eviction named
    _hi, ev = _burst([], [], [(20, k[0])], may_wait=True)
    assert _run(first, ev).evicted_unknown == 1
    # a victim whose gang completes while it terminates ended with its gang
    _hi, ev = _burst([], [(10, k[0])], [], may_wait=True, t_deleted=20,
                     deleted=[k[0], k[1]])
    v = _run(first, ev)
    assert v.never_terminated == 0 and v.failed == v.gang_broken == 1


def test_gang_broken_under_the_floor_unless_the_floor_is_one():
    _res, k, first = _full()            # gangs of 2, min_member 2
    _hi, ev = _burst([], [(10, k[0])], [(20, k[0])], may_wait=True)
    v = _run(first, ev)
    assert not v.ok and v.gang_broken == 1
    # both pods of the gang in one wave: 0 left, which is whole
    _hi, ev = _burst([], [(10, k[0]), (11, k[1])], [(20, k[0]), (21, k[1])],
                     may_wait=True)
    assert _run(first, ev).ok
    # in two waves, with a termination between them: the first broke it
    _hi, ev = _burst([], [(10, k[0]), (30, k[1])], [(20, k[0]), (40, k[1])],
                     may_wait=True)
    assert _run(first, ev).gang_broken == 1
    # an elastic gang (min_member 1) may lose any of its pods
    plan, k, first = _full()
    plan.gang_min_member = np.ones_like(plan.gang_min_member)
    _hi, ev = _burst([], [(10, k[0])], [(20, k[0])], may_wait=True)
    assert _run(first, ev).ok


def test_lost_and_ghost_against_the_stores_keys():
    _res, k, first = _full()
    assert _run(first, live_keys=list(k)).ok
    v = _run(first, live_keys=list(k[1:]))
    assert not v.ok and (v.lost, v.ghost) == (1, 0)
    v = _run(first, live_keys=list(k) + ["default/stranger-0"])
    assert not v.ok and (v.lost, v.ghost) == (0, 1)
    v = _run(first, live_keys=list(k) + [k[0]])          # two records, one key
    assert (v.lost, v.ghost) == (0, 1)
    assert _run(first).ok                                 # not handed over: not held


def test_every_count_is_printed_beside_its_limit():
    _res, _k, first = _full()
    v = _run(first, live_keys=[])
    v.extra = {"of_the_configs_own": 3}
    lines = v.lines()
    for name in validate.LIMITED + ("of_the_configs_own",):
        assert any(ln.startswith(f"validate: {name} = ") and ln.endswith("(limit 0)")
                   for ln in lines), name
    assert v.failed == 8 + 3 and set(v.compared()) \
        == set(validate.LIMITED) | {"of_the_configs_own"}
    assert any("may wait" in ln and "(no limit)" in ln for ln in lines)


# ---- ISSUE 52: a round whose gangs entered as Jobs --------------------------

JOBS = {**CONFIG, "gang": {"size": 2, "min_member": 1}}


def _job_round(created=None, not_running=(), left_behind=(), bound=None):
    plan = generate.Generator(JOBS, seed=1, entry="jobs").plan(4, "j0")
    keys, owners = plan.keys(), plan.job_keys()
    if created is None:
        created = [(k, owners[g]) for k, g in zip(keys, plan.gang.tolist())]
    bound = keys if bound is None else bound
    ev = validate.RoundEvents(
        plan, [(1, list(bound), [NODES[i % 2] for i in range(len(bound))])],
        jobs=validate.JobEvents(created, list(not_running), list(left_behind)))
    return plan, validate.check(NODES, ALLOC, [ev])


def test_a_sound_round_of_jobs_passes_and_prints_its_three_counts():
    plan, v = _job_round()
    assert plan.names[0] == "j0-pg-000000-worker-0"
    assert v.ok and v.of_jobs == dict.fromkeys(validate.OF_JOBS, 0)
    assert list(v.compared())[len(validate.LIMITED):] == list(validate.OF_JOBS)
    for name in validate.OF_JOBS:
        assert f"validate: {name} = 0 (limit 0)" in v.lines()


def test_a_round_of_pods_has_none_of_the_jobs_counts():
    v = _check(_plan(), [])
    assert v.of_jobs == {} and not set(validate.OF_JOBS) & set(v.compared())


@pytest.mark.parametrize("change,count", [
    (lambda c: c[1:], 1),                                   # one missing
    (lambda c: c + [c[0]], 1),                              # one twice
    (lambda c: c + [("default/j0-pg-000000-worker-9", c[0][1])], 1),   # one more
    (lambda c: [("default/j0-pg-000000-workerx-0", c[0][1])] + c[1:], 1),  # renamed
    (lambda c: c[2:], 2),                                   # a Job's pods, all
    (lambda c: c + [("default/a-stranger-0", "default/another-job")], 0),
    (lambda c: [(k, "default/j0-pg-000001") for k, _o in c], 4)],  # wrong owner
    ids=["missing", "twice", "more", "renamed", "a-whole-job",
         "another-jobs-pod", "under-the-wrong-owner"])
def test_pods_not_as_planned_counts_per_job_the_pods_that_are_off(change, count):
    plan, _ = _job_round()
    created = [(k, o) for k, o in zip(
        plan.keys(), [plan.job_keys()[g] for g in plan.gang.tolist()])]
    _plan_, v = _job_round(created=change(created))
    assert v.of_jobs["pods_not_as_planned"] == count
    assert v.ok == (count == 0) and v.failed == count


def test_jobs_not_running_counts_a_job_whose_floor_of_pods_was_bound():
    plan, v = _job_round(not_running=["default/j0-pg-000001"])
    assert v.of_jobs["jobs_not_running"] == 1 and v.failed == 1
    # a Job of which nothing was bound is not held to Running: unbound holds it
    _p, v = _job_round(not_running=["default/j0-pg-000001"], bound=plan.keys()[:2])
    assert v.of_jobs["jobs_not_running"] == 0 and v.unbound == 2
    # min_available 1 of 2: one pod bound is the floor
    _p, v = _job_round(not_running=["default/j0-pg-000001"], bound=plan.keys()[:3])
    assert v.of_jobs["jobs_not_running"] == 1 and v.unbound == 1 and v.split == 0


def test_jobs_left_behind_counts_the_jobs_the_driver_names():
    _p, v = _job_round(left_behind=["default/j0-pg-000000", "default/old-job"])
    assert v.of_jobs["jobs_left_behind"] == 2 and not v.ok and v.failed == 2
