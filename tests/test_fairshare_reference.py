"""The fast cycle held to the plain fair-share reference
(``benchmark/reference/fairshare_ref.py``, float64, written from the
published formulas): what each queue deserves, who is refused under
contention, the float32 gate at ``allocated == deserved``, and the wave
solver against ``solver: sequential`` at a tenth of ``drf-5k``'s size.

The cell ``drf-5k.burst`` keeps demand under capacity (its loop counts a
pod not bound in its round as failed), so there the overuse gate computes
every cycle and never refuses; its refusals are held here, on the CPU.
Everything goes through ``ClusterStore`` -> ``Scheduler.run_once()``."""

import itertools
import json

import numpy as np
import pytest

import volcano_tpu.fastpath as fastpath
from benchmark.harness import generate
from benchmark.harness.cell import ROOT
from benchmark.reference import fairshare_ref as ref
from volcano_tpu.cache import ClusterStore
from volcano_tpu.cache.interface import FakeBinder
from volcano_tpu.scheduler import Scheduler

DRF_5K = json.loads((ROOT / "benchmark" / "configs" / "drf-5k.json").read_text())
CONF = DRF_5K["scheduler_conf"]
SEQUENTIAL = CONF + ("configurations:\n- name: allocate\n  arguments:\n"
                     "    solver: sequential\n")
GI = generate.GI
WEIGHTS = [1, 2, 4, 8]


def config(nodes, pods, pod_cpu=(1, 2, 4), pod_mem=(2, 4, 8), **node):
    """``drf-5k``'s own shapes (queues, weights, gang sizes, conf) at
    another scale, for the benchmark's own generator."""
    cfg = json.loads(json.dumps(DRF_5K))
    cfg["nodes"].update(count=nodes, zones=4, **node)
    cfg["pods"] = {"cpu_choices": list(pod_cpu),
                   "mem_gi_choices": list(pod_mem)}
    cfg["backlog_pods"] = pods
    return cfg


def build(cfg, plan, conf=CONF, weights=None):
    store = ClusterStore(binder=FakeBinder())
    for q in generate.to_queues(cfg):
        if weights is not None:
            q.weight = weights[generate.queue_names(cfg).index(q.name)]
        store.add_queue(q)
    for node in generate.to_nodes(cfg):
        store.add_node(node)
    for pg, pods in generate.to_objects(plan, itertools.count(1)):
        store.add_pod_group(pg)
        for pod in pods:
            store.add_pod(pod)
    return store, Scheduler(store, conf_str=conf)


def capacity(cfg):
    return generate.node_alloc(cfg)[:, :2].sum(axis=0).astype(np.float64)


def gang_requests(plan):
    """[G, 2] float64: each gang's whole request, cpu milli and bytes."""
    out = np.zeros((len(plan.gang_names), 2))
    np.add.at(out, plan.gang, np.stack([plan.cpu_milli, plan.mem_bytes], 1))
    return out


def ref_gangs(cfg, plan):
    names = generate.queue_names(cfg)
    return [ref.Gang(name, names.index(plan.gang_queue[g]), tuple(req), g)
            for g, (name, req) in enumerate(zip(plan.gang_names,
                                                gang_requests(plan)))]


def bound_gangs(store, plan):
    """Names of the gangs bound whole; a gang bound in part fails here
    (the gang guarantee)."""
    binds = store.binder.binds
    got = np.zeros(len(plan.gang_names), np.int64)
    for key, g in zip(plan.keys(), plan.gang):
        got[g] += key in binds
    assert np.all((got == 0) | (got == plan.gang_min_member)), "a split gang"
    return {plan.gang_names[g] for g in np.flatnonzero(got)}


def settle(store, sched, limit=8):
    """Cycles until one binds nothing more."""
    seen = -1
    for _ in range(limit):
        sched.run_once()
        store.flush_binds()
        if len(store.binder.binds) == seen:
            return
        seen = len(store.binder.binds)
    raise AssertionError(f"still binding after {limit} cycles")


@pytest.fixture
def cycles(monkeypatch):
    """Every ``FastCycle`` whose ``_proportion()`` ran, in order."""
    seen = []
    orig = fastpath.FastCycle._proportion

    def spy(self):
        out = orig(self)
        seen.append(self)
        return out

    monkeypatch.setattr(fastpath.FastCycle, "_proportion", spy)
    return seen


# ---- the reference itself, on cases worked by hand ---------------------------


def test_the_reference_on_cases_worked_by_hand():
    # 90 cpu / 900 bytes over weights 1:2; the second asks less than its 60
    # and 600 in both, is clipped, and the first gets what that gave back
    got = ref.deserved([90, 900], [1, 2], [[80, 800], [30, 300]])
    assert got.tolist() == [[60, 600], [30, 300]]
    # below its share in one dimension only: not clipped (Resource.Less)
    got = ref.deserved([90, 900], [1, 2], [[80, 800], [30, 700]])
    assert got.tolist() == [[30, 300], [60, 600]]
    assert ref.share([30, 100], [60, 400]) == 0.5
    assert ref.share([0, 0], [0, 0]) == 0.0 and ref.share([1, 0], [0, 5]) == 1.0
    assert ref.dominant_share([8000, GI], [64000, 64 * GI]) == 0.125
    # a quantum is 10 milli-cpu and 10 MiB: within one is not over
    assert not ref.overused([1009, 0], [1000, 0])
    assert ref.overused([1010, 0], [1000, 0])
    assert ref.overused([0, 10 * 2**20], [0, 0])


# ---- deserved ---------------------------------------------------------------

DESERVED_CASES = {
    # demand / capacity in cpu; every queue's request is a quarter of it
    "undersubscribed": dict(nodes=16, pods=200),          # ~0.45: all met
    "mixed": dict(nodes=8, pods=320),                     # ~1.5: queue-3 met
    "oversubscribed": dict(nodes=8, pods=560),            # ~2.5: none met
    # memory asks 4.6x capacity, cpu 0.6 of it: no queue is below its
    # share in every dimension, so none is clipped
    "memory_binds": dict(nodes=8, pods=320, pod_cpu=(1,),
                         pod_mem=(16, 32, 64)),
}


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
@pytest.mark.parametrize("case", sorted(DESERVED_CASES))
def test_deserved_is_the_references(cycles, case, seed):
    cfg = config(**DESERVED_CASES[case])
    plan = generate.Generator(cfg, seed).plan(cfg["backlog_pods"], "d")
    store, sched = build(cfg, plan)
    sched.run_once()
    store.close()
    fc = cycles[0]
    names = generate.queue_names(cfg)
    requests = np.zeros((4, 2))
    np.add.at(requests, [names.index(q) for q in plan.gang_queue],
              gang_requests(plan))
    want = ref.deserved(capacity(cfg), WEIGHTS, requests)
    got = fc.q_deserved[[fc.queue_index[n] for n in names]][:, :2]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    met = int(np.all(want == requests, axis=1).sum())
    assert met == {"undersubscribed": 4, "mixed": 1, "oversubscribed": 0,
                   "memory_binds": 0}[case]
    # the span says the same
    span = next(s for s in store.flight.recent()[0].spans
                if s.name == "derive:proportion")
    assert span.args["queues"] == 4 and span.args["met"] == met
    np.testing.assert_allclose(span.args["deserved_cpu"], want[:, 0],
                               rtol=1e-6)


# ---- shares under contention -------------------------------------------------


def share_violations(cfg, plan, store, ref_weights=WEIGHTS):
    """The settled cluster against ``fairshare_ref.allocate``: every way in
    which a queue's bound cpu or memory lies further from the reference's
    than one largest gang, or a queue with pending gangs stands under its
    deserved share by more than one gang and what is left of the cluster
    (so that other queues hold what it deserves).

    The tolerance is one gang, not zero: job order inside one solve is
    fixed when the solve is encoded (PARITY.md deviation 3: all of the first
    queue's gangs, then the next queue's, each refused from the gang after
    the one that took its queue past its deserved share), while the
    reference re-picks the queue of least share after every gang.  Both
    stop a queue within one gang past its share; the queue that comes last
    in the fixed order gets what the others' last gangs left, so in
    principle it can stand one gang under the reference for each queue
    before it.  On these seeds it is within one."""
    names = generate.queue_names(cfg)
    reqs = gang_requests(plan)
    walk = ref.allocate(ref_gangs(cfg, plan), ref_weights, capacity(cfg))
    bound = bound_gangs(store, plan)
    got = np.zeros((4, 2))
    pending = np.zeros(4, bool)
    for g, name in enumerate(plan.gang_names):
        q = names.index(plan.gang_queue[g])
        if name in bound:
            got[q] += reqs[g]
        else:
            pending[q] = True
    one_gang = reqs.max(axis=0)
    left = capacity(cfg) - got.sum(axis=0)
    out = []
    for q, name in enumerate(names):
        if np.any(np.abs(got[q] - walk.allocated[q]) > one_gang):
            out.append(f"{name}: bound {got[q]} against the reference's "
                       f"{walk.allocated[q]}, more than {one_gang} apart")
        if pending[q] and ref.less_equal(got[q] + one_gang + left,
                                         walk.deserved[q]):
            out.append(f"{name}: has pending gangs and holds {got[q]} of "
                       f"{walk.deserved[q]} deserved, {left} left")
    return out


OVERSUBSCRIBED = {
    # seed: nodes, pods -> demand 2-3x the cpu capacity
    11: (8, 560),
    2**31 + 12: (12, 900),
    # three waves of 2,048 tasks: the gate across waves and inside one
    13: (90, 6200),
}


@pytest.mark.parametrize("seed", sorted(OVERSUBSCRIBED))
def test_contended_shares_follow_the_weights(seed):
    nodes, pods = OVERSUBSCRIBED[seed]
    cfg = config(nodes, pods)
    plan = generate.Generator(cfg, seed).plan(pods, "c")
    demand = plan.cpu_milli.sum() / capacity(cfg)[0]
    assert 2.0 <= demand <= 3.0
    store, sched = build(cfg, plan)
    settle(store, sched)
    assert share_violations(cfg, plan, store) == []
    store.close()


def test_equal_weights_in_the_programs_place_are_seen():
    """The control: the same comparison with the program's queues all at
    weight 1 (the reference keeps 1:2:4:8) must find the difference."""
    nodes, pods = OVERSUBSCRIBED[11]
    cfg = config(nodes, pods)
    plan = generate.Generator(cfg, 11).plan(pods, "c")
    store, sched = build(cfg, plan, weights=[1, 1, 1, 1])
    settle(store, sched)
    assert share_violations(cfg, plan, store) != []
    store.close()


# ---- the gate at allocated == deserved ---------------------------------------


def edge_plan(a_gangs, b_gangs):
    """Two queues of weight 1.  ``default``: gangs of 4 x (64 cpu, 16Gi);
    ``queue-1``: gangs of 4 x (8 cpu, 512Gi), interleaved by creation."""
    kinds = sorted([(g / a_gangs, "default", 64, 16) for g in range(a_gangs)]
                   + [(g / b_gangs, "queue-1", 8, 512) for g in range(b_gangs)])
    names, cpu, mem, gang = [], [], [], []
    for g, (_at, _queue, c, m) in enumerate(kinds):
        for k in range(4):
            names.append(f"e-pg-{g:04d}-{k}")
            cpu.append(c * 1000)
            mem.append(m * GI)
            gang.append(g)
    return generate.Plan(
        "e", names, np.array(cpu, np.int64), np.array(mem, np.int64),
        np.array(gang, np.int64), [f"e-pg-{g:04d}" for g in range(len(kinds))],
        np.full(len(kinds), 4, np.int64), [k[1] for k in kinds],
        [k[2] for k in kinds], [k[3] for k in kinds])


@pytest.mark.parametrize("node_cpu, a_bound", [(1056, 166), (1055, 165)])
def test_the_gate_at_exact_equality(cycles, node_cpu, a_bound):
    """80 nodes of 1,056 cpu / 4,096Gi, two queues of weight 1, neither
    clipped (``default`` asks more cpu than its half, ``queue-1`` more
    memory): each deserves 42,240 cpu = 42,240,000 milli (past 2**25, where
    float32 steps by 4) and 163,840Gi.  ``default``'s 165th gang brings it
    to its deserved cpu *exactly*, ``queue-1``'s 80th to its deserved memory
    exactly: neither is overused yet (Resource.LessEqual), so each binds
    one gang more, 166 and 81, and is refused from there.  The float32
    ``less_equal`` of the device's gate (``ops/wave.py``, on the in-wave
    sums) and the host's ``_overused_fn`` have to agree with the float64
    reference.  (A node holds 16 of ``default``'s pods and still 4 of
    ``queue-1``'s, so no gang is short of a node.)

    The control proves the test can fail: with 1,055 cpu a node
    ``default`` deserves 42,200 cpu: 40 under 165 gangs' 42,240, less than
    one pod's 64, and over 164 gangs' 41,984: the 165th gang binds, the
    166th is refused."""
    cfg = config(80, 1280, cpu=node_cpu, memory_gi=4096)
    cfg["queues"] = {"count": 2, "weights": [1, 1]}
    plan = edge_plan(200, 120)
    store, sched = build(cfg, plan)
    settle(store, sched)
    bound = bound_gangs(store, plan)
    per_queue = {q: sum(1 for g, name in enumerate(plan.gang_names)
                        if name in bound and plan.gang_queue[g] == q)
                 for q in ("default", "queue-1")}
    walk = ref.allocate(ref_gangs(cfg, plan), [1, 1], capacity(cfg))
    assert bound == walk.admitted
    assert per_queue == {"default": a_bound, "queue-1": 81}
    assert walk.deserved[0, 0] == 40 * node_cpu * 1000 > 2**25
    fc = cycles[0]
    assert fc.q_deserved[fc.queue_index["default"], 0] == walk.deserved[0, 0]
    store.close()


# ---- the wave solver against the sequential one, a tenth of drf-5k -----------


def test_tenth_size_wave_binds_what_sequential_binds():
    """500 nodes x 5,000 pods from the cell's own generator: demand under
    capacity, so both solvers bind every pod, and the wave solver's overuse
    gate refused no job (``job_overskip`` all false)."""
    cfg = config(500, 5000)
    cfg["nodes"]["zones"] = DRF_5K["nodes"]["zones"]
    seed = 2**31 + 27
    plan = generate.Generator(cfg, seed).plan(5000, "t")
    bound = {}
    for name, conf in (("wave", CONF), ("sequential", SEQUENTIAL)):
        store, sched = build(cfg, plan, conf)
        sched.run_once()
        store.flush_binds()
        bound[name] = set(store.binder.binds)
        if name == "wave":
            solve = store.flight.recent()[-1].solve
            assert solve["queues"] == 4 and solve["fetches"] == 1
            assert solve["jobs"] == len(plan.gang_names)
            assert solve["gang_size_max"] == 16
            assert solve["overuse_gated_jobs"] == 0
        store.close()
    assert bound["wave"] == bound["sequential"] == set(plan.keys())
