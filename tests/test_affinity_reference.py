"""The fast cycle held to the plain reference of inter-pod affinity,
anti-affinity and the soft topology spread
(``benchmark/reference/affinity_ref.py``): a seeded mixed backlog binds whole
with no violation, one pod a cycle lands node for node where the reference
says, the soft spread is settled (one pod a cycle it moves pods, a gang
solved in one wave it does not: ``PARITY.md``), a control with the affinity
inputs emptied is caught, and twenty burst rounds of a toy of
``affinity-10k`` lower no program after the warm-up round.

The cell ``affinity-10k.burst`` cannot put ``violations`` into its
``correct`` yet (``harness/validate.py`` reads no gang kind), so the two
affinity guarantees of its configuration are held here, on the CPU.
Everything goes through ``ClusterStore`` -> ``Scheduler.run_once()``."""

import itertools
import json

import numpy as np
import pytest

import volcano_tpu.fastpath as fastpath
from benchmark.harness import generate, loop
from benchmark.harness.cell import ROOT
from benchmark.reference import affinity_ref as ref
from volcano_tpu.api import GROUP_NAME_ANNOTATION, AffinityTerm, Pod, PodGroup
from volcano_tpu.cache import ClusterStore
from volcano_tpu.cache.interface import FakeBinder
from volcano_tpu.scheduler import Scheduler

AFFINITY_10K = json.loads(
    (ROOT / "benchmark" / "configs" / "affinity-10k.json").read_text())
CONF = AFFINITY_10K["scheduler_conf"]
GI = generate.GI
ZONES = 8


def config(nodes, pods, mix=(0.3, 0.3, 0.3)):
    """``affinity-10k``'s own shapes (gangs of 8, node and pod sizes,
    conf) at another scale and mix, for the benchmark's own generator."""
    cfg = json.loads(json.dumps(AFFINITY_10K))
    cfg["nodes"].update(count=nodes, zones=ZONES)
    cfg["backlog_pods"] = pods
    cfg["affinity_mix"] = dict(zip(("affinity", "anti_affinity", "spread"), mix))
    return cfg


def node_zone(cfg):
    n = cfg["nodes"]
    return np.arange(n["count"]) % n["zones"]


def run_backlog(cfg, seed):
    """One backlog through the store's event API and ``run_once()``;
    returns ``(plan, pod_node)``."""
    plan = generate.Generator(cfg, seed).plan(cfg["backlog_pods"], "x")
    store = ClusterStore(binder=FakeBinder())
    for node in generate.to_nodes(cfg):
        store.add_node(node)
    for pg, pods in generate.to_objects(plan, itertools.count(1)):
        store.add_pod_group(pg)
        for pod in pods:
            store.add_pod(pod)
    sched = Scheduler(store, conf_str=CONF)
    for _ in range(4):
        sched.run_once()
        store.flush_binds()
        if len(store.binder.binds) == plan.n_pods:
            break
    index = {name: i for i, name in enumerate(generate.node_names(cfg))}
    pod_node = np.array([index.get(store.binder.binds.get(k), -1)
                         for k in plan.keys()], np.int64)
    off_fast = [r.path for r in store.flight.recent() if r.path != "fast"]
    store.close()
    assert not off_fast
    return plan, pod_node


# ---- the reference itself, on cases worked by hand ---------------------------


def test_violations_on_cases_worked_by_hand():
    zone = np.array([0, 1, 0, 1])                 # four nodes, two zones
    kinds = ["affinity", "anti_affinity", "spread", ""]
    gang = np.repeat(np.arange(4), 2)
    clean = ref.violations(kinds, gang, [0, 2, 1, 3, 0, 1, 0, 0], zone)
    assert (clean["affinity_pods"], clean["affinity_outside"]) == (2, 0)
    assert (clean["anti_pods"], clean["anti_shared"]) == (2, 0)
    assert clean["spread_gangs"] == 1 and clean["spread_zones"].tolist() == [2]
    # the affinity gang over two zones, the anti gang on one node, the
    # spread gang in one zone, an unbound pod counted by nobody
    bad = ref.violations(kinds, gang, [0, 1, 3, 3, 2, 0, -1, 0], zone)
    assert (bad["affinity_pods"], bad["affinity_outside"]) == (2, 1)
    assert (bad["anti_pods"], bad["anti_shared"]) == (2, 2)
    assert bad["spread_zones"].tolist() == [1]
    # three of an affinity gang: the zone that holds most is its home
    three = ref.violations(["affinity"], [0, 0, 0], [1, 3, 0], zone)
    assert three["affinity_outside"] == 1


def test_allowed_and_spread_on_cases_worked_by_hand():
    zone = np.array([0, 1, 0, 1, -1])             # node 4 has no zone label
    web = {"app": "web"}
    on_zone, on_host = ref.term(web, "zone"), ref.term(web, ref.HOSTNAME)
    standing = [ref.Resident(1, web, job="j")]
    # affinity: the resident's zone; a node without the label never
    assert ref.allowed(zone, standing, {}, affinity=[on_zone]).tolist() == [
        False, True, False, True, False]
    # the first-pod rule: nobody matches, the pod matches itself
    assert ref.allowed(zone, [], web, affinity=[on_zone]).all()
    assert not ref.allowed(zone, [], {"app": "db"}, affinity=[on_zone]).any()
    # anti-affinity, the pod's term against residents ...
    assert ref.allowed(zone, standing, {}, anti_affinity=[on_host]).tolist() == [
        True, False, True, True, True]
    assert ref.allowed(zone, standing, {}, anti_affinity=[on_zone]).tolist() == [
        True, False, True, False, True]
    # ... and a resident's term against the pod
    guarded = [ref.Resident(2, {"app": "db"}, anti_affinity=(on_host,))]
    assert ref.allowed(zone, guarded, web).tolist() == [
        True, True, False, True, True]
    assert ref.allowed(zone, guarded, {"app": "db"}).all()
    # the soft spread: -weight per mate of the pod's own job in the zone
    mates = standing + [ref.Resident(3, web, job="j"), ref.Resident(0, web, job="k")]
    assert ref.spread_scores(zone, mates, "j", [("zone", 10)]).tolist() == [
        0.0, -20.0, 0.0, -20.0, 0.0]
    alloc = np.tile(np.array([64000, 256 * GI, 256], np.int64), (5, 1))
    used = np.zeros_like(alloc)
    req = (1000, 2 * GI)
    assert ref.choose(alloc, used, req) == 0
    assert ref.choose(alloc, used, req, may=[False, False, True, True, True]) == 2
    assert ref.choose(alloc, used, req,
                      extra=ref.spread_scores(zone, [ref.Resident(0, web, job="j")],
                                              "j", [("zone", 10)])) == 1
    assert ref.choose(alloc, used, req, may=np.zeros(5, bool)) == -1


# ---- a mixed backlog: the two guarantees of affinity-10k ---------------------


@pytest.mark.parametrize("seed", [2**31 + 31, 7, 90210])
def test_a_mixed_backlog_binds_whole_with_no_violation(seed):
    """64 nodes in 8 zones, 320 pods in gangs of 8, three tenths of the
    gangs of each kind: every pod bound, every affinity gang in one zone,
    no two pods of an anti-affinity gang on one node."""
    cfg = config(64, 320)
    plan, pod_node = run_backlog(cfg, seed)
    assert (pod_node >= 0).all()
    v = ref.violations(plan.gang_kind, plan.gang, pod_node, node_zone(cfg))
    assert v["affinity_pods"] >= 16 and v["anti_pods"] >= 16 and v["spread_gangs"] >= 2
    assert (v["affinity_outside"], v["anti_shared"]) == (0, 0)
    # A gang of 8 solved in one wave is scored against the state at the
    # wave's start, where no mate is placed yet: the soft spread moves
    # nothing and the gang packs into one zone (PARITY.md, inter-pod
    # terms).  test_one_pod_a_cycle_... shows the term itself is right.
    assert v["spread_zones"].max() <= 2


def test_the_reference_catches_a_cycle_without_its_affinity_inputs(monkeypatch):
    """The control: the same backlog with the fast cycle's affinity inputs
    emptied (every pending row reads as carrying no term) packs anti-affine
    gangs onto one node, and ``violations`` says so."""

    class NoTerms:
        def __init__(self, column):
            self.has_val = column.has_val

        def gather(self, rows):
            none = np.zeros(0, np.int64)
            return (none, none, np.zeros(0, np.float32)) if self.has_val \
                else (none, none)

    orig = fastpath.FastCycle._affinity_and_profiles

    def stripped(self, *a, **kw):
        m = self.m
        kept = m.c_ip_aff, m.c_ip_anti, m.c_ip_soft
        m.c_ip_aff, m.c_ip_anti, m.c_ip_soft = map(NoTerms, kept)
        try:
            return orig(self, *a, **kw)
        finally:
            m.c_ip_aff, m.c_ip_anti, m.c_ip_soft = kept

    monkeypatch.setattr(fastpath.FastCycle, "_affinity_and_profiles", stripped)
    cfg = config(64, 320)
    plan, pod_node = run_backlog(cfg, 2**31 + 31)
    assert (pod_node >= 0).all()
    v = ref.violations(plan.gang_kind, plan.gang, pod_node, node_zone(cfg))
    assert v["anti_shared"] > 0


# ---- one pod a cycle: node for node ------------------------------------------


class Cluster:
    """64 nodes of ``affinity-10k``'s size in 8 zones, one pending pod a
    cycle; the ledger of what stands where is kept here, from the binder's
    record alone."""

    def __init__(self):
        self.cfg = config(64, 8)
        self.store = ClusterStore(binder=FakeBinder())
        for node in generate.to_nodes(self.cfg):
            self.store.add_node(node)
        self.sched = Scheduler(self.store, conf_str=CONF)
        self.zone = node_zone(self.cfg)
        self.index = {n: i for i, n in enumerate(generate.node_names(self.cfg))}
        self.alloc = generate.node_alloc(self.cfg)
        self.used = np.zeros_like(self.alloc)
        self.residents = []
        self.stamps = itertools.count(1)
        self.groups = set()

    def place(self, name, group, cpu, mem_gi, labels, **terms):
        """Submit one pod of ``group`` (created with ``min_member`` 1 when
        new), run one cycle, return the node index it was bound to."""
        if group not in self.groups:
            self.groups.add(group)
            self.store.add_pod_group(PodGroup(
                name=group, min_member=1, queue="default",
                creation_timestamp=float(next(self.stamps))))
        self.store.add_pod(Pod(
            name=name, uid=f"t-{name}", labels=dict(labels),
            annotations={GROUP_NAME_ANNOTATION: group},
            containers=[{"cpu": str(cpu), "memory": f"{mem_gi}Gi"}],
            creation_timestamp=float(next(self.stamps)), **terms))
        self.sched.run_once()
        self.store.flush_binds()
        host = self.store.binder.binds.get(f"default/{name}")
        assert host is not None, f"{name} not bound"
        node = self.index[host]
        self.used[node] += (cpu * 1000, mem_gi * GI, 1)
        anti = tuple(ref.term(t.match_labels, t.topology_key)
                     for t in terms.get("anti_affinity", ()))
        self.residents.append(ref.Resident(node, dict(labels),
                                           job=f"default/{group}",
                                           anti_affinity=anti))
        return node

    def want(self, cpu, mem_gi, labels, group="", affinity=(), anti=(),
             spread=(), constrained=True):
        req = (cpu * 1000, mem_gi * GI)
        if not constrained:
            return ref.choose(self.alloc, self.used, req)
        may = ref.allowed(self.zone, self.residents, labels,
                          [ref.term(t.match_labels, t.topology_key) for t in affinity],
                          [ref.term(t.match_labels, t.topology_key) for t in anti])
        extra = ref.spread_scores(self.zone, self.residents,
                                  f"default/{group}", spread)
        return ref.choose(self.alloc, self.used, req, may, extra)


@pytest.fixture
def cluster():
    c = Cluster()
    yield c
    c.store.close()


def test_one_pod_a_cycle_to_a_residents_zone(cluster):
    web = {"app": "web"}
    at = cluster.place("r0", "res", 1, 2, web, node_selector={"zone": "zone-3"})
    assert at == 3
    t = AffinityTerm(match_labels=web, topology_key="zone")
    free = cluster.want(1, 2, {}, constrained=False)
    want = cluster.want(1, 2, {}, affinity=[t])
    assert (free, want) == (0, 11)          # the constraint moves the choice
    assert cluster.place("p0", "pend", 1, 2, {}, affinity=[t]) == want


def test_one_pod_a_cycle_away_from_a_residents_host(cluster):
    db = {"app": "db"}
    t = AffinityTerm(match_labels=db, topology_key="kubernetes.io/hostname")
    assert cluster.place("r0", "res", 1, 2, db, node_selector={"zone": "zone-0"}) == 0
    free = cluster.want(1, 8, db, constrained=False)
    want = cluster.want(1, 8, db, anti=[t])
    assert (free, want) == (0, 1)           # node 0 scores best and is barred
    assert cluster.place("p0", "pend", 1, 8, db, anti_affinity=[t]) == want


def test_one_pod_a_cycle_a_gangs_first_pod_and_its_second(cluster):
    ring = {"app": "ring"}
    t = AffinityTerm(match_labels=ring, topology_key="zone")
    assert cluster.place("f0", "fill", 1, 2, {},
                         node_selector={"zone": "zone-5"}) == 5
    # nobody matches the term and the pod matches itself: every node open,
    # and the filler's node balances best
    want = cluster.want(1, 8, ring, affinity=[t])
    assert want == cluster.want(1, 8, ring, constrained=False) == 5
    assert cluster.place("g0", "gang", 1, 8, ring, affinity=[t]) == want
    # the second is held to the first's zone, where an empty node wins
    want = cluster.want(1, 4, ring, affinity=[t])
    assert (cluster.want(1, 4, ring, constrained=False), want) == (0, 13)
    assert cluster.place("g1", "gang", 1, 4, ring, affinity=[t]) == want


def test_one_pod_a_cycle_a_spread_pod_among_its_mates(cluster):
    """The soft spread as the reference reads it: each pod of the job, one a
    cycle, goes to the best node of a zone that holds fewest of its mates.
    Four pods cover four zones; without the term all four pack."""
    labels = {"app": "spread"}
    spread = [("zone", 10)]
    nodes = []
    for k in range(4):
        want = cluster.want(1, 4, labels, group="sp", spread=spread)
        got = cluster.place(f"s{k}", "sp", 1, 4, labels, topology_spread=spread)
        assert got == want, (k, got, want)
        nodes.append(got)
    assert len({n % ZONES for n in nodes}) == 4
    assert cluster.want(1, 4, labels, constrained=False) in nodes


@pytest.mark.xfail(strict=True, reason="PARITY.md, inter-pod terms: the "
                   "program checks the pending pod's own terms against the "
                   "residents, not a resident's anti-affinity term against "
                   "a pending pod that carries none")
def test_one_pod_a_cycle_a_residents_term_against_the_pod(cluster):
    db = {"app": "db"}
    t = AffinityTerm(match_labels=db, topology_key="kubernetes.io/hostname")
    assert cluster.place("r0", "res", 1, 2, db, anti_affinity=[t],
                         node_selector={"zone": "zone-0"}) == 0
    want = cluster.want(1, 8, db)
    assert want == 1 and cluster.want(1, 8, db, constrained=False) == 0
    assert cluster.place("p0", "pend", 1, 8, db) == want


# ---- the shapes a round's terms give the programs do not move ---------------


def test_twenty_burst_rounds_of_a_toy_lower_nothing_after_warm_up():
    """200 nodes in 16 zones, 2,000 pods in gangs of 8, the 5 / 5 / 10 mix,
    the benchmark's own driver: after one warm-up round, twenty rounds
    whose term counts are fresh binomial draws lower ``_static_planes``,
    ``_coarse_shortlist`` and ``_solve_wave`` 0 times (a bucket taken anew
    from each draw lowered 6 programs in 4 rounds), each round one cycle on
    the fast path, every pod bound, no violation."""
    from benchmark import run as bench_run

    cfg = json.loads(json.dumps(AFFINITY_10K))
    cfg["nodes"]["count"] = 200
    cfg["backlog_pods"] = 2000
    compiles = bench_run.Compiles()
    gen = generate.Generator(cfg, 2**31 + 7)
    driver = loop.Driver(cfg, max_cycles=4, read_lanes=True)
    try:
        driver.round(gen.plan(2000, "warm"), 2000)
        warm = len(compiles.names)
        assert {"jit(_solve_wave)", "jit(_coarse_shortlist)",
                "jit(_static_planes)"} <= set(compiles.names[:warm])
        drawn = set()
        for r in range(20):
            plan = gen.plan(2000, f"w{r:02d}")
            rec = driver.round(plan, 2000)
            assert rec.cycles == 1 and not rec.lanes.get("_off_fast_path")
            drawn.add(sum(k != "" for k in plan.gang_kind))
            solve = driver.store.flight.recent()[-1].solve
            assert solve["aff_terms"] == sum(k != "" for k in plan.gang_kind)
            assert solve["aff_terms_padded"] == 128 and solve["aff_chunks"] == 1
            index = {n: i for i, n in enumerate(generate.node_names(cfg))}
            hosts = {k: h for _t, keys, hs in rec.arrivals
                     for k, h in zip(keys, hs)}
            pod_node = np.array([index[hosts[k]] for k in plan.keys()])
            v = ref.violations(plan.gang_kind, plan.gang, pod_node,
                               np.arange(200) % 16)
            assert (v["affinity_outside"], v["anti_shared"]) == (0, 0)
        assert len(drawn) > 5               # the draws did differ
        assert compiles.names[warm:] == []
    finally:
        driver.close()
