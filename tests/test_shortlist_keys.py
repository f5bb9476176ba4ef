"""The coarse shortlist ranks one row per distinct scoring key
(``ops/wave.shortlist_keys``) and hands a key's ranking to each of its
profile rows.  Held here, on the CPU: the keyed passes equal a ranking of
every profile row element for element (the full pass, the warm pass, one
device and a mesh of four), a change of the key set sends a warm pass back
to a full one, the two counts reach the cycle record, and a fast cycle
binds what it binds with every row a key of its own."""

import dataclasses
import itertools
import json

import jax
import numpy as np
import pytest

from benchmark.harness import generate
from benchmark.harness.cell import ROOT
from volcano_tpu.api import GROUP_NAME_ANNOTATION, PodGroup
from volcano_tpu.cache import ClusterStore
from volcano_tpu.cache.interface import FakeBinder
from volcano_tpu.ops import devincr as dvm
from volcano_tpu.ops import wave
from volcano_tpu.ops.allocate import SolveNodes
from volcano_tpu.ops.nodeclass import NodeClasses
from volcano_tpu.ops.scoring import ScoreWeights
from volcano_tpu.ops.wave import AffinityArgs, SolveProfiles, SparseProfiles
from volcano_tpu.scheduler import Scheduler

N, U, E, R = 256, 64, 8, 3
SL_K, BLOCKS = 24, 8
SHAPES, LIVE = 5, 40        # distinct pod shapes; rows before the padding
FEATURES = (True, True, True, True, False, False, False)

needs_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                             reason="needs 4 (virtual) devices")


class Case:
    """A seeded solve at a toy shape: 256 nodes of 8 classes, a profile
    table of 64 rows: 40 live rows drawn from 5 pod shapes (so rows
    repeat), rows 5 .. 20 with a term entry of their own (rows that
    differ only in the four ``t_*`` tables), the rest all-zero padding."""

    def __init__(self, seed, cnt0_any, extra=False):
        rng = np.random.default_rng(seed)
        f32, u32, i32 = np.float32, np.uint32, np.int32
        alloc = np.tile(f32([64, 256, 110]), (N, 1))
        used = rng.integers(0, 48, (N, R)).astype(f32)
        cls_of = rng.integers(0, 8, N).astype(i32)
        cls_labels = rng.integers(0, 2**8, (8, 1)).astype(u32)
        cls_taints = (rng.integers(0, 4, (8, 1)) == 0).astype(u32)
        self.nodes = SolveNodes(
            idle=alloc - used, allocatable=alloc,
            releasing=(rng.integers(0, 8, (N, R)) == 0).astype(f32) * 4,
            pipelined=np.zeros((N, R), f32),
            ntasks=rng.integers(0, 100, N).astype(i32),
            max_tasks=np.full(N, 110, i32),
            ports=(rng.integers(0, 4, (N, 1)) == 0).astype(u32),
            ready=rng.integers(0, 16, N) > 0,
            label_bits=cls_labels[cls_of], taint_bits=cls_taints[cls_of])
        self.cls = NodeClasses(
            class_id=cls_of, label_bits=cls_labels, taint_bits=cls_taints,
            ready=np.ones(8, bool))
        shape_of = np.concatenate(
            [rng.integers(0, SHAPES, LIVE), np.full(U - LIVE, SHAPES)])

        def rows(table):        # a row per shape, and the padding's zeros
            table = np.concatenate([table, np.zeros_like(table[:1])])
            return table[shape_of]

        req = rng.integers(1, 9, (SHAPES, R)).astype(f32)
        self.nine = (
            rows(req), rows(req),
            rows((rng.integers(0, 3, (SHAPES, 1)) == 0).astype(u32)),
            rows(rng.integers(0, 2, (SHAPES, 1)).astype(u32)),
            rows(rng.integers(0, 4, (SHAPES, 2, 1)).astype(u32)),
            rows(rng.integers(0, 3, SHAPES).astype(i32)),
            rows(rng.integers(0, 2, (SHAPES, 1)).astype(u32)),
            rows(rng.integers(0, 8, (SHAPES, 2, 1)).astype(u32)),
            rows(rng.integers(0, 10, (SHAPES, 2)).astype(f32)),
        )
        term_rows = np.arange(5, 21)
        t = [np.zeros((U, E), bool) for _ in range(3)]
        soft = np.zeros((U, E), f32)
        for u in term_rows:
            e = int(rng.integers(0, E))
            t[int(rng.integers(0, 3))][u, e] = True
            soft[u, e] = f32(rng.integers(0, 3))
        self.tables = (*t, soft)
        cnt0 = np.zeros((E, 4 + N), i32)
        if cnt0_any:
            cnt0[rng.integers(0, E, 200), rng.integers(0, 4 + N, 200)] = 1
        self.aff = AffinityArgs(
            node_dom=np.stack([np.arange(N) % 4, 4 + np.arange(N)], 1).astype(i32),
            term_key=rng.integers(0, 2, E).astype(i32), cnt0=cnt0,
            t_req_aff=np.zeros((1, E), bool), t_req_anti=np.zeros((1, E), bool),
            t_matches=np.zeros((1, E), bool), t_soft=np.zeros((1, E), f32))
        self.cnt0_any = cnt0_any
        self.extra = (rng.integers(0, 8, (SHAPES + 1, N)) > 0)[shape_of] \
            if extra else None
        self.weights = ScoreWeights(1.0, np.ones(R, f32), 1.0, 0.0, 1.0, 1.0)

    @property
    def prof(self):
        return SolveProfiles(*self.nine, *self.tables)

    @property
    def features(self):
        return FEATURES[:5] + (self.extra is not None, False)

    def keys(self, identity=False):
        """``shortlist_keys`` of the table, or every row a key of its own."""
        if identity:
            return np.arange(U, dtype=np.int32), np.arange(U, dtype=np.int32), U, "id"
        sp, _dense = wave._sparse_profiles(self.prof)
        assert isinstance(sp, SparseProfiles)
        return wave.shortlist_keys(sp, self.extra, None, own_terms=self.cnt0_any)

    def statics(self):
        with jax.default_matmul_precision("float32"):
            return wave._static_planes(
                self.nodes, self.prof, self.cls, self.weights.node_affinity_weight,
                chunk=16, has_taints=True, cls_identity=False)

    def coarse(self, key_rows, key_of, with_cand, stat=None, chunk=8, nodes=None,
               mesh_shards=1):
        ones = np.ones((1, 1), bool) if self.extra is None else self.extra
        with jax.default_matmul_precision("float32"):
            return wave._coarse_shortlist(
                nodes or self.nodes, self.prof, ones, np.zeros((1, 1), np.float32),
                self.cls, self.aff, self.weights, np.float32(1e-6), 2,
                key_rows, key_of, sl_k=SL_K, chunk=chunk, features=self.features,
                cnt0_any=self.cnt0_any, cls_identity=False, mesh_shards=mesh_shards,
                n_blocks=BLOCKS, with_cand=with_cand, static_ext=stat is not None,
                stat_ok=None if stat is None else stat[0],
                stat_score=None if stat is None else stat[1])

    def shortlist(self, dv, keys, stat=None):
        """``DeviceIncremental.shortlist`` as ``solve_wave`` calls it."""
        key_rows, key_of, _n, tok = keys
        with jax.default_matmul_precision("float32"):
            return np.asarray(dv.shortlist(
                self.nodes, self.prof, np.ones((1, 1), bool),
                np.zeros((1, 1), np.float32), self.cls, self.aff, self.weights,
                np.float32(1e-6), 2, key_rows, key_of, tok, sl_k=SL_K, chunk=8,
                features=self.features, cnt0_any=self.cnt0_any,
                cls_identity=False, mesh_shards=1, stat=stat))


def _key_count(cnt0_any):
    """The shapes in use and the padding row; with resident counts every
    row that has a term entry besides."""
    return (SHAPES + 1) + (16 if cnt0_any else 0)


# ---- the keys ----------------------------------------------------------------


@pytest.mark.parametrize("cnt0_any", [False, True])
def test_rows_share_a_key_only_where_every_value_read_is_equal(cnt0_any):
    case = Case(3, cnt0_any)
    key_rows, key_of, n_keys, _tok = case.keys()
    assert n_keys <= _key_count(cnt0_any) and len(key_of) == U
    assert len(key_rows) == (64 if cnt0_any else 16)    # the count's bucket
    own = set(range(5, 21)) if cnt0_any else set()
    for u in range(U):
        k = key_rows[key_of[u]]
        assert all(np.array_equal(col[u], col[k]) for col in case.nine)
        if u in own:                    # a row with a term entry: its own key
            assert k == u and (key_of == key_of[u]).sum() == 1
        elif cnt0_any:                  # and nobody else's
            assert k not in own
    assert (key_rows[n_keys:] == key_rows[0]).all()


def test_a_table_whose_rows_all_differ_is_ranked_row_by_row():
    case = Case(4, False)
    nine = list(case.nine)
    nine[5] = np.arange(U, dtype=np.int32)              # aff_terms: all differ
    sp = SparseProfiles(*nine, wave._sparse_profiles(case.prof)[0].terms)
    key_rows, key_of, n_keys, _tok = wave.shortlist_keys(sp, None, None, False)
    assert n_keys == U == len(key_rows)
    assert np.array_equal(key_rows[key_of], np.arange(U))


def test_the_custom_plugins_rows_are_part_of_a_key():
    case = Case(5, False, extra=True)
    key_rows, key_of, n_keys, _tok = case.keys()
    assert n_keys == len(np.unique(key_of)) <= SHAPES + 1
    sl = np.asarray(case.coarse(key_rows, key_of, False))
    full = np.asarray(case.coarse(*case.keys(identity=True)[:2], False))
    assert np.array_equal(sl, full)
    other = Case(5, False, extra=True)
    other.extra = other.extra.copy()
    other.extra[2] = ~other.extra[2]                    # one row's verdicts move
    assert other.keys()[2] == n_keys + 1
    assert other.keys()[3] != case.keys()[3]


# ---- the keyed pass against a ranking of every row ------------------------------


@pytest.mark.parametrize("static_ext", [False, True])
@pytest.mark.parametrize("with_cand", [False, True])
@pytest.mark.parametrize("cnt0_any", [False, True])
@pytest.mark.parametrize("seed", [11, 2**31 + 12])
def test_the_keyed_shortlist_is_the_full_one(seed, cnt0_any, with_cand, static_ext):
    """``sl_keys[key_of]`` against a pass in which every row is a key:
    the ``[U, sl_k]`` arrays equal, and a key's candidates those of each
    of its rows."""
    case = Case(seed, cnt0_any)
    stat = case.statics() if static_ext else None
    key_rows, key_of, n_keys, _tok = case.keys()
    assert n_keys < U // 2
    got = case.coarse(key_rows, key_of, with_cand, stat)
    full = case.coarse(*case.keys(identity=True)[:2], with_cand, stat)
    if not with_cand:
        got, full = (got,), (full,)
    sl, sl_full = np.asarray(got[0]), np.asarray(full[0])
    assert sl.shape == (U, SL_K) and np.array_equal(sl, sl_full)
    assert len({row.tobytes() for row in sl}) > 1       # the rankings do differ
    for mine, theirs in zip(got[1:], full[1:]):
        mine, theirs = np.asarray(mine), np.asarray(theirs)
        assert mine.shape == (len(key_rows), BLOCKS, SL_K)
        assert np.array_equal(mine[key_of], theirs)


@needs_4
@pytest.mark.parametrize("cnt0_any", [False, True])
def test_the_keyed_shortlist_is_the_full_one_on_a_mesh_of_four(cnt0_any):
    """The node planes sharded over four devices, the selection
    shard-local: the keyed pass, the pass over every row and the
    one-device pass give one array, and the keyed array lies where the
    full one does."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from volcano_tpu.parallel.mesh import NODES_AXIS, make_mesh

    case = Case(21, cnt0_any)
    mesh = make_mesh(4)
    rows = NamedSharding(mesh, P(NODES_AXIS))
    nodes = SolveNodes(*[jax.device_put(a, rows) for a in case.nodes])
    stat = case.statics()
    key_rows, key_of, _n, _tok = case.keys()
    one = case.coarse(key_rows, key_of, True, stat)
    got = case.coarse(key_rows, key_of, True, stat, nodes=nodes, mesh_shards=4)
    full = case.coarse(*case.keys(identity=True)[:2], True, stat, nodes=nodes,
                       mesh_shards=4)
    assert np.array_equal(np.asarray(got[0]), np.asarray(full[0]))
    assert np.array_equal(np.asarray(got[0]), np.asarray(one[0]))
    for mine, theirs in zip(got[1:], full[1:]):
        assert np.array_equal(np.asarray(mine)[key_of], np.asarray(theirs))
    assert got[0].sharding.is_equivalent_to(full[0].sharding, 2)


# ---- the warm pass -------------------------------------------------------------


def _moved(case, rows):
    """``case`` with the node rows ``rows`` emptied: what a cycle's dirty
    set stands for."""
    idle = case.nodes.idle.copy()
    idle[rows] = case.nodes.allocatable[rows]
    case.nodes = case.nodes._replace(idle=idle)


@pytest.mark.parametrize("change", ["none", "shape", "term"])
def test_a_warm_pass_holds_its_keys_and_a_changed_key_set_re_ranks(change):
    """Three solves under one warm key.  The second, two node rows dirty,
    is a warm pass over the same keys and gives the full pass's array.
    Before the third the key set changes: a row takes a new pod shape, or
    (resident counts present) gains a term entry.  The candidates kept
    belong to another key set, so the pass is a full one and its array the
    full pass's; without a change it is warm again."""
    case = Case(31, cnt0_any=True)
    dv = dvm.DeviceIncremental()
    stat = case.statics()

    def solve(dirty):
        dv.begin_solve("static", "warm", None if dirty is None else np.array(dirty))
        sl = case.shortlist(dv, case.keys(), stat)
        dv.end_solve()
        full = np.asarray(case.coarse(*case.keys(identity=True)[:2], True, stat)[0])
        assert np.array_equal(sl, full)
        return dv.last_mode

    assert solve(None) == "full"
    _moved(case, [3, 200])
    assert solve([3, 200]) == "warm" and dv.last_blocks == (2, 16)
    keys_before = case.keys()[2]
    if change == "shape":
        nine = [a.copy() for a in case.nine]
        nine[0][30] = nine[1][30] = np.float32([9, 9, 9])
        case.nine = tuple(nine)
        stat = case.statics()
    elif change == "term":
        key_of = case.keys()[1]
        row = next(u for u in range(21, LIVE) if (key_of == key_of[u]).sum() > 1)
        tables = [t.copy() for t in case.tables]
        tables[1][row, 2] = True
        case.tables = tuple(tables)
    assert case.keys()[2] == keys_before + (change != "none")
    _moved(case, [77])
    assert solve([77]) == ("warm" if change == "none" else "full")
    assert dv.counts == {"warm": 2 if change == "none" else 1,
                         "full": 1 if change == "none" else 2, "skip": 0}


def test_a_null_delta_hands_back_the_shortlist_of_these_rows():
    """No dirty row and the same keys: the shortlist kept is handed back
    as it is.  The same keys handed to other rows (two rows trade places)
    is another token, and the pass a full one."""
    case = Case(41, cnt0_any=False)
    dv = dvm.DeviceIncremental()
    dv.begin_solve("static", "warm", None)
    first = case.shortlist(dv, case.keys())
    dv.end_solve()
    dv.begin_solve("static", "warm", np.zeros(0, np.int64))
    assert np.array_equal(case.shortlist(dv, case.keys()), first)
    assert dv.last_mode == "warm" and dv.last_blocks == (0, 16)
    dv.end_solve()
    key_of = case.keys()[1]
    a = 0
    b = next(u for u in range(LIVE) if key_of[u] != key_of[a])
    swap = np.arange(U)
    swap[[a, b]] = b, a
    case.nine = tuple(col[swap] for col in case.nine)
    dv.begin_solve("static", "warm", np.zeros(0, np.int64))
    second = case.shortlist(dv, case.keys())
    assert dv.last_mode == "full"
    assert np.array_equal(second, first[swap])


# ---- the counts, and the cycle ---------------------------------------------------


AFFINITY_10K = json.loads(
    (ROOT / "benchmark" / "configs" / "affinity-10k.json").read_text())


def _two_rounds(identity, monkeypatch):
    """A toy of ``affinity-10k.burst`` (64 nodes in 16 zones, 256 pods in
    gangs of 8, a mix of 30 / 30 / 30) bound onto the empty cluster, then a
    round that finds its counts: one more pod for every constrained gang,
    with the gang's labels and terms, while the gang is resident.  Returns
    each round's binds and the solve counts of its cycle."""
    if identity:
        def every_row(sp, extra_prof, score_prof, own_terms, marks=None):
            u = np.arange(sp.terms.shape[0], dtype=np.int32)
            return u, u, len(u), "identity"
        monkeypatch.setattr(wave, "shortlist_keys", every_row)
    cfg = json.loads(json.dumps(AFFINITY_10K))
    cfg["nodes"]["count"] = 64
    cfg["backlog_pods"] = 256
    cfg["affinity_mix"] = {"affinity": 0.3, "anti_affinity": 0.3, "spread": 0.3}
    plan = generate.Generator(cfg, 2**31 + 51).plan(256, "a")
    stamps = itertools.count(1)
    gangs = generate.to_objects(plan, stamps)
    late = [(PodGroup(name=f"late-{pg.name}", min_member=1, queue=pg.queue,
                      creation_timestamp=float(next(stamps))),
             [dataclasses.replace(
                 pods[0], name=f"late-{pods[0].name}", uid=f"late-{pods[0].uid}",
                 annotations={GROUP_NAME_ANNOTATION: f"late-{pg.name}"},
                 creation_timestamp=float(next(stamps)))])
            for (pg, pods), kind in zip(gangs, plan.gang_kind) if kind]
    store = ClusterStore(binder=FakeBinder())
    for node in generate.to_nodes(cfg):
        store.add_node(node)
    sched = Scheduler(store, conf_str=cfg["scheduler_conf"])
    out, seen = [], 0
    try:
        for batch in (gangs, late):
            for pg, pods in batch:
                store.add_pod_group(pg)
                for pod in pods:
                    store.add_pod(pod)
            sched.run_once()
            store.flush_binds()
            cycle = store.flight.recent()[-1]
            assert cycle.path == "fast"
            binds = dict(store.binder.binds)
            assert len(binds) == seen + sum(len(pods) for _pg, pods in batch)
            seen = len(binds)
            out.append((binds, cycle.solve))
    finally:
        store.close()
    return out


def test_a_fast_cycle_binds_what_it_binds_with_every_row_a_key(monkeypatch):
    """Two rounds of the toy, the second onto resident pods that match its
    terms (``cnt0_any``): pod for pod the node the same run gives with the
    key map forced to the identity.  The record's ``solve`` block says how
    many rows were served and how many keys ranked."""
    keyed = _two_rounds(False, monkeypatch)
    plain = _two_rounds(True, monkeypatch)
    for (binds, solve), (binds_id, solve_id) in zip(keyed, plain):
        assert binds == binds_id
        assert solve["shortlist_rows"] == solve_id["shortlist_rows"] == 64
        assert solve_id["shortlist_keys"] == 64
        assert 1 < solve["shortlist_keys"] < solve["shortlist_rows"]
    # an empty cluster: the pod shapes and the padding row, not a key a gang
    (_b, first), (_b2, second) = keyed
    assert first["aff_cnt0_entries"] == 0 and first["shortlist_keys"] <= 10
    assert first["aff_prof_entries"] > 20
    # resident counts: a key of its own for every row with a term entry
    assert second["aff_cnt0_entries"] > 0
    assert second["shortlist_keys"] >= second["aff_rows"] > 20


def test_solve_wave_says_rows_and_keys_and_all_rows_differing_reads_both_equal():
    from volcano_tpu.synth import solve_args_from_store, synthetic_cluster

    store = synthetic_cluster(n_nodes=32, n_pods=64, gang_size=4, seed=61)
    args, _maps = solve_args_from_store(store)
    store.close()
    wave.solve_wave(*args, wave=64)
    seen = wave.LAST_TWOPHASE
    assert seen["shortlist_rows"] == 64 and 1 < seen["shortlist_keys"] < 64
    tasks = args[1]
    req = np.asarray(tasks.req).copy()
    req[:, 0] += np.arange(64, dtype=np.float32) / 1024     # 64 pod shapes
    args = (args[0], tasks._replace(req=req, init_req=req), *args[2:])
    wave.solve_wave(*args, wave=64, shape_marks={"U": 64})
    assert (seen["shortlist_rows"], seen["shortlist_keys"]) == (64, 64)
