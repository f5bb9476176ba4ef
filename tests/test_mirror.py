"""Mirror maintenance: compaction, dynamic updates, fallback eligibility."""

import copy
import os

import numpy as np
import pytest

from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    AffinityTerm,
    Node,
    Pod,
    PodGroup,
    TaskInfo,
    Toleration,
)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.cache.mirror import HOSTNAME_KEY, JOB_SELECTOR
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster


def test_compaction_preserves_scheduling():
    """Deleting >half the pod table triggers compaction; scheduling after
    compaction matches a fresh store with the same surviving state."""
    store = ClusterStore()
    for i in range(4):
        store.add_node(Node(name=f"n{i}",
                            allocatable={"cpu": "8", "memory": "16Gi"}))
    # Churn: add and delete enough pods to cross the compaction threshold.
    dead = []
    for i in range(5000):
        p = Pod(name=f"tmp-{i}", containers=[{"cpu": "100m",
                                              "memory": "64Mi"}])
        store.add_pod(p)
        dead.append(p)
    for p in dead:
        store.delete_pod(p)
    assert store.mirror.n_dead == 0 or store.mirror.n_pods < 5000
    # Survivors scheduled after compaction.
    store.add_pod_group(PodGroup(name="g", min_member=3))
    for i in range(3):
        store.add_pod(Pod(name=f"w{i}",
                          containers=[{"cpu": "1", "memory": "1Gi"}],
                          annotations={GROUP_NAME_ANNOTATION: "g"}))
    Scheduler(store).run_once()
    assert len(store.binder.binds) == 3


def test_custom_plugin_conf_falls_back_to_object_path():
    """Non-built-in plugin names make the fast path ineligible; the object
    session handles the cycle and still binds."""
    conf = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: gang
  - name: priority
  - name: conformance
"""
    # Sanity: this conf IS eligible; now an unknown plugin is not.
    conf_custom = conf + "  - name: my-custom-plugin\n"
    import volcano_tpu.fastpath as fp
    from volcano_tpu.framework import parse_scheduler_conf

    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    assert fp.FastCycle(store, parse_scheduler_conf(conf)).eligible()
    parsed = parse_scheduler_conf(conf_custom)
    assert not fp.FastCycle(store, parsed).eligible()
    Scheduler(store, conf_str=conf_custom).run_once()
    assert len(store.binder.binds) == 8


def test_mirror_tracks_bind_and_evict_status():
    from volcano_tpu.api import TaskStatus

    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    Scheduler(store).run_once()
    m = store.mirror
    bound_rows = np.flatnonzero(
        m.p_status[:m.n_pods] == int(TaskStatus.Bound)
    )
    assert len(bound_rows) == 8
    # Evict one pod through the store; mirror follows.
    pod = next(iter(store.pods.values()))
    ti = store.jobs[pod.job_id()].tasks[pod.uid]
    store.evict(ti, "test")
    row = m.p_row[pod.uid]
    assert m.p_status[row] == int(TaskStatus.Releasing)


def test_checkpoint_then_schedule_more(tmp_path):
    """A restored store keeps scheduling new work (mirror rebuilt via the
    event API replay)."""
    from volcano_tpu.persistence import load_store, save_store

    store = synthetic_cluster(n_nodes=4, n_pods=8, gang_size=2)
    Scheduler(store).run_once()
    path = str(tmp_path / "ckpt")
    save_store(store, path)
    b = load_store(path)
    b.add_pod_group(PodGroup(name="late", min_member=2))
    for i in range(2):
        b.add_pod(Pod(name=f"late-{i}",
                      containers=[{"cpu": "1", "memory": "1Gi"}],
                      annotations={GROUP_NAME_ANNOTATION: "late"}))
    Scheduler(b).run_once()
    assert any(k.endswith("late-0") for k in b.binder.binds)


def test_object_path_status_writes_refresh_mirror_columns():
    """update_job_status / record_job_condition (the object session's
    write-back) must re-sync the mirror's persistent j_phase_code /
    j_st_* / j_cond_sig columns, or the fast path's change detection
    works off stale 'last written' state after a slow-path cycle."""
    from volcano_tpu.api import PodGroup, PodGroupCondition
    from volcano_tpu.cache import ClusterStore

    store = ClusterStore()
    pg = PodGroup(name="g", min_member=2)
    store.add_pod_group(pg)
    m = store.mirror
    row = m.j_row[pg.uid]
    assert m.j_phase_code[row] == 1  # Pending

    # Object-path write-back: phase + counters via update_job_status.
    snap = store.snapshot()
    job = snap.jobs[pg.uid]
    job.pod_group.status.phase = "Running"
    job.pod_group.status.running = 2
    store.update_job_status(job)
    assert m.j_phase_code[row] == 3
    assert m.j_st_run[row] == 2

    # Condition write via record_job_condition refreshes the signature.
    cond = PodGroupCondition(
        type="Unschedulable", status="True", transition_id="t",
        reason="NotEnoughResources", message="0/2 ready",
    )
    store.record_job_condition(job, cond)
    assert m.j_cond_sig[row] == (
        hash(("NotEnoughResources", "0/2 ready")) & 0x7FFFFFFFFFFFFFFF
    )


def test_job_uid_rank_extends_its_uid_array_and_stays_the_full_sort():
    """The rank is a strict monotone map of the uid strings whether the
    string array was made in one go or extended round by round, with
    uids of growing width, and across a pod-table compaction (which
    carries the job table, and the cached array, over)."""
    store = ClusterStore()
    m = store.mirror
    assert len(m.job_uid_rank()) == 0
    rng = np.random.default_rng(5)

    def check():
        rank = m.job_uid_rank()
        uids = list(m.j_uid)
        want = np.empty(len(uids), np.int64)
        want[np.argsort(np.array(uids), kind="stable")] = np.arange(len(uids))
        np.testing.assert_array_equal(rank, want)
        assert [uids[i] for i in np.argsort(rank)] == sorted(uids)

    for step in range(12):
        for _ in range(int(rng.integers(1, 40))):
            name = "g" + "x" * step + str(int(rng.integers(0, 10 ** 6)))
            store.add_pod_group(PodGroup(name=name, min_member=1))
        check()
        assert len(m._j_uid_arr) == len(m.j_uid)
    first = m._j_uid_arr
    pods = [Pod(name=f"tmp-{i}", containers=[{"cpu": "100m"}])
            for i in range(4200)]
    for p in pods:
        store.add_pod(p)
    gen = m.compact_gen
    for p in pods:
        store.delete_pod(p)
    assert m.compact_gen > gen and m._j_uid_arr is first
    store.add_pod_group(PodGroup(name="after", min_member=1))
    check()


# ---------------------------------------------- one record a distinct spec

_ROW_COLUMNS = ("p_status", "p_node", "p_node_name", "p_job", "p_prio",
                "p_create", "p_alive", "p_be", "p_has_ip", "p_has_tol",
                "p_critical", "p_prof")
_ROW_CSR = ("c_req", "c_init_req", "c_sel", "c_ports", "c_ip_aff",
            "c_ip_anti", "c_ip_soft")
_INTERNERS = ("profiles", "terms", "labels", "ports", "scalar_slots",
              "topo_keys")
_RECORD = ("req", "init_req", "sel", "ports", "aff_alts", "pref",
           "ip_req_aff", "ip_req_anti", "ip_soft", "has_ip", "priority",
           "best_effort", "prof")


def _csr_row(col, r):
    """Row ``r`` as the column's readers are given it: a pod's row of
    the seven columns asked by pod row, a side table's own."""
    got = col.gather(np.array([r]))
    assert col.lens(np.array([r])).tolist() == [len(got[0])]
    return got[1].tolist(), got[2].tolist() if col.has_val else None


def _tols(tols):
    return [(t.key, t.operator, t.value, t.effect) for t in tols]


def _row_view(m, r):
    """Everything the mirror holds of pod row ``r``, as plain values."""
    feat = m.p_feat[r]
    return (
        m.p_key[r],
        [getattr(m, name)[r] for name in _ROW_COLUMNS],
        [_csr_row(getattr(m, name), r) for name in _ROW_CSR],
        [_csr_row(m.c_aff_alt, a)
         for a in range(*(int(e[0]) for e in m.aff_ranges(np.array([r]))))],
        [(_csr_row(m.c_pref, a), m.pref_w[a])
         for a in range(*(int(e[0]) for e in m.pref_ranges(np.array([r]))))],
        [getattr(feat, name) for name in _RECORD] + [_tols(feat.tol)],
        (feat.req_res.milli_cpu, feat.req_res.memory,
         dict(feat.req_res.scalars or {}), feat.init_res.milli_cpu,
         feat.init_res.memory, dict(feat.init_res.scalars or {})),
    )


def _mirrors_agree(a, b, same_rows):
    """Two mirrors hold the same: every interned index, every live
    pod's row by uid and every term's members by uid; where the two
    were to lay their rows out alike, every column whole as well."""
    for name in _INTERNERS:
        assert getattr(a, name).items == getattr(b, name).items, name
    assert a.term_info == b.term_info
    assert set(a.p_row) == set(b.p_row)
    for uid, ra in a.p_row.items():
        assert _row_view(a, ra) == _row_view(b, b.p_row[uid]), uid
    for ma, mb in zip(a.term_members, b.term_members):
        assert (sorted(a.p_uid[r] for r in ma if a.p_alive[r])
                == sorted(b.p_uid[r] for r in mb if b.p_alive[r]))
    if not same_rows:
        return
    n = len(a.p_uid)
    assert a.p_uid == b.p_uid and a.p_key == b.p_key
    assert (a.n_dead, a.compact_gen) == (b.n_dead, b.compact_gen)
    for name in _ROW_COLUMNS:
        np.testing.assert_array_equal(getattr(a, name)[:n],
                                      getattr(b, name)[:n], err_msg=name)
    # The spec tables differ (a row a spec against a row a pod); what a
    # reader gathers for the whole pod table does not, dtype included.
    rows = np.arange(n)
    for name in _ROW_CSR:
        ca, cb = getattr(a, name), getattr(b, name)
        np.testing.assert_array_equal(ca.lens(rows), cb.lens(rows))
        for ga, gb in zip(ca.gather(rows), cb.gather(rows), strict=True):
            assert ga.dtype == gb.dtype, name
            np.testing.assert_array_equal(ga, gb, err_msg=name)
    assert a.term_members == b.term_members
    assert a._pods_by_pair == b._pods_by_pair


def _gang_pod(i, **spec):
    g = i // 4
    spec.setdefault("labels", {"app": f"g{g % 3}"})
    spec.setdefault("containers", [{"cpu": str(1 + g % 3), "memory": "2Gi"}])
    return Pod(name=f"p{i}", uid=f"u{i}", creation_timestamp=float(i + 1),
               annotations={GROUP_NAME_ANNOTATION: f"pg{g % 2}"}, **spec)


def _shared(i, held):
    """A gang's pods share their sub-objects by reference, as the
    benchmark's and ``synth``'s do."""
    g = i // 4
    if held.get("g") != g:
        held.update(g=g, labels={"app": f"g{g % 3}"},
                    containers=[{"cpu": str(1 + g % 3), "memory": "2Gi"}])
    return _gang_pod(i, labels=held["labels"], containers=held["containers"])


def _mutated(i, held):
    """One containers list for every pod, changed in place on the way:
    a pod is encoded as the list reads when it is added."""
    box = held.setdefault("containers", [{"cpu": "1", "memory": "2Gi"}])
    if i % 1000 == 500:
        box[0]["cpu"] = str(1 + i // 1000)
    if i % 1000 == 750:
        box.append({"cpu": "250m", "example.com/gpu": 1})
    if i % 1000 == 900:
        box.pop()
    return _gang_pod(i, containers=box)


def _term(i):
    return AffinityTerm(match_labels={"app": f"g{i // 4 % 3}"},
                        topology_key="zone")


_SPEC_CASES = {
    # name: (pod of index i, specs encoded with the memo on or None for
    #        "one an add", SPEC_MEMO_CAP of the store with the memo)
    "shared-by-reference": (_shared, 3, None),
    "fresh-objects": (lambda i, held: _gang_pod(i), 3, None),
    "list-mutated-between-adds": (_mutated, None, None),
    "equal-terms-two-namespaces": (
        lambda i, held: _gang_pod(
            i, namespace=("a", "b")[i // 4 % 2], affinity=[_term(i)],
            preferred_anti_affinity=[(_term(i + 4), 5)]), None, None),
    "spread-in-two-jobs": (
        lambda i, held: _gang_pod(
            i, containers=[{"cpu": "1"}], topology_spread=[("zone", 10)],
            node_selector={"zone": "z1"}, host_ports=[8080 + i // 8 % 2]),
        4, None),
    "two-priorities": (
        lambda i, held: _gang_pod(i, priority=(None, 1, 7)[i % 3],
                                  containers=[{"cpu": "1"}]), 3, None),
    "toleration": (
        lambda i, held: _gang_pod(
            i, containers=[{"cpu": "1"}], init_containers=[{"cpu": "2"}],
            required_node_affinity=[{"zone": "z0"}, {"zone": "z1"}],
            preferred_node_affinity=[({"rack": "r1"}, 3)],
            tolerations=[Toleration(key="gpu", operator="Exists",
                                    effect=("", "NoSchedule")[i % 2])]),
        2, None),
    "a-spec-a-pod": (
        lambda i, held: _gang_pod(i, containers=[{"cpu": f"{i + 1}m"}]),
        None, None),
    "cap-reached-mid-stream": (
        lambda i, held: _gang_pod(i, containers=[{"cpu": str(1 + i // 16 % 5)}]),
        None, 3),
    "update-with-fresh-object-of-equal-spec": (
        lambda i, held: _gang_pod(i), 3, None),
}


@pytest.mark.parametrize("case", sorted(_SPEC_CASES))
def test_a_spec_encoded_once_leaves_the_mirror_it_left_encoded_per_pod(case):
    """``StoreMirror._feat`` gives every pod of one spec one record
    (ISSUE 46).  The same seeded stream of adds, updates, binds
    (copy-on-write), deletes and compactions into two stores, one of
    which forgets every spec at once (``SPEC_MEMO_CAP`` 0: a spec a
    pod, what the mirror did before), leaves the two mirrors alike:
    columns, CSR columns, interned indices, term members."""
    make, specs, cap = _SPEC_CASES[case]
    fresh_update = case.startswith("update-with-fresh")

    def run(memo_cap):
        store = ClusterStore()
        if memo_cap is not None:
            store.mirror.SPEC_MEMO_CAP = memo_cap
        for z in range(2):
            store.add_node(Node(name=f"n{z}", labels={"zone": f"z{z}"},
                                allocatable={"cpu": "9000", "memory": "9000Gi",
                                             "example.com/gpu": 9000}))
        for g in range(2):
            store.add_pod_group(PodGroup(name=f"pg{g}", min_member=1))
        rng = np.random.default_rng(46)
        held, live, n_added, in_place = {}, [], 0, 0

        def add(k):
            nonlocal n_added
            for _ in range(k):
                pod = make(n_added, held)
                store.add_pod(pod)
                live.append(pod.uid)
                n_added += 1

        add(4300)
        for uid in rng.choice(live, 300, replace=False).tolist():
            pod = store.pods[uid]
            if rng.random() < 0.5:
                store.bind(TaskInfo(pod), f"n{int(rng.integers(2))}")
            elif fresh_update:
                # What a client decodes from the wire: a new object,
                # equal in everything, running.
                dead0 = store.mirror.n_dead
                new = make(int(uid[1:]), {})
                new.phase = "Running"
                new.node_name = "n0"
                store.update_pod(new)
                in_place += store.mirror.n_dead == dead0
            else:
                new = copy.copy(pod)
                new.phase = "Running"
                new.node_name = "n1"
                store.update_pod(new)
        gen = store.mirror.compact_gen
        for uid in live[:2300]:
            store.delete_pod(store.pods[uid])
        del live[:2300]
        assert store.mirror.compact_gen > gen
        add(200)        # specs met before the compaction are still met
        return store, in_place

    (memo, in_place), (plain, replaced) = run(cap), run(0)
    _mirrors_agree(memo.mirror, plain.mirror, same_rows=not fresh_update)
    assert plain.mirror._spec_memo == {}
    encoded = memo._between.specs_encoded
    assert plain._between.specs_encoded >= 4500
    if specs is not None:
        assert encoded == specs
    elif case == "a-spec-a-pod":
        assert encoded == 4500          # the key's microsecond, no more
    else:
        assert 3 < encoded < 4500 // 4
    if fresh_update:
        # With the memo the new object is handed the row's own record and
        # takes the "same spec blob" branch of ``upsert_pod``, which
        # rewrites status, node, job and creation time where the row
        # is (the priority is the record's); per pod it is a row's death
        # and another's birth.
        assert in_place > 100 and replaced == 0
        row = memo.mirror.p_row["u4299"]
        assert memo.mirror.p_prio[row] == 1
        assert memo.mirror.p_create[row] == 4300.0


# ------------------------------------- one row a spec, asked by pod row

_SPEC_KINDS = {
    "plain": lambda v: dict(
        containers=[{"cpu": str(1 + v), "memory": "1Gi"}]),
    "selector-tolerations-ports": lambda v: dict(
        containers=[{"cpu": "1"}],
        node_selector={"zone": f"z{v % 2}", "disk": "ssd"},
        tolerations=[Toleration(key="gpu", operator="Exists",
                                effect="NoSchedule"),
                     Toleration(key="team", operator="Equal",
                                value=f"t{v}")],
        host_ports=[8080 + v, 9090]),
    "node-affinity": lambda v: dict(
        containers=[{"cpu": "2", "memory": "1Gi"}],
        required_node_affinity=[{"zone": "z0"},
                                {"zone": "z1", "rack": f"r{v}"}][:1 + v % 2],
        preferred_node_affinity=[({"rack": f"r{v}"}, 3),
                                 ({"zone": "z1"}, 1 + v)]),
    "inter-pod": lambda v: dict(
        labels={"app": f"a{v}"}, containers=[{"cpu": "1", "memory": "2Gi"}],
        affinity=[AffinityTerm({"app": f"a{v}"}, "zone")],
        anti_affinity=[AffinityTerm({"app": "x"}, HOSTNAME_KEY,
                                    namespaces=["other", "default"])],
        preferred_affinity=[(AffinityTerm({"tier": "db"}, "zone"), 5)],
        preferred_anti_affinity=[(AffinityTerm({"app": f"a{v}"}, "rack"),
                                  2 + v)]),
    "topology-spread": lambda v: dict(
        containers=[{"cpu": "1"}],
        topology_spread=[("zone", 10), ("rack", 1 + v)]),
    "best-effort": lambda v: dict(containers=[], priority=v),
    "init-containers": lambda v: dict(
        containers=[{"cpu": "1", "example.com/gpu": 1}],
        init_containers=[{"cpu": str(2 + v), "memory": "4Gi"},
                         {"cpu": "1", "example.com/nic": 2}]),
}


def _kind_pod(kind, i, **over):
    """Pod ``i`` of a case's stream: two in three of the case's kind in
    one of four variants, the others plain, dealt to five jobs."""
    spec = dict(name=f"p{i}", uid=f"u{i}", creation_timestamp=float(i + 1),
                annotations={GROUP_NAME_ANNOTATION: f"pg{i % 5}"})
    spec.update((_SPEC_KINDS[kind] if i % 3
                 else _SPEC_KINDS["plain"])(i // 7 % 4))
    spec.update(over)
    return Pod(**spec)


def _plain_encoding(m, pod):
    """What a mirror that encodes every pod by itself holds of ``pod``
    (never met by a store), in ``m``'s interned indices."""
    def res(r):
        slots = [0] * bool(r.milli_cpu) + [1] * bool(r.memory)
        vals = [v for v in (r.milli_cpu, r.memory) if v]
        for name, quant in (r.scalars or {}).items():
            if quant:
                slots.append(2 + m.scalar_slots.index[name])
                vals.append(quant)
        return slots, vals

    def pairs(d):
        return [m.labels.index[kv] for kv in d.items()]

    def term(t):
        ns = tuple(sorted(t.namespaces)) if t.namespaces else (pod.namespace,)
        return m.terms.index[
            (tuple(sorted(t.match_labels.items())), t.topology_key, ns)]

    soft = ([(term(t), float(w)) for t, w in pod.preferred_affinity]
            + [(term(t), -float(w)) for t, w in pod.preferred_anti_affinity]
            + [(m.terms.index[(((JOB_SELECTOR, pod.job_id()),), key, None)],
                -float(w)) for key, w in pod.topology_spread])
    return {
        "c_req": res(pod.resource_request()),
        "c_init_req": res(pod.init_resource_request()),
        "c_sel": (pairs(pod.node_selector), None),
        "c_ports": ([m.ports.index[p] for p in pod.host_ports], None),
        "c_ip_aff": ([term(t) for t in pod.affinity], None),
        "c_ip_anti": ([term(t) for t in pod.anti_affinity], None),
        "c_ip_soft": ([e for e, _ in soft], [w for _, w in soft]),
        "aff": [pairs(alt) for alt in pod.required_node_affinity],
        "pref": [(pairs(sel), float(w))
                 for sel, w in pod.preferred_node_affinity],
        "tol": _tols(pod.tolerations),
    }


def _readers_get_the_plain_encoding(m, want_of_row, rng):
    """Every ragged column, gathered for the whole table and for rows
    drawn with repeats, dead rows among them, is the plain encoding's
    rows laid end to end: indices, values, order and dtype."""
    n = len(m.p_uid)
    assert set(want_of_row) == set(range(n))
    want = {r: _plain_encoding(m, pod) for r, pod in want_of_row.items()}
    drawn = rng.integers(0, n, 300)
    for rows in (np.arange(n), drawn, drawn[:1], drawn[:0]):
        for name in _ROW_CSR:
            col = getattr(m, name)
            per_row = [want[r][name] for r in rows.tolist()]
            lens = [len(idx) for idx, _ in per_row]
            got = col.gather(rows)
            assert [a.dtype for a in got] == (
                [np.int64, np.int32] + [np.float32] * col.has_val), name
            np.testing.assert_array_equal(col.lens(rows), lens)
            assert col.lens(rows).dtype == np.int64
            np.testing.assert_array_equal(
                got[0], np.repeat(np.arange(len(rows)), lens))
            assert got[1].tolist() == [i for idx, _ in per_row for i in idx]
            if col.has_val:
                np.testing.assert_array_equal(
                    got[2], np.array([v for _, vals in per_row for v in vals],
                                     np.float32))
    for r, lo, hi, plo, phi in zip(drawn.tolist(), *m.aff_ranges(drawn),
                                   *m.pref_ranges(drawn)):
        assert [_csr_row(m.c_aff_alt, a)[0]
                for a in range(lo, hi)] == want[r]["aff"]
        assert [(_csr_row(m.c_pref, a)[0], m.pref_w[a])
                for a in range(plo, phi)] == want[r]["pref"]
        assert _tols(m.p_feat[r].tol) == want[r]["tol"]
        assert m.p_has_tol[r] == bool(want[r]["tol"])
    alive = np.flatnonzero(m.p_alive[:n])
    assert len(m.s_feat) >= len({id(m.p_feat[r]) for r in alive.tolist()})
    assert [f.row for f in m.s_feat] == list(range(len(m.s_feat)))


@pytest.mark.parametrize("kind", sorted(_SPEC_KINDS))
def test_readers_asking_by_pod_row_get_what_a_row_a_pod_would_hold(kind):
    """A spec's ragged features are one row of the spec table (ISSUE 47)
    and the readers' ``gather(rows)`` / ``lens(rows)``, the ``aff`` /
    ``pref`` ranges and the tolerations answer by pod row as a table
    with one row a pod would: after the adds (the memo overflowing on
    the way), after spec-changing updates, after a compaction, and for
    a ``Pod`` object that comes back when its spec's row is gone."""
    store = ClusterStore()
    m = store.mirror
    m.SPEC_MEMO_CAP = 3
    for z in range(2):
        store.add_node(Node(name=f"n{z}", labels={"zone": f"z{z}"},
                            allocatable={"cpu": "64", "memory": "64Gi"}))
    for g in range(5):
        store.add_pod_group(PodGroup(name=f"pg{g}", min_member=1))
    rng = np.random.default_rng(47)
    want_of_row = {}

    def put(pod, event):
        event(pod)
        want_of_row[m.p_row[pod.uid]] = copy.deepcopy(pod)

    # Three pods of a spec nobody else has, whose objects are kept.
    held = [_kind_pod(kind, 1, name=f"h{i}", uid=f"h{i}",
                      containers=[{"cpu": "77m"}]) for i in range(3)]
    for pod in held:
        put(pod, store.add_pod)
    for i in range(4300):
        put(_kind_pod(kind, i), store.add_pod)
    assert store._between.specs_encoded > 8     # the memo overflowed
    assert len(m.s_feat) == store._between.specs_encoded
    _readers_get_the_plain_encoding(m, want_of_row, rng)

    for i in rng.choice(4300, 60, replace=False).tolist():
        # Another spec under the same uid: the row dies, a new one is born.
        dead0 = m.n_dead
        put(_kind_pod(kind, i, **_SPEC_KINDS[kind](5)), store.update_pod)
        assert m.n_dead == dead0 + 1
    _readers_get_the_plain_encoding(m, want_of_row, rng)

    gen = m.compact_gen
    for pod in held:
        store.delete_pod(pod)
    for i in range(2300):
        store.delete_pod(store.pods[f"u{i}"])
    assert m.compact_gen == gen + 1
    # Rows were renumbered; the deletes after it left tombstones, which
    # keep their key.  A pod's later row is the later entry.
    by_key = {f"default/{pod.name}": pod for pod in want_of_row.values()}
    want_of_row = {row: by_key[key] for row, key in enumerate(m.p_key)}
    _readers_get_the_plain_encoding(m, want_of_row, rng)
    # The table holds the specs with a live row and no other; the memo
    # likewise; the record the kept objects carry has no row any more.
    live = np.flatnonzero(m.p_alive[:len(m.p_uid)])
    assert ({id(f) for f in m.s_feat}
            >= {id(m.p_feat[r]) for r in live.tolist()})
    assert len(m.s_feat) <= len({id(m.p_feat[r])
                                 for r in range(len(m.p_uid))})
    assert all(f.row >= 0 and m.s_feat[f.row] is f
               for f in m._spec_memo.values())
    feat = held[0]._mirror_feat
    assert feat.row == -1 and all(f is not feat for f in m.s_feat)

    encoded = store._between.specs_encoded
    put(held[0], store.add_pod)             # the same object, re-added
    assert held[0]._mirror_feat is feat and m.s_feat[feat.row] is feat
    assert store._between.specs_encoded == encoded + 1   # a row was written
    put(held[1], store.add_pod)             # the record has its row again
    assert store._between.specs_encoded == encoded + 1
    for i in range(4300, 4500):
        put(_kind_pod(kind, i), store.add_pod)
    assert store._between.spec_rows == len(m.s_feat)
    _readers_get_the_plain_encoding(m, want_of_row, rng)


def _own_spec_gang(g, size=8):
    """A gang whose pods carry a required anti-affinity term on the
    gang's own label, as ``affinity-10k``'s constrained gangs do: a spec
    nobody else has."""
    labels = {"gang": f"g{g}"}
    term = AffinityTerm(dict(labels), HOSTNAME_KEY)
    return [Pod(name=f"g{g}-{i}", uid=f"g{g}-{i}", labels=labels,
                containers=[{"cpu": "1", "memory": "1Gi"}],
                anti_affinity=[term],
                annotations={GROUP_NAME_ANNOTATION: "pg"})
            for i in range(size)]


def test_the_spec_table_is_bounded_by_the_specs_with_a_live_row():
    """Rounds of gangs that each bring a spec of their own and leave
    with their pods (``affinity-10k``'s 2,500 a round): after every
    compaction the table holds one row a spec that still has a live
    pod, and round after round it reaches the same size."""
    store = ClusterStore()
    m = store.mirror
    store.add_pod_group(PodGroup(name="pg", min_member=1))
    residents = [Pod(name=f"r{i}", uid=f"r{i}",
                     containers=[{"cpu": str(1 + i % 3)}],
                     annotations={GROUP_NAME_ANNOTATION: "pg"})
                 for i in range(900)]
    for pod in residents:
        store.add_pod(pod)
    assert len(m.s_feat) == 3
    peaks, g = [], 0
    for _round in range(6):
        batch = []
        for _ in range(600):
            batch.extend(_own_spec_gang(g))
            g += 1
        for pod in batch:
            store.add_pod(pod)
        peaks.append(len(m.s_feat))
        gen = m.compact_gen
        for pod in batch:
            store.delete_pod(pod)
            if m.compact_gen != gen:
                gen = m.compact_gen
                live = np.flatnonzero(m.p_alive[:len(m.p_uid)]).tolist()
                assert len(m.s_feat) == len({id(m.p_feat[r]) for r in live})
                assert store._between.spec_rows == len(m.s_feat)
                assert len(m._spec_memo) <= len(m.s_feat)
                np.testing.assert_array_equal(
                    m.p_spec[live], [m.p_feat[r].row for r in live])
        assert gen > 0
    # The residents' 3 + a round's 600 + at most the round before's,
    # whose tombstones wait for the next compaction; the later rounds
    # reach no higher than the earlier ones.
    assert max(peaks) <= 3 + 2 * 600
    assert max(peaks[3:]) <= max(peaks[:3])
    assert store._between.specs_encoded == 3 + 6 * 600


def test_one_record_shared_by_rows_of_two_jobs_binds_as_the_object_path():
    """Fast cycle against object session (``test_fastpath``'s
    comparison) where one record is the spec of rows of different jobs,
    inter-pod terms (``has_aff``) among them: the same binds."""
    conf = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""

    def run(fast):
        store = ClusterStore()
        for i in range(6):
            store.add_node(Node(
                name=f"n{i}", labels={"zone": f"z{i % 2}"},
                allocatable={"cpu": "8", "memory": "16Gi"}))
        term = AffinityTerm({"role": "db"}, HOSTNAME_KEY)
        for j in range(3):
            store.add_pod_group(PodGroup(name=f"j{j}", min_member=2))
            for i in range(2):
                store.add_pod(Pod(
                    name=f"db{j}-{i}", uid=f"db{j}-{i}",
                    creation_timestamp=float(1 + 10 * j + i),
                    labels={"role": "db"}, anti_affinity=[term],
                    containers=[{"cpu": "2", "memory": "1Gi"}],
                    annotations={GROUP_NAME_ANNOTATION: f"j{j}"}))
                store.add_pod(Pod(
                    name=f"web{j}-{i}", uid=f"web{j}-{i}",
                    creation_timestamp=float(5 + 10 * j + i),
                    containers=[{"cpu": "3", "memory": "2Gi"}],
                    node_selector={"zone": "z1"},
                    annotations={GROUP_NAME_ANNOTATION: f"j{j}"}))
        m = store.mirror
        assert len(m.s_feat) == 2 and store._between.specs_encoded == 2
        a, b = m.p_row["db0-0"], m.p_row["db2-1"]
        assert m.p_feat[a] is m.p_feat[b] and m.p_job[a] != m.p_job[b]
        assert m.p_has_ip[a] and m.p_spec[a] == m.p_spec[b]
        os.environ["VOLCANO_TPU_FASTPATH"] = "1" if fast else "0"
        try:
            Scheduler(store, conf_str=conf).run_once()
        finally:
            os.environ.pop("VOLCANO_TPU_FASTPATH", None)
        return dict(store.binder.binds)

    slow, fast = run(False), run(True)
    assert fast == slow and len(fast) == 12
    assert len({fast[f"default/db{j}-{i}"]
                for j in range(3) for i in range(2)}) == 6
