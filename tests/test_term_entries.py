"""The inter-pod term data crosses from the fast cycle's encode to
``solve_wave`` as entries (``ops/wave.SparseProfiles``,
``arrays/affinity.CountEntries``), never as dense ``[U, Ep]`` / ``[Ep, D]``
host tables: what the cycle's record says of the hand-off
(``solve.aff_prof_entries``, ``aff_cnt0_entries``, ``aff_host_dense_bytes``)
against counts made here pod by pod, the encode cache's hit path, and a
cycle without terms.  Everything through ``ClusterStore`` ->
``Scheduler.run_once()`` but the hit path, which reads the encode itself."""

import copy

import numpy as np
import pytest

import volcano_tpu.ops.wave as wave
from benchmark import run as bench_run
from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    AffinityTerm,
    Node,
    Pod,
    PodGroup,
)
from volcano_tpu.arrays.affinity import CountEntries
from volcano_tpu.cache import ClusterStore
from volcano_tpu.cache.interface import FakeBinder
from volcano_tpu.fastpath import FastCycle
from volcano_tpu.framework import parse_scheduler_conf
from volcano_tpu.scheduler import Scheduler

CONF = ("actions: enqueue, allocate\ntiers:\n- plugins:\n  - name: gang\n"
        "  - name: predicates\n  - name: nodeorder\n")
HOSTNAME = "kubernetes.io/hostname"
ZONES, PER_ZONE = 4, 4


def cluster():
    store = ClusterStore(binder=FakeBinder())
    for z in range(ZONES):
        for i in range(PER_ZONE):
            store.add_node(Node(
                name=f"z{z}-n{i}",
                allocatable={"cpu": "16", "memory": "64Gi", "pods": 64},
                labels={"zone": f"z{z}"}))
    return store


def gang(store, name, n, labels, node=None, phase=None, **terms):
    pg = PodGroup(name=name, min_member=n)
    if phase:
        pg.status.phase = phase
    store.add_pod_group(pg)
    pods = [Pod(name=f"{name}-{k}", labels=labels,
                containers=[{"cpu": "1", "memory": "1Gi"}],
                annotations={GROUP_NAME_ANNOTATION: name},
                **({"node_name": node, "phase": "Running"} if node else {}),
                **terms) for k in range(n)]
    for pod in pods:
        store.add_pod(pod)
    return pods


def submit(store, tag):
    """One round's traffic: a zone-affine gang, a host-anti-affine gang, a
    zone-spread gang and two plain ones.  Returns the pending pods."""
    db = AffinityTerm(match_labels={"app": "db"}, topology_key="zone")
    lonely = AffinityTerm(match_labels={"app": "lonely"}, topology_key=HOSTNAME)
    return (gang(store, f"aff{tag}", 3, {"app": "db"}, affinity=[db])
            + gang(store, f"anti{tag}", 4, {"app": "lonely"},
                   anti_affinity=[lonely])
            + gang(store, f"spread{tag}", 4, {"app": "web"},
                   topology_spread=[("zone", 10)])
            + gang(store, f"plain{tag}a", 4, {"app": "a"})
            + gang(store, f"plain{tag}b", 4, {"app": "b"}))


def counted_by_hand(pending, standing):
    """(profile-term entries, count entries) of a round, pod by pod:
    one entry per (distinct pod shape, term it names or matches), and one
    per (term, domain in which a standing pod matches it).  ``standing``
    is ``[(pod, node name)]``."""
    def job(pod):
        return pod.annotations[GROUP_NAME_ANNOTATION]

    terms = {}                                  # term -> matches(pod)
    for pod in pending:
        for t in pod.affinity + pod.anti_affinity:
            sel = dict(t.match_labels)
            terms[("sel", tuple(sorted(sel.items())), t.topology_key)] = (
                lambda p, sel=sel: all(p.labels.get(k) == v
                                       for k, v in sel.items()))
        for key, _w in pod.topology_spread:
            terms[("job", job(pod), key)] = (
                lambda p, j=job(pod): job(p) == j)
    shapes = {(tuple(sorted(p.labels.items())), job(p)
               if p.topology_spread else "", bool(p.affinity),
               bool(p.anti_affinity)): p for p in pending}
    prof = sum(1 for p in shapes.values()
               for matches in terms.values() if matches(p))
    cells = set()
    for term, matches in terms.items():
        for pod, node in standing:
            if matches(pod):
                cells.add((term, node if term[2] == HOSTNAME
                           else node.split("-")[0]))
    return prof, len(cells)


@pytest.mark.parametrize("thresholds", ["device-scatter", "dense-upload"])
def test_a_cycle_with_terms_hands_entries_over(monkeypatch, thresholds):
    """Two rounds of term-carrying traffic over standing pods that match
    their terms: the record's entry counts are the ones made by hand, no
    dense host table is built where the tables are born on the device
    (thresholds lowered; a small table densified for upload is counted),
    and the second round lowers no program and moves no shape bucket."""
    if thresholds == "device-scatter":
        monkeypatch.setattr(wave, "PROF_SPARSE_MIN", 0)
        monkeypatch.setattr(wave, "CNT0_SPARSE_MIN", 0)
    store = cluster()
    standing = [(p, "z1-n0") for p in gang(
        store, "res-db", 2, {"app": "db"}, node="z1-n0", phase="Running")]
    standing += [(p, "z0-n1") for p in gang(
        store, "res-db2", 1, {"app": "db"}, node="z0-n1", phase="Running")]
    standing += [(p, "z2-n3") for p in gang(
        store, "res-lonely", 1, {"app": "lonely"}, node="z2-n3",
        phase="Running")]
    sched = Scheduler(store, conf_str=CONF)
    heard = bench_run.Compiles()       # names of the programs JAX lowers
    try:
        marks = lowered = None
        for tag in ("r0", "r1"):
            pending = submit(store, tag)
            want_prof, want_cnt = counted_by_hand(pending, standing)
            assert (want_prof, want_cnt) == (3, 3 if tag == "r0" else 7)
            sched.run_once()
            store.flush_binds()
            rec = store.flight.recent()[-1]
            assert rec.path == "fast"
            binds = store.binder.binds
            assert all(f"default/{p.name}" in binds for p in pending)
            solve = rec.solve
            assert solve["aff_terms"] == 3
            assert solve["aff_prof_entries"] == want_prof
            assert solve["aff_cnt0_entries"] == want_cnt
            if thresholds == "device-scatter":
                assert solve["aff_host_dense_bytes"] == 0
            else:
                # [64, Ep + 1] x (3 bool + f32) and [Ep + 1, D] int32
                Ep, D = solve["aff_terms_padded"], solve["aff_domains"]
                assert solve["aff_host_dense_bytes"] == (
                    64 * (Ep + 1) * 7 + (Ep + 1) * D * 4)
            if tag == "r0":
                marks = dict(store._solve_shape_marks)
                lowered = len(heard.names)
            standing += [(p, binds[f"default/{p.name}"]) for p in pending]
        assert store._solve_shape_marks == marks
        assert heard.names[lowered:] == []
    finally:
        store.close()


def test_a_cycle_without_terms_counts_none():
    store = cluster()
    gang(store, "plain", 4, {"app": "a"})
    Scheduler(store, conf_str=CONF).run_once()
    store.flush_binds()
    assert len(store.binder.binds) == 4
    solve = store.flight.recent()[-1].solve
    for k in ("aff_prof_entries", "aff_cnt0_entries", "aff_host_dense_bytes",
              "aff_terms"):
        assert not solve.get(k), k
    store.close()


def test_the_encode_caches_hit_path_counts_a_new_resident():
    """Same pending rows, one matching pod now standing on a node: the
    cached encode is reused (no new profile generation) and only the
    count entries are rebuilt, with the resident's."""
    store = cluster()
    held = gang(store, "held", 1, {"app": "db"}, phase="Pending")[0]
    db = AffinityTerm(match_labels={"app": "db"}, topology_key="zone")
    gang(store, "aff", 3, {"app": "db"}, phase="Inqueue", affinity=[db])
    conf = parse_scheduler_conf(CONF)

    def encode():
        cyc = FastCycle(store, conf)
        with store._lock:
            cyc.derive()
            cyc._proportion()
            jobs, rows = cyc._pending_rows(cyc._ordered_jobs())
            inputs, _pid, profiles, _ncls = cyc._solve_inputs(
                jobs, rows, slim=True)
        return rows, inputs[7].cnt0, profiles, store._encode_cache["gen"]

    rows0, cnt_a, prof_a, gen_a = encode()
    assert isinstance(cnt_a, CountEntries) and len(cnt_a.rows) == 0
    assert isinstance(prof_a, wave.SparseProfiles)
    bound = copy.copy(held)                 # the bind's copy-on-write
    bound.node_name, bound.phase = "z1-n0", "Running"
    store.add_pod(bound)
    rows1, cnt_b, prof_b, gen_b = encode()
    assert np.array_equal(rows0, rows1) and gen_b == gen_a
    assert prof_b is prof_a
    zone = store.mirror.node_dom()[store.mirror.n_row["z1-n0"], 0]
    assert (cnt_b.rows.tolist(), cnt_b.cols.tolist(), cnt_b.vals.tolist()) \
        == ([0], [int(zone)], [1])
    assert cnt_b.shape == cnt_a.shape
    store.close()
