"""Two-phase device solve (ISSUE 4): node-class compaction +
per-profile top-K shortlists.

Pins what the hierarchical solve must guarantee against the full-``N``
single-phase solve it replaces:

- bind-for-bind parity on fixed seeds at configs-2/3/5-like shapes with
  the shortlist genuinely restrictive (K << N), including the affinity
  mix and a gang that can only bind through the fallback rescore;
- capacity + gang atomicity under shortlist exhaustion;
- fallback counters exported per reason and consistent with the binds;
- the compacted fine-phase planes really are [U, K] with K << N;
- devsnap class-plane delta correctness after node mutations.

All tier-1, JAX_PLATFORMS=cpu.
"""

import numpy as np
import pytest

import volcano_tpu.ops.wave as wave
from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.metrics import metrics
from volcano_tpu.synth import solve_args_from_store, synthetic_cluster

pytestmark = pytest.mark.tier1


def _pin(monkeypatch, k, twophase):
    """Pin the shortlist length AND the walk ranking depth to ``k`` for
    BOTH modes (the module-level TOPK is read at import, the shortlist
    length per call), so parity compares identical walk depths."""
    monkeypatch.setenv("VOLCANO_TPU_TOPK", str(k))
    monkeypatch.setattr(wave, "TOPK", k)
    monkeypatch.setenv("VOLCANO_TPU_TWOPHASE", "1" if twophase else "0")


def _solve(store, wave_sz=64):
    args, _ = solve_args_from_store(store)
    res = wave.solve_wave(*args, wave=wave_sz)
    return args, res


def _assigned(res):
    return np.asarray(res.assigned)


def _fb(res):
    return (int(np.asarray(res.fb_exhausted)),
            int(np.asarray(res.fb_affinity)))


def _check_invariants(args, res):
    nodes, tasks, jobs = args[0], args[1], args[2]
    assigned = _assigned(res)
    idle0 = np.asarray(nodes.idle)
    req = np.asarray(tasks.req)
    use = np.zeros_like(idle0)
    for i, n in enumerate(assigned):
        if n >= 0:
            use[n] += req[i]
    assert (use <= idle0 + 1e-3).all(), "node oversubscription"
    job = np.asarray(tasks.job)
    real = np.asarray(tasks.real)
    minav = np.asarray(jobs.min_available)
    rb = np.asarray(jobs.ready_base)
    counts = {}
    for i in range(len(assigned)):
        if real[i] and assigned[i] >= 0:
            counts[job[i]] = counts.get(job[i], 0) + 1
    for j, c in counts.items():
        assert rb[j] + c >= minav[j], "gang atomicity violated"
    never = np.asarray(res.never_ready)
    for i in range(len(assigned)):
        if real[i] and never[job[i]]:
            assert assigned[i] == -1, "discarded job left an allocation"


# --------------------------------------------------------------- parity


PARITY_SHAPES = [
    # config-2-like: binpack+predicates, single-queue-ish
    ("cfg2", 12, dict(n_nodes=48, n_pods=160, gang_size=4, n_queues=2,
                      seed=3)),
    # config-3-like: weighted multi-queue DRF mix
    ("cfg3", 16, dict(n_nodes=48, n_pods=128, n_queues=4,
                      queue_weights=(1, 2, 4, 8),
                      gang_sizes=(2, 4, 8, 16), seed=5)),
    # config-5-like: inter-pod affinity / anti-affinity / spread mix
    ("cfg5", 16, dict(n_nodes=32, n_pods=96, gang_size=4, zones=4,
                      affinity_fraction=0.2, anti_affinity_fraction=0.1,
                      spread_fraction=0.2, seed=3)),
]


@pytest.mark.parametrize("name,k,shape",
                         PARITY_SHAPES, ids=[s[0] for s in PARITY_SHAPES])
def test_twophase_bind_for_bind_parity(monkeypatch, name, k, shape):
    """Fixed-seed parity: with the shortlist restricted to K << N, the
    two-phase solve binds the same pods to the same nodes as the full
    solve (same walk depth in both modes)."""
    _pin(monkeypatch, k, twophase=False)
    _, full = _solve(synthetic_cluster(**shape))
    _pin(monkeypatch, k, twophase=True)
    args, two = _solve(synthetic_cluster(**shape))
    assert wave.LAST_TWOPHASE["enabled"]
    assert np.array_equal(_assigned(full), _assigned(two))
    _check_invariants(args, two)
    # Fallback counters always export (zeros allowed on shapes where
    # nothing exhausts).
    ex, aff = _fb(two)
    assert ex >= 0 and aff >= 0


def test_twophase_shortlist_planes_are_compacted(monkeypatch):
    """The fine-phase candidate planes are [U, K] with K << N."""
    _pin(monkeypatch, 8, twophase=True)
    store = synthetic_cluster(n_nodes=64, n_pods=128, gang_size=4, seed=1)
    _, res = _solve(store)
    info = wave.LAST_TWOPHASE
    assert info["enabled"] and info["compacted_classes"]
    u_rows, s = info["shortlist"]
    n = info["n_nodes"]
    assert s == 8 and n == 64 and s < n // 4
    assert u_rows >= 1
    assert (_assigned(res) >= 0).sum() == 128


def _fallback_cluster():
    """12 identical nodes; job A's 8 single-node-sized pods saturate the
    shortlist prefix (identical nodes rank by index), so job B's gang of
    4 can only bind through the full-N fallback rescore."""
    store = ClusterStore()
    for i in range(12):
        store.add_node(Node(
            name=f"n{i:02d}", allocatable={"cpu": "4", "memory": "8Gi"}
        ))
    store.add_pod_group(PodGroup(name="filler", min_member=8))
    for r in range(8):
        store.add_pod(Pod(
            name=f"filler-{r}",
            annotations={GROUP_NAME_ANNOTATION: "filler"},
            containers=[{"cpu": "4", "memory": "8Gi"}],
        ))
    store.add_pod_group(PodGroup(name="gang", min_member=4))
    for r in range(4):
        store.add_pod(Pod(
            name=f"gang-{r}",
            annotations={GROUP_NAME_ANNOTATION: "gang"},
            containers=[{"cpu": "3", "memory": "6Gi"}],
        ))
    return store


def test_twophase_gang_binds_only_via_fallback(monkeypatch):
    """A gang whose shortlist is fully claimed by earlier waves still
    binds (fallback full-N rescore), bind-for-bind equal to the full
    solve, with the exhaustion counted and exported."""
    _pin(monkeypatch, 4, twophase=False)
    _, full = _solve(_fallback_cluster(), wave_sz=16)
    _pin(monkeypatch, 4, twophase=True)
    args, two = _solve(_fallback_cluster(), wave_sz=16)
    assert np.array_equal(_assigned(full), _assigned(two))
    assert (_assigned(two) >= 0).sum() == 12  # all 12 pods bound
    ex, aff = _fb(two)
    assert ex > 0, "shortlist exhaustion must be counted"
    assert aff == 0
    _check_invariants(args, two)


def test_twophase_exhaustion_keeps_capacity_and_gang_atomicity(
        monkeypatch):
    """Overcommitted cluster + tiny shortlist: whatever binds must still
    respect capacity and gang atomicity, and unbindable gangs discard
    cleanly (capacity restored)."""
    _pin(monkeypatch, 4, twophase=True)
    store = synthetic_cluster(n_nodes=24, n_pods=256, gang_size=8,
                              n_queues=2, seed=11)
    args, res = _solve(store)
    _check_invariants(args, res)
    # Parity of *placement count* with the full solve under the same
    # pressure (identical walk depth).
    _pin(monkeypatch, 4, twophase=False)
    _, full = _solve(synthetic_cluster(n_nodes=24, n_pods=256,
                                       gang_size=8, n_queues=2, seed=11))
    assert (_assigned(res) >= 0).sum() == (_assigned(full) >= 0).sum()


def test_fallback_cap_limits_rescores(monkeypatch):
    """VOLCANO_TPU_FB_CAP bounds the fallback rescore ROUNDS; past the
    cap exhausted profiles stay Pending (the sampling-cutoff
    semantics) — and the cap never breaks capacity/gang invariants."""
    _pin(monkeypatch, 4, twophase=True)
    monkeypatch.setenv("VOLCANO_TPU_FB_CAP", "0")
    _, uncapped = _solve(_fallback_cluster(), wave_sz=16)
    monkeypatch.setenv("VOLCANO_TPU_FB_CAP", "1")
    args, res = _solve(_fallback_cluster(), wave_sz=16)
    ex, aff = _fb(res)
    ex_unc, _aff_unc = _fb(uncapped)
    # One round fired (both profiles of that attempt rescored), later
    # exhaustions were refused: fewer rescored profiles than uncapped,
    # and the gang that needed a later round stays Pending.
    assert 0 < ex + aff < ex_unc
    assert (_assigned(res) >= 0).sum() < (_assigned(uncapped) >= 0).sum()
    _check_invariants(args, res)


# ------------------------------------------------- metrics + scheduler


def test_fallback_counter_exported_via_scheduler(monkeypatch):
    """Driving the full fast path: the per-reason counter series and the
    cycle's stats pick up the kernel's fallback counts."""
    from volcano_tpu.fastpath import FastCycle
    from volcano_tpu.scheduler import Scheduler

    _pin(monkeypatch, 4, twophase=True)
    cycles = {}
    count = FastCycle._count_shortlist_fb

    def spy(self, exhausted, affinity):
        count(self, exhausted, affinity)
        cycles[id(self)] = self

    monkeypatch.setattr(FastCycle, "_count_shortlist_fb", spy)

    def series_total():
        data = metrics.solve_shortlist_fallback.data
        return sum(data.values())

    before = series_total()
    store = _fallback_cluster()
    Scheduler(store).run_once()
    store.flush_binds()
    assert all(p.node_name for p in store.pods.values())
    delta = series_total() - before
    assert delta > 0
    assert sum(c.stats["shortlist_fallbacks"]
               for c in cycles.values()) == delta


# --------------------------------------------- devsnap class planes


def test_devsnap_class_planes_delta_after_node_mutation(monkeypatch):
    """Node mutations between cycles: a label change that alters the
    class SET re-uploads the class_id plane + tables but keeps the
    other node planes on the delta path, and the post-mutation solve
    matches a fresh store with the same final state bind-for-bind."""
    from volcano_tpu.scheduler import Scheduler

    _pin(monkeypatch, 8, twophase=True)
    store = synthetic_cluster(n_nodes=8, n_pods=16, gang_size=2, seed=17)
    sched = Scheduler(store)
    sched.run_once()
    snap = store.device_snapshot
    assert snap.class_uploads >= 1
    full_before = snap.full_uploads
    cls_uploads_before = snap.class_uploads

    # Mutate one node's labels -> new class signature set.
    store.add_node(Node(
        name="node-000000",
        allocatable={"cpu": "64", "memory": "256Gi", "pods": 256},
        labels={"pool": "relabelled"},
    ))
    store.add_pod_group(PodGroup(name="late", min_member=1))
    store.add_pod(Pod(
        name="late-0",
        annotations={GROUP_NAME_ANNOTATION: "late"},
        node_selector={"pool": "relabelled"},
        containers=[{"cpu": "1", "memory": "1Gi"}],
    ))
    sched.run_once()
    store.flush_binds()
    # The class tables re-uploaded (new signature set), the node planes
    # did NOT take the full path (label delta scatters still apply).
    assert snap.class_uploads > cls_uploads_before
    assert snap.full_uploads == full_before
    assert snap.delta_uploads >= 1
    # The selector-pinned pod landed on the relabelled node: the
    # device-resident class planes really reflect the mutation.
    late = [p for p in store.pods.values() if p.name == "late-0"]
    assert late and late[0].node_name == "node-000000"