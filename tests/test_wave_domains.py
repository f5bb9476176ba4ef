"""Exactness guards for the wave solver's domain machinery.

Round-4 rewrote the per-attempt count lookup as an MXU matmul against a
domain-membership one-hot and added wave-disjoint term detection that
skips the global count write-back.  Both are claimed EXACT; these tests
pin that claim:

- matmul path vs gather path produce identical placements
  (``DOM_MM_MAX_MB`` forced to 0 switches back to the gather);
- multi-wave solves with terms SHARED across waves (disjoint detection
  off) still agree with the single-wave solve;
- the sub-round filter's tightened gate changes nothing observable.

jax caches compiled programs per (shape, static args), so each variant
clears the jit caches after monkeypatching the module constants.
"""

import jax
import numpy as np
import pytest

import volcano_tpu.ops.wave as wave_mod
from volcano_tpu.api import GROUP_NAME_ANNOTATION
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster


def affinity_store(seed=0, n_nodes=24, n_pods=96):
    return synthetic_cluster(
        n_nodes=n_nodes, n_pods=n_pods, gang_size=4, zones=3,
        affinity_fraction=0.25, anti_affinity_fraction=0.15,
        spread_fraction=0.15, seed=seed,
    )


def placements(store):
    return {f"{p.namespace}/{p.name}": p.node_name
            for p in store.pods.values()}


def solve(store):
    Scheduler(store).run_once()
    return placements(store)


def test_dom_matmul_matches_gather_path(monkeypatch):
    """cnt @ dom_oh must equal the per-element gather bit-for-bit in
    every consumed form (feasibility classification + soft score →
    identical placements)."""
    base = solve(affinity_store(seed=7))
    assert any(v for v in base.values())
    monkeypatch.setattr(wave_mod, "DOM_MM_MAX_MB", 0)  # force gather
    jax.clear_caches()
    try:
        gather = solve(affinity_store(seed=7))
    finally:
        jax.clear_caches()
    assert base == gather


def test_multiwave_shared_terms_match_single_wave(monkeypatch):
    """Multi-wave solves where gangs STRADDLE wave boundaries (gang 5
    over wave 24), so their terms appear in several waves: the disjoint
    detection must turn OFF and the cross-wave count flow must place
    the same task count as the single-wave solve.  Drives solve_wave
    directly with an explicit wave= (the scheduler always uses the
    default wave size; monkeypatching the module constant cannot reach
    the def-time default)."""
    from volcano_tpu.synth import solve_args_from_store

    def term_store():
        return synthetic_cluster(
            n_nodes=24, n_pods=120, gang_size=5, zones=3,
            affinity_fraction=0.3, anti_affinity_fraction=0.2,
            spread_fraction=0.1, seed=11,
        )

    args, _ = solve_args_from_store(term_store())
    single = np.asarray(wave_mod.solve_wave(*args).assigned)

    seen_flags = []
    orig = wave_mod._term_windows

    def spy(*a, **k):
        out = orig(*a, **k)
        seen_flags.append(out[2])
        return out

    monkeypatch.setattr(wave_mod, "_term_windows", spy)
    args2, _ = solve_args_from_store(term_store())
    multi = np.asarray(wave_mod.solve_wave(*args2, wave=24).assigned)

    assert seen_flags and seen_flags[-1] is False, (
        f"gangs of 5 straddling wave-24 boundaries must defeat the "
        f"disjoint detection: {seen_flags}"
    )
    # Cross-shard/cross-wave reduction order may flip score near-ties;
    # placement COUNT parity plus per-solve validity are the invariants.
    assert int((multi >= 0).sum()) == int((single >= 0).sum())
    # Capacity validity: charged requests never exceed allocatable.
    tasks = args2[1]
    nodes = args2[0]
    req = np.asarray(tasks.req)
    alloc = np.asarray(nodes.allocatable)
    used = np.zeros_like(alloc)
    placed = np.flatnonzero(multi[:len(req)] >= 0)
    np.add.at(used, multi[placed], req[placed])
    assert not (used > alloc + 1e-3).any()


def test_forced_nondisjoint_write_back_roundtrip(monkeypatch):
    """Explicitly force the non-disjoint (write-back) compile path on a
    normal store and assert placements match the disjoint path — the
    write-back must be a semantic no-op when terms don't actually
    cross waves."""
    base = solve(affinity_store(seed=13))
    orig = wave_mod._term_windows

    def force_nondisjoint(*a, **k):
        out = orig(*a, **k)
        return (*out[:2], False)

    monkeypatch.setattr(wave_mod, "_term_windows", force_nondisjoint)
    jax.clear_caches()
    try:
        forced = solve(affinity_store(seed=13))
    finally:
        jax.clear_caches()
    assert base == forced


def test_conflict_compaction_overflow_parity(monkeypatch):
    """More than GCAP (256) anti-affinity givers in one wave force the
    full-scatter/full-gather fallback branches: placements must match
    the object path exactly either way."""
    from volcano_tpu.api import AffinityTerm, Node, Pod, PodGroup
    from volcano_tpu.cache import ClusterStore

    # Env guard: the overflow precondition (300 givers in ONE wave,
    # > GCAP = min(256, W)) requires the default wave size; a smaller
    # one would make this test silently cover only the compact branch.
    assert wave_mod.DEFAULT_WAVE >= 300, wave_mod.DEFAULT_WAVE

    def build():
        s = ClusterStore()
        for i in range(40):
            s.add_node(Node(name=f"n{i:02d}",
                            allocatable={"cpu": "64", "memory": "128Gi",
                                         "pods": 256}))
        # 300 single-pod anti-affinity jobs sharing ONE app label: every
        # pod is simultaneously a giver and an anti requirer of the same
        # term, so the sub-round conflict machinery sees ~300 giver rows
        # (> GCAP) while capacity forces multi-attempt resolution.
        for j in range(300):
            pg = PodGroup(name=f"anti-{j:03d}", min_member=1)
            s.add_pod_group(pg)
            s.add_pod(Pod(
                name=f"anti-{j:03d}-0",
                labels={"app": "shared"},
                annotations={GROUP_NAME_ANNOTATION: pg.name},
                containers=[{"cpu": "1", "memory": "1Gi"}],
                anti_affinity=[AffinityTerm(
                    match_labels={"app": "shared"},
                    topology_key="kubernetes.io/hostname",
                )],
            ))
        return s

    res = {}
    for mode, env in (("fast", "1"), ("object", "0")):
        monkeypatch.setenv("VOLCANO_TPU_FASTPATH", env)
        store = build()
        Scheduler(store).run_once()
        res[mode] = placements(store)
    # Anti-affinity against a shared label: at most one pod per node,
    # 40 nodes -> exactly 40 placed, and the full PLACEMENTS agree.
    assert res["fast"] == res["object"]
    placed = [v for v in res["fast"].values() if v]
    assert len(placed) == 40
    assert len(set(placed)) == len(placed)  # one per node


def test_count_update_overflow_parity(monkeypatch):
    """More than GCAP (256) ACCEPTED matching tasks in one sub-round
    force the count-update full-scatter fallback (soft spread terms:
    every pod matches its job's term and places immediately on roomy
    nodes).  Placements and scores must match the object path."""
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup
    from volcano_tpu.cache import ClusterStore

    assert wave_mod.DEFAULT_WAVE >= 300, wave_mod.DEFAULT_WAVE

    def build():
        s = ClusterStore()
        for i in range(8):
            s.add_node(Node(
                name=f"n{i}",
                allocatable={"cpu": "64", "memory": "128Gi",
                             "pods": 256},
                topology={"zone": f"z{i % 4}"},
            ))
        # One shared spread job of 300 pods: every pod matches the
        # job's soft term, capacity accepts all in the first waves.
        pg = PodGroup(name="spread", min_member=300)
        s.add_pod_group(pg)
        for j in range(300):
            s.add_pod(Pod(
                name=f"spread-{j:03d}",
                labels={"app": "spread"},
                annotations={GROUP_NAME_ANNOTATION: pg.name},
                containers=[{"cpu": "1", "memory": "1Gi"}],
                topology_spread=[("zone", 10)],
            ))
        return s

    res = {}
    for mode, env in (("fast", "1"), ("object", "0")):
        monkeypatch.setenv("VOLCANO_TPU_FASTPATH", env)
        store = build()
        Scheduler(store).run_once()
        res[mode] = placements(store)
    assert all(v for v in res["fast"].values())
    assert res["fast"] == res["object"]
