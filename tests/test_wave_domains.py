"""Exactness guards for the wave solver's domain machinery.

The ``has_aff`` solve reads its count window by ``count_plane`` (K row
gathers from the window laid domain-major) and detects wave-disjoint
terms to skip the global count write-back.  Both are claimed EXACT;
these tests pin that claim:

- ``count_plane`` gives the integers of the element gather it replaced,
  kept here as the reference;
- a solve with terms reaches ``count_plane`` from all three of its
  callers, with the shortlists on and off, and places what the same
  solve places with the element gather in its stead; the two overflow
  parities hold the fast path to the object path;
- multi-wave solves with terms SHARED across waves (disjoint detection
  off) still agree with the single-wave solve;
- the sub-round filter's tightened gate changes nothing observable.

jax caches compiled programs per (shape, static args), so each variant
clears the jit caches after monkeypatching a module attribute.
"""

import jax
import jax.numpy as jnp
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


def _element_gather(cnt, node_dom, term_key):
    """The expression ``count_plane`` replaced (``ops/allocate.py``'s scan
    loop still reads its counts so): one scalar read a (row, term)."""
    nd_t = jnp.take(node_dom, term_key, axis=1)  # [R, E]
    cv = cnt[jnp.arange(cnt.shape[0])[None, :], jnp.maximum(nd_t, 0)]
    return jnp.where(nd_t >= 0, cv, 0)


# K keys, R rows, E terms, D domains; what of the input is special.
PLANE_CASES = {
    "one-key": dict(K=1, R=40, E=16, D=256),
    "two-keys": dict(K=2, R=64, E=128, D=384),
    "three-keys": dict(K=3, R=48, E=24, D=200),
    "no-domain-under-one-key": dict(K=2, R=32, E=16, D=128, missing="one"),
    "no-domain-under-any-key": dict(K=3, R=32, E=16, D=128, missing="all"),
    "all-dummy-window": dict(K=2, R=32, E=16, D=128, dummy=True),
    "terms-not-a-multiple-of-8": dict(K=2, R=32, E=13, D=128),
    "domains-not-a-multiple-of-128": dict(K=2, R=32, E=16, D=131),
    "rows-a-permutation-of-the-nodes": dict(K=2, R=64, E=16, D=96, perm=True),
    "hyper-50k-chip-shape-narrowed": dict(K=2, R=512, E=128, D=1579),
    # affinity-10k's window at an eighth of its 10,016 domains
    "affinity-10k-shape-narrowed": dict(K=2, R=512, E=128, D=1252),
    # the one-pod probe cycle's window
    "one-row": dict(K=2, R=1, E=16, D=128),
}


@pytest.mark.parametrize("case", list(PLANE_CASES))
def test_count_plane_reads_what_the_element_gather_read(case):
    """``count_plane`` against the element gather, bit for bit, on counts
    that are NOT zero outside a term's own key's domains (the row form
    does not lean on that), jitted and not."""
    c = PLANE_CASES[case]
    K, R, E, D = c["K"], c["R"], c["E"], c["D"]
    rng = np.random.default_rng(sum(map(ord, case)))
    node_dom = rng.integers(0, D, (R, K)).astype(np.int32)
    node_dom[:, 0] = D - 1 - node_dom[:, 0] % 7      # the last domains too
    if c.get("missing") == "one":
        node_dom[rng.random(R) < 0.4, rng.integers(0, K)] = -1
    elif c.get("missing") == "all":
        node_dom[rng.random(R) < 0.4] = -1
        node_dom[0], node_dom[-1] = -1, -1
    term_key = rng.integers(0, K, E).astype(np.int32)
    cnt = rng.integers(0, 9, (E, D)).astype(np.int32)
    if c.get("dummy"):
        # a window of nothing but the dummy scratch row: key 0, no count
        term_key[:] = 0
        cnt[:] = 0
    if c.get("perm"):
        # the conflict filter's rows: node_dom[choice]
        node_dom = node_dom[rng.permutation(R)]
    want = np.asarray(_element_gather(cnt, node_dom, term_key))
    assert want.shape == (R, E) and (c.get("dummy") or want.any())
    for fn in (wave_mod.count_plane, jax.jit(wave_mod.count_plane)):
        got = fn(jnp.asarray(cnt), jnp.asarray(node_dom),
                 jnp.asarray(term_key))
        assert got.dtype == jnp.int32 and got.shape == (R, E)
        np.testing.assert_array_equal(np.asarray(got), want)
    if c.get("missing"):
        assert (want[(node_dom < 0).all(axis=1)] == 0).all()


@pytest.mark.parametrize("twophase", ["1", "0"])
def test_a_solve_with_terms_reads_its_counts_by_count_plane(
        monkeypatch, twophase):
    """A solve with terms goes through ``count_plane`` from all three of
    its callers (the shortlist attempt, the conflict filter and, with
    the shortlists off, the full-N planes that the fallback rescore
    reads too) and places what the same solve places with the element
    gather in ``count_plane``'s stead: every consumed form of the plane
    (feasibility classification + soft score) is the same."""
    monkeypatch.setenv("VOLCANO_TPU_TWOPHASE", twophase)
    # count_plane is looked up when _solve_wave is traced: the rows it
    # was asked for say which of its callers the trace went through.
    asked = []
    plane = wave_mod.count_plane

    def spy(cnt, node_dom, term_key):
        asked.append(node_dom.shape[0])
        return plane(cnt, node_dom, term_key)

    monkeypatch.setattr(wave_mod, "count_plane", spy)
    jax.clear_caches()
    try:
        rows = solve(affinity_store(seed=7))
        monkeypatch.setattr(wave_mod, "count_plane", _element_gather)
        jax.clear_caches()
        elements = solve(affinity_store(seed=7))
    finally:
        jax.clear_caches()
    assert any(v for v in rows.values()) and rows == elements
    # every node's plane (the attempt's, and the fallback rescore's where
    # there are shortlists) and the conflict filter's W chosen rows
    N, W = min(asked), max(asked)
    assert asked.count(N) == (2 if twophase == "1" else 1), asked
    assert asked.count(W) == 1 and W > N, asked


@pytest.mark.parametrize("terms", [True, False])
def test_the_record_counts_count_plane_recomputes(terms):
    """``solve.aff_count_reads`` of the cycle's record: above 0 where pods
    carry terms, 0 where none does (that trace has no such counter)."""
    store = affinity_store(seed=7) if terms else synthetic_cluster(
        n_nodes=24, n_pods=96, gang_size=4, zones=3, seed=7)
    Scheduler(store).run_once()
    solve_counts = store.flight.recent()[-1].solve
    assert (solve_counts["aff_terms"] > 0) == terms
    assert (solve_counts["aff_count_reads"] > 0) == terms
    assert solve_counts["aff_count_reads"] >= 0


def test_the_record_reckons_the_count_pair_alone():
    """``solve.aff_device_bytes`` on one device: the two ``[Ep + 1, D]``
    int32 count tensors and nothing else, with no chip's share beside."""
    store = affinity_store(seed=7)
    Scheduler(store).run_once()
    s = store.flight.recent()[-1].solve
    assert s["aff_terms_padded"] >= s["aff_terms"] > 0 and s["aff_domains"] > 0
    assert s["aff_device_bytes"] \
        == 2 * (s["aff_terms_padded"] + 1) * s["aff_domains"] * 4
    assert "aff_device_bytes_chip" not in s


def test_no_name_selects_another_form_of_the_count_read():
    """The domain one-hot and its size gate went with PR 48; neither the
    kernel nor the cycle's account of it names them again, so the fork
    cannot come back as a knob unnoticed."""
    import ast
    from pathlib import Path

    gone = {"dom_mm_on", "DOM_MM_MAX_MB"}
    pkg = Path(wave_mod.__file__).parents[1]
    for rel, stays in (("ops/wave.py", "count_plane"),
                       ("fastpath.py", "_count_affinity")):
        names = set()
        for node in ast.walk(ast.parse((pkg / rel).read_text())):
            for field in ("id", "attr", "name", "asname"):
                if isinstance(getattr(node, field, None), str):
                    names.add(getattr(node, field))
        assert stays in names and not gone & names, (rel, gone & names)


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
