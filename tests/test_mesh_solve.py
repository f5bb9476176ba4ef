"""Mesh-native sharded wave solve (ISSUE 7): shard-local two-phase +
sharded devsnap deltas + pipelined mesh cycles.

What the mesh path must now guarantee on the virtual CPU mesh
(``xla_force_host_platform_device_count``, conftest — the same
decomposition runs unchanged on a real multi-chip TPU slice):

- the shard-local ranking + winner reduction (``ops.wave._topk_nodes``)
  is EXACTLY ``jax.lax.top_k`` including ties;
- the sharded solve is bind-for-bind identical to the single-device
  solve at fixed seeds, including shortlist-fallback and gang-atomicity
  cases (deterministic tie-breaks make this exact, not approximate);
- node churn under a mesh re-ships only dirty rows into the sharded
  devsnap planes (delta scatter), never the full plane set;
- pipelined dispatch works with ``store.solve_mesh`` set, and the
  staleness guard still drops rows invalidated during the overlap.

All tier-1, JAX_PLATFORMS=cpu.
"""

import numpy as np
import pytest

import jax

import volcano_tpu.ops.wave as wave
from volcano_tpu.api import (
    GROUP_NAME_ANNOTATION,
    Node,
    Pod,
    PodGroup,
)
from volcano_tpu.cache import ClusterStore
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import solve_args_from_store, synthetic_cluster

pytestmark = pytest.mark.tier1

needs_4 = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 virtual devices"
)


def _mesh(n=4):
    from volcano_tpu.parallel import make_mesh

    return make_mesh(n)


# ------------------------------------------------------- winner reduction


def test_topk_nodes_matches_global_topk():
    """The two-stage shard-local selection (per-shard top-k, then the
    (score, global node id) winner reduction) returns exactly what the
    global top_k returns — membership AND order, ties included."""
    rng = np.random.default_rng(7)
    for u, n, k, sh in [(5, 64, 7, 4), (3, 128, 128, 8), (2, 32, 10, 8),
                        (4, 16, 16, 4), (1, 256, 33, 4)]:
        # Small integer value set => heavy score ties across shards.
        s = rng.integers(0, 4, size=(u, n)).astype(np.float32)
        ref = np.asarray(jax.lax.top_k(s, k)[1])
        got = np.asarray(wave._topk_nodes(s, k, sh))
        assert np.array_equal(ref, got), (u, n, k, sh)
    # Degenerate: everything infeasible (all-NEG plane).
    s = np.full((3, 64), float(np.float32(-1e30)), np.float32)
    assert np.array_equal(
        np.asarray(jax.lax.top_k(s, 9)[1]),
        np.asarray(wave._topk_nodes(s, 9, 4)),
    )
    # Non-divisible node axis falls back to the global form.
    s = rng.normal(size=(2, 30)).astype(np.float32)
    assert np.array_equal(
        np.asarray(jax.lax.top_k(s, 5)[1]),
        np.asarray(wave._topk_nodes(s, 5, 4)),
    )


# --------------------------------------------------- solver-level parity


@needs_4
@pytest.mark.parametrize("shape", [
    dict(n_nodes=64, n_pods=128, gang_size=4, n_queues=2, seed=3),
    dict(n_nodes=32, n_pods=96, gang_size=4, zones=4,
         affinity_fraction=0.2, anti_affinity_fraction=0.1,
         spread_fraction=0.2, seed=5),
], ids=["plain", "affinity"])
def test_mesh_wave_solve_bind_for_bind(shape):
    """The sharded wave solve assigns every task the SAME node as the
    single-device solve (not just the same count): every cross-chip
    reduction is an exact-integer psum or a comparison, and the winner
    reduction carries global node ids for the tie-break."""
    from volcano_tpu.parallel import sharded_solve_wave

    args, _ = solve_args_from_store(synthetic_cluster(**shape))
    single = np.asarray(wave.solve_wave(*args).assigned)
    sharded = np.asarray(sharded_solve_wave(_mesh(4), args).assigned)
    assert np.array_equal(single, sharded)
    assert (single >= 0).any()


def _fallback_cluster():
    """12 identical nodes; the filler job's 8 single-node-sized pods
    saturate the shortlist prefix (identical nodes rank by index), so
    the gang of 4 binds only through the full-N fallback rescore —
    which under a mesh must run shard-local and reduce the same way."""
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


@needs_4
def test_mesh_shortlist_fallback_parity(monkeypatch):
    """Shortlist exhaustion under sharding: the gang that binds only
    via the fallback rescore binds bind-for-bind like the single-device
    two-phase solve, the exhaustion is counted on both paths, and gang
    atomicity holds (all 12 pods bound)."""
    from volcano_tpu.parallel import sharded_solve_wave

    monkeypatch.setenv("VOLCANO_TPU_TOPK", "4")
    monkeypatch.setattr(wave, "TOPK", 4)
    monkeypatch.setenv("VOLCANO_TPU_TWOPHASE", "1")

    args, _ = solve_args_from_store(_fallback_cluster())
    single = wave.solve_wave(*args, wave=16)
    args2, _ = solve_args_from_store(_fallback_cluster())
    sharded = sharded_solve_wave(_mesh(4), args2, wave=16)

    a_single = np.asarray(single.assigned)
    a_mesh = np.asarray(sharded.assigned)
    assert np.array_equal(a_single, a_mesh)
    assert (a_mesh >= 0).sum() == 12  # gang atomic: everything bound
    assert int(np.asarray(sharded.fb_exhausted)) > 0
    assert int(np.asarray(sharded.fb_exhausted)) == int(
        np.asarray(single.fb_exhausted)
    )


# ------------------------------------------------- full-cycle parity


@needs_4
def test_mesh_full_cycle_bind_for_bind(monkeypatch):
    """Complete fastpath cycle on the mesh: every pod binds to the SAME
    node the single-device cycle picks (dict equality of the binder's
    pod -> hostname map), with the affinity mix exercising the sharded
    count tensors."""
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    kw = dict(n_nodes=64, n_pods=128, gang_size=4, zones=4,
              affinity_fraction=0.25, anti_affinity_fraction=0.25,
              spread_fraction=0.25, seed=31)
    single = synthetic_cluster(**kw)
    Scheduler(single).run_once()
    single.flush_binds()

    meshed = synthetic_cluster(**kw)
    meshed.solve_mesh = _mesh(4)
    Scheduler(meshed).run_once()
    meshed.flush_binds()

    assert dict(meshed.binder.binds) == dict(single.binder.binds)
    assert len(meshed.binder.binds) == 128
    single.close()
    meshed.close()


# --------------------------------------------- sharded devsnap deltas


@needs_4
def test_mesh_devsnap_delta_after_node_churn(monkeypatch):
    """Node churn under the mesh re-ships only the dirty rows into the
    mesh-sharded persistent planes (delta scatter on the owning shard),
    NOT the full plane set — the re-upload carve-out the mesh path used
    to force is gone."""
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    store = synthetic_cluster(seed=17, n_nodes=8, n_pods=16, gang_size=2)
    store.solve_mesh = _mesh(4)
    sched = Scheduler(store)
    sched.run_once()

    snap = store.device_snapshot
    assert snap.mesh is store.solve_mesh
    assert snap.full_uploads >= 1
    full_before = snap.full_uploads
    # Every persistent node plane is committed SHARDED on the node axis
    # (each chip holds its shard only).
    from jax.sharding import NamedSharding

    for name, plane in snap._planes.items():
        sh = plane.sharding
        assert isinstance(sh, NamedSharding), name
        assert sh.spec and sh.spec[0] == "nodes", name

    # One-node mutation: epoch bumps, one row dirty.
    store.add_node(Node(
        name="node-000000",
        allocatable={"cpu": "64", "memory": "256Gi", "pods": 256},
        labels={"freshly": "relabelled"},
    ))
    store.add_pod_group(PodGroup(name="late", min_member=1))
    store.add_pod(Pod(
        name="late-0",
        annotations={GROUP_NAME_ANNOTATION: "late"},
        containers=[{"cpu": "1", "memory": "1Gi"}],
    ))
    sched.run_once()
    store.flush_binds()
    assert snap.delta_uploads >= 1, "churn must ride the delta scatter"
    assert snap.full_uploads == full_before, \
        "node churn must not full-re-upload the sharded planes"
    assert all(p.node_name for p in store.pods.values())
    store.close()


# -------------------------------------------------- pipelined mesh


@needs_4
def test_mesh_pipelined_cycle_commits(monkeypatch):
    """Pipelined dispatch with ``solve_mesh`` set: cycle N parks the
    sharded solve as an InflightSolve, cycle N+1 fetches (one
    jax.device_get assembling the mesh result) and commits."""
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    store = synthetic_cluster(seed=23, n_nodes=16, n_pods=32, gang_size=2)
    store.pipeline = True
    store.solve_mesh = _mesh(4)
    sched = Scheduler(store)
    sched.run_once()
    # The solve is parked, not committed: pipelining engaged on the mesh.
    assert store._inflight_solve is not None
    assert store._inflight_solve.kind == "local"
    sched.run_once()
    store.flush_binds()
    assert len(store.binder.binds) == 32
    store.close()


@needs_4
def test_mesh_pipelined_staleness_guard_drops_deleted(monkeypatch):
    """A pod deleted while its sharded solve is in flight must NOT be
    committed: the staleness guard re-validates the mesh result exactly
    like the single-device one."""
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    store = synthetic_cluster(seed=29, n_nodes=16, n_pods=32, gang_size=1)
    store.pipeline = True
    store.solve_mesh = _mesh(4)
    sched = Scheduler(store)
    sched.run_once()
    assert store._inflight_solve is not None

    victim = next(p for p in store.pods.values()
                  if p.node_name is None)
    store.delete_pod(victim)
    sched.run_once()
    sched.run_once()
    store.flush_binds()
    key = f"{victim.namespace}/{victim.name}"
    assert key not in store.binder.binds
    assert len(store.binder.binds) == 31  # everyone else lands
    store.close()


# ------------------------------------------- the asked-for mesh or nothing


@needs_4
def test_mesh_env_builds_the_mesh_once(monkeypatch):
    from volcano_tpu.parallel.mesh import mesh_from_env

    monkeypatch.setenv("VOLCANO_TPU_MESH", "4")
    store = ClusterStore()
    mesh = mesh_from_env(store)
    assert mesh is not None and mesh.devices.size == 4
    assert mesh_from_env(store) is mesh is store.solve_mesh
    store.close()


@pytest.mark.parametrize("raw", ["4096", "four"])
def test_mesh_env_raises_when_the_mesh_cannot_be_built(monkeypatch, raw):
    """More devices than the backend has, or not a number: the cycle
    fails instead of carrying on on one device."""
    from volcano_tpu.parallel.mesh import mesh_from_env

    monkeypatch.setenv("VOLCANO_TPU_MESH", raw)
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    store = synthetic_cluster(seed=1, n_nodes=8, n_pods=16, gang_size=2)
    with pytest.raises(RuntimeError, match="VOLCANO_TPU_MESH"):
        mesh_from_env(store)
    with pytest.raises(RuntimeError, match="VOLCANO_TPU_MESH"):
        Scheduler(store).run_once()
    assert not store.binder.binds
    store.close()
