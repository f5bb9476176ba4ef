"""``hyper-50k`` on the mesh path, held to its plain reference: the
unsharded program.  A toy of the cell (512 nodes in 16 zones, so that the
hostname domains outnumber everything else; 2,048 pods a round in gangs of
8; the 5 / 5 / 10 mix) goes through ``benchmark/run.py``'s own ``run`` with
the conf's ``mesh: 4`` and without, on the virtual CPU devices
``conftest.py`` forces: bind for bind the same node for every pod of every
round, and under a count budget the global reckoning would chunk.  Then
the conf argument itself, the shape buckets on the mesh, and what the
record says of a mesh solve."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import generate, loop
from benchmark.reference import affinity_ref
from volcano_tpu.cache import ClusterStore
from volcano_tpu.framework.conf import parse_scheduler_conf
from volcano_tpu.ops import wave
from volcano_tpu.parallel.mesh import make_mesh, mesh_from_env
from volcano_tpu.scheduler import Scheduler

ROOT = cell_mod.ROOT
SEED = 2**31 + 3333
NODES, BATCH, ROUNDS = 512, 2048, 3
MESH_CONF = "configurations:\n- name: allocate\n  arguments:\n    mesh: 4\n"

needs_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                             reason="needs 4 (virtual) devices")


def _toy_config(mesh: bool) -> dict:
    cfg = json.loads((ROOT / "benchmark" / "configs" / "hyper-50k.json").read_text())
    cfg.update(name="hyper-toy", backlog_pods=BATCH)
    cfg["nodes"]["count"] = NODES
    cfg["probe"].update(probes=6, keep_pods=NODES)
    assert cfg["scheduler_conf"].endswith(MESH_CONF)
    if not mesh:
        cfg["scheduler_conf"] = cfg["scheduler_conf"][:-len(MESH_CONF)]
    return cfg


def _three_rounds(driver, gen, batch_pods, seconds, on_round=None):
    """``loop.run_window`` by count and not by the clock, so that two runs
    generate the same plans: the window of every toy run below."""
    counted = []
    for i in range(ROUNDS):
        counted.append(driver.round(gen.plan(batch_pods, f"w{i:04d}"), batch_pods))
        if on_round is not None:
            on_round(counted[-1])
    return counted


class Toy:
    """One run of the toy cell through ``run.py``'s ``run``: the result
    object, each round's binds, the violations of the two affinity
    guarantees on them, and the timed store's flight records."""

    def __init__(self, tmp, mesh, trace=False, env=None):
        tmp.mkdir()
        home = tmp / "benchmark"
        (home / "configs").mkdir(parents=True)
        for part in ("layer_metrics", "traffic"):
            os.symlink(ROOT / "benchmark" / part, home / part)
        cfg = _toy_config(mesh)
        (home / "configs" / "hyper-toy.json").write_text(json.dumps(cfg))
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        bench["configs"] = [{"name": "hyper-toy", "source": "a test", "reduced": [],
                             "file": "benchmark/configs/hyper-toy.json",
                             "why": "a toy of hyper-50k"}]
        bench["workloads"] = [{"name": "hyper-toy.burst", "config": "hyper-toy",
                               "traffic": "burst", "chips": 4 if mesh else 1,
                               "why": "a test"}]
        (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
        seen = {}
        set_up = bench_run.set_up

        def keep_driver(*a, **kw):
            out = set_up(*a, **kw)
            seen["driver"] = out[0]
            return out

        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("JAX_COMPILATION_CACHE_DIR", os.environ.get(
                "JAX_COMPILATION_CACHE_DIR", str(tmp / "xla")))
            mp.delenv("VOLCANO_TPU_MESH", raising=False)
            for k, v in (env or {}).items():
                mp.setenv(k, v)
            mp.setattr(bench_run, "OUT_DIR", tmp / "out")
            mp.setattr(bench_run, "set_up", keep_driver)
            mp.setattr(loop, "run_window", _three_rounds)
            cell = cell_mod.load_cell("hyper-toy.burst", tmp / "BENCHMARK.json")
            self.result = bench_run.run(cell, SEED, 1.0, trace)
        driver = seen["driver"]
        self.records = driver.store.flight.recent()
        index = {n: i for i, n in enumerate(generate.node_names(cfg))}
        zone = np.arange(NODES) % 16
        self.binds, self.violations = {}, {}
        for r in driver.rounds:
            hosts = {k: h for _t, keys, hs in r.arrivals for k, h in zip(keys, hs)}
            self.binds[r.plan.tag] = hosts
            if r.plan.n_pods == BATCH:
                pod_node = np.array([index[hosts[k]] for k in r.plan.keys()])
                self.violations[r.plan.tag] = affinity_ref.violations(
                    r.plan.gang_kind, r.plan.gang, pod_node, zone)

    def solves(self):
        """The ``solve`` blocks of the full rounds' cycles."""
        return [r.solve for r in self.records
                if r.solve and r.solve["rows"] == BATCH]


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    return Toy(tmp_path_factory.mktemp("hyper") / "one", mesh=False, trace=True)


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    return Toy(tmp_path_factory.mktemp("hyper") / "four", mesh=True, trace=True)


def _same_binds(a: Toy, b: Toy):
    assert list(a.binds) == list(b.binds) and len(a.binds) == 2 + ROUNDS + 6
    for tag in a.binds:
        assert a.binds[tag] == b.binds[tag], tag
    assert sum(len(h) for h in a.binds.values()) == (2 + ROUNDS) * BATCH + 6


def _sound(t: Toy):
    assert t.result["correct"] is True and t.result["failed"] == 0
    assert t.result["attempted"] == ROUNDS * BATCH
    assert len(t.violations) == 2 + ROUNDS      # warm-up, window, the probe's fill
    for tag, v in t.violations.items():
        assert v["affinity_pods"] >= 40 and v["anti_pods"] >= 40, tag
        assert (v["affinity_outside"], v["anti_shared"]) == (0, 0), tag
    assert all(r.path == "fast" and r.error is None for r in t.records)


# ---- the sharded program against the unsharded one ---------------------------


def test_the_toy_binds_every_pod_to_the_same_node_with_mesh_4_as_without(
        one_device, four_devices):
    """Warm-up, three window rounds with completions between them, the
    probe's fill and six one-pod probes: every pod of every round on the
    node the one-device program gave it; no violation of the two affinity
    guarantees on either; ``correct``; nothing lowered after the warm-up."""
    _sound(one_device)
    _sound(four_devices)
    _same_binds(one_device, four_devices)
    for t in (one_device, four_devices):
        assert t.result["metrics"]["compiles_in_window"]["value"] == 0
    assert four_devices.result["device"]["count"] >= 4


def test_the_record_says_what_a_mesh_solve_held_and_placed(one_device, four_devices):
    """``solve`` gains ``mesh_shards``, a chip's share of the affinity
    tensors and the bytes placed against those found resident;
    ``device:shard`` stands inside ``device`` with its counts.  None of it
    on one device."""
    for s in four_devices.solves():
        assert s["mesh_shards"] == 4 and s["aff_chunks"] == 1
        assert s["aff_domains"] == 16 + NODES
        whole = 2 * (s["aff_terms_padded"] + 1) * s["aff_domains"] * 4
        assert s["aff_device_bytes"] == whole
        assert s["aff_device_bytes_chip"] == -(-whole // 4)
        assert s["mesh_put_bytes"] > 0 and s["mesh_resident_bytes"] > 0
    cycle = next(r for r in four_devices.records
                 if r.solve and r.solve["rows"] == BATCH)
    by_name = {s.name: s for s in cycle.spans}
    shard, device = by_name["device:shard"], by_name["device"]
    assert device.ts_ns <= shard.ts_ns
    assert shard.ts_ns + shard.dur_ns <= device.ts_ns + device.dur_ns
    assert set(shard.args) == {"arrays", "bytes", "cache_hits"}
    assert shard.args["bytes"] == cycle.solve["mesh_put_bytes"]
    assert by_name["device:dispatch"].args == {"mesh_shards": 4}
    new = {"mesh_shards", "aff_device_bytes_chip",
           "mesh_put_bytes", "mesh_resident_bytes"}
    for r in one_device.records:
        assert not new & set(r.solve or {})
        assert all(s.name != "device:shard" for s in r.spans)
    assert {k for s in four_devices.solves() for k in s} \
        - {k for s in one_device.solves() for k in s} == new


def test_the_record_counts_the_count_plane_recomputes(one_device, four_devices):
    """``solve.aff_count_reads``: how often the cycle's solves recomputed the
    count plane.  Above 0 in every full round, on one device and on the mesh
    alike (the same program text, so the same count); in the probe's one-pod
    cycles 0 where the pod carries no term (its window is all dummy rows)
    and 1 where it does."""
    for t in (one_device, four_devices):
        assert all(s["aff_count_reads"] > 0 for s in t.solves())
        probes = [r.solve for r in t.records if r.solve and r.solve["rows"] == 1]
        assert len(probes) == 6
        assert {s["aff_count_reads"] for s in probes} <= {0, 1}
        assert 0 in {s["aff_count_reads"] for s in probes}
        assert all((s["aff_count_reads"] > 0) == (s["aff_rows"] > 0) for s in probes)
    assert [s["aff_count_reads"] for s in one_device.solves()] \
        == [s["aff_count_reads"] for s in four_devices.solves()]


@needs_4
def test_a_chips_share_meets_the_count_budget_where_the_whole_would_chunk(
        one_device, tmp_path):
    """Under a budget of 0.2 MB the whole count pair (64 or 128 x 528 x
    8 B) is cut into chunks on one device; a chip's quarter of it is not,
    so the mesh solves each round in one piece and binds as the unchunked
    one-device run does."""
    env = {"VOLCANO_TPU_AFF_BUDGET_MB": "0.2"}
    t = Toy(tmp_path / "budget", mesh=True, env=env)
    _sound(t)
    assert {s["aff_chunks"] for s in t.solves()} == {1}
    _same_binds(one_device, t)
    for s in t.solves():
        bucket = wave.bucket_pow2(s["aff_terms"], floor=1)
        assert bucket * s["aff_domains"] * 8 > 0.2e6 >= bucket * s["aff_domains"] * 2
    # the same budget on one device: the global reckoning cuts several
    cfg = _toy_config(mesh=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("VOLCANO_TPU_AFF_BUDGET_MB", "0.2")
        driver = loop.Driver(cfg, max_cycles=4)
        try:
            driver.round(generate.Generator(cfg, SEED).plan(BATCH, "warm"), 0)
            solve = driver.store.flight.recent()[-1].solve
            assert solve["aff_chunks"] > 1 and "mesh_shards" not in solve
        finally:
            driver.close()


# ---- the conf argument ---------------------------------------------------------


def _allocate_mesh(conf_str: str):
    conf = parse_scheduler_conf(conf_str)
    args = {c.name: c.arguments for c in conf.configurations}
    return args.get("allocate", {}).get("mesh")


BASE_CONF = _toy_config(mesh=False)["scheduler_conf"]


@needs_4
def test_the_conf_argument_builds_the_mesh_once_per_store(monkeypatch):
    monkeypatch.delenv("VOLCANO_TPU_MESH", raising=False)
    asked = _allocate_mesh(BASE_CONF + MESH_CONF)
    assert asked == "4"
    store = ClusterStore()
    mesh = mesh_from_env(store, asked)
    assert mesh is not None and mesh.devices.size == 4
    assert mesh_from_env(store, asked) is mesh is store.solve_mesh
    store.close()


@needs_4
def test_the_conf_argument_loses_to_the_embedders_mesh_and_beats_the_environment(
        monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_MESH", "2")
    store = ClusterStore()
    assert mesh_from_env(store, "4").devices.size == 4      # beats the environment
    assert mesh_from_env(store, "1") is None                # and says one device
    assert store.solve_mesh is None
    assert mesh_from_env(store, None).devices.size == 2     # absent: the environment
    # an embedder's mesh, set later and known by its identity (JAX interns
    # meshes, so one over other devices than the resolver's own)
    theirs = store.solve_mesh = make_mesh(3)
    assert mesh_from_env(store, "4") is theirs is store.solve_mesh
    store.close()
    # and set before the first cycle, as embedders do
    store = ClusterStore()
    theirs = store.solve_mesh = make_mesh(2)
    assert mesh_from_env(store, "4") is theirs is store.solve_mesh
    assert mesh_from_env(store, None) is theirs
    store.close()


@pytest.mark.parametrize("raw", ["x", "4096"])
def test_a_conf_argument_that_cannot_be_met_fails_the_cycle(monkeypatch, raw):
    """Not an integer, or more chips than the backend has: the cycle fails
    and nothing is bound on one device instead."""
    from volcano_tpu.synth import synthetic_cluster

    monkeypatch.delenv("VOLCANO_TPU_MESH", raising=False)
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    store = synthetic_cluster(seed=1, n_nodes=8, n_pods=16, gang_size=2)
    conf = BASE_CONF + MESH_CONF.replace("mesh: 4", f"mesh: {raw}")
    with pytest.raises(RuntimeError, match="allocate argument mesh"):
        mesh_from_env(store, raw)
    with pytest.raises(RuntimeError, match="allocate argument mesh"):
        Scheduler(store, conf_str=conf).run_once()
    assert not store.binder.binds
    store.close()


def test_no_conf_argument_and_no_environment_is_one_device(monkeypatch):
    from volcano_tpu.synth import synthetic_cluster

    monkeypatch.delenv("VOLCANO_TPU_MESH", raising=False)
    assert _allocate_mesh(BASE_CONF) is None
    store = synthetic_cluster(seed=1, n_nodes=8, n_pods=16, gang_size=2)
    Scheduler(store, conf_str=BASE_CONF).run_once()
    assert store.solve_mesh is None and len(store.binder.binds) == 16
    assert "mesh_shards" not in store.flight.recent()[-1].solve
    store.close()


@needs_4
def test_a_conf_reload_that_changes_the_mesh_voids_what_was_placed(monkeypatch):
    """4 -> 2 -> none under one live store: each change replaces the mesh
    the resolver built, drops the mesh plane cache and moves devincr's
    placement token; the binds go on."""
    monkeypatch.delenv("VOLCANO_TPU_MESH", raising=False)
    cfg = _toy_config(mesh=True)
    gen = generate.Generator(cfg, SEED)
    driver = loop.Driver(cfg, max_cycles=4)
    store = driver.store
    try:
        driver.round(gen.plan(BATCH, "a"), 0)
        four = store.solve_mesh
        assert four.devices.size == 4 and "node_dom" in store._mesh_plane_cache
        assert store.device_snapshot.mesh is four
        token4 = store._devincr_cache._place_tok
        assert token4[0] == "mesh" and token4[2] == 4
        driver.sched = Scheduler(store, conf_str=cfg["scheduler_conf"].replace(
            "mesh: 4", "mesh: 2"))
        store._mesh_plane_cache["stale"] = ("key", None)
        driver.round(gen.plan(BATCH, "b"), 0)
        two = store.solve_mesh
        assert two.devices.size == 2 and "stale" not in store._mesh_plane_cache
        assert store.device_snapshot.mesh is two
        assert store._devincr_cache._place_tok[2] == 2
        assert store.flight.recent()[-1].solve["mesh_shards"] == 2
        driver.sched = Scheduler(store, conf_str=BASE_CONF)
        driver.round(gen.plan(BATCH, "c"), 0)
        assert store.solve_mesh is None and not store._mesh_plane_cache
        assert store._devincr_cache._place_tok == ("single",)
        assert "mesh_shards" not in store.flight.recent()[-1].solve
        assert driver.binder.count == 3 * BATCH
    finally:
        driver.close()


# ---- the shapes a round's terms give the mesh's programs do not move -----------


@needs_4
def test_a_term_count_that_crosses_a_power_of_two_lowers_nothing_on_the_mesh(
        monkeypatch):
    """Rounds of 300, 40 and 150 constrained gangs of 512 on the mesh: the
    first is the high-water round, and the two after it (whose own buckets
    would be smaller powers of two on every axis) lower no program: the
    mesh dispatch hands ``solve_wave`` the store's shape marks as the
    one-device dispatch does."""
    monkeypatch.delenv("VOLCANO_TPU_MESH", raising=False)
    cfg = _toy_config(mesh=True)
    driver = loop.Driver(cfg, max_cycles=4)
    compiles = bench_run.Compiles()

    def plan(share, tag, seed):
        c = json.loads(json.dumps(cfg))
        c["affinity_mix"] = {"affinity": share, "anti_affinity": share,
                             "spread": share}
        return generate.Generator(c, seed).plan(4096, tag)

    try:
        terms = []
        for i, share in enumerate((0.2, 0.025, 0.1)):
            p = plan(share, f"r{i}", SEED + i)
            rec = driver.round(p, 4096)
            assert rec.cycles == 1
            solve = driver.store.flight.recent()[-1].solve
            assert solve["mesh_shards"] == 4
            terms.append(solve["aff_terms"])
            if i == 0:
                marks = dict(driver.store._solve_shape_marks)
                lowered = len(compiles.names)
                assert {"jit(_solve_wave)", "jit(_coarse_shortlist)"} \
                    <= set(compiles.names)
        assert terms[0] > 2 * terms[2] > 4 * terms[1] > 0     # powers of two apart
        assert {"Ep", "U", "UM", "EW"} <= set(marks)
        assert driver.store._solve_shape_marks == marks
        assert compiles.names[lowered:] == []
    finally:
        driver.close()
