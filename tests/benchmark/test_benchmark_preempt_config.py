"""``preempt-10k`` (BASELINE configs[3]) and its cell ``preempt-10k.evict``:
the files state the deployment in numbers and pass the eviction cell's
clause; the nine metrics of the what-if engine report there and nowhere
else; a cut of the cell (48 nodes, everything else the files' own) runs
through ``run.py``'s ``run`` on the CPU with the real ``preempt_ref.py``
judging every victim; and hand-built rounds that break one rule each fire
that rule's count and no other."""

import json
import os
import shutil

import numpy as np
import pytest

import test_benchmark_contract as contract
from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import generate
from benchmark.harness.validate import RoundEvents
from benchmark.reference import preempt_ref
from test_benchmark_cell import CONF_PREEMPT

ROOT = cell_mod.ROOT
CELL = "preempt-10k.evict"
NINE = ["preempt_plan_ms", "reclaim_plan_ms", "plan_victims_ms",
        "plan_scores_ms", "plan_select_ms", "whatif_solve_ms",
        "whatif_victims", "whatif_gangs_tried", "victim_scores_ms_per_round"]
GI = 1 << 30


def test_the_file_loads_and_states_its_deployment(bench_file=ROOT / "BENCHMARK.json"):
    c = cell_mod.load_cell(CELL, bench_file)
    cfg, bench = c.config, json.loads(bench_file.read_text())
    entry = {e["name"]: e for e in bench["configs"]}["preempt-10k"]
    assert entry["source"] == cfg["source"] and "configs[3]" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["chips"] and c.chips == 1
    north = json.loads((c.home / "configs" / "north-10k.json").read_text())
    assert cfg["nodes"] == north["nodes"] and cfg["chips"] == north["chips"]
    assert cfg["nodes"]["count"] == 10000
    # one cpu size, so that the residents fill the cluster whatever the deck
    # deals; memory sizes 1 Gi apart and under a quarter node, so that four
    # pods always fit a node and the probe's candidates lie closer than
    # bfloat16 resolves
    assert cfg["pods"] == {"cpu_choices": [16], "mem_gi_choices": [61, 62, 63]}
    assert 4 * max(cfg["pods"]["mem_gi_choices"]) <= cfg["nodes"]["memory_gi"]
    assert cfg["backlog_pods"] == 40000 and cfg["gang"] == {"size": 8}
    assert cfg["queues"] == {"count": 4, "weights": [1, 2, 4, 8],
                             "reclaimable": [True] * 4}
    low, high = cfg["priority_classes"]
    assert low == {"name": "low", "value": 10, "share": 0.9, "gang": {
        "size": 8, "min_member": 1, "max_unavailable": 8}}
    assert high == {"name": "high", "value": 1000, "share": 0.1,
                    "queues": ["queue-1", "queue-2", "queue-3"]}
    assert cfg["scheduler_conf"] == CONF_PREEMPT
    assert cfg["guarantees"]["checks"] == ["preempt"]
    assert "640,128 > 640,000" in cfg["capacity_arithmetic"]
    assert cfg["probe"]["before_drain"] == 0
    assert 0 < cfg["probe"]["keep_pods"] < cfg["backlog_pods"]
    # the traffic, key by key
    assert {k: v for k, v in c.traffic.items()
            if k not in ("name", "what", "loop", "assumed")} == {
        "resident_fraction": 1.0, "waiting_fraction": 0.01,
        "batch_fraction": 0.0002, "pods_run": True, "resident_class": "low",
        "batch_class": "high", "settle_cycles": 2, "termination_cycles": 0,
        "max_cycles": 4, "warmup_rounds": 4, "bind_wait_s": 30}
    sizes = c.sizes()
    assert (sizes["resident_pods"], sizes["waiting_pods"], sizes["batch_pods"],
            sizes["profile_seconds"]) == (40000, 400, 8, 10.0)
    # four pods fill a node's cpu; the residents fill the cluster
    nodes = cfg["nodes"]
    assert sizes["resident_pods"] * 16 == nodes["count"] * nodes["cpu"]
    # what the generator makes of it
    gen = generate.Generator(cfg, 2**31 + 49)
    low_plan = gen.plan(64, "resident", klass=sizes["resident_class"])
    assert set(low_plan.gang_queue) == set(generate.queue_names(cfg))
    assert low_plan.gang_min_member.tolist() == [1] * 8
    assert low_plan.sizes().tolist() == [8] * 8
    assert set(low_plan.gang_max_unavailable) == {8}
    gen.batch_class = sizes["batch_class"]
    bursts = [gen.plan(sizes["batch_pods"], f"w{i}") for i in range(6)]
    assert [b.gang_queue for b in bursts] \
        == [["queue-3"], ["queue-1"], ["queue-2"]] * 2
    for b in bursts:
        assert b.gang_priority == ["high"] and b.gang_min_member.tolist() == [8]
        assert b.cpu_milli.tolist() == [16000] * 8
        assert len(set(b.mem_bytes.tolist())) == 1      # one size a gang
    assert {b.mem_bytes[0] // GI for b in bursts} == {61, 62, 63}


def test_the_real_cell_passes_the_eviction_clause():
    c = cell_mod.load_cell(CELL)
    assert contract.holds_evictions(c)
    contract.eviction_clause(c)
    contract.test_every_cells_files_resolve(CELL)


@pytest.mark.parametrize("metric", NINE)
def test_the_metric_reports_on_this_cell_and_no_other(metric):
    entry = {m["name"]: m for m in contract.BENCH["per_layer"]}[metric]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "what-if engine"
    assert entry["moves"] == "backlog_to_bind_ms"
    on_file = json.loads((ROOT / "benchmark" / "layer_metrics"
                          / f"{metric}.json").read_text())
    assert on_file["what"] and on_file["reader"] in (
        "span_self", "record", "profile")
    assert metric in [m["name"] for m in cell_mod.load_cell(CELL).per_layer]


@pytest.mark.parametrize("name", sorted(contract.SEVEN))
def test_the_seven_cells_load_as_they_did(name):
    c = cell_mod.load_cell(name)
    names = [m["name"] for m in c.per_layer]
    assert not set(names) & set(NINE)
    assert names == [m["name"] for m in contract.BENCH["per_layer"]
                     if "workloads" not in m]
    assert not contract.holds_evictions(c)


# ---- a cut of the cell, run on the CPU ---------------------------------------


@pytest.fixture
def cut(tmp_path, monkeypatch):
    """The real files with 48 nodes in place of 10,000 (192 residents, 16
    waiting, one gang of 8 a round) and a probe to match; no ``reference/``
    beside them, so the check is the repo's own ``preempt_ref.py``.  The
    suite's conftest pins the host walk for the legacy tests: the program's
    default lane is put back."""
    monkeypatch.delenv("VOLCANO_TPU_EVICT_DEVICE", raising=False)
    monkeypatch.delenv("VOLCANO_TPU_EVICT_CAP", raising=False)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    home = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark" / "layer_metrics", home / "layer_metrics")
    (home / "configs").mkdir()
    (home / "traffic").mkdir()
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "preempt-10k.json").read_text())
    config["nodes"]["count"] = 48
    config["backlog_pods"] = 192
    config["probe"].update(probes=8, keep_pods=140)
    (home / "configs" / "preempt-10k.json").write_text(json.dumps(config))
    traffic = json.loads((ROOT / "benchmark" / "traffic" / "evict.json").read_text())
    traffic.update(batch_fraction=8 / 192, waiting_fraction=16 / 192,
                   warmup_rounds=2, bind_wait_s=5.0, profile_seconds=0.2)
    (home / "traffic" / "evict.json").write_text(json.dumps(traffic))
    real["configs"] = [c for c in real["configs"] if c["name"] == "preempt-10k"]
    real["workloads"] = [w for w in real["workloads"] if w["name"] == CELL]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(real))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                      str(tmp_path / "xla")))
    monkeypatch.setattr(bench_run, "OUT_DIR", tmp_path / "out")
    return path


def test_a_cut_of_the_cell_runs_and_every_victim_is_judged(cut, capsys):
    c = cell_mod.load_cell(CELL, cut)
    contract.eviction_clause(c)
    assert set(c.traffic) == set(cell_mod.load_cell(CELL).traffic) | {
        "profile_seconds"}
    result = bench_run.run(c, seed=2**31 + 49, seconds=1.0, trace=True,
                           control=True)
    captured = capsys.readouterr()
    out = captured.out
    assert result["correct"] is True and result["failed"] == 0, out[-4000:]
    for name in preempt_ref.COUNTS + ("lost", "ghost", "never_terminated",
                                      "gang_broken", "unbound"):
        assert result["compared"][name] == {"value": 0, "limit": 0}, name
        assert f"validate: {name} = 0 (limit 0)" in out
    line = [ln for ln in out.splitlines() if " evictions seen, " in ln
            and ln.startswith("window:")][0]
    evictions = int(line.split()[1])
    assert evictions > 0 and f"{evictions} terminations ended" in line
    assert "0 programs lowered inside the window" in out
    # the probe has teeth here: the same reference in bfloat16 misses
    control = [ln for ln in out.splitlines() if ln.startswith("control:")][0]
    assert "probe: 0 of 8 one-pod choices differ" in out
    assert int(control.split("place ")[1].split()[0]) >= 1, control
    # and the run says how its victims were admitted (the whole run's)
    tally = [ln for ln in captured.err.splitlines()
             if ln.startswith("preempt_ref:")][0]
    assert int(tally.split()[1]) >= evictions
    # the what-if engine's metrics read a number (the chip's own needs a chip)
    read = set(result["metrics"])
    assert set(NINE) - read == {"victim_scores_ms_per_round"}
    assert result["metrics"]["whatif_victims"]["value"] >= 8
    assert result["metrics"]["whatif_gangs_tried"]["value"] >= 2
    for name in ("plan_victims_ms", "plan_scores_ms", "plan_select_ms",
                 "preempt_plan_ms", "whatif_solve_ms"):
        assert result["metrics"][name]["value"] > 0, name
    assert result["metrics"]["compiles_in_window"]["value"] == 0


# ---- planted cases: hand-built rounds, one broken rule each -------------------

PLANTED = {"name": "planted", "nodes": {"count": 2, "cpu": 64, "memory_gi": 256},
           "pods": {"cpu_choices": [16], "mem_gi_choices": [32]},
           "gang": {"size": 1},
           "queues": {"count": 2, "weights": [1, 1], "reclaimable": [True, True]},
           "priority_classes": [
               {"name": "low", "value": 10}, {"name": "high", "value": 1000},
               {"name": "system-cluster-critical", "value": 2000000000}]}
NODES = {"names": ["node-000000", "node-000001"], "labels": [{}, {}]}


def _plan(tag, gangs):
    """``gangs``: (queue, class, pods, min_member) each; pods of 16 cpu / 32 Gi."""
    names, gang, gang_names = [], [], []
    for g, (_queue, _klass, size, _floor) in enumerate(gangs):
        gang_names.append(f"{tag}-pg-{g}")
        names += [f"{tag}-pg-{g}-{k}" for k in range(size)]
        gang += [g] * size
    n = len(names)
    return generate.Plan(
        tag, names, np.full(n, 16000, np.int64), np.full(n, 32 * GI, np.int64),
        np.array(gang, np.int64), gang_names,
        np.array([g[3] for g in gangs], np.int64), [g[0] for g in gangs],
        gang_priority=[g[1] for g in gangs],
        gang_size=np.array([g[2] for g in gangs], np.int64))


def _rounds(running, pending, evicted):
    """A round that binds ``running`` and one that submits ``pending`` and
    evicts the pods ``evicted`` (indices into the first plan)."""
    first, second = _plan("a", running), _plan("b", pending)
    keys = first.keys()
    hosts = [NODES["names"][i % 2] for i in range(len(keys))]
    return [RoundEvents(first, [(10, keys, hosts)]),
            RoundEvents(second, [], (), [(20 + i, keys[k])
                                         for i, k in enumerate(evicted)])]


def _never_ran():
    events = _rounds([("default", "low", 2, 1)], [("default", "high", 1, 1)], [])
    key = events[1].plan.keys()[0]       # pending, never bound
    events[1] = RoundEvents(events[1].plan, [], (), [(20, key)])
    return events


CASES = {
    # default holds 6 of 8 slots and deserves 4: reclaim may take from it,
    # but not its pod of the top (critical) class
    "victim_critical": lambda: _rounds(
        [("default", "system-cluster-critical", 1, 1), ("default", "low", 5, 1),
         ("queue-1", "low", 2, 1)], [("queue-1", "low", 2, 1)], [0]),
    # pods of its own class wait in its own queue, nobody in the other
    "victim_unjustified": lambda: _rounds(
        [("default", "low", 6, 1), ("queue-1", "low", 2, 1)],
        [("queue-1", "low", 2, 1)], [6]),
    # a gang of min_member 4 cut to 3 for a pod of a higher class
    "gang_under_floor": lambda: _rounds(
        [("default", "low", 4, 4)], [("default", "high", 1, 1)], [0]),
    # default deserves 4 slots and holds 6: the fourth victim leaves it two
    # pods under (one pod is the replay's slack)
    "queue_under_deserved": lambda: _rounds(
        [("default", "low", 6, 1), ("queue-1", "low", 2, 1)],
        [("queue-1", "low", 4, 1)], [0, 1, 2, 3]),
    "victim_not_running": _never_ran,
    # one pod of 16 cpu waits, two are taken for it
    "evicted_beyond_demand": lambda: _rounds(
        [("default", "low", 2, 1)], [("default", "high", 1, 1)], [0, 1]),
}


@pytest.mark.parametrize("count", preempt_ref.COUNTS)
def test_a_planted_case_fires_its_own_count_and_no_other(count):
    got = preempt_ref.check(CASES[count](), NODES, PLANTED)
    assert set(got) == set(preempt_ref.COUNTS)
    assert got == {name: int(name == count) for name in preempt_ref.COUNTS}


def test_the_same_rounds_with_the_rule_kept_fire_nothing():
    """The first three victims of the reclaim case, and the preempt case
    with one victim: every count 0."""
    events = CASES["queue_under_deserved"]()
    events[1] = RoundEvents(events[1].plan, [], (), events[1].evictions[:3])
    assert not any(preempt_ref.check(events, NODES, PLANTED).values())
    events = CASES["evicted_beyond_demand"]()
    events[1] = RoundEvents(events[1].plan, [], (), events[1].evictions[:1])
    assert not any(preempt_ref.check(events, NODES, PLANTED).values())


# ---- the same, at the cell's size ---------------------------------------------


def _cell_rounds(evict):
    """The real files' set-up as the stamps of a sound run would give it
    (40,000 residents bound four a node, 400 waiting) and one round of the
    window: a burst of one high gang, and the evictions ``evict(running,
    waiting, burst)`` names (keys; ``running`` by queue, oldest first)."""
    c = cell_mod.load_cell(CELL)
    sizes = c.sizes()
    gen = generate.Generator(c.config, 2**31 + 4949)
    names = generate.node_names(c.config)
    resident = gen.plan(sizes["resident_pods"], "resident",
                        klass=sizes["resident_class"])
    waiting = gen.plan(sizes["waiting_pods"], "waiting",
                       klass=sizes["resident_class"], may_wait=True)
    gen.batch_class = sizes["batch_class"]
    burst = gen.plan(sizes["batch_pods"], "w0000")
    keys = resident.keys()
    hosts = [names[i // 4] for i in range(len(keys))]
    running = {}
    for key, g in zip(keys, resident.gang.tolist()):
        running.setdefault(resident.gang_queue[g], []).append(key)
    victims = evict(running, waiting.keys(), burst)
    events = [RoundEvents(resident, [(10, keys, hosts)]),
              RoundEvents(waiting, []),
              RoundEvents(burst, [], (), [(20 + i, k)
                                          for i, k in enumerate(victims)])]
    nodes = {"names": names, "labels": generate.node_labels(c.config)}
    return events, nodes, c.config


def _sound(running, waiting, burst):
    """What a round of the cell takes: 8 of the burst's own queue for the
    burst, one of ``default`` for the waiting tier."""
    return running[burst.gang_queue[0]][:8] + running["default"][:1]


# fault -> (the victims of the round, the counts that must read > 0)
AT_SIZE = {
    # twice the burst's need from its own queue
    "sixteen_for_a_gang_of_eight": (
        lambda r, w, b: r[b.gang_queue[0]][:16] + r["default"][:1],
        {"evicted_beyond_demand": 8}),
    # from a tenant that stands under its share, for the burst of another
    "a_queue_under_its_share": (
        lambda r, w, b: _sound(r, w, b) + r[
            [q for q in ("queue-1", "queue-2", "queue-3")
             if q != b.gang_queue[0]][0]][:1],
        {"queue_under_deserved": 1}),
    # default stands 300 pods over its share: 340 leave it under it, by more
    # than the replay's slack of one gang, and are more than anybody waits for
    "default_pushed_under_its_share": (
        lambda r, w, b: _sound(r, w, b)[:8] + r["default"][:340],
        {"queue_under_deserved": None, "evicted_beyond_demand": None}),
    # a pod that waits, and a victim taken twice
    "a_pod_that_never_ran": (
        lambda r, w, b: _sound(r, w, b) + w[:1] + r["default"][:1],
        {"victim_not_running": 2}),
}


def test_at_the_cells_size_a_sound_round_reads_nothing():
    events, nodes, config = _cell_rounds(_sound)
    counts, tally = preempt_ref.judged(events, nodes, config)
    assert not any(counts.values()), counts
    # memory is not short on this cluster, so default stands under its
    # deserved memory: the published comparison refuses what reclaim takes
    # from it, the program's share reading admits it (preempt_ref's head)
    assert tally == {"victims": 9, "by_preempt": 8, "by_reclaim": 0,
                     "by_share_alone": 1}


@pytest.mark.parametrize("fault", sorted(AT_SIZE))
def test_at_the_cells_size_a_fault_fires(fault):
    evict, want = AT_SIZE[fault]
    events, nodes, config = _cell_rounds(evict)
    counts, _tally = preempt_ref.judged(events, nodes, config)
    for name, n in want.items():
        assert counts[name] == n if n is not None else counts[name] > 0, counts
    assert {k for k, v in counts.items() if v} == set(want), counts
