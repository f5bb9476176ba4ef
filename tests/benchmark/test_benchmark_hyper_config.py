"""``hyper-50k``: the configuration file says what the issue says (the
source's 50,000 nodes in 16 zones, the 5 / 5 / 10 mix, 100,000 pods a
round as the one cut, four chips named by the deployment's own conf), it is
``affinity-10k`` in everything else, its cell is a four-chip cell within
the share such cells may take, and its capacity is what the file reckons.
The mesh path it runs is held to the unsharded program in
``tests/test_hyper_mesh.py``."""

import json

from benchmark.harness import cell as cell_mod
from benchmark.harness import generate
from test_benchmark_contract import reports_its_per_layer
from volcano_tpu.framework.arguments import get_action_args
from volcano_tpu.framework.conf import parse_scheduler_conf

ROOT = cell_mod.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
AFFINITY = json.loads((ROOT / "benchmark" / "configs" / "affinity-10k.json").read_text())


def test_the_file_states_its_deployment():
    cfg = cell_mod.load_cell("hyper-50k.burst").config
    entry = next(e for e in BENCH["configs"] if e["name"] == "hyper-50k")
    assert entry["source"] == cfg["source"] == AFFINITY["source"]
    assert "configs[4]" in cfg["source"] and "50k-node x 500k-pod" in cfg["source"]
    assert entry["file"] == "benchmark/configs/hyper-50k.json"
    # one cut, of scale, forced by the run's time limit: the pods a round
    assert entry["reduced"] == cfg["reduced"] == ["pods"]
    assert (cfg["pods_cut"]["source"], cfg["pods_cut"]["here"]) == (500000, 100000)
    assert "time limit" in cfg["pods_cut"]["why"] and "2 pods a node" in cfg["pods_cut"]["why"]
    assert "nodes_cut" not in cfg
    assert (cfg["chips"]["source"], cfg["chips"]["here"]) == (4, 4) and cfg["chips"]["why"]
    assert cfg["nodes"]["count"] == 50000 and cfg["nodes"]["zones"] == 16
    assert cfg["backlog_pods"] == 100000
    assert cfg["affinity_mix"] == {"affinity": 0.05, "anti_affinity": 0.05,
                                   "spread": 0.10}
    for key in ("device_arithmetic", "shapes_kept", "sizes_from",
                "scheduler_conf_from"):
        assert cfg[key]
    assert "BENCH_FULL=1" in cfg["sizes_from"]


def test_everything_else_is_affinity_10ks():
    cfg = cell_mod.load_cell("hyper-50k.burst").config
    assert {k: v for k, v in cfg["nodes"].items() if k != "count"} \
        == {k: v for k, v in AFFINITY["nodes"].items() if k != "count"}
    for key in ("pods", "gang", "queues", "affinity_mix", "affinity_mix_what"):
        assert cfg[key] == AFFINITY[key], key
    assert {k: cfg["probe"][k] for k in ("probes", "before_drain", "keep_pods")} \
        == {"probes": 48, "before_drain": 2, "keep_pods": 10000}
    assert cfg["probe"] == AFFINITY["probe"]
    assert set(cfg["guarantees"]) == set(AFFINITY["guarantees"])
    for name, said in cfg["guarantees"].items():
        if name in ("pod_affinity", "pod_anti_affinity"):
            assert "affinity_ref.py" in said and "not yet" not in said
            assert "since PR 40 by this cell's correct" in said
            assert "tests/test_hyper_mesh.py" in said
        elif name == "checks":
            assert said == ["affinity"]
        else:
            assert said == AFFINITY["guarantees"][name]
    assert set(AFFINITY["assumed"]) - set(cfg["assumed"]) \
        == {"16 zones as labels, nodes dealt to them in turn (625 a zone)"}
    assert any("3,125 a zone" in a for a in cfg["assumed"])
    assert any("config_5, full shape" in a for a in cfg["assumed"])
    assert any("v5e-4" in a for a in cfg["assumed"])
    # 16 zones of 3,125 nodes, dealt in turn
    zones = {}
    for name, i in zip(generate.node_names(cfg), range(50000)):
        zones[i % 16] = zones.get(i % 16, 0) + 1
    assert set(zones.values()) == {3125}


def test_the_deployments_conf_names_its_chips():
    cfg = cell_mod.load_cell("hyper-50k.burst").config
    added = "configurations:\n- name: allocate\n  arguments:\n    mesh: 4\n"
    assert cfg["scheduler_conf"] == AFFINITY["scheduler_conf"] + added
    conf = parse_scheduler_conf(cfg["scheduler_conf"])
    args = get_action_args(conf.configurations, "allocate")
    assert dict(args) == {"mesh": "4"} and args.get_int("mesh", 0) == 4
    assert conf.actions == parse_scheduler_conf(AFFINITY["scheduler_conf"]).actions
    assert [[p.name for p in t.plugins] for t in conf.tiers] == [
        [p.name for p in t.plugins]
        for t in parse_scheduler_conf(AFFINITY["scheduler_conf"]).tiers]
    assert get_action_args(
        parse_scheduler_conf(AFFINITY["scheduler_conf"]).configurations,
        "allocate") is None


def test_the_cell_is_a_four_chip_cell_within_their_share(
        bench_file=ROOT / "BENCHMARK.json"):
    bench = json.loads(bench_file.read_text())
    c = cell_mod.load_cell("hyper-50k.burst", bench_file)
    assert (c.chips, c.config_name, c.traffic_name) == (4, "hyper-50k", "burst")
    sizes = c.sizes()
    assert (sizes["batch_pods"], sizes["resident_pods"], sizes["warmup_rounds"],
            sizes["max_cycles"]) == (100000, 0, 1, 4)
    # its per-layer metrics: those that list no cells and those that name it
    reports_its_per_layer(c, bench)
    assert {m["name"] for m in c.end_to_end} == {
        "bind_rate", "backlog_to_bind_ms", "submit_to_bind_p95_ms", "setup_s"}
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert "hyper-50k.burst" in four
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
    assert "mesh_shards 4" in next(w["why"] for w in bench["workloads"]
                                   if w["name"] == c.name)


def test_the_capacity_is_what_the_file_reckons():
    cfg = cell_mod.load_cell("hyper-50k.burst").config
    pods = cfg["backlog_pods"] + cfg["probe"]["probes"]
    nodes = cfg["nodes"]
    cpu = pods * max(cfg["pods"]["cpu_choices"])
    mem = pods * max(cfg["pods"]["mem_gi_choices"])
    per_zone = nodes["count"] // nodes["zones"]
    said = cfg["capacity_arithmetic"]
    for number in (cpu, nodes["count"] * nodes["cpu"], mem,
                   nodes["count"] * nodes["memory_gi"], pods,
                   nodes["count"] * nodes["pods"], per_zone,
                   per_zone * nodes["cpu"]):
        assert f"{number:,}" in said, number
    assert (cpu, mem, pods, per_zone) == (400192, 800384, 100048, 3125)
    assert (nodes["count"] * nodes["cpu"], per_zone * nodes["cpu"]) == (3200000, 200000)
    # the tightest constraint: one gang against one zone, and 8 nodes of 50,000
    gang_cpu = cfg["gang"]["size"] * max(cfg["pods"]["cpu_choices"])
    assert gang_cpu == 32 <= nodes["cpu"] < per_zone * nodes["cpu"]
    assert "12.5 %" in said
    assert cfg["backlog_pods"] * max(cfg["pods"]["cpu_choices"]) \
        == 0.125 * nodes["count"] * nodes["cpu"]
