"""``affinity-10k``: the configuration file says what the issue says, its
branch of the one generator (``affinity_mix``) deals the three kinds in the
file's shares with the file's keys, the plain reference stands alone, and a
tenth-size copy runs ``run.py``'s whole path on the CPU with no violation of
the two affinity guarantees on its binds."""

import json
import os
from collections import Counter

import numpy as np

from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import generate
from benchmark.reference import affinity_ref
from test_benchmark_contract import reports_its_per_layer

ROOT = cell_mod.ROOT
SEED = 2**31 + 3131


def test_the_file_loads_and_states_its_deployment(bench_file=ROOT / "BENCHMARK.json"):
    c = cell_mod.load_cell("affinity-10k.burst", bench_file)
    cfg = c.config
    assert c.chips == 1 and c.config_name == "affinity-10k" and c.traffic_name == "burst"
    bench = json.loads(bench_file.read_text())
    entry = next(e for e in bench["configs"] if e["name"] == "affinity-10k")
    assert entry["source"] == cfg["source"] and "configs[4]" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["nodes", "pods", "chips"]
    # each cut with the source's value beside it and why
    assert (cfg["nodes_cut"]["source"], cfg["nodes_cut"]["here"]) == (50000, 10000)
    assert (cfg["pods_cut"]["source"], cfg["pods_cut"]["here"]) == (500000, 100000)
    assert (cfg["chips"]["source"], cfg["chips"]["here"]) == (4, 1)
    assert all(cfg[k]["why"] for k in ("nodes_cut", "pods_cut", "chips"))
    assert cfg["nodes"]["count"] == 10000 and cfg["backlog_pods"] == 100000
    assert cfg["affinity_mix"] == {"affinity": 0.05, "anti_affinity": 0.05,
                                   "spread": 0.10}
    north = json.loads((ROOT / "benchmark" / "configs" / "north-10k.json").read_text())
    # the shapes are north-10k's: nodes, pod sizes, gangs, conf, probe
    assert cfg["nodes"] == north["nodes"] and cfg["pods"] == north["pods"]
    assert cfg["gang"] == north["gang"] == {"size": 8}
    assert cfg["queues"] == north["queues"]
    assert cfg["scheduler_conf"] == north["scheduler_conf"]
    assert {k: cfg["probe"][k] for k in ("probes", "before_drain", "keep_pods")} \
        == {"probes": 48, "before_drain": 2, "keep_pods": 10000}
    assert set(north["guarantees"]) < set(cfg["guarantees"])
    for name in ("pod_affinity", "pod_anti_affinity"):
        said = cfg["guarantees"][name]
        assert "affinity_ref.py" in said and "not yet" not in said
        assert "since PR 40 by this cell's correct" in said
    # the configuration's own check enters correct by name (ISSUE 40)
    assert cfg["guarantees"]["checks"] == ["affinity"]
    assert c.home == bench_file.parent / "benchmark"
    from benchmark.harness import checks
    assert checks.names(cfg) == ["affinity"]
    assert checks.load(ROOT / "benchmark", "affinity") is affinity_ref.check
    assert any("soft" in a and "weight 10" in a for a in cfg["assumed"])
    assert c.sizes()["batch_pods"] == 100000 and c.sizes()["resident_pods"] == 0
    # its per-layer metrics: those that list no cells and those that name it
    reports_its_per_layer(c, bench)
    # and the cell that came in with it
    churn = cell_mod.load_cell("binpack-1k.churn", bench_file)
    assert (churn.sizes()["resident_pods"], churn.sizes()["batch_pods"],
            churn.sizes()["warmup_rounds"]) == (10000, 100, 3)


def test_the_capacity_is_what_the_file_reckons():
    cfg = cell_mod.load_cell("affinity-10k.burst").config
    pods = cfg["backlog_pods"] + cfg["probe"]["probes"]
    nodes = cfg["nodes"]
    cpu = pods * max(cfg["pods"]["cpu_choices"])
    mem = pods * max(cfg["pods"]["mem_gi_choices"])
    per_zone = nodes["count"] // nodes["zones"]
    said = cfg["capacity_arithmetic"]
    for number in (cpu, nodes["count"] * nodes["cpu"], mem,
                   nodes["count"] * nodes["memory_gi"], pods,
                   nodes["count"] * nodes["pods"], per_zone * nodes["cpu"]):
        assert f"{number:,}" in said, number
    assert (cpu, mem, pods, per_zone) == (400192, 800384, 100048, 625)
    assert cpu < nodes["count"] * nodes["cpu"]
    # the tightest constraint: one gang against one zone, and 8 nodes of 10,000
    gang_cpu = cfg["gang"]["size"] * max(cfg["pods"]["cpu_choices"])
    assert gang_cpu == 32 <= nodes["cpu"] < per_zone * nodes["cpu"]
    assert cfg["backlog_pods"] * max(cfg["pods"]["cpu_choices"]) \
        >= 0.1 * nodes["count"] * nodes["cpu"]        # a tenth of capacity or more


def test_the_generator_deals_the_three_kinds_in_the_files_shares():
    cfg = cell_mod.load_cell("affinity-10k.burst").config
    plan = generate.Generator(cfg, SEED).plan(cfg["backlog_pods"], "x")
    assert plan.n_pods == 100000 and len(plan.gang_names) == 12500
    assert set(int(s) for s in plan.gang_min_member) == {8}
    kinds = Counter(plan.gang_kind)
    assert set(kinds) == {"", "affinity", "anti_affinity", "spread"}
    # binomial draws of 12,500: 625 +- 24, 625 +- 24, 1,250 +- 34
    assert 525 <= kinds["affinity"] <= 725 and 525 <= kinds["anti_affinity"] <= 725
    assert 1110 <= kinds["spread"] <= 1390
    # the API objects carry the file's keys, each term its gang's own label
    some = {}
    for (pg, pods), kind in zip(generate.to_objects(plan, iter(range(1, 10**7))),
                                plan.gang_kind):
        some.setdefault(kind, (pg, pods))
        if len(some) == 4:
            break
    pg, pods = some["affinity"]
    (t,) = pods[0].affinity
    assert (t.topology_key, t.match_labels) == ("zone", {"app": pg.name})
    assert all(p.affinity is pods[0].affinity and p.labels == {"app": pg.name}
               and not p.anti_affinity and not p.topology_spread for p in pods)
    pg, pods = some["anti_affinity"]
    (t,) = pods[0].anti_affinity
    assert (t.topology_key, t.match_labels) == ("kubernetes.io/hostname",
                                                {"app": pg.name})
    assert not pods[0].affinity and not pods[0].topology_spread
    pg, pods = some["spread"]
    assert pods[0].topology_spread == [("zone", 10)]
    assert not pods[0].affinity and not pods[0].anti_affinity
    pg, pods = some[""]
    assert not (pods[0].affinity or pods[0].anti_affinity
                or pods[0].topology_spread)
    # 16 zones of 625 nodes, dealt in turn
    zones = Counter(n.labels["zone"] for n in generate.to_nodes(cfg))
    assert len(zones) == 16 and set(zones.values()) == {625}


def test_the_reference_takes_nothing_from_the_program():
    """The rule ``test_benchmark_contract.py`` holds ``score_ref.py`` to."""
    text = (ROOT / "benchmark" / "reference" / "affinity_ref.py").read_text()
    body = text.split('"""', 2)[2]
    assert "volcano_tpu" not in body and "harness" not in body
    imports = [ln for ln in body.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "from typing import Dict, NamedTuple, Optional, Sequence, Tuple",
                       "import numpy as np", "from . import score_ref"]


def test_a_tenth_size_copy_runs_end_to_end(tmp_path, monkeypatch, capsys):
    """1,000 nodes x 10,000 pods, everything else the file's: set-up,
    window, validation, probe, result line, on the CPU; every round bound in
    one cycle, nothing failed, no program lowered in the window, and on
    every round's binds no pod outside its affinity gang's zone and no two
    pods of an anti-affinity gang on one node."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    home = tmp_path / "benchmark"
    home.mkdir()
    for part in ("layer_metrics", "traffic", "reference"):
        os.symlink(ROOT / "benchmark" / part, home / part)
    (home / "configs").mkdir()
    cfg = json.loads((ROOT / "benchmark" / "configs" / "affinity-10k.json").read_text())
    cfg.update(name="affinity-1k", backlog_pods=10000)
    cfg["nodes"]["count"] = 1000
    cfg["probe"].update(probes=12, keep_pods=1000)
    (home / "configs" / "affinity-1k.json").write_text(json.dumps(cfg))
    real["configs"] = [{"name": "affinity-1k", "source": "a test",
                        "file": "benchmark/configs/affinity-1k.json",
                        "reduced": [], "why": "a tenth of affinity-10k"}]
    real["workloads"] = [{"name": "affinity-1k.burst", "config": "affinity-1k",
                          "traffic": "burst", "chips": 1, "why": "a test"}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(real))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                      str(tmp_path / "xla")))
    monkeypatch.setattr(bench_run, "OUT_DIR", tmp_path / "out")
    seen = {}
    set_up = bench_run.set_up

    def keep_driver(*a, **kw):
        out = set_up(*a, **kw)
        seen["driver"] = out[0]
        return out

    monkeypatch.setattr(bench_run, "set_up", keep_driver)
    rc = bench_run.main(["--workload", "affinity-1k.burst", "--seed", str(SEED),
                         "--seconds", "1", "--trace", "1",
                         "--benchmark-file", str(path)])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["attempted"] % 10000 == 0
    assert any("cycles per round [1]" in ln for ln in lines)
    assert any("probe: 0 of 12" in ln for ln in lines)
    assert any("0 programs lowered inside the window" in ln for ln in lines)
    assert {"host_lanes_ms", "commit_lane_ms", "device_lane_ms",
            "ingest_us_per_pod"} <= set(result["metrics"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    # the two guarantees are in the cell's correct, by the file's checks
    assert "validate: affinity_outside = 0 (limit 0)" in lines
    assert "validate: anti_shared = 0 (limit 0)" in lines
    assert result["compared"]["anti_shared"] == {"value": 0, "limit": 0}
    assert any("own checks ['affinity']" in ln for ln in lines)
    # and by hand, round by round, on the same binds
    index = {n: i for i, n in enumerate(generate.node_names(cfg))}
    zone = np.arange(1000) % 16
    rounds = [r for r in seen["driver"].rounds if r.plan.n_pods == 10000]
    assert len(rounds) >= 3                 # warm-up, window, the probe's fill
    for r in rounds:
        hosts = {k: h for _t, keys, hs in r.arrivals for k, h in zip(keys, hs)}
        pod_node = np.array([index[hosts[k]] for k in r.plan.keys()])
        v = affinity_ref.violations(r.plan.gang_kind, r.plan.gang, pod_node, zone)
        assert v["affinity_pods"] > 300 and v["anti_pods"] > 300
        assert (v["affinity_outside"], v["anti_shared"]) == (0, 0), r.plan.tag
        assert v["spread_gangs"] > 80
