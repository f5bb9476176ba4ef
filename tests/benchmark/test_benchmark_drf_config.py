"""``drf-5k``: the configuration file says what the issue says, its branch
of the one generator (several weighted queues, a set of gang sizes) does what
the file says, and a tenth-size copy runs ``run.py``'s whole path on the CPU."""

import json
import os
from collections import Counter

from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import generate
from test_benchmark_contract import reports_its_per_layer

ROOT = cell_mod.ROOT
SEED = 2**31 + 2027


def test_the_file_loads_and_states_its_deployment(bench_file=ROOT / "BENCHMARK.json"):
    c = cell_mod.load_cell("drf-5k.burst", bench_file)
    cfg = c.config
    assert c.chips == 1 and c.config_name == "drf-5k" and c.traffic_name == "burst"
    bench = json.loads(bench_file.read_text())
    entry = next(e for e in bench["configs"] if e["name"] == "drf-5k")
    assert entry["source"] == cfg["source"] and "configs[2]" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["chips"]
    assert cfg["nodes"]["count"] == 5000 and cfg["backlog_pods"] == 50000
    assert cfg["queues"]["count"] == 4 and cfg["queues"]["weights"] == [1, 2, 4, 8]
    assert cfg["gang"]["sizes"] == [2, 4, 8, 16]
    north = json.loads((ROOT / "benchmark" / "configs" / "north-10k.json").read_text())
    assert cfg["scheduler_conf"] == north["scheduler_conf"]
    assert cfg["pods"] == north["pods"] and cfg["probe"]["probes"] == 48
    assert dict(cfg["nodes"], count=0) == dict(north["nodes"], count=0)
    assert set(north["guarantees"]) < set(cfg["guarantees"])
    assert {"queue_share", "queue_share_under_contention"} <= set(cfg["guarantees"])
    assert c.sizes()["batch_pods"] == 50000 and c.sizes()["resident_pods"] == 0
    # its per-layer metrics: those that list no cells and those that name it
    reports_its_per_layer(c, bench)


def test_the_worst_deal_is_what_the_file_reckons():
    cfg = cell_mod.load_cell("drf-5k.burst").config
    pods = cfg["backlog_pods"] + cfg["probe"]["probes"]
    nodes = cfg["nodes"]
    cpu = pods * max(cfg["pods"]["cpu_choices"])
    mem = pods * max(cfg["pods"]["mem_gi_choices"])
    said = cfg["capacity_arithmetic"]
    for number in (cpu, nodes["count"] * nodes["cpu"], mem,
                   nodes["count"] * nodes["memory_gi"], pods,
                   nodes["count"] * nodes["pods"]):
        assert f"{number:,}" in said, number
    assert (cpu, mem, pods) == (200192, 400384, 50048)
    assert cpu < nodes["count"] * nodes["cpu"]
    # a gang of 16 at 4 cpu fills one empty node exactly
    assert max(cfg["gang"]["sizes"]) * max(cfg["pods"]["cpu_choices"]) == nodes["cpu"]


def test_queues_weights_and_gangs_as_the_file_says():
    cfg = cell_mod.load_cell("drf-5k.burst").config
    weights = {q.name: q.weight for q in generate.to_queues(cfg)}
    # ``default`` is the store's own queue, weight 1; the harness adds the rest
    assert weights == {"queue-1": 2, "queue-2": 4, "queue-3": 8}
    assert generate.queue_names(cfg) == ["default", "queue-1", "queue-2", "queue-3"]
    from volcano_tpu.cache import ClusterStore

    store = ClusterStore()
    for q in generate.to_queues(cfg):
        store.add_queue(q)
    assert {n: q.weight for n, q in store.queues.items()} == {"default": 1, **weights}
    store.close()
    plan = generate.Generator(cfg, SEED).plan(cfg["backlog_pods"], "x")
    assert plan.n_pods == 50000
    per_queue = Counter(plan.gang_queue)
    assert set(per_queue) == set(generate.queue_names(cfg))
    assert max(per_queue.values()) - min(per_queue.values()) <= 1
    sizes = Counter(int(s) for s in plan.gang_min_member)
    assert set(sizes) == {2, 4, 8, 16}
    assert 6000 <= len(plan.gang_names) <= 7400      # ~6,670 gangs of mean 7.5
    # every size reaches every queue
    seen = {(q, int(s)) for q, s in zip(plan.gang_queue, plan.gang_min_member)}
    assert len(seen) == 16


def test_a_tenth_size_copy_runs_end_to_end(tmp_path, monkeypatch, capsys):
    """500 nodes x 5,000 pods, everything else the file's: set-up, window,
    validation, probe, result line, on the CPU; every round bound in one
    cycle and nothing failed."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    home = tmp_path / "benchmark"
    home.mkdir()
    for part in ("layer_metrics", "traffic"):
        os.symlink(ROOT / "benchmark" / part, home / part)
    (home / "configs").mkdir()
    cfg = json.loads((ROOT / "benchmark" / "configs" / "drf-5k.json").read_text())
    cfg.update(name="drf-500", backlog_pods=5000)
    cfg["nodes"]["count"] = 500
    cfg["probe"].update(probes=12, keep_pods=1000)
    (home / "configs" / "drf-500.json").write_text(json.dumps(cfg))
    real["configs"] = [{"name": "drf-500", "source": "a test",
                        "file": "benchmark/configs/drf-500.json",
                        "reduced": [], "why": "a tenth of drf-5k"}]
    real["workloads"] = [{"name": "drf-500.burst", "config": "drf-500",
                          "traffic": "burst", "chips": 1, "why": "a test"}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(real))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                      str(tmp_path / "xla")))
    monkeypatch.setattr(bench_run, "OUT_DIR", tmp_path / "out")
    rc = bench_run.main(["--workload", "drf-500.burst", "--seed", str(SEED),
                         "--seconds", "1", "--trace", "1",
                         "--benchmark-file", str(path)])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["attempted"] % 5000 == 0
    assert any("cycles per round [1]" in ln for ln in lines)
    assert any("probe: 0 of 12" in ln for ln in lines)
    assert {"host_lanes_ms", "commit_lane_ms", "device_lane_ms",
            "ingest_us_per_pod"} <= set(result["metrics"])
