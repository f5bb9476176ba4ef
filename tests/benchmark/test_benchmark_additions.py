"""``benchmark/README.md``'s table "Adding to it: files and entries only",
made row by row on a copy of the real benchmark in every run of the suite: a
traffic mix and a plain cell under it, a second plain cell on four chips, a
configuration that holds evictions (``preempt-10k`` at ``PERF.md`` section
7 (3)'s shape) with its traffic, its check and its cell, a per-layer metric
of that cell alone and one of every cell, a traffic mix whose gangs enter as
Jobs (``entry: jobs``) with a plain cell under it and, in the shape the next
``model_config`` PR brings (``service-gang``, BASELINE configs[0]), a
configuration with ``gang.min_member``, a ``job`` block, ``probe.fill_pods``
and a check of its own, its cell under that traffic and ten per-layer metrics
of that cell alone, one of them a nested key of ``between``.  New files and
appended entries, nothing else; the contract, the configuration tests'
loading assertions and every test of this directory that goes over the real
benchmark's cells, configurations or metrics then pass on the copy, and the
cells the benchmark has load as they did.  Files are loaded; no scheduler
runs.

Every name added here ends in ``-added`` or ``_added``, so that the real
``preempt-10k`` (or any other cell, mix or metric) can come in beside them:
a later PR gives its own files other names than these."""

import copy
import json
import shutil

import pytest

import test_benchmark_affinity_config as affinity_test
import test_benchmark_contract as contract
import test_benchmark_cycle_lanes as lanes_test
import test_benchmark_drf_config as drf_test
import test_benchmark_generate as generate_test
import test_benchmark_hyper_config as hyper_test
import test_benchmark_preempt_config as preempt_test
import test_benchmark_whatif_counters as whatif_test
from benchmark.harness import cell as cell_mod
from benchmark.harness import generate
from test_benchmark_cell import (CONF_PREEMPT, JOB_BLOCK,  # noqa: F401
                                 toy_preempt)

ROOT = cell_mod.ROOT
EVICT = "preempt-10k-added.evict-added"
NEW_CELLS = {
    "affinity-10k.churn-added": ("affinity-10k", "churn-added", 1),  # (1) plain
    "hyper-50k.churn-added": ("hyper-50k", "churn-added", 4),   # (2) four chips
    EVICT: ("preempt-10k-added", "evict-added", 1),         # (3) holds evictions
    "binpack-1k.asjobs-added": ("binpack-1k", "asjobs-added", 1),   # (6) as Jobs
    "service-gang-added.asjobs-added":                  # and a Job's own config
        ("service-gang-added", "asjobs-added", 1),
}
JOBS = "binpack-1k.asjobs-added"
SERVICE_GANG = "service-gang-added.asjobs-added"
AS_JOBS = (JOBS, SERVICE_GANG)
# (4) of the evicting cell alone, (5) of every cell
NEW_METRICS = {
    "preempt_plan_ms_added": ("what-if engine", "span_self",
                              {"name": "preempt_plan", "scale": 1e3}, [EVICT]),
    "encode_lane_ms_added": ("fast cycle host lanes", "lane",
                             {"lanes": ["encode"], "scale": 1e3}, None),
}
ALL_CELLS_METRIC = "encode_lane_ms_added"
# (6) ten of the cell that enters as Jobs alone: its spans, and the store's
# own account of the time between two cycles by a dotted key
for _name, _reader, _args in (
        ("admit_us_per_pod_added", "span", {"span": "admit", "per": "pod", "scale": 1e6}),
        ("pump_ms_added", "span", {"span": "pump", "scale": 1e3}),
        ("reconcile_ms_added", "span", {"span": "reconcile", "scale": 1e3}),
        ("submit_ms_added", "span", {"span": "submit", "scale": 1e3}),
        ("complete_ms_added", "span", {"span": "complete", "scale": 1e3}),
        ("notify_self_ms_added", "span_self", {"name": "commit:notify", "scale": 1e3}),
        ("pod_updates_added", "record", {"key": "between.events.Pod/update.n"}),
        ("pod_adds_added", "record", {"key": "between.events.Pod/add.n"}),
        ("job_spec_rows_added", "record", {"key": "between.spec_rows", "reduce": "max"}),
        ("lock_held_ms_added", "record", {"key": "between.lock_held_s", "scale": 1e3})):
    NEW_METRICS[_name] = ("admission and controllers", _reader, _args, [SERVICE_GANG])
STUB_REF = '''"""A stand-in: the victims' reference comes with the cell."""


def check(events, nodes, config):
    return {"victims_unjudged": 0}
'''
JOB_STUB_REF = '''"""A stand-in: the replay of a Job's lifecycle comes with the cell."""


def check(events, nodes, config):
    return {"pods_no_job_desires": 0}
'''
FILL_PODS = 32_000


def add_to(root):
    """The additions under ``root``, which holds a ``BENCHMARK.json`` and its
    ``benchmark/``; no file that is there is written to but that list."""
    home = root / "benchmark"
    cfg = json.loads((home / "configs" / "north-10k.json").read_text())
    nodes = cfg["nodes"]
    cfg.update(
        name="preempt-10k-added", source="BASELINE.json configs[3]: Preempt + reclaim "
        "actions with PriorityClass, 10k nodes, oversubscribed queues",
        scheduler_conf=CONF_PREEMPT, reduced=["chips"],
        pods={"cpu_choices": [16], "mem_gi_choices": [32]},
        backlog_pods=nodes["count"] * nodes["cpu"] // 16,   # four a node: full
        queues={"count": 4, "weights": [1, 2, 4, 8], "reclaimable": [True] * 4},
        priority_classes=[
            {"name": "low", "value": 10, "share": 0.9,
             "gang": {"size": 8, "min_member": 1, "max_unavailable": 8}},
            # over its share, ``default`` may not grow: the bursts are the
            # other three tenants'
            {"name": "high", "value": 1000, "share": 0.1,
             "queues": ["queue-1", "queue-2", "queue-3"]}],
        guarantees=dict(cfg["guarantees"], checks=["preempt_added"]))
    traffic = {
        "name": "evict-added", "resident_fraction": 1.0,
        "batch_fraction": 8 / cfg["backlog_pods"], "waiting_fraction": 0.01,
        "warmup_rounds": 4, "max_cycles": 4, "settle_cycles": 2,
        "termination_cycles": 0, "pods_run": True, "resident_class": "low",
        "batch_class": "high"}
    churn = json.loads((home / "traffic" / "churn.json").read_text())
    burst = json.loads((home / "traffic" / "burst.json").read_text())
    # (6) burst with the user one layer further out: admission, the controllers
    asjobs = dict(burst, name="asjobs-added", entry="jobs", pods_run=True,
                  max_cycles=6, max_pumps=8)
    # ... and the configuration of a Job: examples/job.yaml on north-10k's nodes
    north = json.loads((home / "configs" / "north-10k.json").read_text())
    jobs_cfg = dict(
        north, name="service-gang-added", source="BASELINE.json configs[0]: "
        "example/job.yaml 3-replica gang PodGroup, through admission and the "
        "controllers", pods={"cpu_choices": [1], "mem_gi_choices": [1]},
        gang={"size": 6, "min_member": 3}, queues={"count": 1}, job=JOB_BLOCK,
        backlog_pods=100_002,
        probe={"probes": 12, "fill_pods": FILL_PODS},
        guarantees=dict(north["guarantees"], checks=["job_added"]),
        capacity_arithmetic="at most 100,002 pods of a round + the probe's "
        "100,002 + its own fill of 32,000 + 12 probe pods, x 1 cpu = 232,016 of "
        "10,000 x 64 = 640,000 cpu; x 1 Gi of 2,560,000 Gi; of 2,560,000 pod slots")
    new = {home / "configs" / "preempt-10k-added.json": json.dumps(cfg),
           home / "configs" / "service-gang-added.json": json.dumps(jobs_cfg),
           home / "reference" / "job_added_ref.py": JOB_STUB_REF,
           home / "traffic" / "evict-added.json": json.dumps(traffic),
           home / "traffic" / "churn-added.json":
               json.dumps(dict(churn, name="churn-added")),
           home / "traffic" / "asjobs-added.json": json.dumps(asjobs),
           home / "reference" / "preempt_added_ref.py": STUB_REF}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for held, why in ((cfg, "a full cluster"), (jobs_cfg, "a Job, as the user sends it")):
        bench["configs"].append({
            "name": held["name"], "source": held["source"], "why": why,
            "reduced": held["reduced"],
            "file": f"benchmark/configs/{held['name']}.json"})
    for name, (config, mix, chips) in NEW_CELLS.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": chips, "why": name})
    for name, (layer, reader, args, cells) in NEW_METRICS.items():
        unit = "us/pod" if args.get("per") else "ms" if "scale" in args else "count"
        on_file = {"name": name, "unit": unit, "layer": layer,
                   "moves": "backlog_to_bind_ms", "reader": reader, "args": args}
        new[home / "layer_metrics" / f"{name}.json"] = json.dumps(on_file)
        entry = {"name": name, "unit": unit, "better": "lower", "layer": layer,
                 "source": {"span": "host_clock", "record": "program_counter"}
                 .get(reader, "program_span"), "moves": "backlog_to_bind_ms"}
        bench["per_layer"].append(dict(entry, workloads=cells) if cells else entry)
    for path, text in new.items():
        assert not path.exists(), path
        path.write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root / "BENCHMARK.json"


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """``BENCHMARK.json`` of a copy of the real benchmark with the additions."""
    root = tmp_path_factory.mktemp("grown")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "tests" / "benchmark").mkdir(parents=True)   # a path of the file's
    return add_to(root)


def test_no_byte_of_an_original_file_differs_and_entries_are_only_appended(grown):
    for f in (ROOT / "benchmark").rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts:
            assert (grown.parent / f.relative_to(ROOT)).read_bytes() == f.read_bytes(), f
    new = json.loads(grown.read_text())
    for key, was in contract.BENCH.items():
        if isinstance(was, list) and isinstance(was[0], dict):
            assert all(entry in new[key] for entry in was), key
        else:
            assert new[key] == was, key
    assert [w["name"] for w in new["workloads"]] == contract.CELLS + list(NEW_CELLS)


@pytest.mark.parametrize("test", [
    contract.test_top_level_keys_and_size,
    contract.test_every_file_under_paths_has_an_allowed_name,
    contract.test_todays_cells_and_configurations_are_still_there,
    contract.test_configs, contract.test_workloads, contract.test_metrics],
    ids=lambda f: f.__name__[5:])
def test_the_contract_holds_of_the_grown_benchmark(grown, test):
    test(json.loads(grown.read_text()), grown.parent)


@pytest.mark.parametrize("name", list(NEW_CELLS))
def test_every_added_cell_resolves(grown, name):
    contract.test_every_cells_files_resolve(name, grown)
    c = cell_mod.load_cell(name, grown)
    assert contract.holds_evictions(c) == (name == EVICT)
    assert c.chips == NEW_CELLS[name][2]
    # what every cell of the benchmark reports, and what was added for it
    every = [m["name"] for m in contract.per_layer_of(contract.BENCH, name)]
    assert [m["name"] for m in c.per_layer] == every + [
        m for m, (*_file, cells) in NEW_METRICS.items()
        if cells is None or name in cells]


@pytest.mark.parametrize("test", [
    affinity_test.test_the_file_loads_and_states_its_deployment,
    drf_test.test_the_file_loads_and_states_its_deployment,
    hyper_test.test_the_cell_is_a_four_chip_cell_within_their_share],
    ids=["affinity", "drf", "hyper"])
def test_the_configuration_tests_load_from_the_grown_benchmark(grown, test):
    test(grown)


@pytest.mark.parametrize("name", contract.CELLS)
def test_the_benchmarks_cells_load_as_they_did(grown, name):
    contract.test_every_cells_files_resolve(name, grown)
    was, now = cell_mod.load_cell(name), cell_mod.load_cell(name, grown)
    assert (now.sizes(), now.end_to_end, now.chips, now.config, now.traffic) \
        == (was.sizes(), was.end_to_end, was.chips, was.config, was.traffic)
    added = [m for m in now.per_layer if m not in was.per_layer]
    assert [m for m in now.per_layer if m in was.per_layer] == was.per_layer
    assert [m["name"] for m in added] == [ALL_CELLS_METRIC]


def test_the_cell_whose_gangs_enter_as_jobs_is_plain_and_says_so(grown):
    """``entry`` and ``max_pumps`` are the traffic's; every cell that was
    there enters as pods, as it did."""
    c = cell_mod.load_cell(JOBS, grown)
    sizes = c.sizes()
    assert (sizes["entry"], sizes["max_pumps"], sizes["pods_run"],
            sizes["max_cycles"]) == ("jobs", 8, True, 6)
    assert not contract.holds_evictions(c)
    was = cell_mod.load_cell("binpack-1k.burst", grown).sizes()
    assert (was["entry"], was["max_pumps"], was["pods_run"]) == ("pods", 4, False)
    assert {k: v for k, v in sizes.items()
            if k not in ("entry", "max_pumps", "pods_run", "max_cycles")} \
        == {k: v for k, v in was.items()
            if k not in ("entry", "max_pumps", "pods_run", "max_cycles")}
    # every cell says how it enters: as pods unless its traffic says jobs,
    # and one that does states what a Jobs cell must
    as_jobs = []
    for name in contract.CELLS + list(NEW_CELLS):
        cell = cell_mod.load_cell(name, grown)
        entry = cell.sizes()["entry"]
        if cell.traffic.get("entry") != "jobs":
            assert entry == "pods", name
            continue
        as_jobs.append(name)
        assert entry == "jobs" and cell.sizes()["pods_run"], name
        assert cell.sizes()["max_cycles"] >= 2, name
        assert not any(float(x) > 0
                       for x in cell.config.get("affinity_mix", {}).values()), name
        assert not contract.holds_evictions(cell), name
    # the two added here are held so; a real cell may be, and none is yet
    assert [name for name in as_jobs if name in NEW_CELLS] == list(AS_JOBS)
    # the same draw as the burst's, under the names the controller will give
    plan = generate.Generator(c.config, 2**31 + 52, entry=sizes["entry"]) \
        .plan(sizes["batch_pods"], "w0000")
    assert plan.names[0] == "w0000-pg-000000-worker-0"
    assert len(generate.to_jobs(plan, iter(range(1, 10**6)),
                                c.config.get("job"))) == len(plan.gang_names)


def test_the_configuration_of_a_job_states_its_gang_its_block_and_its_fill(grown):
    """``service-gang``'s shape by files and entries: an elastic gang without
    a class, ``examples/job.yaml``'s block, a check of its own and a probe
    that brings a fill of its own, which the file's arithmetic counts."""
    c = cell_mod.load_cell(SERVICE_GANG, grown)
    contract.test_every_cells_files_resolve(SERVICE_GANG, grown)
    cfg, sizes = c.config, c.sizes()
    assert (sizes["entry"], sizes["pods_run"], sizes["batch_pods"]) \
        == ("jobs", True, 100_002)
    assert cfg["gang"] == {"size": 6, "min_member": 3} and cfg["job"] == JOB_BLOCK
    assert cfg["probe"] == {"probes": 12, "fill_pods": FILL_PODS}
    assert f"{FILL_PODS:,}" in cfg["capacity_arithmetic"]
    assert cfg["guarantees"]["checks"] == ["job_added"]
    assert (c.home / "reference" / "job_added_ref.py").is_file()
    assert "priority_classes" not in cfg and not contract.holds_evictions(c)
    plan = generate.Generator(cfg, 2**31 + 54, entry="jobs").plan(600, "w0000")
    assert plan.sizes().tolist() == [6] * 100
    assert plan.gang_min_member.tolist() == [3] * 100
    job, keys = generate.to_jobs(plan, iter(range(1, 10**4)), cfg["job"])[0]
    assert (job.min_available, job.max_retry, sorted(job.plugins), len(keys)) \
        == (3, 5, ["env", "ssh", "svc"], 6)
    # ten metrics of this cell alone, one a nested key of ``between``
    own = [m for m in c.per_layer if m.get("workloads") == [SERVICE_GANG]]
    assert len(own) == 10
    assert {"reader": "record", "args": {"key": "between.events.Pod/update.n"}}.items() \
        <= next(m for m in own if m["name"] == "pod_updates_added").items()
    assert not [m for m in cell_mod.load_cell(JOBS, grown).per_layer if m in own]


OVER_THE_REAL_BENCHMARK = (
    [(whatif_test.test_the_counter_is_an_appended_entry_and_a_file_of_this_cell, m)
     for m in whatif_test.TWO]
    + [(preempt_test.test_the_metric_reports_on_this_cell_and_no_other, m)
       for m in preempt_test.NINE]
    + [(preempt_test.test_the_seven_cells_load_as_they_did, n)
       for n in sorted(contract.SEVEN)]
    + [(lanes_test.test_the_file_resolves_against_its_entry, n)
       for n in sorted(lanes_test.LANE_ALL) + sorted(lanes_test.PROFILE)]
    + [(lanes_test.test_they_are_the_last_eight_entries_and_nothing_else_moved,),
       (hyper_test.test_the_file_states_its_deployment,)]
    + [(generate_test.test_the_seven_cells_plans_are_the_parents_byte_for_byte, n)
       for n in sorted(generate_test.PARENT_DIGEST)])


@pytest.mark.parametrize(
    "test", OVER_THE_REAL_BENCHMARK,
    ids=lambda t: "-".join([t[0].__module__[15:], t[0].__name__[5:30], *t[1:]]))
def test_what_goes_over_the_real_benchmark_holds_of_the_grown_one(
        grown, monkeypatch, test):
    """The tests of this directory that go over ``BENCHMARK.json``'s cells,
    configurations or metrics by a loop or by name, read here with the grown
    file in the real one's place: a cell that enters as Jobs, its
    configuration and its ten metrics fail none of them."""
    bench = json.loads(grown.read_text())
    load = cell_mod.load_cell
    monkeypatch.setattr(cell_mod, "load_cell", lambda name, benchmark_file=grown:
                        load(name, benchmark_file))
    for module in (contract, lanes_test, hyper_test):
        monkeypatch.setattr(module, "BENCH", bench)
    test[0](*test[1:])


def test_the_evicting_cells_bursts_go_to_the_queues_its_class_names(grown):
    c = cell_mod.load_cell(EVICT, grown)
    gen = generate.Generator(c.config, 2**31 + 42)
    low = gen.plan(64, "resident", klass=c.sizes()["resident_class"])
    gen.batch_class = c.sizes()["batch_class"]
    bursts = [gen.plan(c.sizes()["batch_pods"], f"w{i}") for i in range(6)]
    assert set(low.gang_queue) == set(generate.queue_names(c.config))
    assert [b.gang_queue for b in bursts] \
        == [["queue-3"], ["queue-1"], ["queue-2"]] * 2


@pytest.mark.parametrize("where,key,value,clause", [
    ("config", "guarantees", {"checks": []}, "a check of the victims"),
    ("traffic", "pods_run", False, "the residents run"),
    ("traffic", "resident_fraction", 0.99, "finds the cluster full")])
def test_an_eviction_cell_that_states_less_fails_its_clause(grown, where, key,
                                                            value, clause):
    c = copy.deepcopy(cell_mod.load_cell(EVICT, grown))
    contract.eviction_clause(c)
    getattr(c, where)[key] = value
    with pytest.raises(AssertionError, match=clause):
        contract.eviction_clause(c)


def test_the_toys_files_pass_the_eviction_clause(toy_preempt):  # noqa: F811
    """The toy that runs evictions on the CPU (``test_benchmark_cell.py``)
    and the clause cannot drift apart: if one misses, the toy's files change."""
    contract.test_every_cells_files_resolve("toypre.pre", toy_preempt)
    assert contract.holds_evictions(cell_mod.load_cell("toypre.pre", toy_preempt))
