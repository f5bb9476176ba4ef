"""``BENCHMARK.json`` against the contract's shape: allowed characters, the
keys of every entry, bounds, and that every cell's files resolve.

Every test here is a function of ``(bench, root)``, the repo's own by
default: ``test_benchmark_additions.py`` calls them on a copy that has gained
cells, a configuration and metrics by files and entries alone.  What today's
benchmark holds is named once, here (``SEVEN``, ``FIVE``, ``NINETEEN``), as
what must still be there; nothing counts a list a later PR may grow."""

import json
import re

import pytest

from benchmark.harness import cell as cell_mod

ROOT = cell_mod.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
# Today's cells, configurations and all-cell per-layer metrics: a later PR
# adds to them and takes none away.
SEVEN = {"north-10k.burst", "binpack-1k.burst", "north-10k.churn",
         "drf-5k.burst", "affinity-10k.burst", "binpack-1k.churn",
         "hyper-50k.burst"}
FIVE = {"north-10k", "binpack-1k", "drf-5k", "affinity-10k", "hyper-50k"}
NINETEEN = {
    "ingest_us_per_pod", "complete_us_per_pod", "host_lanes_ms",
    "commit_lane_ms", "device_lane_ms", "device_busy_ms_per_round",
    "compiles_in_window", "cycle_unattributed_ms", "cycle_prologue_ms",
    "solve_prep_ms", "device_dispatch_ms", "cycle_obs_ms", "bind_handoff_ms",
    "cycle_gc_ms", "solve_wave_ms_per_round", "coarse_shortlist_ms_per_round",
    "order_lane_ms", "derive_lane_ms", "enqueue_lane_ms"}
EVICTION_KEYS = ("termination_cycles", "settle_cycles", "pods_run",
                 "waiting_fraction", "resident_class", "batch_class")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def per_layer_of(bench, cell):
    """The ``per_layer`` entries a cell reports: those that list no cells,
    and those that name it."""
    return [m for m in bench["per_layer"] if cell in m.get("workloads", [cell])]


def reports_its_per_layer(c, bench):
    """A loaded cell's per-layer metrics are those entries, today's
    nineteen among them and whatever files have added since."""
    names = [m["name"] for m in c.per_layer]
    assert names == [m["name"] for m in per_layer_of(bench, c.name)]
    assert set(names) >= NINETEEN


def test_top_level_keys_and_size(bench=BENCH, root=ROOT):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (root / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32 and all(map(_line, bench["command"]))
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (root / p).is_dir()


def test_every_file_under_paths_has_an_allowed_name(bench=BENCH, root=ROOT):
    for p in bench["paths"]:
        for f in (root / p).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert PATH.match(str(f.relative_to(root))), f


def test_todays_cells_and_configurations_are_still_there(bench=BENCH, root=ROOT):
    """The one place that names them: as subsets, and the seven as plain."""
    assert SEVEN <= {w["name"] for w in bench["workloads"]}
    assert FIVE <= {c["name"] for c in bench["configs"]}
    assert NINETEEN <= {m["name"] for m in bench["per_layer"]
                        if "workloads" not in m}
    for name in sorted(SEVEN):
        assert not holds_evictions(cell_mod.load_cell(name, root / "BENCHMARK.json"))


def test_configs(bench=BENCH, root=ROOT):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        held = json.loads((root / c["file"]).read_text())
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        for key in ("guarantees", "capacity_arithmetic", "assumed",
                    "scheduler_conf", "backlog_pods"):
            assert key in held


def test_workloads(bench=BENCH, root=ROOT):
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and _line(w["why"])
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def test_metrics(bench=BENCH, root=ROOT):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert len(e2e) == len(bench["end_to_end"]) <= 16
    assert len(layer) == len(bench["per_layer"]) <= 128
    assert not set(e2e) & set(layer)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in layer.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES and _line(m["layer"])
    for m in list(e2e.values()) + list(layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)


def holds_evictions(c):
    """A cell is *plain* when its configuration has no tiers and no queue
    that may be reclaimed from and its traffic none of the keys of the
    kubelet's side and the tiers; else it is an eviction cell.  Under
    ``entry: jobs`` ``pods_run`` is no such key: a Job reads Running only of
    pods that run, and the loader demands it."""
    jobs = c.traffic.get("entry") == "jobs"
    return ("priority_classes" in c.config
            or "reclaimable" in c.config.get("queues", {})
            or any(key in c.traffic for key in EVICTION_KEYS
                   if not (jobs and key == "pods_run")))


def eviction_clause(c):
    """What an eviction cell's files must state, from their numbers alone:
    a cluster kept full *is* the deployment."""
    sizes, cfg = c.sizes(), c.config
    classes = {k["name"]: k for k in cfg.get("priority_classes", [])}
    low, high = classes.get(sizes["resident_class"]), classes.get(sizes["batch_class"])
    assert low and high and high["value"] > low["value"], \
        "the batch's class outranks the residents'"
    gang = low.get("gang") or cfg["gang"]
    size = max(gang.get("sizes", [gang.get("size", 1)]))
    assert size == 1 or gang.get("min_member", size) < size, \
        "a resident gang can lose a pod"
    assert sizes["pods_run"] and sizes["resident_pods"] > 0, \
        "the residents run"
    nodes, cpu = cfg["nodes"], cfg["pods"]["cpu_choices"]
    # full whatever the deck deals: a burst fits only by taking room
    assert (sizes["resident_pods"] + sizes["batch_pods"]) * min(cpu) \
        > nodes["count"] * nodes["cpu"], "a burst finds the cluster full"
    assert sizes["settle_cycles"] >= 1 + sizes["termination_cycles"], \
        "the victims settle before the next burst"
    assert cfg["guarantees"].get("checks"), "a check of the victims, by name"


@pytest.mark.parametrize("name", CELLS)
def test_every_cells_files_resolve(name, bench_file=ROOT / "BENCHMARK.json"):
    c = cell_mod.load_cell(name, bench_file)
    assert c.config["name"] == c.config_name
    assert c.traffic["name"] == c.traffic_name
    sizes = c.sizes()
    gang = c.config["gang"]["size"]
    assert sizes["batch_pods"] % gang == 0 and sizes["resident_pods"] % gang == 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for spec in c.per_layer:
        on_file = json.loads((c.home / "layer_metrics"
                              / f"{spec['name']}.json").read_text())
        for key in ("unit", "layer", "moves"):
            assert on_file[key] == spec[key], (spec["name"], key)
        assert spec["reader"] in ("span", "lane", "profile", "counter",
                                  "record", "span_self")
    if holds_evictions(c):
        return eviction_clause(c)
    # none of the seven holds an eviction, a tier or a pod that may wait;
    # pods run in a plain cell only where its gangs enter as Jobs
    assert (sizes["termination_cycles"], sizes["settle_cycles"],
            sizes["pods_run"], sizes["waiting_pods"], sizes["resident_class"],
            sizes["batch_class"]) \
        == (0, 0, sizes["entry"] == "jobs", 0, None, None)
    assert sizes["entry"] in ("pods", "jobs") and sizes["max_pumps"] >= 1
    assert "priority_classes" not in c.config
    assert "reclaimable" not in c.config.get("queues", {})
    # demand stays under capacity, by the file's own arithmetic
    nodes = c.config["nodes"]
    pods = sizes["resident_pods"] + sizes["batch_pods"]
    assert pods * max(c.config["pods"]["cpu_choices"]) < nodes["count"] * nodes["cpu"]
    assert pods * max(c.config["pods"]["mem_gi_choices"]) < nodes["count"] * nodes["memory_gi"]
    assert pods < nodes["count"] * nodes["pods"]


def test_the_harness_takes_only_what_it_may_from_the_program():
    """bench.py, synth.py, oracle.py, the object session and ``Service`` are
    not imported; no knob of the program is set; the harness itself watches
    nothing (the controllers it builds under ``entry: jobs`` do, as in a
    deployment)."""
    banned = re.compile(r"^\s*(from|import)\s+(bench|volcano_tpu\.(synth|oracle|"
                        r"session|framework|actions|service|sim))\b", re.M)
    for f in (ROOT / "benchmark").rglob("*.py"):
        text = f.read_text()
        assert not banned.search(text), f
        assert "VOLCANO_TPU_" not in text, f
        assert not re.search(r"\.watch\(", text), f
    ref = (ROOT / "benchmark" / "reference" / "score_ref.py").read_text()
    assert "volcano_tpu" not in ref.split('"""', 2)[2]
    val = (ROOT / "benchmark" / "harness" / "validate.py").read_text()
    assert "import volcano_tpu" not in val and "from volcano_tpu" not in val
    for f in (ROOT / "benchmark" / "reference").glob("*.py"):
        body = f.read_text()
        assert "import volcano_tpu" not in body and "from volcano_tpu" not in body, f
    # What is imported from the program, all of it, and what the kubelet's
    # side of a cycle calls on the store: README.md names each.
    taken = set()
    for f in (ROOT / "benchmark").rglob("*.py"):
        for mod, names in re.findall(
                r"^\s*from (volcano_tpu[\w.]*) import (\([^)]*\)|[^\n]+)",
                f.read_text(), re.M):
            taken |= {(mod, n.strip()) for n in names.strip("()").split(",")
                      if n.strip()}
    assert taken == {
        ("volcano_tpu", "device"), ("volcano_tpu.cache", "ClusterStore"),
        ("volcano_tpu.scheduler", "Scheduler"),
        ("volcano_tpu.api", "Node"), ("volcano_tpu.api", "Queue"),
        ("volcano_tpu.api", "PriorityClass"), ("volcano_tpu.api", "PodPhase"),
        ("volcano_tpu.api", "GROUP_NAME_ANNOTATION"),
        ("volcano_tpu.api", "AffinityTerm"), ("volcano_tpu.api", "Pod"),
        ("volcano_tpu.api", "PodGroup"),
        # entry: jobs (harness/jobs.py, generate.to_jobs): these and no more
        ("volcano_tpu.controllers", "ControllerManager"),
        ("volcano_tpu.controllers", "Job"),
        ("volcano_tpu.controllers", "TaskSpec"),
        ("volcano_tpu.controllers", "LifecyclePolicy"),
        ("volcano_tpu.controllers", "JobPhase"),
        ("volcano_tpu.webhooks", "AdmittedStore")}
    assert not [name for _mod, name in taken if name.startswith("_")]
    readme = (ROOT / "benchmark" / "README.md").read_text()
    for _mod, name in taken:
        assert f"`{name}`" in readme or f"`volcano_tpu.{name}`" in readme, name
    loop_py = (ROOT / "benchmark" / "harness" / "loop.py").read_text()
    calls = set(re.findall(r"\bstore\.(\w+)\b", loop_py))
    assert calls == {"add_queue", "add_priority_class", "add_node",
                     "async_bind", "add_pod_group", "add_pod", "update_pod",
                     "delete_pod", "delete_pod_group", "flush_binds", "pods",
                     "flight", "close"}, calls
    # entry: jobs: what harness/jobs.py reads and calls, on the store (the
    # kubelet's side and the records a Job leaves) and on the two objects
    # that stand for the controller plane
    jobs_py = (ROOT / "benchmark" / "harness" / "jobs.py").read_text()
    body = jobs_py.split('"""', 2)[2]
    on_store = set(re.findall(r"\bstore\.(\w+)\b", body))
    assert on_store == {"pods", "batch_jobs", "pod_groups", "update_pod",
                        "delete_pod"}, on_store
    assert set(re.findall(r"\badmitted\.(\w+)\b", body + loop_py)) \
        == {"add_batch_job", "delete_batch_job", "add_queue"}
    assert set(re.findall(r"\bmanager\.(\w+)\b", body)) == {"process"}
    for name in (calls | on_store | {"add_batch_job", "delete_batch_job",
                                     "process"}) - {"async_bind", "close"}:
        assert f"`{name}`" in readme or f"`store.{name}" in readme \
            or f"`ClusterStore.{name}" in readme \
            or f".{name}`" in readme or f".{name}()`" in readme, name
