"""``BENCHMARK.json`` against the contract's shape: allowed characters, the
keys of every entry, bounds, and that every cell's files resolve."""

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


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32 and all(map(_line, BENCH["command"]))
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()


def test_every_file_under_paths_has_an_allowed_name():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts or f.suffix == ".pyc":
                continue
            assert PATH.match(str(f.relative_to(ROOT))), f


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        held = json.loads((ROOT / c["file"]).read_text())
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        for key in ("guarantees", "capacity_arithmetic", "assumed",
                    "scheduler_conf", "backlog_pods"):
            assert key in held


def test_workloads():
    assert len(set(CELLS)) == len(CELLS) and 1 <= len(CELLS) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and _line(w["why"])
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layer = {m["name"]: m for m in BENCH["per_layer"]}
    assert len(e2e) == len(BENCH["end_to_end"]) <= 16
    assert len(layer) == len(BENCH["per_layer"]) <= 128
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
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_every_cells_files_resolve(name):
    c = cell_mod.load_cell(name)
    assert c.config["name"] == c.config_name
    assert c.traffic["name"] == c.traffic_name
    sizes = c.sizes()
    gang = c.config["gang"]["size"]
    assert sizes["batch_pods"] % gang == 0 and sizes["resident_pods"] % gang == 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for spec in c.per_layer:
        on_file = json.loads((ROOT / "benchmark" / "layer_metrics"
                              / f"{spec['name']}.json").read_text())
        for key in ("unit", "layer", "moves"):
            assert on_file[key] == spec[key], (spec["name"], key)
        assert spec["reader"] in ("span", "lane", "profile", "counter",
                                  "record", "span_self")
    # none of the seven holds an eviction, a tier or a pod that may wait
    assert (sizes["termination_cycles"], sizes["settle_cycles"],
            sizes["pods_run"], sizes["waiting_pods"], sizes["resident_class"],
            sizes["batch_class"]) == (0, 0, False, 0, None, None)
    assert "priority_classes" not in c.config
    assert "reclaimable" not in c.config.get("queues", {})
    # demand stays under capacity, by the file's own arithmetic
    nodes = c.config["nodes"]
    pods = sizes["resident_pods"] + sizes["batch_pods"]
    assert pods * max(c.config["pods"]["cpu_choices"]) < nodes["count"] * nodes["cpu"]
    assert pods * max(c.config["pods"]["mem_gi_choices"]) < nodes["count"] * nodes["memory_gi"]
    assert pods < nodes["count"] * nodes["pods"]


def test_the_harness_takes_only_what_it_may_from_the_program():
    """bench.py, synth.py, oracle.py and the object session are not imported;
    no knob of the program is set."""
    banned = re.compile(r"^\s*(from|import)\s+(bench|volcano_tpu\.(synth|oracle|"
                        r"session|framework|actions))\b", re.M)
    for f in (ROOT / "benchmark").rglob("*.py"):
        text = f.read_text()
        assert not banned.search(text), f
        assert "VOLCANO_TPU_" not in text, f
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
        ("volcano_tpu.api", "PodGroup")}
    readme = (ROOT / "benchmark" / "README.md").read_text()
    for _mod, name in taken:
        assert f"`{name}`" in readme or f"`volcano_tpu.{name}`" in readme, name
    loop_py = (ROOT / "benchmark" / "harness" / "loop.py").read_text()
    calls = set(re.findall(r"\bstore\.(\w+)\b", loop_py))
    assert calls == {"add_queue", "add_priority_class", "add_node",
                     "async_bind", "add_pod_group", "add_pod", "update_pod",
                     "delete_pod", "delete_pod_group", "flush_binds", "pods",
                     "flight", "close"}, calls
    for name in calls - {"async_bind", "close"}:
        assert f"`{name}`" in readme or f"`store.{name}" in readme \
            or f"`ClusterStore.{name}" in readme, name
