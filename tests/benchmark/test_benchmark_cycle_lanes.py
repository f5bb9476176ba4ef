"""The eight per-layer metrics of ISSUE 25 are data: each file resolves
against its ``BENCHMARK.json`` entry, and its reader returns a number from
a record shaped like the program's — the ``lane`` ones on a toy cell's
traced run (CPU), the ``profile`` ones on ``harness/trace_sample.json``."""

import json
import shutil

import pytest

from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import readers, trace_reduce

ROOT = cell_mod.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LANE = {
    "cycle_prologue_ms": ("cycle driver", ["prologue", "inflight"]),
    "solve_prep_ms": ("fast cycle host lanes", ["solve_prep"]),
    "cycle_obs_ms": ("cycle driver", ["journey", "audit", "record"]),
    "bind_handoff_ms": ("cycle driver", ["bind_handoff"]),
    "cycle_gc_ms": ("cycle driver", ["gc"]),
    "device_dispatch_ms": ("solve", ["device_coarse", "device_fine"]),
}
PROFILE = {
    "solve_wave_ms_per_round": "jit__solve_wave(2)",
    "coarse_shortlist_ms_per_round": "jit__coarse_shortlist(1)",
}


def _spec(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(LANE) + sorted(PROFILE))
def test_the_file_resolves_against_its_entry(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    spec = _spec(name)
    assert set(spec) == {"name", "unit", "layer", "moves", "what", "reader",
                         "args"}
    for key in ("name", "unit", "layer", "moves"):
        assert spec[key] == entry[key]
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
    assert entry["moves"] == "backlog_to_bind_ms"
    assert "workloads" not in entry     # reported in every cell
    assert spec["reader"] in readers.READERS
    assert spec["args"]["scale"] == 1000.0   # the readers work in seconds
    if name in LANE:
        layer, lanes = LANE[name]
        assert (spec["reader"], entry["source"]) == ("lane", "program_span")
        assert spec["layer"] == layer and spec["args"]["lanes"] == lanes
    else:
        assert (spec["reader"], entry["source"]) == ("profile", "device_trace")
        assert spec["layer"] == "solve" and spec["args"]["per"] == "round"


def test_they_are_the_last_eight_entries_and_nothing_else_moved():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:8] == ["ingest_us_per_pod", "complete_us_per_pod",
                         "host_lanes_ms", "commit_lane_ms",
                         "cycle_unattributed_ms", "compiles_in_window",
                         "device_lane_ms", "device_busy_ms_per_round"]
    assert set(names[8:]) == set(LANE) | set(PROFILE) and len(names) == 16


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of a toy cell (24 nodes, batches of 24 pods) with
    every per-layer metric of ``BENCHMARK.json``, on the CPU."""
    tmp = tmp_path_factory.mktemp("lanes")
    home = tmp / "benchmark"
    shutil.copytree(ROOT / "benchmark" / "layer_metrics", home / "layer_metrics")
    (home / "configs").mkdir()
    (home / "traffic").mkdir()
    config = json.loads((ROOT / "benchmark" / "configs"
                         / "binpack-1k.json").read_text())
    config.update(name="toy", backlog_pods=240)
    config["nodes"].update(count=24, zones=3)
    (home / "configs" / "toy.json").write_text(json.dumps(config))
    (home / "traffic" / "drip.json").write_text(json.dumps({
        "name": "drip", "resident_fraction": 0.5, "batch_fraction": 0.1,
        "warmup_rounds": 2, "max_cycles": 4, "profile_seconds": 0.2}))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "toy", "source": "a test",
                         "file": "benchmark/configs/toy.json", "reduced": [],
                         "why": "toy"}]
    bench["workloads"] = [{"name": "toy.drip", "config": "toy",
                           "traffic": "drip", "chips": 1, "why": "toy"}]
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    out_dir, bench_run.OUT_DIR = bench_run.OUT_DIR, tmp / "out"
    try:
        cell = cell_mod.load_cell("toy.drip", path)
        return bench_run.run(cell, 2**31 + 25, 1.0, True)
    finally:
        bench_run.OUT_DIR = out_dir


@pytest.mark.parametrize("name", sorted(LANE))
def test_a_lane_metric_reads_a_number_from_the_programs_record(traced, name):
    assert traced["correct"] is True
    metric = traced["metrics"][name]
    assert metric["unit"] == "ms" and metric["value"] >= 0.0
    if name != "bind_handoff_ms":   # a list append: may round to nothing
        assert metric["value"] > 0.0


def test_the_lanes_partition_what_the_harness_times(traced):
    """``cycle_unattributed_ms`` is the outside check of the inside
    partition: run_once() by the harness's clock minus every top-level
    lane is the record's own residual plus the sealing."""
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    named = (m["host_lanes_ms"] + m["device_lane_ms"] + m["cycle_prologue_ms"]
             + m["solve_prep_ms"] + m["cycle_obs_ms"] + m["bind_handoff_ms"]
             + m["cycle_gc_ms"])
    assert 0.0 <= m["cycle_unattributed_ms"] < 0.25 * named
    # The dispatch legs lie inside the device lane.
    assert m["device_dispatch_ms"] <= m["device_lane_ms"]


@pytest.mark.parametrize("name", sorted(PROFILE))
def test_a_profile_metric_reads_its_program_from_the_sample_trace(name):
    sample = json.loads((ROOT / "benchmark" / "harness"
                         / "trace_sample.json").read_text())
    prof = trace_reduce.reduce(sample, window_s=4.0)
    obs = readers.Observed(rounds=[], profile=prof, profiled_rounds=2)
    value = readers.read(_spec(name), obs)
    # the pattern takes the program whatever its id, and no other
    assert value == pytest.approx(prof["program_s"][PROFILE[name]] / 2 * 1e3)
    other = dict(prof, program_s={"jit_convert_element_type": 1.0})
    assert readers.read(_spec(name), readers.Observed(
        rounds=[], profile=other, profiled_rounds=2)) is None
