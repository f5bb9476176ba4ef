"""The eight per-layer metrics of ISSUE 25 and the three lane metrics of
ISSUE 40 are data: each file resolves against its ``BENCHMARK.json`` entry,
and its reader returns a number from a record shaped like the program's — the
``lane`` ones on a toy cell's traced run (CPU), the ``profile`` ones on
``harness/trace_sample.json``.  The ``record`` and ``span_self`` readers
read the same toy run's cycle records."""

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
    "cycle_obs_ms": ("cycle driver", ["audit", "record"]),
    "bind_handoff_ms": ("cycle driver", ["bind_handoff"]),
    "cycle_gc_ms": ("cycle driver", ["gc"]),
    "device_dispatch_ms": ("solve", ["device_coarse", "device_fine"]),
}
# ISSUE 40: the proof that the list takes files.
LANE_40 = {
    "order_lane_ms": ("fast cycle host lanes", ["order"]),
    "derive_lane_ms": ("fast cycle host lanes", ["derive"]),
    "enqueue_lane_ms": ("fast cycle host lanes", ["enqueue"]),
}
LANE_ALL = {**LANE, **LANE_40}
PROFILE = {
    "solve_wave_ms_per_round": "jit__solve_wave(2)",
    "coarse_shortlist_ms_per_round": "jit__coarse_shortlist(1)",
}


def _spec(name):
    return json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(LANE_ALL) + sorted(PROFILE))
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
    if name in LANE_ALL:
        layer, lanes = LANE_ALL[name]
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
    assert set(names[8:16]) == set(LANE) | set(PROFILE)
    # The first sixteen keep their order; anything may follow them.
    assert names[:16] == [
        "ingest_us_per_pod", "complete_us_per_pod", "host_lanes_ms",
        "commit_lane_ms", "cycle_unattributed_ms", "compiles_in_window",
        "device_lane_ms", "device_busy_ms_per_round", "cycle_prologue_ms",
        "solve_prep_ms", "cycle_obs_ms", "bind_handoff_ms", "cycle_gc_ms",
        "device_dispatch_ms", "solve_wave_ms_per_round",
        "coarse_shortlist_ms_per_round"]
    assert names[16:19] == list(LANE_40) and len(set(names)) == len(names)


def test_no_lane_metric_names_a_lane_the_program_no_longer_has():
    """``journey`` left the synchronous path in PR 32 (docs/tracing.md)."""
    for f in (ROOT / "benchmark" / "layer_metrics").glob("*.json"):
        spec = json.loads(f.read_text())
        assert "journey" not in spec.get("args", {}).get("lanes", []), f.name
        assert "journey" not in spec.get("args", {}).get(
            "minus_all_lanes_except", []), f.name


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
    set_up, seen = bench_run.set_up, {}

    def keep_driver(*a, **kw):
        out = set_up(*a, **kw)
        seen["driver"] = out[0]
        return out

    bench_run.set_up = keep_driver
    try:
        cell = cell_mod.load_cell("toy.drip", path)
        result = bench_run.run(cell, 2**31 + 25, 1.0, True)
        result["_rounds"] = [r for r in seen["driver"].rounds
                             if r.plan.tag.startswith("w0")]
        return result
    finally:
        bench_run.OUT_DIR, bench_run.set_up = out_dir, set_up


@pytest.mark.parametrize("name", sorted(LANE_ALL))
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


def test_the_three_lanes_lie_inside_the_host_lanes(traced):
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert m["order_lane_ms"] + m["derive_lane_ms"] + m["enqueue_lane_ms"] \
        + m["commit_lane_ms"] < m["host_lanes_ms"]


@pytest.mark.parametrize("args,expect", [
    ({"key": "solve.rows", "reduce": "max"}, 24.0),      # a batch of the toy
    ({"key": "solve.rows"}, 24.0),                       # one cycle a round
    ({"key": "solve.nodes", "scale": 0.5}, 12.0),
    ({"key": "between.events.Pod/add.n"}, 24.0),         # a key with a slash
    ({"key": "between.gc.gen2.n"}, 0.0),
    ({"key": "whatif.victims"}, None),                   # no plan, no number
    ({"key": "solve.devincr_mode"}, None),               # not a number
    ({"key": "solve.no.such.key"}, None)])
def test_the_record_reader_reads_a_dotted_key(traced, args, expect):
    obs = readers.Observed(rounds=traced["_rounds"])
    got = readers.read({"reader": "record", "args": args}, obs)
    assert got == expect


def test_the_record_reader_sums_or_takes_the_most_over_a_rounds_cycles(traced):
    import copy

    r = copy.copy(traced["_rounds"][0])
    r.records = [{"spans": [], "solve": {"rows": 5}, "whatif": None, "between": None},
                 {"spans": [], "solve": None, "whatif": {"victims": 3}, "between": None},
                 {"spans": [], "solve": {"rows": 7}, "whatif": {"victims": 4}, "between": None}]
    obs = readers.Observed(rounds=[r])
    read = lambda **a: readers.read({"reader": "record", "args": a}, obs)  # noqa: E731
    assert read(key="solve.rows") == 12 and read(key="solve.rows", reduce="max") == 7
    assert read(key="whatif.victims") == 7
    assert read(key="whatif.victims", reduce="max", scale=2.0) == 8


def test_the_span_self_reader_takes_the_children_off(traced):
    rounds = traced["_rounds"]
    obs = readers.Observed(rounds=rounds[:1])       # one round: sums are exact
    read = lambda name: readers.read(  # noqa: E731
        {"reader": "span_self", "args": {"name": name, "scale": 1e3}}, obs)
    commit, lane = read("commit"), rounds[0].lanes["commit"] * 1e3
    kids = sum(read(f"commit:{k}") for k in ("guard", "journey", "state",
                                              "records", "bind"))
    # the commit lane is the commit span: its self time and its children's
    assert 0 < commit < lane and commit + kids == pytest.approx(lane, rel=1e-6)
    # a leaf's self time is its time; a name no cycle has reads nothing
    one = rounds[0].records[0]["spans"]
    assert read("device:fetch") > 0 and read("whatif_solve") is None
    assert {len(span) for span in one} == {4}
    # by hand, on a record of three spans
    import copy

    r = copy.copy(rounds[0])
    r.records = [{"spans": [("a", 10_000_000, 1, None), ("b", 4_000_000, 2, 1),
                            ("c", 1_000_000, 3, 2), ("a", 2_000_000, 4, None)]}]
    obs = readers.Observed(rounds=[r])
    assert readers.read({"reader": "span_self", "args": {"name": "a"}}, obs) \
        == pytest.approx(0.008)
    assert readers.read({"reader": "span_self", "args": {"name": "b"}}, obs) \
        == pytest.approx(0.003)


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
