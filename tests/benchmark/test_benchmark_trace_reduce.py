"""The reduction from a profiler trace to busy time, idle share, per-program
totals and labelled gaps, checked on the small trace kept beside it
(``benchmark/harness/trace_sample.json``, in the shape of a v5e trace)."""

import json

import pytest

from benchmark.harness import cell, readers, trace_reduce

SAMPLE = cell.ROOT / "benchmark" / "harness" / "trace_sample.json"


@pytest.fixture(scope="module")
def trace():
    return json.loads(SAMPLE.read_text())


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_busy_is_the_union_of_operations_on_the_chip(trace):
    out = trace_reduce.reduce(trace, window_s=4.0)
    # ops: [1010,1012] + [1020,1050] + [3020,3050] ms; the overlapping
    # fusion counts once, the Steps and async-copy lines not at all
    assert out["busy_s"] == pytest.approx(0.062)
    assert out["window_s"] == 4.0 and out["chips"] == 1
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.9845)


def test_window_falls_back_to_the_benchmarks_spans(trace):
    assert trace_reduce.reduce(trace)["window_s"] == pytest.approx(4.0)


def test_programs_by_total_time(trace):
    out = trace_reduce.reduce(trace, window_s=4.0)
    assert out["device_ops"][0] == ["jit__solve_wave(2)", pytest.approx(0.060)]
    assert out["device_ops"][1] == ["jit__coarse_shortlist(1)", pytest.approx(0.002)]
    assert out["program_s"]["jit__solve_wave(2)"] == pytest.approx(0.060)


def test_gaps_are_cut_at_span_boundaries_and_labelled(trace):
    gaps = trace_reduce.reduce(trace, window_s=4.0, top=4)["idle_gaps"]
    assert [g[0] for g in gaps] == ["submit", "submit", "complete", "complete"]
    assert [g[1] for g in gaps] == pytest.approx([1.0, 1.0, 0.9, 0.9])
    everything = trace_reduce.reduce(trace, window_s=4.0, top=99)["idle_gaps"]
    sched = sorted(g[1] for g in everything if g[0] == "schedule")
    # round 1: 10 ms before the shortlist, 8 ms between the programs, 50 ms
    # after; round 2: 20 ms before and 50 ms after the solve
    assert sched == pytest.approx([0.008, 0.010, 0.020, 0.050, 0.050])
    assert sum(g[1] for g in everything) + 0.062 == pytest.approx(4.0)


def test_no_device_plane_is_nothing_to_read():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench:submit", 0, 10]]}]}]}
    assert trace_reduce.reduce(host_only) == {}
    obs = readers.Observed(rounds=[], profile={}, profiled_rounds=0)
    assert readers.read_profile({"quantity": "busy"}, obs) is None


def test_profile_reader_by_pattern_and_busy(trace):
    prof = trace_reduce.reduce(trace, window_s=4.0)
    obs = readers.Observed(rounds=[], profile=prof, profiled_rounds=2)
    busy = readers.read_profile({"quantity": "busy", "per": "round",
                                 "scale": 1e3}, obs)
    assert busy == pytest.approx(31.0)
    solve = readers.read_profile({"pattern": r"^jit__solve_wave\(",
                                  "per": "round", "scale": 1e3}, obs)
    assert solve == pytest.approx(30.0)
    assert readers.read_profile({"pattern": "no_such_program"}, obs) is None


def test_describe_names_planes_and_lines(trace):
    text = "\n".join(trace_reduce.describe(trace))
    assert "/device:TPU:0" in text and "XLA Ops" in text and "bench:submit" in text
