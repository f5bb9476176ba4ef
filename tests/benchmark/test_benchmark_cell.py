"""A cell is data: a toy configuration and a toy traffic mix, written by the
test into a temporary directory, run ``run.py``'s whole path on the CPU
(set-up, window, validation, probe, result line).  The same path with the
timed path broken underneath comes out ``correct: false``."""

import json
import os
import shutil

import pytest

from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import loop

ROOT = cell_mod.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """BENCHMARK.json with one new configuration, one new traffic mix, one
    new cell and one new per-layer metric: files and entries only."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    home = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark" / "layer_metrics", home / "layer_metrics")
    (home / "configs").mkdir()
    (home / "traffic").mkdir()
    config = json.loads((ROOT / "benchmark" / "configs" / "binpack-1k.json").read_text())
    config.update(name="toy", backlog_pods=240)
    config["nodes"].update(count=24, zones=3)
    config["gang"] = {"sizes": [2, 4]}
    config["queues"] = {"count": 2, "weights": [1, 3]}
    (home / "configs" / "toy.json").write_text(json.dumps(config))
    (home / "traffic" / "drip.json").write_text(json.dumps({
        "name": "drip", "resident_fraction": 0.5, "batch_fraction": 0.1,
        "warmup_rounds": 2, "max_cycles": 4, "profile_seconds": 0.2}))
    (home / "layer_metrics" / "schedule_ms.json").write_text(json.dumps({
        "name": "schedule_ms", "unit": "ms", "layer": "cycle driver",
        "moves": "backlog_to_bind_ms", "reader": "span",
        "args": {"span": "schedule", "scale": 1e3}}))
    real["configs"] = [{"name": "toy", "source": "a test",
                        "file": "benchmark/configs/toy.json", "reduced": [],
                        "why": "toy"}]
    real["workloads"] = [{"name": "toy.drip", "config": "toy",
                          "traffic": "drip", "chips": 1, "why": "toy"}]
    real["per_layer"].append({
        "name": "schedule_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "cycle driver",
        "moves": "backlog_to_bind_ms", "workloads": ["toy.drip"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(real))
    # main() sets this when it is unset; keep the test's process as it was.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                      str(tmp_path / "xla")))
    monkeypatch.setattr(bench_run, "OUT_DIR", tmp_path / "out")
    return path


def _last_line(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
def test_toy_cell_runs_end_to_end(toy, capsys, trace):
    rc = bench_run.main(["--workload", "toy.drip", "--seed", str(2**31 + 99),
                         "--seconds", "1", "--trace", str(trace),
                         "--benchmark-file", str(toy)])
    assert rc == 0
    result, lines = _last_line(capsys)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["attempted"] % 24 == 0
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"
    # A CPU rehearsal reports no device number.
    assert result["device"]["memory_peak_bytes"] is None
    names = set(result["metrics"])
    if trace:
        assert "schedule_ms" in names and "ingest_us_per_pod" in names
        assert "host_lanes_ms" in names and "commit_lane_ms" in names
        assert "device_busy_ms_per_round" not in names  # nothing to read
        assert "bind_rate" not in names
    else:
        assert names == {"bind_rate", "backlog_to_bind_ms",
                         "submit_to_bind_p95_ms", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] >= 0
    assert any("CPU rehearsal" in ln for ln in lines)
    assert any("probe: 0 of 48" in ln for ln in lines)


class _Idle:
    """A scheduler whose every other cycle returns its state unchanged."""

    def __init__(self, real, every):
        self.real, self.every, self.calls = real, every, 0

    def run_once(self):
        self.calls += 1
        if self.calls % self.every:
            self.real.run_once()


class _Misdirect:
    """A binder slot in which every answer is altered where it is produced:
    all binds go to the first node."""

    def __init__(self, inner, node):
        self.inner, self.node = inner, node

    def bind_keys(self, keys, hosts):
        self.inner.bind_keys(keys, [self.node] * len(list(hosts)))


class _SecondChoice:
    """A binder slot in which one answer is altered where it is produced:
    the first lone pod goes to the last node, which has room but is not the
    best-scoring one.  Every guarantee holds; only the probe can tell."""

    def __init__(self, inner, node):
        self.inner, self.node, self.done = inner, node, False

    def bind_keys(self, keys, hosts):
        keys, hosts = list(keys), list(hosts)
        if len(keys) == 1 and not self.done:
            hosts, self.done = [self.node], True
        self.inner.bind_keys(keys, hosts)


def _broken_idle(store, conf):
    # 1 of 1 cycles idle: no pod is ever bound.
    return _Idle(loop.default_scheduler(store, conf), every=1)


def _broken_misdirect(store, conf):
    store.binder = _Misdirect(store.binder, "node-000000")
    return loop.default_scheduler(store, conf)


def _broken_second_choice(store, conf):
    store.binder = _SecondChoice(store.binder, "node-000023")
    return loop.default_scheduler(store, conf)


@pytest.mark.parametrize("broken,symptom", [
    (_broken_idle, "validate: unbound ="),
    (_broken_misdirect, "validate: oversubscribed ="),
    (_broken_second_choice, "probe:")])
def test_broken_timed_path_is_not_correct(toy, capsys, broken, symptom):
    cell = cell_mod.load_cell("toy.drip", toy)
    # keep the idle scheduler's rounds short
    result = bench_run.run(cell, seed=7, seconds=0.5, trace=False,
                           make_scheduler=broken, bind_wait_s=0.01)
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert result["failed"] > 0
    line = [ln for ln in out.splitlines() if ln.startswith(symptom)][0]
    assert int(line.split("=")[-1].split()[0] if "=" in line
               else line.split()[1]) > 0
    if symptom == "probe:":       # nothing but the node choice is wrong
        assert "validate: unbound = 0" in out
        assert "validate: oversubscribed = 0" in out


def test_no_accelerator_and_no_cpu_named_fails(toy, monkeypatch):
    """``require_accelerator`` is the program's; the harness calls it before
    it builds anything."""
    from volcano_tpu import device

    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    cell = cell_mod.load_cell("toy.drip", toy)
    with pytest.raises(RuntimeError, match="no accelerator"):
        bench_run.run(cell, seed=1, seconds=0.1, trace=False)
