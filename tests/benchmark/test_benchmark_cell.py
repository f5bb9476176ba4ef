"""A cell is data: a toy configuration and a toy traffic mix, written by the
test into a temporary directory, run ``run.py``'s whole path on the CPU
(set-up, window, validation, probe, result line).  The same path with the
timed path broken underneath comes out ``correct: false``.  A second toy
fills its nodes with a low priority class and sends bursts of a high one:
the round holds evictions, the kubelet's side ends them, and the same path
with the eviction path broken comes out ``correct: false``.  A third toy's
gangs enter as Jobs of ``examples/job.yaml``'s shape (``entry: jobs``):
admission and the controllers stand between the client and the store, a round
is two cycles and three pumps to Running, and each count of that entry goes
to 1 when the toy is broken the matching way; with ``probe.fill_pods`` its
probe brings a fill of its own, which the client places as pods among the
Jobs, and a fill that is counted under a Job, placed past a node's room or
deleted behind the client comes out ``correct: false``."""

import json
import os
import shutil
import statistics
import time
from collections import deque

import pytest

from benchmark import run as bench_run
from benchmark.harness import cell as cell_mod
from benchmark.harness import loop

ROOT = cell_mod.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
TOY_REF = '''"""The toy's own guarantee: every bind names a node with a zone."""


def check(events, nodes, config):
    assert len(nodes["labels"]) == len(nodes["names"]) == config["nodes"]["count"]
    zoned = {n for n, labels in zip(nodes["names"], nodes["labels"])
             if "zone" in labels}
    return {"off_the_zones": sum(
        host not in zoned for ev in events
        for _t, _keys, hosts in ev.arrivals for host in hosts)}
'''
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _bench_file(tmp_path, monkeypatch, bench):
    """The toy's ``BENCHMARK.json``, written; the run's trace and compile
    cache go under ``tmp_path``."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    # main() sets this when it is unset; keep the test's process as it was.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                      str(tmp_path / "xla")))
    monkeypatch.setattr(bench_run, "OUT_DIR", tmp_path / "out")
    return path


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
    config["guarantees"]["checks"] = ["toy"]
    (home / "configs" / "toy.json").write_text(json.dumps(config))
    (home / "reference").mkdir()
    (home / "reference" / "toy_ref.py").write_text(TOY_REF)
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
    (home / "layer_metrics" / "solve_rows.json").write_text(json.dumps({
        "name": "solve_rows", "unit": "rows", "layer": "solve",
        "moves": "backlog_to_bind_ms", "reader": "record",
        "args": {"key": "solve.rows", "reduce": "max"}}))
    (home / "layer_metrics" / "commit_self_ms.json").write_text(json.dumps({
        "name": "commit_self_ms", "unit": "ms",
        "layer": "fast cycle host lanes", "moves": "backlog_to_bind_ms",
        "reader": "span_self", "args": {"name": "commit", "scale": 1e3}}))
    # a span only a round that enters as Jobs has: nothing to read here
    (home / "layer_metrics" / "pump_ms.json").write_text(json.dumps({
        "name": "pump_ms", "unit": "ms", "layer": "admission and controllers",
        "moves": "backlog_to_bind_ms", "reader": "span",
        "args": {"span": "pump", "scale": 1e3}}))
    for name, unit, source, layer in (
            ("pump_ms", "ms", "host_clock", "admission and controllers"),
            ("schedule_ms", "ms", "host_clock", "cycle driver"),
            ("solve_rows", "rows", "program_counter", "solve"),
            ("commit_self_ms", "ms", "program_span", "fast cycle host lanes")):
        real["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "backlog_to_bind_ms",
            "workloads": ["toy.drip"]})
    return _bench_file(tmp_path, monkeypatch, real)


CONF_PREEMPT = """actions: "enqueue, allocate, preempt, reclaim, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""
PREEMPT_REF = '''"""The toy's own guarantee: no pod of the highest class is a victim."""


def check(events, nodes, config):
    top = max(config["priority_classes"], key=lambda c: c["value"])["name"]
    klass = {}
    for ev in events:
        plan = ev.plan
        for key, g in zip(plan.keys(), plan.gang.tolist()):
            klass[key] = plan.gang_priority[g]
    return {"victim_of_the_top_class": sum(
        klass.get(key) == top for ev in events for _t, key in ev.evictions)}
'''


@pytest.fixture
def toy_preempt(request, tmp_path, monkeypatch):
    """A cell that holds evictions, by files and entries alone: 48 nodes of
    4 pod sizes each, kept full by single pods of class ``low`` (192
    resident, 12 more that may wait), two weighted queues of which one may
    be reclaimed from, bursts of two gangs of 4 of class ``high``,
    CONF_PREEMPT.  The program's own default lane plans the evictions (the
    suite's conftest pins the host walk for the legacy tests, which restores
    no victim; the harness itself sets no knob).  A test may give the cycles a
    termination takes (``request.param``; 0, and two settle cycles, else)."""
    grace = getattr(request, "param", 0)
    monkeypatch.delenv("VOLCANO_TPU_EVICT_DEVICE", raising=False)
    monkeypatch.delenv("VOLCANO_TPU_EVICT_CAP", raising=False)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    home = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark" / "layer_metrics", home / "layer_metrics")
    for part in ("configs", "traffic", "reference"):
        (home / part).mkdir()
    config = json.loads((ROOT / "benchmark" / "configs" / "binpack-1k.json").read_text())
    config.update(name="toypre", backlog_pods=192, scheduler_conf=CONF_PREEMPT)
    config["nodes"].update(count=48, cpu=8, memory_gi=32, pods=16, zones=0)
    config["pods"] = {"cpu_choices": [2], "mem_gi_choices": [4]}
    config["gang"] = {"size": 4}
    config["queues"] = {"count": 2, "weights": [1, 2],
                        "reclaimable": [True, False]}
    config["priority_classes"] = [
        {"name": "low", "value": 10, "share": 0.9,
         "gang": {"size": 1, "min_member": 1}},
        {"name": "high", "value": 1000, "share": 0.1,
         "gang": {"size": 4, "min_member": 4}}]
    config["probe"] = {"probes": 6, "before_drain": 0, "keep_pods": 150}
    config["guarantees"]["checks"] = ["toypre"]
    (home / "configs" / "toypre.json").write_text(json.dumps(config))
    (home / "reference" / "toypre_ref.py").write_text(PREEMPT_REF)
    (home / "traffic" / "pre.json").write_text(json.dumps({
        "name": "pre", "resident_fraction": 1.0, "batch_fraction": 1 / 24,
        "waiting_fraction": 1 / 16, "warmup_rounds": 2, "max_cycles": 6,
        "settle_cycles": 2 + grace, "termination_cycles": grace, "pods_run": True,
        "resident_class": "low", "batch_class": "high", "bind_wait_s": 5.0,
        "profile_seconds": 0.2}))
    (home / "layer_metrics" / "whatif_victims.json").write_text(json.dumps({
        "name": "whatif_victims", "unit": "pods", "layer": "what-if engine",
        "moves": "backlog_to_bind_ms", "reader": "record",
        "args": {"key": "whatif.victims", "reduce": "sum"}}))
    (home / "layer_metrics" / "whatif_solve_ms.json").write_text(json.dumps({
        "name": "whatif_solve_ms", "unit": "ms", "layer": "what-if engine",
        "moves": "backlog_to_bind_ms", "reader": "span_self",
        "args": {"name": "whatif_solve", "scale": 1e3}}))
    real["configs"] = [{"name": "toypre", "source": "a test",
                        "file": "benchmark/configs/toypre.json", "reduced": [],
                        "why": "toy"}]
    real["workloads"] = [{"name": "toypre.pre", "config": "toypre",
                          "traffic": "pre", "chips": 1, "why": "toy"}]
    for name, unit, source in (("whatif_victims", "pods", "program_counter"),
                               ("whatif_solve_ms", "ms", "program_span")):
        real["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "what-if engine", "moves": "backlog_to_bind_ms",
            "workloads": ["toypre.pre"]})
    return _bench_file(tmp_path, monkeypatch, real)


JOB_BLOCK = {"plugins": {"ssh": [], "env": [], "svc": []},
             "policies": [{"event": "PodEvicted", "action": "RestartJob"}],
             "max_retry": 5}


@pytest.fixture
def toy_jobs(request, tmp_path, monkeypatch):
    """A cell whose gangs enter as Jobs, by files and entries alone: 48
    nodes, Jobs of ``examples/job.yaml``'s shape (6 replicas of 1 cpu / 1 Gi,
    ``minAvailable`` 3, plugins ``ssh``, ``env``, ``svc``, ``PodEvicted`` ->
    ``RestartJob``, ``maxRetry`` 5), four of them a burst, and three
    per-layer metrics that read the new spans with the ``span`` reader as it
    is.  A test may give the traffic's ``max_pumps`` (``request.param``) or,
    as a dict, the configuration's ``probe`` block."""
    param = getattr(request, "param", None)
    max_pumps = None if isinstance(param, dict) else param
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    home = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark" / "layer_metrics", home / "layer_metrics")
    for part in ("configs", "traffic"):
        (home / part).mkdir()
    config = json.loads((ROOT / "benchmark" / "configs" / "binpack-1k.json").read_text())
    config.update(name="toyjob", backlog_pods=48, job=JOB_BLOCK)
    config["nodes"].update(count=48, cpu=8, memory_gi=32, pods=16, zones=0)
    config["pods"] = {"cpu_choices": [1], "mem_gi_choices": [1]}
    config["gang"] = {"size": 6, "min_member": 3}
    config["probe"] = {"probes": 4, "before_drain": 1, "keep_pods": 24}
    if isinstance(param, dict):
        config["probe"] = param
    (home / "configs" / "toyjob.json").write_text(json.dumps(config))
    traffic = {"name": "asjobs", "entry": "jobs", "resident_fraction": 0.0,
               "batch_fraction": 0.5, "warmup_rounds": 1, "max_cycles": 6,
               "pods_run": True, "profile_seconds": 0.2}
    if max_pumps is not None:
        traffic["max_pumps"] = max_pumps
    (home / "traffic" / "asjobs.json").write_text(json.dumps(traffic))
    real["configs"] = [{"name": "toyjob", "source": "a test",
                        "file": "benchmark/configs/toyjob.json", "reduced": [],
                        "why": "toy"}]
    real["workloads"] = [{"name": "toyjob.asjobs", "config": "toyjob",
                          "traffic": "asjobs", "chips": 1, "why": "toy"}]
    for name, span, per in (("admit_us_per_pod", "admit", "pod"),
                            ("pump_ms", "pump", None),
                            ("reconcile_ms", "reconcile", None)):
        args = {"span": span, "scale": 1e6 if per else 1e3}
        if per:
            args["per"] = per
        unit = "us/pod" if per else "ms"
        (home / "layer_metrics" / f"{name}.json").write_text(json.dumps({
            "name": name, "unit": unit, "layer": "admission and controllers",
            "moves": "bind_rate", "reader": "span", "args": args}))
        real["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "host_clock", "layer": "admission and controllers",
            "moves": "bind_rate", "workloads": ["toyjob.asjobs"]})
    # and the store's own count of what the kubelet's reports cost it, a
    # nested key of the record's ``between`` block, by the ``record`` reader
    (home / "layer_metrics" / "pod_updates.json").write_text(json.dumps({
        "name": "pod_updates", "unit": "count", "layer": "store + mirror",
        "moves": "bind_rate", "reader": "record",
        "args": {"key": "between.events.Pod/update.n"}}))
    real["per_layer"].append({
        "name": "pod_updates", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "store + mirror",
        "moves": "bind_rate", "workloads": ["toyjob.asjobs"]})
    return _bench_file(tmp_path, monkeypatch, real)


def _keep_driver(monkeypatch):
    seen = {}
    set_up = bench_run.set_up

    def keep(*a, **kw):
        out = set_up(*a, **kw)
        seen["driver"] = out[0]
        return out

    monkeypatch.setattr(bench_run, "set_up", keep)
    return seen


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
        # a record metric and a span_self metric, added by files alone
        assert result["metrics"]["solve_rows"]["value"] == 24
        assert 0 < result["metrics"]["commit_self_ms"]["value"] \
            < result["metrics"]["commit_lane_ms"]["value"]
        assert "host_lanes_ms" in names and "commit_lane_ms" in names
        assert "device_busy_ms_per_round" not in names  # nothing to read
        assert "pump_ms" not in names           # no pump in a round of pods
        assert "bind_rate" not in names
    else:
        assert names == {"bind_rate", "backlog_to_bind_ms",
                         "submit_to_bind_p95_ms", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] >= 0
    assert any("CPU rehearsal" in ln for ln in lines)
    assert any("probe: 0 of 48" in ln for ln in lines)
    # the configuration's own check, by name, is in the verdict
    assert "validate: off_the_zones = 0 (limit 0)" in lines
    # every number compared beside its limit, last in the line
    assert list(result)[-1] == "compared"
    assert result["compared"]["off_the_zones"] == {"value": 0, "limit": 0}
    assert {"unknown", "double", "unbound", "oversubscribed", "split",
            "evicted_unknown", "evicted_twice", "never_terminated",
            "gang_broken", "lost", "ghost", "probe_misses"} \
        <= set(result["compared"])
    assert any("0 evictions seen" in ln for ln in lines)


def test_under_pods_the_probes_rounds_and_the_backlogs_clock_are_as_they_were(
        toy, monkeypatch, capsys):
    """No ``fill_pods`` and ``entry: pods``: after the window the fill and
    the probes, no round more; the backlog's clock starts at the round's
    last ``add_pod``, which is where its submit ends."""
    seen = _keep_driver(monkeypatch)
    cell = cell_mod.load_cell("toy.drip", toy)
    result = bench_run.run(cell, seed=2**31 + 54, seconds=0.5, trace=False)
    capsys.readouterr()
    rounds = seen["driver"].rounds
    tags = [r.plan.tag for r in rounds]
    assert tags[tags.index("probefill"):] == ["probefill"] + [
        f"probe{k:03d}" for k in range(cell.config["probe"]["probes"])]
    assert all(r.jobs is None and r.t_admitted == 0 and not r.pumps for r in rounds)
    counted = [r for r in rounds if r.plan.tag.startswith("w0")]
    waits = [(max(t for t, _k, _h in r.arrivals) - r.t_submitted) / 1e6
             for r in counted]
    assert result["metrics"]["backlog_to_bind_ms"]["value"] \
        == pytest.approx(statistics.median(waits))
    assert all(r.submit_ns[-1] <= r.t_submitted for r in counted)


@pytest.mark.parametrize("trace,toy_preempt", [(0, 0), (1, 1)],
                         indirect=["toy_preempt"])
def test_toy_preempt_cell_holds_evictions(toy_preempt, monkeypatch, capsys, trace):
    """Untraced with terminations that end before the next cycle, traced
    with a cycle of grace (the room is Releasing for one cycle more, so a
    round takes one cycle more)."""
    grace = trace
    seen = _keep_driver(monkeypatch)
    cell = cell_mod.load_cell("toypre.pre", toy_preempt)
    result = bench_run.run(cell, seed=2**31 + 40, seconds=0.5, trace=bool(trace))
    out = capsys.readouterr().out
    driver = seen["driver"]
    assert result["correct"] is True and result["failed"] == 0, out[-3000:]
    compared = result["compared"]
    for name in ("never_terminated", "evicted_unknown", "evicted_twice",
                 "gang_broken", "lost", "ghost", "double", "oversubscribed",
                 "unknown", "unbound", "split", "victim_of_the_top_class",
                 "probe_misses"):
        assert compared[name] == {"value": 0, "limit": 0}, name
        if name != "probe_misses":
            assert f"validate: {name} = 0 (limit 0)" in out
    counted = [r for r in driver.rounds if r.plan.tag.startswith("w0")]
    evictions = sum(len(r.evictions) for r in counted)
    assert len(counted) >= 1 and evictions > 0
    assert sum(len(r.terminations) for r in counted) == evictions
    assert f"{evictions} evictions seen, {evictions} terminations ended" in out
    # the warm-up rounds are of the window's shape: bursts onto a full cluster
    window = [r for r in driver.rounds if r.plan.tag.startswith("warm")] + counted
    assert len(window) >= 3, len(window)
    assert all(len(r.evictions) >= 4 for r in window), [len(r.evictions) for r in window]
    # the evictor's stamps are the binder's clock; every one is inside its round
    for r in driver.rounds:
        assert all(r.t_start <= t <= r.t_end for t, _key in r.evictions)
        assert all(r.t_start <= t <= r.t_end for t, _key in r.terminations)
    # a round needs an evicting cycle and a binding one, and waits for neither
    assert all(r.cycles >= 2 + grace for r in window)
    assert all(r.waits_timed_out == 0 and r.wait_s < 1.0 < driver.bind_wait_s
               for r in driver.rounds)
    assert "0 waits of the run reached bind_wait_s" in out
    # the waiting tier takes the room after the completions, inside the round
    assert all(r.settle_cycles == 2 + grace and r.spans()["settle"] > 0
               for r in window)
    assert driver.termination_cycles == grace
    assert all(r.t_scheduled <= r.t_completed < r.t_end for r in window)
    assert all(r.spans()["round"] == pytest.approx(sum(
        r.spans()[k] for k in ("submit", "schedule", "complete", "settle")))
        for r in window)
    # pods that may wait are in nobody's attempted
    waiting = [r for r in driver.rounds if r.plan.may_wait]
    assert len(waiting) == 1 and waiting[0].plan.n_pods == 12
    assert waiting[0].cycles == 1
    assert result["attempted"] == sum(r.plan.n_pods for r in counted) == 8 * len(counted)
    assert "12 pods may wait" in out
    # classes, as the API wants them
    pods = list(driver.fifo)[-1][1]
    assert {(p.priority_class, p.priority) for p in pods} == {("high", 1000)}
    assert driver.store.priority_classes["low"].value == 10
    assert [(q.name, q.weight, q.reclaimable)
            for q in driver.store.raw_queues.values()] \
        == [("default", 1, True), ("queue-1", 2, False)]
    if trace:
        assert result["metrics"]["whatif_victims"]["value"] >= 4
        assert result["metrics"]["whatif_solve_ms"]["value"] > 0
        assert {"order_lane_ms", "derive_lane_ms", "enqueue_lane_ms"} \
            <= set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"bind_rate", "backlog_to_bind_ms",
                                          "submit_to_bind_p95_ms", "setup_s"}


@pytest.mark.parametrize("trace", [0, 1])
def test_toy_jobs_cell_takes_a_job_from_the_users_call_to_running(
        toy_jobs, monkeypatch, capsys, trace):
    """Submit -> Running through admission and the controllers: the round
    is two cycles (``enqueue`` admits PodGroups that have no pods yet, then
    the pods bind) and three pumps (PodGroup, pods, Job Running)."""
    from volcano_tpu.controllers import JobPhase
    from volcano_tpu.webhooks import AdmittedStore

    calls = {}                  # job key -> when its add_batch_job was entered
    admit = AdmittedStore.add_batch_job

    def stamped(self, job):
        calls[job.key] = time.perf_counter_ns()
        admit(self, job)

    monkeypatch.setattr(AdmittedStore, "add_batch_job", stamped)
    seen = _keep_driver(monkeypatch)
    cell = cell_mod.load_cell("toyjob.asjobs", toy_jobs)
    result = bench_run.run(cell, seed=2**31 + 52, seconds=0.5, trace=bool(trace))
    out = capsys.readouterr().out
    driver = seen["driver"]
    assert result["correct"] is True and result["failed"] == 0, out[-3000:]
    for name in ("pods_not_as_planned", "jobs_not_running", "jobs_left_behind",
                 "lost", "ghost", "unbound", "split", "unknown", "probe_misses"):
        assert result["compared"][name] == {"value": 0, "limit": 0}, name
    assert "validate: jobs_left_behind = 0 (limit 0)" in out
    assert "no phase of the run used up its 4 pumps" in out
    counted = [r for r in driver.rounds if r.plan.tag.startswith("w0")]
    bursts = [r for r in driver.rounds if r.plan.n_pods == 24]
    assert counted and len(bursts) >= len(counted) + 2     # warm-up, the fill
    assert result["attempted"] == 24 * len(counted)
    for r in bursts:
        # the plan states what the controller will call the pods
        assert r.plan.names[:2] == [f"{r.plan.tag}-pg-000000-worker-0",
                                    f"{r.plan.tag}-pg-000000-worker-1"]
        assert sorted(k for k, _owner in r.jobs.created) == sorted(r.plan.keys())
        assert {owner for _k, owner in r.jobs.created} == set(r.plan.job_keys())
        assert r.plan.gang_min_member.tolist() == [3] * 4
        assert r.plan.sizes().tolist() == [6] * 4
        # two cycles, three pumps to Running; the Jobs' own end takes two more
        assert r.cycles == 2 and r.waits_timed_out == 0
        assert r.pumps == {"submit": 1, "schedule": 1, "reconcile": 1,
                           "complete": 2 if r.deleted else 0}, r.pumps
        assert not r.pumps_used_up
        assert r.jobs.not_running == [] and r.jobs.left_behind == []
        # the submit stamp is the user's call, a gang: taken just before its
        # own add_batch_job is entered, after the one before it was
        stamps = r.submit_ns.reshape(4, 6)
        entered = [calls[key] for key in r.plan.job_keys()]
        assert (stamps == stamps[:, :1]).all()
        assert all(a <= b for a, b in zip(stamps[:, 0].tolist(), entered))
        assert all(a < b for a, b in zip(entered, stamps[1:, 0].tolist()))
        assert r.t_start <= stamps[0, 0] and entered[-1] < r.t_admitted \
            <= r.t_submitted <= r.t_scheduled <= r.t_reconciled <= r.t_completed
        spans = r.spans()
        assert 0 < spans["admit"] < spans["submit"] and spans["reconcile"] > 0
        assert spans["pump"] == pytest.approx(sum(r.pump_s.values()))
        assert spans["round"] == pytest.approx(sum(
            spans[k] for k in ("submit", "schedule", "reconcile", "complete",
                               "settle")))
    # what stands in the store is what the ledger holds alive, as Jobs do
    store = driver.store
    alive = [job for job, _keys in driver.fifo]
    assert sorted(store.batch_jobs) == sorted(job.key for job in alive)
    assert sorted(store.pod_groups) == sorted(job.key for job in alive)
    assert {j.status.state.phase for j in store.batch_jobs.values()} \
        == {JobPhase.Running.value}
    job = alive[-1]
    assert (job.min_available, job.max_retry, sorted(job.plugins)) \
        == (1, 5, ["env", "ssh", "svc"])                # a probe's lone pod
    assert [(p.event, p.action) for p in job.policies] \
        == [("PodEvicted", "RestartJob")]
    pod = next(reversed(store.pods.values()))
    assert pod.owner_job == job.key and pod.task_name == "worker"
    assert pod.env["VC_PROCESS_COUNT"] == "1" and "VK_TASK_INDEX" in pod.env
    assert any("gangs enter as Jobs" in ln for ln in out.splitlines())
    if trace:
        metrics = result["metrics"]
        assert {"admit_us_per_pod", "pump_ms", "reconcile_ms"} <= set(metrics)
        assert metrics["pump_ms"]["value"] > metrics["reconcile_ms"]["value"] > 0
        # a round's cycles see the last round's 24 Succeeded reports and the
        # Running reports made between its own two
        assert metrics["pod_updates"]["value"] >= 24
        # the first cycle of a round meets PodGroups that have no pods, and
        # the fast cycle takes them
        assert all([rec["path"] for rec in r.records] == ["fast", "fast"]
                   for r in bursts)
        assert "traced: 0 cycles left the fast path or failed" in out
    else:
        assert set(result["metrics"]) == {"bind_rate", "backlog_to_bind_ms",
                                          "submit_to_bind_p95_ms", "setup_s"}
        # the backlog stands when the user has made the last call: the
        # controllers' first pump lies inside backlog_to_bind_ms, not before
        waits = [(max(t for t, _k, _h in r.arrivals) - r.t_admitted) / 1e6
                 for r in counted]
        assert result["metrics"]["backlog_to_bind_ms"]["value"] \
            == pytest.approx(statistics.median(waits))
        for wait, r in zip(waits, counted):
            old = wait - (r.t_submitted - r.t_admitted) / 1e6   # PR 52's clock
            assert wait - old >= r.pump_s["submit"] * 1e3 > 0


def test_a_jobs_cell_with_an_affinity_mix_or_no_running_pods_does_not_load(toy_jobs):
    home = toy_jobs.parent / "benchmark"
    config = json.loads((home / "configs" / "toyjob.json").read_text())
    traffic = json.loads((home / "traffic" / "asjobs.json").read_text())
    (home / "configs" / "toyjob.json").write_text(json.dumps(
        dict(config, affinity_mix={"affinity": 0.05})))
    with pytest.raises(SystemExit, match="no inter-pod terms"):
        cell_mod.load_cell("toyjob.asjobs", toy_jobs)
    (home / "configs" / "toyjob.json").write_text(json.dumps(config))
    (home / "traffic" / "asjobs.json").write_text(json.dumps(
        dict(traffic, pods_run=False)))
    with pytest.raises(SystemExit, match="needs pods_run"):
        cell_mod.load_cell("toyjob.asjobs", toy_jobs)
    (home / "traffic" / "asjobs.json").write_text(json.dumps(
        dict(traffic, entry="service")))
    with pytest.raises(SystemExit, match="'pods' and 'jobs'"):
        cell_mod.load_cell("toyjob.asjobs", toy_jobs)


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


# ---- the eviction path, broken (on the toy that holds evictions) ------------


def _broken_kubelet(store, conf):
    """The kubelet's side ends no termination: the room stays Releasing."""
    import pytest as _pytest

    patch = _pytest.MonkeyPatch()
    patch.setattr(loop.Driver, "_end_termination", lambda self, key, pod: None)
    _broken_kubelet.undo = patch.undo
    return loop.default_scheduler(store, conf)


class _EvictsTheBatch:
    """A scheduler that, once a gang of the batch's own class runs, evicts
    one of its pods through the store."""

    def __init__(self, real, store):
        self.real, self.store, self.done = real, store, False

    def run_once(self):
        self.real.run_once()
        if not self.done:
            for pod in list(self.store.pods.values()):
                if pod.priority_class == "high" and pod.phase == "Running" \
                        and not pod.deleting:
                    self.store.evict(pod, "a test")     # reads pod.uid alone
                    self.done = True
                    break


def _broken_evicts_the_batch(store, conf):
    return _EvictsTheBatch(loop.default_scheduler(store, conf), store)


class _TwiceInALife:
    """A binder slot that hands a key's second life over twice."""

    def __init__(self, inner):
        self.inner, self.seen = inner, set()

    def bind_keys(self, keys, hosts):
        keys, hosts = list(keys), list(hosts)
        again = [(k, h) for k, h in zip(keys, hosts) if k in self.seen]
        self.seen.update(keys)
        self.inner.bind_keys(keys + [k for k, _h in again],
                             hosts + [h for _k, h in again])


def _broken_double_of_a_restored_key(store, conf):
    store.binder = _TwiceInALife(store.binder)
    return loop.default_scheduler(store, conf)


class _DeletesBehindTheClient:
    """A scheduler that once deletes a running pod nobody asked it to."""

    def __init__(self, real, store):
        self.real, self.store, self.calls = real, store, 0

    def run_once(self):
        self.real.run_once()
        self.calls += 1
        if self.calls == 8:
            low = [p for p in self.store.pods.values()
                   if p.phase == "Running" and not p.deleting
                   and p.priority_class == "low"]
            # the youngest: the oldest finish before the run ends, and
            # the ledger then forgets them with the client
            self.store.delete_pod(low[-1])


def _broken_deletes_behind_the_client(store, conf):
    return _DeletesBehindTheClient(loop.default_scheduler(store, conf), store)


# ---- the Job's path, broken (on the toy whose gangs enter as Jobs) ----------


def _broken_renames_a_pod(store, conf):
    """The controller's first pod of the window bears another name."""
    add_pod, done = store.add_pod, []

    def add(pod):
        if not done and pod.owner_job.startswith("default/w0000"):
            done.append(pod)
            pod.name += "x"
        add_pod(pod)

    store.add_pod = add
    return loop.default_scheduler(store, conf)


def _broken_withholds_running(store, conf):
    """The Running reports of one Job's pods never reach the store."""
    update_pod = store.update_pod

    def update(pod):
        if pod.phase == "Running" and pod.owner_job == "default/w0000-pg-000001":
            return
        update_pod(pod)

    store.update_pod = update
    return loop.default_scheduler(store, conf)


def _broken_skips_a_delete(store, conf):
    """The first ``delete_batch_job`` is swallowed: the Job is never cleaned up."""
    delete, done = store.delete_batch_job, []

    def delete_batch_job(key):
        if not done:
            return done.append(key)
        delete(key)

    store.delete_batch_job = delete_batch_job
    return loop.default_scheduler(store, conf)


class _HoldsOnce(deque):
    """A work queue that holds an equal request once, as the reference's
    does: a request equal to one that waits is not added again."""

    def __init__(self):
        super().__init__()
        self.waiting, self.folded = set(), 0

    @staticmethod
    def _key(r):
        return (r.namespace, r.job_name, r.task_name, r.event, r.exit_code,
                r.action, r.job_version)

    def append(self, req):
        if self._key(req) in self.waiting:
            self.folded += 1
        else:
            self.waiting.add(self._key(req))
            super().append(req)

    def popleft(self):
        req = super().popleft()
        self.waiting.discard(self._key(req))
        return req


def _broken_slow_controller(store, conf, folds=False):
    """A job controller that handles one request a pump: with
    ``max_pumps: 1`` it is never pumped to the end, whatever its queue
    (``folds``: ``JobController.queue`` planted to hold an equal request
    once; the controller is built by now and its queue is still empty)."""
    from volcano_tpu.controllers import JobController

    patch = pytest.MonkeyPatch()
    process_all = JobController.process_all
    patch.setattr(JobController, "process_all",
                  lambda self, max_iters=1: process_all(self, 1))
    if folds:
        queues = {}
        patch.setattr(JobController, "queue", property(
            lambda self: queues.setdefault(id(self), _HoldsOnce()),
            lambda self, value: None), raising=False)
    _broken_slow_controller.undo = patch.undo
    return loop.default_scheduler(store, conf)


def _broken_slow_controller_that_folds(store, conf):
    return _broken_slow_controller(store, conf, folds=True)


JOBS_PATH = {_broken_renames_a_pod, _broken_withholds_running,
             _broken_skips_a_delete, _broken_slow_controller}
EVICTION_PATH = {_broken_kubelet, _broken_evicts_the_batch,
                 _broken_double_of_a_restored_key,
                 _broken_deletes_behind_the_client}


@pytest.mark.parametrize("broken,symptoms", [
    (_broken_idle, ["validate: unbound ="]),
    (_broken_misdirect, ["validate: oversubscribed ="]),
    (_broken_second_choice, ["probe:"]),
    (_broken_kubelet, ["validate: never_terminated =", "validate: unbound ="]),
    (_broken_evicts_the_batch, ["validate: victim_of_the_top_class ="]),
    (_broken_double_of_a_restored_key, ["validate: double ="]),
    (_broken_deletes_behind_the_client, ["validate: lost ="]),
    (_broken_renames_a_pod, ["validate: pods_not_as_planned = 1 "]),
    (_broken_withholds_running, ["validate: jobs_not_running = 1 "]),
    (_broken_skips_a_delete, ["validate: jobs_left_behind = 1 "])])
def test_broken_timed_path_is_not_correct(request, capsys, broken, symptoms):
    if broken in EVICTION_PATH:
        path = request.getfixturevalue("toy_preempt")
        cell, wait = cell_mod.load_cell("toypre.pre", path), None
    elif broken in JOBS_PATH:
        path = request.getfixturevalue("toy_jobs")
        cell, wait = cell_mod.load_cell("toyjob.asjobs", path), None
    else:
        path = request.getfixturevalue("toy")
        # keep the idle scheduler's rounds short
        cell, wait = cell_mod.load_cell("toy.drip", path), 0.01
    try:
        result = bench_run.run(cell, seed=7, seconds=0.5, trace=False,
                               make_scheduler=broken, bind_wait_s=wait)
    finally:
        getattr(broken, "undo", lambda: None)()
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert result["failed"] > 0
    for symptom in symptoms:
        line = [ln for ln in out.splitlines() if ln.startswith(symptom)][0]
        assert int(line.split("=")[-1].split()[0] if "=" in line
                   else line.split()[1]) > 0, line
    if symptoms == ["probe:"]:    # nothing but the node choice is wrong
        assert "validate: unbound = 0" in out
        assert "validate: oversubscribed = 0" in out
    if broken in EVICTION_PATH:   # and no cycle's wait was a time-out
        assert "0 waits of the run reached bind_wait_s" in out
    if broken is _broken_withholds_running:     # its wait ended, by pumps
        assert "OUT OF PUMPS (max_pumps 4): reconcile in 1 rounds" in out


@pytest.mark.parametrize("slow", [_broken_slow_controller,
                                  _broken_slow_controller_that_folds],
                         ids=["the_programs_queue", "a_queue_that_holds_once"])
@pytest.mark.parametrize("toy_jobs", [1], indirect=True)
def test_a_program_too_slow_to_reconcile_ends_with_its_last_line(
        toy_jobs, monkeypatch, capsys, slow):
    """No pump has a time limit, so every wait on the controllers is counted
    in pumps: a controller that cannot keep up leaves pods failed, and the
    run goes on to its result line and says which phase ran out.  Slow is
    one request a pump, so that the guard holds whether the program's queue
    keeps every request or holds an equal one once."""
    seen = _keep_driver(monkeypatch)
    cell = cell_mod.load_cell("toyjob.asjobs", toy_jobs)
    assert cell.sizes()["max_pumps"] == 1
    try:
        result = bench_run.run(cell, seed=7, seconds=0.5, trace=False,
                               make_scheduler=slow)
        queue = seen["driver"].jobs.manager.job_controller.queue
    finally:
        _broken_slow_controller.undo()
    # the plant took: equal requests were folded there, and only there
    assert getattr(queue, "folded", 0) > 0 \
        if slow is _broken_slow_controller_that_folds else type(queue) is deque
    out = capsys.readouterr().out
    assert set(result) == RESULT_KEYS
    assert result["correct"] is False and result["failed"] > 0
    assert result["attempted"] > 0
    assert result["compared"]["unbound"]["value"] > 0
    line = [ln for ln in out.splitlines() if "OUT OF PUMPS (max_pumps 1)" in ln]
    assert line and "its pumps took up to" in line[0], out[-2000:]


# ---- the probe's own fill (``probe.fill_pods``), on the same toy -------------


FILL = {"probes": 4, "fill_pods": 48}
FILL_AND_DRAIN = dict(FILL, before_drain=1, keep_pods=24)


@pytest.mark.parametrize("toy_jobs", [FILL, FILL_AND_DRAIN], indirect=True,
                         ids=["no_drain", "a_drain_through_it"])
def test_toy_jobs_probe_brings_a_fill_of_its_own(toy_jobs, monkeypatch, capsys):
    """``probe.fill_pods``: after the batch-sized fill, which enters as Jobs
    like the window's batches, one round of that many pods in the
    configuration's gang shape which the client places itself, as pods, on
    the emptiest nodes; they run and stay, the ledger holds them like any
    round's pods and no count of a Jobs cell sees them.  A drain that
    reaches them completes Jobs as Jobs and their gangs as pods."""
    from volcano_tpu.api import PodPhase

    seen = _keep_driver(monkeypatch)
    cell = cell_mod.load_cell("toyjob.asjobs", toy_jobs)
    drains = "keep_pods" in cell.config["probe"]
    result = bench_run.run(cell, seed=2**31 + 54, seconds=0.5, trace=False)
    out = capsys.readouterr().out
    driver = seen["driver"]
    assert result["correct"] is True and result["failed"] == 0, out[-3000:]
    assert {name for name, c in result["compared"].items() if c["value"]} \
        == {"fullest_node"}
    assert {"pods_not_as_planned", "jobs_not_running", "jobs_left_behind",
            "lost", "ghost", "unbound", "oversubscribed", "probe_misses"} \
        <= set(result["compared"])
    tags = [r.plan.tag for r in driver.rounds]
    after = tags[tags.index("probefill"):]
    assert after == ["probefill", "probefill-own"] + [f"probe{k:03d}" for k in range(4)]
    fill, own, first = driver.rounds[-6:-3]
    # the batch-sized fill is the controllers' to make, the probe's own is not
    assert fill.plan.n_pods == 24 and fill.jobs is not None and fill.pumps["submit"] == 1
    assert len(fill.jobs.created) == 24 and not fill.deleted
    assert own.plan.n_pods == 48 and own.plan.sizes().tolist() == [6] * 8
    assert own.plan.gang_min_member.tolist() == [3] * 8
    assert own.jobs is None and own.pumps == {} and own.t_admitted == 0
    assert own.cycles == 0 and not own.deleted and own.spans()["schedule"] == 0
    # no bind of it reached the binder: its arrival is the client's placement,
    # one pod at a time on the node that held the fewest (8 cpu a node: the
    # window's and the fill's Jobs stand 8 a node on the first three)
    (_t, keys, hosts), = own.arrivals
    assert keys == own.plan.keys()
    assert not set(keys) & {k for _t, ks, _h in driver.binder.arrivals for k in ks}
    assert hosts == [f"node-{i:06d}" for i in list(range(3, 48)) + [3, 4, 5]]
    # the next pump learns its records and counts none as made under a Job
    assert sorted(k for k, _owner in first.jobs.created) == first.plan.keys()
    assert set(own.plan.keys()) <= set(driver.jobs.uid_of)
    store = driver.store
    left = [p for p in store.pods.values() if p.name.startswith("probefill-own")]
    if drains:
        # keep_pods 24: the four Jobs of the fill finish as Jobs, then five
        # gangs of the probe's own as pods, in the first probe's round
        assert sorted(first.deleted) == sorted(fill.plan.keys()
                                               + own.plan.keys()[:30])
        assert len(left) == 18 and first.pumps["complete"] == 2
        assert not [k for k in store.pod_groups if "probefill-own-pg-000004" in k]
    else:
        assert len(left) == 48
    placed = dict(zip(keys, hosts))
    for pod in left:
        assert (pod.owner_job, pod.uid, pod.phase, pod.node_name) \
            == ("", f"bench-{pod.name}", PodPhase.Running,
                placed[f"default/{pod.name}"])
    assert sorted(store.batch_jobs) == sorted(
        job.key for job, pods in driver.fifo if isinstance(pods[0], str))
    line = [ln for ln in out.splitlines() if ln.startswith("after the window")]
    assert "48 pods of its own, placed by the client as pods" in line[0]
    assert "at the first probe 42 nodes hold 1, 3 nodes hold 2, 3 nodes hold 8" \
        in line[0]


def _fill_counted_under_a_job(store, conf):
    """The harness's own book wrong: the probe's own fill recorded as a
    round of Jobs, so its pods are owed under Jobs nobody made."""
    from benchmark.harness.validate import JobEvents

    patch = pytest.MonkeyPatch()
    place = loop.Driver.place

    def as_jobs(self, plan, hosts):
        rec = place(self, plan, hosts)
        rec.jobs = JobEvents((), (), ())
        return rec

    patch.setattr(loop.Driver, "place", as_jobs)
    _fill_counted_under_a_job.undo = patch.undo
    return loop.default_scheduler(store, conf)


def _fill_dealt_to_one_node(store, conf):
    """The harness's own dealing wrong: every pod of the probe's own fill
    placed on the first node, which the fill's Jobs have filled."""
    from benchmark.harness import probe

    patch = pytest.MonkeyPatch()
    patch.setattr(probe, "_deal",
                  lambda driver, plan: ["node-000000"] * plan.n_pods)
    _fill_dealt_to_one_node.undo = patch.undo
    return loop.default_scheduler(store, conf)


class _DeletesOfTheFill:
    """A scheduler that once deletes a running pod of the probe's own fill."""

    def __init__(self, real, store):
        self.real, self.store, self.done = real, store, False

    def run_once(self):
        self.real.run_once()
        own = [] if self.done else [
            p for p in self.store.pods.values() if p.phase == "Running"
            and p.name.startswith("probefill-own")]
        if own:
            self.store.delete_pod(own[-1])
            self.done = True


def _fill_deleted_behind_the_client(store, conf):
    return _DeletesOfTheFill(loop.default_scheduler(store, conf), store)


@pytest.mark.parametrize("toy_jobs", [FILL], indirect=True, ids=["fill_pods"])
@pytest.mark.parametrize("broken,symptom", [
    (_fill_counted_under_a_job, "pods_not_as_planned"),
    (_fill_dealt_to_one_node, "oversubscribed"),
    (_fill_deleted_behind_the_client, "lost")])
def test_a_fill_of_the_probes_own_that_goes_wrong_is_not_correct(
        toy_jobs, capsys, broken, symptom):
    cell = cell_mod.load_cell("toyjob.asjobs", toy_jobs)
    try:
        result = bench_run.run(cell, seed=7, seconds=0.5, trace=False,
                               make_scheduler=broken)
    finally:
        getattr(broken, "undo", lambda: None)()
    out = capsys.readouterr().out
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"][symptom]["value"] >= 1, out[-3000:]
    assert f"validate: {symptom} = {result['compared'][symptom]['value']} " in out
    # the window and the batch-sized fill were sound: the fill alone is caught
    assert result["compared"]["unbound"]["value"] == 0


def test_a_fill_past_the_clusters_room_does_not_load(toy_jobs):
    """Window, fill, the probe's own fill and the probes stay under the
    cluster's cpu, memory and pod slots, by the files' own numbers."""
    home = toy_jobs.parent / "benchmark"
    config = json.loads((home / "configs" / "toyjob.json").read_text())

    def load(**probe):
        (home / "configs" / "toyjob.json").write_text(json.dumps(
            dict(config, probe=dict(config["probe"], **probe))))
        return cell_mod.load_cell("toyjob.asjobs", toy_jobs)

    # 48 nodes of 8 cpu: 24 + 24 + 331 + 4 = 383 of 384
    assert load(fill_pods=331).config["probe"]["fill_pods"] == 331
    with pytest.raises(SystemExit, match="fill_pods 332.*384 cpu of the cluster's 384"):
        load(fill_pods=332)
    with pytest.raises(SystemExit, match="at least one pod"):
        load(fill_pods=0)


def test_no_accelerator_and_no_cpu_named_fails(toy, monkeypatch):
    """``require_accelerator`` is the program's; the harness calls it before
    it builds anything."""
    from volcano_tpu import device

    monkeypatch.setattr(device, "cpu_requested", lambda: False)
    cell = cell_mod.load_cell("toy.drip", toy)
    with pytest.raises(RuntimeError, match="no accelerator"):
        bench_run.run(cell, seed=1, seconds=0.1, trace=False)
