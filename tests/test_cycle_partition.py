"""The cycle accounts for itself (ISSUE 25): the lanes of a
``CycleRecord`` partition ``Scheduler.run_once()``, the time is named
where it is (``commit:*`` / ``device:*`` children, ``bind:*`` events of
the dispatcher thread, ``store:rebuild_objects``), the solve's counts
ride the record, and every lane is also a ``vc:<lane>`` annotation on
the profiler's clock.

Holds the lanes rule stated in ``volcano_tpu/obs/trace.py``.  All on a
small CPU cluster through ``Scheduler.run_once()``; tier-1.
"""

import itertools
import sys
import threading
import time

import pytest

from volcano_tpu.obs import export
from volcano_tpu.obs.trace import NESTED_LANES, Tracer
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

pytestmark = pytest.mark.tier1

NEW_LANES = {"prologue", "inflight", "solve_prep",
             "bind_handoff", "audit", "record", "gc"}
OLD_LANES = {"derive", "order", "encode", "device", "commit", "close"}


def _store(seed=7, **kw):
    kw.setdefault("n_nodes", 8)
    kw.setdefault("n_pods", 32)
    kw.setdefault("gang_size", 4)
    store = synthetic_cluster(seed=seed, **kw)
    # Async bind dispatch, as in production and in the benchmark.
    store.async_bind = True
    return store


def _cycle(store, **kw):
    """One ``run_once()`` with the binds flushed; returns its record and
    the caller's own clock around the call."""
    t0 = time.perf_counter()
    Scheduler(store, **kw).run_once()
    outer_s = time.perf_counter() - t0
    store.flush_binds()
    return store.flight.recent()[-1], outer_s


def _top_level_sum(rec):
    return sum(s for name, s in rec.lanes.items()
               if name not in NESTED_LANES)


def _by_id(rec):
    return {s.span_id: s for s in rec.spans}


def _children(rec, parent):
    return [s for s in rec.spans if s.parent_id == parent.span_id]


def _assert_partitioned(rec):
    assert _top_level_sum(rec) <= rec.duration_s
    assert rec.unattributed_s == pytest.approx(
        rec.duration_s - _top_level_sum(rec), abs=1e-12)
    assert rec.unattributed_s >= 0.0
    assert rec.to_dict()["unattributed_ms"] == pytest.approx(
        rec.unattributed_s * 1e3, abs=1e-3)


def _assert_lanes_rule(rec):
    """No lane's span has a lane's span as ancestor; a lane's seconds
    are the sum of its spans; the nested pair is the one exception and
    has no span of its own."""
    spans = _by_id(rec)
    by_lane = {}
    for s in rec.spans:
        if s.lane is None:
            continue
        assert s.tid == "cycle"
        by_lane[s.lane] = by_lane.get(s.lane, 0.0) + s.dur_ns * 1e-9
        up = spans.get(s.parent_id)
        while up is not None:
            assert up.lane is None, (s.name, "lies under lane", up.name)
            up = spans.get(up.parent_id)
    assert set(by_lane) == set(rec.lanes) - set(NESTED_LANES)
    for lane, seconds in by_lane.items():
        assert rec.lanes[lane] == pytest.approx(seconds, abs=1e-9)
    # Every lane span lies inside the outer ``cycle`` span.
    cycle = [s for s in rec.spans if s.name == "cycle"]
    assert len(cycle) == 1 and cycle[0].parent_id == 0
    for s in rec.spans:
        if s.lane is not None:
            assert s.ts_ns >= cycle[0].ts_ns
            assert (s.ts_ns + s.dur_ns
                    <= cycle[0].ts_ns + cycle[0].dur_ns)


# ------------------------------------------------------------ the partition


def test_every_new_lane_is_present_beside_the_old_ones():
    rec, _ = _cycle(_store())
    assert rec.path == "fast" and rec.error is None
    assert NEW_LANES <= set(rec.lanes)
    assert OLD_LANES <= set(rec.lanes)
    assert rec.pods_bound == 32
    # The ``dispatched`` stamp is no lane (ISSUE 32): it runs inside
    # ``device``, in the wait for the solve.
    assert "journey" not in rec.lanes
    assert not [s for s in rec.spans if s.name == "journey"]


def test_lanes_sum_to_at_most_the_duration_and_the_record_states_the_rest():
    store = _store(seed=11)
    rec, _ = _cycle(store)
    _assert_partitioned(rec)
    # A cycle with nothing to solve is partitioned too.
    idle, _ = _cycle(store)
    assert idle.pods_bound == 0
    _assert_partitioned(idle)
    assert {"prologue", "inflight", "audit", "record", "gc"} <= set(idle.lanes)


def test_duration_means_run_once():
    """The record covers entry to exit of ``run_once()``: the caller's
    own clock around the call reads the same, up to the sealing."""
    store = _store(seed=13)
    _cycle(store)  # compile
    for _ in range(3):
        _submit_more(store)
        rec, outer_s = _cycle(store)
        assert rec.pods_bound == 4
        assert rec.duration_s <= outer_s
        assert outer_s - rec.duration_s < 0.005


_more = itertools.count(1)


def _submit_more(store, n=4):
    """One more gang of ``n`` small pods."""
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup

    tag = next(_more)
    pg = PodGroup(name=f"more{tag}", min_member=n)
    store.add_pod_group(pg)
    for k in range(n):
        store.add_pod(Pod(name=f"more{tag}-{k}",
                          annotations={GROUP_NAME_ANNOTATION: pg.name},
                          containers=[{"cpu": "1", "memory": "1Gi"}]))


def _pipelined():
    store = _store(seed=17)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    sched.run_once()
    store.flush_binds()
    return store.flight.recent()


def _object_path(monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_FASTPATH", "0")
    store = _store(seed=19, n_nodes=4, n_pods=8, gang_size=2)
    rec, _ = _cycle(store)
    assert rec.path == "object"
    return [rec]


CONF_PREEMPT = """
actions: "enqueue, allocate, preempt, reclaim, backfill"
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


def _preempt_conf():
    from volcano_tpu.synth import preempt_cluster

    store = preempt_cluster(n_nodes=4, fill_per_node=4, n_pending=8,
                            gang_size=2, seed=23)
    store.async_bind = True
    rec, _ = _cycle(store, conf_str=CONF_PREEMPT)
    assert rec.path == "fast"
    assert {"preempt", "reclaim"} <= set(rec.lanes)
    return [rec]


@pytest.mark.parametrize("path", ["sync", "pipelined", "object", "preempt"])
def test_no_lane_span_lies_under_a_lane_span(path, monkeypatch):
    if path == "sync":
        recs = [_cycle(_store())[0]]
    elif path == "pipelined":
        recs = _pipelined()
        assert {"inflight_fetch", "inflight_commit"} <= {
            s.name for s in recs[-1].spans}
    elif path == "object":
        recs = _object_path(monkeypatch)
        assert {"prologue", "open", "close", "record", "gc"} <= set(
            recs[0].lanes)
    else:
        recs = _preempt_conf()
    for rec in recs:
        _assert_lanes_rule(rec)
        _assert_partitioned(rec)


# ------------------------------------------------------------- the children


def test_commit_children_have_the_commit_span_as_parent_and_fit_in_it():
    rec, _ = _cycle(_store())
    commits = [s for s in rec.spans if s.lane == "commit"]
    assert commits
    seen = set()
    for commit in commits:
        kids = _children(rec, commit)
        assert kids and all(k.name.startswith("commit:") for k in kids)
        assert all(k.lane is None for k in kids)
        assert sum(k.dur_ns for k in kids) <= commit.dur_ns
        seen |= {k.name for k in kids}
    assert {"commit:guard", "commit:journey", "commit:state",
            "commit:records"} <= seen
    # Nowhere else: a ``commit:*`` span always has a commit lane above.
    spans = _by_id(rec)
    for s in rec.spans:
        if s.name.startswith("commit:"):
            assert spans[s.parent_id].lane == "commit"


def test_commit_notify_and_bind_children_on_the_watched_sync_bind_path():
    store = _store(seed=29)
    store.async_bind = False
    seen = []
    store.watch(lambda kind, event, obj: seen.append((kind, event)))
    rec, _ = _cycle(store)
    names = {s.name for s in rec.spans}
    assert {"commit:bind", "commit:notify"} <= names
    assert ("Pod", "bind") in seen
    # One span per block, never one per pod.
    assert sum(1 for s in rec.spans if s.name == "commit:notify") == sum(
        1 for s in rec.spans if s.lane == "commit")
    _assert_lanes_rule(rec)


def test_pipelined_commit_children_hang_under_inflight_commit():
    rec = _pipelined()[-1]
    commit = next(s for s in rec.spans if s.name == "inflight_commit")
    assert commit.lane == "commit"
    assert {"commit:guard", "commit:journey", "commit:state"} <= {
        k.name for k in _children(rec, commit)}
    first = _pipelined()[0]
    dispatch = next(s for s in first.spans if s.name == "dispatch")
    assert dispatch.lane == "device"
    assert "dispatch:journey" in {k.name for k in _children(first, dispatch)}
    assert "journey" not in first.lanes


def test_device_children_have_the_device_span_as_parent_and_fit_in_it():
    rec, _ = _cycle(_store())
    devices = [s for s in rec.spans if s.name == "device"]
    assert devices and all(s.lane == "device" for s in devices)
    for dev in devices:
        kids = _children(rec, dev)
        kids = sorted(kids, key=lambda k: k.ts_ns)
        assert [k.name for k in kids] == [
            "device:dispatch", "device:journey", "device:host_prep",
            "device:fetch", "device:gate"]
        assert all(k.lane is None for k in kids)
        assert sum(k.dur_ns for k in kids) <= dev.dur_ns
        assert dev.args["rows"] > 0
        # The ``dispatched`` stamp (ISSUE 32): after the programs are
        # enqueued, before the host waits for them, inside the lane.
        stamp = kids[1]
        assert stamp.args == {"rows": dev.args["rows"],
                              "fresh": dev.args["rows"]}
        assert kids[0].ts_ns + kids[0].dur_ns <= stamp.ts_ns
        assert stamp.ts_ns + stamp.dur_ns <= kids[3].ts_ns
        assert dev.ts_ns <= stamp.ts_ns
        assert stamp.ts_ns + stamp.dur_ns <= dev.ts_ns + dev.dur_ns
    # The dispatch legs are lanes of their own inside ``device`` (the one
    # nested pair), timed in ops/wave.py, and fit in the dispatch span.
    dispatch_s = sum(s.dur_ns for s in rec.spans
                     if s.name == "device:dispatch") * 1e-9
    assert set(NESTED_LANES) <= set(rec.lanes)
    assert (rec.lanes["device_coarse"] + rec.lanes["device_fine"]
            <= dispatch_s)
    # The back-dated reconstructions are gone.
    assert not {"device_coarse", "device_fine", "device_solve"} & {
        s.name for s in rec.spans}


def test_derive_and_order_children_on_a_four_queue_cycle():
    """ISSUE 27: four weighted queues and gangs in the same cycle: the
    fair-share children lie inside ``derive`` / ``order``, the lanes
    still partition ``run_once()``, and the overuse gate's count rode
    the one fetch that was there."""
    store = _store(seed=27, n_nodes=12, n_pods=96, gang_size=4, n_queues=4,
                   queue_weights=(1, 2, 4, 8))
    rec, outer_s = _cycle(store)
    _assert_partitioned(rec)
    _assert_lanes_rule(rec)
    assert rec.duration_s <= outer_s
    want = {"derive": ["derive:proportion"],
            "order": ["order:shares", "order:queues", "order:jobs",
                      "order:tasks"]}
    for lane, names in want.items():
        parents = [s for s in rec.spans if s.name == lane]
        assert parents and all(s.lane == lane for s in parents)
        for parent in parents:
            kids = sorted(_children(rec, parent), key=lambda k: k.ts_ns)
            assert [k.name for k in kids] == names
            assert all(k.lane is None for k in kids)
            assert sum(k.dur_ns for k in kids) <= parent.dur_ns
            for k in kids:
                assert parent.ts_ns <= k.ts_ns
                assert (k.ts_ns + k.dur_ns
                        <= parent.ts_ns + parent.dur_ns)
    args = {s.name: s.args for s in rec.spans if ":" in s.name}
    assert args["derive:proportion"]["queues"] == 4
    assert len(args["derive:proportion"]["deserved_cpu"]) == 4
    assert args["derive:proportion"]["iterations"] >= 1
    assert args["order:queues"] == {"queues": 4, "overused": 0}
    assert args["order:tasks"] == {"cache_hit": False}
    solve = rec.solve
    assert solve["queues"] == 4 and solve["jobs"] == 24
    assert solve["gang_size_max"] == 4
    assert solve["dispatches"] == solve["fetches"] == 1
    assert solve["overuse_gated_jobs"] == 0


# ---------------------------------------------------- bind thread and store


def test_a_bind_batch_leaves_five_events_on_the_bind_track():
    store = _store(seed=31)
    _cycle(store)           # the batch's events drain with the next record
    rec, _ = _cycle(store)
    spans = [s for r in store.flight.recent() for s in r.spans]
    bind = [s for s in spans if s.tid == "bind"]
    assert sorted(s.name for s in bind) == [
        "bind:binder", "bind:materialize", "bind:on_success",
        "bind:queue_wait", "bind:release"]
    for s in bind:
        assert s.args == {"pods": 32} and s.parent_id == 0
        assert s.lane is None and s.dur_ns >= 0
    order = [s.name for s in sorted(bind, key=lambda s: s.ts_ns)]
    assert order == ["bind:queue_wait", "bind:materialize", "bind:binder",
                     "bind:on_success", "bind:release"]
    # Batches, not pods: nothing is recorded per pod anywhere.
    assert len(spans) < 3 * 60


def test_a_delete_after_a_commit_leaves_one_rebuild_event():
    store = _store(seed=37)
    _cycle(store)
    store.delete_pod(next(iter(store.pods.values())))
    store.delete_pod(next(iter(store.pods.values())))
    rec, _ = _cycle(store)
    # The deletes alone leave the stale model alone: nobody read it.
    assert not [s for s in rec.spans if s.name == "store:rebuild_objects"]
    assert rec.object_model == {"stale": 1, "stale_events": 2}
    assert len(store.jobs) == 8     # a reader: pays the rebuild
    store.delete_pod(next(iter(store.pods.values())))   # kept up, fresh
    rec, _ = _cycle(store)
    rebuilds = [s for s in rec.spans if s.name == "store:rebuild_objects"]
    assert len(rebuilds) == 1
    assert rebuilds[0].tid == "store" and rebuilds[0].parent_id == 0
    # The two victims are gone, and their deletes were taken stale.
    assert rebuilds[0].args == {"pods": 30, "stale_events": 2}
    assert rebuilds[0].dur_ns > 0
    assert rec.object_model == {"stale": 0, "stale_events": 0}
    assert rec.to_dict()["object_model"] == rec.object_model


# --------------------------------------------------------------- the counts


def test_solve_counts_on_a_solving_cycle_and_none_on_a_null_delta_skip():
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup

    store = _store(seed=41)
    # A gang no node can hold keeps the pending set non-empty, so the
    # allocate action reaches its skip check on the idle cycles.
    store.add_pod_group(PodGroup(name="big", min_member=1))
    store.add_pod(Pod(
        name="big-0", annotations={GROUP_NAME_ANNOTATION: "big"},
        containers=[{"cpu": "512", "memory": "512Gi"}]))
    rec, _ = _cycle(store)
    solve = rec.solve
    assert isinstance(solve, dict)
    assert rec.to_dict()["solve"] == solve
    assert solve["dispatches"] >= 1 and solve["rows"] >= 33
    assert solve["nodes"] == 8
    assert solve["fetches"] == solve["dispatches"]
    assert solve["fetch_bytes"] > 0
    assert solve["arg_puts"] > 0 and solve["arg_put_bytes"] > 0
    assert solve["devincr_mode"] in ("warm", "full", None)
    # The device snapshot was built in this cycle: full uploads, and
    # the bytes of what was put.
    assert solve["devsnap_full"] >= 1 and solve["devsnap_puts"] >= 1
    assert solve["devsnap_put_bytes"] > 0
    assert solve["devsnap_delta"] == 0
    # Nothing changes: the next cycles skip the dispatch wholesale.
    skipped = None
    for _ in range(4):
        nxt, _ = _cycle(store)
        if any("null-delta" in ev for ev in nxt.device_events):
            skipped = nxt
            break
    assert skipped is not None, "no null-delta skip within four idle cycles"
    assert skipped.solve is None
    assert skipped.to_dict()["solve"] is None


def test_solve_counts_hit_the_device_snapshot_on_a_second_solve():
    store = _store(seed=43)
    _cycle(store)
    _submit_more(store)
    rec, _ = _cycle(store)
    assert rec.solve is not None and rec.solve["rows"] == 4
    assert rec.solve["devsnap_full"] == 0
    assert rec.solve["devsnap_hits"] + rec.solve["devsnap_delta"] >= 1


# ----------------------------------------------------- off, failed, fallback


def test_with_tracing_off_the_lanes_stay_and_no_span_is_recorded(monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_TRACE", "0")
    store = _store(seed=47)
    rec, _ = _cycle(store)
    assert rec.spans == []
    assert NEW_LANES <= set(rec.lanes) and OLD_LANES <= set(rec.lanes)
    _assert_partitioned(rec)
    victim = next(iter(store.pods.values()))
    store.delete_pod(victim)
    rec, _ = _cycle(store)
    assert rec.spans == []      # no bind:* and no store:* event either


def test_a_failed_cycle_still_seals_a_partitioned_record(monkeypatch):
    from volcano_tpu import fastpath

    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")

    def boom(self):
        raise RuntimeError("injected")

    monkeypatch.setattr(fastpath.FastCycle, "_allocate", boom)
    store = _store(seed=53)
    with pytest.raises(RuntimeError, match="injected"):
        Scheduler(store).run_once()
    rec = store.flight.recent()[-1]
    assert rec.error == "RuntimeError" and rec.path == "fast"
    assert {"prologue", "derive", "bind_handoff", "audit", "record",
            "gc"} <= set(rec.lanes)
    _assert_partitioned(rec)
    _assert_lanes_rule(rec)
    cycle = next(s for s in rec.spans if s.name == "cycle")
    assert cycle.args["error"] == "RuntimeError"
    # The frame is closed: the next cycle opens its own.
    assert store.tracer.cycle() is not store.tracer.cycle()


def test_a_fallback_gives_two_records_that_partition_the_call(monkeypatch):
    """A failed fast cycle and the object session it falls back to are
    two records of one ``run_once()``: the second starts where the
    first was sealed."""
    from volcano_tpu import fastpath

    def boom(self):
        raise RuntimeError("injected")

    # conftest pins FALLBACK=never for the suite.
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "auto")
    monkeypatch.setattr(fastpath.FastCycle, "_allocate", boom)
    store = _store(seed=59, n_nodes=4, n_pods=8, gang_size=2)
    t0 = time.perf_counter()
    Scheduler(store).run_once()
    outer_s = time.perf_counter() - t0
    store.flush_binds()
    failed, fallback = store.flight.recent()
    assert (failed.path, failed.error) == ("fast", "RuntimeError")
    assert (fallback.path, fallback.error) == ("object", None)
    assert fallback.seq == failed.seq + 1
    for rec in (failed, fallback):
        _assert_partitioned(rec)
    assert "prologue" in failed.lanes and "open" in fallback.lanes
    assert "gc" in fallback.lanes and "gc" not in failed.lanes
    assert failed.duration_s + fallback.duration_s <= outer_s
    assert fallback.t_wall >= failed.t_wall


# --------------------------------------------------- the profiler's clock


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the
    names and how they nest."""

    def __init__(self):
        self.open = []
        self.seen = []      # (name, names open above it)

    def __call__(self, name):
        return _Annotation(self, name)


class _Annotation:
    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.log.seen.append((self.name, tuple(self.log.open)))
        self.log.open.append(self.name)

    def __exit__(self, *exc):
        assert self.log.open.pop() == self.name


def test_every_lane_and_the_cycle_open_a_vc_annotation():
    store = _store(seed=61)
    log = store.tracer.annotate = _Annotations()
    rec, _ = _cycle(store)
    assert log.open == []
    names = [name for name, _ in log.seen]
    assert names[0] == "vc:cycle" and names.count("vc:cycle") == 1
    lanes = set(rec.lanes) - set(NESTED_LANES)
    assert set(names) == {"vc:cycle"} | {"vc:" + lane for lane in lanes}
    # Each lane directly under the cycle: lanes do not nest.
    for name, above in log.seen[1:]:
        assert above == ("vc:cycle",), (name, above)
    # Children are not annotated.
    assert not any(":" in name[3:] for name in names)


def test_the_drivers_hand_the_profilers_factory_to_the_stores_tracer():
    from jax.profiler import TraceAnnotation

    from volcano_tpu.obs.trace import null_tracer

    store = _store(seed=67)
    assert store.tracer.annotate is None    # obs/ is stdlib-only
    _cycle(store)
    assert store.tracer.annotate is TraceAnnotation
    assert null_tracer().annotate is None


def test_annotations_are_kept_with_tracing_off(monkeypatch):
    monkeypatch.setenv("VOLCANO_TPU_TRACE", "0")
    store = _store(seed=71)
    log = store.tracer.annotate = _Annotations()
    rec, _ = _cycle(store)
    assert rec.spans == []
    assert {"vc:cycle", "vc:commit", "vc:gc"} <= {n for n, _ in log.seen}


# ------------------------------------------------ threads, SLO, the export


def test_spans_of_two_cycle_threads_on_one_tracer_do_not_mix():
    """Under the sharded control plane several cycle threads share one
    store's tracer, and a prologue runs before the store lock: each
    thread's parent stack and span buffer are its own."""
    tracer = Tracer(enabled=True)
    errors, drained = [], {}
    stop = time.perf_counter() + 1.0

    def work(tag):
        try:
            mine = []
            while time.perf_counter() < stop:
                with tracer.cycle() as scope:
                    with scope.lane(f"{tag}:lane"):
                        with tracer.span(f"{tag}:child"):
                            pass
                        tracer.event(f"{tag}:event", "t", 0, 1, tid=tag)
                    assert tracer.cycle() is scope
                    mine.extend(tracer.drain())
            drained[tag] = mine
        except Exception as e:      # pragma: no cover - the failure case
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(f"t{i}",))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(drained) == 8
    for tag, spans in drained.items():
        own = {s.span_id: s for s in spans if s.tid == "cycle"}
        assert own, tag
        for s in own.values():
            # Every span this thread recorded is its own, and a child's
            # parent is this thread's lane span, never another's.
            assert s.name.startswith(tag) or s.name == "cycle"
            if s.name == f"{tag}:child":
                assert own[s.parent_id].name == f"{tag}:lane"


def test_the_new_lanes_trip_no_budget_and_raise_nothing_in_the_slo():
    store = _store(seed=73)
    slo = store.auditor.slo
    slo.declare("cycle", 1e9)
    slo.declare("device", 1e9)
    rec, _ = _cycle(store)
    assert rec.anomalies == []
    snap = slo.snapshot()
    assert set(snap) <= {"cycle", "device", "idle", "ttb"}
    assert not any(entry.get("breached") for entry in snap.values())
    # The SLO's cycle observation ends where the audit lane begins.
    assert snap["cycle"]["p99_ms"] <= rec.duration_s * 1e3


def test_the_export_gives_bind_and_store_their_tracks_and_lanes_their_name():
    store = _store(seed=79)
    _cycle(store)
    store.delete_pod(next(iter(store.pods.values())))
    _cycle(store)
    names = {ev["name"] for ev in export.trace_events(store.flight.recent())}
    assert "store:rebuild_objects" not in names     # the delete read nothing
    assert store.nodes      # a reader of the model after a commit
    _cycle(store)
    events = export.trace_events(store.flight.recent())
    tracks = {ev["args"]["name"]: ev["tid"] for ev in events
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"cycle", "rpc", "bind", "store"} <= set(tracks)
    by_name = {}
    for ev in events:
        if ev["ph"] == "X":
            by_name.setdefault(ev["name"], []).append(ev)
    assert all(ev["tid"] == tracks["bind"]
               for name in by_name if name.startswith("bind:")
               for ev in by_name[name])
    assert by_name["store:rebuild_objects"][0]["tid"] == tracks["store"]
    assert by_name["commit"][0]["args"]["lane"] == "commit"
    assert by_name["action:enqueue"][0]["args"]["lane"] == "enqueue"
    assert "lane" not in by_name["commit:journey"][0]["args"]
    assert "lane" not in by_name["cycle"][0]["args"]
