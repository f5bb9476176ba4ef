"""Observability layer (ISSUE 3): trace spans, the cycle flight
recorder, Perfetto export, and the /debug endpoints.

Pins the acceptance contracts:

- a pipelined run's exported trace contains dispatch and commit spans
  for the SAME solve-id in adjacent cycles, linked via flow references,
  and loads cleanly as Chrome ``trace_event`` JSON;
- forced staleness drops (concurrent delete + competing bind + node
  churn) produce per-reason drop counters that sum exactly to the
  dropped rows, with ``/debug/cycles`` returning the matching record;
- the ring buffer is bounded; lane breakdowns survive tracing being
  disabled.

All CPU-only (conftest pins JAX_PLATFORMS=cpu); tier-1.
"""

import copy
import json
import urllib.request

import pytest

from volcano_tpu.api import GROUP_NAME_ANNOTATION, Node, Pod, PodGroup
from volcano_tpu.cache import ClusterStore
from volcano_tpu.metrics import metrics
from volcano_tpu.obs import CycleRecord, FlightRecorder, export
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

pytestmark = pytest.mark.tier1


def _small(seed=7, **kw):
    kw.setdefault("n_nodes", 8)
    kw.setdefault("n_pods", 32)
    kw.setdefault("gang_size", 4)
    return synthetic_cluster(seed=seed, **kw)


# ------------------------------------------------------------ trace export


def test_pipelined_trace_links_dispatch_and_commit_across_cycles():
    """The acceptance contract: dispatch span (cycle N) and the
    fetch/commit spans (cycle N+1) share one solve-id flow, the export
    emits matching flow start/finish events, and the whole trace
    round-trips as JSON."""
    store = _small()
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()  # cycle 1: dispatch only
    sched.run_once()  # cycle 2: commit lands
    store.flush_binds()

    recs = store.flight.recent()
    assert len(recs) == 2
    c1, c2 = recs
    solve_id = c1.dispatched_solve_id
    assert solve_id is not None
    # The SAME solve-id committed in the adjacent cycle.
    assert c2.committed_solve_id == solve_id
    dispatch_spans = [s for s in c1.spans if s.name == "dispatch"]
    commit_spans = [s for s in c2.spans
                    if s.name in ("inflight_fetch", "inflight_commit")]
    assert len(dispatch_spans) == 1
    assert len(commit_spans) == 2
    assert dispatch_spans[0].flow == solve_id
    assert all(s.flow == solve_id for s in commit_spans)

    # Export round-trips as Chrome trace_event JSON.
    blob = json.dumps(export.perfetto_trace(recs))
    trace = json.loads(blob)
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert "ph" in ev and "pid" in ev and "name" in ev
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float))
    # Flow arrow: one start + one finish carrying the solve-id, start
    # on the dispatch, finish on the commit side, in time order.
    starts = [ev for ev in events
              if ev["ph"] == "s" and ev["id"] == solve_id]
    finishes = [ev for ev in events
                if ev["ph"] == "f" and ev["id"] == solve_id]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["ts"] < finishes[0]["ts"]
    # Complete events for the linked spans exist with the flow id in
    # their args.
    xnames = {ev["name"] for ev in events if ev["ph"] == "X"}
    assert {"dispatch", "inflight_fetch", "inflight_commit"} <= xnames


def test_cycle_record_fields_cover_overlap_accounting():
    store = _small(seed=11)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    sched.run_once()
    store.flush_binds()
    recs = store.flight.recent()
    # Cycle 1 considered all 32 pending rows exactly once (no
    # double-counting across solver rounds).
    assert recs[0].pods_considered == 32
    rec = recs[-1]
    assert rec.path == "fast"
    assert rec.pods_bound == 32
    assert rec.inflight_fetch_wait_ms is not None
    # Nothing moved during the overlap: dispatch and commit see the
    # same mirror state.
    assert rec.mutation_seq_at_dispatch == rec.mutation_seq_at_commit
    assert rec.epoch_at_dispatch == rec.epoch_at_commit
    assert rec.duration_s > 0
    d = rec.to_dict()
    assert d["lanes_ms"] and "derive" in d["lanes_ms"]
    json.dumps(d)  # JSON-serializable as served by /debug/cycles


# ------------------------------------------------------ staleness reasons


def _drop_scenario_store():
    """Two roomy nodes, five plain pods, one selector pod — every
    staleness-drop reason below is then forceable during the overlap."""
    store = ClusterStore()
    store.add_node(Node(
        name="n0", allocatable={"cpu": "8", "memory": "32Gi", "pods": 64},
        labels={"zone": "a"},
    ))
    store.add_node(Node(
        name="n1", allocatable={"cpu": "8", "memory": "32Gi", "pods": 64},
    ))
    pg = PodGroup(name="g", min_member=1)
    store.add_pod_group(pg)
    for k in range(5):
        store.add_pod(Pod(
            name=f"p{k}",
            annotations={GROUP_NAME_ANNOTATION: pg.name},
            containers=[{"cpu": "1", "memory": "1Gi"}],
        ))
    store.add_pod(Pod(
        name="picky",
        annotations={GROUP_NAME_ANNOTATION: pg.name},
        containers=[{"cpu": "1", "memory": "1Gi"}],
        node_selector={"zone": "a"},
    ))
    store.pipeline = True
    return store


def _counter_totals():
    return dict(metrics.pipeline_stale_drops.data)


def test_drop_reasons_sum_exactly_to_dropped_rows():
    """Concurrent delete + competing bind + node churn during the
    overlap: the per-reason counts sum exactly to the dropped rows, and
    each forced reason is attributed."""
    store = _drop_scenario_store()
    sched = Scheduler(store)
    sched.run_once()  # dispatch over the 6 pending pods
    assert store._inflight_solve is not None

    # deleted: p0 goes away.
    victim = next(p for p in store.pods.values() if p.name == "p0")
    store.delete_pod(victim)
    # competing-bind: p1 is bound by "someone else" mid-overlap.
    p1 = next(p for p in store.pods.values() if p.name == "p1")
    p1b = copy.copy(p1)
    p1b.node_name = "n1"
    store.update_pod(p1b)
    # node-epoch-churn: the node table moves, so the selector row
    # ("picky") solved against stale label planes.
    store.add_node(Node(
        name="n1", allocatable={"cpu": "8", "memory": "32Gi", "pods": 64},
        labels={"freshly": "labelled"},
    ))

    before = _counter_totals()
    sched.run_once()  # fetch + staleness-guarded commit
    store.flush_binds()

    rec = next(r for r in reversed(store.flight.recent())
               if r.committed_solve_id is not None)
    assert rec.pods_dropped > 0
    assert sum(rec.drop_reasons.values()) == rec.pods_dropped
    assert rec.drop_reasons.get("deleted") == 1
    assert rec.drop_reasons.get("competing-bind") == 1
    # Node churn drops every node-sensitive row; "picky" is one of them.
    assert rec.drop_reasons.get("node-epoch-churn", 0) >= 1
    # The counter series moved by exactly the recorded amounts.
    after = _counter_totals()
    for reason, n in rec.drop_reasons.items():
        key = (("reason", reason),)
        assert after.get(key, 0.0) - before.get(key, 0.0) == n


def test_capacity_theft_attributed_as_capacity_taken():
    store = ClusterStore()
    for i in range(2):
        store.add_node(Node(
            name=f"n{i}",
            allocatable={"cpu": "1", "memory": "8Gi", "pods": 64},
        ))
    store.add_pod_group(PodGroup(name="g", min_member=1))
    for k in range(2):
        store.add_pod(Pod(
            name=f"p{k}",
            annotations={GROUP_NAME_ANNOTATION: "g"},
            containers=[{"cpu": "1", "memory": "1Gi"}],
        ))
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()  # dispatch: p0 -> one node, p1 -> the other
    for i in range(2):
        store.add_pod(Pod(
            name=f"thief{i}",
            annotations={GROUP_NAME_ANNOTATION: "g"},
            containers=[{"cpu": "1", "memory": "1Gi"}],
            node_name=f"n{i}",
        ))
    sched.run_once()  # guard drops both rows
    rec = next(r for r in reversed(store.flight.recent())
               if r.committed_solve_id is not None)
    assert rec.drop_reasons == {"capacity-taken": 2}
    assert rec.pods_dropped == 2


def test_lost_reply_recorded_not_as_clean_commit(monkeypatch):
    """A remote solve whose reply is lost must NOT record a committed
    solve-id with zero drops (that reads as a clean commit); the rows
    count under the lost-reply reason and the event names the solve."""
    from volcano_tpu import pipeline as pl

    store = _small(seed=19)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    inflight = store._inflight_solve
    assert inflight is not None
    n_rows = len(inflight.task_rows)
    inflight.kind = "remote"  # present the handle as a remote dispatch

    def lost(self):
        raise OSError("connection reset by peer")

    monkeypatch.setattr(pl.InflightSolve, "fetch", lost)
    sched.run_once()
    rec = store.flight.recent()[-1]
    assert rec.committed_solve_id is None
    assert rec.drop_reasons.get("lost-reply") == n_rows
    assert any("reply lost" in ev for ev in rec.device_events)


def test_compaction_void_counts_whole_result():
    store = _small(seed=9)
    store.pipeline = True
    sched = Scheduler(store)
    sched.run_once()
    n_inflight = len(store._inflight_solve.task_rows)
    store.mirror.compact_gen += 1  # what maybe_compact() does
    sched.run_once()
    rec = store.flight.recent()[-1]
    assert rec.drop_reasons.get("compaction") == n_inflight


# ------------------------------------------------------- /debug endpoints


def test_debug_endpoints_serve_ring_and_trace():
    """/debug/cycles, /debug/cycles/<seq> and /debug/trace serve the
    flight recorder over HTTP, including the drop accounting of a
    staleness-guarded cycle."""
    from volcano_tpu.service import Service

    store = _drop_scenario_store()
    sched = Scheduler(store)
    sched.run_once()
    victim = next(p for p in store.pods.values() if p.name == "p0")
    store.delete_pod(victim)
    sched.run_once()
    store.flush_binds()
    want = next(r for r in reversed(store.flight.recent())
                if r.committed_solve_id is not None)

    svc = Service(store=store, schedule_period=30.0,
                  controller_period=5.0)
    port = svc.start(http_port=0)
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                return json.loads(r.read())

        cycles = get("/debug/cycles")
        assert isinstance(cycles, list) and cycles
        match = [c for c in cycles if c["seq"] == want.seq]
        assert match, "the staleness cycle is in the served ring"
        assert match[0]["drop_reasons"] == dict(want.drop_reasons)
        assert match[0]["pods_dropped"] == want.pods_dropped
        assert (sum(match[0]["drop_reasons"].values())
                == match[0]["pods_dropped"])

        one = get(f"/debug/cycles/{want.seq}")
        assert one["seq"] == want.seq
        assert one["spans"], "per-cycle endpoint includes spans"

        trace = get("/debug/trace?cycles=8")
        assert "traceEvents" in trace and trace["traceEvents"]
        assert get("/debug/cycles?n=1")[-1]["seq"] == cycles[-1]["seq"]

        missing_rc = None
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/cycles/999999",
                timeout=10)
        except urllib.error.HTTPError as err:
            missing_rc = err.code
        assert missing_rc == 404
    finally:
        svc.stop()


# --------------------------------------------------------------- plumbing


def test_flight_recorder_ring_is_bounded():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record(CycleRecord(session=f"s{i}"))
    recs = fr.recent()
    assert [r.seq for r in recs] == [7, 8, 9, 10]
    assert fr.get(10).session == "s9"
    assert fr.get(1) is None
    assert fr.recent(2)[0].seq == 9
    assert fr.recent(0) == []
    assert fr.recent(-3) == []


def test_lanes_survive_tracing_disabled(monkeypatch):
    """VOLCANO_TPU_TRACE=0 drops span records but keeps the lane
    breakdown on the flight record."""
    monkeypatch.setenv("VOLCANO_TPU_TRACE", "0")
    store = _small(seed=13)
    Scheduler(store).run_once()
    store.flush_binds()
    rec = store.flight.recent()[-1]
    assert rec.spans == []
    assert rec.lanes
    assert "derive" in rec.lanes


def test_object_session_cycles_are_recorded(monkeypatch):
    """The object path (fast path disabled) records cycles too, with
    snapshot/action/plugin spans."""
    monkeypatch.setenv("VOLCANO_TPU_FASTPATH", "0")
    store = _small(seed=17, n_nodes=4, n_pods=8, gang_size=2)
    Scheduler(store).run_once()
    store.flush_binds()
    rec = store.flight.recent()[-1]
    assert rec.path == "object"
    names = {s.name for s in rec.spans}
    assert "snapshot" in names
    assert any(n.startswith("action:") for n in names)
    assert any(n.startswith("plugin:") for n in names)
