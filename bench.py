"""Benchmark suite: the five BASELINE.json configurations.

Select with BENCH_CONFIG=1..5, or the default "north" — the NORTH-STAR
shape itself (10k nodes x 100k pending pods, plain binpack+predicates,
gang 8): the driver-recorded number is the headline metric, lane split
included in the stderr comment.  Each config prints ONE JSON line
{"metric", "value", "unit", "vs_baseline"} on stdout; details go to
stderr.

Configs (BASELINE.json.configs):
  1. 3-replica gang Job end-to-end through the full service (admission ->
     job controller -> PodGroup -> scheduler -> bind -> simulated kubelet),
     the rebuild's `example/job.yaml on kind`.
  2. Synthetic 1k x 10k binpack+predicates, single queue.
  3. DRF multi-queue fairness: 5k nodes, 4 weighted queues, mixed gang sizes.
  4. Preempt + reclaim: 10k nodes fully occupied by low-priority victims,
     20k pending high-priority pods.
  5. Hyperscale bin-pack with inter-pod affinity / topology spread
     (full 50k x 500k when BENCH_FULL=1; 10k x 100k otherwise — the
     north-star shape).

The north-star budget is 100 ms OpenSession->Bind at 10k x 100k on one TPU
chip; vs_baseline = budget/measured with the budget scaled linearly by task
count (>= 1.0 means on budget at the measured scale).

Configs 2/3/5/north additionally report a `pipelined` metric (ISSUE 1
double-buffered sessions): steady-state cycle time amortized over >= 5
consecutive cycles on one store, each committing the previous cycle's
asynchronously-dispatched solve while dispatching the next — the plain
metric stays the synchronous loop.  Both JSON lines carry the per-lane
split in a "lanes" tail, and every JSON line names the device it was
taken on ("device": platform, kind, count); without an accelerator the
run fails at start unless JAX_PLATFORMS=cpu asked for the CPU.

Env knobs: BENCH_NODES/BENCH_PODS/BENCH_GANG/BENCH_REPEATS override config
defaults; BENCH_PIPELINE=0 skips the pipelined pass, BENCH_PIPE_CYCLES
sets the steady-state cycle count (min 5).  BENCH_TOPK A/Bs the
two-phase device solve in one run: the selected config executes twice —
"(shortlist on)" then "(shortlist off)" — emitting both JSON tails (a
numeric BENCH_TOPK > 1 also pins VOLCANO_TPU_TOPK for the on-pass); the
device_coarse/device_fine sub-lanes and the shortlist-fallback counts
ride the lane/fallback tails.  Every config additionally writes a
Perfetto-loadable trace file (flight-recorder cycles, BENCH_TRACE_DIR;
default /tmp/vtpu_bench_traces) and reports staleness-drop totals plus
per-lane p50/p95 (steady-state cycles only) in the machine-readable
JSON tail.

BENCH_HOST=1 (ISSUE 8) A/Bs the incremental host lanes in one run: the
selected config executes three times — "(incremental on)",
"(incremental off)" (full-rebuild derive, no host-lane caches), and
"(incremental fallback)" (VOLCANO_TPU_DIRTY_CAP=1, so every cycle
exercises the dirty-overflow fallback) — each emitting plain +
pipelined JSON tails whose `host_lanes_ms` field sums the host lanes
(derive+order+encode+commit+close+enqueue+feed+backfill) and whose
`lane_p50`/`lane_p95` tails carry the steady-state distribution.

BENCH_MESH=<devices> (ISSUE 7) A/Bs the mesh-native sharded solve in
one run: the mesh is built on the default backend (that many chips of a
TPU host, or virtual host devices under JAX_PLATFORMS=cpu; fewer devices
than asked for fails the run), then the selected config executes twice —
"(mesh on)" with every
store's ``solve_mesh`` set (node axis + count tensors sharded, sharded
devsnap, shard-local two-phase rankings) and "(mesh off)" plain — each
emitting its JSON tail with the usual lane split, plus one extra
"mesh winner-reduce" JSON line microbenching the cross-chip reduction
(the two-stage shard-local top-k vs the global top-k on the same
sharded plane).

BENCH_COMPOSED=1 (ISSUE 12) runs the authoritative north-star
composition: one "(plain)" synchronous pass followed by one
"(composed)" pipelined steady state with the mesh (BENCH_COMPOSED_MESH
devices of the default backend), VOLCANO_TPU_DEVINCR,
VOLCANO_TPU_INCREMENTAL
and a BENCH_COMPOSED_FRAC (default 5%) churn feed all engaged together,
ending with the null-delta probe.  The "composed" JSON tail carries the
engagement proof (mesh shards, devincr warm/full/skip, incremental
derive modes, plain-vs-composed ratio, knob matrix); every tail now
also reports compile/warmup separately from steady state (compile_ms +
warmup_cycles_ms).

BENCH_WIRE=1 (ISSUE 10) A/Bs the remote-solver transport in one run:
an in-process ``SolverServer`` thread serves solves over the REAL
loopback TCP stack (the solve shares this process's jit cache, so the
A/B isolates wire costs, not compile variance), every benched store
gets its own ``RemoteSolver`` client, and the selected config executes
three times — "(wire delta)" (``VOLCANO_TPU_WIRE=1``: delta solve
frames against the child's per-connection mirror), "(wire full)"
(``VOLCANO_TPU_WIRE=0``: classic v1 full frames), and
"(wire fallback)" (``VOLCANO_TPU_WIRE=fallback``: the delta machinery
runs but every frame voids the cache first, exercising the full-frame
fallback path).  The pipelined feed re-pends only BENCH_WIRE_FRAC of
the bound rows (default 5%, the steady-state churn shape), and each
pipelined JSON tail carries a "wire" section: per-kind frame counts
and bytes over the steady-state cycles, bytes/cycle (the number the
delta-vs-full A/B compares), and fallback counts by reason.
"""

import copy
import json
import os
import re
import sys
import time
from contextlib import contextmanager

from volcano_tpu.device import cpu_requested, device_info, require_accelerator

NORTH_STAR_MS = 100.0
NORTH_STAR_PODS = 100000

# BENCH_TOPK A/B driver state: suffix appended to every emitted metric
# name, so one run carries both "(shortlist on)"/"(shortlist off)" JSON
# tails (see main()).
_MODE_SUFFIX = ""
# BENCH_MESH A/B driver state: the jax.sharding.Mesh the benched stores
# dispatch over ("(mesh on)" pass), or None for the plain pass.
_MESH = None
# BENCH_DEVINCR driver state (ISSUE 9): the fraction of bound rows the
# pipelined feed re-pends per cycle (1.0 = everything — the classic
# steady-state loop; the devincr A/B uses a sparse fraction so the
# dirty set looks like production churn, not a full re-pend), and
# whether to append a null-delta probe (feed off for two cycles,
# asserting the skip path) to the pipelined pass.
_FEED_FRACTION = 1.0
_DEVINCR_PROBE = False

# BENCH_WIRE driver state (ISSUE 10): the in-process solver server's
# loopback port; when set, every benched store solves through its own
# RemoteSolver client and the pipelined tail carries wire telemetry.
_REMOTE_PORT = None

# The HOST lanes whose serial sum floors the pipelined cycle (ISSUE 8):
# everything the cycle thread does besides the device dispatch/fetch.
HOST_LANES = ("derive", "order", "encode", "commit", "close", "enqueue",
              "feed", "backfill")


def _host_lane_sum_ms(lanes) -> float:
    return sum(lanes.get(k, 0.0) for k in HOST_LANES) * 1e3


@contextmanager
def _twophase_env(on: bool, topk: int = 0):
    """Pin the two-phase knobs for one A/B pass (ops/wave.py reads them
    per call, so flipping works within one process; each mode compiles
    its own jit specialization)."""
    keys = ("VOLCANO_TPU_TWOPHASE", "VOLCANO_TPU_TOPK")
    old = {k: os.environ.get(k) for k in keys}
    os.environ["VOLCANO_TPU_TWOPHASE"] = "1" if on else "0"
    if on and topk > 1:
        os.environ["VOLCANO_TPU_TOPK"] = str(topk)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _attach_remote(store):
    """BENCH_WIRE: point the store at the in-process solver server over
    loopback TCP; returns the client (caller closes it)."""
    if _REMOTE_PORT is None:
        return None
    from volcano_tpu.solver_service import RemoteSolver

    client = RemoteSolver(f"127.0.0.1:{_REMOTE_PORT}")
    store.remote_solver = client
    return client


# Audit tail (ISSUE 13): the bench loops stash the benched store's
# auditor stats here; _emit folds them into the next JSON tail (every
# tail carries the audited-cycles count + measured overhead).
_AUDIT_TAIL = None

# Journey tail (ISSUE 18): same stash pattern for the pod-journey log —
# every pipelined tail carries ttb_p50/p95/p99 and the gang
# time-to-full-bind percentiles.
_JOURNEY_TAIL = None


def _collect_audit(store):
    global _AUDIT_TAIL
    a = getattr(store, "auditor", None)
    if a is not None and a.enabled:
        _AUDIT_TAIL = a.audit_stats()


def _collect_journey(store):
    global _JOURNEY_TAIL
    jr = getattr(store, "journey", None)
    if jr is not None:
        _JOURNEY_TAIL = jr.stats()


def _bench_mesh(n_dev):
    """The ``n_dev``-device mesh of a mesh mode, on the default backend.
    A CPU run asked for by name (JAX_PLATFORMS=cpu: the hack/ smokes) gets
    its virtual host devices first; on any backend, fewer devices than
    asked for raises."""
    if cpu_requested():
        from volcano_tpu.virtualcpu import force_virtual_cpu_platform

        force_virtual_cpu_platform(n_dev)
    from volcano_tpu.parallel import make_mesh

    return make_mesh(n_dev)


def _emit(metric, value_ms, n_pods, extra="", budget_ms=None, lanes=None,
          records=None, fallbacks=None, rebalance=None, devincr=None,
          wire=None, preempt=None, compile_ms=None, warmup_cycles=None,
          composed=None, endurance=None, pool=None, shards=None,
          topology=None):
    global _AUDIT_TAIL, _JOURNEY_TAIL
    metric = metric + _MODE_SUFFIX
    if budget_ms is None:
        budget_ms = NORTH_STAR_MS * (n_pods / NORTH_STAR_PODS)
    payload = {
        "metric": metric,
        "value": round(value_ms, 2),
        "unit": "ms",
        "vs_baseline": round(
            budget_ms / value_ms if value_ms > 0 else 0.0, 4
        ),
        # The backend the row was taken on rides every JSON line, so a
        # CPU run can never be read as a chip number.
        "device": device_info(),
    }
    if compile_ms is not None:
        # Compile/warmup time reported SEPARATELY from steady-state
        # (ISSUE 12 satellite: a multi-second jit spike inside
        # cycles_ms pollutes the distribution — steady-state numbers
        # NEVER include warmup cycles, and this field is where the jit
        # cost lives).
        payload["compile_ms"] = round(compile_ms, 1)
    if warmup_cycles is not None:
        payload["warmup_cycles_ms"] = [
            round(t * 1e3, 1) for t in warmup_cycles
        ]
    if composed:
        # BENCH_COMPOSED tail (ISSUE 12): the authoritative north-star
        # composition — which lanes engaged and what each mode counted.
        payload["composed"] = dict(composed)
    if rebalance:
        # BENCH_REBALANCE tail: frag-score before/after + plan stats
        # (docs/rebalance.md).
        payload["rebalance"] = dict(rebalance)
    if preempt:
        # BENCH_PREEMPT tail: what-if plan outcomes, evictions,
        # convergence + zero-lost-pods proof (docs/preempt_reclaim.md).
        payload["preempt"] = dict(preempt)
    if topology:
        # BENCH_TOPOLOGY tail (ISSUE 20): best-block fit before the
        # defrag wave, gang contiguity after it, placement-outcome
        # counts + zero-lost-pods proof (docs/topology.md).
        payload["topology"] = dict(topology)
    if fallbacks:
        # Two-phase shortlist-fallback rescores over the measured
        # cycles, by reason (docs/metrics.md).
        payload["shortlist_fallbacks"] = dict(fallbacks)
    if devincr:
        # Device-incremental decisions over the measured cycles
        # (warm/full/skip counts + static-plane hits, ISSUE 9).
        payload["devincr"] = dict(devincr)
    if wire:
        # Remote-solver transport telemetry over the steady-state
        # cycles (ISSUE 10): per-kind frame counts/bytes, bytes/cycle,
        # and fallback reasons.
        payload["wire"] = dict(wire)
    if endurance:
        # BENCH_ENDURANCE tail (ISSUE 13): cycles survived, anomaly
        # verdict, fault-wave counts, p99s vs budgets, audit overhead
        # (docs/observability.md).
        payload["endurance"] = dict(endurance)
    if pool:
        # BENCH_POOL tail (ISSUE 15): hedge dispatches/wins, failovers,
        # per-replica frame counts, device-lane percentiles, lost-pod
        # and anomaly verdicts per pool size (docs/tuning.md).
        payload["pool"] = dict(pool)
    if shards:
        # BENCH_SHARDS tail (ISSUE 16): binds/sec + conflict rate +
        # per-shard lane splits per shard count, plus the contention
        # phase's zero-lost-pods verdict (docs/sharding.md).
        payload["shards"] = dict(shards)
    if _AUDIT_TAIL is not None:
        # Runtime-auditor block (ISSUE 13): sampled cycles + measured
        # overhead ride every tail, so any bench row doubles as an
        # audit-overhead datapoint.
        payload["audit"] = _AUDIT_TAIL
        _AUDIT_TAIL = None
    if _JOURNEY_TAIL is not None:
        # Pod-journey block (ISSUE 18): time-to-bind percentiles + gang
        # time-to-full-bind over the benched store's journey log.
        payload["journey"] = _JOURNEY_TAIL
        _JOURNEY_TAIL = None
    if lanes:
        # Lane split rides in the JSON tail so the driver's record
        # carries the per-mode breakdown, not just the total.
        payload["lanes"] = {
            k: round(v * 1e3, 1)
            for k, v in sorted(lanes.items(), key=lambda kv: -kv[1])
            if v >= 5e-4
        }
        # Host-lane serial sum (incl. the pipelined feed lane, ISSUE 8
        # satellite — the accounting must sum to the cycle time):
        # the number the BENCH_HOST incremental A/B compares.
        payload["host_lanes_ms"] = round(_host_lane_sum_ms(lanes), 2)
    if records:
        # Flight-recorder tail (ISSUE 3): staleness-drop totals by
        # reason and per-lane p50/p95 over the steady-state cycles, so
        # the record captures the distribution, not just the best.
        drops = {}
        for rec in records:
            for reason, n in rec.drop_reasons.items():
                drops[reason] = drops.get(reason, 0) + n
        payload["drops"] = drops
        payload["lane_p50"], payload["lane_p95"] = _lane_pctl(records)
        _write_trace(metric, records)
    print(json.dumps(payload))
    if extra:
        print(f"# {extra}", file=sys.stderr)


def _lane_pctl(records):
    """Per-lane p50/p95 milliseconds over the given cycle records."""
    by_lane = {}
    for rec in records:
        for lane, sec in rec.lanes.items():
            by_lane.setdefault(lane, []).append(sec * 1e3)

    def pct(vals, q):
        vals = sorted(vals)
        i = min(int(q * (len(vals) - 1) + 0.5), len(vals) - 1)
        return round(vals[i], 2)

    p50 = {k: pct(v, 0.50) for k, v in by_lane.items()}
    p95 = {k: pct(v, 0.95) for k, v in by_lane.items()}
    return p50, p95


def _write_trace(metric, records):
    """One Perfetto trace file per emitted config/mode (chrome://tracing
    or ui.perfetto.dev; see docs/tracing.md)."""
    from volcano_tpu.obs import export

    out_dir = os.environ.get("BENCH_TRACE_DIR",
                             "/tmp/vtpu_bench_traces")
    try:
        os.makedirs(out_dir, exist_ok=True)
        slug = re.sub(r"[^a-z0-9]+", "-",
                      metric.lower()).strip("-")[:80]
        path = export.write_trace(
            os.path.join(out_dir, f"trace_{slug}.json"), records
        )
        print(f"# trace: {path}", file=sys.stderr)
    except OSError as err:  # trace files are best-effort
        print(f"# trace write failed: {err}", file=sys.stderr)


def _cycle_bench(make_store, conf, repeats, warm_store=None):
    """Measure one full scheduling cycle (OpenSession -> Bind) steady-state:
    warm-up compiles, then fresh stores of the same shape hit the jit cache."""
    from volcano_tpu.scheduler import Scheduler

    # Bind dispatch is async in production (the reference's goroutine
    # binds are not part of its e2e cycle latency either); binds are
    # flushed after timing before counting.  BENCH_SYNC_BIND=1 keeps the
    # binder calls inside the timed cycle — the control run quantifying
    # the measurement-boundary change.
    async_bind = os.environ.get("BENCH_SYNC_BIND") != "1"
    store = warm_store if warm_store is not None else make_store(0)
    store.async_bind = async_bind
    if _MESH is not None:
        store.solve_mesh = _MESH
    client = _attach_remote(store)
    binder = store.binder
    t0 = time.perf_counter()
    Scheduler(store, conf_str=conf).run_once()
    warm_s = time.perf_counter() - t0
    store.flush_binds()
    bound = len(binder.binds)
    evicted = len(getattr(store.evictor, "evicts", []))
    if client is not None:
        client.close()

    times = []
    lanes_best = None
    records = []
    for r in range(repeats):
        store_r = make_store(r + 1)
        store_r.async_bind = async_bind
        if _MESH is not None:
            store_r.solve_mesh = _MESH
        client_r = _attach_remote(store_r)
        sched_r = Scheduler(store_r, conf_str=conf)
        t0 = time.perf_counter()
        sched_r.run_once()
        times.append(time.perf_counter() - t0)
        if times[-1] == min(times):
            lanes_best = getattr(store_r, "last_cycle_lanes", None)
        # Flight-recorder records survive the store close (plain list
        # of plain records); one timed cycle each -> the repeat set IS
        # the steady-state distribution.
        records.extend(store_r.flight.recent())
        store_r.flush_binds()
        _collect_audit(store_r)
        _collect_journey(store_r)
        # The dispatcher thread's callbacks pin the store; stop it so the
        # repeat's full mirror is actually freed.
        store_r.close()
        if client_r is not None:
            client_r.close()
        del store_r, sched_r
    e2e_ms = min(times) * 1e3 if times else warm_s * 1e3
    return e2e_ms, bound, evicted, warm_s, times, lanes_best, records


def _pipelined_bench(make_store, conf, cycles=None):
    """Steady-state pipelined cycle time (ISSUE 1 double-buffered
    sessions), amortized over >= 5 consecutive cycles on ONE store.

    Every cycle commits the previous cycle's dispatched solve at its top
    and dispatches a fresh one from allocate; the workload feed
    (store.cycle_feed) re-pends the rows the commit just bound, so the
    backlog is constant and each cycle does commit(N-1) + dispatch(N) —
    the device round trip of session N overlapping cycle N's close and
    cycle N+1's derive/order/encode.  The first two cycles (compile +
    pipeline fill) are warm-up; the amortized mean over the rest is the
    steady-state number the north-star target reads."""
    import numpy as np

    from volcano_tpu.api import TaskStatus
    from volcano_tpu.scheduler import Scheduler

    st_bound = int(TaskStatus.Bound)
    if cycles is None:
        cycles = max(int(os.environ.get("BENCH_PIPE_CYCLES", 5)), 5)
    store = make_store(0)
    store.async_bind = os.environ.get("BENCH_SYNC_BIND") != "1"
    store.pipeline = True
    if _MESH is not None:
        # Pipelined dispatch works under a mesh (ISSUE 7): the parked
        # InflightSolve's arrays live sharded across the chips.
        store.solve_mesh = _MESH
    client = _attach_remote(store)
    fed = {"total": 0}

    def feed(fc):
        m = fc.m
        rows = np.flatnonzero(
            (m.p_status[:fc.Pn] == st_bound) & m.p_alive[:fc.Pn]
        )
        if _FEED_FRACTION < 1.0 and len(rows):
            # Sparse steady-state churn (BENCH_DEVINCR): re-pend only a
            # fraction of the bound rows, so the per-cycle dirty set
            # looks like production (a few hundred rows), not a full
            # backlog re-pend.
            rows = rows[:max(1, int(len(rows) * _FEED_FRACTION))]
        if len(rows):
            fed["total"] += len(rows)
            fc._unbind_rows(rows)

    store.cycle_feed = feed
    sched = Scheduler(store, conf_str=conf)
    # Warm-up cycles are timed INDIVIDUALLY so compile/jit spikes are
    # reported per cycle in the warmup_cycles_ms tail, never inside
    # the steady-state cycles_ms (ISSUE 12 satellite).
    warm_cycles = []

    def _warm_once():
        t0 = time.perf_counter()
        sched.run_once()
        warm_cycles.append(time.perf_counter() - t0)

    _warm_once()  # warm-up: compile + first dispatch (no commit yet)
    _warm_once()  # pipeline fill: first commit lands
    if _DEVINCR_PROBE or client is not None:
        # Device-incremental / wire A/B: the warm-shortlist kernel
        # compiles on its FIRST warm-eligible cycle (the pending set
        # stabilizes a couple of cycles after the backlog first
        # commits); keep that compile out of the measured steady
        # state, in every mode (the extra cycles are mode-symmetric —
        # without this the A/B's first mode eats the compile alone).
        for _ in range(3):
            _warm_once()
    warm_s = sum(warm_cycles)
    # Steady-state seam reset: the re-pend feed keeps the backlog
    # constant, but the two warm-up cycles already accumulated
    # two-phase shortlist-fallback counts (cold jit, first fill) —
    # reset the per-store accumulator here so the emitted fallback tail
    # covers exactly the steady-state cycles and the shortlist-on/off
    # pipelined rows stay comparable.  (The epoch-keyed class planes
    # deliberately survive: the feed mutates pods, not nodes.)
    store._shortlist_fb = {}
    # Wire-telemetry seam (BENCH_WIRE): counters to this point cover
    # warm-up (incl. the connection's first, necessarily-full frame);
    # the steady-state delta is what the A/B compares.
    wire0 = None
    if client is not None:
        wire0 = (dict(client.frame_counts), dict(client.frame_bytes),
                 dict(client.wire_fallbacks))
    times = []
    lane_acc = {}
    for _ in range(cycles):
        t0 = time.perf_counter()
        sched.run_once()
        times.append(time.perf_counter() - t0)
        for k, v in (store.last_cycle_lanes or {}).items():
            lane_acc[k] = lane_acc.get(k, 0.0) + v
    amortized_ms = sum(times) / len(times) * 1e3
    lanes = {k: v / len(times) for k, v in lane_acc.items()}
    wire = None
    if client is not None:
        counts0, bytes0, fb0 = wire0
        frames = {k: client.frame_counts[k] - counts0.get(k, 0)
                  for k in client.frame_counts}
        wbytes = {k: client.frame_bytes[k] - bytes0.get(k, 0)
                  for k in client.frame_bytes}
        wire = {
            "frames": frames,
            "bytes": wbytes,
            "bytes_per_cycle": round(sum(wbytes.values()) / cycles),
            "fallbacks": {
                k: v - fb0.get(k, 0)
                for k, v in client.wire_fallbacks.items()
                if v - fb0.get(k, 0)
            },
        }
    store.flush_binds()
    bound_per_cycle = fed["total"] // max(cycles + 1, 1)
    # Steady-state flight records only (the two warm-up cycles carry
    # compile + pipeline-fill time and would skew the percentiles).
    records = store.flight.recent()[-len(times):]
    fallbacks = dict(getattr(store, "_shortlist_fb", {}) or {})
    devincr = None
    dv = getattr(store, "_devincr_cache", None)
    if dv is not None:
        devincr = dict(dv.counts)
        devincr["static_hits"] = dv.static_hits
        devincr["static_builds"] = dv.static_builds
    if _DEVINCR_PROBE:
        # Null-delta probe (ISSUE 9): feed off, backlog committed, ONE
        # pending-but-unschedulable gang keeping the pending set
        # non-empty (an empty set early-outs before any solve and would
        # prove nothing).  With the lane on, idle cycles must complete
        # WITHOUT a solve dispatch (the skip proof); with it off, every
        # cycle re-dispatches the futile solve — measured, not assumed.
        from volcano_tpu.api import (
            GROUP_NAME_ANNOTATION as _GNA,
            Pod as _Pod,
            PodGroup as _PodGroup,
        )

        store.cycle_feed = None
        sched.run_once()  # commits the last dispatched solve
        store.add_pod_group(_PodGroup(name="bench-nullprobe",
                                      min_member=1))
        store.add_pod(_Pod(
            name="bench-nullprobe-0",
            annotations={_GNA: "bench-nullprobe"},
            containers=[{"cpu": "900000", "memory": "900000Gi"}],
        ))
        sched.run_once()  # dispatches the (failing) probe solve
        sched.run_once()  # commits its empty result
        seq0 = store._solve_seq
        skip0 = dv.counts["skip"] if dv is not None else 0
        t0 = time.perf_counter()
        probe_n = 2
        for _ in range(probe_n):
            sched.run_once()
        probe_ms = (time.perf_counter() - t0) / probe_n * 1e3
        if devincr is None:
            devincr = {}
        devincr["null_delta_cycle_ms"] = round(probe_ms, 3)
        devincr["null_delta_dispatches"] = store._solve_seq - seq0
        if dv is not None:
            devincr["null_delta_skips"] = dv.counts["skip"] - skip0
    _collect_audit(store)
    _collect_journey(store)
    store.close()
    if client is not None:
        client.close()
    return (amortized_ms, bound_per_cycle, warm_s, times, lanes, records,
            fallbacks, devincr, wire, warm_cycles)


def _emit_pipelined(label, mk, conf, n_pods):
    if os.environ.get("BENCH_PIPELINE", "1") == "0":
        return
    (amortized_ms, bound, warm_s, times, lanes, records,
     fallbacks, devincr, wire, warm_cycles) = _pipelined_bench(mk, conf)
    _emit(
        f"{label} (pipelined steady-state, amortized {len(times)} cycles)",
        amortized_ms, n_pods,
        f"warmup={warm_s:.2f}s bound_per_cycle={bound} "
        f"pods/s={bound / (amortized_ms / 1e3):.0f} "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes,
        records=records,
        fallbacks=fallbacks,
        devincr=devincr,
        wire=wire,
        compile_ms=warm_s * 1e3,
        warmup_cycles=warm_cycles,
    )


def _lane_note(lanes) -> str:
    if not lanes:
        return ""
    parts = [f"{k}={v * 1e3:.0f}ms" for k, v in
             sorted(lanes.items(), key=lambda kv: -kv[1]) if v >= 5e-4]
    return " lanes[" + " ".join(parts) + "]"


CONF_BASE = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""

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


def config_1():
    """End-to-end 3-replica gang job through the full control plane."""
    from volcano_tpu.controllers.apis import Job, TaskSpec
    from volcano_tpu.service import Service

    # Prewarm the solver jit on the same padded shape bucket so the
    # measured latency is steady-state control-plane time, not XLA compile.
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    warm = synthetic_cluster(n_nodes=2, n_pods=3, gang_size=3)
    Scheduler(warm).run_once()

    svc = Service(simulate=True, schedule_period=0.01,
                  controller_period=0.005)
    for i in range(2):
        from volcano_tpu.api import Node

        svc.store.add_node(
            Node(name=f"node-{i}",
                 allocatable={"cpu": "8", "memory": "16Gi", "pods": 64})
        )
    job = Job(
        name="test-job",
        min_available=3,
        tasks=[TaskSpec(
            name="worker", replicas=3,
            containers=[{"cpu": "1", "memory": "1Gi"}],
        )],
    )
    svc.start(http_port=0)
    try:
        t0 = time.perf_counter()
        svc.admitted.add_batch_job(job)
        deadline = t0 + 60.0
        while time.perf_counter() < deadline:
            pods = [
                p for p in svc.store.pods.values()
                if p.owner_job == job.key and p.phase == "Running"
            ]
            if len(pods) >= 3:
                break
            time.sleep(0.002)
        else:
            raise RuntimeError("job did not reach Running in 60s")
        e2e_ms = (time.perf_counter() - t0) * 1e3
    finally:
        svc.stop()
    # Budget: the reference on kind needs >= one 1 s schedule period plus
    # controller reconcile latency before pods run; call it 2 s.
    _emit("gang job submit->3 pods Running (full control plane)", e2e_ms, 3,
          "pods_running=3", budget_ms=2000.0)


def config_2(n_nodes, n_pods, gang, repeats):
    from volcano_tpu.synth import synthetic_cluster

    build_t0 = time.perf_counter()
    store = synthetic_cluster(n_nodes=n_nodes, n_pods=n_pods, gang_size=gang)
    build_s = time.perf_counter() - build_t0
    e2e_ms, bound, _, warm_s, times, lanes, recs = _cycle_bench(
        lambda r: synthetic_cluster(n_nodes=n_nodes, n_pods=n_pods,
                                    gang_size=gang, seed=r),
        CONF_BASE, repeats, warm_store=store,
    )
    _emit(
        f"OpenSession->Bind e2e @ {n_nodes} nodes x {n_pods} pending pods "
        f"(gang {gang})",
        e2e_ms, n_pods,
        f"warmup={warm_s:.2f}s bound={bound} "
        f"pods/s={bound / (e2e_ms / 1e3):.0f} build={build_s:.2f}s "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes,
        records=recs,
        compile_ms=warm_s * 1e3,
    )
    _emit_pipelined(
        f"OpenSession->Bind e2e @ {n_nodes} nodes x {n_pods} pending pods "
        f"(gang {gang})",
        lambda r: synthetic_cluster(n_nodes=n_nodes, n_pods=n_pods,
                                    gang_size=gang, seed=r),
        CONF_BASE, n_pods,
    )


def config_3(repeats):
    from volcano_tpu.synth import synthetic_cluster

    n_nodes = int(os.environ.get("BENCH_NODES", 5000))
    n_pods = int(os.environ.get("BENCH_PODS", 50000))
    mk = lambda r: synthetic_cluster(
        n_nodes=n_nodes, n_pods=n_pods, n_queues=4,
        queue_weights=(1, 2, 4, 8), gang_sizes=(2, 4, 8, 16), seed=r,
    )
    e2e_ms, bound, _, warm_s, times, lanes, recs = _cycle_bench(
        mk, CONF_BASE, repeats)
    _emit(
        f"DRF multi-queue e2e @ {n_nodes} nodes x {n_pods} pods, 4 queues",
        e2e_ms, n_pods,
        f"warmup={warm_s:.2f}s bound={bound} "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes,
        records=recs,
        compile_ms=warm_s * 1e3,
    )
    _emit_pipelined(
        f"DRF multi-queue e2e @ {n_nodes} nodes x {n_pods} pods, 4 queues",
        mk, CONF_BASE, n_pods,
    )


def config_4(repeats):
    from volcano_tpu.synth import preempt_cluster

    n_nodes = int(os.environ.get("BENCH_NODES", 10000))
    n_pending = int(os.environ.get("BENCH_PODS", 20000))
    mk = lambda r: preempt_cluster(n_nodes=n_nodes, n_pending=n_pending,
                                   seed=r)
    e2e_ms, bound, evicted, warm_s, times, lanes, recs = _cycle_bench(
        mk, CONF_PREEMPT, repeats)
    # No pipelined row: the preempt/reclaim actions mutate node capacity
    # AFTER the allocate dispatch, so every overlapped commit would hit
    # the staleness guard's re-validation — the plain number IS the
    # honest one for this config.
    _emit(
        f"preempt+reclaim e2e @ {n_nodes} nodes oversubscribed, "
        f"{n_pending} pending high-pri pods",
        e2e_ms, n_pending,
        f"warmup={warm_s:.2f}s bound={bound} evicted={evicted} "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes,
        records=recs,
        compile_ms=warm_s * 1e3,
    )


def config_5(repeats):
    from volcano_tpu.synth import synthetic_cluster

    full = os.environ.get("BENCH_FULL") == "1"
    n_nodes = int(os.environ.get("BENCH_NODES", 50000 if full else 10000))
    n_pods = int(os.environ.get("BENCH_PODS", 500000 if full else 100000))
    mk = lambda r: synthetic_cluster(
        n_nodes=n_nodes, n_pods=n_pods, gang_size=8, zones=16,
        affinity_fraction=0.05, anti_affinity_fraction=0.05,
        spread_fraction=0.1, seed=r,
    )
    e2e_ms, bound, _, warm_s, times, lanes, recs = _cycle_bench(
        mk, CONF_BASE, repeats)
    _emit(
        f"hyperscale binpack+affinity e2e @ {n_nodes} nodes x "
        f"{n_pods} pods",
        e2e_ms, n_pods,
        f"warmup={warm_s:.2f}s bound={bound} "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes,
        records=recs,
        compile_ms=warm_s * 1e3,
    )
    _emit_pipelined(
        f"hyperscale binpack+affinity e2e @ {n_nodes} nodes x "
        f"{n_pods} pods",
        mk, CONF_BASE, n_pods,
    )


def config_north(repeats):
    """The north-star shape, plain: 10k nodes x 100k pods, gang 8."""
    from volcano_tpu.synth import synthetic_cluster

    n_nodes = int(os.environ.get("BENCH_NODES", 10000))
    n_pods = int(os.environ.get("BENCH_PODS", 100000))
    mk = lambda r: synthetic_cluster(
        n_nodes=n_nodes, n_pods=n_pods, gang_size=8, zones=16, seed=r,
    )
    e2e_ms, bound, _, warm_s, times, lanes, recs = _cycle_bench(
        mk, CONF_BASE, repeats)
    _emit(
        f"OpenSession->Bind e2e @ {n_nodes} nodes x {n_pods} pending "
        f"pods (north star, plain)",
        e2e_ms, n_pods,
        f"warmup={warm_s:.2f}s bound={bound} "
        f"pods/s={bound / (e2e_ms / 1e3):.0f} "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes,
        records=recs,
        compile_ms=warm_s * 1e3,
    )
    _emit_pipelined(
        f"OpenSession->Bind e2e @ {n_nodes} nodes x {n_pods} pending "
        f"pods (north star)",
        mk, CONF_BASE, n_pods,
    )


def config_rebalance():
    """BENCH_REBALANCE: fragmented-cluster defragmentation (ISSUE 5).

    BENCH_NODES worker nodes (4 cpu) each stranded by a 3-cpu filler,
    an equal count of 3-cpu spill nodes, and a high-priority gang of
    BENCH_NODES/2 whole-node tasks that allocate+backfill alone can
    never place.  Measures the planning+commit cycle and the cycles to
    full convergence (gang bound, every filler re-bound), and emits a
    frag-score-before/after tail (docs/rebalance.md)."""
    import time as _t

    from volcano_tpu.api import (
        GROUP_NAME_ANNOTATION,
        Node,
        Pod,
        PodGroup,
        PriorityClass,
    )
    from volcano_tpu.cache import ClusterStore, FakeBinder
    from volcano_tpu.framework import (
        REBALANCE_SCHEDULER_CONF,
        parse_scheduler_conf,
    )
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.sim import ClusterSimulator

    workers = int(os.environ.get("BENCH_NODES", 64))
    gang = max(workers // 2, 1)
    os.environ["VOLCANO_TPU_REBALANCE_DRAIN_CAP"] = str(workers)

    store = ClusterStore(binder=FakeBinder())
    store.add_priority_class(PriorityClass(name="bench-high", value=100))
    for i in range(workers):
        store.add_node(Node(name=f"w{i}", allocatable={
            "cpu": "4", "memory": "16Gi", "pods": 110}))
        store.add_node(Node(name=f"s{i}", allocatable={
            "cpu": "3", "memory": "16Gi", "pods": 110}))
    for i in range(workers):
        store.add_pod_group(PodGroup(name=f"bf{i}", min_member=1))
        store.add_pod(Pod(
            name=f"bfill{i}",
            annotations={GROUP_NAME_ANNOTATION: f"bf{i}"},
            containers=[{"cpu": "3", "memory": "1Gi"}],
        ))
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=2)
    sched.run_once()
    sim.step()
    store.add_pod_group(PodGroup(
        name="benchgang", min_member=gang, priority_class="bench-high"))
    for i in range(gang):
        store.add_pod(Pod(
            name=f"bg{i}",
            annotations={GROUP_NAME_ANNOTATION: "benchgang"},
            containers=[{"cpu": "4", "memory": "1Gi"}],
        ))

    def frag_now():
        """Mean frag score vs the gang's whole-node profile on live
        planes (one FastCycle derive + the planner kernel)."""
        import jax
        import numpy as np

        from volcano_tpu.fastpath import FastCycle
        from volcano_tpu.ops.rebalance import frag_scores

        cyc = FastCycle(store, parse_scheduler_conf(
            REBALANCE_SCHEDULER_CONF))
        with store._lock:
            cyc.derive()
        prof = np.zeros((1, cyc.R), np.float32)
        prof[0, 0] = 4000.0  # the gang task: 4 cpu (milli)
        prof[0, 1] = float(1 << 30)  # 1Gi
        fs = frag_scores(cyc.n_idle.astype(np.float32),
                         cyc.n_alloc.astype(np.float32), cyc.n_ready,
                         np.zeros_like(cyc.n_idle), prof, cyc.eps)
        (frag,) = jax.device_get((fs.frag,))
        alive = cyc.n_alive
        return float(frag[alive].mean()) if alive.any() else 0.0

    from volcano_tpu.metrics import metrics as _metrics

    def _evictions_total():
        return sum(_metrics.rebalance_evictions.data.values())

    ev_before = _evictions_total()
    frag_before = frag_now()
    t0 = _t.perf_counter()
    sched.run_once()  # plans + commits the migration wave
    plan_cycle_ms = (_t.perf_counter() - t0) * 1e3
    converged_cycles = 0
    for _ in range(24):
        converged_cycles += 1
        sim.step()
        sched.run_once()
        bound = sum(1 for p in store.pods.values()
                    if p.name.startswith("bg") and p.node_name)
        if bound >= gang:
            break
    frag_after = frag_now()
    ledger = store.migrations
    _emit(
        f"Rebalance plan+commit cycle @ {2 * workers} nodes, "
        f"{gang}-task gang",
        plan_cycle_ms, gang,
        f"converged_in={converged_cycles} cycles "
        f"plans={ledger.committed_plans if ledger else 0} "
        f"frag {frag_before:.3f} -> {frag_after:.3f}",
        budget_ms=NORTH_STAR_MS,
        lanes=store.last_cycle_lanes,
        rebalance={
            "frag_before": round(frag_before, 4),
            "frag_after": round(frag_after, 4),
            "gang": gang,
            "evictions": int(_evictions_total() - ev_before),
            "committed_plans": (ledger.committed_plans
                                if ledger else 0),
            "converged_cycles": converged_cycles,
        },
    )
    store.close()


def config_topology():
    """BENCH_TOPOLOGY: fragmented-fabric contiguous gang placement
    (ISSUE 20, docs/topology.md).

    ``synth.fabric_cluster`` at the acceptance shape: 2 racks x 2 ICI
    slices of 16 nodes, every slice stranded by 2 Running fillers, and
    a pending 32-task require-contiguous gang no single block can host
    (each slice fits 28 of 32).  Measures the cycle that pregates the
    gang AND plans+commits the slice-defrag wave, then the cycles to
    full contiguous convergence (gang bound in one block, every filler
    re-bound).  The tail carries the best-block fit before the wave vs
    the gang's contiguity after it, the placement-outcome counters,
    and the zero-lost-pods proof."""
    import time as _t

    import numpy as np

    from volcano_tpu.api.spec import FABRIC_RACK, FABRIC_SLICE
    from volcano_tpu.cache import FakeBinder
    from volcano_tpu.framework import (
        REBALANCE_SCHEDULER_CONF,
        parse_scheduler_conf,
    )
    from volcano_tpu.metrics import metrics as _metrics
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.sim import ClusterSimulator
    from volcano_tpu.synth import fabric_cluster

    racks = int(os.environ.get("BENCH_TOPO_RACKS", 2))
    slices = int(os.environ.get("BENCH_TOPO_SLICES", 2))
    slice_nodes = int(os.environ.get("BENCH_TOPO_SLICE_NODES", 16))
    gang = int(os.environ.get("BENCH_GANG", 32))
    n_nodes = racks * slices * slice_nodes
    n_fillers = racks * slices * 2
    os.environ["VOLCANO_TPU_REBALANCE_DRAIN_CAP"] = str(n_nodes)

    store = fabric_cluster(racks=racks, slices_per_rack=slices,
                           nodes_per_slice=slice_nodes, gang_tasks=gang,
                           binder=FakeBinder())
    sched = Scheduler(store, conf_str=REBALANCE_SCHEDULER_CONF)
    sim = ClusterSimulator(store, grace_steps=2)

    def best_block_fit():
        """Fraction of the gang's pending demand the best single
        fabric block can host right now (the contiguity ceiling,
        kernel-scored on live planes)."""
        import jax

        from volcano_tpu.fastpath import FastCycle
        from volcano_tpu.ops import topology as topo

        pending = sum(1 for p in store.pods.values()
                      if p.name.startswith("fabgang")
                      and not p.node_name)
        if not pending:
            return 1.0
        cyc = FastCycle(store, parse_scheduler_conf(
            REBALANCE_SCHEDULER_CONF))
        with store._lock:
            cyc.derive()
        _, block, n_blocks = topo.fabric_planes(store.mirror)
        if not n_blocks:
            return 0.0
        prof = np.zeros((1, cyc.R), np.float32)
        prof[0, 0] = 2000.0  # the gang task: 2 cpu (milli)
        prof[0, 1] = float(1 << 30)  # 1Gi
        cnt = np.array([pending], np.int32)
        bid = np.full((len(cyc.n_idle),), -1, np.int32)
        bid[:cyc.Nn] = block[:cyc.Nn]
        bf = topo.gang_block_fit(
            cyc.n_idle.astype(np.float32), cyc.n_ready, cyc.n_ntasks,
            cyc.n_maxtasks, bid, prof, cnt, cyc.eps,
            n_blocks=int(n_blocks))
        (score,) = jax.device_get((bf.score,))
        return float(score.max()) / float(pending)

    def gang_contiguity():
        """Largest single-block share of the gang's BOUND members
        (0 while the pregate holds everything back)."""
        per_block = {}
        bound = 0
        for p in store.pods.values():
            if not p.name.startswith("fabgang") or not p.node_name:
                continue
            bound += 1
            n = store.nodes.get(p.node_name)
            labels = (getattr(n, "labels", None)
                      or getattr(getattr(n, "node", None), "labels", {})
                      or {})
            key = (labels.get(FABRIC_RACK), labels.get(FABRIC_SLICE))
            per_block[key] = per_block.get(key, 0) + 1
        return (max(per_block.values()) / bound) if bound else 0.0

    def _placements(outcome):
        return _metrics.topology_placements.data.get(
            (("outcome", outcome),), 0.0)

    def _fillers_bound():
        return sum(1 for p in store.pods.values()
                   if p.name.startswith("filler-") and p.node_name)

    ev0 = sum(_metrics.rebalance_evictions.data.values())
    inf0 = _placements("infeasible")
    cont0 = _placements("contiguous")
    fit_before = best_block_fit()
    t0 = _t.perf_counter()
    sched.run_once()  # pregates the gang + plans/commits the wave
    plan_cycle_ms = (_t.perf_counter() - t0) * 1e3
    converged_cycles = 0
    for _ in range(24):
        converged_cycles += 1
        sim.step()
        sched.run_once()
        bound = sum(1 for p in store.pods.values()
                    if p.name.startswith("fabgang") and p.node_name)
        if bound >= gang and _fillers_bound() >= n_fillers:
            break
    ledger = store.migrations
    contig_after = gang_contiguity()
    _emit(
        f"Topology defrag plan+commit cycle @ {n_nodes} nodes, "
        f"{gang}-task require-contiguous gang",
        plan_cycle_ms, gang,
        f"converged_in={converged_cycles} cycles "
        f"fit_before={fit_before:.3f} contiguity_after={contig_after:.3f}",
        budget_ms=NORTH_STAR_MS,
        lanes=store.last_cycle_lanes,
        topology={
            "fit_before": round(fit_before, 4),
            "contiguity_after": round(contig_after, 4),
            "gang": gang,
            "infeasible_transitions": int(_placements("infeasible")
                                          - inf0),
            "contiguous_placements": int(_placements("contiguous")
                                         - cont0),
            "committed_plans": (ledger.committed_plans
                                if ledger else 0),
            "evictions": int(sum(
                _metrics.rebalance_evictions.data.values()) - ev0),
            "converged_cycles": converged_cycles,
            "lost_pods": n_fillers - _fillers_bound(),
        },
    )
    store.close()


def config_preempt():
    """BENCH_PREEMPT: device-native priority-tier preemption (ISSUE 11,
    docs/preempt_reclaim.md).

    BENCH_NODES worker nodes each fully occupied by a Running
    low-priority batch pod (one single-member PodGroup per node — the
    disruption budgets bite per group), plus a Pending high-priority
    serving gang of BENCH_NODES/2 whole-node tasks.  Allocate alone can
    never place the gang; the preempt lane plans victims via the
    jitted kernel, proves the wave with a what-if solve, and commits.
    Measures the plan+commit cycle and cycles to convergence through
    the eviction grace window, and emits a "preempt" JSON tail (plans,
    evictions, restores, zero-lost-pods) the run-e2e smoke asserts
    device-lane engagement from."""
    import time as _t

    from volcano_tpu.cache import ClusterStore, FakeBinder, FakeEvictor
    from volcano_tpu.metrics import metrics as _metrics
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.sim import ClusterSimulator

    conf = """
actions: "enqueue, allocate, preempt"
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
"""
    workers = int(os.environ.get("BENCH_NODES", 64))
    gang = max(workers // 2, 1)
    os.environ.setdefault("VOLCANO_TPU_EVICT_DEVICE", "1")
    os.environ["VOLCANO_TPU_EVICT_CAP"] = str(workers)

    store = ClusterStore(binder=FakeBinder(), evictor=FakeEvictor())
    ClusterSimulator.priority_tier_workload(
        store, workers=workers, serving_tasks=gang)
    sched = Scheduler(store, conf_str=conf)
    sim = ClusterSimulator(store, grace_steps=2)

    def _plans():
        return {
            k[0][1] + "/" + k[1][1]: int(v)
            for k, v in _metrics.whatif_plans.data.items()
        }

    def _evictions():
        return int(sum(_metrics.preempt_evictions.data.values()))

    ev_before = _evictions()
    n_logical = len(store.pods)
    t0 = _t.perf_counter()
    sched.run_once()  # plans + proves + commits the preempt wave
    plan_cycle_ms = (_t.perf_counter() - t0) * 1e3
    converged_cycles = 0
    bound = 0
    for _ in range(24):
        converged_cycles += 1
        sim.step()
        sched.run_once()
        bound = sum(1 for p in store.pods.values()
                    if p.name.startswith("serving-") and p.node_name)
        if bound >= gang:
            break
    restored = sum(1 for p in store.pods.values() if "-mig" in p.uid)
    ledger = store.migrations
    _emit(
        f"Preempt plan+prove+commit cycle @ {workers} nodes, "
        f"{gang}-task serving gang over batch",
        plan_cycle_ms, gang,
        f"converged_in={converged_cycles} cycles bound={bound} "
        f"evictions={_evictions() - ev_before} restored={restored}",
        budget_ms=NORTH_STAR_MS,
        lanes=store.last_cycle_lanes,
        preempt={
            "gang": gang,
            "gang_bound": bound,
            "plans": _plans(),
            "evictions": int(_evictions() - ev_before),
            "restored": restored,
            "committed_plans": (ledger.committed_plans
                                if ledger else 0),
            "converged_cycles": converged_cycles,
            "pods_before": n_logical,
            "pods_after": len(store.pods),
            "lost_pods": n_logical - len(store.pods),
        },
    )
    store.close()


def config_composed():
    """BENCH_COMPOSED=1 (ISSUE 12): the authoritative north-star run.

    Every fast lane built since PR 6 — mesh-sharded solve, persistent
    device incrementality (``VOLCANO_TPU_DEVINCR``), incremental host
    lanes (``VOLCANO_TPU_INCREMENTAL``), pipelined double-buffered
    sessions, and a steady sparse churn feed — engaged TOGETHER in one
    configuration at the north-star shape, instead of each A/B'd in
    isolation.  Two passes:

    - "(plain)": the synchronous single-device cycle;
    - "(composed)": pipelined steady state with the mesh, both
      incrementality lanes, and a ``BENCH_COMPOSED_FRAC`` (default 5%)
      churn feed, ending with the null-delta probe.

    The composed JSON tail carries the engagement proof the e2e smoke
    asserts: mesh shard count, devincr warm/full/skip counts,
    host-incremental derive modes (delta counted from the metrics
    registry), the plain-vs-composed ratio, and the knob matrix.

    ``BENCH_COMPOSED_MESH`` (default 4) sizes the mesh, built on the
    default backend (``_bench_mesh``: four chips of a TPU host, or
    virtual host devices under JAX_PLATFORMS=cpu); a backend with fewer
    devices fails the run."""
    global _MODE_SUFFIX, _MESH, _FEED_FRACTION, _DEVINCR_PROBE

    try:
        n_dev = max(0, int(os.environ.get("BENCH_COMPOSED_MESH", "4")))
    except ValueError:
        n_dev = 4
    mesh = _bench_mesh(n_dev) if n_dev >= 2 else None
    # Pin the composed knob matrix explicitly (docs/tuning.md "Composed
    # profile"): every lane ON — the point is the interaction, not the
    # A/B.
    os.environ["VOLCANO_TPU_TWOPHASE"] = "1"
    os.environ["VOLCANO_TPU_INCREMENTAL"] = "1"
    os.environ["VOLCANO_TPU_DEVINCR"] = "1"
    try:
        frac = float(os.environ.get("BENCH_COMPOSED_FRAC", "0.05"))
    except ValueError:
        frac = 0.05
    n_nodes = int(os.environ.get("BENCH_NODES", 10000))
    n_pods = int(os.environ.get("BENCH_PODS", 100000))
    repeats = int(os.environ.get("BENCH_REPEATS", 3))
    from volcano_tpu.synth import synthetic_cluster

    mk = lambda r: synthetic_cluster(
        n_nodes=n_nodes, n_pods=n_pods, gang_size=8, zones=16, seed=r,
    )
    label = (f"OpenSession->Bind e2e @ {n_nodes} nodes x {n_pods} "
             f"pending pods (north star")

    # ---- pass 1: plain — the synchronous cycle.
    _MESH = None
    _MODE_SUFFIX = ""
    plain_ms, bound, _, warm_s, times, lanes, recs = _cycle_bench(
        mk, CONF_BASE, repeats)
    _emit(
        label + ", plain)", plain_ms, n_pods,
        f"warmup={warm_s:.2f}s bound={bound} "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes, records=recs, compile_ms=warm_s * 1e3,
    )

    # ---- pass 2: composed — everything on, one pipelined steady state.
    from volcano_tpu.metrics import metrics as _metrics

    def _derive_modes():
        return {
            dict(k).get("mode", "?"): int(v)
            for k, v in _metrics.host_incremental_derives.data.items()
        }

    derives0 = _derive_modes()
    _MESH = mesh
    _FEED_FRACTION = min(max(frac, 0.0), 1.0)
    _DEVINCR_PROBE = True
    try:
        (amortized_ms, bound_pc, warm_s, times, lanes, records,
         fallbacks, devincr, wire, warm_cycles) = _pipelined_bench(
            mk, CONF_BASE)
    finally:
        _MESH = None
        _FEED_FRACTION = 1.0
        _DEVINCR_PROBE = False
    derives1 = _derive_modes()
    comp = {
        "mesh_shards": int(mesh.devices.size) if mesh is not None else 1,
        "feed_fraction": _round_frac(frac),
        "plain_ms": round(plain_ms, 2),
        "pipelined_ms": round(amortized_ms, 2),
        "speedup_vs_plain": round(plain_ms / amortized_ms, 2)
        if amortized_ms > 0 else 0.0,
        "incremental_derives": {
            m: derives1.get(m, 0) - derives0.get(m, 0)
            for m in set(derives0) | set(derives1)
        },
        "knobs": {
            "VOLCANO_TPU_MESH": (int(mesh.devices.size)
                                 if mesh is not None else 0),
            "VOLCANO_TPU_TWOPHASE": 1,
            "VOLCANO_TPU_INCREMENTAL": 1,
            "VOLCANO_TPU_DEVINCR": 1,
            "pipeline": 1,
            "wire": "remote" if _REMOTE_PORT is not None else "local",
        },
    }
    _emit(
        label + f", composed, {len(times)} steady cycles)",
        amortized_ms, n_pods,
        f"warmup={warm_s:.2f}s bound_per_cycle={bound_pc} "
        f"plain={plain_ms:.1f}ms composed={amortized_ms:.1f}ms "
        f"cycles_ms={[round(t * 1e3, 1) for t in times]}"
        + _lane_note(lanes),
        lanes=lanes, records=records, fallbacks=fallbacks,
        devincr=devincr, wire=wire, compile_ms=warm_s * 1e3,
        warmup_cycles=warm_cycles, composed=comp,
    )


ENDURANCE_CONF = """
actions: "enqueue, allocate, backfill, preempt, rebalance"
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


def _restart_pool_member(servers, idx, victim, reason):
    """Kill + restart pool member ``idx`` (the ISSUE 15 fault legs):
    sever the replica's live connection FIRST (the server's conn
    thread exits on the dead socket and releases the established
    tuple), drop the listener, rebind the same port with a bounded
    retry, carry the straggler hook over, and respawn the serve
    thread.  When the kernel keeps the old tuple a fresh ephemeral
    port is still a faithful child restart — the replica is RETARGETED
    so its next reconnect dials the new port instead of the dead one
    (the heal assertions depend on the reconnect actually landing)."""
    import threading as _threading

    from volcano_tpu.solver_service import SolverServer

    vport = servers[idx].port
    with victim._lock:
        victim._close_locked(reason)
    servers[idx].shutdown()
    ns = None
    for _attempt in range(50):
        try:
            ns = SolverServer(port=vport)
            break
        except OSError:
            time.sleep(0.1)
    if ns is None:
        ns = SolverServer(port=0)
        victim.port = ns.port
    ns.solve_delay_fn = servers[idx].solve_delay_fn
    servers[idx] = ns
    _threading.Thread(target=ns.serve_forever, daemon=True).start()
    return ns


def config_endurance():
    """BENCH_ENDURANCE=1 (ISSUE 13): the compressed-hours survival gate.

    A pipelined steady state at 2k nodes x 20k pods (10k x 100k with
    ``BENCH_FULL=1``) under sustained churn PLUS scheduled fault waves
    — node flaps, solver-child kills (connection severed + server
    restarted: reconnect -> full frame -> deltas re-engage), periodic
    high-priority preempt gangs, full pod lifecycle churn
    (delete-running + re-add) that drives real pod-table compactions —
    with the runtime auditor ON (``VOLCANO_TPU_AUDIT_SAMPLE``,
    harness default 16) and SLO budgets declared from a calibration
    window.  Phases:

    1. warm-up (compile + pipeline fill, untimed),
    2. calibration (10 cycles: declares cycle/device p99 budgets at
       ``BENCH_ENDURANCE_BUDGET_MULT`` x the observed median, unless
       ``VOLCANO_TPU_SLO_*`` pinned them),
    3. audit-overhead A/B (churn-only: auditor off then on,
       ``audit_overhead_pct`` in the tail — the <2% envelope),
    4. endurance (``BENCH_ENDURANCE_CYCLES``, default 300, faults on).

    The JSON tail carries cycles survived, the anomaly verdict,
    fault-wave counts, steady p50/p99 vs the declared budgets, and the
    audit overhead; the process **exits nonzero on any anomaly** —
    this is the gate hack/run-endurance.sh and the e2e smoke call.
    """
    import threading as _threading

    import numpy as _np

    from volcano_tpu.api import (
        GROUP_NAME_ANNOTATION,
        Pod,
        PodGroup,
        PriorityClass,
        TaskStatus,
    )
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.sim import ClusterSimulator
    from volcano_tpu.synth import synthetic_cluster

    full = os.environ.get("BENCH_FULL") == "1"
    n_nodes = int(os.environ.get("BENCH_NODES",
                                 10000 if full else 2000))
    n_pods = int(os.environ.get("BENCH_PODS",
                                100000 if full else 20000))
    cycles = max(int(os.environ.get("BENCH_ENDURANCE_CYCLES", "300")),
                 40)
    try:
        frac = float(os.environ.get("BENCH_ENDURANCE_FRAC", "0.05"))
    except ValueError:
        frac = 0.05
    try:
        del_frac = float(os.environ.get(
            "BENCH_ENDURANCE_DELETE_FRAC", "0.005"))
    except ValueError:
        del_frac = 0.005
    # Sampled audits every 16th cycle by default (denser than the
    # production 64: the gate's whole point is coverage per wall-hour).
    os.environ.setdefault("VOLCANO_TPU_AUDIT_SAMPLE", "16")
    # The gate exists to EXPOSE fast-path failures: a silent
    # object-session fallback would absorb exactly the breakage the
    # fault waves exist to provoke.
    os.environ["VOLCANO_TPU_FALLBACK"] = "never"

    store = synthetic_cluster(n_nodes=n_nodes, n_pods=n_pods,
                              gang_size=8, zones=16, seed=0)
    store.pipeline = True
    store.async_bind = True
    auditor = store.auditor
    st_bound = int(TaskStatus.Bound)
    st_running = int(TaskStatus.Running)

    # Solver child(ren) over real loopback TCP, so the kill wave severs
    # real connections (BENCH_ENDURANCE_WIRE=0 keeps the in-process
    # solver; the kill wave then no-ops).  BENCH_ENDURANCE_POOL=<n>
    # (>= 2) is the pool leg (ISSUE 15): n servers behind a SolverPool,
    # a mild straggler on replica 0 with tight hedge knobs so hedges
    # fire regularly, and kill waves that hit RANDOM pool members — so
    # some kills land mid-hedge.  Default 1 keeps the historic
    # single-connection harness byte-for-byte.
    server = client = None
    servers = []
    pool_n = 1
    try:
        pool_n = max(1, int(os.environ.get("BENCH_ENDURANCE_POOL",
                                           "1")))
    except ValueError:
        pool_n = 1
    # Sharded-control-plane leg (ISSUE 16): BENCH_ENDURANCE_SHARDS=<n>
    # (>= 2) runs the whole gate — churn + flaps + preempt waves +
    # compactions + solver kills — with n cycle shards over the one
    # store, each with its own solver lane.  The shared node pool plus
    # the churn feed makes same-node races between shards routine; the
    # zero-anomaly verdict is then the optimistic commit protocol's
    # endurance proof.  Mutually exclusive with the pool leg (each
    # shard owns exactly one connection).
    try:
        shards_n = max(1, int(os.environ.get("BENCH_ENDURANCE_SHARDS",
                                             "1")))
    except ValueError:
        shards_n = 1
    if shards_n > 1:
        pool_n = 1
    shard_clients = []
    shard_servers = []
    wire_on = os.environ.get("BENCH_ENDURANCE_WIRE", "1") != "0"
    if wire_on and pool_n > 1:
        import random as _random

        from volcano_tpu.solver_pool import SolverPool
        from volcano_tpu.solver_service import SolverServer

        os.environ.setdefault("VOLCANO_TPU_POOL_HEDGE_P99_MULT", "2.0")
        os.environ.setdefault("VOLCANO_TPU_POOL_HEDGE_MIN_MS", "20")
        for k in range(pool_n):
            srv = SolverServer(port=0)
            if k == 0:
                # Mild periodic straggle: enough to trigger hedges,
                # small enough to keep the calibrated budgets honest.
                srv.solve_delay_fn = (
                    lambda i: 0.06 if i % 7 == 0 else 0.0)
            _threading.Thread(target=srv.serve_forever,
                              daemon=True).start()
            servers.append(srv)
        client = SolverPool([f"127.0.0.1:{s.port}" for s in servers])
        store.remote_solver = client
        _kill_rng = _random.Random(5)
    elif wire_on:
        from volcano_tpu.solver_service import RemoteSolver, SolverServer

        server = SolverServer(port=0)
        _threading.Thread(target=server.serve_forever,
                          daemon=True).start()
        client = RemoteSolver(f"127.0.0.1:{server.port}")
        store.remote_solver = client
        # Extra solver lanes for shards 1..n-1 (the wire protocol is
        # strict request/reply per connection; shard 0 keeps `client`
        # and stays the kill wave's victim).
        for _ in range(shards_n - 1):
            srv = SolverServer(port=0)
            _threading.Thread(target=srv.serve_forever,
                              daemon=True).start()
            shard_servers.append(srv)
            shard_clients.append(RemoteSolver(f"127.0.0.1:{srv.port}"))

    # Steady churn feed: re-pend a fraction of the freshly-bound rows.
    def feed(fc):
        m = fc.m
        rows = _np.flatnonzero(
            (m.p_status[:fc.Pn] == st_bound) & m.p_alive[:fc.Pn]
        )
        if len(rows):
            fc._unbind_rows(rows[:max(1, int(len(rows) * frac))])

    store.cycle_feed = feed
    wave_queue = "default"
    if shards_n > 1:
        from volcano_tpu.api import Queue
        from volcano_tpu.shard import ShardedScheduler, stable_shard

        sched = ShardedScheduler(store, conf_str=ENDURANCE_CONF,
                                 shards=shards_n)
        if client is not None:
            sched.shards[0].remote_solver = client
            for ctx, cl in zip(sched.shards[1:], shard_clients):
                ctx.remote_solver = cl
        # The preempt waves must land in a queue OWNED BY the evictor
        # shard (shard 0): evict actions run only there under the
        # sharded plane (docs/sharding.md), so a wave gang homed
        # elsewhere would pend forever and the gate would measure a
        # stall, not the protocol.
        qi = 0
        while stable_shard(f"endur-q{qi}", shards_n) != 0:
            qi += 1
        wave_queue = f"endur-q{qi}"
        store.add_queue(Queue(name=wave_queue, weight=4))
    else:
        sched = Scheduler(store, conf_str=ENDURANCE_CONF)
    sim = ClusterSimulator(store, grace_steps=1)

    def one_cycle():
        t0 = time.perf_counter()
        sched.run_once()
        dt = time.perf_counter() - t0
        store.flush_binds()
        sim.step()
        return dt

    # Scenario helpers shared by every phase -------------------------
    from volcano_tpu.api import PodPhase

    clone_seq = 0
    wave_seq = 0
    d_per_cycle = max(1, int(n_pods * del_frac))
    wave_cpu = os.environ.get("BENCH_ENDURANCE_WAVE_CPU", "40")

    def _lifecycle_churn(n):
        """Full pod lifecycle: delete n Running pods (tombstones ->
        real compactions) and re-add fresh clones into their gangs, so
        the backlog holds and the add/delete conservation flows run."""
        nonlocal clone_seq
        # Snapshot under the store lock (the async bind dispatcher
        # mutates `pods` concurrently; the lockdep leg enforces this).
        with store._lock:
            running = [p for p in store.pods.values()
                       if int(p.task_status()) == st_running
                       and not p.deleting][:n]
        for pod in running:
            store.delete_pod(pod)
            clone_seq += 1
            clone = copy.copy(pod)
            clone.uid = f"{pod.uid}-e{clone_seq}"
            clone.name = f"{pod.name}-e{clone_seq}"
            clone.node_name = None
            clone.deleting = False
            clone.exit_code = 0
            clone.phase = PodPhase.Pending
            store.add_pod(clone)

    def _submit_wave():
        """One high-priority 4-task gang of large pods: places only by
        evicting batch residents (victim-selection -> what-if ->
        ledger-restore under load)."""
        nonlocal wave_seq
        wave_seq += 1
        gname = f"endur-hi{wave_seq}"
        store.add_pod_group(PodGroup(
            name=gname, min_member=4, priority_class="endur-hi",
            queue=wave_queue))
        for t in range(4):
            store.add_pod(Pod(
                name=f"{gname}-{t}",
                annotations={GROUP_NAME_ANNOTATION: gname},
                containers=[{"cpu": wave_cpu, "memory": "8Gi"}],
                priority=1000,
            ))
        return gname

    def _teardown_wave(gname):
        with store._lock:  # snapshot: binds land concurrently
            members = [p for p in store.pods.values()
                       if (p.annotations or {}).get(
                           GROUP_NAME_ANNOTATION) == gname]
        for p in members:
            store.delete_pod(p)
        if f"default/{gname}" in store.pod_groups:
            store.delete_pod_group(f"default/{gname}")

    def _flip_node(name, ready):
        ni = store.nodes.get(name)
        if ni is None or ni.node is None:
            return
        spec = ni.node
        spec.ready = ready
        store.update_node(spec)

    # ---- phase 1: warm-up (compile + pipeline fill) -----------------
    # Includes one wave gang shape-identical to the endurance waves:
    # the wave solver compiles per shape bucket, so the preempt /
    # victim-selection / what-if kernels jit HERE, not inside the
    # calibrated SLO window.
    warm_cycles = [one_cycle() for _ in range(3)]
    store.add_priority_class(PriorityClass(name="endur-hi", value=1000))
    warm_gang = _submit_wave()
    warm_cycles.extend(one_cycle() for _ in range(6))

    # ---- phase 2: calibration + budget declaration ------------------
    # Calibrate UNDER the endurance load shape — lifecycle churn
    # running and a wave gang pending — or the declared budget would
    # describe a steady state the endurance phase never runs in.
    calib = []
    for _ in range(12):
        _lifecycle_churn(d_per_cycle)
        calib.append(one_cycle())
    _teardown_wave(warm_gang)
    try:
        mult = float(os.environ.get("BENCH_ENDURANCE_BUDGET_MULT",
                                    "25"))
    except ValueError:
        mult = 25.0
    calib_ms = sorted(t * 1e3 for t in calib)
    # Median of the loaded calibration window — the tail would let one
    # calibration-time jit spike inflate the budget into vacuity.
    cycle_budget = calib_ms[len(calib_ms) // 2] * mult
    if not os.environ.get("VOLCANO_TPU_SLO_CYCLE_P99_MS"):
        # 10% allowed violations: fault-recovery cycles (reconnect +
        # full frame, flap-forced full derives) are EXPECTED to spike;
        # the budget catches sustained regression, not single faults.
        auditor.slo.declare("cycle", cycle_budget, allowed_frac=0.10)
    # The device lane stays tracked-but-unbudgeted unless the operator
    # pins VOLCANO_TPU_SLO_DEVICE_P99_MS: on CPU hosts its tail is
    # dominated by genuine jit recompiles (one-time on real chips with
    # the persistent compile cache), which would flake the gate.

    # ---- phase 3: audit-overhead A/B (churn only, no faults) --------
    # Interleaved off/on pairs with per-pair order swap, scored by the
    # median PAIRWISE delta: consecutive-block drift, 2-cycle
    # periodicity, and single OS/jit hiccups would each swamp a
    # sub-2% effect measured any cruder way.
    ab_n = max(int(os.environ.get("BENCH_ENDURANCE_AB_CYCLES", "15")),
               5)
    t_off, t_on = [], []
    for k in range(ab_n):
        for on_first in ((k % 2 == 0), not (k % 2 == 0)):
            auditor.set_enabled(on_first)
            _lifecycle_churn(d_per_cycle)
            (t_on if on_first else t_off).append(one_cycle())
    auditor.set_enabled(True)
    deltas = sorted(on - off for on, off in zip(t_on, t_off))
    med_off = sorted(t_off)[len(t_off) // 2]
    overhead_pct = (deltas[len(deltas) // 2] / med_off * 100.0
                    if med_off > 0 else 0.0)
    # The in-process truth: the auditor times its own passes; the
    # endurance phase below reports that directly too.
    overhead_ms0 = auditor.audit_stats()["overhead_ms"]

    # ---- phase 3b: journey-overhead A/B (ISSUE 18) ------------------
    # Same interleaved-pairs design, toggling the pod-journey log
    # instead of the auditor: detaching the store/mirror handles is the
    # journey's kill switch, so the off leg pays exactly one getattr
    # per seam.  Scored identically (median pairwise delta / median
    # off), with one refinement: each leg takes the MIN of two cycles.
    # The journey's steady-state cost is microseconds against cycles
    # whose one-sided spikes (gc, jit warms, tombstone derives) are
    # milliseconds — a single-sample leg couples those spikes straight
    # into the pairwise delta, and min-of-two filters them without
    # biasing a genuine per-cycle cost (which both samples would pay).
    jr = store.journey
    t_joff, t_jon = [], []
    if jr is not None:
        for k in range(ab_n):
            for on_leg in ((k % 2 == 0), not (k % 2 == 0)):
                store.journey = jr if on_leg else None
                store.mirror.journey = jr if on_leg else None
                leg = []
                for _ in range(2):
                    _lifecycle_churn(d_per_cycle)
                    leg.append(one_cycle())
                (t_jon if on_leg else t_joff).append(min(leg))
        store.journey = jr
        store.mirror.journey = jr
        # Close the blind window: pods that moved while the journey was
        # detached re-adopt via a bulk resync (synthetic roots), so the
        # conservation check at the end stays airtight.
        with store._lock:
            m = store.mirror
            resync_pairs = [(m.p_uid[i], int(m.p_status[i]))
                            for i in range(len(m.p_uid))
                            if m.p_alive[i] and m.p_uid[i]]
        jr.pod_resync(resync_pairs)
    jdeltas = sorted(on - off for on, off in zip(t_jon, t_joff))
    med_joff = sorted(t_joff)[len(t_joff) // 2] if t_joff else 0.0
    journey_overhead_pct = (
        jdeltas[len(jdeltas) // 2] / med_joff * 100.0
        if med_joff > 0 else 0.0)

    # ---- phase 4: endurance (faults on) -----------------------------
    from volcano_tpu.metrics import metrics as _metrics

    # The in-process truth (the audit_stats idiom): the journey times
    # its own capture entry points, so the endurance phase also reports
    # capture time as a fraction of total cycle time directly —
    # immune to the A/B's noise floor.
    jcap0 = store.journey.capture_ns if store.journey is not None else 0
    flap_every = max(cycles // 10, 20)
    wave_every = max(cycles // 4, 25)
    kill_at = {cycles // 2, (3 * cycles) // 4}
    with store._lock:  # compact_gen is lock-guarded mirror state
        compact0 = store.mirror.compact_gen
    node_names = [f"node-{i:06d}" for i in range(n_nodes)]
    flaps = kills = 0
    flapped = None  # (name, restore_at_cycle)
    wave_groups = []  # (group_name, teardown_at)
    times = []
    for i in range(cycles):
        if i % flap_every == flap_every - 1 and flapped is None:
            name = node_names[(i // flap_every) % n_nodes]
            _flip_node(name, False)
            flapped = (name, i + 5)
            flaps += 1
        if flapped is not None and i >= flapped[1]:
            _flip_node(flapped[0], True)
            flapped = None
        if i % wave_every == wave_every - 1:
            wave_groups.append((_submit_wave(), i + wave_every // 2))
        for gname, teardown in list(wave_groups):
            if i >= teardown:
                _teardown_wave(gname)
                wave_groups.remove((gname, teardown))
        if i in kill_at and servers:
            # Pool leg (ISSUE 15): kill/restart a RANDOM member — the
            # straggler + tight hedge knobs keep hedges in flight, so
            # some kills land mid-hedge.  The severed replica's reply
            # rides the lost-reply machinery (or the hedge winner
            # commits in its place); its reconnect ships a full frame
            # and deltas re-engage per replica.
            kills += 1
            idx = _kill_rng.randrange(len(servers))
            _restart_pool_member(servers, idx,
                                 client.replicas[idx].client,
                                 "endurance-kill")
        elif i in kill_at and server is not None:
            # Solver-child kill: restart the server AND sever the live
            # connection, so the per-connection wire mirror + devincr
            # caches die with it; the client reconnect must heal to a
            # full frame before deltas re-engage.
            kills += 1
            port = server.port
            # Sever the live connection FIRST (the server's conn
            # thread exits on the dead socket and releases the
            # established tuple), then drop the listener and rebind.
            with client._lock:
                client._close_locked("endurance-kill")
            server.shutdown()
            from volcano_tpu.solver_service import SolverServer

            server = None
            for _attempt in range(20):
                try:
                    server = SolverServer(port=port)
                    break
                except OSError:
                    time.sleep(0.1)
            if server is None:
                # The old tuple is stuck in the kernel: a fresh
                # ephemeral port + fresh client is still a faithful
                # child restart (full reconnect, empty mirror).
                server = SolverServer(port=0)
                client.close()
                from volcano_tpu.solver_service import RemoteSolver

                client = RemoteSolver(f"127.0.0.1:{server.port}")
                store.remote_solver = client
                if shards_n > 1:
                    # Shard 0 resolves its lane from its own context,
                    # not the store slot (docs/sharding.md).
                    sched.shards[0].remote_solver = client
            _threading.Thread(target=server.serve_forever,
                              daemon=True).start()
        _lifecycle_churn(d_per_cycle)
        times.append(one_cycle())

    # ---- verdict + tail ---------------------------------------------
    store.cycle_feed = None
    # Journey conservation (ISSUE 18): every pod the mirror says is
    # bound-ish must have a complete, orphan-free journey.  Violations
    # land as journey-orphan / journey-incomplete anomalies in the
    # auditor ring and fail the gate like any other anomaly.
    jviol = 0
    bound_checked = 0
    if store.journey is not None:
        bound_mask = (int(TaskStatus.Allocated) | int(TaskStatus.Binding)
                      | int(TaskStatus.Bound) | int(TaskStatus.Running)
                      | int(TaskStatus.Succeeded))
        with store._lock:
            m = store.mirror
            bound_uids = [m.p_uid[i] for i in range(len(m.p_uid))
                          if m.p_alive[i] and m.p_uid[i]
                          and int(m.p_status[i]) & bound_mask]
        bound_checked = len(bound_uids)
        for a in store.journey.conservation_check(bound_uids):
            jviol += 1
            auditor.report(a)
    anoms = auditor.total_anomalies()
    with auditor._lock:
        by_reason = dict(auditor.anomaly_counts)
    slo = auditor.slo.snapshot()
    times_ms = sorted(t * 1e3 for t in times)

    def pct(q):
        return round(times_ms[min(int(q * (len(times_ms) - 1) + 0.5),
                                  len(times_ms) - 1)], 2)

    ledger = store.migrations
    with store._lock:  # lock-guarded store/mirror state for the tail
        shard_table = store.shard_table
        compact_gen = store.mirror.compact_gen
    endurance = {
        "cycles": cycles,
        "anomalies": anoms,
        "anomalies_by_reason": by_reason,
        "cycle_p50_ms": pct(0.50),
        "cycle_p99_ms": pct(0.99),
        "cycle_budget_ms": round(cycle_budget, 2),
        "slo": slo,
        "audit_overhead_pct": round(overhead_pct, 2),
        # Direct in-process measure over the endurance phase: the
        # auditor's own timed passes / the phase's wall time — the
        # stable <2%-envelope number (the A/B above corroborates it
        # against anything the timers cannot see).
        "audit_overhead_direct_pct": round(
            (auditor.audit_stats()["overhead_ms"] - overhead_ms0)
            / max(sum(times) * 1e3, 1e-9) * 100.0, 3),
        "node_flaps": flaps,
        "preempt_waves": wave_seq,
        "preempt_evictions": int(sum(
            _metrics.preempt_evictions.data.values())),
        "solver_kills": kills,
        "compactions": compact_gen - compact0,
        "pods_deleted": clone_seq,
        "ledger_restored": (ledger.restored_pods
                            if ledger is not None else 0),
        "wire": ({"frames": dict(client.frame_counts),
                  "fallbacks": dict(client.wire_fallbacks)}
                 if client is not None else None),
        # Pool leg (ISSUE 15): per-replica health + hedge/failover
        # totals, so the gate's tail proves random-member kills healed
        # with the pool still hedging.  (client is None under
        # BENCH_ENDURANCE_WIRE=0 regardless of the pool knob.)
        "pool": (client.health_snapshot()
                 if pool_n > 1 and client is not None else None),
        # Sharded leg (ISSUE 16): conflict/steal totals + per-shard
        # cycle counts, so the gate's tail proves the optimistic
        # protocol actually raced (conflicts > 0 under this schedule)
        # and still conserved every pod.
        "shards": (
            {
                "n": shards_n,
                "conflicts": int(sum(
                    _metrics.shard_conflicts.data.values())),
                "steals": int(sum(
                    _metrics.shard_steals.data.values())),
                "per_shard": [ctx.debug_snapshot()
                              for ctx in sched.shards],
                "table": shard_table.snapshot(),
            } if shards_n > 1 else None),
        # Journey leg (ISSUE 18): capture volume, the conservation
        # verdict over every bound-ish pod, and the measured capture
        # overhead — the interleaved journey-off A/B delta AND the
        # self-timed capture fraction of the endurance phase (the
        # in-process truth; the A/B's resolution floor is the host's
        # cycle jitter).  The <2% gate reads journey_direct_pct.
        "journey": ({
            **store.journey.stats(),
            "bound_pods_checked": bound_checked,
            "conservation_violations": jviol,
            "journey_overhead_pct": round(journey_overhead_pct, 2),
            "journey_direct_pct": (round(
                (store.journey.capture_ns - jcap0) / 1e6
                / sum(times_ms) * 100.0, 3) if times_ms else 0.0),
        } if store.journey is not None else None),
    }
    _collect_audit(store)
    _collect_journey(store)
    _emit(
        f"Endurance @ {n_nodes} nodes x {n_pods} pods "
        f"({cycles} churn cycles, faults on)",
        pct(0.50), n_pods,
        f"anomalies={anoms} flaps={flaps} waves={wave_seq} kills={kills} "
        f"compactions={endurance['compactions']} "
        f"overhead={overhead_pct:.2f}% warmup={sum(warm_cycles):.2f}s",
        lanes=store.last_cycle_lanes,
        records=store.flight.recent(),
        endurance=endurance,
        compile_ms=sum(warm_cycles) * 1e3,
    )
    store.close()
    if client is not None:
        client.close()
    for cl in shard_clients:
        cl.close()
    if server is not None:
        server.shutdown()
        time.sleep(0.2)
    for srv in servers + shard_servers:
        srv.shutdown()
    if servers or shard_servers:
        time.sleep(0.2)
    if anoms:
        print(f"# ENDURANCE FAILED: {anoms} anomalies "
              f"({by_reason})", file=sys.stderr)
        raise SystemExit(1)


def config_pool():
    """BENCH_POOL=1 (ISSUE 15): solver replica pool A/B — pool sizes
    {1,2,3} over in-process ``SolverServer``s with an injected
    straggler + kill fault schedule.

    Every server straggles (``BENCH_POOL_STRAGGLE_MS``, default 250 ms,
    on every ``BENCH_POOL_STRAGGLE_EVERY``-th solve, default 5) so
    health-scored routing alone cannot dodge the tail — the pool=2/3
    rows isolate what HEDGING buys.  Mid-run, a random replica is
    killed and restarted (connection severed + listener rebound), so
    every row also pays one lost-reply re-place; the tail proves the
    kill cost exactly that (zero lost pods, failover counted, the
    killed replica's deltas re-engaged after its full-frame reconnect).

    Per size, one JSON row: steady pipelined cycle p50 plus a "pool"
    tail — hedge dispatches/wins, failovers, per-replica frame counts,
    the killed replica's post-restart frames, device-lane p50/p99 (the
    acceptance number: pool=2 hedging must cut device p99 >= 20% vs
    pool=1 under this schedule), lost pods, and the anomaly verdict.
    """
    import threading as _threading

    import numpy as _np

    from volcano_tpu.api import TaskStatus
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.solver_pool import SolverPool
    from volcano_tpu.solver_service import SolverServer
    from volcano_tpu.synth import synthetic_cluster

    n_nodes = int(os.environ.get("BENCH_NODES", 256))
    n_pods = int(os.environ.get("BENCH_PODS", 2048))
    cycles = max(int(os.environ.get("BENCH_POOL_CYCLES", "40")), 20)
    straggle_s = float(os.environ.get("BENCH_POOL_STRAGGLE_MS",
                                      "250")) / 1e3
    straggle_every = max(
        int(os.environ.get("BENCH_POOL_STRAGGLE_EVERY", "5")), 2)
    sizes = [int(s) for s in os.environ.get(
        "BENCH_POOL_SIZES", "1,2,3").split(",") if s.strip()]
    # The straggler delays are real wall time; hedge past a tight
    # deadline so the A/B exercises the lane (operators tune these in
    # docs/tuning.md "Solver replica pool").
    os.environ.setdefault("VOLCANO_TPU_POOL_HEDGE_P99_MULT", "3.0")
    os.environ.setdefault("VOLCANO_TPU_POOL_HEDGE_MIN_MS", "25")
    st_bound = int(TaskStatus.Bound)

    def _spawn(k):
        servers = []
        for _ in range(k):
            server = SolverServer(port=0)
            server.solve_delay_fn = (
                lambda i: straggle_s if i % straggle_every == 0
                else 0.0)
            _threading.Thread(target=server.serve_forever,
                              daemon=True).start()
            servers.append(server)
        return servers

    for size in sizes:
        servers = _spawn(size)
        pool = SolverPool(
            [f"127.0.0.1:{s.port}" for s in servers], size=size)
        store = synthetic_cluster(n_nodes=n_nodes, n_pods=n_pods,
                                  gang_size=4, seed=3)
        store.pipeline = True
        store.async_bind = os.environ.get("BENCH_SYNC_BIND") != "1"
        store.remote_solver = pool

        def feed(fc):
            m = fc.m
            rows = _np.flatnonzero(
                (m.p_status[:fc.Pn] == st_bound) & m.p_alive[:fc.Pn]
            )
            if len(rows):
                fc._unbind_rows(rows[:max(1, len(rows) // 20)])

        store.cycle_feed = feed
        sched = Scheduler(store, conf_str=CONF_BASE)
        warm = []
        for _ in range(4):
            t0 = time.perf_counter()
            sched.run_once()
            warm.append(time.perf_counter() - t0)
        kill_at = cycles // 2
        killed = 0
        post_kill0 = None
        times = []
        for i in range(cycles):
            if i == kill_at:
                # Kill + restart the CURRENT PRIMARY mid-stream — the
                # member carrying the in-flight allocate stream, the
                # case the acceptance bar pins (the severed reply costs
                # at most one cycle's lost-reply re-place, or a
                # mid-hedge rescue + failover).  A random member can be
                # sitting idle under health-scored routing, making the
                # kill free and the failover assertion vacuous.  The
                # tail snapshots its frame counters so deltas provably
                # re-engage afterwards.
                with pool._lock:
                    killed = pool._primary
                victim = pool.replicas[killed].client
                _restart_pool_member(servers, killed, victim,
                                     "pool-kill")
                post_kill0 = dict(victim.frame_counts)
            t0 = time.perf_counter()
            sched.run_once()
            times.append(time.perf_counter() - t0)
        store.cycle_feed = None
        for _ in range(3):
            sched.run_once()
        store.flush_binds()
        m = store.mirror
        lost = sum(
            1 for r in range(m.n_pods)
            if m.p_uid[r] is not None and m.p_alive[r]
            and int(m.p_status[r]) != st_bound
        )
        recs = store.flight.recent()[-len(times):]
        dev = sorted(
            rec.lanes.get("device", 0.0) * 1e3 for rec in recs)

        def pct(q):
            return round(dev[min(int(q * (len(dev) - 1) + 0.5),
                                 len(dev) - 1)], 2)

        h = pool.health_snapshot()
        kc = pool.replicas[killed].client.frame_counts
        drops = {}
        for rec in recs:
            for reason, n in rec.drop_reasons.items():
                drops[reason] = drops.get(reason, 0) + n
        tail = {
            "size": size,
            "straggle_ms": round(straggle_s * 1e3, 1),
            "straggle_every": straggle_every,
            "hedge_dispatches": h["hedge_dispatches"],
            "hedge_wins": h["hedge_wins"],
            "failovers": h["failovers"],
            "per_replica_frames": pool.per_replica_frames(),
            "killed_replica": killed,
            "post_kill_frames": {
                k: kc[k] - (post_kill0 or {}).get(k, 0)
                for k in kc
            },
            "device_p50_ms": pct(0.50),
            "device_p99_ms": pct(0.99),
            "lost_reply_rows": drops.get("lost-reply", 0),
            "lost_pods": lost,
            "anomalies": store.auditor.total_anomalies(),
        }
        _collect_audit(store)
        _collect_journey(store)
        times_ms = sorted(t * 1e3 for t in times)
        _emit(
            f"Solver pool A/B @ {n_nodes} nodes x {n_pods} pods "
            f"(pool={size}, straggler "
            f"{straggle_s * 1e3:.0f}ms/{straggle_every})",
            times_ms[len(times_ms) // 2], n_pods,
            f"device_p99={tail['device_p99_ms']}ms "
            f"hedges={tail['hedge_dispatches']} "
            f"wins={tail['hedge_wins']} "
            f"failovers={tail['failovers']} lost_pods={lost}",
            lanes=store.last_cycle_lanes,
            records=recs,
            pool=tail,
            compile_ms=sum(warm) * 1e3,
        )
        store.close()
        pool.close()
        for s in servers:
            s.shutdown()
        time.sleep(0.2)


def config_shards():
    """BENCH_SHARDS=1,2,4 (ISSUE 16): sharded control plane A/B — N
    cycle threads over one logical cluster, each shard fronted by its
    own in-process ``SolverServer`` with an injected solve delay
    (``BENCH_SHARDS_SOLVE_MS``, default 30 ms) so the device round trip
    dominates and the pipelined overlap is what the A/B measures: N
    shards keep N solves in flight, so binds/sec scales with N until
    the lock-serialized host cycle saturates.

    Per shard count, three phases over fresh stores:

    - **drain** (conflict-free partition): queues confined to disjoint
      node zones by selector, no churn — every shard count must bind
      the SAME total with zero cross-shard conflicts (hack/run-e2e.sh
      asserts both);
    - **throughput**: steady churn feed over the same partitioned
      store, cycles driven round-robin for ``BENCH_SHARDS_SECS`` —
      binds/sec is the headline (the acceptance bar: >= 1.6x at
      shards=2 vs shards=1).  The overlap being measured is the
      PIPELINED solve (each shard's device round trip cooks while its
      siblings' cycles run), so a single driving thread suffices and
      keeps the number free of lock-barging noise;
    - **contention**: a deliberately tight shared node pool under
      aggressive churn, so same-node races between shards are routine
      — the verdict is conflict-voided rows re-placing at ZERO lost
      pods with the conservation auditor clean.

    One JSON row per shard count: binds/sec, conflict rate, and
    per-shard lane tails (cycles / binds / device p50, split by the
    ``@sN`` session-uid suffix).
    """
    import threading as _threading

    import numpy as _np

    from volcano_tpu.api import TaskStatus
    from volcano_tpu.metrics import metrics as _metrics
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.shard import ShardedScheduler
    from volcano_tpu.solver_service import RemoteSolver, SolverServer
    from volcano_tpu.synth import synthetic_cluster

    sizes = [int(s) for s in os.environ.get(
        "BENCH_SHARDS", "1,2,4").split(",") if s.strip()]
    n_nodes = int(os.environ.get("BENCH_NODES", 64))
    n_pods = int(os.environ.get("BENCH_PODS", 512))
    n_queues = max(int(os.environ.get("BENCH_SHARDS_QUEUES", "8")),
                   max(sizes))
    solve_s = float(os.environ.get("BENCH_SHARDS_SOLVE_MS", "30")) / 1e3
    secs = max(float(os.environ.get("BENCH_SHARDS_SECS", "6")), 1.0)
    # The throughput window produces hundreds of cycles across shards;
    # the ring must retain the whole window for the binds/sec count
    # and the per-shard splits.
    os.environ.setdefault("VOLCANO_TPU_FLIGHT_CYCLES", "8192")
    os.environ.setdefault("VOLCANO_TPU_AUDIT_SAMPLE", "8")
    st_bound = int(TaskStatus.Bound)

    def _conflicts():
        return int(sum(_metrics.shard_conflicts.data.values()))

    def _partitioned_store():
        """Queues confined to disjoint node zones by selector: the
        feasible sets never overlap across queues, so NO shard split
        of this workload can race — the drain/throughput phases
        measure pure scaling, with the commit gate provably quiet."""
        from volcano_tpu.api import (GROUP_NAME_ANNOTATION, Node, Pod,
                                     PodGroup, Queue)
        from volcano_tpu.cache import ClusterStore

        store = ClusterStore()
        for i in range(n_nodes):
            z = i % n_queues
            store.add_node(Node(
                name=f"node-{i:04d}",
                allocatable={"cpu": "64", "memory": "256Gi",
                             "pods": 256},
                labels={"zone": f"z{z}"},
            ))
        for q in range(n_queues):
            store.add_queue(Queue(name=f"shq-{q}", weight=1))
        g = made = 0
        while made < n_pods:
            q = g % n_queues
            size = min(4, n_pods - made) or 1
            pg = PodGroup(name=f"pg-{g:05d}", min_member=size,
                          queue=f"shq-{q}")
            store.add_pod_group(pg)
            for k in range(size):
                store.add_pod(Pod(
                    name=f"pg-{g:05d}-{k}",
                    annotations={GROUP_NAME_ANNOTATION: pg.name},
                    containers=[{"cpu": "2", "memory": "4Gi"}],
                    node_selector={"zone": f"z{q}"},
                ))
                made += 1
            g += 1
        return store

    def _mk(size, store):
        """Scheduler + one solver lane per shard over ``store`` (the
        wire protocol is strict request/reply per connection, so
        concurrent in-flight shards each need their own client)."""
        store.pipeline = True
        store.async_bind = os.environ.get("BENCH_SYNC_BIND") != "1"
        servers, clients = [], []
        for _ in range(max(size, 1)):
            srv = SolverServer(port=0)
            srv.solve_delay_fn = lambda i: solve_s
            _threading.Thread(target=srv.serve_forever,
                              daemon=True).start()
            servers.append(srv)
            clients.append(RemoteSolver(f"127.0.0.1:{srv.port}"))
        if size <= 1:
            store.remote_solver = clients[0]
            sched = Scheduler(store, conf_str=CONF_BASE,
                              schedule_period=0.0)
        else:
            sched = ShardedScheduler(store, conf_str=CONF_BASE,
                                     schedule_period=0.0, shards=size)
            for ctx, cl in zip(sched.shards, clients):
                ctx.remote_solver = cl
        return store, sched, servers, clients

    def _teardown(store, servers, clients):
        store.close()
        for c in clients:
            c.close()
        for s in servers:
            s.shutdown()
        time.sleep(0.2)

    def _bound(store):
        m = store.mirror
        return int(_np.count_nonzero(
            m.p_alive[:m.n_pods]
            & (m.p_status[:m.n_pods] == st_bound)))

    st_pending = int(TaskStatus.Pending)

    def _lost(store):
        m = store.mirror
        return sum(
            1 for r in range(m.n_pods)
            if m.p_uid[r] is not None and m.p_alive[r]
            and int(m.p_status[r]) != st_bound
        )

    def _lost_strict(store):
        """Pods that vanished from BOTH states — the conservation
        failure a voided commit could cause.  On the deliberately
        oversubscribed contention pool, Pending leftovers are the
        expected backlog, not a loss."""
        m = store.mirror
        return sum(
            1 for r in range(m.n_pods)
            if m.p_uid[r] is not None and m.p_alive[r]
            and int(m.p_status[r]) not in (st_bound, st_pending)
        )

    def _pending(store):
        m = store.mirror
        return int(_np.count_nonzero(
            m.p_alive[:m.n_pods]
            & (m.p_status[:m.n_pods] == st_pending)))

    def _last_seq(store):
        recs = store.flight.recent()
        return recs[-1].seq if recs else 0

    baseline_rate = None
    for size in sizes:
        c0 = _conflicts()
        # ---- phase 1: drain (conflict-free partition) ---------------
        store, sched, servers, clients = _mk(size, _partitioned_store())
        rounds = 0
        while rounds < 40 and _bound(store) < n_pods:
            sched.run_once()
            rounds += 1
        store.flush_binds()
        drain = {
            "rounds": rounds,
            "bound": _bound(store),
            "conflicts": _conflicts() - c0,
        }

        # ---- phase 2: throughput (steady churn) ---------------------
        def feed(fc):
            m = fc.m
            rows = _np.flatnonzero(
                (m.p_status[:fc.Pn] == st_bound) & m.p_alive[:fc.Pn]
            )
            if len(rows):
                fc._unbind_rows(rows[:max(1, len(rows) // 8)])

        store.cycle_feed = feed
        for _ in range(6):
            sched.run_once()  # warm the churn shapes before timing
        c1 = _conflicts()
        seq0 = _last_seq(store)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < secs:
            sched.run_once()
        elapsed = time.perf_counter() - t0
        recs = [r for r in store.flight.recent() if r.seq > seq0]
        binds = sum(r.pods_bound for r in recs)
        rate = binds / max(elapsed, 1e-9)
        if baseline_rate is None:
            baseline_rate = rate
        per_shard = {}
        for r in recs:
            k = (r.session.rsplit("@", 1)[1]
                 if "@" in r.session else "s0")
            d = per_shard.setdefault(
                k, {"cycles": 0, "binds": 0, "_dev": []})
            d["cycles"] += 1
            d["binds"] += r.pods_bound
            d["_dev"].append(r.lanes.get("device", 0.0) * 1e3)
        for d in per_shard.values():
            dev = sorted(d.pop("_dev"))
            d["device_p50_ms"] = (
                round(dev[len(dev) // 2], 2) if dev else 0.0)
        thr_conflicts = _conflicts() - c1
        store.cycle_feed = None
        for _ in range(3):
            sched.run_once()
        store.flush_binds()
        lost_ab = _lost(store)
        anoms_ab = store.auditor.total_anomalies()
        cycle_ms = sorted(r.duration_s * 1e3 for r in recs)
        p50 = cycle_ms[len(cycle_ms) // 2] if cycle_ms else 0.0
        _teardown(store, servers, clients)

        # ---- phase 3: contention (tight shared pool, forced races) --
        c2 = _conflicts()
        steals0 = int(sum(_metrics.shard_steals.data.values()))
        store2, sched2, servers2, clients2 = _mk(
            size, synthetic_cluster(
                n_nodes=max(6, n_nodes // 10),
                n_pods=max(96, n_pods // 4), gang_size=4,
                n_queues=n_queues, node_cpu="16", seed=7))

        def feed2(fc):
            m = fc.m
            rows = _np.flatnonzero(
                (m.p_status[:fc.Pn] == st_bound) & m.p_alive[:fc.Pn]
            )
            if len(rows):
                fc._unbind_rows(rows[:max(1, len(rows) // 3)])

        for _ in range(4):
            sched2.run_once()
        store2.cycle_feed = feed2
        t1 = time.perf_counter()
        while time.perf_counter() - t1 < max(secs / 2, 2.0):
            sched2.run_once()
        store2.cycle_feed = None
        for _ in range(4):
            sched2.run_once()
        store2.flush_binds()
        contention = {
            "conflicts": _conflicts() - c2,
            "steals": int(sum(
                _metrics.shard_steals.data.values())) - steals0,
            "pending_backlog": _pending(store2),
            "lost_pods": _lost_strict(store2),
            "anomalies": store2.auditor.total_anomalies(),
        }
        _collect_audit(store2)
        _collect_journey(store2)

        tail = {
            "shards": size,
            "solve_ms": round(solve_s * 1e3, 1),
            "drain": drain,
            "binds_per_sec": round(rate, 1),
            "speedup_vs_shard1": (
                round(rate / baseline_rate, 3) if baseline_rate else None),
            "throughput_conflicts": thr_conflicts,
            "conflict_rate": round(thr_conflicts / max(binds, 1), 5),
            "per_shard": per_shard,
            "lost_pods": lost_ab,
            "anomalies": anoms_ab,
            "contention": contention,
        }
        _emit(
            f"Sharded control plane @ {n_nodes} nodes x {n_pods} pods "
            f"(shards={size}, solve {solve_s * 1e3:.0f}ms)",
            p50, n_pods,
            f"binds/sec={tail['binds_per_sec']} "
            f"speedup={tail['speedup_vs_shard1']} "
            f"conflicts={thr_conflicts} "
            f"contention_lost={contention['lost_pods']} "
            f"contention_anoms={contention['anomalies']}",
            records=recs,
            shards=tail,
        )
        _teardown(store2, servers2, clients2)


def _round_frac(f):
    return round(min(max(f, 0.0), 1.0), 4)


def _emit_mesh_microbench(mesh):
    """One JSON line quantifying the cross-chip reduce of the sharded
    selection: the two-stage shard-local top-k (winner reduction over
    [U, shards*K] (score, node id) pairs) vs the global top-k, both on
    the SAME node-sharded score plane at the config's node count."""
    import numpy as np

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import volcano_tpu.ops.wave as wave

    n_nodes = int(os.environ.get("BENCH_NODES", 10000))
    np_pad = 1 << max(0, (n_nodes - 1).bit_length())
    n_dev = int(mesh.devices.size)
    if np_pad % n_dev:
        return
    u_rows = 256
    k = wave.shortlist_size(np_pad)
    rng = np.random.default_rng(0)
    scores = rng.normal(size=(u_rows, np_pad)).astype(np.float32)
    sharded = jax.device_put(scores, NamedSharding(mesh, P(None, "nodes")))
    two = jax.jit(lambda x: wave._topk_nodes(x, k, n_dev))
    glb = jax.jit(lambda x: wave._topk_nodes(x, k, 1))

    def best_of(fn, arg, n=5):
        fn(arg).block_until_ready()  # compile + warm
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn(arg).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e3

    reduce_ms = best_of(two, sharded)
    global_ms = best_of(glb, sharded)
    print(json.dumps({
        "metric": f"mesh winner-reduce microbench{_MODE_SUFFIX}",
        "value": round(reduce_ms, 3),
        "unit": "ms",
        "device": device_info(),
        "mesh": {
            "devices": n_dev,
            "n_nodes_padded": np_pad,
            "profiles": u_rows,
            "shortlist_k": k,
            "shard_local_topk_ms": round(reduce_ms, 3),
            "global_topk_ms": round(global_ms, 3),
        },
    }))


def _run_selected(raw, repeats):
    if raw == "north":
        config_north(repeats)
        return
    config = int(raw)
    if config == 1:
        config_1()
    elif config == 2:
        config_2(
            int(os.environ.get("BENCH_NODES", 1000)),
            int(os.environ.get("BENCH_PODS", 10000)),
            int(os.environ.get("BENCH_GANG", 4)),
            repeats,
        )
    elif config == 3:
        config_3(repeats)
    elif config == 4:
        config_4(repeats)
    elif config == 5:
        config_5(repeats)
    else:
        raise SystemExit(f"unknown BENCH_CONFIG={config}")


def main():
    global _MODE_SUFFIX, _MESH, _FEED_FRACTION, _DEVINCR_PROBE
    global _REMOTE_PORT
    # No silent CPU run: fail here unless JAX_PLATFORMS asked for it.
    require_accelerator("bench.py")
    raw = os.environ.get("BENCH_CONFIG", "north")
    # min-of-5 by default: host-clock latency on a shared host varies
    # between runs, and the minimum is the stable estimator.
    repeats = int(os.environ.get("BENCH_REPEATS", 5))
    if os.environ.get("BENCH_REBALANCE"):
        # Fragmented-cluster defragmentation lane (ISSUE 5): its own
        # scenario, not a mode of the five configs.
        config_rebalance()
        return
    if os.environ.get("BENCH_TOPOLOGY"):
        # Topology-aware gang placement lane (ISSUE 20): fragmented
        # fabric + slice-defrag convergence, not a mode of the configs.
        config_topology()
        return
    if os.environ.get("BENCH_PREEMPT"):
        # Device-native priority-tier preemption lane (ISSUE 11): its
        # own fragmented-priority scenario, not a mode of the configs.
        config_preempt()
        return
    if os.environ.get("BENCH_COMPOSED"):
        # The authoritative north-star composition (ISSUE 12): mesh +
        # device incrementality + incremental host lanes + pipelining
        # + steady churn, engaged together in one run.
        config_composed()
        return
    if os.environ.get("BENCH_ENDURANCE"):
        # The compressed-hours survival gate (ISSUE 13): churn + fault
        # waves with the runtime auditor on; exits nonzero on any
        # anomaly (hack/run-endurance.sh, docs/observability.md).
        config_endurance()
        return
    if os.environ.get("BENCH_POOL"):
        # Solver replica pool A/B (ISSUE 15): pool sizes {1,2,3} under
        # an injected straggler + kill schedule; the pool tails carry
        # hedge/failover counts and device-lane p50/p99 per size.
        config_pool()
        return
    if os.environ.get("BENCH_SHARDS"):
        # Sharded control plane A/B (ISSUE 16): shard counts {1,2,4}
        # over one logical cluster; the shard tails carry binds/sec,
        # conflict rate, and per-shard lane splits.
        config_shards()
        return
    mesh_raw = os.environ.get("BENCH_MESH")
    if mesh_raw:
        # Mesh A/B (ISSUE 7): build the mesh on the default backend
        # (_bench_mesh), then run the config mesh-on and mesh-off plus
        # the winner-reduce microbench.
        try:
            n_dev = max(2, int(mesh_raw))
        except ValueError:
            n_dev = 4
        mesh = _bench_mesh(n_dev)
        for on in (True, False):
            _MODE_SUFFIX = " (mesh on)" if on else " (mesh off)"
            _MESH = mesh if on else None
            if on:
                _emit_mesh_microbench(_MESH)
            _run_selected(raw, repeats)
        _MODE_SUFFIX = ""
        _MESH = None
        return
    host = os.environ.get("BENCH_HOST")
    if host:
        # Incremental host-lane A/B (ISSUE 8): the selected config runs
        # three times — "(incremental on)", "(incremental off)" (every
        # derive takes the proven full-rebuild path and no host-lane
        # cache is consulted), and "(incremental fallback)" (tracking
        # stays ON but VOLCANO_TPU_DIRTY_CAP=1 overflows every cycle,
        # so the dirty-cap fallback is EXERCISED and measured, not just
        # dodged).  Each pass emits the usual plain + pipelined rows;
        # the pipelined row's host_lanes_ms + lane_p50/p95 tails carry
        # the per-lane p50/p95 across steady-state cycles.
        modes = (
            ("on", {"VOLCANO_TPU_INCREMENTAL": "1"}),
            ("off", {"VOLCANO_TPU_INCREMENTAL": "0"}),
            ("fallback", {"VOLCANO_TPU_INCREMENTAL": "1",
                          "VOLCANO_TPU_DIRTY_CAP": "1"}),
        )
        keys = {k for _, env in modes for k in env}
        old = {k: os.environ.get(k) for k in keys}
        try:
            for mode, env in modes:
                for k in keys:
                    os.environ.pop(k, None)
                os.environ.update(env)
                _MODE_SUFFIX = f" (incremental {mode})"
                _run_selected(raw, repeats)
        finally:
            _MODE_SUFFIX = ""
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return
    wire_ab = os.environ.get("BENCH_WIRE")
    if wire_ab:
        # Remote-wire transport A/B (ISSUE 10): an in-process solver
        # server thread serves every mode over real loopback TCP (the
        # solve shares this process's jit cache — the A/B isolates
        # wire costs), the pipelined feed re-pends BENCH_WIRE_FRAC of
        # the bound rows (default 5%, production-churn shape), and the
        # selected config runs three times — "(wire delta)"
        # (VOLCANO_TPU_WIRE=1), "(wire full)" (=0, classic v1 frames),
        # "(wire fallback)" (=fallback, every frame exercises the
        # forced full-frame path).  Each pipelined row's "wire" tail
        # carries steady-state frame counts/bytes + bytes_per_cycle:
        # the delta-vs-full ratio is the headline.
        import threading

        from volcano_tpu.solver_service import SolverServer

        try:
            frac = float(os.environ.get("BENCH_WIRE_FRAC", "0.05"))
        except ValueError:
            frac = 0.05
        server = SolverServer(port=0)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        _REMOTE_PORT = server.port
        _FEED_FRACTION = min(max(frac, 0.0), 1.0)
        modes = (
            ("delta", {"VOLCANO_TPU_WIRE": "1"}),
            ("full", {"VOLCANO_TPU_WIRE": "0"}),
            ("fallback", {"VOLCANO_TPU_WIRE": "fallback"}),
        )
        old_wire = os.environ.get("VOLCANO_TPU_WIRE")
        try:
            for mode, env in modes:
                os.environ.update(env)
                _MODE_SUFFIX = f" (wire {mode})"
                _run_selected(raw, repeats)
        finally:
            _MODE_SUFFIX = ""
            _REMOTE_PORT = None
            _FEED_FRACTION = 1.0
            if old_wire is None:
                os.environ.pop("VOLCANO_TPU_WIRE", None)
            else:
                os.environ["VOLCANO_TPU_WIRE"] = old_wire
            server.shutdown()
            # Let the per-connection daemon threads observe their
            # closed sockets before interpreter teardown starts
            # unloading XLA under them.
            time.sleep(0.2)
        return
    dev = os.environ.get("BENCH_DEVINCR")
    if dev:
        # Device-lane incremental A/B (ISSUE 9): the selected config
        # runs three times — "(devincr on)" (persistent static planes +
        # warm shortlists + null-delta skips), "(devincr off)"
        # (VOLCANO_TPU_DEVINCR=0: every solve re-evaluates statics and
        # re-ranks all N), and "(devincr fallback)" (the lane is ON but
        # VOLCANO_TPU_DIRTY_CAP=1 overflows tracking every cycle, so
        # the proven full-recompute fallback is EXERCISED and measured,
        # not just dodged).  The pipelined feed re-pends only
        # BENCH_DEVINCR_FRAC of the bound rows (default 5%) so the
        # steady-state dirty set looks like production churn, and each
        # pipelined pass ends with a null-delta probe (two feed-less
        # cycles that must skip the dispatch wholesale).
        try:
            frac = float(os.environ.get("BENCH_DEVINCR_FRAC", "0.05"))
        except ValueError:
            frac = 0.05
        modes = (
            ("on", {"VOLCANO_TPU_DEVINCR": "1"}),
            ("off", {"VOLCANO_TPU_DEVINCR": "0"}),
            ("fallback", {"VOLCANO_TPU_DEVINCR": "1",
                          "VOLCANO_TPU_DIRTY_CAP": "1"}),
        )
        keys = {k for _, env in modes for k in env}
        old = {k: os.environ.get(k) for k in keys}
        _FEED_FRACTION = min(max(frac, 0.0), 1.0)
        _DEVINCR_PROBE = True
        try:
            for mode, env in modes:
                for k in keys:
                    os.environ.pop(k, None)
                os.environ.update(env)
                _MODE_SUFFIX = f" (devincr {mode})"
                _run_selected(raw, repeats)
        finally:
            _MODE_SUFFIX = ""
            _FEED_FRACTION = 1.0
            _DEVINCR_PROBE = False
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return
    ab = os.environ.get("BENCH_TOPK")
    if ab:
        # A/B the two-phase solve in ONE run: the selected config runs
        # twice — shortlist on (BENCH_TOPK > 1 also pins
        # VOLCANO_TPU_TOPK to it; any other value keeps the adaptive
        # default) then shortlist off — emitting both JSON tails with a
        # mode suffix, so one run captures the lane-split delta the
        # two-phase solve buys.
        try:
            topk = int(ab)
        except ValueError:
            topk = 0
        for on in (True, False):
            _MODE_SUFFIX = " (shortlist on)" if on else " (shortlist off)"
            with _twophase_env(on, topk):
                _run_selected(raw, repeats)
        _MODE_SUFFIX = ""
        return
    _run_selected(raw, repeats)


if __name__ == "__main__":
    main()
