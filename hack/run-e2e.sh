#!/usr/bin/env bash
# E2E suite against the simulated cluster (the rebuild's kind analog,
# hack/run-e2e-kind.sh): full control-plane + scheduler + fake kubelet.
set -euo pipefail
cd "$(dirname "$0")/.."
# Green-gate first (ISSUE 2): vclint + csrc ASAN/TSAN smoke + tier-1
# suite — the e2e pass below must never run on a red tree.
hack/run-checks.sh
# The pipelined-mode pass (tests/test_pipeline.py: double-buffered
# sessions over the remote-solver split, overlap-correctness gate) runs
# inside run-checks.sh's tier-1 leg above — not repeated here.
# Composed bind parity (ISSUE 12): the everything-on configuration
# (mesh + devincr + incremental + pipelining) must land bit-for-bit
# the same binds as the everything-off configuration once both reach
# quiescence on the same seeded backlog.
JAX_PLATFORMS=cpu python -c '
from volcano_tpu.virtualcpu import force_virtual_cpu_platform
force_virtual_cpu_platform(4)
import os
from volcano_tpu.parallel import make_mesh
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

def run(on):
    os.environ.update({
        "VOLCANO_TPU_DEVINCR": "1" if on else "0",
        "VOLCANO_TPU_INCREMENTAL": "1" if on else "0",
        "VOLCANO_TPU_TWOPHASE": "1" if on else "0",
    })
    store = synthetic_cluster(n_nodes=256, n_pods=2048, gang_size=4,
                              zones=4, seed=9)
    if on:
        store.pipeline = True
        store.solve_mesh = make_mesh(4, platform="cpu")
    sched = Scheduler(store)
    for _ in range(4 if on else 2):
        sched.run_once()
    store.flush_binds()
    binds = {p.name: p.node_name for p in store.pods.values()}
    assert all(binds.values()), "backlog did not fully bind"
    store.close()
    return binds

on = run(True)
off = run(False)
assert on == off, "composed binds differ from the everything-off run"
print(f"composed bind parity OK ({len(on)} pods bit-for-bit)")
'
# Endurance smoke (ISSUE 13 + the ISSUE 15 pool leg): >= 200 churn
# cycles at a small shape with the full fault schedule — mid-run
# kill/restarts of RANDOM solver-pool members (a straggler + tight
# hedge knobs keep hedges in flight, so kills can land mid-hedge),
# node flaps, preempt waves, and enough lifecycle churn to force at
# least one real pod-table compaction — auditors on every cycle.  The
# gate exits nonzero on any anomaly; the tail assertion additionally
# proves the faults actually fired and the audit verdict is clean
# (0 anomalies = conservation held = zero lost pods).
BENCH_ENDURANCE_POOL=2 BENCH_NODES=64 BENCH_PODS=1024 \
  BENCH_ENDURANCE_CYCLES=200 BENCH_ENDURANCE_DELETE_FRAC=0.03 \
  VOLCANO_TPU_AUDIT_SAMPLE=8 JAX_PLATFORMS=cpu \
  python hack/endurance.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
tails = [r["endurance"] for r in rows if "endurance" in r]
assert tails, "no endurance tail emitted"
e = tails[0]
assert e["anomalies"] == 0, f"endurance anomalies: {e}"
assert e["cycles"] >= 200, e
assert e["solver_kills"] >= 1, f"no solver kill exercised: {e}"
assert e["compactions"] >= 1, f"no compaction exercised: {e}"
assert e["node_flaps"] >= 1 and e["preempt_waves"] >= 1, e
p = e.get("pool")
assert p and p["size"] == 2, f"pool leg did not engage: {e}"
assert p["hedge_dispatches"] >= 1, f"no hedge exercised: {p}"
audits = [r["audit"] for r in rows if "audit" in r]
assert audits and audits[0]["sampled_cycles"] >= 1, audits
c, k, n = e["cycles"], e["solver_kills"], e["compactions"]
h = p["hedge_dispatches"]
print(f"endurance smoke OK ({c} cycles, {k} pool-member kills, "
      f"{h} hedges, {n} compactions, 0 anomalies)")
'
# Journey smoke (ISSUE 18): /debug/pods/<uid> + the /debug/health
# journey rollup on a TWO-SHARD store mid-churn — the stitched
# cross-shard timeline and the why-pending verdict must serve while
# the shards are still re-pending and re-binding the backlog, and the
# conservation check over every bound pod must come back empty.
JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, urllib.request
import numpy as np
from volcano_tpu.api import TaskStatus
from volcano_tpu.service import Service
from volcano_tpu.shard import ShardedScheduler
from volcano_tpu.synth import synthetic_cluster

ST_BOUND = int(TaskStatus.Bound)
store = synthetic_cluster(n_nodes=16, n_pods=96, gang_size=4,
                          n_queues=4, seed=7)
store.pipeline = True

def feed(fc):
    m = fc.m
    rows = np.flatnonzero(
        (m.p_status[:fc.Pn] == ST_BOUND) & m.p_alive[:fc.Pn])
    if len(rows):
        fc._unbind_rows(rows[: max(1, len(rows) // 4)])

store.cycle_feed = feed
sched = ShardedScheduler(store, shards=2)
svc = Service(store=store, schedule_period=30.0, controller_period=5.0)
port = svc.start(http_port=0)

def get(path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read())

def bound_uids():
    with store._lock:
        m = store.mirror
        return [m.p_uid[i] for i in range(len(m.p_uid))
                if m.p_alive[i] and m.p_uid[i]
                and int(m.p_status[i]) == ST_BOUND]

try:
    for i in range(12):
        sched.run_once()
        if i == 6:
            # Mid-churn scrape: half the backlog is in flight right now.
            uid = bound_uids()[0]
            tl = get(f"/debug/pods/{uid}")
            assert tl["uid"] == uid and tl["events"], tl
            assert tl["events"][0]["kind"] == "enqueued", tl["events"][0]
            assert "why_pending" in tl, sorted(tl)
            roll = get("/debug/health")["journey"]
            assert roll["pods_tracked"] > 0, roll
            assert any(q["bound_total"] > 0
                       for q in roll["queues"].values()), roll
    store.flush_binds()
    bound = bound_uids()
    anoms = store.journey.conservation_check(bound)
    assert not anoms, [a.to_dict() for a in anoms]
    print(f"journey smoke OK (2 shards, {len(bound)} bound pods, "
          "mid-churn /debug/pods served, conservation clean)")
finally:
    svc.stop()
    store.close()
PYEOF
exec python -m pytest tests/test_scheduler_e2e.py tests/test_controllers.py \
  tests/test_admission_cli.py tests/test_examples.py \
  tests/test_remote_solver.py tests/test_rendezvous_e2e.py -q "$@"
