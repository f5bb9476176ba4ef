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
# BENCH_MESH smoke (ISSUE 7): the mesh-native sharded solve A/B on 4
# virtual host devices (JAX_PLATFORMS=cpu) at a small shape — asserts the mesh
# pass completes, pipelines, and emits its JSON tail (plain vs mesh,
# lane splits, winner-reduce microbench).
BENCH_MESH=4 BENCH_CONFIG=2 BENCH_NODES=256 BENCH_PODS=2048 \
  BENCH_REPEATS=1 BENCH_PIPE_CYCLES=5 JAX_PLATFORMS=cpu \
  python bench.py
# BENCH_HOST smoke (ISSUE 8): the incremental host-lane A/B at a small
# shape — asserts all three modes (on / off / dirty-cap fallback)
# complete, pipeline, and emit their host_lanes_ms JSON tails.
BENCH_HOST=1 BENCH_CONFIG=2 BENCH_NODES=128 BENCH_PODS=1024 \
  BENCH_REPEATS=1 BENCH_PIPE_CYCLES=5 JAX_PLATFORMS=cpu \
  python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
want = {"(incremental on)", "(incremental off)", "(incremental fallback)"}
modes = {m for m in want for r in rows if m in r["metric"]}
assert modes == want, f"missing BENCH_HOST modes: {want - modes}"
assert any("host_lanes_ms" in r for r in rows), "no host_lanes_ms tail"
print(f"BENCH_HOST smoke OK ({len(rows)} rows)")
'
# BENCH_DEVINCR smoke (ISSUE 9): the device-lane incremental A/B at a
# small shape — asserts all three modes (on / off / dirty-cap
# forced-fallback) complete, pipeline, and emit their devincr JSON
# tails, the on/fallback passes actually take their warm/full paths,
# and the null-delta probe completes WITHOUT a solve dispatch when the
# lane is on.
BENCH_DEVINCR=1 BENCH_CONFIG=2 BENCH_NODES=128 BENCH_PODS=1024 \
  BENCH_REPEATS=1 BENCH_PIPE_CYCLES=5 JAX_PLATFORMS=cpu \
  python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
want = {"(devincr on)", "(devincr off)", "(devincr fallback)"}
modes = {m for m in want for r in rows if m in r["metric"]}
assert modes == want, f"missing BENCH_DEVINCR modes: {want - modes}"
tails = {m: r["devincr"] for m in want for r in rows
         if m in r["metric"] and "devincr" in r}
assert tails["(devincr on)"]["warm"] >= 1, tails
assert tails["(devincr on)"]["null_delta_dispatches"] == 0, tails
assert tails["(devincr on)"]["null_delta_skips"] >= 1, tails
assert tails["(devincr fallback)"]["warm"] == 0, tails
assert tails["(devincr fallback)"]["full"] >= 1, tails
assert tails["(devincr off)"]["null_delta_dispatches"] >= 1, tails
print(f"BENCH_DEVINCR smoke OK ({len(rows)} rows)")
'
# BENCH_WIRE smoke (ISSUE 10): the remote-solver transport A/B at a
# small shape — asserts all three modes (delta / full / forced
# fallback) complete over real loopback TCP with the 5%-churn
# pipelined feed and emit their wire JSON tails, the delta pass
# actually ships delta frames for FEWER bytes/cycle than full frames,
# and the fallback pass counts its forced full-frame fallbacks.
BENCH_WIRE=1 BENCH_CONFIG=2 BENCH_NODES=128 BENCH_PODS=1024 \
  BENCH_REPEATS=1 BENCH_PIPE_CYCLES=5 JAX_PLATFORMS=cpu \
  python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
want = {"(wire delta)", "(wire full)", "(wire fallback)"}
modes = {m for m in want for r in rows if m in r["metric"]}
assert modes == want, f"missing BENCH_WIRE modes: {want - modes}"
tails = {m: r["wire"] for m in want for r in rows
         if m in r["metric"] and "wire" in r}
assert tails["(wire delta)"]["frames"]["delta"] >= 1, tails
assert tails["(wire full)"]["frames"]["delta"] == 0, tails
assert tails["(wire fallback)"]["frames"]["delta"] == 0, tails
assert tails["(wire fallback)"]["fallbacks"].get("forced", 0) >= 1, tails
ratio = tails["(wire full)"]["bytes_per_cycle"] / max(
    tails["(wire delta)"]["bytes_per_cycle"], 1)
assert ratio > 2, f"delta frames did not shrink the wire: {ratio:.1f}x"
print(f"BENCH_WIRE smoke OK ({len(rows)} rows, {ratio:.1f}x fewer "
      "bytes/cycle on deltas)")
'
# BENCH_POOL smoke (ISSUE 15): the solver replica pool A/B at a small
# shape under the injected straggler + kill schedule — asserts pool=2
# hedging cuts the device-lane p99 >= 20% vs pool=1, the mid-stream
# replica kill heals with deltas re-engaged (post-restart full frame
# then deltas on the killed replica) at the cost of at most one
# cycle's lost-reply re-place, and zero pods are lost (0 anomalies).
BENCH_POOL=1 BENCH_NODES=128 BENCH_PODS=1024 BENCH_POOL_CYCLES=24 \
  BENCH_POOL_SIZES=1,2 JAX_PLATFORMS=cpu \
  python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
tails = {r["pool"]["size"]: r["pool"] for r in rows if "pool" in r}
assert set(tails) == {1, 2}, f"missing pool sizes: {sorted(tails)}"
p1, p2 = tails[1], tails[2]
assert p2["hedge_dispatches"] >= 1, p2
assert p2["hedge_wins"] >= 1, p2
assert p2["device_p99_ms"] <= 0.8 * p1["device_p99_ms"], (
    "hedging did not cut device p99 >= 20%%: pool1=%s pool2=%s"
    % (p1["device_p99_ms"], p2["device_p99_ms"]))
for size, t in tails.items():
    assert t["lost_pods"] == 0, f"pool={size} lost pods: {t}"
    assert t["anomalies"] == 0, f"pool={size} anomalies: {t}"
    # The killed replica healed: its post-restart stream is a full
    # frame followed by re-engaged deltas.
    pk = t["post_kill_frames"]
    assert pk.get("full", 0) >= 1 and pk.get("delta", 0) >= 1, t
assert p2["failovers"] + p2["lost_reply_rows"] >= 1, p2
cut = 100 * (1 - p2["device_p99_ms"] / p1["device_p99_ms"])
print("BENCH_POOL smoke OK (device p99 %.0fms -> %.0fms, %.0f%% cut, "
      "%s hedges / %s wins)" % (p1["device_p99_ms"], p2["device_p99_ms"],
                                cut, p2["hedge_dispatches"],
                                p2["hedge_wins"]))
'
# BENCH_SHARDS smoke (ISSUE 16): the sharded control plane A/B at a
# small shape — asserts shards=2 actually engages (both shards run
# cycles and bind), the drain phase binds the SAME total as shards=1
# with ZERO cross-shard conflicts on the zone-partitioned workload,
# and the contention-heavy phase resolves its forced same-node races
# with zero lost pods and the conservation auditor clean.
BENCH_SHARDS=1,2 BENCH_NODES=32 BENCH_PODS=192 BENCH_SHARDS_SECS=4 \
  BENCH_SHARDS_SOLVE_MS=25 JAX_PLATFORMS=cpu \
  python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
tails = {r["shards"]["shards"]: r["shards"] for r in rows
         if "shards" in r}
assert set(tails) == {1, 2}, f"missing shard sizes: {sorted(tails)}"
s1, s2 = tails[1], tails[2]
# shards=2 engaged: both shards ran cycles and bound pods.
per = s2["per_shard"]
assert set(per) == {"s0", "s1"}, per
assert all(v["cycles"] >= 1 for v in per.values()), per
assert sum(v["binds"] for v in per.values()) >= 1, per
# Conflict-free partition: same bind total as shards=1, gate quiet.
assert s2["drain"]["bound"] == s1["drain"]["bound"], (s1, s2)
assert s1["drain"]["conflicts"] == 0, s1
assert s2["drain"]["conflicts"] == 0, s2
assert s2["throughput_conflicts"] == 0, s2
for size, t in tails.items():
    assert t["lost_pods"] == 0, f"shards={size} lost pods: {t}"
    assert t["anomalies"] == 0, f"shards={size} anomalies: {t}"
    c = t["contention"]
    assert c["lost_pods"] == 0, f"shards={size} contention lost: {c}"
    assert c["anomalies"] == 0, f"shards={size} contention anoms: {c}"
# The contention phase actually raced across shards.
assert s2["contention"]["conflicts"] >= 1, s2
print("BENCH_SHARDS smoke OK (%s -> %s binds/sec, %.2fx, "
      "%s contention conflicts, 0 lost)"
      % (s1["binds_per_sec"], s2["binds_per_sec"],
         s2["speedup_vs_shard1"], s2["contention"]["conflicts"]))
'
# BENCH_TOPOLOGY smoke (ISSUE 20): topology-aware gang placement on a
# fragmented 2-rack fabric — asserts the pregate held the
# require-contiguous gang exactly once (topology-infeasible), one
# slice-defrag plan committed, the gang converged FULLY contiguous
# (every member in one fabric block), and zero pods were lost (every
# drained filler re-bound).
BENCH_TOPOLOGY=1 JAX_PLATFORMS=cpu python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
tails = [r["topology"] for r in rows if "topology" in r]
assert tails, "no topology tail emitted"
t = tails[0]
assert t["infeasible_transitions"] == 1, f"pregate never held: {t}"
assert t["committed_plans"] >= 1, f"defrag never committed: {t}"
assert t["fit_before"] < 1.0, f"fabric was not fragmented: {t}"
assert t["contiguity_after"] == 1.0, f"gang not contiguous: {t}"
assert t["contiguous_placements"] >= 1, t
assert t["evictions"] >= 1, t
assert t["lost_pods"] == 0, f"pods lost: {t}"
print("BENCH_TOPOLOGY smoke OK (fit %.3f -> contiguity %.3f, "
      "%s evictions, %s cycles)"
      % (t["fit_before"], t["contiguity_after"], t["evictions"],
         t["converged_cycles"]))
'
# BENCH_PREEMPT smoke (ISSUE 11): the device-native preempt lane on a
# small fragmented-priority cluster — asserts the DEVICE lane actually
# engaged (a committed what-if plan + evictions through the shared
# ledger), the serving gang bound, and zero pods were lost (every
# evicted batch pod restored as Pending and re-placed or parked).
BENCH_PREEMPT=1 BENCH_NODES=8 JAX_PLATFORMS=cpu \
  VOLCANO_TPU_EVICT_DEVICE=1 python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
tails = [r["preempt"] for r in rows if "preempt" in r]
assert tails, "no preempt tail emitted"
t = tails[0]
assert t["committed_plans"] >= 1, f"device lane never committed: {t}"
assert t["plans"].get("preempt/committed", 0) >= 1, t
assert t["evictions"] >= 1, t
assert t["gang_bound"] >= t["gang"], f"serving gang did not bind: {t}"
assert t["lost_pods"] == 0, f"pods lost: {t}"
assert t["restored"] == t["evictions"], t
print("BENCH_PREEMPT smoke OK (%s evictions, %s cycles to bind)"
      % (t["evictions"], t["converged_cycles"]))
'
# BENCH_COMPOSED smoke (ISSUE 12): every fast lane engaged TOGETHER —
# virtual 4-device mesh + devincr + incremental host lanes + pipelining
# + 5% churn — in one run.  Asserts the composed tail proves engagement
# of every lane (mesh shards > 1, devincr warm counted, null-delta
# skips with ZERO dispatches, incremental derives in delta mode) and
# that the composed pipelined cycle beats the plain pass.
BENCH_COMPOSED=1 BENCH_COMPOSED_MESH=4 BENCH_NODES=256 BENCH_PODS=2048 \
  BENCH_REPEATS=1 BENCH_PIPE_CYCLES=5 JAX_PLATFORMS=cpu \
  python bench.py | python -c '
import json, sys
rows = [json.loads(l) for l in sys.stdin if l.strip()]
comp = [r for r in rows if "composed" in r]
assert comp, "no composed tail emitted"
r = comp[0]
c = r["composed"]
assert c["mesh_shards"] > 1, c
assert c["pipelined_ms"] < c["plain_ms"], c
assert c["incremental_derives"].get("delta", 0) >= 1, c
dv = r["devincr"]
assert dv["warm"] >= 1, dv
assert dv["null_delta_dispatches"] == 0, dv
assert dv["null_delta_skips"] >= 1, dv
assert "compile_ms" in r and "warmup_cycles_ms" in r, sorted(r)
print("BENCH_COMPOSED smoke OK (%sms plain -> %sms composed, "
      "%s shards)" % (c["plain_ms"], c["pipelined_ms"],
                      c["mesh_shards"]))
'
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
BENCH_ENDURANCE=1 BENCH_ENDURANCE_POOL=2 BENCH_NODES=64 BENCH_PODS=1024 \
  BENCH_ENDURANCE_CYCLES=200 BENCH_ENDURANCE_DELETE_FRAC=0.03 \
  VOLCANO_TPU_AUDIT_SAMPLE=8 JAX_PLATFORMS=cpu \
  python bench.py | python -c '
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
