#!/usr/bin/env bash
# Endurance gate (ISSUE 13, docs/observability.md): a compressed-hours
# simulator run — pipelined steady state under sustained churn, node
# flaps, solver-child kills/restarts, preempt waves and pod-table
# compactions — with the runtime conservation auditor ON and SLO
# budgets declared from a calibration window.  Exits nonzero on ANY
# anomaly; the JSON tail carries cycles survived, the anomaly verdict,
# p99s vs budgets, and the measured audit overhead (<2% envelope).
#
# Defaults run the 2k x 20k shape (~minutes on one chip / CPU);
# BENCH_FULL=1 runs the slow 10k x 100k tier.  All BENCH_ENDURANCE_*
# knobs (cycles, churn fraction, delete fraction, budget multiplier)
# and VOLCANO_TPU_AUDIT_SAMPLE pass straight through.
set -euo pipefail
cd "$(dirname "$0")/.."

: "${BENCH_ENDURANCE_CYCLES:=300}"
: "${VOLCANO_TPU_AUDIT_SAMPLE:=16}"
export BENCH_ENDURANCE_CYCLES VOLCANO_TPU_AUDIT_SAMPLE

# The first leg pins the HISTORIC single-connection path regardless of
# how the pool/shard legs below are sized — without the explicit
# pool=1 shards=1 an exported BENCH_ENDURANCE_POOL>=2 or
# BENCH_ENDURANCE_SHARDS>=2 would silently turn this into a second
# pool/shard run and leave the single-connection path ungated.
BENCH_ENDURANCE_POOL=1 BENCH_ENDURANCE_SHARDS=1 \
  python hack/endurance.py "$@" | tee /tmp/_vtpu_endurance_single.json
echo "endurance gate OK (0 anomalies)"

# Journey leg (ISSUE 18): the tail's journey block must prove the
# conservation check ran clean over every bound-ish pod (zero
# journey-orphan / journey-incomplete — any violation already failed
# the run above as an anomaly, this asserts the check actually
# EXECUTED over a non-empty set) and the capture overhead stays
# inside the <2%-of-cycle-time envelope.  The gated number is the
# journey's SELF-TIMED capture fraction of the endurance phase
# (journey_direct_pct, the audit-stats idiom): the journey-off A/B
# delta is also reported, but its resolution floor is the host's
# cycle jitter (the audit A/B on the same schedule swings +-5% on a
# loaded CPU host), so a sub-2% effect can't be gated through it
# without flaking.
python - /tmp/_vtpu_endurance_single.json <<'PYEOF'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
tails = [r["endurance"] for r in rows if "endurance" in r]
assert tails, "no endurance tail emitted"
j = tails[0].get("journey")
assert j is not None, "journey block missing from the endurance tail"
assert j["bound_pods_checked"] > 0, j
assert j["conservation_violations"] == 0, j
assert j["events"] > 0 and j["bound"] > 0, j
assert j["ttb_p50_ms"] is not None, j
pct = j["journey_direct_pct"]
assert pct < 2.0, f"journey overhead {pct}% breaches the 2% envelope"
print(f"endurance journey leg OK ({j['bound_pods_checked']} bound pods "
      f"conserved, {j['events']} events, capture {pct}% of cycle time,"
      f" A/B delta {j['journey_overhead_pct']}%)")
PYEOF

# Pool leg (ISSUE 15): the same churn + fault schedule over a 2-replica
# solver pool — kill waves hit RANDOM members while a straggler keeps
# hedges in flight (so kills can land mid-hedge); exits nonzero on any
# anomaly (0 anomalies = conservation held = zero lost pods).  Skip
# with BENCH_ENDURANCE_POOL=1; size with BENCH_ENDURANCE_POOL=<n>.
: "${BENCH_ENDURANCE_POOL:=2}"
export BENCH_ENDURANCE_POOL
if [ "${BENCH_ENDURANCE_POOL}" -gt 1 ]; then
  BENCH_ENDURANCE_SHARDS=1 \
    BENCH_ENDURANCE_CYCLES=$(( BENCH_ENDURANCE_CYCLES / 2 > 150 \
      ? BENCH_ENDURANCE_CYCLES / 2 : 150 )) python hack/endurance.py "$@"
  echo "endurance pool leg OK (0 anomalies, pool=${BENCH_ENDURANCE_POOL})"
fi

# Sharded leg (ISSUE 16): the same churn + fault schedule driven by a
# TWO-SHARD control plane over one logical cluster — cross-shard bind
# races resolve through the optimistic commit gate, preempt waves home
# on the evictor shard, and kill waves respawn the shard-0 solver lane.
# Conservation must hold across shard boundaries: exits nonzero on any
# anomaly.  Skip with BENCH_ENDURANCE_SHARDS=1; size with
# BENCH_ENDURANCE_SHARDS=<n> (forces pool=1 — one wire lane per shard).
: "${BENCH_ENDURANCE_SHARDS:=2}"
export BENCH_ENDURANCE_SHARDS
shard_secs=""
if [ "${BENCH_ENDURANCE_SHARDS}" -gt 1 ]; then
  t0=$SECONDS
  BENCH_ENDURANCE_CYCLES=$(( BENCH_ENDURANCE_CYCLES / 2 > 150 \
      ? BENCH_ENDURANCE_CYCLES / 2 : 150 )) python hack/endurance.py "$@"
  shard_secs=$(( SECONDS - t0 ))
  echo "endurance shard leg OK (0 anomalies, shards=${BENCH_ENDURANCE_SHARDS})"
fi

# Lockdep leg (ISSUE 17): the shard-leg shape once more with the
# annotation-derived runtime lock enforcement armed
# (VOLCANO_TPU_LOCKDEP=1, obs/lockdep.py) — every guarded-by attribute
# access is checked against the held-lock set and every acquisition
# feeds the process-wide order graph.  Violations land in the auditor
# ring as lockdep-violation / lock-order-cycle anomalies, so the same
# zero-anomaly exit gates them.  The wall-clock delta vs the
# enforcement-off shard leg above is the measured lockdep overhead.
# Skip with BENCH_ENDURANCE_LOCKDEP=0.
: "${BENCH_ENDURANCE_LOCKDEP:=1}"
if [ "${BENCH_ENDURANCE_LOCKDEP}" != "0" ]; then
  t0=$SECONDS
  VOLCANO_TPU_LOCKDEP=1 \
    BENCH_ENDURANCE_CYCLES=$(( BENCH_ENDURANCE_CYCLES / 2 > 150 \
      ? BENCH_ENDURANCE_CYCLES / 2 : 150 )) python hack/endurance.py "$@"
  lockdep_secs=$(( SECONDS - t0 ))
  if [ -n "${shard_secs}" ] && [ "${shard_secs}" -gt 0 ]; then
    echo "endurance lockdep leg OK (0 anomalies," \
      "${lockdep_secs}s vs ${shard_secs}s enforcement-off," \
      "overhead $(( (lockdep_secs - shard_secs) * 100 / shard_secs ))%)"
  else
    echo "endurance lockdep leg OK (0 anomalies, ${lockdep_secs}s)"
  fi
fi
