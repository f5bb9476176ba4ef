"""The journey log's batch path against a per-event reference (ISSUE 26).

``JourneyLog.pod_rows`` stamps a batch with array work over columnar
per-pod state; ``pod_event`` writes the same columns one pod at a time.
Both must leave what a plain per-event log leaves.  The reference below
is that plain log, written out on its own (a dict of per-pod records,
a deque as the ring, ``bisect`` histograms, ``SLOTracker.observe_sample``
once per first bind); every scenario drives it and the real log from one
seeded event sequence on one fake clock and compares every reader.

Also here: the two bulk sinks against their one-at-a-time forms, and
the work count (a 20,000-row ``bound`` batch with every per-event
routine patched to raise).  All CPU-only; tier-1.
"""

import random
from bisect import bisect_left
from collections import deque

import numpy as np
import pytest

from volcano_tpu.metrics.metrics import (
    _DEFAULT_BUCKETS, _Histogram, Metrics, _labels_key)
from volcano_tpu.obs import Auditor, JourneyLog, SLOTracker
from volcano_tpu.obs import journey as journey_mod
from volcano_tpu.obs.journey import KINDS

pytestmark = pytest.mark.tier1

BOUND_MASK = (1 << 1) | (1 << 3) | (1 << 4) | (1 << 5) | (1 << 7)
ST_PENDING, ST_BOUND, ST_RUNNING = 1, 1 << 4, 1 << 5


class Clock:
    """Stands in for the ``time`` module inside obs/journey.py."""

    def __init__(self):
        self.t = 1_000_000_000

    def time_ns(self):
        return self.t

    def perf_counter_ns(self):
        return 0


# ----------------------------------------------------- the reference


def _pct(vals, q):
    if not vals:
        return None
    vals = sorted(vals)
    return round(vals[min(int(q * (len(vals) - 1) + 0.5), len(vals) - 1)], 3)


class RefHist:
    def __init__(self):
        self.data = {}

    def observe(self, value, **labels):
        st = self.data.setdefault(
            _labels_key(labels), [[0] * (len(_DEFAULT_BUCKETS) + 1), 0.0, 0])
        st[0][bisect_left(_DEFAULT_BUCKETS, value)] += 1
        st[1] += value
        st[2] += 1


class RefJourney:
    """One dict entry per pod, one loop iteration per event."""

    def __init__(self, cap, anchor, slo):
        self.anchor = anchor
        self.ring = deque(maxlen=cap)
        self.written = 0
        self.pods, self.gangs = {}, {}
        self.kinds = {}
        self.rebinds = self.reconsiders = self.bound_total = 0
        self.ttb, self.ttfc = deque(maxlen=4096), deque(maxlen=4096)
        self.gang_ttfb = deque(maxlen=1024)
        self.queue_ttb, self.queue_counts = {}, {}
        self.h_ttfc, self.h_ttb, self.h_gang = RefHist(), RefHist(), RefHist()
        self.slo = slo
        self.breaches = []

    def _pod(self, queue, gang, now, synthetic):
        return dict(queue=queue, gang=gang, enq=now, first=None, bound=None,
                    last=now, last_kind="enqueued", status=ST_PENDING,
                    drops=deque(maxlen=8), monotone=True,
                    synthetic=synthetic, restored_from=None)

    def _mark_bound(self, st, now):
        st["bound"] = now
        self.bound_total += 1
        ms = (now - st["enq"]) / 1e6
        self.ttb.append(ms)
        q = st["queue"] or "none"
        self.queue_ttb.setdefault(q, deque(maxlen=256)).append(ms)
        self.queue_counts.setdefault(q, {"enqueued": 0, "bound": 0})[
            "bound"] += 1
        self.h_ttb.observe(ms, queue=q)
        if self.slo is not None and not st["synthetic"]:
            self.breaches.extend(self.slo.observe_sample("ttb", ms))
        g = self.gangs.get(st["gang"]) if st["gang"] else None
        if g is not None:
            g["bound"] += 1
            if not g["done"] and g["members"] > 0 \
                    and g["bound"] >= g["members"]:
                g["done"] = True
                gms = (now - g["first_enq"]) / 1e6
                self.gang_ttfb.append(gms)
                self.h_gang.observe(gms)

    def _status(self, st, status, now):
        if status >= 0:
            st["status"] = status
            if status & BOUND_MASK and st["bound"] is None:
                self._mark_bound(st, now)

    def apply(self, uid, kind, now, status=-1, queue="", gang="", shard=-1,
              solve_id=0, epoch=-1, detail=""):
        if not uid:
            return
        st = self.pods.get(uid)
        if kind == "enqueued":
            if st is None:
                st = self.pods[uid] = self._pod(queue, gang, now, False)
                if gang:
                    g = self.gangs.setdefault(gang, dict(
                        first_enq=now, members=0, bound=0, alive=0,
                        done=False))
                    g["members"] += 1
                    g["alive"] += 1
                self.queue_counts.setdefault(
                    queue, {"enqueued": 0, "bound": 0})["enqueued"] += 1
            self._status(st, status, now)
        elif st is None:
            st = self.pods[uid] = self._pod(queue, "", now, True)
        if now < st["last"]:
            st["monotone"] = False
        st["last"], st["last_kind"] = now, kind
        if kind == "dispatched":
            if st["first"] is None:
                st["first"] = now
                ms = (now - st["enq"]) / 1e6
                self.ttfc.append(ms)
                self.h_ttfc.observe(ms, queue=st["queue"] or "none")
        elif kind == "dropped":
            st["drops"].append((detail, shard))
        elif kind == "bound":
            self._status(st, ST_BOUND, now)
        elif kind == "status-sync":
            self._status(st, status, now)
        elif kind == "removed":
            del self.pods[uid]
            g = self.gangs.get(st["gang"]) if st["gang"] else None
            if g is not None:
                g["alive"] -= 1
                if g["alive"] <= 0:
                    del self.gangs[st["gang"]]
        row = {"uid": uid, "kind": kind,
               "ts_us": round((self.anchor + now) / 1e3, 1)}
        if shard >= 0:
            row["shard"] = shard
        if solve_id:
            row["solve_id"] = solve_id
        if epoch >= 0:
            row["handoff_epoch"] = epoch
        if detail:
            row["detail"] = detail
        self.ring.append(row)
        self.written += 1
        self.kinds[kind] = self.kinds.get(kind, 0) + 1

    def resync(self, pairs, now):
        for uid, status in pairs:
            if not uid:
                continue
            st = self.pods.get(uid)
            if st is None:
                st = self.pods[uid] = self._pod("", "", now, True)
            self._status(st, status, now)

    def restored(self, old, new, now):
        if new in self.pods:
            self.pods[new]["restored_from"] = old
        self.apply(new, "restored", now, detail=old)

    # readers

    def verdict(self, st):
        if st["status"] & BOUND_MASK:
            return "bound"
        if st["last_kind"] in ("evicted", "migration-planned"):
            return f"{st['last_kind']} (awaiting restore)"
        if not st["drops"]:
            return ("never considered (queue backlog)" if st["first"] is None
                    else "considered, no drops recorded (awaiting commit)")
        runs = []
        for key in st["drops"]:
            if runs and runs[-1][0] == key:
                runs[-1][1] += 1
            else:
                runs.append([key, 1])
        return ", ".join(
            (reason or "dropped") + (f" x{n}" if n > 1 else "")
            + (f" on shard {shard}" if shard >= 0 else "")
            for (reason, shard), n in runs)

    def why_pending(self, uid):
        st = self.pods.get(uid)
        return ("unknown (no journey state)" if st is None
                else self.verdict(st))

    def timeline(self, uid):
        st = self.pods.get(uid)
        events = [r for r in self.ring if r["uid"] == uid]
        if st is None and not events:
            return None
        body = {"uid": uid, "events": events}
        if st is None:
            body["why_pending"] = "removed (events only)"
            return body
        since = lambda t: (None if t is None
                           else round((t - st["enq"]) / 1e6, 3))
        body.update({
            "queue": st["queue"], "gang": st["gang"],
            "status": st["status"],
            "enqueued_us": round((self.anchor + st["enq"]) / 1e3, 1),
            "time_to_first_consider_ms": since(st["first"]),
            "time_to_bind_ms": since(st["bound"]),
            "last_kind": st["last_kind"], "monotone": st["monotone"],
            "restored_from": st["restored_from"],
            "why_pending": self.verdict(st),
        })
        return body

    def queue_rollup(self):
        return {
            "queues": {q: {
                "enqueued_total": c["enqueued"], "bound_total": c["bound"],
                "ttb_p50_ms": _pct(list(self.queue_ttb.get(q, ())), 0.50),
                "ttb_p99_ms": _pct(list(self.queue_ttb.get(q, ())), 0.99),
            } for q, c in sorted(self.queue_counts.items())},
            "pods_tracked": len(self.pods),
            "gangs_tracked": len(self.gangs),
            "events_total": self.written,
        }

    def stats(self):
        return {
            "events": self.written,
            "events_dropped": max(self.written - self.ring.maxlen, 0),
            "pods": len(self.pods), "bound": self.bound_total,
            "rebinds": self.rebinds, "reconsiders": self.reconsiders,
            "ttfc_p50_ms": _pct(list(self.ttfc), 0.50),
            "ttb_p50_ms": _pct(list(self.ttb), 0.50),
            "ttb_p95_ms": _pct(list(self.ttb), 0.95),
            "ttb_p99_ms": _pct(list(self.ttb), 0.99),
            "gang_ttfb_p50_ms": _pct(list(self.gang_ttfb), 0.50),
            "gang_ttfb_p99_ms": _pct(list(self.gang_ttfb), 0.99),
        }

    def conservation(self, uids):
        orphans = [u for u in uids if u not in self.pods]
        incomplete = [u for u in uids if u in self.pods and (
            self.pods[u]["bound"] is None or not self.pods[u]["monotone"])]
        out = []
        if orphans:
            out.append(("journey-orphan", {"count": len(orphans),
                                           "uids": orphans[:5]}))
        if incomplete:
            out.append(("journey-incomplete", {"count": len(incomplete),
                                               "uids": incomplete[:5]}))
        return out


# ------------------------------------------------------ the scenarios
#
# A scenario is a list of ops on one clock:
#   ("tick", ns)                      the clock moves (ns may be < 0)
#   ("event", uid, kind, kwargs)      JourneyLog.pod_event
#   ("rows", uids, kind, kwargs)      JourneyLog.pod_rows
#   ("repeat", n, kind) / ("resync", pairs) / ("restored", old, new)


def _enqueue(ops, uids, queue, gang, rng, status=ST_PENDING):
    for u in uids:
        ops.append(("tick", rng.randrange(1_000, 400_000)))
        ops.append(("event", u, "enqueued",
                    dict(status=status, queue=queue, gang=gang)))


def _rows(ops, uids, kind, rng, **kw):
    ops.append(("tick", rng.randrange(100_000, 30_000_000)))
    ops.append(("rows", list(uids), kind, kw))


def sc_queues_and_gangs(rng):
    """Several queues and gangs, bound in shuffled batches."""
    ops, uids = [], []
    for g in range(12):
        members = [f"p{g}-{k}" for k in range(rng.choice((1, 2, 4, 8)))]
        _enqueue(ops, members, f"q{g % 3}" if g % 4 else "", f"g{g}", rng)
        uids += members
    _enqueue(ops, ["solo-a", "solo-b"], "q1", "", rng)
    uids += ["solo-a", "solo-b"]
    rng.shuffle(uids)
    for lo in range(0, len(uids), 17):
        _rows(ops, uids[lo:lo + 17], "dispatched", rng, solve_id=lo + 1,
              shard=lo % 2)
        _rows(ops, uids[lo:lo + 17], "bound", rng, solve_id=lo + 1,
              shard=lo % 2)
    return ops


def sc_gang_completes_mid_batch(rng):
    """Gangs partly bound before the batch, complete inside it at their
    need-th pod; one whose members never all come; one already done."""
    ops = []
    _enqueue(ops, [f"a{k}" for k in range(6)], "q", "ga", rng)
    _enqueue(ops, [f"b{k}" for k in range(4)], "q", "gb", rng)
    _enqueue(ops, [f"c{k}" for k in range(3)], "q2", "gc", rng)
    _enqueue(ops, [f"d{k}" for k in range(2)], "q2", "gd", rng)
    _rows(ops, ["a0", "a1", "a2", "d0", "d1"], "bound", rng)
    ops.append(("event", "b3", "bound", {}))
    # ga needs 3 of its 3 here, gb 3 of 3 but interleaved so that gb is
    # done before ga; gc is left one short.
    _rows(ops, ["b0", "a3", "c0", "b1", "a4", "b2", "c1", "a5"],
          "bound", rng, solve_id=9)
    _enqueue(ops, ["a6"], "q", "ga", rng)      # joins a done gang
    _rows(ops, ["a6", "c2"], "bound", rng)
    return ops


def sc_uid_twice_and_none(rng):
    """A uid twice in one batch is first-time once; None uids and empty
    strings are skipped; an all-None batch stamps nothing."""
    ops = []
    _enqueue(ops, ["x", "y", "z"], "q", "g", rng)
    _rows(ops, ["x", None, "y", "x", "", "z", "y"], "dispatched", rng,
          solve_id=4)
    _rows(ops, [None, None], "dispatched", rng)
    _rows(ops, ["z", "x", "z", None, "x"], "bound", rng, solve_id=4)
    _rows(ops, [], "bound", rng)
    _rows(ops, ["y", "y"], "bound", rng)
    return ops


def sc_synthetic_roots(rng):
    """Pods adopted without an ``enqueued``: through a batch (twice in
    it), through pod_event, through pod_resync; the ttb SLO lane skips
    them; a later ``enqueued`` for an adopted pod creates nothing."""
    ops = []
    _enqueue(ops, ["r0", "r1"], "q", "g", rng)
    _rows(ops, ["s0", "r0", "s1", "s0"], "dispatched", rng, solve_id=2)
    ops.append(("tick", 5_000_000))
    ops.append(("event", "s2", "dropped",
                dict(detail="capacity-taken", shard=1)))
    _rows(ops, ["s1", "r1", "s3", "r0", "s3"], "bound", rng)
    ops.append(("tick", 2_000_000))
    ops.append(("resync", [("s4", ST_RUNNING), ("s2", ST_PENDING),
                           (None, ST_BOUND), ("s0", ST_BOUND)]))
    ops.append(("event", "s4", "enqueued",
                dict(status=ST_PENDING, queue="q", gang="g")))
    return ops


def sc_removed_and_slot_reuse(rng):
    """``removed`` frees the slot (and the last member the gang); the
    next pods reuse both and must not inherit drops, binds or links."""
    ops = []
    _enqueue(ops, ["m0", "m1", "m2"], "q", "g0", rng)
    _rows(ops, ["m0", "m1", "m2"], "dispatched", rng, solve_id=1)
    _rows(ops, ["m0", "m1"], "dropped", rng, detail="capacity-taken",
          shard=1, epoch=3)
    _rows(ops, ["m0", "m1", "m2"], "bound", rng)
    ops.append(("restored", "victim", "m1"))
    for u in ("m0", "m1", "m2"):
        ops.append(("tick", 100_000))
        ops.append(("event", u, "removed", dict(status=ST_BOUND)))
    _enqueue(ops, ["n0", "n1", "n2", "n3"], "q9", "g1", rng)
    _rows(ops, ["n3", "n0"], "dispatched", rng, solve_id=2)
    _rows(ops, ["n0", "n1", "n2", "n3"], "bound", rng, solve_id=2)
    ops.append(("tick", 100_000))
    ops.append(("event", "n2", "removed", dict(status=ST_BOUND)))
    _enqueue(ops, ["m0"], "q", "g0", rng, status=ST_BOUND)  # comes back bound
    return ops


def sc_drops_with_shard_and_epoch(rng):
    """Drop chains: runs compress, the chain keeps its last 8, shard
    and hand-off epoch reach the ring; evict / what-if kinds in bulk."""
    ops = []
    _enqueue(ops, [f"d{k}" for k in range(5)], "q", "", rng)
    _rows(ops, ["d0", "d1", "d2"], "dispatched", rng, solve_id=7, shard=0)
    for rep in range(11):
        _rows(ops, ["d0", "d1"] if rep % 3 else ["d0", "d2", "d0"],
              "dropped", rng,
              detail=("cross-shard-conflict" if rep % 4 == 0
                      else "capacity-taken"),
              shard=rep % 2, epoch=rep if rep % 4 == 0 else -1)
    _rows(ops, ["d4"], "dropped", rng, detail="topology-infeasible")
    _rows(ops, ["d3"], "dropped", rng, detail="")
    _rows(ops, ["d1", "d3"], "evicted", rng, shard=1)
    _rows(ops, ["d3"], "evict-reverted", rng, shard=1)
    _rows(ops, ["d2"], "bound", rng, solve_id=7)
    ops.append(("repeat", 40, "bound"))
    ops.append(("repeat", 7, "dispatched"))
    ops.append(("repeat", 3, "unbound"))
    return ops


def sc_batch_larger_than_ring(rng):
    """cap = 64: a 150-row batch leaves its last 64 rows; then the ring
    wraps under smaller batches and single events."""
    ops = []
    uids = [f"w{k}" for k in range(150)]
    for lo in range(0, 150, 5):
        _enqueue(ops, uids[lo:lo + 5], f"q{lo % 2}", f"g{lo}", rng)
    _rows(ops, uids, "dispatched", rng, solve_id=1)
    _rows(ops, uids[:40], "bound", rng, solve_id=1)
    ops.append(("tick", 10))
    ops.append(("event", "w149", "status-sync", dict(status=ST_RUNNING)))
    _rows(ops, uids[40:], "bound", rng, solve_id=1)
    _rows(ops, uids[:64], "evicted", rng)       # exactly the ring
    _rows(ops, uids[:30], "evict-reverted", rng)
    return ops


def sc_clock_steps_back(rng):
    """A wall-clock step backwards marks exactly the pods stamped after
    it, in a batch and one at a time."""
    ops = []
    _enqueue(ops, ["t0", "t1", "t2", "t3"], "q", "", rng)
    _rows(ops, ["t0", "t1", "t2", "t3"], "dispatched", rng)
    ops.append(("tick", -5_000_000_000))
    ops.append(("rows", ["t0", "t2"], "bound", {}))
    ops.append(("event", "t3", "bound", {}))
    _rows(ops, ["t1"], "bound", rng)
    return ops


def sc_breach_inside_batch(rng):
    """ttb samples around the budget so that, with a budget declared,
    breach edges rise and clear inside batches (slow pods enqueued
    long before, fast ones just now)."""
    ops, slow, fast = [], [], []
    _enqueue(ops, slow := [f"s{k}" for k in range(30)], "q", "", rng)
    ops.append(("tick", 400_000_000))
    _enqueue(ops, fast := [f"f{k}" for k in range(60)], "q", "", rng)
    ops.append(("tick", 1_000_000))
    ops.append(("rows", fast[:20] + slow[:12] + fast[20:50] + slow[12:20],
                "bound", {}))
    ops.append(("tick", 1_000_000))
    ops.append(("rows", slow[20:] + fast[50:], "bound", {}))
    return ops


def sc_random(rng):
    """Everything at once, several hundred events."""
    ops, live, known, n = [], [], [], 0
    for step in range(120):
        r = rng.random()
        if r < 0.30 or not live:
            g = f"g{step}" if rng.random() < 0.7 else ""
            new = [f"u{n + k}" for k in range(rng.randrange(1, 7))]
            n += len(new)
            _enqueue(ops, new, rng.choice(("", "qa", "qb", "qc")), g, rng,
                     status=rng.choice((ST_PENDING,) * 9 + (ST_BOUND,)))
            live += new
            known += new
        elif r < 0.85:
            batch = rng.sample(live, min(len(live), rng.randrange(1, 40)))
            if rng.random() < 0.3:
                batch += [None, rng.choice(batch), f"ghost{step}"]
                known.append(f"ghost{step}")
                live.append(f"ghost{step}")
            kind = rng.choice(("dispatched", "dispatched", "bound", "bound",
                               "dropped", "evicted", "migration-planned"))
            _rows(ops, batch, kind, rng, solve_id=rng.randrange(0, 50),
                  shard=rng.randrange(-1, 3),
                  epoch=rng.randrange(-1, 4) if kind == "dropped" else -1,
                  detail=(rng.choice(("capacity-taken", "stale-node", ""))
                          if kind == "dropped" else ""))
        elif r < 0.95:
            u = live.pop(rng.randrange(len(live)))
            ops.append(("tick", rng.randrange(-2_000, 50_000)))
            ops.append(("event", u, "removed", dict(status=ST_BOUND)))
        else:
            u = rng.choice(live)
            ops.append(("tick", 1_000))
            ops.append(("event", u, rng.choice(("bound", "status-sync",
                                                "dispatched", "dropped")),
                        dict(status=rng.choice((ST_PENDING, ST_RUNNING)),
                             detail="backfill", shard=0)))
    return ops


SCENARIOS = {
    "queues-and-gangs": (sc_queues_and_gangs, 256),
    "gang-completes-mid-batch": (sc_gang_completes_mid_batch, 256),
    "uid-twice-and-none": (sc_uid_twice_and_none, 256),
    "synthetic-roots": (sc_synthetic_roots, 256),
    "removed-and-slot-reuse": (sc_removed_and_slot_reuse, 256),
    "drops-with-shard-and-epoch": (sc_drops_with_shard_and_epoch, 256),
    "batch-larger-than-ring": (sc_batch_larger_than_ring, 64),
    "clock-steps-back": (sc_clock_steps_back, 256),
    "breach-inside-batch": (sc_breach_inside_batch, 256),
    "random-1": (sc_random, 128),
    "random-2": (sc_random, 512),
    "random-3": (sc_random, 64),
}


def _hist(h):
    return {k: (v[0], v[2]) for k, v in h.data.items()}


def _sums(h):
    return {k: v[1] for k, v in h.data.items()}


def _stamp_by_slot(jr, column, uids, kind, kw):
    """A batch as the fast cycle stamps its rows (ISSUE 32,
    ``fastpath._journey_rows``): the ``dispatched`` stamp leaves each
    pod's slot in a column, the ``bound`` stamp reads the column and
    looks up no uid but those the column does not know.  ``column`` is
    keyed by uid here, where the cycle's is by mirror row; a row without
    a uid (tombstoned) drops out before either."""
    live = [u for u in uids if u]
    if kind == "dispatched":
        column.update(zip(live, jr.pod_rows(live, kind, **kw).tolist()))
    elif kind == "bound":
        sl = np.fromiter((column.get(u, -1) for u in live), np.int64,
                         len(live))
        jr.pod_slots(sl, live[len(live) - min(len(live), jr.capacity):],
                     kind, miss_uids=[u for u, s in zip(live, sl) if s < 0],
                     **kw)
    else:
        jr.pod_rows(uids, kind, **kw)


def _drive(monkeypatch, name, budget, by_slot=False):
    build, cap = SCENARIOS[name]
    ops = build(random.Random(f"{name}/{budget}"))
    clock = Clock()
    monkeypatch.setattr(journey_mod, "time", clock)
    slo, ref_slo = SLOTracker(window=32), SLOTracker(window=32)
    if budget:
        for t in (slo, ref_slo):
            t.declare("ttb", 100.0, allowed_frac=0.25)
    auditor = Auditor(enabled=False)
    jr = JourneyLog(capacity=cap, slo=slo, auditor=auditor)
    jr._metrics = reg = Metrics()
    ref = RefJourney(cap, jr._anchor_ns, ref_slo)
    uids = set()
    column = {}
    for op in ops:
        now = clock.t - jr._anchor_ns
        if op[0] == "tick":
            clock.t += op[1]
        elif op[0] == "event":
            jr.pod_event(op[1], op[2], **op[3])
            ref.apply(op[1], op[2], now, **op[3])
            uids.add(op[1])
            if op[2] == "removed":  # the mirror tombstones the row
                column.pop(op[1], None)
        elif op[0] == "rows" and by_slot:
            _stamp_by_slot(jr, column, op[1], op[2], op[3])
            for u in op[1]:
                ref.apply(u, op[2], now, **op[3])
            uids.update(u for u in op[1] if u)
        elif op[0] == "rows":
            jr.pod_rows(op[1], op[2], **op[3])
            for u in op[1]:
                ref.apply(u, op[2], now, **op[3])
            uids.update(u for u in op[1] if u)
        elif op[0] == "repeat":
            jr.repeat_rows(op[1], op[2])
            if op[2] == "bound":
                ref.rebinds += op[1]
            elif op[2] == "dispatched":
                ref.reconsiders += op[1]
        elif op[0] == "resync":
            jr.pod_resync(op[1])
            ref.resync(op[1], now)
            uids.update(u for u, _ in op[1] if u)
        elif op[0] == "restored":
            jr.pod_restored(op[1], op[2])
            ref.restored(op[1], op[2], now)
    return jr, ref, reg, auditor, sorted(uids), ops


@pytest.mark.parametrize("by_slot", [False, True], ids=["uids", "slots"])
@pytest.mark.parametrize("budget", [False, True],
                         ids=["no-budget", "ttb-budget"])
@pytest.mark.parametrize("name", list(SCENARIOS))
def test_batch_path_equals_per_event_reference(monkeypatch, name, budget,
                                               by_slot):
    """``slots``: every ``bound`` batch goes through ``pod_slots`` with
    the slots its pods' ``dispatched`` stamps returned (ISSUE 32), and
    leaves what the uid path and the per-event log leave."""
    jr, ref, reg, auditor, uids, ops = _drive(monkeypatch, name, budget,
                                              by_slot)
    assert uids and any(op[0] == "rows" for op in ops)

    for uid in uids + ["never-seen"]:
        assert jr.timeline(uid) == ref.timeline(uid), uid
        assert jr.why_pending(uid) == ref.why_pending(uid), uid
    assert jr.queue_rollup() == ref.queue_rollup()
    got = jr.stats()
    for key in ("capture_ms", "bulk_calls", "bulk_events", "scalar_events"):
        got.pop(key)
    slot_hits = got.pop("slot_hits")
    assert got == ref.stats()
    assert jr.trace_rows() == list(ref.ring)
    assert [(a.reason, a.detail) for a in jr.conservation_check(
        uids + ["never-seen"])] == ref.conservation(uids + ["never-seen"])

    for mine, theirs in ((reg.pod_time_to_first_consider, ref.h_ttfc),
                         (reg.pod_time_to_bind, ref.h_ttb),
                         (reg.gang_time_to_full_bind, ref.h_gang)):
        assert _hist(mine) == _hist(theirs), mine.name
        for key, total in _sums(theirs).items():
            assert _sums(mine)[key] == pytest.approx(total, rel=1e-9)
    assert reg.journey_events.data == {
        (("kind", k),): float(n) for k, n in ref.kinds.items()}

    # The ttb lane and what it raised, edge for edge.
    assert jr.slo.snapshot() == ref.slo.snapshot()
    assert [(a.reason, a.detail) for a in auditor.anomalies()] == [
        ("slo-budget-exceeded", b) for b in ref.breaches]
    if name == "breach-inside-batch":
        assert bool(ref.breaches) == budget
    st = jr.stats()
    assert st["bulk_events"] + st["scalar_events"] == st["events"]
    assert st["bulk_events"] == sum(
        sum(1 for u in op[1] if u) for op in ops if op[0] == "rows")
    # Only a ``bound`` batch by slots counts hits, and at most its pods.
    bound_events = sum(sum(1 for u in op[1] if u) for op in ops
                       if op[0] == "rows" and op[2] == "bound")
    assert 0 <= slot_hits <= (bound_events if by_slot else 0)
    if by_slot and name in ("queues-and-gangs", "batch-larger-than-ring"):
        assert slot_hits == bound_events    # all dispatched just before


def test_edge_kinds_are_not_batch_kinds():
    jr = JourneyLog(capacity=64)
    for kind in ("enqueued", "status-sync", "removed"):
        with pytest.raises(ValueError):
            jr.pod_rows(["u"], kind)
    assert jr.stats()["events"] == 0
    assert set(KINDS) >= {"dispatched", "bound", "dropped"}


# ------------------------------------------------------ the two sinks


@pytest.mark.parametrize("values", [
    [],
    [0.005, 0.01, 1, 1.0, 10000, 10000.0],      # exactly on an edge
    [0.0049999, 0.0050001, 9999.99, 10000.01, 1e9, 0.0, -1.0],
    "random",
], ids=["empty", "on-the-edges", "beside-the-edges", "random"])
def test_histogram_observe_many_equals_observe(values):
    if values == "random":
        rng = np.random.default_rng(26)
        values = np.concatenate([
            rng.lognormal(3, 3, 5000), np.asarray(_DEFAULT_BUCKETS)])
    one, many = _Histogram("h", ""), _Histogram("h", "")
    for v in values:
        one.observe(float(v), queue="q")
    many.observe_many(values, queue="q")
    one.observe(2.0)
    many.observe_many(np.asarray([2.0]))
    many.observe_many([], queue="untouched")
    assert _hist(one) == _hist(many)
    assert set(_sums(one)) == set(_sums(many))
    for key, total in _sums(one).items():
        assert _sums(many)[key] == pytest.approx(total, rel=1e-9)
    for state in many.data.values():
        assert all(type(c) is int for c in state[0])
        assert type(state[1]) is float and type(state[2]) is int


def _slo_pair(budget, window=32):
    one, many = SLOTracker(window=window), SLOTracker(window=window)
    if budget is not None:
        for t in (one, many):
            t.declare("ttb", *budget)
    return one, many


def _feed_both(one, many, batches):
    edges_one, edges_many = [], []
    for batch in batches:
        for v in batch:
            edges_one += one.observe_sample("ttb", v)
        edges_many += many.observe_samples("ttb", batch)
    return edges_one, edges_many


@pytest.mark.parametrize("sizes", [
    (5, 7, 3), (32,), (16, 16), (100,), (31, 1, 1, 64), (15, 1), (0, 3),
], ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("budget", [None, (50.0, 0.1), (50.0, 0.5)],
                         ids=["unbudgeted", "tight", "loose"])
def test_slo_observe_samples_equals_observe_sample(sizes, budget):
    """Window (32) shorter than, equal to and longer than the batch."""
    from volcano_tpu.metrics import metrics

    rng = random.Random(str((sizes, budget)))
    batches = [[rng.choice((5.0, 20.0, 49.999, 50.0, 50.001, 80.0, 900.0))
                for _ in range(n)] for n in sizes]
    one, many = _slo_pair(budget)
    edges_one, edges_many = _feed_both(one, many, batches)
    assert edges_one == edges_many
    assert one.snapshot() == many.snapshot()
    assert one.violations == many.violations
    assert one.observations == many.observations == (
        {"ttb": sum(sizes)} if sum(sizes) else {})
    if budget is not None and sum(sizes) >= 16:
        # The gauge holds the last sample's burn, as the loop leaves it.
        burn = many.snapshot()["ttb"]["burn_rate"]
        assert metrics.slo_burn_rate.data[(("lane", "ttb"),)] == burn


def test_slo_breach_raised_and_cleared_within_one_batch():
    """One 200-sample batch: healthy, a burst over the target, healthy
    again, a second burst — two rising edges, the lane ends unbreached
    ... and every edge's detail is the loop's."""
    batch = [10.0] * 40 + [500.0] * 10 + [10.0] * 60 + [500.0] * 12 \
        + [10.0] * 78
    one, many = _slo_pair((100.0, 0.25))
    edges_one, edges_many = _feed_both(one, many, [batch])
    assert len(edges_one) == 2 and edges_many == edges_one
    assert [e["over_in_window"] for e in edges_many] == [8, 8]
    snap = many.snapshot()["ttb"]
    assert snap == one.snapshot()["ttb"]
    assert snap["breached"] is False and snap["violations_total"] == 22
    # A breach standing when the batch begins is no new edge; it clears
    # and rises again inside the next batch.
    one, many = _slo_pair((100.0, 0.25))
    e1, e2 = _feed_both(one, many, [[500.0] * 20, [500.0] * 5 + [1.0] * 40
                                    + [500.0] * 9])
    assert e1 == e2 and len(e2) == 2
    assert one.snapshot() == many.snapshot()


# ------------------------------------------------------- work count


def test_bound_batch_takes_no_per_event_routine(monkeypatch):
    """20,000 first binds in one batch, with every one-at-a-time
    routine patched to raise: the batch path alone does the work."""
    n = 20_000
    slo = SLOTracker()
    slo.declare("ttb", 1e9)
    jr = JourneyLog(slo=slo, auditor=Auditor(enabled=False))
    jr._metrics = reg = Metrics()
    uids = [f"pod-{i}" for i in range(n)]
    for i, u in enumerate(uids):
        jr.pod_event(u, "enqueued", status=ST_PENDING, queue=f"q{i % 3}",
                     gang=f"g{i // 8}")

    def boom(*a, **kw):
        raise AssertionError("per-event routine on the batch path")

    monkeypatch.setattr(_Histogram, "observe", boom)
    monkeypatch.setattr(SLOTracker, "observe_sample", boom)
    for routine in ("_apply", "_sync_status", "_new_pod", "_join_gang"):
        monkeypatch.setattr(JourneyLog, routine, boom)
    jr.pod_rows(uids, "dispatched", solve_id=1)
    jr.pod_rows(uids, "bound", solve_id=1)

    st = jr.stats()
    assert st["bulk_events"] == 2 * n and st["bulk_calls"] == 2
    assert st["scalar_events"] == n and st["bound"] == n
    assert st["events"] == 3 * n
    assert jr.queue_rollup()["queues"]["q1"]["bound_total"] == n // 3 + 1
    assert sum(v[2] for v in reg.pod_time_to_bind.data.values()) == n
    assert reg.gang_time_to_full_bind.data[()][2] == n // 8
    assert slo.snapshot()["ttb"]["observations"] == n
    assert jr.conservation_check(uids) == []


# ------------------------------------------------ the fast path's seam


def test_cycle_spans_say_how_many_rows_took_the_batch_path():
    """``device:journey`` carries ``args = {"rows", "fresh"}`` and
    ``commit:journey`` beside them ``slot_hits``, the rows whose slot
    the ``dispatched`` stamp had left; ``stats()`` counts the batches.
    A re-pend feed makes the second cycle's rows repeats: they fold
    into bulk counters and never reach the log."""
    from volcano_tpu.api import TaskStatus
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=26)
    sched = Scheduler(store)

    def stamps():
        rec = store.flight.recent()[-1]
        return {s.name: s.args for s in rec.spans
                if s.name in ("journey", "device:journey",
                              "commit:journey")}

    sched.run_once()
    store.flush_binds()
    assert stamps() == {"device:journey": {"rows": 32, "fresh": 32},
                        "commit:journey": {"rows": 32, "fresh": 32,
                                           "slot_hits": 32}}
    st = store.journey.stats()
    assert (st["bulk_calls"], st["bulk_events"]) == (2, 64)
    assert st["scalar_events"] == 32 and st["bound"] == 32
    assert st["slot_hits"] == 32

    def feed(fc):
        m = fc.m
        rows = np.flatnonzero(
            (m.p_status[:fc.Pn] == int(TaskStatus.Bound))
            & m.p_alive[:fc.Pn])
        fc._unbind_rows(rows[:8])

    store.cycle_feed = feed
    sched.run_once()
    store.flush_binds()
    assert stamps() == {"device:journey": {"rows": 8, "fresh": 0},
                        "commit:journey": {"rows": 8, "fresh": 0,
                                           "slot_hits": 0}}
    st = store.journey.stats()
    assert (st["bulk_calls"], st["bulk_events"]) == (2, 64)
    assert (st["rebinds"], st["reconsiders"]) == (8, 8)
    assert st["slot_hits"] == 32
    store.close()


# ------------------------------------- rows keep their slots (ISSUE 32)


def test_a_bound_batch_by_slots_looks_no_uid_up(monkeypatch):
    """20,000 first binds from the slots the ``dispatched`` stamp
    returned, with the uid -> slot dict refusing every lookup and every
    one-at-a-time routine patched to raise; only the ring's 1,024 uids
    are handed over."""
    n = 20_000
    jr = JourneyLog(capacity=1024, slo=SLOTracker(),
                    auditor=Auditor(enabled=False))
    jr._metrics = Metrics()
    uids = [f"pod-{i}" for i in range(n)]
    for i, u in enumerate(uids):
        jr.pod_event(u, "enqueued", status=ST_PENDING, queue=f"q{i % 3}",
                     gang=f"g{i // 8}")
    sl = jr.pod_rows(uids, "dispatched", solve_id=1)
    assert sl.dtype == np.int64 and len(sl) == n
    assert sl.tolist() == [jr._slot[u] for u in uids]

    class NoLookups(dict):
        def get(self, *a):
            raise AssertionError("uid looked up on the slot path")

        __getitem__ = get

    def boom(*a, **kw):
        raise AssertionError("per-event routine on the slot path")

    jr._slot = NoLookups(jr._slot)
    for routine in ("_apply", "_sync_status", "_new_pod", "_resolve"):
        monkeypatch.setattr(JourneyLog, routine, boom)
    jr.pod_slots(sl, uids[-1024:], "bound", solve_id=1)

    jr._slot = dict(jr._slot)
    st = jr.stats()
    assert st["slot_hits"] == n and st["bound"] == n
    assert (st["bulk_calls"], st["bulk_events"]) == (2, 2 * n)
    assert [r["uid"] for r in jr.trace_rows()] == uids[-1024:]
    assert {r["kind"] for r in jr.trace_rows()} == {"bound"}
    assert jr.conservation_check(uids) == []
    with pytest.raises(ValueError):
        jr.pod_slots(sl, uids, "removed")


def test_a_stamp_carries_the_instant_it_is_handed(monkeypatch):
    """``pod_rows(now=)``: the events, the first consideration and the
    latency carry the instant taken earlier, not the call's; an instant
    before the pod's last event marks it as a clock step would."""
    clock = Clock()
    monkeypatch.setattr(journey_mod, "time", clock)
    jr = JourneyLog(capacity=64)
    jr._metrics = Metrics()
    for u in ("a", "b"):
        jr.pod_event(u, "enqueued", status=ST_PENDING, queue="q")
    clock.t += 7_000_000
    entered = jr.now()
    assert entered == clock.t - jr._anchor_ns
    clock.t += 590_000_000          # the dispatch, and the stamp after it
    jr.pod_rows(["a"], "dispatched", now=entered)
    jr.pod_rows(["b"], "dispatched")
    a, b = jr.timeline("a"), jr.timeline("b")
    assert a["time_to_first_consider_ms"] == 7.0
    assert b["time_to_first_consider_ms"] == 597.0
    assert a["events"][-1]["ts_us"] == round(
        (jr._anchor_ns + entered) / 1e3, 1)
    assert b["events"][-1]["ts_us"] - a["events"][-1]["ts_us"] == 590_000.0
    assert a["monotone"] and b["monotone"]
    jr.pod_rows(["b"], "evicted", now=entered)     # before b's last event
    assert jr.timeline("b")["monotone"] is False


def _cycle_over(store):
    from volcano_tpu.fastpath import FastCycle
    from volcano_tpu.framework import (DEFAULT_SCHEDULER_CONF,
                                       parse_scheduler_conf)

    return FastCycle(store, parse_scheduler_conf(DEFAULT_SCHEDULER_CONF))


def _uid_path_rows(cyc, rows, kind, **kw):
    """``FastCycle._journey_rows`` as it was before ISSUE 32: first-time
    rows by the masks, their uids gathered, one ``pod_rows``."""
    jr = cyc.store.journey
    n = len(rows)
    _, considered, bound_seen, _ = cyc._journey_masks()
    mask = considered if kind == "dispatched" else bound_seen
    rows = rows[~mask[rows]]
    mask[rows] = True
    if n > len(rows):
        jr.repeat_rows(n - len(rows), kind)
    if len(rows):
        jr.pod_rows(map(cyc.m.p_uid.__getitem__, rows.tolist()), kind,
                    shard=cyc._journey_shard(), **kw)


def _seeded_store(monkeypatch, clock):
    """1,200 pods in gangs of 4 over two queues on a 1,024-event ring;
    four more pods enter while the journey is detached (adopted
    mid-life at their first stamp); one pod is deleted (its row a
    tombstone)."""
    import itertools

    import volcano_tpu.api.spec as spec
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from volcano_tpu.synth import synthetic_cluster

    monkeypatch.setattr(spec, "_uid_counter", itertools.count(1))
    monkeypatch.setattr(spec, "_ts_counter", itertools.count(1))
    monkeypatch.setenv("VOLCANO_TPU_JOURNEY_EVENTS", "1024")
    clock.t = 1_000_000_000
    store = synthetic_cluster(n_nodes=8, n_pods=1200, gang_size=4,
                              n_queues=2, seed=32)
    jr = store.journey
    assert jr.capacity == 1024
    store.journey = store.mirror.journey = None
    store.add_pod_group(PodGroup(name="late", min_member=4))
    for k in range(4):
        store.add_pod(Pod(name=f"late-{k}",
                          annotations={GROUP_NAME_ANNOTATION: "late"},
                          containers=[{"cpu": "1", "memory": "1Gi"}]))
    store.journey = store.mirror.journey = jr
    victim = store.pods[store.mirror.p_uid[17]]
    store.delete_pod(victim)
    return store, victim.uid


def _journey_facts(store, uids):
    jr = store.journey
    stats = jr.stats()
    for key in ("capture_ms", "slot_hits"):
        stats.pop(key)
    return {"timelines": {u: jr.timeline(u) for u in uids},
            "stats": stats, "rollup": jr.queue_rollup(),
            "ring": jr.trace_rows()}


def test_slot_path_and_uid_path_leave_the_same_journey(monkeypatch):
    """(a) One seeded batch through ``_journey_rows`` on one store and
    through the uid path on its twin, on one clock: a row twice, a
    tombstoned row, pods adopted mid-life, a row the ``dispatched``
    stamp never saw, a batch larger than the ring.  Field for field."""
    clock = Clock()
    monkeypatch.setattr(journey_mod, "time", clock)
    facts = []
    for slot_path in (True, False):
        store, dead_uid = _seeded_store(monkeypatch, clock)
        m = store.mirror
        total = len(m.p_uid)
        assert total == 1204 and m.p_uid[17] is None
        late = [r for r, u in enumerate(m.p_uid)
                if u and store.pods[u].name.startswith("late-")]
        assert len(late) == 4
        order = np.random.default_rng(32).permutation(total)
        unseen = int(order[100])
        assert unseen not in (5, 9, 17) and unseen not in late
        # Dispatched: every row but one, the tombstone in, row 5 twice.
        disp = np.concatenate([order[order != unseen], [5]])
        # Committed: most rows, in another order, and then row 9, the
        # tombstone and the row no stamp saw (once more, if drawn).
        bound = np.concatenate([
            np.random.default_rng(7).permutation(total)[:1150],
            late, [9, 17, unseen]])
        stamp = (_cycle_over(store)._journey_rows if slot_path
                 else lambda *a, **kw: _uid_path_rows(
                     _cycle_over(store), *a, **kw))
        with store._lock:
            clock.t += 3_000_000
            stamp(disp, "dispatched", solve_id=3)
            clock.t += 90_000_000
            args = stamp(bound, "bound", solve_id=3)
        uids = [u for u in m.p_uid if u] + [dead_uid, "never-seen"]
        facts.append(_journey_facts(store, uids))
        if slot_path:
            # Every live row but the unseen one came with its slot; the
            # tombstone has no event on either path.
            live = int(m.p_alive[bound].sum())
            assert live == len(bound) - int((bound == 17).sum())
            hits = live - int((bound == unseen).sum())
            assert args == {"rows": len(bound), "fresh": len(bound),
                            "slot_hits": hits}
            assert store.journey.stats()["slot_hits"] == hits
            # Adopted mid-life: synthetic roots, bound all the same.
            for r in late:
                assert facts[0]["timelines"][m.p_uid[r]][
                    "time_to_bind_ms"] == 90.0
        store.close()
    assert facts[0]["ring"] == facts[1]["ring"]
    assert len(facts[0]["ring"]) == 1024
    assert facts[0]["stats"] == facts[1]["stats"]
    assert facts[0]["rollup"] == facts[1]["rollup"]
    assert facts[0]["timelines"] == facts[1]["timelines"]
    assert facts[0]["timelines"]["never-seen"] is None
    # The tombstoned row was not adopted anew: its pod's last events
    # have left the ring and no state is left of it.
    assert facts[0]["timelines"][dead_uid] is None
    assert facts[0]["stats"]["bound"] > 1100


def test_a_reused_slot_is_never_bound_through_a_dead_rows_entry(monkeypatch):
    """(b) A pod deleted after its ``dispatched`` stamp frees its
    journey slot; the next pod takes that slot, and a new row.  The
    column still names the slot at the dead row: a ``bound`` stamp over
    that row stamps nobody, and the newcomer binds through its own row,
    by uid, as a pod the column does not know."""
    clock = Clock()
    monkeypatch.setattr(journey_mod, "time", clock)
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=32)
    jr, m = store.journey, store.mirror
    rows = np.arange(32)
    with store._lock:
        _cycle_over(store)._journey_rows(rows, "dispatched", solve_id=1)
        column = store._journey_masks[3]
    victim = store.pods[m.p_uid[11]]
    slot = jr._slot[victim.uid]
    assert column[11] == slot
    clock.t += 1_000_000
    store.delete_pod(victim)
    store.add_pod_group(PodGroup(name="next", min_member=1))
    newcomer = Pod(name="next-0", annotations={GROUP_NAME_ANNOTATION: "next"},
                   containers=[{"cpu": "1", "memory": "1Gi"}])
    store.add_pod(newcomer)
    row = m.p_row[newcomer.uid]
    assert jr._slot[newcomer.uid] == slot and row == 32
    assert not m.p_alive[11] and m.p_uid[11] is None
    with store._lock:
        column = _cycle_over(store)._journey_masks()[3]
        assert column[11] == slot and column[row] == -1  # stale, unknown
        clock.t += 50_000_000
        args = _cycle_over(store)._journey_rows(rows, "bound", solve_id=1)
    assert args == {"rows": 32, "fresh": 32, "slot_hits": 31}
    late = jr.timeline(newcomer.uid)
    assert late["time_to_bind_ms"] is None and late["status"] == ST_PENDING
    assert late["last_kind"] == "enqueued"
    assert [e["kind"] for e in late["events"]] == ["enqueued"]
    assert jr.stats()["bound"] == 31
    assert jr.why_pending(newcomer.uid) == "never considered (queue backlog)"
    with store._lock:
        clock.t += 2_000_000
        args = _cycle_over(store)._journey_rows(
            np.asarray([row, 11]), "bound", solve_id=2)
    # Row 11 was seen bound above, so only the newcomer is first-time.
    assert args == {"rows": 2, "fresh": 1, "slot_hits": 0}
    late = jr.timeline(newcomer.uid)
    assert late["time_to_bind_ms"] is not None and late["status"] == ST_BOUND
    assert jr.stats()["bound"] == 32 and jr.stats()["slot_hits"] == 31
    store.close()


def test_another_journey_on_the_store_drops_the_column():
    """The slots are the attached journey's own: a store handed another
    ``JourneyLog`` starts the column (and the masks) afresh."""
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=33)
    rows = np.arange(32)
    with store._lock:
        _cycle_over(store)._journey_rows(rows, "dispatched")
        assert (store._journey_masks[3] >= 0).all()
    other = JourneyLog(capacity=64)
    store.journey = store.mirror.journey = other
    with store._lock:
        mk = _cycle_over(store)._journey_masks()
        assert (mk[3] == -1).all() and not mk[1].any()
        args = _cycle_over(store)._journey_rows(rows, "bound")
    assert args == {"rows": 32, "fresh": 32, "slot_hits": 0}
    assert other.stats()["bound"] == 32 and other.stats()["pods"] == 32
    store.close()


def test_a_compaction_between_two_cycles_drops_the_column():
    """(c) The column lives and dies with the first-time masks: a
    compaction renumbers the rows, the next cycle starts both afresh
    and its own ``dispatched`` stamp fills the column for its rows."""
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=34)
    sched, m, jr = Scheduler(store), store.mirror, store.journey
    sched.run_once()
    store.flush_binds()
    key, _, bound_seen, column = store._journey_masks
    assert key == (m.compact_gen, jr) and len(column) == 32
    assert bound_seen.all() and (column >= 0).all()
    assert column.tolist() == [jr._slot[u] for u in m.p_uid]

    gen = m.compact_gen
    store.add_pod_group(PodGroup(name="churn", min_member=1))
    for k in range(4400):
        pod = Pod(name=f"churn-{k}",
                  annotations={GROUP_NAME_ANNOTATION: "churn"},
                  containers=[{"cpu": "1", "memory": "1Gi"}])
        store.add_pod(pod)
        store.delete_pod(pod)
    assert m.compact_gen > gen and len(m.p_uid) < 4096
    store.add_pod_group(PodGroup(name="late", min_member=4))
    for k in range(4):
        store.add_pod(Pod(name=f"late-{k}",
                          annotations={GROUP_NAME_ANNOTATION: "late"},
                          containers=[{"cpu": "1", "memory": "1Gi"}]))
    sched.run_once()
    store.flush_binds()
    rec = store.flight.recent()[-1]
    stamps = {s.name: s.args for s in rec.spans
              if s.name in ("device:journey", "commit:journey")}
    assert stamps == {"device:journey": {"rows": 4, "fresh": 4},
                      "commit:journey": {"rows": 4, "fresh": 4,
                                         "slot_hits": 4}}
    key, considered, bound_seen, column = store._journey_masks
    assert key == (m.compact_gen, jr)
    assert len(column) == len(considered) == len(m.p_uid)
    known = np.flatnonzero(column >= 0)
    assert sorted(store.pods[m.p_uid[r]].name for r in known) == [
        f"late-{k}" for k in range(4)]
    assert column[known].tolist() == [jr._slot[m.p_uid[r]] for r in known]
    assert considered.sum() == bound_seen.sum() == 4
    store.close()


def test_dispatched_events_carry_the_instant_before_the_dispatch(
        monkeypatch):
    """(d) The stamp runs after the programs are enqueued; its events
    carry the instant the rows entered the solve, taken before."""
    import time

    from volcano_tpu.fastpath import FastCycle
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=8, n_pods=32, gang_size=4, seed=35)
    jr = store.journey
    seen = {}
    solve_sync = FastCycle._solve_sync

    def slow_dispatch(self, *a, **kw):
        seen["before"] = jr.now()
        time.sleep(0.05)
        seen["stamped"] = jr.stats()["bulk_events"]
        try:
            return solve_sync(self, *a, **kw)
        finally:
            seen["after"] = jr.now()

    monkeypatch.setattr(FastCycle, "_solve_sync", slow_dispatch)
    Scheduler(store).run_once()
    store.flush_binds()
    assert seen["stamped"] == 0 and seen["after"] - seen["before"] >= 50e6
    events = [r for r in jr.trace_rows() if r["kind"] == "dispatched"]
    assert len(events) == 32 and len({r["ts_us"] for r in events}) == 1
    at_us = events[0]["ts_us"]
    assert at_us <= round((jr._anchor_ns + seen["before"]) / 1e3, 1)
    rec = store.flight.recent()[-1]
    spans = {s.name: s for s in rec.spans}
    stamp, dispatch = spans["device:journey"], spans["device:dispatch"]
    assert dispatch.dur_ns >= 50e6
    assert stamp.ts_ns >= dispatch.ts_ns + dispatch.dur_ns
    # On the tracer's clock the events lie before the dispatch span ends
    # by at least the dispatch, though the stamp began after it.
    assert at_us * 1e3 <= stamp.ts_ns - 50e6 + 1e6
    for uid in list(store.pods)[:4]:
        t = jr.timeline(uid)
        assert t["monotone"] is True
        assert [e["kind"] for e in t["events"]] == [
            "enqueued", "dispatched", "bound"]
    store.close()
