"""Pod-journey tracing: per-pod scheduling timelines (ISSUE 18).

Every observability layer so far is cycle-centric — lane spans, flight
records, conservation flows, SLO windows — but none answers the
question a batch-system user actually asks: *where did my pod's time
go, and why is it still pending?*  With the sharded control plane a
single pod's life spans shards (considered on shard A, voided by a
cross-shard conflict, re-placed by shard B), so the signal cannot be
reconstructed from any one recorder.  ``JourneyLog`` is the pod-centric
plane: a bounded columnar event ring plus a per-pod summary, captured
at every sanctioned mirror/fast-path writer (the writer-discipline lint
VCL706 guarantees no writer bypasses it).

Event vocabulary (docs/observability.md):

- ``enqueued``           pod row created in the mirror (store edge)
- ``status-sync``        external status overwrite (update / resync)
- ``dispatched``         first entered a device solve (solve_id, shard)
- ``dropped``            staleness-guard drop, one exclusive reason
                         (``cross-shard-conflict`` carries the losing
                         shard and the ownership handoff epoch)
- ``bound``              commit/backfill landed the placement
- ``unbound``            bind-failure resync or steady-state re-pend
- ``evicted`` / ``evict-reverted``  fastpath_evict state transitions
- ``migration-planned``  what-if plan committed this pod as a victim
- ``restored``           migration ledger re-added it under a new uid
- ``removed``            pod row tombstoned (store edge)

Cost discipline: a batch of rows (``pod_rows``: the fast path's
``dispatched`` / ``bound`` / ``dropped`` seams) is stamped with a fixed
number of Python operations whatever its size — one uid -> slot pass,
scatters over the per-pod columns, a ``bincount`` for each histogram
and for the gangs, slice writes into the ring.  The pass is made once
per solve: ``pod_rows`` returns the slots it resolved, the fast cycle
keeps them by mirror row, and the ``bound`` stamp of the same rows
(``pod_slots``) starts from them (ISSUE 32).  In a burst every row
is first-time, so the batch is the whole backlog (100,000 rows twice a
cycle at the north star): on the chip's host such a stamp takes 47-49
ms, where one pod at a time it took 310 (``dispatched``) and 648
(``bound``) (PERF.md section 6, PR 26).  The steady-state feed (re-pend
+ re-bind of the SAME rows every cycle) never reaches the log: the
caller's row masks (``fastpath.FastCycle._journey_masks``) fold it into
bulk counters.  The store edge (``enqueued`` / ``status-sync`` /
``removed``) is one ``pod_event`` per pod, writing the same columns.

Latency feeds: first-dispatch observes time-to-first-consider, first
bind observes time-to-bind (per queue) and the gang's
time-to-full-bind once every member seen is bound; time-to-bind also
feeds the ``ttb`` SLO lane (``VOLCANO_TPU_SLO_TTB_P99_MS``) whose
burn-rate breaches surface as ``slo-budget-exceeded`` anomalies.

Conservation: ``conservation_check(bound_uids)`` proves every pod
bound at the end of a fault schedule has a complete, orphan-free
journey — a state rooted at ``enqueued`` (``journey-orphan``
otherwise) with a recorded bind and monotone event order across shard
handoffs (``journey-incomplete`` otherwise).  A/B harnesses that ran
with the journey detached re-adopt via ``pod_resync`` (synthetic
roots, explicitly tolerated).

``array`` columns viewed through numpy by a batch, one small lock;
kill switch ``VOLCANO_TPU_JOURNEY=0`` leaves the store with
``journey = None`` so hot paths pay one attribute load.
"""

from __future__ import annotations

import os
import threading
import time
from array import array
from collections import deque
from itertools import groupby, repeat
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from .audit import Anomaly

DEFAULT_EVENTS = 65536

# TaskStatus bit-flags that mean "this pod holds (or held) a placement"
# (api/types.py): Allocated | Binding | Bound | Running | Succeeded.
_BOUND_MASK = (1 << 1) | (1 << 3) | (1 << 4) | (1 << 5) | (1 << 7)
_ST_BOUND = 1 << 4  # TaskStatus.Bound

KINDS = (
    "enqueued", "status-sync", "dispatched", "dropped", "bound",
    "unbound", "evicted", "evict-reverted", "migration-planned",
    "restored", "removed",
)
_KIND_CODE = {k: i for i, k in enumerate(KINDS)}
# Kinds that carry a per-pod payload (status, queue, gang): the store
# edge's, one ``pod_event`` each; every other kind can come as a batch.
_EDGE_KINDS = ("enqueued", "status-sync", "removed")

# Per-pod drop-chain depth (why-pending evidence window).
_DROP_CHAIN = 8
# Latency-percentile sample windows.
_TTB_WINDOW = 4096
_GANG_WINDOW = 1024
_QUEUE_WINDOW = 256
# Per-kind metric counts fold into the registry counter in batches of
# this many events (read paths flush too, so totals stay fresh).
_FLUSH_EVERY = 256

# Per-pod columns, indexed by journey slot: ``array``s, so that a single
# event writes them at list speed, viewed through numpy by a batch.
# ``first`` / ``bound`` are _UNSET until the pod's first dispatch / bind;
# ``queue`` indexes ``_queue_names``; ``gang`` is a gang slot or -1;
# ``mark`` is a batch's scratch.
_POD_COLS = (("_p_enq", "q"), ("_p_first", "q"), ("_p_bound", "q"),
             ("_p_last", "q"), ("_p_kind", "b"), ("_p_status", "q"),
             ("_p_solve", "q"), ("_p_shard", "i"), ("_p_flags", "b"),
             ("_p_queue", "i"), ("_p_gang", "i"), ("_p_mark", "q"))
_GANG_COLS = (("_g_first_enq", "q"), ("_g_members", "i"),
              ("_g_bound", "i"), ("_g_alive", "i"), ("_g_done", "b"))
# The event ring's numeric columns (uid and detail are lists beside them).
_EV_COLS = (("_ev_kind", "b"), ("_ev_shard", "i"), ("_ev_solve", "q"),
            ("_ev_epoch", "q"), ("_ev_ts", "q"))
_UNSET = -(1 << 63)  # no timestamp: the wall clock may step below 0
# ``_p_flags`` bits.  _SYNTHETIC: adopted without an ``enqueued`` (the
# journey was detached when the pod entered): conservation treats the
# root as complete, the ttb SLO lane skips it, it belongs to no gang.
_NONMONO = 1
_SYNTHETIC = 2


def journey_on() -> bool:
    return os.environ.get("VOLCANO_TPU_JOURNEY", "1") != "0"


def ring_capacity() -> int:
    try:
        return max(int(os.environ.get("VOLCANO_TPU_JOURNEY_EVENTS",
                                      DEFAULT_EVENTS)), 1024)
    except ValueError:
        return DEFAULT_EVENTS


def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    vals = sorted(vals)
    i = min(int(q * (len(vals) - 1) + 0.5), len(vals) - 1)
    return round(vals[i], 3)


class JourneyLog:
    """Bounded columnar per-pod event timeline + per-pod summaries.

    Writers call under the store lock (mirror writers / fast path); readers are the /debug HTTP threads.  All
    shared state is guarded by the journey's own ``_lock`` — never
    taken around store state, so a /debug/pods scrape cannot block the
    cycle thread on store work.
    """

    def __init__(self, capacity: Optional[int] = None, slo=None,
                 auditor=None):
        cap = ring_capacity() if capacity is None else max(int(capacity), 8)
        self._cap = cap
        self._lock = threading.Lock()
        # Wall anchor (obs/trace.py idiom): perf_counter deltas stay
        # monotone; adding the anchor aligns exported timestamps with
        # the tracer's span clock.
        self._anchor_ns = time.time_ns() - time.perf_counter_ns()
        # Columnar ring, overwrite-oldest.  guarded-by: _lock
        self._ev_uid: List[Optional[str]] = [None] * cap
        self._ev_detail: List[Optional[str]] = [None] * cap
        for name, code in _EV_COLS:
            setattr(self, name, array(code, [0]) * cap)
        self._head = 0  # next write slot; guarded-by: _lock
        self._count = 0  # events ever written; guarded-by: _lock
        # Summaries: one store of truth, the columns of _POD_COLS /
        # _GANG_COLS by slot (doubling; a ``removed`` pod's slot and a
        # dead gang's are reused).  guarded-by: _lock
        self._slot: Dict[str, int] = {}
        self._free: List[int] = []
        self._hi = 0  # pod slots ever handed out
        self._gang_slot: Dict[str, int] = {}
        self._gang_names: List[str] = []
        self._gang_free: List[int] = []
        for name, code in _POD_COLS + _GANG_COLS:
            setattr(self, name, array(code, [0]) * 64)
        self._queue_id: Dict[str, int] = {"": 0}
        self._queue_names: List[str] = [""]
        # The rare per-pod payloads, sparse by slot: the recent
        # (reason, shard) drop attributions, newest last, made on a
        # pod's first drop; the evicted uid a restored pod links to.
        self._drops: Dict[int, deque] = {}
        self._restored_from: Dict[int, str] = {}
        # Counters.  guarded-by: _lock
        self.events_total = 0
        self.rebinds = 0  # steady-state re-pend loop, counted in bulk
        self.reconsiders = 0
        self.unbinds_bulk = 0
        self.bound_total = 0
        # How the events came: ``pod_rows`` batches and their events,
        # against events stamped one at a time.
        self.bulk_calls = 0
        self.bulk_events = 0
        self.scalar_events = 0
        # Events of ``pod_slots`` batches that came with their slot.
        self.slot_hits = 0
        # Per-kind event counts on their way to the registry counter,
        # folded every _FLUSH_EVERY events: inc() takes the registry-wide
        # metrics lock and builds a sorted tuple.  guarded-by: _lock
        self._kind_counts: Dict[str, int] = {}
        self._unflushed = 0
        self._metrics = None  # lazy ..metrics handle (import cycle)
        # Self-timed capture cost (the in-process truth, audit_stats
        # idiom): nanoseconds spent inside the capture entry points,
        # two perf_counter reads per CALL (not per event).
        self.capture_ns = 0
        # Latency sample windows for stats() / the queue rollup.
        self._ttb_ms: deque = deque(maxlen=_TTB_WINDOW)
        self._ttfc_ms: deque = deque(maxlen=_TTB_WINDOW)
        self._gang_ttfb_ms: deque = deque(maxlen=_GANG_WINDOW)
        self._queue_ttb: Dict[str, deque] = {}
        self._queue_counts: Dict[str, Dict[str, int]] = {}
        # SLO feed (ttb lane) + breach intake (auditor.report).
        self.slo = slo
        self.auditor = auditor

    # ------------------------------------------------------------ capture

    def pod_event(self, uid: Optional[str], kind: str, *,
                  status: int = -1, queue: str = "", gang: str = "",
                  shard: int = -1, solve_id: int = 0, epoch: int = -1,
                  detail: str = "") -> None:
        """Record one event for one pod (writers hold the store lock)."""
        if not uid:
            return
        t0 = time.perf_counter_ns()
        now = time.time_ns() - self._anchor_ns
        with self._lock:
            self._apply(uid, kind, now, status, queue, gang, shard,
                        solve_id, epoch, detail)
            self.capture_ns += time.perf_counter_ns() - t0

    @property
    def capacity(self) -> int:
        """Events the ring holds."""
        return self._cap

    def now(self) -> int:
        """The instant a stamp taken now would carry, for a caller that
        stamps later (``pod_rows(..., now=)``)."""
        return time.time_ns() - self._anchor_ns

    def pod_rows(self, uids: Iterable[Optional[str]], kind: str, *,
                 shard: int = -1, solve_id: int = 0, epoch: int = -1,
                 detail: str = "", now: Optional[int] = None
                 ) -> "np.ndarray":
        """Stamp one event on every pod of a batch (the fast path's
        vectorized writers): one timestamp, one lock acquisition and a
        fixed number of Python operations whatever the batch's size —
        the same facts ``pod_event`` called once per uid would leave.
        ``now`` is the instant the events carry, when that is earlier
        than the call (``self.now()`` then).  Returns the pods' slots,
        one for each uid that is not None or empty, in batch order: a
        caller that keeps them hands them to ``pod_slots`` and the
        next stamp skips the uid -> slot pass."""
        if kind in _EDGE_KINDS:
            raise ValueError(f"{kind!r} carries a per-pod payload: "
                             "pod_event")
        t0 = time.perf_counter_ns()
        if now is None:
            now = time.time_ns() - self._anchor_ns
        uids = list(filter(None, uids))
        with self._lock:
            sl = self._resolve(uids, now)
            if uids:
                self._stamp(sl, uids, kind, now, shard, solve_id, epoch,
                            detail)
            self.capture_ns += time.perf_counter_ns() - t0
        return sl

    def pod_slots(self, slots: "np.ndarray", tail_uids: List[str],
                  kind: str, *, miss_uids: Iterable[str] = (),
                  shard: int = -1, solve_id: int = 0, epoch: int = -1,
                  detail: str = "") -> None:
        """``pod_rows`` for a batch whose slots the caller kept from an
        earlier stamp's return: no uid of the batch is looked up.
        ``slots[i] < 0`` is a pod whose slot the caller does not know;
        ``miss_uids`` are the uids of those, in batch order.  The event
        ring holds uids, of a batch's last ``capacity`` events at most:
        ``tail_uids`` ends with them (the last ``min(len(slots),
        capacity)`` uids of the batch; more in front does no harm).
        The caller answers for each slot being its pod's own: a slot is
        reused once its pod is ``removed``."""
        if kind in _EDGE_KINDS:
            raise ValueError(f"{kind!r} carries a per-pod payload: "
                             "pod_event")
        t0 = time.perf_counter_ns()
        now = time.time_ns() - self._anchor_ns
        with self._lock:
            miss = np.flatnonzero(slots < 0)
            if len(miss):
                slots = slots.copy()
                slots[miss] = self._resolve(list(miss_uids), now)
            if len(slots):
                self.slot_hits += len(slots) - len(miss)
                self._stamp(slots, tail_uids, kind, now, shard, solve_id,
                            epoch, detail)
            self.capture_ns += time.perf_counter_ns() - t0

    def repeat_rows(self, n: int, kind: str) -> None:
        """Steady-state bulk accounting: the feed re-pends and re-binds
        the SAME rows every cycle; their journeys are already complete,
        so only counters move."""
        if n <= 0:
            return
        t0 = time.perf_counter_ns()
        with self._lock:
            if kind == "bound":
                self.rebinds += n
            elif kind == "dispatched":
                self.reconsiders += n
            else:
                self.unbinds_bulk += n
            self.capture_ns += time.perf_counter_ns() - t0

    def pod_resync(self, pairs: Iterable[Tuple[Optional[str], int]]
                   ) -> None:
        """Bulk status adoption (mirror.resync_status, or a harness
        re-attaching a detached journey): missing pods get synthetic
        roots; pods whose status says placed get a state-sync bind so
        the conservation invariant holds across the blind window."""
        t0 = time.perf_counter_ns()
        now = time.time_ns() - self._anchor_ns
        with self._lock:
            for uid, status in pairs:
                if not uid:
                    continue
                s = self._slot.get(uid)
                if s is None:
                    s = self._new_pod(uid, now, "", -1, _SYNTHETIC)
                self._sync_status(s, int(status), now)
            self.capture_ns += time.perf_counter_ns() - t0

    def pod_restored(self, old_uid: str, new_uid: str) -> None:
        """Migration-ledger stitch: the restored pod's fresh journey
        links back to the evicted victim's uid."""
        now = time.time_ns() - self._anchor_ns
        with self._lock:
            s = self._slot.get(new_uid)
            if s is not None:
                self._restored_from[s] = old_uid
            self._apply(new_uid, "restored", now, -1, "", "", -1, 0,
                        -1, old_uid)

    # -------------------------------------------------- pod and gang slots

    def _grow(self, cols) -> None:
        # A new array each time, never a resize: a numpy view a batch
        # still holds keeps its (old) buffer.
        for name, _ in cols:
            setattr(self, name, getattr(self, name) * 2)

    def _new_pod(self, uid: str, now: int, queue: str, gang: int,
                 flags: int) -> int:
        if self._free:
            s = self._free.pop()
        else:
            s = self._hi
            if s == len(self._p_enq):
                self._grow(_POD_COLS)
            self._hi = s + 1
        q = self._queue_id.get(queue)
        if q is None:
            q = self._queue_id[queue] = len(self._queue_names)
            self._queue_names.append(queue)
        self._slot[uid] = s
        self._p_enq[s] = self._p_last[s] = now
        self._p_first[s] = self._p_bound[s] = _UNSET
        self._p_shard[s] = -1
        self._p_kind[s] = self._p_solve[s] = 0
        self._p_status[s] = 1  # TaskStatus.Pending
        self._p_flags[s] = flags
        self._p_queue[s] = q
        self._p_gang[s] = gang
        return s

    def _join_gang(self, gang: str, now: int) -> int:
        g = self._gang_slot.get(gang)
        if g is None:
            if self._gang_free:
                g = self._gang_free.pop()
                self._gang_names[g] = gang
            else:
                g = len(self._gang_names)
                if g == len(self._g_alive):
                    self._grow(_GANG_COLS)
                self._gang_names.append(gang)
            self._gang_slot[gang] = g
            self._g_first_enq[g] = now
            self._g_members[g] = self._g_bound[g] = 0
            self._g_alive[g] = self._g_done[g] = 0
        self._g_members[g] += 1
        self._g_alive[g] += 1
        return g

    def _registry(self):
        if self._metrics is None:
            from ..metrics import metrics

            self._metrics = metrics
        return self._metrics

    def _report(self, breaches: List[dict]) -> None:
        if self.auditor is not None:
            for breach in breaches:
                self.auditor.report(Anomaly("slo-budget-exceeded", breach))

    # ---------------------------------------------- one event (locked)

    def _apply(self, uid: str, kind: str, now: int, status: int,
               queue: str, gang: str, shard: int, solve_id: int,
               epoch: int, detail: str) -> None:
        s = self._slot.get(uid)
        if kind == "enqueued":
            if s is None:
                s = self._new_pod(
                    uid, now, queue,
                    self._join_gang(gang, now) if gang else -1, 0)
                qc = self._queue_counts.setdefault(
                    queue, {"enqueued": 0, "bound": 0})
                qc["enqueued"] += 1
            if status >= 0:
                self._sync_status(s, status, now)
        elif s is None:
            # Event for a pod the journey never saw enqueue (adopted
            # mid-life, e.g. re-attach after an A/B window): synthesize
            # the root so the timeline stays rooted.
            s = self._new_pod(uid, now, queue, -1, _SYNTHETIC)
        if now < self._p_last[s]:
            self._p_flags[s] |= _NONMONO
        self._p_last[s] = now
        self._p_kind[s] = code = _KIND_CODE.get(kind, 0)
        if kind == "dispatched":
            self._p_solve[s] = solve_id
            self._p_shard[s] = shard
            if self._p_first[s] == _UNSET:
                self._p_first[s] = now
                ms = (now - self._p_enq[s]) / 1e6
                self._ttfc_ms.append(ms)
                self._registry().pod_time_to_first_consider.observe(
                    ms, queue=self._queue_names[self._p_queue[s]] or "none")
        elif kind == "dropped":
            self._drop_chain(s).append((detail, shard))
        elif kind == "bound":
            self._sync_status(s, _ST_BOUND, now)
        elif kind == "status-sync":
            if status >= 0:
                self._sync_status(s, status, now)
        elif kind == "removed":
            del self._slot[uid]
            self._free.append(s)
            self._drops.pop(s, None)
            self._restored_from.pop(s, None)
            g = self._p_gang[s]
            if g >= 0:
                self._g_alive[g] -= 1
                if self._g_alive[g] <= 0:
                    del self._gang_slot[self._gang_names[g]]
                    self._gang_free.append(g)
        # Ring append (columnar, overwrite-oldest).
        i = self._head
        self._ev_uid[i] = uid
        self._ev_detail[i] = detail or None
        self._ev_kind[i] = code
        self._ev_shard[i] = shard
        self._ev_solve[i] = solve_id
        self._ev_epoch[i] = epoch
        self._ev_ts[i] = now
        self._head = (i + 1) % self._cap
        self.scalar_events += 1
        self._count_events(kind, 1)

    def _drop_chain(self, s: int) -> deque:
        d = self._drops.get(s)
        if d is None:
            d = self._drops[s] = deque(maxlen=_DROP_CHAIN)
        return d

    def _count_events(self, kind: str, n: int) -> None:
        self._count += n
        self.events_total += n
        kc = self._kind_counts
        kc[kind] = kc.get(kind, 0) + n
        self._unflushed += n
        if self._unflushed >= _FLUSH_EVERY:
            self._flush_kind_counts()

    def _flush_kind_counts(self) -> None:
        """Fold the batched per-kind counts into the registry counter
        (caller holds ``_lock``); also runs on every read path so a
        scrape after a quiet spell sees fresh totals."""
        if not self._kind_counts:
            return
        inc = self._registry().journey_events.inc
        for kind, n in self._kind_counts.items():
            inc(n, kind=kind)
        self._kind_counts.clear()
        self._unflushed = 0

    def _sync_status(self, s: int, status: int, now: int) -> None:
        """The pod's status, and its first bind if the status says it
        holds a placement."""
        self._p_status[s] = status
        if not (status & _BOUND_MASK) or self._p_bound[s] != _UNSET:
            return
        self._p_bound[s] = now
        self.bound_total += 1
        ms = (now - self._p_enq[s]) / 1e6
        self._ttb_ms.append(ms)
        q = self._queue_names[self._p_queue[s]] or "none"
        self._queue_bound(q, 1).append(ms)
        self._registry().pod_time_to_bind.observe(ms, queue=q)
        if self.slo is not None and not self._p_flags[s] & _SYNTHETIC:
            self._report(self.slo.observe_sample("ttb", ms))
        g = self._p_gang[s]
        if g >= 0:
            self._g_bound[g] += 1
            if not self._g_done[g] and 0 < self._g_members[g] \
                    <= self._g_bound[g]:
                self._g_done[g] = 1
                gms = (now - self._g_first_enq[g]) / 1e6
                self._gang_ttfb_ms.append(gms)
                self._registry().gang_time_to_full_bind.observe(gms)

    def _queue_bound(self, q: str, n: int) -> deque:
        """Count ``n`` first binds for queue ``q``; its ttb window."""
        qc = self._queue_counts.setdefault(q, {"enqueued": 0, "bound": 0})
        qc["bound"] += n
        return self._queue_ttb.setdefault(q, deque(maxlen=_QUEUE_WINDOW))

    # ------------------------------------------------ a batch (locked)

    def _view(self, name: str) -> "np.ndarray":
        col = getattr(self, name)
        return np.frombuffer(col, col.typecode)

    def _resolve(self, uids: List[str], now: int) -> "np.ndarray":
        """The slots of ``uids``, one dict lookup each; a pod the
        journey never saw enqueue (adopted mid-life) gets its synthetic
        root here, at its first occurrence."""
        n = len(uids)
        sl = np.fromiter(map(self._slot.get, uids, repeat(-1)),
                         np.int64, n)
        for i in np.flatnonzero(sl < 0).tolist():  # adopted mid-life
            s = self._slot.get(uids[i])
            sl[i] = (self._new_pod(uids[i], now, "", -1, _SYNTHETIC)
                     if s is None else s)
        return sl

    def _stamp(self, sl: "np.ndarray", tail_uids: List[str], kind: str,
               now: int, shard: int, solve_id: int, epoch: int,
               detail: str) -> None:
        """``_apply`` over a batch of slots, as array work.  Every pod
        of the batch shares ``now``, so a slot that comes twice is
        first-time once, at its first occurrence.  ``tail_uids`` ends
        with the uids of the batch's last ``min(n, cap)`` events."""
        n = len(sl)
        last = self._view("_p_last")
        stale = last[sl] > now
        if stale.any():
            self._view("_p_flags")[sl[stale]] |= _NONMONO
        last[sl] = now
        self._view("_p_kind")[sl] = code = _KIND_CODE.get(kind, 0)
        if kind == "dispatched":
            self._view("_p_solve")[sl] = solve_id
            self._view("_p_shard")[sl] = shard
            fs = self._first_time(sl, "_p_first", now)
            if len(fs):
                ms = (now - self._view("_p_enq")[fs]) / 1e6
                self._ttfc_ms.extend(ms[-_TTB_WINDOW:].tolist())
                hist = self._registry().pod_time_to_first_consider
                for q, vals in self._by_queue(fs, ms):
                    hist.observe_many(vals, queue=q)
        elif kind == "dropped":
            drop = (detail, shard)
            for s in sl.tolist():  # churn-sized
                self._drop_chain(s).append(drop)
        elif kind == "bound":
            self._view("_p_status")[sl] = _ST_BOUND
            fs = self._first_time(sl, "_p_bound", now)
            if len(fs):
                self._first_binds(fs, now)
        # Ring: slice writes; of a batch larger than the ring only the
        # last ``cap`` events land, as overwrite-oldest leaves them.
        cap = self._cap
        k = min(n, cap)
        h = (self._head + n - k) % cap
        cut = min(k, cap - h)
        t = len(tail_uids) - k  # where the batch's last k uids begin
        for a, b, lo in ((h, h + cut, t), (0, k - cut, t + cut)):
            if b > a:
                self._ev_uid[a:b] = tail_uids[lo:lo + b - a]
                self._ev_detail[a:b] = repeat(detail or None, b - a)
                for (name, _), v in zip(_EV_COLS, (code, shard, solve_id,
                                                   epoch, now)):
                    self._view(name)[a:b] = v
        self._head = (h + k) % cap
        self.bulk_calls += 1
        self.bulk_events += n
        self._count_events(kind, n)

    def _first_time(self, sl, name: str, now: int):
        """Stamp ``now`` into column ``name`` where it is unset; the
        slots it was unset for, in batch order."""
        col = self._view(name)
        fs = sl[col[sl] == _UNSET]
        # A slot that comes twice reads back, at one of its positions,
        # the other's mark: keep its first occurrence.
        at, mark = np.arange(len(fs)), self._view("_p_mark")
        mark[fs] = at
        if (mark[fs] != at).any():
            fs = fs[np.sort(np.unique(fs, return_index=True)[1])]
        col[fs] = now
        return fs

    def _by_queue(self, fs, ms):
        """``ms`` split by the queue label of its pod (slots ``fs``),
        batch order kept within a queue."""
        qid = self._view("_p_queue")[fs]
        return [(self._queue_names[q] or "none", ms[qid == q])
                for q in np.flatnonzero(np.bincount(qid)).tolist()]

    def _first_binds(self, fs, now: int) -> None:
        """``_sync_status``'s first-bind leg for the slots ``fs``."""
        self.bound_total += len(fs)
        ms = (now - self._view("_p_enq")[fs]) / 1e6
        self._ttb_ms.extend(ms[-_TTB_WINDOW:].tolist())
        hist = self._registry().pod_time_to_bind
        for q, vals in self._by_queue(fs, ms):
            self._queue_bound(q, len(vals)).extend(
                vals[-_QUEUE_WINDOW:].tolist())
            hist.observe_many(vals, queue=q)
        if self.slo is not None:
            real = (self._view("_p_flags")[fs] & _SYNTHETIC) == 0
            self._report(self.slo.observe_samples("ttb", ms[real]))
        gs = self._view("_p_gang")[fs]
        gs = gs[gs >= 0]
        if not len(gs):
            return
        # Gangs that complete inside the batch, in the order their
        # completing binds come: a gang with ``need`` binds to go is
        # done at its need-th pod of the batch.
        cnt = np.bincount(gs)
        hit = np.flatnonzero(cnt)
        cnt = cnt[hit]
        bound = self._view("_g_bound")
        members = self._view("_g_members")[hit]
        need = np.maximum(members - bound[hit], 1)
        bound[hit] += cnt.astype(np.int32)
        done = self._view("_g_done")
        new = (done[hit] == 0) & (members > 0) & (cnt >= need)
        if not new.any():
            return
        order = np.argsort(gs, kind="stable")
        at = order[(np.cumsum(cnt) - cnt + need - 1)[new]]
        g = hit[new][np.argsort(at, kind="stable")]
        done[g] = 1
        gms = ((now - self._view("_g_first_enq")[g]) / 1e6).tolist()
        self._gang_ttfb_ms.extend(gms[-_GANG_WINDOW:])
        self._registry().gang_time_to_full_bind.observe_many(gms)

    # -------------------------------------------------------------- reads

    def _ring_indices(self) -> List[int]:
        if self._count < self._cap:
            return list(range(self._head))
        return list(range(self._head, self._cap)) + \
            list(range(self._head))

    def _row(self, i: int) -> dict:
        row = {
            "uid": self._ev_uid[i],
            "kind": KINDS[self._ev_kind[i]],
            "ts_us": round((self._anchor_ns + self._ev_ts[i]) / 1e3, 1),
        }
        if self._ev_shard[i] >= 0:
            row["shard"] = self._ev_shard[i]
        if self._ev_solve[i]:
            row["solve_id"] = self._ev_solve[i]
        if self._ev_epoch[i] >= 0:
            row["handoff_epoch"] = self._ev_epoch[i]
        if self._ev_detail[i]:
            row["detail"] = self._ev_detail[i]
        return row

    def trace_rows(self) -> List[dict]:
        """Chronological ring dump for the Perfetto exporter."""
        with self._lock:
            return [self._row(i) for i in self._ring_indices()]

    def timeline(self, uid: str) -> Optional[dict]:
        """The /debug/pods/<uid> body: stitched cross-shard event list
        (oldest first) + summary + why-pending verdict.  Returns None
        for a pod the journey never saw."""
        with self._lock:
            s = self._slot.get(uid)
            events = [self._row(i) for i in self._ring_indices()
                      if self._ev_uid[i] == uid]
            if s is None and not events:
                return None
            body = {"uid": uid, "events": events}
            if s is not None:
                g = self._p_gang[s]
                enq, first, bound = (self._p_enq[s], self._p_first[s],
                                     self._p_bound[s])
                body.update({
                    "queue": self._queue_names[self._p_queue[s]],
                    "gang": self._gang_names[g] if g >= 0 else "",
                    "status": self._p_status[s],
                    "enqueued_us": round((self._anchor_ns + enq) / 1e3, 1),
                    "time_to_first_consider_ms": (
                        round((first - enq) / 1e6, 3)
                        if first != _UNSET else None),
                    "time_to_bind_ms": (
                        round((bound - enq) / 1e6, 3)
                        if bound != _UNSET else None),
                    "last_kind": KINDS[self._p_kind[s]],
                    "monotone": not self._p_flags[s] & _NONMONO,
                    "restored_from": self._restored_from.get(s),
                    "why_pending": self._verdict(s),
                })
            else:
                body["why_pending"] = "removed (events only)"
            return body

    def why_pending(self, uid: str) -> str:
        with self._lock:
            s = self._slot.get(uid)
            if s is None:
                return "unknown (no journey state)"
            return self._verdict(s)

    def _verdict(self, s: int) -> str:
        """Compress the recent drop-reason chain into one operator
        sentence, e.g. ``capacity-taken x4 on shard 1,
        cross-shard-conflict on shard 0``."""
        if self._p_status[s] & _BOUND_MASK:
            return "bound"
        last_kind = KINDS[self._p_kind[s]]
        if last_kind in ("evicted", "migration-planned"):
            return f"{last_kind} (awaiting restore)"
        # Drop evidence wins over the never-dispatched check: a pregate
        # hold (e.g. topology-infeasible) drops the pod without it ever
        # entering a solve, and THAT is the verdict, not "backlog".
        drops = self._drops.get(s)
        if not drops:
            if self._p_first[s] == _UNSET:
                return "never considered (queue backlog)"
            return "considered, no drops recorded (awaiting commit)"
        return ", ".join(self._drop_phrase(key, len(list(run)))
                         for key, run in groupby(drops))

    @staticmethod
    def _drop_phrase(key: Tuple[str, int], n: int) -> str:
        reason, shard = key
        out = reason or "dropped"
        if n > 1:
            out += f" x{n}"
        if shard >= 0:
            out += f" on shard {shard}"
        return out

    def queue_rollup(self) -> dict:
        """Per-queue scheduling-latency rollup for /debug/health."""
        with self._lock:
            self._flush_kind_counts()
            out: Dict[str, dict] = {}
            for q, counts in sorted(self._queue_counts.items()):
                win = list(self._queue_ttb.get(q, ()))
                out[q] = {
                    "enqueued_total": counts["enqueued"],
                    "bound_total": counts["bound"],
                    "ttb_p50_ms": _pct(win, 0.50),
                    "ttb_p99_ms": _pct(win, 0.99),
                }
            return {
                "queues": out,
                "pods_tracked": len(self._slot),
                "gangs_tracked": len(self._gang_slot),
                "events_total": self.events_total,
            }

    def stats(self) -> dict:
        """Event counts and time-to-bind / gang full-bind percentiles
        (the endurance harness's ``journey`` tail block)."""
        with self._lock:
            self._flush_kind_counts()
            ttb = list(self._ttb_ms)
            ttfc = list(self._ttfc_ms)
            gang = list(self._gang_ttfb_ms)
            return {
                "events": self.events_total,
                "capture_ms": round(self.capture_ns / 1e6, 3),
                "events_dropped": max(self._count - self._cap, 0),
                "pods": len(self._slot),
                "bound": self.bound_total,
                "bulk_calls": self.bulk_calls,
                "bulk_events": self.bulk_events,
                "scalar_events": self.scalar_events,
                "slot_hits": self.slot_hits,
                "rebinds": self.rebinds,
                "reconsiders": self.reconsiders,
                "ttfc_p50_ms": _pct(ttfc, 0.50),
                "ttb_p50_ms": _pct(ttb, 0.50),
                "ttb_p95_ms": _pct(ttb, 0.95),
                "ttb_p99_ms": _pct(ttb, 0.99),
                "gang_ttfb_p50_ms": _pct(gang, 0.50),
                "gang_ttfb_p99_ms": _pct(gang, 0.99),
            }

    # ------------------------------------------------------- conservation

    def conservation_check(self, bound_uids: Iterable[str]
                           ) -> List[Anomaly]:
        """The endurance-gate invariant: every pod bound at the end of
        the fault schedule has a complete, orphan-free journey.

        - ``journey-orphan``: a bound pod with NO journey state — some
          writer bypassed the capture seams entirely.
        - ``journey-incomplete``: state exists but the bind was never
          recorded, or the event order went non-monotone across a
          shard handoff.

        Synthetic roots (``pod_resync`` adoption after a deliberate
        detach window) count as complete — the adoption is itself the
        recorded provenance.
        """
        orphans: List[str] = []
        incomplete: List[str] = []
        with self._lock:
            for uid in bound_uids:
                s = self._slot.get(uid)
                if s is None:
                    orphans.append(uid)
                elif self._p_bound[s] == _UNSET \
                        or self._p_flags[s] & _NONMONO:
                    incomplete.append(uid)
        out: List[Anomaly] = []
        if orphans:
            out.append(Anomaly("journey-orphan", {
                "count": len(orphans), "uids": orphans[:5],
            }))
        if incomplete:
            out.append(Anomaly("journey-incomplete", {
                "count": len(incomplete), "uids": incomplete[:5],
            }))
        return out
