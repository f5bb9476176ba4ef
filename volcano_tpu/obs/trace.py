"""Low-overhead trace spans for the scheduling cycle.

Design constraints (ISSUE 3): the hot path records some dozens of spans
per cycle at a 50-300 ms cycle budget, so a span costs two
``time.perf_counter_ns()`` reads and ONE object append — no string
formatting, no dict merging, no allocation beyond the record itself.
The same span that traces a lane also accumulates the cycle's
``lanes[...]`` seconds, so disabling tracing (``VOLCANO_TPU_TRACE=0``)
keeps the lane breakdown intact while skipping the record append.

The lanes rule (ISSUE 25; stated here once, held by
tests/test_cycle_partition.py): **``CycleRecord.lanes`` holds top-level,
inclusive, pairwise disjoint spans of the cycle thread.**  A call site
passes ``lanes=`` to ``span()`` only where no lane span is open above
it; anything nested under a lane is a plain span with a parent
(``commit:journey``, ``device:fetch``), never a lane.  Every
``SpanRecord`` says which lane it accumulated into (``lane``, or None),
so the rule can be checked on any record.  The one exception is the pair ``device_coarse`` /
``device_fine``, which lie inside ``device`` and stay.  So the lanes of
a record sum (without that pair) to at most its ``duration_s``, and the
difference is the record's own ``unattributed_ms``: time of
``Scheduler.run_once()`` that no lane names.

``CycleScope`` is the frame that makes the rule checkable: it is
opened by whoever drives the cycle (``Scheduler.run_once()``; a bare
``run_cycle_fast`` / ``FastCycle.run`` opens its own), owns the outer
``cycle`` span and the lane dict, takes the record the cycle builds,
and seals it into the flight recorder when the outermost holder
leaves — after the last lane (``gc``) closed, so ``duration_s`` means
entry to exit of ``run_once()``.

Threading model: ``span()``, the parent stack under it and the open
scope belong to the calling thread (thread-local state: under the
sharded control plane several cycle threads share one store's tracer,
and the prologue of a cycle runs before the store lock is taken).
Other threads (the bind dispatcher, remote RPC clients, whoever
triggers an object-model rebuild, the collector's hook) contribute
through ``event()``, which appends a parentless record to a deque and
never touches a stack or a lock.  ``drain()`` hands the calling thread's spans plus
every helper-thread event accumulated so far to the record being
sealed.

On the profiler's clock: every lane span and the outer ``cycle`` span
also open the annotation factory handed to the tracer
(``jax.profiler.TraceAnnotation``, by ``scheduler.py`` / ``fastpath.py``
— this module stays stdlib-only) under the name ``vc:<lane>``; such an
annotation is inert unless a profiler trace is running, and then lies
in the same xplane as the device's operations.

Span timestamps are monotonic (``perf_counter_ns``) shifted to the
epoch by a per-tracer anchor captured at construction, so exported
traces from one process share one timeline.

Between two cycles (ISSUE 35): ``BetweenAccount`` counts the store's
event handlers by kind, times one call in ``SAMPLE_STRIDE`` of each
kind phase by phase, and is told of every pass of the collector by the
process's one ``gc.callbacks`` hook (``_Collector``).  The cycle's
frame takes a snapshot of it on entry and turns it into
``CycleRecord.between`` at the seal: what happened since the previous
record was sealed.  No lane, nothing per pod (docs/tracing.md,
"Between two cycles").
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import os
import threading
import time
import weakref
from typing import Dict, List, Optional

# The nested pair the lanes rule allows inside ``device``.
NESTED_LANES = ("device_coarse", "device_fine")

# Who started a full pass of the collector (``between.gc.full_by``): the
# scheduler, every ``GC_FULL_EVERY`` cycles or because the heap doubled,
# or anyone else (the allocator's count, a ``gc.collect()`` of the
# caller's).
FULL_PASS_STARTERS = ("cycles", "growth", "allocator")


class SpanRecord:
    """One completed span.  ``ts_ns`` is epoch nanoseconds; ``flow`` is
    the cross-cycle link id (the pipelined solve-id) or None; ``tid``
    names the logical track ("cycle" for the scheduling thread, "rpc" /
    "bind" / "store" for helper threads); ``lane`` is the lane the span
    accumulated into, None for a child."""

    __slots__ = ("name", "cat", "ts_ns", "dur_ns", "span_id",
                 "parent_id", "flow", "tid", "args", "lane")

    def __init__(self, name, cat, ts_ns, dur_ns, span_id, parent_id,
                 flow, tid, args, lane=None):
        self.name = name
        self.cat = cat
        self.ts_ns = ts_ns
        self.dur_ns = dur_ns
        self.span_id = span_id
        self.parent_id = parent_id
        self.flow = flow
        self.tid = tid
        self.args = args
        self.lane = lane

    def to_dict(self) -> dict:
        d = {
            "name": self.name,
            "cat": self.cat,
            "ts_ns": self.ts_ns,
            "dur_ns": self.dur_ns,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "tid": self.tid,
        }
        if self.lane is not None:
            d["lane"] = self.lane
        if self.flow is not None:
            d["flow"] = self.flow
        if self.args:
            d["args"] = self.args
        return d


class _Span:
    """Context-manager handle; always times (the lane accumulation must
    survive tracing being disabled), appends a record only when the
    tracer is enabled.  ``ann`` is the profiler annotation's name (lane
    spans and the outer cycle span) or None."""

    __slots__ = ("tr", "name", "cat", "flow", "lanes", "lane", "args",
                 "ann", "t0", "span_id", "parent_id", "dur_ns", "_st",
                 "_ann")

    def __init__(self, tr, name, cat, flow, lanes, lane, args, ann):
        self.tr = tr
        self.name = name
        self.cat = cat
        self.flow = flow
        self.lanes = lanes
        self.lane = lane
        self.args = args
        self.ann = ann
        self._ann = None

    def __enter__(self):
        tr = self.tr
        if tr.enabled:
            # Thread-local (see the module docstring); untouched when
            # disabled: the shared disabled tracer serves many stores.
            st = self._st = tr._tls
            stack = st.stack
            self.parent_id = stack[-1] if stack else 0
            self.span_id = next(tr._ids)
            stack.append(self.span_id)
        if self.ann is not None and tr.annotate is not None:
            self._ann = tr.annotate(self.ann)
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        tr = self.tr
        dur = self.dur_ns = t1 - self.t0
        lanes = self.lanes
        if lanes is not None:
            lane = self.lane
            lanes[lane] = lanes.get(lane, 0.0) + dur * 1e-9
        if tr.enabled:
            st = self._st
            st.stack.pop()
            args = self.args
            if exc_type is not None:
                args = dict(args) if args else {}
                args["error"] = exc_type.__name__
            st.spans.append(SpanRecord(
                self.name, self.cat, tr._anchor_ns + self.t0, dur,
                self.span_id, self.parent_id, self.flow, "cycle", args,
                self.lane,
            ))
        return False


class _ThreadState(threading.local):
    """What ``span()`` keeps per calling thread."""

    def __init__(self):
        self.stack: List[int] = []       # open span ids, innermost last
        self.spans: List[SpanRecord] = []  # completed, not yet drained
        self.scope: Optional["CycleScope"] = None


class Tracer:
    """Per-store span sink.  One instance per ``ClusterStore``; a cycle
    thread records spans, ``drain()`` moves them into the flight
    recorder's per-cycle record."""

    def __init__(self, enabled: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get("VOLCANO_TPU_TRACE", "1") != "0"
        self.enabled = bool(enabled)
        # epoch_ns = anchor + perf_counter_ns (captured together).
        self._anchor_ns = time.time_ns() - time.perf_counter_ns()
        self._tls = _ThreadState()
        # event() appends from any thread, drain() pops: a deque does
        # both atomically, so no lock.  There must be none: the
        # collector's hook appends here from whatever bytecode of
        # whatever thread a pass interrupts, event() itself included.
        self._events: collections.deque = collections.deque()
        self._ids = itertools.count(1)
        # Factory of profiler annotations (``vc:<lane>``), handed in
        # through ``tracer_of`` by the cycle drivers; None = none.
        self.annotate = None
        # The account of the time between two cycles, made by the
        # owning store (``BetweenAccount``); None for a bare tracer.
        self.between: Optional["BetweenAccount"] = None

    # ------------------------------------------------------------- spans

    def span(self, name: str, cat: str = "cycle",
             flow: Optional[int] = None,
             lanes: Optional[Dict[str, float]] = None,
             lane: Optional[str] = None,
             args: Optional[dict] = None) -> _Span:
        """Span of the calling (cycle) thread.  ``lanes``/``lane``
        additionally accumulate the elapsed seconds into the cycle's
        lane dict — top-level spans only, see the lanes rule above."""
        if lanes is None:
            return _Span(self, name, cat, flow, None, None, args, None)
        if lane is None:
            lane = name
        return _Span(self, name, cat, flow, lanes, lane, args,
                     "vc:" + lane)

    def event(self, name: str, cat: str, t0_ns: int, dur_ns: int,
              tid: str = "rpc", flow: Optional[int] = None,
              args: Optional[dict] = None) -> None:
        """Append a completed span from ANY thread (RPC clients, the
        bind dispatcher).  ``t0_ns`` is a ``perf_counter_ns`` reading."""
        if not self.enabled:
            return
        self._events.append(SpanRecord(
            name, cat, self._anchor_ns + t0_ns, dur_ns, next(self._ids),
            0, flow, tid, args))

    def timed_event(self, name: str, cat: str = "rpc",
                    tid: str = "rpc", flow: Optional[int] = None,
                    args: Optional[dict] = None) -> "_TimedEvent":
        """Thread-safe time-this-block context manager over ``event()``
        — the one shared shape for RPC call sites (remote side-effect
        clients, the remote solver's send/fetch legs)."""
        return _TimedEvent(self, name, cat, tid, flow, args)

    def drain(self) -> List[SpanRecord]:
        """Hand over the calling thread's spans and every helper-thread
        event accumulated so far (cycle end), and reset."""
        st = self._tls
        spans, st.spans = st.spans, []
        pop = self._events.popleft
        try:
            while True:
                spans.append(pop())
        except IndexError:
            return spans

    # ------------------------------------------------------------- cycle

    def cycle(self, flight=None) -> "CycleScope":
        """The calling thread's open ``CycleScope``, or a new one over
        ``flight`` (a ``FlightRecorder`` or None).  Use as a context
        manager: only the outermost holder opens and seals."""
        scope = self._tls.scope
        return scope if scope is not None else CycleScope(self, flight)


class CycleScope:
    """One ``Scheduler.run_once()``: the outer ``cycle`` span, the lane
    dict every lane of the cycle accumulates into, and the
    ``CycleRecord`` under construction.  Re-entrant on its thread: the
    scheduler opens it, ``run_cycle_fast`` and ``FastCycle.run`` join
    it, and the outermost ``__exit__`` seals the record — duration,
    lanes, ``unattributed_ms`` and spans are final only then."""

    __slots__ = ("tracer", "flight", "lanes", "t_wall", "record",
                 "_span", "_depth", "_t0_ns", "_stamp", "_between")

    def __init__(self, tracer: Tracer, flight=None):
        self.tracer = tracer
        self.flight = flight
        self.lanes: Dict[str, float] = {}
        self.t_wall = 0.0
        self.record = None
        self._span = _Span(tracer, "cycle", "cycle", None, None, None,
                           None, "vc:cycle")
        self._depth = 0
        self._t0_ns = 0
        self._stamp = ()
        self._between = None

    def __enter__(self) -> "CycleScope":
        if self._depth == 0:
            self.tracer._tls.scope = self
            self.t_wall = time.time()
            self._span.__enter__()
            self._t0_ns = self._span.t0
            acct = self.tracer.between
            if acct is not None:
                # What the store did since the previous record was
                # sealed, as this cycle finds it.
                self._between = acct.snapshot(self._t0_ns)
                acct.cycle_open(1)
        self._depth += 1
        return self

    def __exit__(self, exc_type, exc, tb):
        self._depth -= 1
        if self._depth == 0:
            self._span.__exit__(exc_type, exc, tb)
            self.tracer._tls.scope = None
            if self._between is not None:
                self.tracer.between.cycle_open(-1)
            self._seal(time.perf_counter_ns())
        return False

    def lane(self, name: str) -> _Span:
        """A top-level lane span of this cycle."""
        return self.tracer.span(name, lanes=self.lanes)

    def describe(self, cat: str, args: Optional[dict]) -> None:
        """Category and args of the outer ``cycle`` span (the session
        is not known when the scheduler opens the scope)."""
        self._span.cat = cat
        self._span.args = args

    def elapsed_s(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) * 1e-9

    def submit(self, record, stamp=()) -> None:
        """Take the cycle's record.  ``stamp`` are objects whose
        ``cycle_seq`` is set to the record's seq at sealing (the
        auditor's ring copies)."""
        self.record = record
        self._stamp = stamp

    def split(self) -> None:
        """Seal the record taken so far, with what was measured so far,
        and restart the clock and the lanes: what follows in this
        ``run_once()`` is another record's (the object-session fallback
        after a failed fast cycle), and the two partition the call
        between them."""
        self._seal(time.perf_counter_ns())

    def _seal(self, now_ns: int) -> None:
        rec, self.record = self.record, None
        spans = self.tracer.drain()
        if rec is None:
            return
        rec.t_wall = self.t_wall
        rec.duration_s = (now_ns - self._t0_ns) * 1e-9
        rec.lanes = dict(self.lanes)
        rec.spans = spans
        snap, self._between = self._between, None
        if snap is not None:
            rec.between = self.tracer.between.block(snap, spans, now_ns)
        if self._depth:  # split(): the call goes on
            self.t_wall = time.time()
            self._t0_ns = now_ns
            self.lanes.clear()
            if snap is not None:
                self._between = self.tracer.between.snapshot(now_ns)
        if self.flight is not None:
            seq = self.flight.record(rec)
            for obj in self._stamp:
                obj.cycle_seq = seq
        self._stamp = ()


class _TimedEvent:
    """Times a block and appends it via ``Tracer.event`` (no parent
    stack, so safe from any thread and on the shared disabled
    tracer)."""

    __slots__ = ("tr", "name", "cat", "tid", "flow", "args", "t0")

    def __init__(self, tr, name, cat, tid, flow, args):
        self.tr = tr
        self.name = name
        self.cat = cat
        self.tid = tid
        self.flow = flow
        self.args = args

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        tr = self.tr
        if tr.enabled:
            tr.event(self.name, self.cat, self.t0,
                     time.perf_counter_ns() - self.t0, tid=self.tid,
                     flow=self.flow, args=self.args)
        return False


# ------------------------------------------- the time between two cycles

# One call in SAMPLE_STRIDE of each kind is timed: the one its kind's
# count picks (``counts[kind] % SAMPLE_STRIDE == 0``).  A prime, and not
# the 64 a mask would give: gangs come in twos, fours and eights, and a
# timer that always met a gang's first pod would read what only that
# pod pays (the interning of its gang's terms, the gang's first journey
# row).  With a prime the timed place moves through the gang.
SAMPLE_STRIDE = 61

# ``ClusterStore``'s public event handlers, "<Kind>/<event>" as its
# ``_notify`` names them.
EVENT_KINDS = tuple(
    f"{kind}/{event}"
    for kind, events in (
        ("Pod", ("add", "update", "delete")),
        ("PodGroup", ("add", "update", "delete")),
        ("Node", ("add", "update", "delete")),
        ("Queue", ("add", "update", "delete")),
        ("PriorityClass", ("add", "delete")),
        ("ResourceQuota", ("add",)),
        ("Job", ("add", "update", "delete")),
        ("Command", ("add", "delete")),
    )
    for event in events
)
# The three hot ones' places in it (``BetweenAccount.counts`` is a list).
POD_ADD, POD_UPDATE, POD_DELETE = 0, 1, 2

# The bind track's events during which the worker runs Python
# (``bind:queue_wait`` is the batch waiting, not the worker working).
BIND_BUSY = frozenset(("bind:materialize", "bind:binder",
                       "bind:on_success", "bind:release"))


class _Sample:
    """One timed handler call: nanoseconds by phase.  Each ``mark``
    gives the time since the previous one to a phase, so the phases sum
    to the whole; time taken out (a collector pass, a compaction, each
    counted exactly elsewhere) moves ``t`` forward instead.  A stamp
    reads the clock on its way in and again on its way out, so that its
    own bookkeeping is in no phase (what is left of a stamp in the
    phases, the call and the return, is about a tenth of a
    microsecond)."""

    __slots__ = ("acct", "kind", "ns", "t", "clock")

    def __init__(self, acct: "BetweenAccount", kind: int):
        self.acct = acct
        self.kind = kind
        self.ns: Dict[str, int] = {}
        self.clock = acct.clock
        self.t = 0

    def mark(self, phase: str) -> "_Sample":
        now = self.clock()
        ns = self.ns
        ns[phase] = ns.get(phase, 0) + now - self.t
        self.t = self.clock()
        return self

    def close(self, phase: str) -> None:
        """The lock is released: the rest goes to ``phase`` and the
        call into the account."""
        self.mark(phase)
        acct = self.acct
        with acct._lock:
            acct._samples[self.kind] += 1
            sums = acct._sums[self.kind]
            for name, ns in self.ns.items():
                sums[name] = sums.get(name, 0) + ns
            acct._open = None


class BetweenAccount:
    """What a store did between two cycles, by itself: its event
    handlers' calls by kind (exact), their seconds by kind and phase
    (from one timed call in ``SAMPLE_STRIDE``), the collector's passes
    (exact, from ``_Collector``), the pod table's compactions (exact),
    the pod specs the mirror encoded (exact) and the rows of its spec
    table (a level, not a count).  Lifetime counters;
    ``snapshot()`` copies them at a cycle's start and ``block()`` turns
    the difference to the previous sealed record's snapshot into
    ``CycleRecord.between``.

    Who writes what: ``counts`` the handlers, ``specs_encoded`` and
    ``spec_rows`` the mirror, under the STORE's lock;
    ``_samples`` / ``_sums`` / ``_open`` a timed call, under ``_lock``;
    the collector's numbers the hook, of which one runs at a time in a
    process.  The hook takes no lock at all: a pass interrupts whatever
    bytecode its thread was at, a locked region included."""

    def __init__(self, tracer: "Tracer", clock=time.perf_counter_ns):
        self._tracer = weakref.ref(tracer)
        self.clock = clock
        # By place in EVENT_KINDS: the calls, the timed calls, their
        # nanoseconds by phase.
        n = len(EVENT_KINDS)
        self.counts: List[int] = [0] * n
        self._samples: List[int] = [0] * n
        self._sums: List[Dict[str, int]] = [{} for _ in range(n)]
        self._open: Optional[_Sample] = None
        # [passes, ns, collected] by generation, then of the passes
        # inside an open cycle (any generation).
        self._gc = [[0, 0, 0] for _ in range(4)]
        # Full passes by who started them (FULL_PASS_STARTERS), inside
        # a cycle or not; the counts at the last seal.
        self._full = [0] * len(FULL_PASS_STARTERS)
        self._full_base = list(self._full)
        self._gc_longest_ns = 0
        self._compact = [0, 0]  # compactions, ns
        # Pod specs the mirror encoded (``StoreMirror._encode``), under
        # the store's lock: an add or update whose spec it had met is
        # not one.
        self.specs_encoded = 0
        # The rows of the mirror's spec table as it stands: up by one
        # with every spec counted above, down at a compaction to the
        # specs that still have a live pod.
        self.spec_rows = 0
        self._cycles_open = 0
        self._lock = threading.Lock()
        self._base = self.snapshot(0)
        self._t0_ns = time.perf_counter_ns()
        tracer.between = self
        _collector.watch(tracer)

    # ---------------------------------------------------- the event path

    def sample(self, kind: int) -> Optional[_Sample]:
        """A timer for this call of ``kind``, whose count said it is
        due; None while the tracer is off or another timed call is
        open (a handler calling a handler, or two threads at once)."""
        tr = self._tracer()
        if tr is None or not tr.enabled:
            return None
        with self._lock:
            if self._open is not None:
                return None
            st = self._open = _Sample(self, kind)
        st.t = self.clock()
        return st

    def took_out(self, dur_ns: int) -> None:
        """Time that is counted exactly elsewhere leaves the open timed
        call, if there is one."""
        st = self._open
        if st is not None:
            st.t += dur_ns

    def compacted(self, t0_ns: int, gc_ns0: int) -> None:
        """``StoreMirror.maybe_compact`` rebuilt the pod table: counted
        whole, without the collector's passes inside it."""
        dur = self.clock() - t0_ns - (self.gc_ns() - gc_ns0)
        self._compact[0] += 1
        self._compact[1] += dur
        self.took_out(dur)

    def gc_ns(self) -> int:
        return sum(g[1] for g in self._gc)

    # ------------------------------------------------------ the collector

    def collected(self, tr: "Tracer", gen: int, info: dict, t0_ns: int,
                  dur_ns: int, starter: str) -> None:
        """One pass of the collector, told by the process's hook;
        ``starter`` is the scheduler's reason for a full pass of its
        own (``scheduled_pass``), ``allocator`` for any other."""
        # A pass while a cycle is open on the store is the cycle's
        # (``run_once()`` switches the collector off, so its ``gc``
        # lane's own sweep), apart from the interval's.
        mine = self._gc[3 if self._cycles_open else gen]
        mine[0] += 1
        mine[1] += dur_ns
        collected = info.get("collected", 0)
        mine[2] += collected
        if not self._cycles_open and dur_ns > self._gc_longest_ns:
            self._gc_longest_ns = dur_ns
        self.took_out(dur_ns)
        if gen:
            # Generation 0 is counted and never recorded: its number
            # grows with the batch.
            args = {"collected": collected,
                    "uncollectable": info.get("uncollectable", 0)}
            if gen == 2:
                self._full[FULL_PASS_STARTERS.index(starter)] += 1
                args["reason"] = starter
            tr.event(f"gc:gen{gen}", "gc", t0_ns, dur_ns, tid="gc",
                     args=args)

    def cycle_open(self, delta: int) -> None:
        with self._lock:
            self._cycles_open += delta

    # ------------------------------------------------------- the record

    def snapshot(self, t1_ns: int) -> dict:
        """The counters as a cycle finds them at its start, ``t1_ns``."""
        return {
            "t1_ns": t1_ns,
            "counts": list(self.counts),
            "samples": list(self._samples),
            "sums": [dict(v) for v in self._sums],
            "gc": [list(g) for g in self._gc],
            "compact": list(self._compact),
            "specs_encoded": self.specs_encoded,
            "spec_rows": self.spec_rows,
        }

    def block(self, snap: dict, spans: list, seal_ns: int) -> dict:
        """``CycleRecord.between`` of the record being sealed: ``snap``
        against the snapshot the previous sealed record took.  A
        snapshot that no record took (a cycle that ended without one)
        leaves the base where it was, so nothing counted is lost."""
        tr = self._tracer()
        anchor = tr._anchor_ns if tr is not None else 0
        timing = tr is not None and tr.enabled
        with self._lock:
            base, t0_ns = self._base, self._t0_ns
            if snap["t1_ns"] >= base["t1_ns"]:
                self._base = snap
            self._t0_ns = seal_ns
            # No pass is the interval's while a cycle is open, so the
            # longest is still what the snapshot would have found.
            longest_ns, self._gc_longest_ns = self._gc_longest_ns, 0
            # Full passes by starter run seal to seal, the cycle's own
            # (a due pass at the end of its ``gc`` lane) included.
            full = list(self._full)
            full0, self._full_base = self._full_base, full
        t1_ns = snap["t1_ns"]
        t0_ns = min(t0_ns, t1_ns)
        out = {"t0_ns": anchor + t0_ns, "t1_ns": anchor + t1_ns,
               "stride": SAMPLE_STRIDE}
        events = out["events"] = {}
        held_s = 0.0
        for i, kind in enumerate(EVENT_KINDS):
            n = max(0, snap["counts"][i] - base["counts"][i])
            if not n:
                continue
            ev = events[kind] = {"n": n}
            if not timing:
                continue
            k = max(0, snap["samples"][i] - base["samples"][i])
            sums, sums0 = snap["sums"][i], base["sums"][i]
            phases = {name: ns - sums0.get(name, 0)
                      for name, ns in sums.items()}
            whole = sum(phases.values())
            # The estimate is sum x stride: each timed call stands for
            # the stride's calls, whichever record they fall into, so
            # estimates add up over records without a bias (a block of
            # fewer calls than the stride has often no timed call, and
            # then reads 0).
            ev.update(samples=k, sampled_s=_s(whole),
                      est_s=_s(whole * SAMPLE_STRIDE),
                      phases={name: {"sampled_s": _s(ns),
                                     "est_s": _s(ns * SAMPLE_STRIDE)}
                              for name, ns in phases.items()})
            held_s += _s((whole - phases.get("lock_wait", 0))
                         * SAMPLE_STRIDE)
        out["specs_encoded"] = snap["specs_encoded"] - base["specs_encoded"]
        out["spec_rows"] = snap["spec_rows"]
        if not timing:
            return out
        out["lock_held_s"] = round(held_s, 9)
        # The cycle's own passes come after its snapshot: read now.
        snap["gc"][3] = list(self._gc[3])
        gcd = out["gc"] = {
            name: {"n": g[0] - g0[0], "s": _s(g[1] - g0[1]),
                   "collected": g[2] - g0[2]}
            for name, g, g0 in zip(("gen0", "gen1", "gen2", "in_cycle"),
                                   snap["gc"], base["gc"])}
        gcd["longest_s"] = _s(longest_ns)
        gcd["full_by"] = {name: n - n0 for name, n, n0
                          in zip(FULL_PASS_STARTERS, full, full0)}
        out["compactions"] = snap["compact"][0] - base["compact"][0]
        out["compact_s"] = _s(snap["compact"][1] - base["compact"][1])
        out["bind_busy_s"] = _busy_inside(
            spans, anchor + t0_ns, anchor + t1_ns)
        return out


def _busy_inside(spans: list, t0_ns: int, t1_ns: int) -> float:
    """Seconds of [t0_ns, t1_ns] in which the bind worker ran: the
    union of its ``BIND_BUSY`` events, cut to the interval."""
    cuts = sorted(
        (max(s.ts_ns, t0_ns), min(s.ts_ns + s.dur_ns, t1_ns))
        for s in spans if s.tid == "bind" and s.name in BIND_BUSY)
    total, end = 0, t0_ns
    for a, b in cuts:
        a = max(a, end)
        if b > a:
            total += b - a
            end = b
    return _s(total)


def _s(ns) -> float:
    """Nanoseconds as seconds, to the nanosecond."""
    return round(ns * 1e-9, 9)


class _Collector:
    """The process's one ``gc.callbacks`` hook, however many stores it
    makes: it times every pass of the collector and tells the accounts
    of the live tracers, reached through weak references (a dropped
    store's tracer and account go with it).  On the profiler's clock a
    pass of generation 1 or 2 lies under ``vc:gc1`` / ``vc:gc2``.
    ``reason`` is set around a full pass that the scheduler starts
    (``scheduled_pass``); one pass runs at a time in a process."""

    def __init__(self):
        self.clock = time.perf_counter_ns
        self.reason: Optional[str] = None
        self._tracers: tuple = ()  # weakref.ref(Tracer), copy-on-write
        self._lock = threading.Lock()
        self._t0 = 0
        self._ann = None

    def watch(self, tracer: "Tracer") -> None:
        with self._lock:
            self._tracers = tuple(
                r for r in self._tracers if r() is not None
            ) + (weakref.ref(tracer),)
            if self.hook not in gc.callbacks:
                gc.callbacks.append(self.hook)

    def hook(self, phase: str, info: dict) -> None:
        gen = info.get("generation", 0)
        if phase == "start":
            if gen:
                for ref in self._tracers:
                    tr = ref()
                    if (tr is not None and tr.enabled
                            and tr.annotate is not None):
                        self._ann = tr.annotate(f"vc:gc{gen}")
                        self._ann.__enter__()
                        break
            self._t0 = self.clock()
            return
        t0 = self._t0
        dur = self.clock() - t0
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        starter = (gen == 2 and self.reason) or "allocator"
        for ref in self._tracers:
            tr = ref()
            if tr is not None and tr.enabled and tr.between is not None:
                tr.between.collected(tr, gen, info, t0, dur, starter)


_collector = _Collector()


@contextlib.contextmanager
def scheduled_pass(reason: str):
    """Around a full pass that the scheduler starts itself
    (``scheduler.py`` ``_FullPasses``): the accounts count it under
    ``reason``, one of ``FULL_PASS_STARTERS``, and not under
    ``allocator``."""
    _collector.reason = reason
    try:
        yield
    finally:
        _collector.reason = None

_NULL = Tracer(enabled=False)


def null_tracer() -> Tracer:
    """Shared disabled tracer for call sites whose cache object carries
    no tracer (bare test doubles standing in for a ClusterStore)."""
    return _NULL


def tracer_of(obj, annotate=None) -> Tracer:
    """The object's tracer, or the shared disabled one.  ``annotate``
    is the profiler's annotation factory, handed in by the cycle
    drivers (which import JAX anyway); the object's own tracer keeps
    the first one it is given."""
    tr = getattr(obj, "tracer", None)
    if tr is None:
        return _NULL
    if annotate is not None and tr.annotate is None:
        tr.annotate = annotate
    return tr
