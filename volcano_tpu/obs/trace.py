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
triggers an object-model rebuild) contribute through ``event()``,
which appends a parentless record under the tracer's lock and never
touches a stack.  ``drain()`` hands the calling thread's spans plus
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
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Dict, List, Optional

# The nested pair the lanes rule allows inside ``device``.
NESTED_LANES = ("device_coarse", "device_fine")


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
        self._events: List[SpanRecord] = []  # guarded-by: _lock
        self._ids = itertools.count(1)
        # Guards _events: event() appends from any thread, drain()
        # takes them.  span() itself is lock-free (thread-local).
        self._lock = threading.Lock()
        # Factory of profiler annotations (``vc:<lane>``), handed in
        # through ``tracer_of`` by the cycle drivers; None = none.
        self.annotate = None

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
        rec = SpanRecord(name, cat, self._anchor_ns + t0_ns, dur_ns,
                         next(self._ids), 0, flow, tid, args)
        with self._lock:
            self._events.append(rec)

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
        with self._lock:
            if self._events:
                spans.extend(self._events)
                self._events = []
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
                 "_span", "_depth", "_t0_ns", "_stamp")

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

    def __enter__(self) -> "CycleScope":
        if self._depth == 0:
            self.tracer._tls.scope = self
            self.t_wall = time.time()
            self._span.__enter__()
            self._t0_ns = self._span.t0
        self._depth += 1
        return self

    def __exit__(self, exc_type, exc, tb):
        self._depth -= 1
        if self._depth == 0:
            self._span.__exit__(exc_type, exc, tb)
            self.tracer._tls.scope = None
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
        if self._depth:  # split(): the call goes on
            self.t_wall = time.time()
            self._t0_ns = now_ns
            self.lanes.clear()
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
