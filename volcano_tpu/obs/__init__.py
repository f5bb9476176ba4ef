"""Observability layer: trace spans, cycle flight recorder, Perfetto
export (ISSUE 3), runtime conservation auditor + SLO layer (ISSUE 13).

Six modules, stdlib-only at module scope but for ``journey`` (numpy),
importable without jax so the store and the HTTP service can wire them
unconditionally:

- ``trace``    — the low-overhead span API (``perf_counter_ns``; one
  small record appended per span, nothing else on the fast path) the
  cycle lanes, the pipelined dispatch→fetch→commit chain, the object
  session's action/plugin boundaries, the bind dispatcher and the
  remote RPC clients all record into; the lanes rule (lanes partition
  ``Scheduler.run_once()``) and the ``CycleScope`` that frames a
  cycle's record live there.
- ``recorder`` — the fixed-size ring buffer (default 256 cycles) of
  per-cycle ``CycleRecord``s: lane breakdown and its residual, the
  solve's counts, pods considered / bound /
  dropped, staleness-guard drop counts by reason, in-flight fetch wait,
  device crash events, mirror ``mutation_seq``/``epoch`` at dispatch vs
  commit, what the store did between the previous cycle and this one
  (``between``), and the cycle's spans.
- ``export``   — Chrome/Perfetto ``trace_event`` JSON (loadable in
  ``chrome://tracing`` / https://ui.perfetto.dev), with flow arrows
  linking a pipelined solve's dispatch span in cycle N to its
  fetch/commit spans in cycle N+1 via the solve-id, plus one instant
  event per audit anomaly so correctness failures are visible on the
  latency timeline.
- ``audit``    — the always-on runtime conservation auditor (ISSUE
  13): a double-entry ledger of pod-count flows reconciled against
  mirror truth every cycle, sampled coherence audits of the registered
  cache slots, the migration-ledger zero-lost-pods check, and the
  anomaly ring behind ``/debug/anomalies``.
- ``slo``      — per-lane latency windows with declared budgets and
  error-budget burn tracking; breaches surface as auditor anomalies
  and in ``/debug/health``.
- ``journey``  — pod-centric plane (ISSUE 18): a bounded columnar
  per-pod event timeline (enqueued → dispatched → dropped/evicted →
  bound) captured at every sanctioned writer, feeding per-queue
  time-to-bind / gang full-bind latency, the ``/debug/pods/<uid>``
  why-pending explainer, Perfetto async tracks, and the endurance
  conservation check (``journey-orphan`` / ``journey-incomplete``).

Consumers: ``service.py`` exposes ``/debug/cycles``,
``/debug/cycles/<seq>``, ``/debug/trace?cycles=K``, ``/debug/health``
and ``/debug/anomalies``; the benchmark (``benchmark/run.py``) reads
the flight records' lanes; ``hack/endurance.py`` gates on the auditor's
verdict and prints the audit and journey blocks in its JSON tail.
docs/tracing.md and docs/observability.md document all of it.
"""

from .audit import Anomaly, Auditor
from .journey import JourneyLog, journey_on
from .recorder import CycleRecord, FlightRecorder
from .slo import SLOTracker
from .trace import SpanRecord, Tracer, null_tracer

__all__ = [
    "Anomaly",
    "Auditor",
    "CycleRecord",
    "FlightRecorder",
    "JourneyLog",
    "journey_on",
    "SLOTracker",
    "SpanRecord",
    "Tracer",
    "null_tracer",
]
