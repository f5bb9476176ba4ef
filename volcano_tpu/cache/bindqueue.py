"""Async bind dispatch + rate-limited bind-failure queue.

The reference dispatches every bind on a goroutine and never waits for it
in the scheduling cycle (``pkg/scheduler/cache/cache.go:536-552``); failed
binds push the task onto a rate-limited ``errTasks`` workqueue whose
resync re-derives the task from the API server with exponential backoff
(``cache.go:106-107,627-649``).  This module is that machinery for the
fast path:

- ``BindDispatcher`` owns a worker thread draining batched bind requests
  to the store's ``Binder``.  The scheduling cycle only pays the queue
  append.
- Failures land in a thread-safe failure list the scheduler drains at the
  START of the next cycle (keeping every mirror mutation on the cycle
  thread); each failure re-enters Pending with an exponential per-task
  backoff (``not_before``) during which the solver does not re-place it —
  the analog of the task sitting in the rate-limited errTasks queue.
- The worker speaks to the store's tracer (obs/trace.py), per batch and
  never per pod: ``bind:queue_wait`` (dispatch -> the worker picks the
  batch up), ``bind:materialize``, ``bind:binder`` (the binder calls),
  ``bind:on_success`` and ``bind:release`` (the worker letting go of
  the batch), as thread-safe events on the ``bind`` track with
  ``args={"pods": n}``.  They drain with the next cycle's record.

Who holds a batch.  The dispatcher owns a batch from ``dispatch()`` to
the end of its delivery and not a moment longer: the queue holds it
until the worker pops it, ``_deliver``'s frame holds it (and whatever
the delivery makes: the materialized lists of a deferred entry, the
copies handed to the binder and to ``on_success``, the failure map)
until it returns, and nothing else of the dispatcher ever does — not
the worker's loop, which outlives every batch and would otherwise keep
the last one alive across its wait.  By the next cycle the round's
completions have deleted those pods from the store, so a worker still
holding them would be their last holder, and taking the next batch
would free 100k pod records in one cascade that keeps the interpreter,
in front of that batch's bind.  The release comes before ``_inflight``
drops, so ``flush()`` returning means the dispatcher holds no reference
to anything of the batches it was given; callers, the binder and the
two hooks keep what they choose to keep.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

from ..obs.trace import null_tracer

log = logging.getLogger(__name__)

# Reference workqueue.DefaultItemBasedRateLimiter: 5ms base, 1000s cap.
# Scheduling periods are ~1s, so sub-second delays are invisible; start
# at one period instead.
BACKOFF_BASE = 1.0
BACKOFF_MAX = 60.0


class BindDispatcher:
    """Single worker thread draining batched bind requests."""

    # The worker's idle slot: called, with no argument, by the worker
    # each time it has delivered and let go of a batch and found its
    # queue empty, the program's own thread with nothing to do until the
    # next cycle.  The scheduler's collector policy puts its full pass
    # here (scheduler.py ``_FullPasses``), for every dispatcher of the
    # process: behind the last bind, in front of none.
    idle_slot: Optional[Callable[[], object]] = None

    def __init__(self, binder,
                 on_failure: Callable[[List[Tuple[str, object]]], None],
                 on_success: Optional[Callable[[List[str], List[str]], None]] = None,
                 materialize: Optional[Callable[[list], tuple]] = None,
                 tracer=None):
        self._binder = binder
        self._tracer = tracer if tracer is not None else null_tracer()
        self._on_failure = on_failure
        self._on_success = on_success
        self._materialize = materialize
        self._cv = threading.Condition()
        # guarded-by: _cv
        self._q: List[tuple] = []  # (keys, hosts, pods, entry, t_ns)
        self._stopped = False  # guarded-by: _cv
        self._inflight = 0  # guarded-by: _cv
        # Runtime lockdep (obs/lockdep.py): created lazily, after the
        # owning store's construction-time walk — arm before the worker
        # thread can race the wrap.  No-op when the probe is off.
        from ..obs.lockdep import attach

        attach(self)
        self._thread = threading.Thread(
            target=self._run, name="vc-bind-dispatch", daemon=True
        )
        self._thread.start()

    def dispatch(self, keys: Sequence[str], hosts: Sequence[str],
                 pods: Sequence[object],
                 entry: Optional[list] = None) -> None:
        """Deferred batches pass ``entry`` (from the store's
        ``defer_bind_records``); the worker materializes lists and
        applies the pod.node_name record walk off the scheduling
        cycle's critical path.

        The dispatcher takes a reference to each argument, not a copy,
        and holds it until the batch is delivered (module docstring,
        "Who holds a batch"): the caller may drop its own at once, must
        not mutate the lists before ``flush()``, and gets nothing kept
        alive for it afterwards."""
        with self._cv:
            self._q.append((keys, hosts, pods, entry,
                            time.perf_counter_ns()))
            self._inflight += 1
            self._cv.notify()

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until every dispatched batch has been delivered and
        let go of: on True the dispatcher references nothing of them."""
        deadline = None if timeout is None else time.time() + timeout
        with self._cv:
            while self._inflight > 0:
                remaining = (
                    None if deadline is None else deadline - time.time()
                )
                if remaining is not None and remaining <= 0:
                    return False
                self._cv.wait(remaining)
        return True

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify()
        self._thread.join(timeout=5)

    # ------------------------------------------------------------- worker

    def _run(self) -> None:
        # This frame lives as long as the thread, so no local of it is
        # ever a batch or a part of one (module docstring, "Who holds a
        # batch"): the batch is popped, delivered and let go of inside
        # _deliver's frame.
        now = time.perf_counter_ns
        while True:
            with self._cv:
                while not self._q and not self._stopped:
                    self._cv.wait()
                if self._stopped and not self._q:
                    return
            n_pods, t_done = self._deliver()
            # _deliver's frame went between t_done and here.  While the
            # store still holds the pods that frees three list shells
            # and the delivery's copies; should the worker ever be a
            # batch's last holder again, the cost reads here and not in
            # the next batch's bind:queue_wait.
            self._tracer.event("bind:release", "bind", t_done,
                               now() - t_done, tid="bind",
                               args={"pods": n_pods})
            with self._cv:
                self._inflight -= 1
                self._cv.notify_all()
                idle = not self._q
            idle_slot = BindDispatcher.idle_slot    # the process's one
            if idle and idle_slot is not None:
                try:
                    idle_slot()
                except Exception:
                    log.exception("bind worker's idle slot failed")

    def _deliver(self) -> Tuple[int, int]:
        """Pop the head batch and deliver it: the binder calls, the
        failure hand-back, ``on_success``, the batch's events.  Every
        reference the dispatcher has to the batch, and to what the
        delivery makes of it, is a local of this frame: returning is the
        release.  Returns (pods in the batch, ``perf_counter_ns`` at the
        end of the delivery)."""
        from .interface import BindFailure

        with self._cv:
            keys, hosts, pods, entry, t_queued = self._q.pop(0)
        now = time.perf_counter_ns
        event = self._tracer.event
        t0 = now()
        args = {"pods": len(entry[0] if entry is not None else keys)}
        event("bind:queue_wait", "bind", t_queued, t0 - t_queued,
              tid="bind", args=args)
        if entry is not None:
            # Deferred record walk: tolist + setattr over the whole
            # batch runs here, off the scheduling cycle (idempotent
            # — a failure path may already have forced it through
            # the store's apply_pending_bind_records).
            keys, hosts, pods = self._materialize(entry)
            event("bind:materialize", "bind", t0, now() - t0,
                  tid="bind", args=args)
        t0 = now()
        failed: List[str] = []
        bind_keys = getattr(self._binder, "bind_keys", None)
        batch_ok = False
        if bind_keys is not None:
            try:
                bind_keys(list(keys), list(hosts))
                batch_ok = True
            except BindFailure as bf:
                failed = list(bf.failed)
                batch_ok = True
            except Exception:
                # Indeterminate: some binds may have taken effect.
                # Failing the whole batch would re-queue pods that
                # are already bound and later re-bind them — possibly
                # to a different node — with no unbind of the first
                # placement.  Re-drive per key instead: Bind is
                # idempotent (key -> node assignment), so repeating a
                # key that already landed is a no-op, and each key
                # gets a definite outcome.
                log.exception(
                    "bind batch indeterminate; retrying per key"
                )
        if not batch_ok:
            for pod, host, key in zip(pods, hosts, keys):
                try:
                    self._binder.bind(pod, host)
                except BindFailure:
                    failed.append(key)
                except Exception:
                    log.exception("bind failed for %s", key)
                    failed.append(key)
        event("bind:binder", "bind", t0, now() - t0, tid="bind",
              args=args)
        if failed:
            try:
                # Hand the pod objects back with the keys so the
                # store's drain never re-derives key->pod over the
                # whole pod table.
                by_key = {k: p for k, p in zip(keys, pods)}
                self._on_failure(
                    [(k, by_key.get(k)) for k in failed]
                )
            except Exception:
                log.exception("bind-failure handler failed")
        if self._on_success is not None:
            ok_pairs = None
            if failed:
                fset = set(failed)
                ok_pairs = (
                    [k for k in keys if k not in fset],
                    [h for k, h in zip(keys, hosts) if k not in fset],
                )
            else:
                ok_pairs = (list(keys), list(hosts))
            t0 = now()
            try:
                self._on_success(*ok_pairs)
            except Exception:
                log.exception("bind-success handler failed")
            event("bind:on_success", "bind", t0, now() - t0,
                  tid="bind", args=args)
        return args["pods"], now()
