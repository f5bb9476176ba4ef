"""Scheduler driver: the per-period session loop
(pkg/scheduler/scheduler.go).

Every ``schedule_period`` (default 1 s): re-read the YAML config (hot
reload, scheduler.go:77,89-106), open a session, execute the configured
action list, close the session.  Config parsing failures keep the last good
config.
"""

from __future__ import annotations

import gc
import logging
import sys
import threading
import time
import weakref
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from jax.profiler import TraceAnnotation

from . import actions as _actions  # noqa: F401  (registers actions)
from . import plugins as _plugins  # noqa: F401  (registers plugins)
from .cache import ClusterStore
from .framework import (
    DEFAULT_SCHEDULER_CONF,
    close_session,
    get_action,
    open_session,
    parse_scheduler_conf,
)
from .metrics import metrics
from .obs.trace import scheduled_pass, tracer_of

log = logging.getLogger(__name__)

_compile_cache_enabled = False

# The persistent XLA cache when JAX_COMPILATION_CACHE_DIR does not place
# it: one fixed directory in the checkout, next to csrc/ (resolved like
# native._CSRC).  The directory's path is part of every cache key, so
# it must not depend on $HOME, the pid or the time.
COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".xla_cache"


def enable_compilation_cache() -> None:
    """Persist XLA executables across processes (wave-solver compiles
    run many seconds; a restarted scheduler would otherwise pay them
    again).  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already
    taken the directory from it and none is set here."""
    global _compile_cache_enabled
    if _compile_cache_enabled:
        return
    _compile_cache_enabled = True
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            COMPILE_CACHE_DIR.mkdir(exist_ok=True)
        except OSError as err:  # read-only install: run uncached
            log.warning("compilation cache unavailable (set "
                        "JAX_COMPILATION_CACHE_DIR): %s", err)
            return
        jax.config.update("jax_compilation_cache_dir",
                          str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


import contextlib


@contextlib.contextmanager
def _device_trace():
    """JAX profiler hook (SURVEY.md 5.1: histograms + device trace for
    kernel/transfer time).  Set VOLCANO_TPU_TRACE_DIR=<dir> to capture a
    per-cycle device trace viewable in TensorBoard/Perfetto; unset, this
    is a no-op context.  Best-effort: profiler failures (unwritable dir,
    trace already active) must not abort the scheduling cycle, so entry
    and exit errors are swallowed here — jax.profiler.trace raises at
    __enter__, which a plain try around its construction cannot catch."""
    import os

    trace_dir = os.environ.get("VOLCANO_TPU_TRACE_DIR")
    if not trace_dir:
        yield
        return
    started = False
    try:
        import jax

        jax.profiler.start_trace(trace_dir)
        started = True
    except Exception as err:  # pragma: no cover - profiler is best-effort
        log.warning("device trace unavailable: %s", err)
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as err:  # pragma: no cover
                log.warning("device trace stop failed: %s", err)


class Scheduler:
    def __init__(
        self,
        store: ClusterStore,
        conf_path: Optional[str] = None,
        conf_str: Optional[str] = None,
        schedule_period: float = 1.0,
        gate=None,
        shard=None,
    ):
        self.store = store
        self.conf_path = conf_path
        self.conf_str = conf_str
        self.schedule_period = schedule_period
        # Optional leadership gate: the periodic loop skips cycles while it
        # returns False (active/passive HA, see volcano_tpu.ha).
        self.gate = gate
        # Sharded control plane (shard.py, ISSUE 16): this loop's
        # shard.ShardContext, or None for the default single-scheduler
        # path.  A sharded loop runs the fast path only (the object
        # session is not shard-aware and would double-schedule foreign
        # queues) and drains only its OWN in-flight slot on stop.
        self.shard = shard
        self._stop = threading.Event()
        # run()/stop() may race from different operator threads (service
        # shutdown vs a late start); the lifecycle lock makes the leak
        # window (two run() calls both spawning loop threads) impossible.
        self._lifecycle_lock = threading.Lock()
        # guarded-by: _lifecycle_lock
        self._thread: Optional[threading.Thread] = None
        # The loop thread's ident while _loop runs (written by that
        # thread alone): run_once() asks whether its caller is the loop.
        self._loop_ident: Optional[int] = None
        self._last_conf = None
        self._consecutive_failures = 0

    # --------------------------------------------------------------- config

    def _load_conf(self):
        conf_str = self.conf_str
        if self.conf_path:
            try:
                conf_str = Path(self.conf_path).read_text()
            except OSError as err:
                log.error("Failed to read scheduler conf %s: %s",
                          self.conf_path, err)
                conf_str = None
        if conf_str is None:
            conf_str = DEFAULT_SCHEDULER_CONF
        try:
            conf = parse_scheduler_conf(conf_str)
        except Exception:
            log.exception("Failed to parse scheduler conf; keeping last")
            if self._last_conf is not None:
                return self._last_conf
            conf = parse_scheduler_conf(DEFAULT_SCHEDULER_CONF)
        self._last_conf = conf
        return conf

    # ---------------------------------------------------------------- cycle

    def run_once(self) -> None:
        """One scheduling cycle (scheduler.go:71-87).

        Eligible configurations (built-in plugins, enqueue/allocate/backfill
        actions) run on the vectorized fast path over the store's array
        mirror; anything else uses the object-session path.

        The collector (``_FullPasses``, below ``GC_FULL_EVERY``): it is
        switched off for the duration of the cycle (at 100k-pod scale a
        generation-2 pass walks the store's millions of live objects
        and was measured adding 2.3 s to a 0.9 s preempt+reclaim
        cycle), and from a scheduler's first cycle on the allocator's
        count starts no full pass between cycles either: automatic
        collection is confined to generations 0 and 1, and a full pass
        is started by the scheduler, when it is due (``GC_FULL_EVERY``
        cycles, or the heap doubled since the last one) and no bind is
        on its way.  The ``gc`` lane sweeps generation 0 and, where this
        call is not the loop's and nothing is left to hand over
        (synchronous binds), runs a due full pass at its end; with the
        async dispatcher the bind worker runs it once it has delivered
        the cycle's batch and found its queue empty, and ``_loop`` in
        its period slack.  A caller that switched the collector off, or
        moved the third threshold itself, keeps what it set.
        ``stop()`` and the store's ``close()`` put the threshold
        back."""
        # The cycle's frame (obs/trace.py CycleScope): the record of
        # this cycle covers entry to exit of run_once(), every lane of
        # it is a top-level span, and the record is sealed when the
        # scope closes — after the gc lane below.
        tracer = tracer_of(self.store, annotate=TraceAnnotation)
        with tracer.cycle(getattr(self.store, "flight", None)) as scope:
            gc_was_enabled = gc.isenabled()
            _full_passes.enter(self)
            if gc_was_enabled:
                gc.disable()
            try:
                self._run_once_inner()
            finally:
                _full_passes.leave()
                if gc_was_enabled:
                    gc.enable()
                    with scope.lane("gc"):
                        gc.collect(0)
                        if threading.get_ident() != self._loop_ident:
                            # _loop's pass waits for the period slack.
                            _full_passes.run_if_due()

    def _run_once_inner(self) -> None:
        # The frame run_once() opened on this thread.
        scope = tracer_of(self.store).cycle()
        with scope.lane("prologue"):
            conf = self._load_conf()
            action_names = [
                a.strip() for a in conf.actions.split(",") if a.strip()
            ]
            # Queued async-bind failures re-enter Pending (with backoff)
            # before the cycle snapshots — on this thread, for BOTH the
            # fast path and the object-session fallback (cache.go
            # errTasks resync).
            drain = getattr(self.store, "drain_bind_failures", None)
            if drain is not None:
                drain()
            # Work stealing (shard.py, ISSUE 16): an idle shard claims
            # the most-starved foreign queue BEFORE its cycle snapshots,
            # so the stolen backlog is schedulable this very cycle.
            if self.shard is not None:
                self.shard.maybe_steal(self.store)
            fast = self._fastpath_enabled() or self.shard is not None
            if fast:
                enable_compilation_cache()
                from .fastpath import run_cycle_fast
        with metrics.e2e_timer(), _device_trace():
            if fast:
                try:
                    if run_cycle_fast(self.store, conf, shard=self.shard):
                        return
                except Exception:
                    if self.shard is not None:
                        # The object session is not shard-aware: falling
                        # back would re-schedule every shard's queues
                        # from one thread and double-bind against the
                        # siblings' in-flight solves.  Fail the cycle
                        # loudly instead; the loop's failure accounting
                        # and healthy() surface it.
                        raise
                    if not self._fallback_sensible():
                        # At hyperscale the object session takes hours
                        # per cycle; silently "falling back" would stall
                        # scheduling while masking the device failure.
                        log.exception(
                            "Fast path failed and the cluster is too "
                            "large for the object-session fallback "
                            "(override with VOLCANO_TPU_FALLBACK=always)"
                        )
                        raise
                    log.exception(
                        "Fast path failed; falling back to object session"
                    )
                    # The failed cycle's record ends here; the object
                    # session's begins.
                    scope.split()
            if self.shard is not None:
                # Ineligible config (custom plugins / solver) under
                # sharding: there is no shard-aware fallback.  Loud
                # failure > silently double-scheduling foreign queues.
                raise RuntimeError(
                    "sharded scheduler requires a fast-path-eligible "
                    "configuration (VOLCANO_TPU_SHARDS=1 restores the "
                    "object-session fallback)"
                )
            with scope.lane("prologue"):
                # An in-flight pipelined solve must not survive into
                # the object session: its pods still read as Pending
                # there and would double-schedule when the fast path
                # later committed the stale assignment.  Abandoning is
                # safe — the pods re-place on whichever path runs this
                # cycle.
                from .pipeline import (
                    abandon_inflight,
                    abandon_inflight_plan,
                )

                abandon_inflight(self.store)
                # A parked rebalance plan is also fast-path-only state;
                # it mutates nothing until committed, so dropping it is
                # free.
                abandon_inflight_plan(self.store)
                # The object session snapshots pod RECORDS as scheduling
                # truth: force any deferred bind-record walks (node_name
                # on committed pods, normally applied post-cycle by the
                # bind dispatcher) before building it, or committed pods
                # read as unbound and double-schedule.
                apply_records = getattr(
                    self.store, "apply_pending_bind_records", None
                )
                if apply_records is not None:
                    apply_records()
            self._run_object_session(conf, action_names, scope)

    def _run_object_session(self, conf, action_names, scope) -> None:
        """One object-session cycle, traced + flight-recorded: the
        session's lanes (open, one per action, close) join the cycle's
        scope, which seals the record when run_once() leaves."""
        from .obs.recorder import CycleRecord

        tracer = scope.tracer
        lanes = scope.lanes
        scope.describe("object", None)
        ssn = None
        err = None
        try:
            with tracer.span("open", lanes=lanes):
                ssn = open_session(
                    self.store, conf.tiers, conf.configurations
                )
            try:
                for name in action_names:
                    action = get_action(name)
                    if action is None:
                        log.warning("Unknown action %s", name)
                        continue
                    with metrics.action_timer(name), tracer.span(
                            f"action:{name}", cat="action",
                            lanes=lanes, lane=name):
                        action.execute(ssn)
            finally:
                with tracer.span("close", lanes=lanes):
                    close_session(ssn)
        except BaseException as e:
            err = e
            raise
        finally:
            with scope.lane("record"):
                scope.submit(CycleRecord(
                    session=getattr(ssn, "uid", ""), path="object",
                    error=type(err).__name__ if err is not None else None,
                ))

    @staticmethod
    def _fastpath_enabled() -> bool:
        import os

        return os.environ.get("VOLCANO_TPU_FASTPATH", "1") != "0"

    # Above this tasks x nodes product the object-session fallback is
    # slower than retrying the fast path next period (the object walk is
    # O(tasks x nodes) Python).
    FALLBACK_MAX_WORK = 50_000_000

    def _fallback_sensible(self) -> bool:
        import os

        import numpy as np

        from .api import TaskStatus

        mode = os.environ.get("VOLCANO_TPU_FALLBACK", "auto")
        if mode == "always":
            return True
        if mode == "never":
            return False
        m = self.store.mirror
        # The object walk is O(pending tasks x nodes): a mostly-scheduled
        # large cluster with a handful of pending pods falls back fine.
        pending = int(np.count_nonzero(
            (m.p_status[:m.n_pods] == int(TaskStatus.Pending))
            & m.p_alive[:m.n_pods]
        ))
        return (pending * max(m.n_nodes, 1)) <= self.FALLBACK_MAX_WORK

    # ----------------------------------------------------------------- loop

    def run(self) -> None:
        """Start the periodic loop in a background thread (no-op when
        it is already running; restartable after ``stop()``)."""
        with self._lifecycle_lock:
            if self._thread is not None and self._thread.is_alive():
                return
            # A prior stop() left the event set; clear it under the
            # lifecycle lock (stop() sets it under the same lock) so the
            # fresh thread actually loops.
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True
            )
            self._thread.start()

    # Consecutive failed cycles before healthy() reports False (a crashed
    # TPU runtime is unrecoverable in-process; the health signal lets a
    # supervisor or the HA standby take over — SURVEY.md 5.3).
    UNHEALTHY_AFTER = 3

    def healthy(self) -> bool:
        return self._consecutive_failures < self.UNHEALTHY_AFTER

    # A full (generation-2) pass of the collector is due every N cycles
    # (the process's, whichever Scheduler ran them), or sooner where
    # the heap grows (_FullPasses, below): run_once() suspends the
    # collector while a cycle runs and the allocator starts no full
    # pass between cycles, so cyclic garbage is swept by the
    # scheduler, where the multi-second walk of a 100k-pod store's
    # object graph lies in front of no bind.
    GC_FULL_EVERY = 120

    def _loop(self):
        self._loop_ident = threading.get_ident()
        while not self._stop.is_set():
            t0 = time.time()
            try:
                if self.gate is None or self.gate():
                    self.run_once()
                    self._consecutive_failures = 0
                    # In the period slack, outside the cycle's record.
                    _full_passes.run_if_due()
                else:
                    # A standby runs no cycles; stale leader-era failures
                    # must not keep its health check red.
                    self._consecutive_failures = 0
            except Exception:
                self._consecutive_failures += 1
                log.exception(
                    "Scheduling cycle failed (%d consecutive)",
                    self._consecutive_failures,
                )
            elapsed = time.time() - t0
            self._stop.wait(max(self.schedule_period - elapsed, 0.0))
        self._loop_ident = None

    # stop(): how long to wait for the loop thread.  Cycles never block
    # on the device any more (the pipelined dispatch is asynchronous and
    # the fetch happens at cycle top), so a healthy thread exits within
    # one cycle; the bound covers a wedged device runtime.
    STOP_TIMEOUT = 30.0

    def stop(self, timeout: Optional[float] = None) -> None:
        """Stop the periodic loop and drain the pipelined dispatch.

        Joins the loop thread (it must die — a silently-leaked thread
        kept scheduling behind restarts), then abandons any in-flight
        device solve left parked between cycles: the solved pods are
        still Pending store-side, so nothing is lost — a restarted
        scheduler simply re-places them on its first cycle."""
        with self._lifecycle_lock:
            # Set inside the lifecycle lock: a concurrent run() could
            # otherwise clear the event between our set and the join,
            # leaving this stop() waiting 30 s on a thread that will
            # never exit.
            self._stop.set()
            t = self._thread
            if t is not None:
                t.join(self.STOP_TIMEOUT if timeout is None else timeout)
                if t.is_alive():
                    log.error(
                        "scheduler loop thread did not exit within "
                        "%.0fs; in-flight state NOT drained",
                        self.STOP_TIMEOUT if timeout is None else timeout,
                    )
                    return
                self._thread = None
        # Only after the thread is dead: its next cycle would take the
        # collector's policy up again, and the cycle thread owns the
        # in-flight handle while it runs.  A sharded loop drains only
        # its OWN slot — its siblings' parked solves are still live.
        from .pipeline import abandon_inflight, abandon_inflight_plan

        _full_passes.release(self)
        if self.shard is not None:
            abandon_inflight(self.store, shard=self.shard.index)
            if self.shard.runs_evictions:
                abandon_inflight_plan(self.store)
        else:
            abandon_inflight(self.store)
            abandon_inflight_plan(self.store)


class _FullPasses:
    """Who starts a full (generation-2) pass of the collector, and
    when: the scheduler, never the allocator's count.

    CPython starts a full pass when the objects promoted since the last
    one exceed a quarter of what that one found alive, wherever the
    allocation that trips the count happens to be: a store taking in
    100,000 pods walks its whole heap six times on the way up, inside
    ``add_pod``, and frees nothing.  One policy for the process, since
    the collector is the process's:

    - From the first cycle a ``Scheduler`` runs, the third threshold is
      out of reach (the first two stay what the caller had), so the
      allocator starts passes of generations 0 and 1 only.  A caller
      that switched the collector off, or gave the third threshold a
      value of its own, is left alone.  The Schedulers that have run a
      cycle hold the policy; when the last has stopped, been dropped or
      had its store closed, the threshold goes back to what was found.
    - A full pass is due when ``Scheduler.GC_FULL_EVERY`` cycles have
      run since the last one (``cycles``), or when the heap has doubled
      since (``growth``): the interpreter's count of allocated blocks
      (``sys.getallocatedblocks()``: every small object alive, cyclic
      garbage too, which only a pass frees) is at least twice what the
      last pass left, and ``GROWTH_FLOOR`` more.  So a store on its way
      up is walked once a doubling, one that takes in and lets go of
      the same 100,000 pods round after round not at all, and cyclic
      garbage cannot outgrow the heap the last pass left.  Both numbers
      are read, not kept: no census, nothing counted per object.
    - A due pass starts only while no cycle is open in the process and
      no holder's store has a bind on its way to the binder, at one of
      three places: the end of ``run_once()``'s ``gc`` lane, the bind
      worker's idle slot (``cache/bindqueue.py``), ``_loop``'s period
      slack.  It holds the lock, so a cycle that wants to start waits
      for it, as it would for the interpreter.
    """

    # CPython's own third threshold: another value is the caller's.
    THIRD_DEFAULT = 10
    OUT_OF_REACH = 10 ** 9
    # ``growth`` needs this many blocks more than the last pass left,
    # so that a young process does not collect every cycle: a pass
    # over so few objects is some milliseconds.
    GROWTH_FLOOR = 250_000

    def __init__(self):
        # Re-entrant: a pass, automatic or ours, may drop a holder on
        # the thread that holds the lock, and its weak reference's
        # callback takes it again.
        self._lock = threading.RLock()
        # guarded-by: _lock
        self._holders: Dict[int, weakref.ref] = {}
        self._installed = False     # the third threshold is ours
        self._open = 0      # cycles open in the process
        self._cycles = 0    # cycles since the last full pass
        self._live = 0      # allocated blocks the last full pass left

    # ------------------------------------------------------ the holders

    def enter(self, sched: "Scheduler") -> None:
        """A cycle of ``sched`` starts (before it switches the
        collector off, so that the caller's setting is what is read)."""
        with self._lock:
            self._open += 1
            key = id(sched)
            if key not in self._holders:
                if not self._holders:
                    from .cache.bindqueue import BindDispatcher

                    BindDispatcher.idle_slot = self.run_if_due
                self._holders[key] = weakref.ref(
                    sched, lambda _ref, key=key: self._drop(key))
            if not self._installed and gc.isenabled():
                first, second, third = gc.get_threshold()
                if third == self.THIRD_DEFAULT:
                    gc.set_threshold(first, second, self.OUT_OF_REACH)
                    self._installed = True

    def leave(self) -> None:
        """The cycle ends."""
        with self._lock:
            self._open -= 1
            self._cycles += 1

    def release(self, sched: Optional["Scheduler"] = None,
                store=None) -> None:
        """``sched`` stopped, or ``store`` closed: they hold the policy
        no longer (the next cycle takes it up again)."""
        with self._lock:
            for key, ref in list(self._holders.items()):
                holder = ref()
                if holder is None or holder is sched or (
                        store is not None and holder.store is store):
                    self._drop(key)

    def _drop(self, key: int) -> None:
        with self._lock:
            self._holders.pop(key, None)
            if self._holders:
                return
            from .cache.bindqueue import BindDispatcher

            BindDispatcher.idle_slot = None
            if self._installed:
                self._installed = False
                first, second, third = gc.get_threshold()
                if third == self.OUT_OF_REACH:      # still ours to undo
                    gc.set_threshold(first, second, self.THIRD_DEFAULT)

    # --------------------------------------------------------- the pass

    def _due(self) -> Optional[str]:
        if self._cycles >= Scheduler.GC_FULL_EVERY:
            return "cycles"
        if sys.getallocatedblocks() >= 2 * self._live + self.GROWTH_FLOOR:
            return "growth"
        return None

    def _handing_over(self) -> bool:
        with self._lock:
            holders = list(self._holders.values())
        for ref in holders:
            sched = ref()
            flush = getattr(getattr(sched, "store", None),
                            "flush_binds", None)
            if flush is not None and not flush(0):
                return True
        return False

    def run_if_due(self) -> bool:
        """Run a full pass if one is due and nothing stands in front of
        it: no open cycle, no bind on its way, a collector the caller
        left on.  Returns whether it ran."""
        with self._lock:
            if self._open or not gc.isenabled() or self._handing_over():
                return False
            # Asked last: counting the blocks of a 100,000-pod heap is
            # a third of a millisecond.
            reason = self._due()
            if reason is None:
                return False
            with scheduled_pass(reason):
                gc.collect()
            self._live = sys.getallocatedblocks()
            self._cycles = 0
            return True


_full_passes = _FullPasses()


def release_collector(store) -> None:
    """``store`` is closed: the Schedulers that ran cycles on it hold
    the collector's policy no longer (``ClusterStore.close()``)."""
    _full_passes.release(store=store)
