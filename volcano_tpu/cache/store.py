"""In-memory cluster store: the scheduler cache.

The TPU-native equivalent of ``pkg/scheduler/cache/cache.go``: a mutex-guarded
mirror of cluster state mutated through an event API (the analog of the
reference's informer event handlers, ``cache/event_handlers.go:178-731``),
producing a deep-copied ``ClusterInfo`` snapshot per scheduling cycle
(cache.go:652-730).  It is also the system of record for the control plane:
controllers and the scheduler communicate only through this store, mirroring
how the reference's planes communicate only through the API server.

Bind/Evict mirror cache.go:439-554: they update the cached pod and dispatch to
the pluggable Binder/Evictor; failures resync the task from the store
(errTasks semantics, cache.go:627-649, simplified to synchronous resync).
"""

from __future__ import annotations

import copy
import functools
import threading
import time
from typing import Callable, Dict, List, Optional

from ..api import (
    GROUP_NAME_ANNOTATION,
    NAMESPACE_WEIGHT_KEY,
    ClusterInfo,
    JobInfo,
    NamespaceInfo,
    Node,
    NodeInfo,
    Pod,
    PodGroup,
    PodGroupCondition,
    PodGroupPhase,
    PodPhase,
    PriorityClass,
    Queue,
    QueueInfo,
    ResourceQuota,
    TaskInfo,
    TaskStatus,
    pod_key,
)
from .interface import (
    Binder,
    Evictor,
    FakeBinder,
    FakeEvictor,
    FakeStatusUpdater,
    FakeVolumeBinder,
    StatusUpdater,
    VolumeBinder,
)

from ..obs.trace import (EVENT_KINDS, POD_ADD, POD_DELETE, POD_UPDATE,
                         SAMPLE_STRIDE, BetweenAccount)

DEFAULT_QUEUE = "default"


def _event(kind: str):
    """Marks a public event handler of ``ClusterStore`` other than the
    three pod handlers (which do the same inline, phase by phase): the
    call is counted under the store lock, and one call in
    ``obs.trace.SAMPLE_STRIDE`` of ``kind`` is timed, the wait for the
    lock apart from the time it is held (``CycleRecord.between``)."""

    k = EVENT_KINDS.index(kind)

    def mark(handler):
        @functools.wraps(handler)
        def counted(self, arg):
            bt = self._between
            st = None if bt.counts[k] % SAMPLE_STRIDE else bt.sample(k)
            try:
                with self._lock:
                    bt.counts[k] += 1
                    if st is not None:
                        st.mark("lock_wait")
                    return handler(self, arg)
            finally:
                if st is not None:
                    st.close("held")

        return counted

    return mark


class ClusterStore:
    """Mutex-guarded cluster state mirror + snapshotter."""

    def __init__(
        self,
        binder: Optional[Binder] = None,
        evictor: Optional[Evictor] = None,
        status_updater: Optional[StatusUpdater] = None,
        volume_binder: Optional[VolumeBinder] = None,
        default_queue: str = DEFAULT_QUEUE,
    ):
        self._lock = threading.RLock()
        self._jobs: Dict[str, JobInfo] = {}
        self._nodes: Dict[str, NodeInfo] = {}
        # The fast path (volcano_tpu.fastpath) commits directly to the pod
        # records + array mirror and marks the derived JobInfo/NodeInfo
        # object model stale; it stays stale, untouched by the event
        # handlers below, until somebody reads ``jobs`` / ``nodes``.
        self._objects_stale = False
        # Handler calls that skipped the object model: since it went
        # stale (``store:rebuild_objects`` ``args.stale_events``), and
        # since a cycle record last took them (``CycleRecord.
        # object_model``).
        self._stale_events = 0  # guarded-by: _lock
        self._stale_events_cycle = 0  # guarded-by: _lock
        self.queues: Dict[str, QueueInfo] = {}
        self.priority_classes: Dict[str, PriorityClass] = {}
        self.namespace_weights: Dict[str, int] = {}
        # Raw spec objects (system of record for controllers):
        self.pods: Dict[str, Pod] = {}  # guarded-by: _lock
        self.pod_groups: Dict[str, PodGroup] = {}
        self.raw_queues: Dict[str, Queue] = {}
        # Controller-plane records (the reference stores these as CRDs /
        # core objects in the API server).
        self.batch_jobs: Dict[str, object] = {}  # key -> controllers.apis.Job
        self.commands: Dict[str, object] = {}  # name -> Command
        self.config_maps: Dict[str, Dict[str, str]] = {}  # ns/name -> data
        self.secrets: Dict[str, Dict[str, bytes]] = {}  # ns/name -> data
        self.services: Dict[str, Dict[str, object]] = {}  # ns/name -> spec
        # ns/name -> ingress-isolation spec (NetworkPolicy analog).
        self.network_policies: Dict[str, Dict[str, object]] = {}
        # Count of live pods carrying volume claims: the fast path's
        # commit gate is O(bound pods) when any exist, so claim-free
        # clusters must skip on an O(1) check that cannot miss a
        # volume-carrying pod (unlike gating on the claim registry,
        # which a custom volume binder need not use).
        self.n_volume_pods = 0  # guarded-by: _lock
        # ns/name -> persistent-volume-claim record
        # {"spec", "phase" Pending|Bound, "node", "owner_job"} — the PVC
        # store the job controller creates into (initiateJob PVCs,
        # job_controller_actions.go:394-531) and the volume binder
        # allocates/binds against (cache.go:557-564).
        self.pvcs: Dict[str, Dict[str, object]] = {}  # guarded-by: _lock

        self.binder: Binder = binder or FakeBinder()
        self.evictor: Evictor = evictor or FakeEvictor()
        self.status_updater: StatusUpdater = status_updater or FakeStatusUpdater()
        self.volume_binder: VolumeBinder = (
            volume_binder or StoreVolumeBinder(self)
        )

        # Watchers notified on spec mutations (the controllers' "informers").
        self._watchers: List[Callable[[str, str, object], None]] = []

        # Incremental struct-of-arrays mirror (the TPU-native snapshot
        # serializer's state; see cache/mirror.py).
        from .mirror import StoreMirror

        self.mirror = StoreMirror()
        self.mirror.attach(self.pods)

        # Async bind dispatch + rate-limited bind-failure resync
        # (cache.go:536-552 goroutine binds; 627-649 errTasks).  Sync by
        # default so tests observe binds immediately after a cycle;
        # the production service enables async.
        self.async_bind = False
        self._bind_dispatcher = None
        self._bind_fail_lock = threading.Lock()
        # Successful binds whose backoff entries the cycle thread should
        # clear at the next drain (tracked only while bind_backoff is
        # non-empty, so steady-state binds pay nothing).
        self._succeeded_bind_keys: List[str] = []  # guarded-by: _bind_fail_lock
        # [(key, pod), ...] reported by the dispatcher thread.
        self._failed_bind_keys: List[tuple] = []  # guarded-by: _bind_fail_lock
        # "ns/name" -> (consecutive fails, retry-not-before ts, pod uid).
        # Cycle-thread-owned: mutated only by drain_bind_failures and
        # delete_pod (both under _lock); the dispatcher thread queues
        # clears via _succeeded_bind_keys instead of touching it.
        self.bind_backoff: Dict[str, tuple] = {}  # guarded-by: _lock

        # Per-object user-visible event trail (the reference records
        # Kubernetes Events for Evict/Scheduled/FailedScheduling/
        # Unschedulable — cache.go:487,540,584,790).  Key: "Kind/ns/name";
        # value: list of [reason, message, count, first_ts, last_ts],
        # deduplicated k8s-style on (reason, message).
        # OrderedDict, NOT dict: FIFO eviction at MAX_EVENT_OBJECTS needs
        # O(1) popitem(last=False).  Popping a plain dict's first key via
        # next(iter(...)) re-scans the growing tombstone prefix — 53 us
        # per event at cap (quadratic overall), measured dominating the
        # config-4 close lane.
        import collections as _collections

        # guarded-by: _events_lock
        self._events: "_collections.OrderedDict[str, List[list]]" = (
            _collections.OrderedDict()
        )
        self._events_lock = threading.Lock()
        # Whole batches parked by record_events_deferred, folded into
        # the trails at the next read/record (off the cycle's clock).
        self._deferred_events: List[tuple] = []  # guarded-by: _events_lock

        # Deferred bind-record walks not yet materialized (see
        # defer_bind_records): registered at commit time so failure
        # paths can force them before reading pod records.
        self._record_walk_lock = threading.Lock()
        # guarded-by: _record_walk_lock
        self._pending_record_walks: List[list] = []

        # Parked dispatched-but-uncommitted device solve (pipeline.py
        # InflightSolve): written by the cycle thread at dispatch,
        # popped at the next cycle's top — but also reachable from
        # store.close()/Scheduler.stop() on other threads, so the slot
        # itself is lock-guarded (vclint VCL101/102 enforces this).
        self._inflight_solve = None  # guarded-by: _lock (any-receiver)
        # Parked dispatched-but-uncommitted rebalance plan (pipeline.py
        # InflightPlan): same ownership/locking contract as the solve
        # slot above.
        self._inflight_plan = None  # guarded-by: _lock (any-receiver)
        # Per-shard parked solves (shard.py, ISSUE 16): shard index ->
        # InflightSolve.  The default single-scheduler path never
        # touches this dict — it keeps using _inflight_solve above, so
        # VOLCANO_TPU_SHARDS=1 stays bitwise identical.  Same
        # any-receiver locking contract as the default slot
        # (cycle threads park/pop their own entry; close()/stop()
        # drain from other threads).
        self._shard_inflight: Dict[int, object] = {}  # guarded-by: _lock (any-receiver)
        # Shard ownership table (shard.ShardOwnershipTable), attached by
        # ShardedScheduler; None for the single-scheduler path.  The
        # table's mutable state (steal overrides + handoff epoch) is
        # itself guarded by THIS store's _lock — see shard.py contracts.
        self.shard_table = None  # guarded-by: _lock (any-receiver)
        # Mesh-path persistent plane cache (parallel/mesh.py
        # shard_wave_inputs): epoch-keyed per-device placements of the
        # epoch-stable planes the sharded devsnap does not own (e.g.
        # aff.node_dom).  Written by the cycle thread (FastCycle runs
        # under _lock), cleared by close() and pod-table compaction —
        # a declared, lock-guarded slot, not an ad-hoc attribute.
        self._mesh_plane_cache: Dict = {}  # guarded-by: _lock (any-receiver)
        # Incremental host-lane caches (ISSUE 8, fastpath.py /
        # fastpath_incr.py): content-validated results the steady-state
        # cycle reuses instead of re-deriving — the job-order rank (+
        # its key columns), the pending-task order, the encode-lane
        # profile/affinity structures, the commit path's object arrays,
        # the feed lane's unbind request gather, and the close lane's
        # gang gauge lists.  All written and read ONLY by the cycle
        # thread under the store lock (FastCycle class-holds) and
        # dropped on close(); each carries the mirror versions
        # (mutation-driven content, compact_gen/epoch keys) its entries
        # are valid under — the VCL50x keyed-cache contract.
        self._job_rank_cache = None  # guarded-by: _lock (any-receiver)
        self._pending_order_cache = None  # guarded-by: _lock (any-receiver)
        self._encode_cache = None  # guarded-by: _lock (any-receiver)
        self._objarr_cache = None  # guarded-by: _lock (any-receiver)
        self._unbind_gather_cache = None  # guarded-by: _lock (any-receiver)
        self._close_gang_cache = None  # guarded-by: _lock (any-receiver)
        # Device-lane incremental context (ISSUE 9, ops/devincr.py):
        # persistent [U, C] static planes + warm-shortlist candidates +
        # the null-delta skip proof, keyed on mirror versions
        # (epoch / compact_gen / node_liveness_gen) and content tokens
        # (class-table sig, profile generation, cnt0 hash) assembled by
        # FastCycle._devincr_prepare.  Cycle-thread only, under _lock.
        self._devincr_cache = None  # guarded-by: _lock (any-receiver)
        # High-water marks of the solve's data-dependent shape buckets
        # (ops/wave.settle: padded terms, profile rows, profiles and
        # terms per wave, sparse entry lists), so that the shapes a
        # round's terms give the jitted programs do not move between
        # rounds.  Cycle-thread only, under _lock; for the store's
        # life, but for a device memory exhaustion, which drops them
        # with the chunk budget (FastCycle._on_device_crash).
        self._solve_shape_marks: Dict[str, int] = {}  # guarded-by: _lock (any-receiver)

        # Migration ledger (actions/rebalance.py MigrationLedger),
        # attached by the rebalance lane's first committed plan; the
        # delete_pod hook below restores terminating victims through it.
        self.migrations = None

        # Remote-solver client: a solver_service.RemoteSolver (single
        # connection) or a solver_pool.SolverPool (N replicas with
        # hedged dispatch / failover / what-if offload, ISSUE 15) —
        # attached by Service and tests, None for local-solve stores.
        # Dispatch and fetch run only on the cycle thread; both client
        # types synchronize their own internals (each holds its own
        # lock, never the store's), so the slot needs no store-lock
        # guard beyond the cycle thread's ownership.
        self.remote_solver = None

        # Observability (obs/, ISSUE 3): the per-store span tracer and
        # the cycle flight recorder.  Both are internally synchronized
        # (the recorder's ring lock nests strictly inside _lock and is
        # never taken around store state); stdlib-only, so wiring them
        # unconditionally costs two small objects per store.
        from ..obs import (Auditor, FlightRecorder, JourneyLog,
                           SLOTracker, Tracer, journey_on)

        self.tracer = Tracer()
        # The account of the time between two cycles: every event
        # handler below counts itself in it, the cycle's frame seals it
        # into ``CycleRecord.between`` (obs/trace.py).
        self._between = BetweenAccount(self.tracer)
        self.flight = FlightRecorder()
        # Runtime conservation auditor + SLO layer (obs/audit.py,
        # obs/slo.py, ISSUE 13): internally synchronized like the
        # recorder (the auditor's lock nests strictly inside _lock and
        # is never taken around store state).  The mirror's writers
        # declare pod-count flows through mirror.audit; the fast cycle
        # reconciles + samples at cycle end.
        self.auditor = Auditor()
        self.auditor.slo = SLOTracker()
        self.mirror.audit = self.auditor
        # Pod-journey tracing (obs/journey.py, ISSUE 18): the
        # pod-centric event timeline behind /debug/pods/<uid>, the
        # per-queue time-to-bind latency feeds, and the endurance
        # conservation check.  Internally synchronized like the auditor
        # (its lock nests strictly inside _lock and is never taken
        # around store state).  Kill switch VOLCANO_TPU_JOURNEY=0
        # leaves the slot None so hot paths pay one attribute load.
        self.journey = (JourneyLog(slo=self.auditor.slo,
                                   auditor=self.auditor)
                        if journey_on() else None)
        self.mirror.journey = self.journey
        self.mirror.between = self._between
        # Runtime lock enforcement (obs/lockdep.py, VOLCANO_TPU_LOCKDEP=1):
        # wraps this store's object graph so `# guarded-by:` annotations
        # are asserted live.  A no-op (one env read) when the switch is
        # off.
        from ..obs.lockdep import enable_lockdep

        enable_lockdep(self)
        # Monotonic pipelined solve-id: the flow link between a
        # dispatch span in cycle N and its commit spans in cycle N+1.
        self._solve_seq = 0  # guarded-by: _lock

        # Create the default queue at startup, weight 1 (cache.go:244-254).
        self.add_queue(Queue(name=default_queue, weight=1))

    # ------------------------------------------------------------- events

    EVENTS_PER_OBJECT = 16
    # Hyperscale guard: the event map sheds its oldest objects beyond this
    # (500k-pod snapshots would otherwise pin hundreds of MB of trails).
    MAX_EVENT_OBJECTS = 100_000

    def record_event(self, key: str, reason: str, message: str) -> None:
        """Append a user-visible event to an object's trail
        (``key`` = "Kind/ns/name", e.g. "Pod/default/job-a-0")."""
        import time as _time

        now = _time.time()
        with self._events_lock:
            self._drain_deferred_events_locked()
            self._record_event_locked(key, reason, message, now)

    def _record_event_locked(self, key, reason, message, now) -> None:
        if (key not in self._events
                and len(self._events) >= self.MAX_EVENT_OBJECTS):
            self._events.popitem(last=False)
        trail = self._events.setdefault(key, [])
        for ev in trail:
            if ev[0] == reason and ev[1] == message:
                ev[2] += 1
                ev[4] = now
                return
        trail.append([reason, message, 1, now, now])
        if len(trail) > self.EVENTS_PER_OBJECT:
            del trail[0]

    def record_events(self, items) -> None:
        """Batched ``record_event``: one lock acquisition and one clock
        read for a whole commit's worth of (key, reason, message) tuples.
        The reference's event recorder is likewise an async batcher the
        bind goroutines feed (cache.go:540); at 100k binds/cycle the
        per-call lock + clock overhead is what the batch amortizes."""
        import time as _time

        now = _time.time()
        items = items if isinstance(items, list) else list(items)
        if len(items) >= self.MAX_EVENT_OBJECTS:
            # Bulk fast path (100k bind Scheduled events): inserting N >>
            # cap distinct keys one at a time evicts every pre-existing
            # trail AND the first N-cap batch entries — identical end
            # state to clearing and keeping the batch tail.  Only taken
            # when the batch alone overflows the cap with distinct keys.
            tail: Dict[str, List[list]] = {}
            for key, reason, message in reversed(items):
                if key not in tail:
                    tail[key] = [[reason, message, 1, now, now]]
                    if len(tail) >= self.MAX_EVENT_OBJECTS:
                        break
            if len(tail) >= self.MAX_EVENT_OBJECTS:
                with self._events_lock:
                    # Parked deferred batches are older than this bulk:
                    # the clear below would evict them anyway; drop them
                    # so a later drain cannot resurrect them out of
                    # order.
                    self._deferred_events.clear()
                    self._events.clear()
                    # reversed() above collected newest-first; restore
                    # insertion order oldest-first for FIFO eviction.
                    self._events.update(reversed(tail.items()))
                return
        with self._events_lock:
            self._drain_deferred_events_locked()
            for key, reason, message in items:
                self._record_event_locked(key, reason, message, now)

    def record_events_deferred(self, items) -> None:
        """O(1) enqueue of a whole event batch; the per-event trail
        bookkeeping (~2 us each — 90 ms for a config-4 eviction cycle's
        45k events) runs at the next read/record instead of inside the
        scheduling cycle.  The reference's event recorder is likewise an
        async broadcaster the control loops feed."""
        import time as _time

        with self._events_lock:
            self._deferred_events.append((_time.time(), items))

    def _drain_deferred_events_locked(self) -> None:
        if not self._deferred_events:
            return
        batches, self._deferred_events = self._deferred_events, []
        for now, items in batches:
            for key, reason, message in items:
                self._record_event_locked(key, reason, message, now)

    def events_for(self, key: str) -> List[dict]:
        with self._events_lock:
            self._drain_deferred_events_locked()
            return [
                {"reason": r, "message": m, "count": c,
                 "first_seen": f, "last_seen": l}
                for r, m, c, f, l in self._events.get(key, [])
            ]

    # -------------------------------------------------- async bind machinery

    def defer_bind_records(self, keys_a, hosts_a, pods_a) -> list:
        """Register a deferred bind batch (numpy object arrays).  The
        100k-element tolist + pod.node_name record walk runs when the
        batch is materialized — normally on the bind dispatcher's worker
        thread, post-cycle (the reference's API-server-side NodeName
        write, cache.go:536-552) — but any failure path that is about to
        read pod RECORDS as scheduling truth must force it first via
        ``apply_pending_bind_records`` (committed-but-unnamed pods would
        read as unbound and double-schedule)."""
        entry = [keys_a, hosts_a, pods_a, False]
        with self._record_walk_lock:
            self._pending_record_walks.append(entry)
        return entry

    def _materialize_bind_entry(self, entry: list):
        """Idempotent: lists + node_name walk applied exactly once, from
        whichever thread gets here first."""
        with self._record_walk_lock:
            if not entry[3]:
                keys = entry[0].tolist()
                hosts = entry[1].tolist()
                pods = entry[2].tolist()
                for pod, hostname in zip(pods, hosts):
                    pod.node_name = hostname
                entry[0], entry[1], entry[2] = keys, hosts, pods
                entry[3] = True
                # Remove by IDENTITY, never list.remove: remove scans
                # with ==, and comparing this entry against a DIFFERENT
                # pending entry compares their numpy object arrays
                # elementwise — the ambiguous-truth ValueError that was
                # previously swallowed here left the entry stranded,
                # and apply_pending_bind_records (which loops until the
                # list drains) then never terminated.
                for i, e in enumerate(self._pending_record_walks):
                    if e is entry:
                        del self._pending_record_walks[i]
                        break
            return entry[0], entry[1], entry[2]

    def apply_pending_bind_records(self) -> None:
        """Synchronously apply every registered deferred record walk —
        called before any path that treats pod records as scheduling
        truth (mirror resync, the object-session fallback)."""
        while True:
            with self._record_walk_lock:
                if not self._pending_record_walks:
                    return
                entry = self._pending_record_walks[0]
            self._materialize_bind_entry(entry)

    def dispatch_binds(self, keys, hosts, pods,
                       entry: Optional[list] = None) -> None:
        """Queue a batch of binds on the background dispatcher (the
        goroutine analog); failures surface at the next cycle's
        ``drain_bind_failures``.  ``entry`` marks a deferred batch from
        ``defer_bind_records``: the worker materializes it at process
        time (pass keys/hosts/pods as None)."""
        if self._bind_dispatcher is None:
            from .bindqueue import BindDispatcher

            self._bind_dispatcher = BindDispatcher(
                self.binder, self._on_bind_failures,
                on_success=self._on_bind_success,
                materialize=self._materialize_bind_entry,
                tracer=self.tracer,
            )
        self._bind_dispatcher.dispatch(keys, hosts, pods, entry=entry)

    def flush_binds(self, timeout: Optional[float] = None) -> bool:
        if self._bind_dispatcher is None:
            return True
        return self._bind_dispatcher.flush(timeout)

    def close(self) -> None:
        """Stop background machinery (the bind dispatcher thread).  The
        dispatcher's callbacks pin this store, so long-lived processes
        creating many stores must close them."""
        from ..pipeline import abandon_inflight, abandon_inflight_plan
        from ..scheduler import release_collector

        # A parked pipelined solve holds device buffers (or a remote
        # solver's reply slot); drop it with the store.  A parked
        # rebalance plan mutates nothing until committed — drop it too.
        abandon_inflight(self)
        abandon_inflight_plan(self)
        # Its Schedulers hold the collector's policy no longer.
        release_collector(self)
        with self._lock:
            # Mesh plane cache pins per-device arrays across cycles;
            # a closed store must release them with everything else.
            self._mesh_plane_cache.clear()
            # Host-lane caches pin large arrays (and pod records, via
            # the object arrays); a closed store must not.
            self._job_rank_cache = None
            self._pending_order_cache = None
            self._encode_cache = None
            self._objarr_cache = None
            self._unbind_gather_cache = None
            self._close_gang_cache = None
            # Device-incremental planes pin device buffers (static
            # planes + shortlist candidates); release them too.
            self._devincr_cache = None
        if self._bind_dispatcher is not None:
            self._bind_dispatcher.stop()
            self._bind_dispatcher = None

    def _on_bind_failures(self, failed_pairs) -> None:
        """Dispatcher-thread hook: ``failed_pairs`` is [(key, pod), ...]."""
        with self._bind_fail_lock:
            self._failed_bind_keys.extend(failed_pairs)

    def _on_bind_success(self, keys: List[str], hosts: List[str]) -> None:
        """Dispatcher-thread hook: record Scheduled events (cache.go:540).
        Backoff clears are queued for the cycle thread (``bind_backoff``
        is cycle-thread-owned; popping it here could lose a concurrent
        ``drain_bind_failures`` increment)."""
        # vclint: disable=VCL101 -- dispatcher-thread truthiness probe
        # of the cycle-thread-owned dict; a stale read only delays when
        # clears are queued, and drain_bind_failures reconciles.  Taking
        # _lock here would block this thread for a whole cycle.
        if self.bind_backoff:
            with self._bind_fail_lock:
                self._succeeded_bind_keys.extend(keys)
        # One lock for the whole batch: this runs on the dispatcher
        # thread concurrently with the next scheduling cycle, and per-pod
        # lock churn at 100k binds starves the cycle thread of the GIL.
        self.record_events(
            (f"Pod/{key}", "Scheduled", f"bound to {host}")
            for key, host in zip(keys, hosts)
        )

    def drain_bind_failures(self) -> int:
        """Apply queued bind failures: the task re-enters Pending with an
        exponential backoff window during which the solver skips it (the
        rate-limited errTasks retry, cache.go:627-649).  Runs on the
        scheduling-cycle thread so all mirror mutation stays there."""
        import time as _time

        from .bindqueue import BACKOFF_BASE, BACKOFF_MAX

        with self._bind_fail_lock:
            failed = self._failed_bind_keys
            self._failed_bind_keys = []
            succeeded = self._succeeded_bind_keys
            self._succeeded_bind_keys = []
        if succeeded:
            with self._lock:
                for key in succeeded:
                    self.bind_backoff.pop(key, None)
        if not failed:
            return 0
        now = _time.time()
        n = 0
        with self._lock:
            for key, pod in failed:
                # Skip stale entries: the pod may have been replaced
                # (copy-on-write) or removed since the dispatch.
                if (pod is None or self.pods.get(pod.uid) is not pod
                        or pod.node_name is None):
                    continue
                fails, _, _ = self.bind_backoff.get(key, (0, 0.0, ""))
                fails += 1
                delay = min(BACKOFF_BASE * (2 ** (fails - 1)), BACKOFF_MAX)
                self.bind_backoff[key] = (fails, now + delay, pod.uid)
                pod.node_name = None
                if pod.volumes:
                    # Bind never landed: free the claims it pinned.
                    self.release_claims_for(pod)
                self.mirror.set_pod_state(
                    pod.uid, int(TaskStatus.Pending), -1
                )
                self.mark_objects_stale()
                self.record_event(
                    f"Pod/{key}", "FailedScheduling",
                    f"bind failed; retry in {delay:.0f}s "
                    f"(attempt {fails})",
                )
                # Watchers (job/podgroup controllers) must recount: the
                # commit already notified a bind for this pod before the
                # outcome was known.
                self._notify("Pod", "update", pod)
                n += 1
        return n

    # ----------------------------------------------- lazy object model

    @property
    def jobs(self) -> Dict[str, JobInfo]:
        if self._objects_stale:
            self._rebuild_objects()
        return self._jobs

    @property
    def nodes(self) -> Dict[str, NodeInfo]:
        if self._objects_stale:
            self._rebuild_objects()
        return self._nodes

    def mark_objects_stale(self) -> None:
        """Called by the fast path after a bulk commit: the JobInfo /
        NodeInfo object model is void, and stays so until a reader asks
        for ``jobs`` / ``nodes`` (``snapshot()`` included), which
        rebuilds it from the pod records.

        While it is stale the event handlers keep only what
        ``_rebuild_objects`` reads (the pod and pod-group tables, the
        priority classes, the mirror's node and job rows) and leave the
        model alone: no TaskInfo per event, and no rebuild on the first
        event after a commit.  An ``add_pod`` / ``update_pod`` that
        would raise out of ``_add_task`` on a fresh model
        (``NodeInfo.add_task`` on an over-subscribed or NotReady node)
        is taken without error while stale; the rebuild logs the same
        divergence when somebody reads.

        Going stale lets go of the model: kept, it would be the last
        holder of every pod record it knew, deleted ones included, for
        as long as nobody reads."""
        with self._lock:
            if not self._objects_stale:
                self._objects_stale = True
                self._jobs = {}
                self._nodes = {}

    # holds: _lock
    def _skip_objects(self) -> bool:
        """An event handler's question before it touches the object
        model: true, and counted, while the model is stale."""
        if not self._objects_stale:
            return False
        self._stale_events += 1
        self._stale_events_cycle += 1
        return True

    def take_object_model_counts(self) -> Dict[str, int]:
        """``CycleRecord.object_model``, taken at a cycle's start: is
        the model stale, and how many handler calls skipped it since
        the last cycle took the count."""
        with self._lock:
            n, self._stale_events_cycle = self._stale_events_cycle, 0
            out = {"stale": int(self._objects_stale), "stale_events": n}
            m = self.mirror
            if len(m.terms):
                # The mirror's inter-pod term tables are append-only:
                # every term ever interned against those that still
                # have a member row (absent while none was interned).
                out["terms_interned"] = len(m.terms)
                out["terms_live"] = int(m.terms_live)
            return out

    def _rebuild_objects(self) -> None:
        """Recompute the JobInfo/NodeInfo object model from pods + pod
        groups (the same construction the informer replay performs,
        cache.go:376-417).  Job insertion order follows the mirror's row
        order = original arrival order, keeping dict-iteration behavior
        aligned with the incremental path."""
        with self._lock:
            if not self._objects_stale:
                return
            t0 = time.perf_counter_ns()
            self._objects_stale = False
            stale_events, self._stale_events = self._stale_events, 0
            self._nodes = {}
            for row, name in enumerate(self.mirror.n_name):
                if name is not None and self.mirror.n_alive[row]:
                    self._nodes[name] = NodeInfo(self.mirror.node_objs[row])
            self._jobs = {}
            for uid in self.mirror.j_uid:
                pg = self.pod_groups.get(uid) if uid else None
                if pg is None:
                    continue
                job = JobInfo(uid)
                job.set_pod_group(pg)
                pc = self._priority_class_of(pg)
                if pc is not None:
                    job.priority = pc.value
                self._jobs[uid] = job
            for pod in self.pods.values():
                try:
                    self._add_task(pod)
                except (ValueError, KeyError) as err:
                    # Over-subscription here means upstream divergence;
                    # record and keep rebuilding (resync semantics).
                    import logging

                    logging.getLogger(__name__).error(
                        "rebuild: failed to re-add task %s: %s", pod.uid, err
                    )
            # Only a reader of ``jobs``/``nodes`` pays this, on its own
            # thread, in a cycle or between two: a parentless event on
            # its own track, drained with the next cycle's record.
            self.tracer.event(
                "store:rebuild_objects", "store", t0,
                time.perf_counter_ns() - t0, tid="store",
                args={"pods": len(self.pods),
                      "stale_events": stale_events})

    def _priority_class_of(self, pg: PodGroup) -> Optional[PriorityClass]:
        if not pg.priority_class:
            return None
        return self.priority_classes.get(pg.priority_class)

    # ------------------------------------------------------------- watchers

    def watch(self, fn: Callable[[str, str, object], None]) -> None:
        """Register fn(kind, event, obj) called after each mutation."""
        self._watchers.append(fn)

    def _notify(self, kind: str, event: str, obj: object) -> None:
        for fn in self._watchers:
            fn(kind, event, obj)

    # ------------------------------------------------------- job bookkeeping

    def _get_or_create_job(self, job_id: str) -> JobInfo:
        job = self.jobs.get(job_id)
        if job is None:
            job = JobInfo(job_id)
            self.jobs[job_id] = job
        return job

    def _add_task(self, pod: Pod) -> None:
        ti = TaskInfo(pod)
        if ti.job:
            job = self._get_or_create_job(ti.job)
            job.add_task_info(ti)
        # Terminated pods hold no node resources (the reference filters
        # them out of node accounting, event_handlers.go isTerminated).
        if ti.status in (TaskStatus.Succeeded, TaskStatus.Failed):
            return
        if ti.node_name:
            node = self.nodes.get(ti.node_name)
            if node is None:
                # Task on an unknown node: hold a placeholder so accounting
                # catches up when the node arrives (event_handlers.go addTask).
                node = NodeInfo(None)
                node.name = ti.node_name
                self.nodes[ti.node_name] = node
            fresh = ti.clone()
            fresh.node_name = ""
            node.add_task(fresh)

    def _remove_task(self, pod: Pod) -> None:
        job_id = pod.job_id()
        job = self.jobs.get(job_id) if job_id else None
        if job is not None:
            ti = job.tasks.get(pod.uid)
            if ti is not None:
                job.delete_task_info(ti)
        if pod.node_name:
            node = self.nodes.get(pod.node_name)
            if node is not None:
                probe = TaskInfo(pod)
                if pod_key(pod) in node.tasks:
                    node.remove_task(probe)

    # --------------------------------------------------------- pod handlers

    def add_pod(self, pod: Pod) -> None:
        """Track a pod.  Ungrouped pods (no group annotation) still occupy
        node resources when bound (the reference tracks ANY pod with a
        NodeName, cache.go:320-332); they only lack a schedulable job until
        the podgroup controller wraps them.

        The three pod handlers count themselves in the store's account
        of the time between two cycles (``CycleRecord.between``): every
        call adds one to its kind's count under the lock, and the call
        the count picks, one in ``obs.trace.SAMPLE_STRIDE``, is timed
        phase by phase (``st``, which the mirror is handed for that one
        call).  The other calls pay the add, the test of the count and
        a test of ``st`` at each stamp: 44 bytecodes more than the 2,421
        of an add without them, 40 more than a delete's 591
        (docs/tracing.md, Overhead).

        The pod's spec is encoded by the first pod that brings it and
        by no other (``StoreMirror._feat``; the account's
        ``specs_encoded`` counts those), and its ragged features are
        written then, as the spec's row of the mirror's spec table
        (``_spec_add``; ``spec_rows`` is the table's size): as PR 47
        left the tree an add of a spec the mirror has met executes
        1,733 bytecodes, parses no quantity and appends to no ragged
        column, the first add of a spec 2,718; PR 46's tree read 2,443
        and 2,899 by the same count, and every add 2,746 before it."""
        bt = self._between
        st = (None if bt.counts[POD_ADD] % SAMPLE_STRIDE
              else bt.sample(POD_ADD))
        try:
            with self._lock:
                bt.counts[POD_ADD] += 1
                if st is not None:
                    st.mark("lock_wait")
                self.pods[pod.uid] = pod
                if pod.volumes:
                    self.n_volume_pods += 1
                if not self._skip_objects():
                    self._add_task(pod)
                if st is not None:
                    st.mark("objects")
                self.mirror.upsert_pod(pod, self.mirror.job_row, st)
                self._notify("Pod", "add", pod)
        finally:
            if st is not None:
                st.close("notify")

    def update_pod(self, pod: Pod) -> None:
        bt = self._between
        st = (None if bt.counts[POD_UPDATE] % SAMPLE_STRIDE
              else bt.sample(POD_UPDATE))
        try:
            with self._lock:
                bt.counts[POD_UPDATE] += 1
                if st is not None:
                    st.mark("lock_wait")
                old = self.pods.get(pod.uid)
                fresh = not self._skip_objects()
                if old is not None:
                    if fresh:
                        self._remove_task(old)
                    if old.volumes:
                        self.n_volume_pods -= 1
                self.pods[pod.uid] = pod
                if pod.volumes:
                    self.n_volume_pods += 1
                if fresh:
                    self._add_task(pod)
                if st is not None:
                    st.mark("objects")
                self.mirror.upsert_pod(pod, self.mirror.job_row, st)
                self._notify("Pod", "update", pod)
        finally:
            if st is not None:
                st.close("notify")

    def delete_pod(self, pod: Pod) -> None:
        bt = self._between
        st = (None if bt.counts[POD_DELETE] % SAMPLE_STRIDE
              else bt.sample(POD_DELETE))
        try:
            with self._lock:
                bt.counts[POD_DELETE] += 1
                if st is not None:
                    st.mark("lock_wait")
                old = self.pods.pop(pod.uid, None)
                fresh = not self._skip_objects()
                if old is not None:
                    if fresh:
                        self._remove_task(old)
                    if old.volumes:
                        self.n_volume_pods -= 1
                if self.bind_backoff:
                    # Deleted pods must not pin backoff entries forever.
                    self.bind_backoff.pop(
                        f"{pod.namespace}/{pod.name}", None
                    )
                if st is not None:
                    st.mark("objects")
                gen0 = self.mirror.compact_gen
                self.mirror.remove_pod(pod.uid, st)
                cached = self._objarr_cache
                if cached is not None and cached[0][1] != self.mirror.pod_obj_gen:
                    # The removal moved pod_obj_gen, so the commit path's
                    # object arrays can never be served again; kept, they
                    # would be the last holder of every pod deleted before
                    # the next cycle, whose rebuild (fastpath._obj_arrays,
                    # under the device solve) would free them all at once.
                    self._objarr_cache = None
                self.mirror.maybe_compact()
                if self.mirror.compact_gen != gen0 and self._mesh_plane_cache:
                    # Compaction renumbers rows and voids in-flight device
                    # state wholesale; parked mesh placements resync too.
                    self._mesh_plane_cache.clear()
                self._notify("Pod", "delete", pod)
                if self.migrations is not None and old is not None:
                    # A terminating rebalance victim restores as a fresh
                    # Pending pod (add_pod re-enters the re-entrant lock).
                    self.migrations.pod_deleted(self, old)
        finally:
            if st is not None:
                st.close("notify")

    # -------------------------------------------------------- node handlers

    @_event("Node/add")
    def add_node(self, node: Node) -> None:
        with self._lock:
            self._set_node(node)
            self.mirror.upsert_node(node)
            self._notify("Node", "add", node)

    @_event("Node/update")
    def update_node(self, node: Node) -> None:
        with self._lock:
            self._set_node(node)
            self.mirror.upsert_node(node)
            self._notify("Node", "update", node)

    # holds: _lock
    def _set_node(self, node: Node) -> None:
        if self._skip_objects():
            return
        existing = self.nodes.get(node.name)
        if existing is None:
            self.nodes[node.name] = NodeInfo(node)
        else:
            existing.set_node(node)

    @_event("Node/delete")
    def delete_node(self, name: str) -> None:
        with self._lock:
            if not self._skip_objects():
                self.nodes.pop(name, None)
            self.mirror.remove_node(name)
            self._notify("Node", "delete", name)

    # --------------------------------------------------- pod group handlers

    @_event("PodGroup/add")
    def add_pod_group(self, pg: PodGroup) -> None:
        with self._lock:
            self._set_pod_group(pg)
            self._notify("PodGroup", "add", pg)

    @_event("PodGroup/update")
    def update_pod_group(self, pg: PodGroup) -> None:
        with self._lock:
            self._set_pod_group(pg)
            self._notify("PodGroup", "update", pg)

    # holds: _lock
    def _set_pod_group(self, pg: PodGroup) -> None:
        self.pod_groups[pg.uid] = pg
        pc = self._priority_class_of(pg)
        if self._skip_objects():
            # What the rebuild will give the job.
            priority = pc.value if pc is not None else 0
        else:
            job = self._get_or_create_job(pg.uid)
            job.set_pod_group(pg)
            if pc is not None:
                job.priority = pc.value
            priority = job.priority
        self.mirror.upsert_pod_group(pg, priority)

    @_event("PodGroup/delete")
    def delete_pod_group(self, uid: str) -> None:
        with self._lock:
            self.pod_groups.pop(uid, None)
            job = None if self._skip_objects() else self.jobs.get(uid)
            if job is not None:
                job.unset_pod_group()
                if not job.tasks:
                    del self.jobs[uid]
            self.mirror.remove_pod_group(uid)
            self._notify("PodGroup", "delete", uid)

    # ------------------------------------------------------- queue handlers

    @_event("Queue/add")
    def add_queue(self, queue: Queue) -> None:
        with self._lock:
            self.raw_queues[queue.name] = queue
            self.queues[queue.name] = QueueInfo(queue)
            self._notify("Queue", "add", queue)

    @_event("Queue/update")
    def update_queue(self, queue: Queue) -> None:
        with self._lock:
            self.raw_queues[queue.name] = queue
            self.queues[queue.name] = QueueInfo(queue)
            self._notify("Queue", "update", queue)

    @_event("Queue/delete")
    def delete_queue(self, name: str) -> None:
        with self._lock:
            self.raw_queues.pop(name, None)
            self.queues.pop(name, None)
            self._notify("Queue", "delete", name)

    # ------------------------------------------- priority class / quota

    @_event("PriorityClass/add")
    def add_priority_class(self, pc: PriorityClass) -> None:
        with self._lock:
            self.priority_classes[pc.name] = pc
            self._notify("PriorityClass", "add", pc)

    @_event("PriorityClass/delete")
    def delete_priority_class(self, name: str) -> None:
        with self._lock:
            self.priority_classes.pop(name, None)
            self._notify("PriorityClass", "delete", name)

    @_event("ResourceQuota/add")
    def add_resource_quota(self, quota: ResourceQuota) -> None:
        """Track namespace weight from the quota annotation
        (event_handlers.go quota path + namespace_info.go:33-37)."""
        with self._lock:
            raw = quota.annotations.get(NAMESPACE_WEIGHT_KEY)
            if raw is not None:
                try:
                    self.namespace_weights[quota.namespace] = max(
                        self.namespace_weights.get(quota.namespace, 0), int(raw)
                    )
                except ValueError:
                    pass
            self._notify("ResourceQuota", "add", quota)

    # ---------------------------------------------------- controller plane

    @_event("Job/add")
    def add_batch_job(self, job) -> None:
        with self._lock:
            self.batch_jobs[job.key] = job
            self._notify("Job", "add", job)

    @_event("Job/update")
    def update_batch_job(self, job) -> None:
        with self._lock:
            self.batch_jobs[job.key] = job
            self._notify("Job", "update", job)

    @_event("Job/delete")
    def delete_batch_job(self, key: str) -> None:
        with self._lock:
            job = self.batch_jobs.pop(key, None)
            if job is not None:
                self._notify("Job", "delete", job)

    @_event("Command/add")
    def add_command(self, command) -> None:
        with self._lock:
            self.commands[command.name] = command
            self._notify("Command", "add", command)

    @_event("Command/delete")
    def delete_command(self, name: str) -> None:
        with self._lock:
            self.commands.pop(name, None)

    def put_config_map(self, ns: str, name: str, data: Dict[str, str]) -> None:
        with self._lock:
            self.config_maps[f"{ns}/{name}"] = dict(data)

    def delete_config_map(self, ns: str, name: str) -> None:
        with self._lock:
            self.config_maps.pop(f"{ns}/{name}", None)

    def put_secret(self, ns: str, name: str, data) -> None:
        with self._lock:
            self.secrets[f"{ns}/{name}"] = dict(data)

    def delete_secret(self, ns: str, name: str) -> None:
        with self._lock:
            self.secrets.pop(f"{ns}/{name}", None)

    def put_service(self, ns: str, name: str, spec) -> None:
        with self._lock:
            self.services[f"{ns}/{name}"] = spec

    def delete_service(self, ns: str, name: str) -> None:
        with self._lock:
            self.services.pop(f"{ns}/{name}", None)

    def put_pvc(self, ns: str, name: str, spec,
                owner_job: str = "") -> None:
        """Create/replace a claim record (phase Pending until the volume
        binder binds it)."""
        with self._lock:
            self.pvcs[f"{ns}/{name}"] = {
                "spec": dict(spec) if spec else {},
                "phase": "Pending",
                "node": None,
                "owner_job": owner_job,
            }

    def delete_pvc(self, ns: str, name: str) -> None:
        with self._lock:
            self.pvcs.pop(f"{ns}/{name}", None)

    def release_claims_for(self, pod) -> None:
        """Roll back a failed bind's claim state: claims this pod
        provisioned/bound return to Pending (free to provision anywhere)
        unless another placed pod still references them.  Without this a
        bind failure would pin the claim to the failed node forever and
        the pod could never re-place elsewhere."""
        if not pod.volumes:
            return
        with self._lock:
            claims = {f"{pod.namespace}/{c}" for c, _ in pod.volumes}
            still_held = set()
            for other in self.pods.values():
                if (other.uid == pod.uid or not other.volumes
                        or other.node_name is None):
                    continue
                for c, _ in other.volumes:
                    k = f"{other.namespace}/{c}"
                    if k in claims:
                        still_held.add(k)
            for k in claims - still_held:
                rec = self.pvcs.get(k)
                if rec is not None:
                    rec["phase"] = "Pending"
                    rec["node"] = None

    def delete_pvcs_owned_by(self, job_key: str) -> int:
        """Owner-reference cleanup: claims created by the controller for
        a job die with the Job object (createPVC sets an owner ref,
        job_controller_actions.go:512-531)."""
        with self._lock:
            doomed = [k for k, rec in self.pvcs.items()
                      if rec.get("owner_job") == job_key]
            for k in doomed:
                del self.pvcs[k]
        return len(doomed)

    def put_network_policy(self, ns: str, name: str, spec) -> None:
        """Job-scoped ingress isolation record (the NetworkPolicy the
        reference svc plugin creates, svc.go:252-299)."""
        with self._lock:
            self.network_policies[f"{ns}/{name}"] = spec

    def delete_network_policy(self, ns: str, name: str) -> None:
        with self._lock:
            self.network_policies.pop(f"{ns}/{name}", None)

    # -------------------------------------------------------------- snapshot

    def snapshot(self) -> ClusterInfo:
        """Deep-copied point-in-time view (cache.go:652-730)."""
        with self._lock:
            info = ClusterInfo()
            for name, node in self.nodes.items():
                info.nodes[name] = node.clone()
            for name, queue in self.queues.items():
                info.queues[name] = queue.clone()
            namespaces = set()
            for job_id, job in self.jobs.items():
                # Jobs without a PodGroup are not schedulable yet
                # (cache.go snapshot skips jobs with missing PodGroup).
                if job.pod_group is None:
                    continue
                info.jobs[job_id] = job.clone()
                namespaces.add(job.namespace)
            for ns in namespaces:
                info.namespace_info[ns] = NamespaceInfo(
                    ns, self.namespace_weights.get(ns, 1)
                )
            return info

    # ------------------------------------------------------------ side effects

    # holds: _lock
    def _replace_pod(self, pod, **mutations):
        """Copy-on-write pod replacement: the stored Pod is replaced,
        never mutated, so snapshot TaskInfos holding the old Pod keep
        their point-in-time view.  Re-indexes the job task sets and the
        mirror; returns the new record.  Caller holds the lock."""
        fresh = not self._skip_objects()
        if fresh:
            self._remove_task(pod)
        pod = copy.copy(pod)
        for name, value in mutations.items():
            setattr(pod, name, value)
        self.pods[pod.uid] = pod
        if fresh:
            self._add_task(pod)
        self.mirror.upsert_pod(pod, self.mirror.job_row)
        return pod

    def bind(self, task: TaskInfo, hostname: str) -> None:
        """Bind task's pod to a host (cache.go:492-554, synchronous
        here)."""
        with self._lock:
            pod = self.pods.get(task.uid)
            if pod is None:
                raise KeyError(f"unknown pod {task.uid}")
            self.binder.bind(task, hostname)
            pod = self._replace_pod(pod, node_name=hostname)
            self.record_event(
                f"Pod/{pod.namespace}/{pod.name}", "Scheduled",
                f"bound to {hostname}",
            )
            self._notify("Pod", "bind", pod)

    def evict(self, task: TaskInfo, reason: str) -> None:
        """Evict task's pod (cache.go:439-489, synchronous here)."""
        with self._lock:
            pod = self.pods.get(task.uid)
            if pod is None:
                raise KeyError(f"unknown pod {task.uid}")
            # Mark the cached pod as terminating: resources become
            # Releasing.
            pod = self._replace_pod(pod, deleting=True)
            try:
                self.evictor.evict(pod)
            except Exception:
                # Evict dispatch failed (EvictFailure or a transport
                # error): the pod is NOT terminating.  Revert the record
                # (cache.go:461-466 resyncTask) and let the next cycle
                # re-select victims.
                pod = self._replace_pod(pod, deleting=False)
                self.record_event(
                    f"Pod/{pod.namespace}/{pod.name}", "EvictFailed",
                    "evict dispatch failed; will retry",
                )
                self._notify("Pod", "update", pod)
                return
            self.record_event(
                f"Pod/{pod.namespace}/{pod.name}", "Evict",
                reason or "evicted by scheduler",
            )
            self._notify("Pod", "evict", pod)

    def allocate_volumes(self, task: TaskInfo, hostname: str) -> None:
        self.volume_binder.allocate_volumes(task, hostname)

    def bind_volumes(self, task: TaskInfo) -> None:
        self.volume_binder.bind_volumes(task)

    def update_job_status(self, job: JobInfo) -> JobInfo:
        """Write PodGroup status back (interface.go UpdateJobStatus +
        job_updater.go semantics)."""
        with self._lock:
            pg = job.pod_group
            if pg is None:
                return job
            stored = self.pod_groups.get(pg.uid)
            if stored is not None:
                stored.status = pg.status
                # Keep the mirror's persistent status-snapshot columns
                # coherent: the fast path's write-back change detection
                # reads them as "last written" state.
                self.mirror.refresh_pod_group_status(stored)
                self.status_updater.update_pod_group(stored)
                self._notify("PodGroup", "status", stored)
            return job

    def record_job_condition(self, job: JobInfo, condition: PodGroupCondition) -> None:
        if job.pod_group is None:
            return
        with self._lock:
            # Write to the *stored* PodGroup (the snapshot may share or hold
            # its own reference); replace same-type condition, mirroring
            # jobUpdater behavior.
            pg = self.pod_groups.get(job.pod_group.uid, job.pod_group)
            conditions = [c for c in pg.status.conditions if c.type != condition.type]
            conditions.append(condition)
            pg.status.conditions = conditions
            self.mirror.refresh_pod_group_status(pg)

    # --------------------------------------------------------------- helpers

    def pending_pods(self) -> List[Pod]:
        with self._lock:
            return [
                p
                for p in self.pods.values()
                if p.phase == PodPhase.Pending and not p.node_name
            ]

    def task_in_store(self, uid: str) -> Optional[Pod]:
        with self._lock:
            return self.pods.get(uid)


class StoreVolumeBinder:
    """Volume binder against the store's claim registry (the
    defaultVolumeBinder of cache.go:211-222, backed by ``store.pvcs``
    instead of the upstream scheduler volume binder).

    Accepts either a TaskInfo or a bare Pod (the fast path hands pods);
    pods with no ``volumes`` cost one attribute read."""

    def __init__(self, store: "ClusterStore"):
        self._store = store

    @staticmethod
    def _pod(task):
        return getattr(task, "pod", task)

    def allocate_volumes(self, task, hostname: str) -> None:
        from .interface import VolumeBindFailure

        pod = self._pod(task)
        with self._store._lock:
            for claim, _mount in pod.volumes:
                rec = self._store.pvcs.get(f"{pod.namespace}/{claim}")
                if rec is None:
                    raise VolumeBindFailure(
                        f"claim {pod.namespace}/{claim} not found for "
                        f"{pod.name}"
                    )
                if rec["phase"] == "Pending":
                    # WaitForFirstConsumer analog: the claim provisions
                    # on the node the scheduler picked.
                    rec["node"] = hostname
                elif rec["node"] not in (None, hostname):
                    # Already provisioned elsewhere: node-local claims
                    # can't follow the pod (RWO pinned to another host).
                    raise VolumeBindFailure(
                        f"claim {pod.namespace}/{claim} is bound to "
                        f"{rec['node']}, pod placed on {hostname}"
                    )

    def bind_volumes(self, task) -> None:
        pod = self._pod(task)
        with self._store._lock:
            for claim, _mount in pod.volumes:
                rec = self._store.pvcs.get(f"{pod.namespace}/{claim}")
                if rec is not None:
                    rec["phase"] = "Bound"
        if hasattr(task, "volume_ready"):
            task.volume_ready = True
