"""Vectorized scheduling cycle: the TPU-native fast path.

The object-model session (``framework/session.py``) reproduces the
reference's per-object semantics (``pkg/scheduler/framework/session.go``)
but pays O(cluster) Python work per cycle: a deep-copied snapshot, heap
orderings that dispatch a plugin comparator per comparison, and a per-task
replay of the solver's assignment matrix.  This module is the same cycle —
enqueue, allocate, backfill, session close — expressed over the store's
incremental array mirror (``cache/mirror.py``):

- aggregates (node idle/used, queue allocation, DRF shares, job readiness
  counters) are derived by ``np.add.at``/``bincount`` reductions over the
  pod table instead of object traversals;
- job/queue/namespace orderings precompute one key tuple per job; the
  object path's PriorityQueue pops over total-ordered keys (unique uid
  tie-break) reduce to sorted-list merging (``allocate.go:107-153``), so
  the produced order matches the object path bit-for-bit;
- the assignment matrix from the wave solver is committed in bulk: array
  scatter updates, one batched bind dispatch, and pod records mutated in
  place; the NodeInfo/JobInfo object model is marked stale and lazily
  rebuilt from pods on next access (the fast path itself never reads it);
- pod-group status write-back replicates ``close_session``
  (``framework/framework.go`` jobStatus) and the gang plugin's
  OnSessionClose conditions (``gang.go:140-183``).

Eligibility (``eligible()``): actions within ``FAST_ACTIONS``
({enqueue, allocate, backfill, preempt, reclaim} — preempt/reclaim
dispatch to ``fastpath_evict``), plugins within ``FAST_PLUGINS`` (the
eight built-ins), and the wave solver selected.  Anything else — custom
plugins, unknown actions, solver=sequential — falls back to the object
path, which remains the semantic reference (custom predicate /
node-order / device-mask callbacks still reach the device solver there,
via ``actions/allocate.py``).
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from .api import (
    PodGroupCondition,
    PodGroupPhase,
    TaskStatus,
    TOPOLOGY_REQUIRE,
)
from .api.resource import (
    MIN_MEMORY,
    MIN_MILLI_CPU,
    MIN_MILLI_SCALAR,
    Resource,
)
from .arrays.affinity import (
    AffinityArgs,
    CountEntries,
    count_entries,
    count_entries_of,
    empty_affinity,
)
from .framework.arguments import Arguments, get_action_args
from .framework.framework import POD_GROUP_UNSCHEDULABLE
from .framework.session import _session_counter
from .metrics import metrics
from .obs.trace import tracer_of
from .ops.allocate import SolveJobs, SolveNodes, SolveQueues, SolveTasks
from .ops.scoring import ScoreWeights

log = logging.getLogger(__name__)

F = np.float32
I = np.int32

FAST_ACTIONS = {"enqueue", "allocate", "backfill", "preempt", "reclaim",
                "rebalance"}
FAST_PLUGINS = {
    "priority", "gang", "conformance", "drf", "proportion",
    "predicates", "nodeorder", "binpack",
}

ST_PENDING = int(TaskStatus.Pending)
ST_BOUND = int(TaskStatus.Bound)
ST_BINDING = int(TaskStatus.Binding)
ST_RUNNING = int(TaskStatus.Running)
ST_ALLOCATED = int(TaskStatus.Allocated)
ST_RELEASING = int(TaskStatus.Releasing)
ST_SUCCEEDED = int(TaskStatus.Succeeded)
ST_FAILED = int(TaskStatus.Failed)
ST_UNKNOWN = int(TaskStatus.Unknown)

_ALLOCATED_STATUSES = (ST_BOUND, ST_BINDING, ST_RUNNING, ST_ALLOCATED)

# PodGroup phase coding for the cycle's j_phase array (5 = any other
# phase; 0 = no PodGroup).  _close writes back phases only through
# _PHASE_BY_CODE, so code 5 is never produced as a NEW phase.
_PHASE_CODE = {
    PodGroupPhase.Pending.value: 1,
    PodGroupPhase.Inqueue.value: 2,
    PodGroupPhase.Running.value: 3,
    PodGroupPhase.Unknown.value: 4,
}
_PHASE_BY_CODE = {
    1: PodGroupPhase.Pending.value,
    2: PodGroupPhase.Inqueue.value,
    3: PodGroupPhase.Running.value,
    4: PodGroupPhase.Unknown.value,
}
# Vector form for the close write-back (codes 1-4 only; index 0/5 unused).
_PHASE_STR_BY_CODE = np.array(
    ["", _PHASE_BY_CODE[1], _PHASE_BY_CODE[2], _PHASE_BY_CODE[3],
     _PHASE_BY_CODE[4], ""], object,
)


def _devsnap_counts(store) -> Tuple[int, int, int, int, int]:
    """The running counters of the store's device snapshot
    (ops/devsnap.py): full uploads, delta uploads, hits, host->device
    puts and their bytes; zeros before the first solve builds it."""
    snap = getattr(store, "device_snapshot", None)
    if snap is None:
        return (0, 0, 0, 0, 0)
    return (snap.full_uploads, snap.delta_uploads, snap.hits,
            snap.puts, snap.put_bytes)


def _host_bytes(tree) -> Tuple[int, int]:
    """(count, bytes) of the numpy leaves of ``tree``: what a jitted
    call given ``tree`` transfers host->device (device-resident leaves
    move nothing)."""
    n = nbytes = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, np.ndarray):
            n += 1
            nbytes += leaf.nbytes
    return n, nbytes


def _pow2(n: int, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _pack_bits(n_rows: int, words: int, rows: np.ndarray,
               bits: np.ndarray) -> np.ndarray:
    """Vectorized bitset packing: set ``bits`` in the given rows."""
    out = np.zeros((n_rows, words), np.uint32)
    if len(rows):
        flat = rows.astype(np.int64) * words + (bits >> 5)
        np.bitwise_or.at(
            out.reshape(-1), flat,
            (np.uint32(1) << (bits & 31).astype(np.uint32)),
        )
    return out


def _epoch_cached(m, attr: str, key, build):
    """Node-table cache on the mirror: rebuild via ``build()`` when
    ``key`` (epoch + shape/width components) changed.  Cached arrays are
    write-protected so an in-place mutation of a handed-out reference
    fails loudly instead of corrupting every later cycle."""
    cached = getattr(m, attr, None)
    if cached is not None and cached[0] == key:
        return cached[1:]
    arrays = build()
    for a in arrays:
        a.setflags(write=False)
    setattr(m, attr, (key, *arrays))
    return arrays


def _cmp_key(less):
    """sorted() key from a strict less(a, b) comparator."""
    import functools

    return functools.cmp_to_key(
        lambda a, b: -1 if less(a, b) else (1 if less(b, a) else 0)
    )


def _vec_le(l: np.ndarray, r: np.ndarray, eps: np.ndarray,
            scalar_slot: np.ndarray) -> bool:
    """Epsilon-tolerant Resource.less_equal on dense slot vectors."""
    per = (l < r) | (np.abs(l - r) < eps) | (scalar_slot & (l <= eps))
    return bool(per.all())


def _vec_is_empty(v: np.ndarray, eps: np.ndarray) -> bool:
    return bool((v < eps).all())


class _JobProxy:
    """Just enough of JobInfo for the ordering algorithm."""

    __slots__ = ("row", "uid", "namespace", "queue", "key")

    def __init__(self, row, uid, namespace, queue, key):
        self.row = row
        self.uid = uid
        self.namespace = namespace
        self.queue = queue
        self.key = key


class FastCycle:
    """One vectorized scheduling cycle over the store mirror."""

    # The single entry point (run_cycle_fast) wraps the whole cycle in
    # ``with store._lock``, so every method below runs with the store
    # lock held.
    # vclint: class-holds: _lock

    def __init__(self, store, conf, shard=None):
        self.store = store
        self.conf = conf
        self.m = store.mirror
        # Sharded control plane (shard.py, ISSUE 16): this cycle's
        # shard.ShardContext, or None on the default single-scheduler
        # path (which must stay bitwise identical — every shard branch
        # below is behind `self.shard is not None`).  The session uid
        # carries the shard index so /debug/cycles and the flight
        # recorder attribute cycles per shard for free.
        self.shard = shard
        n = next(_session_counter)
        self.uid = (f"ssn-{n}" if shard is None
                    else f"ssn-{n}@s{shard.index}")
        # Per-shard solver client override: each shard may own its own
        # device lane (service wiring); falls back to the
        # store-wide client.  Resolved once per cycle — both slots are
        # cycle-thread-owned, so no lock is needed beyond ownership.
        self._remote_solver = getattr(store, "remote_solver", None)
        if shard is not None and shard.remote_solver is not None:
            self._remote_solver = shard.remote_solver
        self.action_names = [
            a.strip() for a in conf.actions.split(",") if a.strip()
        ]
        self.plugin_opts: Dict[str, object] = {}
        self._tier_opts_cache: Dict[str, list] = {}
        for tier in conf.tiers:
            for opt in tier.plugins:
                self.plugin_opts.setdefault(opt.name, opt)
        # Pipelined sessions (ISSUE 1): the device solve is dispatched
        # without blocking and committed at the top of the NEXT cycle,
        # hiding the device round trip behind the host lanes.  Opt in
        # per store (service flag) or globally via env.
        flag = getattr(store, "pipeline", None)
        if flag is None:
            flag = os.environ.get("VOLCANO_TPU_PIPELINE", "0") == "1"
        self._pipeline_on = bool(flag)
        # Span tracer (obs/trace.py, ISSUE 3): the cycle's lanes, the
        # pipelined dispatch→fetch→commit chain, and the staleness
        # guard all record spans; a null tracer keeps bare test stores
        # working.  The tracer itself is stdlib-only: the profiler's
        # annotation factory is handed in here (vc:<lane> annotations,
        # inert unless a profiler trace runs).
        self.tracer = tracer_of(store, annotate=TraceAnnotation)
        # The last ``dispatched`` stamp's (rows, uids), for the commit
        # of the same rows (``_journey_bound``).
        self._journey_uids = None

    # --------------------------------------------------------- eligibility

    def eligible(self) -> bool:
        if not set(self.action_names) <= FAST_ACTIONS:
            return False
        if not set(self.plugin_opts) <= FAST_PLUGINS:
            return False
        args = get_action_args(self.conf.configurations, "allocate")
        if args and args.get_str("solver", "wave") != "wave":
            # The exact sequential solver needs dense per-task affinity
            # inputs; the object path provides them.
            return False
        return True

    def _tier_opts(self, flag: str):
        # Config is immutable for the cycle; the evict comparators consult
        # this hundreds of thousands of times, so cache per flag.
        cache = self._tier_opts_cache
        hit = cache.get(flag)
        if hit is None:
            hit = cache[flag] = [
                opt
                for tier in self.conf.tiers
                for opt in tier.plugins
                if getattr(opt, flag, None)
            ]
        return hit

    def _has(self, name: str) -> bool:
        return name in self.plugin_opts

    # ---------------------------------------------------------- derivation

    def derive(self) -> None:
        """Compute per-cycle aggregates from the pod table.

        The heavy pod-axis reductions no longer rerun from scratch each
        cycle: they live in the mirror's persistent ``CycleAggregates``
        (fastpath_incr.py, ISSUE 8), refreshed by subtract-old/add-new
        delta scatters over the mirror's dirty row set — with a proven
        full-rebuild fallback on node-membership churn, compaction, dirty
        overflow, or ``VOLCANO_TPU_INCREMENTAL=0``.  The cycle works on
        COPIES of the persistent planes; its own mutations (commit,
        unbind, evictions) mark rows dirty and reconcile at the NEXT
        derive."""
        from .fastpath_incr import (
            ALLOC_COLS,
            COL,
            aggregates_of,
            incremental_on,
        )

        m = self.m
        self.Pn = Pn = m.n_pods
        self.Nn = Nn = m.n_nodes
        self.R = R = 2 + len(m.scalar_slots)
        self.jobr = m.p_job[:Pn]

        self.slot_names = ["cpu", "memory"] + list(m.scalar_slots.items)
        self.eps = np.full((R,), MIN_MILLI_SCALAR, F)
        self.eps[0] = MIN_MILLI_CPU
        self.eps[1] = MIN_MEMORY
        self.scalar_slot = np.ones((R,), bool)
        self.scalar_slot[:2] = False

        # Node allocatable (dense); rebuilt only when the node table
        # changed (mirror epoch) — the per-cycle CSR gather costs ~5 ms
        # at 10k nodes.
        def _build_alloc():
            alloc = np.zeros((Nn, R), F)
            if Nn:
                csr_rows = m.node_csr_rows(np.arange(Nn))
                er, si, v = m.c_n_alloc.gather(csr_rows)
                alloc[er, si] = v
            return (alloc,)

        (self.n_alloc,) = _epoch_cached(
            m, "_node_alloc_cache", (m.epoch, Nn, R), _build_alloc
        )
        self.n_alive = m.n_alive[:Nn].copy() if Nn else np.zeros(0, bool)
        self.n_ready = (m.n_ready[:Nn] & self.n_alive) if Nn else np.zeros(0, bool)
        self.n_maxtasks = m.n_maxtasks[:Nn].astype(I)

        # Persistent aggregates: resident mask, node usage planes, the
        # per-(job x status) count table, and the per-job resource
        # sums, delta-refreshed from the dirty set (or rebuilt).
        aggr = aggregates_of(m)
        self.aggr = aggr
        # One env read per cycle: VOLCANO_TPU_INCREMENTAL=0 kills the
        # whole incremental host-lane machinery — the aggregate delta
        # refresh AND the order/encode/commit/close caches below.
        self._incr = incremental_on()
        self.derive_mode = aggr.refresh(m, Pn, Nn, R, self.n_alive)
        # Sampled coherence audit of the refreshed planes (ISSUE 13):
        # HERE, right after refresh, the persistent aggregates equal
        # mirror truth by construction — by cycle end they lag the
        # cycle's own commits, so this is the only honest audit point.
        auditor = getattr(self.store, "auditor", None)
        if auditor is not None and auditor.enabled:
            auditor.audit_aggregates_now(m)
        # Device-lane incrementality (ISSUE 9): fold this derive's
        # changed-node capture into the store's DeviceIncremental — the
        # warm-shortlist diff is against the previous SOLVE, which may
        # be several derives back (skip cycles consume empty sets in
        # between).  A full derive poisons the accumulator, so the next
        # solve provably re-ranks fully.
        from .ops.devincr import devincr_on, of_store

        if devincr_on():
            of_store(self.store).accumulate_dirty(
                aggr.last_dirty_nodes if self.derive_mode == "delta"
                else None
            )
        # The cycle's working copies stay float32 (the evict lane's C
        # engine and the solver uploads are 32-bit contracts); the
        # PERSISTENT planes are float64 so the delta arithmetic is
        # exact, and both refresh modes cast the identical f64 values,
        # so the f32 copies are bit-for-bit across modes too.
        self.resident = aggr.resident[:Pn].copy()
        self.n_used = aggr.n_used.astype(F)  # includes releasing
        self.n_releasing = aggr.n_releasing.astype(F)
        self.n_idle = self.n_alloc - self.n_used
        self.n_ntasks = aggr.n_ntasks.astype(I)

        # The eight per-job status counters are column reductions of the
        # persistent count table (exact integers, so the delta path is
        # bit-for-bit with the rebuild).
        self.Jn = Jn = len(m.j_uid)
        sc = aggr.js_counts
        self.j_cnt_alloc = sc[:, ALLOC_COLS].sum(axis=1).astype(I)
        self.j_cnt_succ = sc[:, COL[ST_SUCCEEDED]].astype(I)
        self.j_cnt_fail = sc[:, COL[ST_FAILED]].astype(I)
        self.j_cnt_run = sc[:, COL[ST_RUNNING]].astype(I)
        self.j_cnt_pending = sc[:, COL[ST_PENDING]].astype(I)
        self.j_cnt_empty_pending = aggr.j_empty_pending.astype(I)
        self.j_cnt_total = sc.sum(axis=1).astype(I)
        self.j_cnt_releasing = sc[:, COL[ST_RELEASING]].astype(I)
        self.j_cnt_other = (
            self.j_cnt_total - self.j_cnt_alloc - self.j_cnt_succ
            - self.j_cnt_fail - self.j_cnt_pending - self.j_cnt_releasing
        )
        # ready_task_num (job_info.go:329-348).
        self.j_ready_base = (
            self.j_cnt_alloc + self.j_cnt_succ + self.j_cnt_empty_pending
        )
        # valid_task_num (job_info.go:351-366): allocated|succeeded|pending.
        self.j_valid = self.j_cnt_alloc + self.j_cnt_succ + self.j_cnt_pending

        # Per-job allocated/pending resources (DRF + proportion):
        # float64 persistent planes — resource quantities are integral
        # (milli-CPU / bytes), so the delta scatters are exact — cast
        # to the cycle's f32 working dtype.
        self.j_alloc_res = aggr.j_alloc_res.astype(F)
        self.j_pending_res = aggr.j_pending_res.astype(F)

        # Queues (sorted by name: matches the array encoder's layout).
        self.queue_names = sorted(self.store.queues.keys())
        self.queue_index = {n: i for i, n in enumerate(self.queue_names)}
        self.Qn = len(self.queue_names)
        # Queue-of-job via the mirror's interned queue codes: one small
        # code->index LUT instead of a 12k-job dict-lookup loop.
        lut = np.full(max(len(m.qnames), 1), -1, I)
        for code, nm in enumerate(m.qnames.items):
            qi = self.queue_index.get(nm)
            if qi is not None:
                lut[code] = qi
        self.q_of_job = (
            lut[m.j_queue_code[:Jn]] if Jn else np.full(0, -1, I)
        )

        self.total_res = self.n_alloc[self.n_alive].sum(axis=0) if Nn else np.zeros(R, F)

        # Session job set: jobs with a live PodGroup (snapshot semantics:
        # cache.go snapshot skips jobs with no PodGroup).  flatnonzero,
        # NOT a per-row Python loop — the 12k-iteration interpreter walk
        # sat on the hot cycle thread (ISSUE 8 satellite); every
        # consumer takes it through np.asarray.
        self.session_jobs = np.flatnonzero(m.j_alive[:Jn])
        # Sharded control plane (ISSUE 16): restrict the session to this
        # shard's owned queues.  This is the ONE seam the per-shard
        # mirror view hangs off — _schedulable_rows/_pending_rows/
        # enqueue/backfill/close all derive from session_jobs, while the
        # node planes above stay shared (whole-cluster capacity).
        if self.shard is not None:
            self.session_jobs = self.shard.filter_session_jobs(
                self, self.session_jobs
            )
        # PodGroup refs + status snapshot come straight from the mirror's
        # incrementally-maintained columns (every store add/update
        # funnels through upsert_pod_group) instead of a 45k-object walk
        # per derive.  j_phase codes (_PHASE_CODE): 0 = missing,
        # 1 = Pending, 2 = Inqueue, 3 = Running, 4 = Unknown, 5 = other.
        # The VIEWS alias the mirror arrays on purpose: the cycle's
        # in-place transitions (enqueue's Pending -> Inqueue) and the
        # close write-back update "last written" state that must persist
        # across cycles.
        self.j_pgs = m.j_pg
        self.j_phase = m.j_phase_code[:Jn]
        self.j_st_run = m.j_st_run[:Jn]
        self.j_st_fail = m.j_st_fail[:Jn]
        self.j_st_succ = m.j_st_succ[:Jn]

    # ---------------------------------------------------------- resources

    def _res(self, vec: np.ndarray) -> Resource:
        r = Resource(float(vec[0]), float(vec[1]))
        for i, name in enumerate(self.slot_names[2:], start=2):
            if vec[i]:
                r.set_scalar(name, float(vec[i]))
        return r

    # -------------------------------------------------------------- shares

    def _flush_aggr(self) -> None:
        """Apply deferred per-job/per-queue resource scatter updates.

        _commit defers the j_alloc_res / j_pending_res / q_alloc scatter
        adds (three 200k-entry np.add.at calls at north-star scale) because
        the typical single-round cycle never reads them again; consumers
        that can observe post-commit values flush first.  In-place add.at
        keeps captured references (e.g. _overused_fn's alloc) coherent."""
        pend = getattr(self, "_aggr_pending", None)
        if not pend:
            return
        self._aggr_pending = []
        R = self.R
        for jr_er, si, v, q_er in pend:
            # bincount over flattened (row, slot) indices — several
            # times faster than np.add.at at steady-state entry counts
            # (same exact sums for the integral resource quantities).
            add = np.bincount(
                jr_er.astype(np.int64) * R + si, weights=v,
                minlength=self.Jn * R,
            ).reshape(self.Jn, R).astype(F)
            self.j_alloc_res += add
            self.j_pending_res -= add
            qm = q_er >= 0
            if qm.any():
                qadd = np.bincount(
                    q_er[qm].astype(np.int64) * R + si[qm],
                    weights=v[qm], minlength=self.Qn * R,
                ).reshape(self.Qn, R).astype(F)
                self.q_alloc += qadd

    def _drf_shares(self) -> np.ndarray:
        """Per-job DRF share (drf.go:317-329), vectorized."""
        self._flush_aggr()
        total = self.total_res
        with np.errstate(divide="ignore", invalid="ignore"):
            ratio = np.where(
                total[None, :] > 0,
                self.j_alloc_res / np.where(total[None, :] > 0, total[None, :], 1.0),
                np.where(self.j_alloc_res > 0, 1.0, 0.0),
            )
        return ratio.max(axis=1) if self.R else np.zeros(len(self.j_alloc_res))

    def _proportion(self):
        """Water-fill deserved shares (proportion.go:117-173) over the
        queues that have session jobs.  Mirrors the plugin's Resource-level
        loop exactly (queue counts are small).  Returns the args of the
        ``derive:proportion`` span (None without the plugin)."""
        self._flush_aggr()
        q_alloc = np.zeros((self.Qn, self.R), F)
        q_req = np.zeros((self.Qn, self.R), F)
        q_seen = np.zeros(self.Qn, bool)
        srows = np.asarray(self.session_jobs, np.int64)
        if len(srows):
            qs = self.q_of_job[srows]
            ok = qs >= 0
            srows_q = srows[ok]
            qs = qs[ok]
            q_seen[qs] = True
            np.add.at(q_alloc, qs, self.j_alloc_res[srows_q])
            np.add.at(q_req, qs,
                      self.j_alloc_res[srows_q] + self.j_pending_res[srows_q])
        self.q_alloc = q_alloc
        self.q_seen = q_seen

        deserved_res: Dict[int, Resource] = {}
        share_by_queue: Dict[str, float] = {}
        if not self._has("proportion"):
            self.q_deserved = np.full((self.Qn, self.R), 3.0e38, F)
            self.q_share = share_by_queue
            self.q_deserved_res = deserved_res
            return None

        total = self._res(self.total_res)
        attrs = {}
        for qi in np.flatnonzero(q_seen):
            q = self.store.queues[self.queue_names[qi]]
            attrs[int(qi)] = {
                "weight": q.weight,
                "deserved": Resource.empty(),
                "allocated": self._res(q_alloc[qi]),
                "request": self._res(q_req[qi]),
                "share": 0.0,
            }

        remaining = total.clone()
        meet = set()
        iterations = 0
        while True:
            iterations += 1
            total_weight = sum(
                a["weight"] for qi, a in attrs.items() if qi not in meet
            )
            if total_weight == 0:
                break
            increased = Resource.empty()
            decreased = Resource.empty()
            for qi, a in attrs.items():
                if qi in meet:
                    continue
                old = a["deserved"].clone()
                a["deserved"].add(
                    remaining.clone().multi(a["weight"] / float(total_weight))
                )
                if a["request"].less(a["deserved"]):
                    from .api.resource import res_min

                    a["deserved"] = res_min(a["deserved"], a["request"])
                    meet.add(qi)
                # share update
                s = 0.0
                for rn in a["deserved"].resource_names():
                    from .api.resource import share as _share

                    v = _share(a["allocated"].get(rn), a["deserved"].get(rn))
                    if v > s:
                        s = v
                a["share"] = s
                inc, dec = a["deserved"].diff(old)
                increased.add(inc)
                decreased.add(dec)
            remaining.sub(increased).add(decreased)
            if remaining.is_empty():
                break

        self.q_deserved = np.full((self.Qn, self.R), 3.0e38, F)
        for qi, a in attrs.items():
            self.q_deserved[qi] = self._slots_vec(a["deserved"])
            deserved_res[qi] = a["deserved"]
            share_by_queue[self.queue_names[qi]] = a["share"]
        self.q_share = share_by_queue
        self.q_deserved_res = deserved_res
        return {"queues": len(attrs), "iterations": iterations,
                "met": len(meet),
                "deserved_cpu": [float(a["deserved"].milli_cpu)
                                 for a in attrs.values()]}

    def _slots_vec(self, r: Resource) -> np.ndarray:
        v = np.zeros((self.R,), F)
        v[0] = r.milli_cpu
        v[1] = r.memory
        if r.scalars:
            for name, quant in r.scalars.items():
                idx = self.m.scalar_slots.index.get(name)
                if idx is not None:
                    v[2 + idx] = quant
        return v

    # ------------------------------------------------------------ ordering

    def _job_keys(self, rows: List[int], drf_share: np.ndarray) -> np.ndarray:
        """[Jn] global rank array encoding the tier-ordered job-order key
        (first-nonzero comparator chain == lexicographic compare).

        Incremental (ISSUE 8 order lane): the key COLUMNS are cheap
        vector expressions, so they are rebuilt every call and diffed
        against the rank cached on the store — only jobs whose key
        columns actually changed re-sort, merged back into the cached
        order by a vectorized lexicographic binary search
        (``fastpath_incr.rank_from_cols``).  The uid tie-break column is
        a unique integer rank, so the order is total and the merged rank
        is bit-identical to a full ``np.lexsort``."""
        from .fastpath_incr import rank_from_cols

        m = self.m
        Jn = self.Jn
        plugin_cols = []
        tier_names = []
        for opt in self._tier_opts("enabled_job_order"):
            if opt.name == "priority":
                plugin_cols.append(-m.j_prio[:Jn])
            elif opt.name == "gang":
                plugin_cols.append(self.j_ready_base >= m.j_minav[:Jn])
            elif opt.name == "drf":
                plugin_cols.append(drf_share[:Jn])
            tier_names.append(opt.name)
        uid_rank = m.job_uid_rank()
        # Primary-first column order (rank_from_cols convention); the
        # mirror-backed create column is COPIED — the cache must hold a
        # frozen snapshot, not a view an upsert can mutate in place.
        cols = list(plugin_cols) + [m.j_create[:Jn].copy(), uid_rank]
        store = self.store
        if not getattr(self, "_incr", True):
            rank, _ = rank_from_cols(cols, None)
            return rank
        cached = getattr(store, "_job_rank_cache", None)
        ckey = (Jn, tuple(tier_names))
        prev = cached[1] if cached is not None and cached[0] == ckey \
            else None
        rank, fresh = rank_from_cols(cols, prev)
        store._job_rank_cache = (ckey, fresh)
        return rank

    def _queue_order_fn(self):
        share = self.q_share
        has_prop = self._has("proportion") and any(
            opt.name == "proportion"
            for opt in self._tier_opts("enabled_queue_order")
        )

        def fn(l, r) -> bool:
            if has_prop:
                ls = share.get(l.name, 0.0)
                rs = share.get(r.name, 0.0)
                if ls != rs:
                    return ls < rs
            if l.queue.creation_timestamp == r.queue.creation_timestamp:
                return l.uid < r.uid
            return l.queue.creation_timestamp < r.queue.creation_timestamp

        return fn

    def _namespace_order_fn(self, ns_share: Dict[str, float]):
        drf_ns = any(
            opt.name == "drf"
            for opt in self._tier_opts("enabled_namespace_order")
        ) and self._has("drf")

        def fn(l: str, r: str) -> bool:
            if drf_ns:
                lw = ns_share.get(l, 0.0)
                rw = ns_share.get(r, 0.0)
                if lw != rw:
                    return lw < rw
            return l < r

        return fn

    def _overused_fn(self):
        """Memoized per-queue overuse verdicts (shares are frozen at sort
        time, so one evaluation per queue per pass suffices)."""
        if not self._has("proportion"):
            return lambda q: False
        self._flush_aggr()
        deserved = self.q_deserved_res
        qidx = self.queue_index
        alloc = self.q_alloc
        cache: Dict[str, bool] = {}

        def fn(q) -> bool:
            hit = cache.get(q.name)
            if hit is not None:
                return hit
            qi = qidx.get(q.name)
            if qi is None or qi not in deserved:
                out = False
            else:
                out = not self._res(alloc[qi]).less_equal(deserved[qi])
            cache[q.name] = out
            return out

        return fn

    def _ns_shares(self, drf_share_unused) -> Dict[str, float]:
        """Weighted namespace DRF shares (drf.go:224-258)."""
        self._flush_aggr()
        if not (self._has("drf") and any(
            opt.name == "drf"
            for opt in self._tier_opts("enabled_namespace_order")
        )):
            return {}
        m = self.m
        srows = np.asarray(self.session_jobs, np.int64)
        if not len(srows):
            return {}
        # One scatter-add over namespace codes replaces the per-job
        # vector accumulation loop.
        nsc = m.j_ns_code[srows]
        agg = np.zeros((int(nsc.max()) + 1, self.R), F)
        np.add.at(agg, nsc, self.j_alloc_res[srows])
        total = self.total_res
        out = {}
        for c in np.unique(nsc).tolist():
            al = agg[c]
            with np.errstate(divide="ignore", invalid="ignore"):
                ratio = np.where(total > 0, al / np.where(total > 0, total, 1.0),
                                 np.where(al > 0, 1.0, 0.0))
            s = float(ratio.max()) if len(ratio) else 0.0
            ns = m.ns_names.items[c]
            w = self.store.namespace_weights.get(ns, 1)
            out[ns] = s / float(max(w, 1))
        return out

    # ------------------------------------------------------------- actions

    def run(self) -> None:
        # PodGroups whose phase was mutated in place mid-cycle (enqueue's
        # Pending -> Inqueue gate): the close write-back must not skip
        # them as "unchanged".  Lives on the STORE and is only cleared
        # after a successful write-back, so a cycle that fails between
        # the mutation and close does not strand the transition
        # unpersisted forever.
        store = self.store
        if not hasattr(store, "_phase_dirty_uids"):
            store._phase_dirty_uids = set()
        self._phase_dirty = store._phase_dirty_uids
        # Cycle accounting for the flight recorder (obs/recorder.py).
        self.stats: Dict[str, object] = {
            "considered": 0, "bound": 0, "dropped": 0,
            "drop_reasons": {}, "fetch_wait_ms": None,
            "dispatched_solve_id": None, "committed_solve_id": None,
            "mut_at_dispatch": None, "mut_at_commit": None,
            "epoch_at_dispatch": None, "epoch_at_commit": None,
            "device_events": [],
            "object_model": store.take_object_model_counts(),
        }
        # devsnap's running upload counters as the cycle finds them;
        # the record's ``solve`` carries this cycle's deltas.
        self._devsnap0 = _devsnap_counts(store)
        # The cycle's frame (obs/trace.py CycleScope): the scheduler's
        # when run_once() drives this cycle, else one of its own.  Its
        # lane dict is the per-lane wall-clock breakdown of the cycle
        # (seconds) — top-level spans only, the lanes rule — and ends up
        # as the flight record's ``lanes``; the spans both record AND
        # accumulate the lanes, so disabling tracing keeps the
        # breakdown.
        with self.tracer.cycle(getattr(store, "flight", None)) as scope:
            scope.describe("cycle", {"session": self.uid})
            self.lanes: Dict[str, float] = scope.lanes
            err: Optional[BaseException] = None
            try:
                self._run_body()
            except BaseException as e:
                err = e
                raise
            finally:
                # Failed cycles record too — a flight recorder that
                # only remembers the good cycles answers no incident
                # question.
                self._record_cycle(scope, err)

    def _run_body(self) -> None:
        store = self.store
        tracer = self.tracer
        with tracer.span("derive", lanes=self.lanes):
            self.derive()
            with tracer.span("derive:proportion") as sp:
                sp.args = self._proportion()
        self.new_conditions: Dict[int, PodGroupCondition] = {}
        self._evictor = None
        self._victim_table = None  # whatif.VictimTable, one mirror state's
        # Async bind batches commit collects; dispatched at cycle end so
        # the dispatcher thread's drain (binder RPCs, Scheduled events)
        # does not contend the GIL with commit/close — in the reference
        # that work runs in the API-server process, not the scheduler's.
        self._bind_batches: List[tuple] = []
        try:
            try:
                # Double-buffered sessions: the previous cycle's
                # dispatched-but-uncommitted solve lands FIRST, so its
                # device round trip ran concurrently with that cycle's
                # close/enqueue and this cycle's derive (pipeline.py).
                self._commit_inflight()
                # A rebalance plan dispatched last cycle commits (or
                # voids) right after the solve, against the freshest
                # state this cycle will see (actions/rebalance.py).
                self._commit_inflight_plan()
                # Workload-injection seam (hack/endurance.py's churn, loop
                # tests): new work "arrives" after the commit and before
                # this cycle's actions, so every pipelined cycle both
                # commits session N-1 and dispatches session N.
                feed = getattr(store, "cycle_feed", None)
                if feed is not None:
                    with tracer.span("feed", lanes=self.lanes):
                        feed(self)
                for name in self.action_names:
                    if (self.shard is not None
                            and not self.shard.runs_evictions
                            and name in ("preempt", "reclaim",
                                         "rebalance")):
                        # Evict planners reason over the WHOLE cluster's
                        # victims; only the designated evictor shard
                        # (shard 0) runs them, or two shards would plan
                        # overlapping evictions (shard.py).
                        continue
                    lane = (name if name in ("preempt", "reclaim",
                                             "enqueue", "backfill",
                                             "rebalance")
                            else None)
                    with metrics.action_timer(name), tracer.span(
                            f"action:{name}", cat="action",
                            lanes=(self.lanes if lane else None),
                            lane=lane):
                        if name == "enqueue":
                            self._enqueue()
                        elif name == "allocate":
                            self._allocate()
                        elif name == "backfill":
                            if self._backfill():
                                # Backfill bound BestEffort rows directly
                                # in the mirror; stamp for the staleness
                                # guard (disjoint rows from the solve,
                                # but node task slots moved).
                                self.m.mutation_seq += 1
                        elif name == "preempt":
                            if self._evict_device_on():
                                # Device-native lane (ISSUE 11): plan
                                # victims via the jitted kernel, prove
                                # with a what-if solve, commit (or park)
                                # through the engine — which stamps the
                                # mutation counter itself iff it evicts.
                                from . import whatif

                                whatif.run_evict_action(self, "preempt")
                            else:
                                self._evict_machinery().preempt()
                                # Evictions write p_status directly; the
                                # pipelined staleness guard keys off the
                                # mirror's mutation counter, so stamp the
                                # action (preempt/reclaim run AFTER the
                                # allocate dispatch in the standard
                                # confs).
                                self.m.mutation_seq += 1
                        elif name == "reclaim":
                            if self._evict_device_on():
                                from . import whatif

                                whatif.run_evict_action(self, "reclaim")
                            else:
                                self._evict_machinery().reclaim()
                                self.m.mutation_seq += 1
                        elif name == "rebalance":
                            # Defragmentation planner (ISSUE 5): a
                            # committed plan evicts through the same
                            # machinery as preempt/reclaim and stamps
                            # the mutation counter itself.
                            self._rebalance()
            except BaseException:
                # A failed cycle may leave uncommitted status mutations
                # in the mirror (evictions mid-statement); re-derive
                # dynamic state from the pod records before the caller
                # falls back.  Deferred bind-record walks (node_name on
                # committed pods, normally done post-cycle by the bind
                # dispatcher) must land first or the resync would read
                # committed pods as unbound and double-schedule them —
                # including batches a PRIOR cycle dispatched that the
                # worker has not yet processed.
                store.apply_pending_bind_records()
                self.m.resync_status(self.store.pods)
                raise
            if self._evictor is not None:
                self._evictor.st.flush()
            with tracer.span("close", lanes=self.lanes):
                self._close()
        except BaseException:
            # Failures AFTER the action loop (evictor flush, close) must
            # also land the deferred node_name walks before the caller
            # falls back to the object path — the fallback snapshots pod
            # RECORDS, and committed-but-unnamed pods would read as
            # unbound and double-schedule.  Idempotent with the inner
            # handler's application above.
            store.apply_pending_bind_records()
            raise
        finally:
            # Committed binds dispatch even when close fails: binds are
            # idempotent and the commit bookkeeping already happened.
            with tracer.span("bind_handoff", lanes=self.lanes):
                for keys, hosts, pods, entry in self._bind_batches:
                    store.dispatch_binds(keys, hosts, pods, entry=entry)

    # ------------------------------------------------------------- audit

    def _audit_flow(self, old_status: int, new_status: int,
                    reason: str) -> None:
        """Scalar conservation-flow declaration (obs/audit.py): the
        per-row mirror status writers pair each write with one of
        these, so the cycle-end reconcile can balance declared flows
        against the census."""
        a = getattr(self.store, "auditor", None)
        if a is not None and a.enabled and old_status != new_status:
            a.flow(reason, old_status, new_status)

    def _audit_flow_rows(self, rows, new_status: int,
                         reason: str) -> None:
        """Bulk conservation-flow declaration for the vectorized
        status writes — MUST be called before the ``p_status`` write
        (it classifies the rows' old statuses)."""
        a = getattr(self.store, "auditor", None)
        if a is not None and a.enabled and len(rows):
            a.flow_rows(self.m.p_status, rows, int(new_status), reason)

    # ----------------------------------------------------------- journey

    def _journey_shard(self) -> int:
        return -1 if self.shard is None else int(self.shard.index)

    def _journey_masks(self):
        """First-time row masks for the journey's steady-state bulk
        accounting (obs/journey.py), and beside them the journey slot of
        each row, as its ``dispatched`` stamp resolved it (-1 = not
        known): the feed re-pends and re-binds the
        SAME backlog rows every cycle.  The masks remember which rows
        already recorded their first consideration / first bind, so a
        repeat costs neither the gather of its uid nor the journey's
        uid lookup and folds into a bulk counter; the slots let the
        ``bound`` stamp skip both for the rows it does record.  Row
        indices are stable for a pod's lifetime; a compaction renumbers
        them, so all three are keyed on ``compact_gen`` and rebuilt on a
        bump (uid-keyed journey state survives; only the first-seen
        memo resets, costing one re-record per live pod).  Slots are the
        attached journey's own, so another journey on the store drops
        them too."""
        m = self.m
        n = len(m.p_uid)
        key = (m.compact_gen, self.store.journey)
        mk = getattr(self.store, "_journey_masks", None)
        if mk is None or mk[0] != key:
            mk = self.store._journey_masks = (
                key, np.zeros(n, bool), np.zeros(n, bool),
                np.full(n, -1, np.int64))
        elif len(mk[1]) < n:
            grow = lambda a, fill: np.concatenate(
                [a, np.full(n - len(a), fill, a.dtype)])
            mk = self.store._journey_masks = (
                mk[0], grow(mk[1], False), grow(mk[2], False),
                grow(mk[3], -1))
        return mk

    def _journey_event(self, row: int, kind: str, *,
                       solve_id: int = 0, detail: str = "") -> None:
        """Scalar journey capture for one mirror row."""
        jr = getattr(self.store, "journey", None)
        if jr is None:
            return
        uid = self.m.p_uid[int(row)]
        if uid:
            jr.pod_event(uid, kind, shard=self._journey_shard(),
                         solve_id=solve_id, detail=detail)

    def _journey_rows(self, rows, kind: str, *, solve_id: int = 0,
                      epoch: int = -1, detail: str = "",
                      now: Optional[int] = None) -> Optional[dict]:
        """Bulk journey capture for the vectorized seams.  For the
        steady-state kinds (``dispatched``/``bound``/``unbound``) only
        FIRST-time rows are stamped (see ``_journey_masks``);
        drops and voids are churn-sized, so every row records.
        A ``dispatched`` stamp leaves each row's journey slot in the
        masks' third column and the ``bound`` stamp starts from there
        (``_journey_bound``).  ``now`` is the instant the events carry
        when the stamp runs later (``JourneyLog.now()``).
        Returns the enclosing span's args: the rows given, how many of
        them went to the journey's batch path and, of a ``bound`` stamp,
        how many of those came with their slot."""
        jr = getattr(self.store, "journey", None)
        n = len(rows)
        if jr is None or not n:
            return None
        if kind == "unbound":
            rows = rows[:0]
        elif kind in ("dispatched", "bound"):
            _, considered, bound_seen, slot_of_row = self._journey_masks()
            mask = considered if kind == "dispatched" else bound_seen
            rows = rows[~mask[rows]]
            mask[rows] = True
        if n > len(rows):
            # Re-pend loop: the pods' journeys already hold their first
            # consideration / first-bind latency; count in bulk only.
            jr.repeat_rows(n - len(rows), kind)
        args = {"rows": n, "fresh": len(rows)}
        common = dict(shard=self._journey_shard(), solve_id=solve_id,
                      epoch=epoch, detail=detail)
        if kind == "bound":
            args["slot_hits"] = (
                self._journey_bound(jr, rows, slot_of_row, common)
                if len(rows) else 0)
        elif len(rows):
            # A tombstoned row has no uid: it drops out here, in array
            # form, so that the slots that come back line up with rows.
            rows = rows[self.m.p_alive[rows]]
            uids = list(map(self.m.p_uid.__getitem__, rows.tolist()))
            sl = jr.pod_rows(uids, kind, now=now, **common)
            if kind == "dispatched" and len(sl) == len(rows):
                slot_of_row[rows] = sl
                # The commit of these rows wants their uids once more,
                # for the event ring: strings only, gone with the cycle.
                self._journey_uids = (rows, uids)
        return args

    def _journey_bound(self, jr, rows, slot_of_row, common: dict) -> int:
        """The ``bound`` stamp of first-time ``rows``, from the slots
        their ``dispatched`` stamp left; the rows that came with one.
        A slot is its pod's own while the row lives: only the pod's
        removal frees it, and that tombstones the row for good (new pods
        take new rows; a compaction drops the column).  A row that is
        not alive has no uid and no event, as on the uid path."""
        m = self.m
        rows = rows[m.p_alive[rows]]
        sl = slot_of_row[rows]
        miss_uids = [m.p_uid[r] for r in rows[sl < 0].tolist()]
        # The ring's uids: the dispatched stamp's own list when the
        # commit is of those very rows (a burst), else gathered for the
        # events the ring will hold.
        kept, self._journey_uids = self._journey_uids, None
        if kept is not None and np.array_equal(kept[0], rows):
            tail = kept[1]
        else:
            k = min(len(rows), jr.capacity)
            tail = list(map(m.p_uid.__getitem__,
                            rows[len(rows) - k:].tolist()))
        jr.pod_slots(sl, tail, "bound", miss_uids=miss_uids, **common)
        return len(rows) - len(miss_uids)

    def _record_cycle(self, scope, err: Optional[BaseException]) -> None:
        """Run the cycle-end audits and hand this cycle's record to its
        scope, which completes it (duration, lanes, spans) and seals it
        into the store's flight recorder when ``run_once()`` leaves."""
        from .obs.recorder import CycleRecord

        # Runtime auditor (obs/audit.py, ISSUE 13): conservation
        # reconcile + sampled coherence audits + SLO feed.  Runs even
        # when no flight recorder is attached — the anomaly ring and
        # counters are the production surface; the CycleRecord copy is
        # the forensic one.  The SLO's "cycle" observation is the time
        # from entry to run_once() up to here: the audit, record and
        # gc lanes of this cycle are still to come.
        anoms = []
        auditor = getattr(self.store, "auditor", None)
        if auditor is not None and auditor.enabled:
            with scope.lane("audit"):
                anoms = auditor.end_cycle(self, scope.elapsed_s(), err)
        st = self.stats
        with scope.lane("record"):
            # ``stamp``: the ring copies get the flight seq at sealing,
            # so an operator can walk /debug/anomalies ->
            # /debug/cycles/<seq>.
            scope.submit(CycleRecord(
                session=self.uid, path="fast",
                shard=(None if self.shard is None
                       else int(self.shard.index)),
                pods_considered=int(st["considered"]),
                pods_bound=int(st["bound"]),
                pods_dropped=int(st["dropped"]),
                drop_reasons=dict(st["drop_reasons"]),
                inflight_fetch_wait_ms=st["fetch_wait_ms"],
                dispatched_solve_id=st["dispatched_solve_id"],
                committed_solve_id=st["committed_solve_id"],
                mutation_seq_at_dispatch=st["mut_at_dispatch"],
                mutation_seq_at_commit=st["mut_at_commit"],
                epoch_at_dispatch=st["epoch_at_dispatch"],
                epoch_at_commit=st["epoch_at_commit"],
                device_events=list(st["device_events"]),
                error=type(err).__name__ if err is not None else None,
                rebalance=st.get("rebalance"),
                whatif=st.get("whatif"),
                pool=st.get("pool"),
                anomalies=[a.to_dict() for a in anoms],
                solve=self._solve_record(),
                object_model=st["object_model"],
            ), stamp=anoms)

    def _solve_counts(self) -> Dict[str, object]:
        """This cycle's solve counts (``CycleRecord.solve``), created
        by the first dispatch or in-flight fetch of the cycle: counts
        at the boundaries the ``device`` spans time, never per pod."""
        sc = self.stats.get("solve")
        if sc is None:
            sc = self.stats["solve"] = {
                "dispatches": 0, "rows": 0, "nodes": int(self.Nn),
                "jobs": 0, "queues": 0, "gang_size_max": 0,
                "overuse_gated_jobs": None,
                "devincr_mode": None, "dirty_nodes": None,
                "arg_puts": 0, "arg_put_bytes": 0,
                "fetches": 0, "fetch_bytes": 0,
                "aff_rows": 0, "aff_terms": 0, "aff_terms_padded": 0,
                "aff_domains": 0, "aff_chunks": 0, "aff_device_bytes": 0,
                "aff_prof_entries": 0, "aff_cnt0_entries": 0,
                "aff_host_dense_bytes": 0,
                "shortlist_fb_affinity": 0, "shortlist_fb_exhausted": 0,
                "shortlist_rows": 0, "shortlist_keys": 0,
                "aff_count_reads": 0,
            }
        return sc

    def _count_affinity(self, task_rows: np.ndarray, E: int, Ep: int,
                        Np: int) -> None:
        """What one solve's inter-pod terms give the device (the
        ``aff_*`` solve counts; all 0 in a cycle without terms): the
        pending rows that carry a term, the active terms and their
        padded bucket, the topology domains, and the bytes the
        ``has_aff`` branch of ``_solve_wave`` holds for them, reckoned
        from shapes: the two ``[Ep + 1, D]`` int32 count tensors.  The
        largest solve of the cycle is kept; on a mesh, a chip's share
        of it beside the whole."""
        if getattr(self, "stats", None) is None:
            return  # a bare FastCycle outside run() (tests) records nothing
        m = self.m
        D = max(1, len(m.domains))
        shards = self._mesh_shards()
        if Np % shards:
            shards = 1  # solve_wave's own rule: the global form
        nbytes = 2 * (Ep + 1) * D * 4
        sc = self._solve_counts()
        sc["aff_rows"] = max(sc["aff_rows"], int(
            np.count_nonzero(m.p_has_ip[task_rows])))
        sc["aff_terms"] = max(sc["aff_terms"], int(E))
        sc["aff_terms_padded"] = max(sc["aff_terms_padded"], int(Ep))
        sc["aff_domains"] = max(sc["aff_domains"], D)
        sc["aff_device_bytes"] = max(sc["aff_device_bytes"], nbytes)
        if shards > 1:
            # On a mesh the count pair shards on the domain axis: a
            # chip's share (absent on one device, where
            # aff_device_bytes is the chip's).
            sc["aff_device_bytes_chip"] = max(
                sc.get("aff_device_bytes_chip", 0), -(-nbytes // shards))

    def _count_dispatch(self, rows: int, args, jobs) -> None:
        """One solve handed to the device: its rows, its ``jobs`` (how
        many, over how many queues, the largest gang's ``min_member``)
        and the numpy leaves of ``args`` the jitted call uploads with
        it."""
        sc = self._solve_counts()
        sc["dispatches"] += 1
        sc["rows"] += int(rows)
        sj = np.asarray(jobs, np.int64)
        sc["jobs"] += len(sj)
        sc["queues"] = max(sc["queues"],
                           len(np.unique(self.q_of_job[sj])))
        sc["gang_size_max"] = max(sc["gang_size_max"],
                                  int(self.m.j_minav[sj].max()))
        n, nbytes = _host_bytes(args)
        sc["arg_puts"] += n
        sc["arg_put_bytes"] += nbytes

    def _count_fetch(self, fetched) -> None:
        """One blocking device->host fetch and the bytes it returned."""
        sc = self._solve_counts()
        sc["fetches"] += 1
        sc["fetch_bytes"] += _host_bytes(fetched)[1]

    def _solve_record(self) -> Optional[Dict[str, object]]:
        """``CycleRecord.solve``: the cycle's solve counts plus the
        device snapshot's uploads of this cycle (deltas of devsnap's
        running counters); None when no solve was dispatched or
        fetched (null-delta skip, nothing pending)."""
        sc = self.stats.get("solve")
        if sc is None:
            return None
        dv = self.stats.get("devincr")
        if dv:
            sc["devincr_mode"] = dv.get("mode")
            sc["devincr_static"] = dv.get("static")
        now = _devsnap_counts(self.store)
        full, delta, hits, puts, put_bytes = (
            b - a for a, b in zip(self._devsnap0, now))
        sc.update(devsnap_full=full, devsnap_delta=delta,
                  devsnap_hits=hits, devsnap_puts=puts,
                  devsnap_put_bytes=put_bytes)
        return sc

    def _count_drops(self, reasons: Dict[str, int]) -> None:
        """Fold staleness-guard drop counts into the cycle stats and the
        per-reason counter series."""
        st = self.stats
        dr = st["drop_reasons"]
        for reason, n in reasons.items():
            n = int(n)
            if n <= 0:
                continue
            dr[reason] = dr.get(reason, 0) + n
            metrics.pipeline_stale_drops.inc(n, reason=reason)
            st["dropped"] = int(st["dropped"]) + n

    def _count_shortlist_fb(self, exhausted: int, affinity: int) -> None:
        """Fold the two-phase solve's shortlist-fallback rescore counts
        into the per-reason counter series and the cycle stats."""
        if exhausted <= 0 and affinity <= 0:
            return
        if exhausted > 0:
            metrics.solve_shortlist_fallback.inc(
                exhausted, reason="exhausted")
        if affinity > 0:
            metrics.solve_shortlist_fallback.inc(
                affinity, reason="affinity-required")
        self.stats["shortlist_fallbacks"] = (
            int(self.stats.get("shortlist_fallbacks", 0))
            + exhausted + affinity)
        sc = self._solve_counts()
        sc["shortlist_fb_exhausted"] += int(exhausted)
        sc["shortlist_fb_affinity"] += int(affinity)

    def _record_pool_fetch(self) -> None:
        """Fold the solver pool's last-fetch info (winning replica,
        hedge/failover flags, wait — solver_pool.SolverPool) into the
        cycle's flight record.  Plain RemoteSolver stores carry no
        pool info and record nothing."""
        take = getattr(self._remote_solver, "take_last_fetch_info", None)
        if take is None:
            return
        info = take()
        if info:
            self.stats["pool"] = info

    def _devincr_drop_skip(self) -> None:
        """Void the null-delta skip proof: the previously dispatched
        solve's result was LOST (reply lost / device crash), so even an
        unchanged store must re-dispatch — the lost solve may have
        found placements nobody ever saw."""
        dvc = getattr(self.store, "_devincr_cache", None)
        if dvc is not None:
            dvc.skip_token = None

    def _record_twophase_lanes(self) -> None:
        """Fold the wave solver's coarse/fine dispatch timings into the
        cycle's lane split (device_coarse / device_fine: the one nested
        pair the lanes rule allows, inside ``device``) — these are the
        host-side dispatch legs, timed in ops/wave.py; the span that
        stands around them is ``device:dispatch``, and the residual
        device wait stays on the fetch that consumes the result.  Mesh
        dispatches note the node-axis shard count in the cycle stats."""
        from .ops import wave as _wave_mod

        info = _wave_mod.LAST_TWOPHASE
        if not info.get("enabled"):
            return
        lanes = self.lanes
        coarse = float(info.get("coarse_s", 0.0))
        fine = float(info.get("fine_s", 0.0))
        shards = int(info.get("mesh_shards", 1) or 1)
        if shards > 1:
            self.stats["mesh_shards"] = shards
            # Into the record's ``solve`` block too.
            self._solve_counts()["mesh_shards"] = shards
        # Profile rows the coarse shortlist served and the distinct
        # scoring keys it ranked for them (ops/wave.shortlist_keys): the
        # cycle's largest solve is kept.
        sc = self._solve_counts()
        if info.get("shortlist_rows", 0) >= sc["shortlist_rows"]:
            sc["shortlist_rows"] = int(info["shortlist_rows"])
            sc["shortlist_keys"] = int(info["shortlist_keys"])
        terms = info.get("terms")
        if terms:
            # How the inter-pod term data crossed to this solve: its
            # real entries, and the dense host tables read or built for
            # it (0 where the tables were born on the device).  Of the
            # entries the cycle's largest solve is kept, like the aff_*
            # beside; the bytes add up over its solves.
            sc = self._solve_counts()
            for k in ("prof_entries", "cnt0_entries"):
                sc["aff_" + k] = max(sc["aff_" + k], terms[k])
            sc["aff_host_dense_bytes"] += terms["host_dense_bytes"]
        dvinfo = info.get("devincr")
        if dvinfo:
            # Device-incremental decision of this dispatch (ISSUE 9):
            # cycle stats + the per-mode counter series.
            self.stats["devincr"] = dict(dvinfo)
            mode = dvinfo.get("mode")
            if mode in ("warm", "full"):
                metrics.device_incremental_solves.inc(mode=mode)
        lanes["device_coarse"] = lanes.get("device_coarse", 0.0) + coarse
        lanes["device_fine"] = lanes.get("device_fine", 0.0) + fine

    def _evict_device_on(self) -> bool:
        """True when preempt/reclaim run the device-native
        plan-prove-commit lane (volcano_tpu/whatif.py) instead of the
        host-side victim walk.  ``VOLCANO_TPU_EVICT_DEVICE=0`` (or a
        remote-solver deployment, whose scheduler process cannot run
        the what-if solve) keeps the host walk bind-for-bind."""
        from . import whatif

        return whatif.evict_device_on(self.store)

    def _evict_machinery(self):
        self._flush_aggr()
        if self._evictor is None:
            from .fastpath_evict import FastEvictor

            self._evictor = FastEvictor(self)
        else:
            # Action order is free-form: an allocate/backfill action may
            # have mutated n_idle/n_ntasks since the evictor snapshot.
            self._evictor.resync()
        return self._evictor

    # ------------------------------------------------------------- enqueue

    def _minres_vec(self, pg) -> Optional[np.ndarray]:
        """Dense slot vector of pg.min_resources, cached on the PodGroup.
        None when min_resources names a resource outside the slot layout
        (caller falls back to Resource-object math)."""
        cached = getattr(pg, "_minres_vec", None)
        if cached is not None and cached[0] == self.R:
            return cached[1]
        res = Resource.from_resource_list(pg.min_resources)
        v = np.zeros((self.R,), F)
        v[0] = res.milli_cpu
        v[1] = res.memory
        if res.scalars:
            for name, quant in res.scalars.items():
                idx = self.m.scalar_slots.index.get(name)
                if idx is None:
                    return None
                v[2 + idx] = quant
        try:
            pg._minres_vec = (self.R, v)
        except Exception:
            pass
        return v

    def _enqueue(self) -> None:
        """Gate Pending PodGroups into Inqueue (enqueue.go:52-132).

        The object path's queue/job PriorityQueues have static keys during
        enqueue, so heap pops reduce to: queues in key order, each drained
        of its jobs in key order, with the budget checked between jobs."""
        m = self.m
        store = self.store
        args = get_action_args(self.conf.configurations, "enqueue")
        factor = args.get_float("overcommit-factor", 1.2) if args else 1.2

        # Queue-grouped pending rows, built by array grouping instead of
        # a 12k-row Python loop.  Ordering (queue comparator + job keys)
        # is DEFERRED below the accept-all fast path: when every pending
        # group fits, acceptance is order-independent and the sorts are
        # pure overhead at the north-star shape.
        srows = np.asarray(self.session_jobs, np.int64)
        if not len(srows):
            return
        # Steady-state early-out (ISSUE 8): with no Pending-phase group
        # in the session there is nothing to gate — the queue grouping,
        # unknown-queue scan, and budget prep below are pure overhead
        # (the object path's enqueue likewise does nothing; only its
        # per-job unknown-queue error logs are skipped here, and those
        # re-fire on any cycle that has Pending groups again).
        if not bool((self.j_phase[srows] == 1).any()):
            return
        row_pg = self.j_pgs
        qc = m.j_queue_code[srows]
        uq_codes, uq_first = np.unique(qc, return_index=True)
        uq_codes = uq_codes[np.argsort(uq_first, kind="stable")]
        known = {}
        for c in uq_codes.tolist():
            qname = m.qnames.items[c]
            known[c] = qname if qname in store.queues else None
        bad_codes = [c for c, n in known.items() if n is None]
        if bad_codes:
            # Per-job error log, as the object path emits
            # (enqueue.go:66-69) — unknown queues are rare.
            for row in srows[np.isin(qc, bad_codes)].tolist():
                log.error("Failed to find queue %s for job %s",
                          m.j_queue[row], m.j_uid[row])
        queue_seq = [n for n in (known[c] for c in uq_codes.tolist())
                     if n is not None]
        pend = (self.j_phase[srows] == 1) & np.isin(
            qc, [c for c, n in known.items() if n is not None]
        )
        prows = srows[pend]
        jobs_map: Dict[str, List[int]] = {}
        if len(prows):
            qcp = qc[pend]
            order = np.argsort(qcp, kind="stable")
            qcp_s = qcp[order]
            prows_s = prows[order]
            starts = np.flatnonzero(
                np.concatenate(([True], qcp_s[1:] != qcp_s[:-1]))
            )
            bounds = np.append(starts, len(qcp_s))
            for i, s in enumerate(starts.tolist()):
                jobs_map[known[int(qcp_s[s])]] = (
                    prows_s[s:bounds[i + 1]].tolist()
                )

        eps = self.eps
        scalar_slot = self.scalar_slot
        used_vec = (self.n_used[self.n_alive].sum(axis=0)
                    if self.Nn else np.zeros(self.R, F))
        idle = self.total_res * factor - used_vec

        # Accept-all fast path: when no involved queue has a capability
        # cap and the SUM of every pending group's MinResources fits the
        # overcommitted idle budget, the sequential scan accepts every
        # group (each prefix of charges leaves at least the final idle),
        # so the per-group budget walk collapses to one vector compare.
        if not _vec_is_empty(idle, eps):
            capped = self._has("proportion") and any(
                store.queues[q].queue.capability for q in jobs_map
            )
            if not capped:
                vecs = []
                all_vec = True
                for lst in jobs_map.values():
                    for row in lst:
                        pg = row_pg[row]
                        if pg.min_resources is None:
                            continue
                        v = self._minres_vec(pg)
                        if v is None:
                            all_vec = False
                            break
                        vecs.append(v)
                    if not all_vec:
                        break
                if all_vec:
                    total = (
                        np.sum(np.stack(vecs), axis=0) if vecs
                        else np.zeros(self.R, F)
                    )
                    # Strict fit with slack: the sequential walk below
                    # stops as soon as idle goes empty mid-walk, which
                    # rejects every later group (even MinResources-nil
                    # groups that charge nothing, enqueue.go:98-101).
                    # _vec_le alone tolerates total ≈ idle within eps,
                    # where the walk and the shortcut would diverge —
                    # require a non-empty residual so every prefix of
                    # charges provably leaves a non-empty idle.
                    if (_vec_le(total, idle, eps, scalar_slot)
                            and not _vec_is_empty(idle - total, eps)):
                        inq = PodGroupPhase.Inqueue.value
                        j_uid = m.j_uid
                        dirty = self._phase_dirty
                        j_phase = self.j_phase
                        for lst in jobs_map.values():
                            for row in lst:
                                # j_uid[row] == pg.uid (the PodGroup
                                # dict key) without the property call.
                                row_pg[row].status.phase = inq
                                dirty.add(j_uid[row])
                            j_phase[lst] = 2
                        return

        # Budget walk: order matters from here on (enqueue.go's queue /
        # job PriorityQueue pops), so pay for the sorts now.
        queue_order = self._queue_order_fn()
        drf_share = self._drf_shares()
        jkeys = self._job_keys(self.session_jobs, drf_share).tolist()
        queue_seq.sort(key=_cmp_key(
            lambda l, r: queue_order(store.queues[l], store.queues[r])
        ))
        for lst in jobs_map.values():
            lst.sort(key=jkeys.__getitem__)

        q_cap_vec: Dict[str, Optional[np.ndarray]] = {}
        done = False
        for qname in queue_seq:
            if done:
                break
            for row in jobs_map.get(qname, ()):
                if _vec_is_empty(idle, eps):
                    done = True
                    break
                pg = row_pg[row]
                inqueue = False
                if pg.min_resources is None:
                    inqueue = True
                else:
                    min_vec = self._minres_vec(pg)
                    if min_vec is None:
                        # Unknown resource name: Resource-object fallback.
                        min_req = Resource.from_resource_list(
                            pg.min_resources
                        )
                        if (
                            self._job_enqueueable_obj(qname, pg)
                            and min_req.less_equal(self._res(idle))
                        ):
                            idle = idle - self._slots_vec(min_req)
                            inqueue = True
                    elif (
                        self._job_enqueueable_vec(qname, pg, min_vec,
                                                  q_cap_vec)
                        and _vec_le(min_vec, idle, eps, scalar_slot)
                    ):
                        idle = idle - min_vec
                        inqueue = True
                if inqueue:
                    pg.status.phase = PodGroupPhase.Inqueue.value
                    self.j_phase[row] = 2
                    # The close-phase skip-check compares against this
                    # already-mutated object; record the transition so
                    # the write-back still persists + notifies it.
                    self._phase_dirty.add(pg.uid)

    def _job_enqueueable_vec(self, qname: str, pg, min_vec: np.ndarray,
                             q_cap_vec: Dict) -> bool:
        """proportion's JobEnqueueable veto (proportion.go:231-247)."""
        if not self._has("proportion"):
            return True
        self._flush_aggr()
        queue = self.store.queues.get(qname)
        if queue is None or not queue.queue.capability:
            return True
        if qname not in q_cap_vec:
            q_cap_vec[qname] = self._slots_vec(
                Resource.from_resource_list(queue.queue.capability)
            )
        qi = self.queue_index.get(qname)
        allocated = self.q_alloc[qi] if qi is not None else 0.0
        return _vec_le(min_vec + allocated, q_cap_vec[qname],
                       self.eps, self.scalar_slot)

    def _job_enqueueable_obj(self, qname: str, pg) -> bool:
        if not self._has("proportion"):
            return True
        self._flush_aggr()
        queue = self.store.queues.get(qname)
        if queue is None or not queue.queue.capability:
            return True
        if pg is None or pg.min_resources is None:
            return True
        min_req = Resource.from_resource_list(pg.min_resources)
        qi = self.queue_index.get(qname)
        allocated = (
            self._res(self.q_alloc[qi]) if qi is not None else Resource.empty()
        )
        return min_req.add(allocated).less_equal(
            Resource.from_resource_list(queue.queue.capability)
        )

    # ------------------------------------------------------------ allocate

    # The one runtime failure a solve recovers from in place: device
    # memory exhaustion.  A direct-attached TPU raises it as
    # jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Error allocating
    # device buffer: Attempting to allocate 24.00G. ... There are 15.75G
    # free.; (0x0x0_HBM0)") and stays usable afterwards (observed on a
    # v5e, PR 21), so halving the affinity chunk budget and resuming is
    # a remedy.  It is recognised by type and status name; every other
    # runtime error is not known to be survivable and propagates to the
    # scheduler's health accounting.
    _DEVICE_OOM_STATUS = "RESOURCE_EXHAUSTED"
    # Lowest budget scale the crash handler degrades to (1/64 of the
    # configured VOLCANO_TPU_AFF_BUDGET_MB).
    _MIN_BUDGET_SCALE = 1.0 / 64.0
    # Clean affinity cycles before the degraded budget doubles back up.
    _SCALE_RECOVER_AFTER = 8
    # Consecutive remote-solver fetch failures tolerated as "lost
    # reply" before the pipelined commit fails the cycle (a child that
    # keeps replying garbage never fails the send-side probe).
    REMOTE_FETCH_FAIL_CAP = 3

    @classmethod
    def _is_device_crash(cls, e: BaseException) -> bool:
        import jax

        return (isinstance(e, jax.errors.JaxRuntimeError)
                and str(e).startswith(cls._DEVICE_OOM_STATUS))

    def _on_device_crash(self, e: Exception) -> None:
        """Degrade the affinity chunk budget and re-probe the device.
        Raises the original error when the runtime did not come back —
        the scheduler's health machinery (UNHEALTHY_AFTER) then takes
        over."""
        store = self.store
        scale = getattr(store, "_aff_budget_scale", 1.0)
        scale = max(scale / 2.0, self._MIN_BUDGET_SCALE)
        store._aff_budget_scale = scale
        store._aff_clean_cycles = 0
        # A solve that died mid-stream may have half-updated the warm
        # candidates, and the device-incremental caches hold memory the
        # retry needs: drop everything — the next solve provably
        # full-recomputes on fresh buffers.
        dvc = getattr(store, "_devincr_cache", None)
        if dvc is not None:
            dvc.invalidate()
        # The shape buckets' high-water marks would hold the retry's
        # smaller chunks to the tensors that did not fit.
        store._solve_shape_marks.clear()
        store._encode_cache = None
        log.error(
            "device memory exhausted mid-solve (%s); halving affinity "
            "chunk budget to %.3gx and resuming the cycle", e, scale,
        )
        store.record_event(
            "Scheduler/device", "DeviceCrashRecovered",
            f"solve crashed ({type(e).__name__}); chunk budget now "
            f"{scale:.3g}x",
        )
        metrics.device_crash_recoveries.inc()
        stats = getattr(self, "stats", None)
        if stats is not None:
            stats["device_events"].append(
                f"device crash ({type(e).__name__}); "
                f"chunk budget degraded to {scale:.3g}x"
            )
        import jax
        import jax.numpy as jnp

        try:
            jax.device_get(jnp.zeros((8,)) + 1)
        except Exception:
            log.exception("device runtime unusable after the failure")
            raise e

    def _allocate(self) -> None:
        from .ops.allocate import solve
        from .ops.wave import solve_wave

        args = get_action_args(self.conf.configurations, "allocate")
        rounds = args.get_int("rounds", 1) if args else 1
        solver = args.get_str("solver", "wave") if args else "wave"
        max_rounds = max(rounds, 1) + (3 if solver == "wave" else 0)
        solve_fn = solve_wave if solver == "wave" else solve

        lanes = self.lanes
        store = self.store
        tracer = self.tracer
        # Null-delta fast cycle (ISSUE 9): when nothing the solve is a
        # function of changed since the previous dispatch — and that
        # dispatch's result was fetched and committed — a re-dispatch
        # would reproduce the identical (empty) outcome, so the cycle
        # skips the solve wholesale.  Any bind-backoff entry disables
        # the skip (backoff windows expire on wall time, not on a
        # mirror version).
        from .ops import devincr as _dvm

        # ``solve_prep`` (lane): everything of this action between the
        # order / encode / device / commit lanes.
        dv_store = None
        if solver == "wave" and _dvm.devincr_on():
            dv_store = _dvm.of_store(store)
            if not store.bind_backoff and dv_store.skip_token is not None:
                with tracer.span("solve_prep", lanes=lanes):
                    tok = self._null_delta_token(solver, rounds)
                if dv_store.skip_token == tok:
                    dv_store.counts["skip"] += 1
                    metrics.device_incremental_solves.inc(mode="skip")
                    self.stats["device_events"].append(
                        "null-delta: solve dispatch skipped")
                    self.stats["solve_skipped"] = True
                    return
        # Solve-input token as of the LAST encode of this lane; the
        # epilogue persists it as the skip token iff nothing mutated
        # after that encode (i.e. the final solve placed nothing).
        self._last_encode_token = None
        retry = False
        rnd = 0
        crashes = 0
        had_aff_chunks = False
        while rnd < max_rounds + crashes:
            if rnd >= max(rounds, 1) + crashes and not retry:
                break
            rnd += 1
            with tracer.span("order", lanes=lanes):
                ordered = self._ordered_jobs()
                with tracer.span("order:tasks") as sp:
                    prep = self._pending_rows(ordered)
                    sp.args = {"cache_hit": self._pending_cache_hit}
            if prep is None:
                break
            solve_jobs, task_rows = prep
            # Require-contiguous gangs with no whole-gang fabric block
            # sit the solve out (exclusive drop reason
            # topology-infeasible) instead of scattering.
            with tracer.span("solve_prep", lanes=lanes):
                solve_jobs, task_rows = self._topology_pregate(
                    solve_jobs, task_rows)
            if not len(task_rows):
                break
            # Distinct rows entering solves this cycle: retry rounds
            # re-derive a SUBSET of round 1's pending set (commits only
            # shrink it), so the max over rounds is the distinct count —
            # a per-round += would double-count retried rows.
            self.stats["considered"] = max(
                int(self.stats["considered"]), len(task_rows))
            progress_any = False
            never_any = False
            try:
                with tracer.span("solve_prep", lanes=lanes):
                    # The mesh the solve runs on, if any (store.solve_mesh,
                    # the conf's ``mesh`` argument of this action, or the
                    # VOLCANO_TPU_MESH deploy knob: docs/tuning.md), built
                    # once per store and value; the sizing below reckons
                    # with a chip's share on it.
                    mesh = self._solve_mesh(args)
                    # ``solve_prep:chunks`` (child): sizing the count
                    # tensors against the budget; only a store that has
                    # interned an inter-pod term records it.
                    with (tracer.span("solve_prep:chunks") if len(self.m.terms)
                          else contextlib.nullcontext()) as sp:
                        chunks = list(
                            self._solve_chunks(solve_jobs, task_rows))
                        if self._chunks_had_terms:
                            self._solve_counts()["aff_chunks"] += len(chunks)
                            sp.args = {"chunks": len(chunks)}
                    remote = self._remote_solver
                # Pipelined dispatch (ISSUE 1): a single-chunk wave
                # solve is shipped WITHOUT blocking on the result; the
                # commit lands at the top of the next cycle.  Chunked
                # solves stay synchronous — later chunks must see
                # earlier chunks' placements.  The mesh path pipelines
                # too (ISSUE 7): the InflightSolve payload is simply an
                # AllocResult whose arrays live sharded on the mesh, and
                # fetch()'s jax.device_get assembles them — the
                # staleness guard is host-side numpy either way.
                if (self._pipeline_on and solver == "wave"
                        and len(chunks) == 1):
                    cjobs, crows = chunks[0]
                    had_aff_chunks |= self._chunks_had_terms
                    with tracer.span("encode", lanes=lanes):
                        inputs, pid, profiles, ncls = self._solve_inputs(
                            cjobs, crows, slim=True)
                    # Device-incremental context (ISSUE 9): cache keys
                    # + dirty superset for this dispatch (a token dict
                    # for the remote child, which owns its planes).
                    with tracer.span("solve_prep", lanes=lanes):
                        dv, dv_manifest = self._devincr_prepare(
                            inputs, mesh, remote is not None)
                    kind = "remote" if remote is not None else "local"
                    # The dispatch span opens the solve-id flow; the
                    # matching fetch/commit spans close it in cycle N+1.
                    store._solve_seq += 1
                    solve_id = store._solve_seq
                    with tracer.span(
                            "dispatch", cat="pipeline", flow=solve_id,
                            lanes=lanes, lane="device",
                            args={"kind": kind, "rows": len(crows),
                                  "solve_id": solve_id}):
                        self._count_dispatch(
                            len(crows), (inputs, pid, profiles), cjobs)
                        if remote is not None:
                            # The child process rebuilds node classes
                            # from the numpy frame itself; class planes
                            # do not cross the wire — the manifest's
                            # devincr tokens key the child's own
                            # persistent planes.
                            payload = remote.solve_async(
                                inputs, pid, profiles,
                                devincr=dv_manifest)
                            if dv_manifest is not None:
                                # The child solves every frame it
                                # receives: a successful send anchors
                                # the dirty accumulator on its caches.
                                _dvm.of_store(store).anchor_dirty()
                        else:
                            if mesh is not None:
                                payload = self._solve_mesh_dispatch(
                                    mesh, inputs, pid, profiles, ncls,
                                    devincr=dv)
                            else:
                                payload = solve_fn(
                                    *inputs, pid=pid, profiles=profiles,
                                    taint_any=self._taint_any,
                                    node_classes=ncls, devincr=dv,
                                    shape_marks=store._solve_shape_marks)
                                self._record_twophase_lanes()
                            # Start the device->host transfer now; the
                            # fetch at the next cycle's top only waits
                            # for whatever is still in flight.
                            try:
                                payload.assigned.copy_to_host_async()
                            except AttributeError:
                                pass
                        self._last_encode_token = (
                            self._null_delta_token(solver, rounds)
                            if dv_store is not None else None)
                        self._dispatch_async(
                            cjobs, crows, kind, payload, solve_id,
                            devincr_token=self._last_encode_token)
                    self.stats["dispatched_solve_id"] = solve_id
                    break
                for cjobs, crows in chunks:
                    had_aff_chunks |= self._chunks_had_terms
                    with tracer.span("encode", lanes=lanes):
                        inputs, pid, profiles, ncls = self._solve_inputs(
                            cjobs, crows, slim=(solver == "wave"))
                    # Device-incremental context: single-chunk wave
                    # solves only (chunked solves interleave commits,
                    # so each chunk would need its own proof).
                    dv = dv_manifest = None
                    if solver == "wave" and len(chunks) == 1:
                        with tracer.span("solve_prep", lanes=lanes):
                            dv, dv_manifest = self._devincr_prepare(
                                inputs, mesh, remote is not None)
                            self._last_encode_token = (
                                self._null_delta_token(solver, rounds)
                                if dv_store is not None else None)
                    # The ``device`` lane: a host-clock span over
                    # dispatch, the host work overlapped with the
                    # solve, the blocking fetch and the fabric gate —
                    # each a child span, none a lane.
                    with tracer.span("device", cat="device",
                                     lanes=lanes,
                                     args={"rows": len(crows)}) as dev:
                        # The instant these rows entered the solve: what
                        # their ``dispatched`` events carry, stamped
                        # below while the chip works.
                        jr = getattr(store, "journey", None)
                        entered = None if jr is None else jr.now()
                        with tracer.span("device:dispatch",
                                         cat="device") as disp:
                            self._count_dispatch(
                                len(crows), (inputs, pid, profiles),
                                cjobs)
                            result = self._solve_sync(
                                solver, solve_fn, remote, mesh, inputs,
                                pid, profiles, ncls, dv, dv_manifest)
                            shards = self.stats.get("mesh_shards")
                            if shards:
                                disp.args = {"mesh_shards": shards}
                            # One batched device->host fetch: every
                            # fetch is a blocking round trip, so three
                            # sequential np.asarray() calls would pay
                            # it three times.
                            for arr in (result.assigned,
                                        result.never_ready,
                                        result.fit_failed):
                                try:
                                    arr.copy_to_host_async()
                                except AttributeError:
                                    pass
                        # Journey: these rows entered a device solve
                        # (first-time rows record; repeats bulk-count).
                        # It needs nothing from the solve, so it runs
                        # in the wait for it, as the commit prep does.
                        with tracer.span("device:journey",
                                         cat="device") as sp:
                            sp.args = self._journey_rows(
                                crows, "dispatched", now=entered)
                        # Commit prep that doesn't need the assignments
                        # overlaps the device solve + transfer wait.
                        with tracer.span("device:host_prep",
                                         cat="device"):
                            req_gather = self.m.c_req.gather(crows)
                            self._obj_arrays()
                        # How long the host waited for the chip.
                        with tracer.span("device:fetch", cat="device"):
                            if solver == "wave":
                                # The wave solver always carries the
                                # two-phase fallback counters (zeros
                                # when disabled) and, solved in this
                                # process, the overuse gate's count;
                                # ride the same batched fetch.
                                gated = (
                                    () if result.overuse_gated is None
                                    else (result.overuse_gated,))
                                # ... and a has_aff solve its count of
                                # count-plane recomputes, last.
                                reads = (
                                    () if result.aff_count_reads is None
                                    else (result.aff_count_reads,))
                                fetched = jax.device_get(
                                    (result.assigned,
                                     result.never_ready,
                                     result.fit_failed,
                                     result.fb_exhausted,
                                     result.fb_affinity) + gated + reads)
                            else:
                                fetched = jax.device_get(
                                    (result.assigned,
                                     result.never_ready,
                                     result.fit_failed))
                        self._count_fetch(fetched)
                        assigned, never_ready, fit_failed = fetched[:3]
                        if solver == "wave":
                            self._count_shortlist_fb(
                                int(fetched[3]), int(fetched[4]))
                            if gated:
                                sc = self._solve_counts()
                                sc["overuse_gated_jobs"] = (
                                    (sc["overuse_gated_jobs"] or 0)
                                    + int(fetched[5]))
                            if reads:
                                self._solve_counts()[
                                    "aff_count_reads"] += int(fetched[-1])
                        assigned = assigned[:len(crows)]
                        # Fabric gate: require-contiguous gangs
                        # scattered across blocks are vetoed before the
                        # commit.
                        with tracer.span("device:gate", cat="device"):
                            assigned = self._topology_gate(
                                crows, assigned)
                    metrics.device_solve_latency.observe(
                        dev.dur_ns / 1e6)
                    with tracer.span("commit", lanes=lanes):
                        progress = self._commit(
                            cjobs, crows, assigned, never_ready,
                            fit_failed, req_gather,
                        )
                    progress_any |= progress
                    never_any |= bool(never_ready.any())
            except Exception as e:
                # Mid-solve memory exhaustion: committed chunks already
                # landed; the failed chunk mutated nothing host-side.  Degrade
                # the chunk budget and re-derive the remaining pending
                # work (committed tasks are no longer pending).
                if crashes >= 3 or not self._is_device_crash(e):
                    raise
                crashes += 1
                self._on_device_crash(e)
                retry = True
                continue
            retry = never_any and progress_any
            if not progress_any:
                break
        if had_aff_chunks and not crashes:
            # Gradual budget recovery: after _SCALE_RECOVER_AFTER clean
            # affinity cycles the degraded budget doubles back toward 1.
            scale = getattr(store, "_aff_budget_scale", 1.0)
            if scale < 1.0:
                clean = getattr(store, "_aff_clean_cycles", 0) + 1
                if clean >= self._SCALE_RECOVER_AFTER:
                    store._aff_budget_scale = min(1.0, scale * 2.0)
                    store._aff_clean_cycles = 0
                else:
                    store._aff_clean_cycles = clean
        if dv_store is not None:
            # Persist the skip proof iff nothing mutated after the last
            # encode — i.e. the final solve of this lane placed nothing
            # (a pipelined dispatch counts: its commit lands next cycle
            # and bumps the mutation counter if it binds, breaking the
            # proof before the next skip check reads it).
            with tracer.span("solve_prep", lanes=lanes):
                tok_now = (
                    self._null_delta_token(solver, rounds)
                    if self._last_encode_token is not None else None)
            dv_store.skip_token = (
                tok_now if tok_now is not None
                and tok_now == self._last_encode_token else None)

    def _solve_sync(self, solver: str, solve_fn, remote, mesh, inputs,
                    pid, profiles, ncls, dv, dv_manifest):
        """Dispatch one synchronous solve (the body of the
        ``device:dispatch`` span) and return its result handle."""
        if solver != "wave":
            return solve_fn(*inputs)
        if remote is not None:
            # Remote-solver split (BASELINE north-star bridge): inputs
            # cross to the device-owning process as one C++-packed
            # frame; assignment vectors come back as numpy.  The child
            # rebuilds node classes from the frame itself.
            from .ops import devincr as _dvm

            result = remote.solve(inputs, pid, profiles,
                                  devincr=dv_manifest)
            if dv_manifest is not None:
                _dvm.of_store(self.store).anchor_dirty()
            mode = getattr(remote, "last_devincr_mode", None)
            if mode in ("warm", "full"):
                metrics.device_incremental_solves.inc(mode=mode)
            return result
        if mesh is not None:
            return self._solve_mesh_dispatch(
                mesh, inputs, pid, profiles, ncls, devincr=dv)
        result = solve_fn(*inputs, pid=pid, profiles=profiles,
                          taint_any=self._taint_any,
                          node_classes=ncls, devincr=dv,
                          shape_marks=self.store._solve_shape_marks)
        self._record_twophase_lanes()
        return result

    # --------------------------------------- device-lane incrementality

    def _dirty_nodes_now(self) -> Optional[np.ndarray]:
        """Node rows touched by the mirror's still-unconsumed dirty pod
        rows (old node from the aggregate shadow — the state as of the
        last derive — plus current node), or None when tracking
        overflowed.  Together with the derive-time captures accumulated
        on the DeviceIncremental this is a superset of every node whose
        solve inputs changed since the previous solve (ISSUE 9)."""
        m = self.m
        if m._pod_dirty_overflow:
            return None
        rows = np.flatnonzero(m._pod_dirty_mask[:self.Pn])
        if not len(rows):
            return np.zeros(0, np.int64)
        aggr = self.aggr
        if len(aggr.sh_node) < self.Pn:
            return None
        nds = np.concatenate([
            m.p_node[rows].astype(np.int64),
            aggr.sh_node[rows].astype(np.int64),
        ])
        return np.unique(nds[nds >= 0])

    # Affinity count tables past this size are not content-hashed per
    # solve; warm shortlists simply disable (full re-rank — today's
    # behavior) there.  8 MB ≈ 8 ms of blake2b worst case on the cycle
    # thread, a bounded fraction of the warm win; beyond it the hash
    # itself would eat the saving.
    _DEVINCR_CNT0_HASH_MAX = 8_000_000

    def _devincr_prepare(self, inputs, mesh, remote: bool):
        """Assemble the device-incremental cache keys + dirty superset
        for the solve about to dispatch (ISSUE 9).  Returns ``(dv,
        manifest)``: the store's DeviceIncremental primed via
        ``begin_solve`` for local/mesh dispatches, or a JSON-able token
        dict for the remote solver child (which keeps its own
        persistent planes keyed on these frames' tokens)."""
        import hashlib

        from .ops import devincr as _dvm
        from .ops import wave as _wave_mod

        m = self.m
        if not _dvm.devincr_on() or not _wave_mod._two_phase_on():
            return None, None
        gen = getattr(self, "_profile_gen", None)
        if gen is None:
            return None, None
        ws = inputs[4]
        wt = (
            float(ws.binpack_weight),
            tuple(np.asarray(ws.binpack_res, np.float32).tolist()),
            float(ws.least_req_weight), float(ws.most_req_weight),
            float(ws.balanced_weight), float(ws.node_affinity_weight),
        )
        cls_tok = self._cls_sig or f"identity-{m.epoch}"
        static_key = (cls_tok, int(gen), wt, int(self._solve_np),
                      self.R)
        aff = inputs[7]
        # The count table's content token, from its entries (the table
        # itself is never on the host); the size rule is the table's.
        cnt0 = count_entries_of(aff.cnt0)
        warm_key = None
        if 4 * cnt0.shape[0] * cnt0.shape[1] <= self._DEVINCR_CNT0_HASH_MAX:
            if len(cnt0.rows):
                h = hashlib.blake2b(digest_size=16)
                h.update(repr(cnt0.shape).encode())
                for col in (cnt0.rows, cnt0.cols, cnt0.vals):
                    h.update(col.tobytes())
                cnt0_tok = h.hexdigest()
            else:
                cnt0_tok = f"z{cnt0.shape}"
            warm_key = (static_key, int(m.epoch),
                        int(m.node_liveness_gen), int(m.compact_gen),
                        self.Nn, cnt0_tok)
        dv = self.store._devincr_cache
        if dv is None:
            dv = _dvm.of_store(self.store)
        dirty = dv.take_dirty(self._dirty_nodes_now())
        # None = tracking overflowed: the whole node axis re-ranks.
        self._solve_counts()["dirty_nodes"] = (
            None if dirty is None else int(len(dirty)))
        if remote:
            return None, {
                "static_key": repr(static_key),
                "warm_key": repr(warm_key) if warm_key is not None
                else None,
                "dirty_nodes": (dirty.tolist() if dirty is not None
                                else None),
            }
        dv.set_mesh(mesh)
        dv.begin_solve(static_key, warm_key, dirty)
        return dv, None

    def _null_delta_token(self, solver: str, rounds: int):
        """Content token over every input the allocate lane's solve is
        a function of: equality across cycles proves a re-dispatched
        solve would see bit-equal inputs and reproduce the previous
        (empty) outcome — the null-delta fast cycle's skip proof
        (ISSUE 9).  Conservative by construction: any mirror mutation
        (mutation_seq/dirty_seq), node churn (epoch/liveness), row
        renumbering (compact_gen), PodGroup phase/min-member drift, or
        queue share/deserved change breaks equality."""
        import hashlib

        m = self.m
        Jn = self.Jn
        h = hashlib.blake2b(digest_size=16)
        h.update(m.j_phase_code[:Jn].tobytes())
        h.update(m.j_minav[:Jn].tobytes())
        h.update(np.ascontiguousarray(self.q_deserved).tobytes())
        h.update(np.ascontiguousarray(self.q_alloc).tobytes())
        return (
            int(m.mutation_seq), int(m.epoch), int(m.compact_gen),
            int(m.dirty_seq), int(m.node_liveness_gen),
            self.Pn, self.Nn, Jn, self.Qn, self.R,
            h.hexdigest(), solver, int(rounds),
            tuple(self.action_names), tuple(sorted(self.plugin_opts)),
        )

    # ------------------------------------------------- pipelined sessions

    def _dispatch_async(self, cjobs: List[int], crows: np.ndarray,
                        kind: str, payload, solve_id: int = 0,
                        devincr_token=None) -> None:
        """Park a dispatched-but-unread device solve on the store; the
        device round trip then runs concurrently with this cycle's
        backfill/close/enqueue and the next cycle's derive, and
        ``_commit_inflight`` lands it at the top of cycle N+1 (the
        double-buffered session of ISSUE 1).  ``payload`` is either a
        jax ``AllocResult`` with ``copy_to_host_async`` already issued
        (kind "local") or a ``solver_service.PendingSolve`` (kind
        "remote"); ``solve_id`` is the trace flow id linking this
        dispatch to next cycle's fetch/commit spans."""
        from .pipeline import InflightSolve

        # Commit prep that needs no assignment overlaps the round trip.
        req_gather = self.m.c_req.gather(crows)
        # Journey: these rows entered a device solve (first-time rows
        # record with the flow's solve-id; repeats bulk-count).  Under
        # the ``dispatch`` span here, so a child and no lane.
        with self.tracer.span("dispatch:journey") as sp:
            sp.args = self._journey_rows(crows, "dispatched",
                                         solve_id=solve_id)
        shard_idx = None if self.shard is None else self.shard.index
        shard_seq = None
        if self.shard is not None:
            # Cross-shard gate token: sibling commits bump the first
            # component, queue steals the second (shard.py, ISSUE 16).
            shard_seq = (int(self.m.shard_commit_seq),
                         int(self.shard.table.epoch))
        inflight = InflightSolve(
            kind, payload, list(cjobs), crows, req_gather,
            self.m.mutation_seq, self.m.epoch, self.m.compact_gen,
            self.Nn, solve_id=solve_id, dirty_seq=self.m.dirty_seq,
            devincr_token=devincr_token, shard=shard_idx,
            shard_seq=shard_seq,
        )
        if self.shard is None:
            self.store._inflight_solve = inflight
        else:
            self.store._shard_inflight[self.shard.index] = inflight

    def _solve_mesh(self, args: Optional[Arguments] = None):
        """The mesh this store's solves run on, or None for one device
        (``parallel/mesh.mesh_from_env``): an embedder's
        ``store.solve_mesh``, then the ``mesh`` argument of the conf's
        ``allocate`` action (``args``; looked up when not handed in),
        then ``VOLCANO_TPU_MESH``."""
        from .parallel.mesh import mesh_from_env

        if args is None:
            args = get_action_args(self.conf.configurations, "allocate")
        return mesh_from_env(self.store, args.get("mesh") if args else None)

    def _mesh_shards(self) -> int:
        """Devices of the mesh ``_solve_mesh`` last resolved (1: none)."""
        mesh = getattr(self.store, "solve_mesh", None)
        return 1 if mesh is None else int(mesh.devices.size)

    def _solve_mesh_dispatch(self, mesh, inputs, pid, profiles, ncls,
                             devincr=None):
        """Dispatch the wave solve over the device mesh: node axis +
        affinity count tensors sharded (parallel/mesh.py
        shard_wave_inputs), the two-phase rankings shard-local with the
        per-profile winner reduction as the only cross-chip step
        (ops/wave.py _topk_nodes).  The sharded devsnap planes pass
        straight through committed; the remaining epoch-stable plane
        (aff.node_dom) rides the store's declared mesh plane cache
        (cleared on close()/compaction, guarded by the store lock this
        cycle already holds).  The shape buckets are the store's, as on
        one device (``store._solve_shape_marks``)."""
        from .parallel.mesh import sharded_solve_wave_cycle

        placed: Dict[str, int] = {}
        result = sharded_solve_wave_cycle(
            mesh, inputs, pid, profiles,
            plane_cache=self.store._mesh_plane_cache,
            epoch=self.m.epoch,
            taint_any=self._taint_any,
            node_classes=ncls,
            devincr=devincr,
            shape_marks=self.store._solve_shape_marks,
            # ``device:shard`` (child of ``device``; of ``dispatch`` or
            # ``whatif_solve`` on those paths): the host -> mesh
            # placement alone.
            shard_span=lambda: self.tracer.span("device:shard",
                                                cat="device"),
            placed=placed,
        )
        sc = self._solve_counts()
        sc["mesh_put_bytes"] = sc.get("mesh_put_bytes", 0) + placed["bytes"]
        sc["mesh_resident_bytes"] = (sc.get("mesh_resident_bytes", 0)
                                     + placed["resident_bytes"])
        self._record_twophase_lanes()
        return result

    def _inflight_voided(self, inflight) -> bool:
        """True when the parked solve predates a mirror compaction and
        was dropped whole."""
        m = self.m
        if inflight.compact_gen != m.compact_gen:
            # Pod rows were renumbered while the solve was in flight;
            # the whole result is void (rows are otherwise stable for a
            # pod's lifetime).  The pods are still Pending and re-place
            # this cycle.
            log.info("in-flight solve predates a mirror compaction; "
                     "dropped (%d rows re-place this cycle)",
                     len(inflight.task_rows))
            self._count_drops({"compaction": len(inflight.task_rows)})
            # Row indices are void, but the compaction preserved uids
            # 1:1 — the journey masks rebuilt on the gen bump, so the
            # uid lookup below must NOT use the stale rows.  The void
            # is whole-result: attribute it without row translation.
            jr = getattr(self.store, "journey", None)
            if jr is not None:
                jr.repeat_rows(len(inflight.task_rows), "unbound")
            self.stats["device_events"].append(
                f"solve {inflight.solve_id} voided by mirror compaction"
            )
            inflight.abandon()
            return True
        return False

    def _commit_inflight(self) -> None:
        """Fetch + commit the previous cycle's dispatched solve (runs
        first, before this cycle's actions).  A staleness guard drops
        rows invalidated by store mutations that landed during the
        overlap — pod deleted/bound/evicted, node gone, capacity taken
        by the fast path — the same per-task semantics the async-bind
        failure queue already has; everything else commits exactly as a
        synchronous cycle would have."""
        from .pipeline import take_inflight

        m = self.m
        lanes = self.lanes
        tracer = self.tracer
        # The ``inflight`` lane is the taking of the parked solve and
        # its void check; the fetch below is ``device`` and the commit
        # is ``commit``, each top-level (the lanes rule).
        with tracer.span("inflight", lanes=lanes):
            inflight = take_inflight(
                self.store,
                None if self.shard is None else self.shard.index,
            )
            if inflight is None:
                return
            if self._inflight_voided(inflight):
                return
        flow = inflight.solve_id or None
        # committed_solve_id is set only once the fetch SUCCEEDS: a
        # record showing a committed id with zero drops for a solve
        # whose reply was lost would read as a clean commit — exactly
        # the investigation the recorder exists for.
        fetch_span = tracer.span(
            "inflight_fetch", cat="pipeline", flow=flow, lanes=lanes,
            lane="device",
            args={"rows": len(inflight.task_rows),
                  "solve_id": inflight.solve_id},
        )
        try:
            with fetch_span:
                assigned = inflight.fetch()
        except Exception as e:
            if inflight.kind == "remote" and isinstance(
                    e, (OSError, ConnectionError, ValueError)):
                # Lost reply (solver child died, connection dropped):
                # the pods are still Pending and re-place below; a
                # persistently DEAD child surfaces synchronously at
                # this cycle's own dispatch (solve_async's send) — but
                # a child that keeps replying garbage (codec drift)
                # never fails the send, so consecutive fetch failures
                # are capped: past the cap the cycle fails loudly and
                # the scheduler's failure/health accounting takes over
                # instead of looping forever placing nothing.
                fails = getattr(
                    self.store, "_remote_fetch_fails", 0) + 1
                self.store._remote_fetch_fails = fails
                if fails >= self.REMOTE_FETCH_FAIL_CAP:
                    log.error(
                        "in-flight remote solve fetch failed %d "
                        "consecutive times; failing the cycle", fails,
                    )
                    raise
                log.warning(
                    "in-flight remote solve reply lost; %d rows "
                    "re-place this cycle",
                    len(inflight.task_rows), exc_info=True,
                )
                self._count_drops(
                    {"lost-reply": len(inflight.task_rows)})
                self._journey_rows(inflight.task_rows, "dropped",
                                   solve_id=inflight.solve_id,
                                   detail="lost-reply")
                self.stats["device_events"].append(
                    f"solve {inflight.solve_id} reply lost "
                    f"({type(e).__name__}); fetch failure "
                    f"{fails}/{self.REMOTE_FETCH_FAIL_CAP}"
                )
                self._devincr_drop_skip()
                self._record_pool_fetch()
                return
            if self._is_device_crash(e):
                # Execution-time exhaustion surfaces at the async fetch,
                # not at dispatch: route them through the same budget
                # degradation the synchronous solve gets (halve the
                # affinity chunk budget, re-probe the runtime; raises
                # when the device stayed down so the scheduler's
                # failure/health accounting takes over).
                log.warning(
                    "in-flight solve fetch hit a device crash; %d "
                    "rows re-place this cycle",
                    len(inflight.task_rows),
                )
                # The crash event itself lands via _on_device_crash.
                self._count_drops(
                    {"device-crash": len(inflight.task_rows)})
                self._journey_rows(inflight.task_rows, "dropped",
                                   solve_id=inflight.solve_id,
                                   detail="device-crash")
                self._devincr_drop_skip()
                self._on_device_crash(e)
                return
            # A programming error must propagate, exactly as it would
            # from a synchronous solve.
            raise
        self.store._remote_fetch_fails = 0
        self._count_fetch(assigned)
        self.stats["committed_solve_id"] = inflight.solve_id or None
        self._count_shortlist_fb(*inflight.fallbacks)
        self._record_pool_fetch()
        if inflight.kind == "remote":
            # The child reported its device-incremental decision in the
            # reply manifest (decoded by the fetch above).
            mode = getattr(self._remote_solver,
                           "last_devincr_mode", None)
            if mode in ("warm", "full"):
                metrics.device_incremental_solves.inc(mode=mode)
        # The residual wait is the pipeline's health signal: it
        # approaches zero exactly when the overlap works.  The
        # dispatch->available round trip is unobservable here (the
        # solve may have finished during the inter-cycle sleep), so
        # device_solve_latency keeps its synchronous-solve meaning and
        # gets nothing from this path.
        fetch_wait_ms = fetch_span.dur_ns / 1e6
        metrics.inflight_fetch_wait.observe(fetch_wait_ms)
        self.stats["fetch_wait_ms"] = round(fetch_wait_ms, 3)
        # Dispatch-vs-commit delta of the solve LANDING this cycle (how
        # much the world moved during its overlap); the solve this cycle
        # dispatches is paired in the NEXT cycle's record.
        self.stats["mut_at_dispatch"] = int(inflight.mutation_seq)
        self.stats["epoch_at_dispatch"] = int(inflight.epoch)
        self.stats["mut_at_commit"] = int(m.mutation_seq)
        self.stats["epoch_at_commit"] = int(m.epoch)
        with tracer.span(
                "inflight_commit", cat="pipeline", flow=flow,
                lanes=lanes, lane="commit",
                args={"solve_id": inflight.solve_id,
                      "dispatch_mutation_seq": inflight.mutation_seq,
                      "dispatch_epoch": inflight.epoch}):
            task_rows = inflight.task_rows
            assigned = np.asarray(assigned[:len(task_rows)]).astype(
                np.int64, copy=False)
            req_gather = inflight.req_gather
            stale = (m.mutation_seq != inflight.mutation_seq
                     or self.Nn != inflight.n_nodes)
            # Cross-shard commit gate (shard.py, ISSUE 16): the token
            # captured at dispatch was (mirror.shard_commit_seq,
            # ownership-table handoff epoch).  An advance of the first
            # component means ANOTHER shard committed binds during the
            # overlap (our own shard never commits after its own
            # pipelined dispatch within one cycle); the second forces
            # re-validation across a queue steal even when nothing else
            # moved.  mutation_seq already makes the commit-race case
            # stale — cross_shard only re-attributes the voids.
            cross_shard = False
            if self.shard is not None and inflight.shard_seq is not None:
                cur_seq = (int(m.shard_commit_seq),
                           int(self.shard.table.epoch))
                cross_shard = cur_seq != inflight.shard_seq
                stale = stale or cross_shard
            if not stale and m.dirty_seq != inflight.dirty_seq:
                # Agreement contract (ISSUE 8): every writer that marks
                # the dirty set also bumps the mutation counter, so a
                # quiet mutation_seq with an advanced dirty_seq means a
                # writer broke the contract — revalidate defensively
                # instead of skipping on the broken proof.
                log.error(
                    "dirty set advanced (%d -> %d) without a "
                    "mutation_seq bump; revalidating in-flight solve "
                    "defensively", inflight.dirty_seq, m.dirty_seq,
                )
                stale = True
            if stale:
                assigned = self._revalidate_inflight(
                    task_rows, assigned,
                    node_churn=(m.epoch != inflight.epoch),
                    cross_shard=cross_shard,
                )
                # Row set changed: let _commit re-gather the committed
                # rows.
                req_gather = None
            # Fabric gate after the staleness guard: rows it vetoes are
            # already -1, so the topology-infeasible reason stays
            # exclusive with the revalidation vocabulary.
            assigned = self._topology_gate(
                task_rows, assigned, solve_id=inflight.solve_id)
            if (assigned >= 0).any():
                self._commit(
                    inflight.solve_jobs, task_rows, assigned,
                    np.zeros(len(inflight.solve_jobs), bool),
                    np.zeros(len(task_rows), bool), req_gather,
                )

    def _revalidate_inflight(self, task_rows: np.ndarray,
                             assigned: np.ndarray,
                             node_churn: bool = False,
                             cross_shard: bool = False) -> np.ndarray:
        """Drop assignment rows invalidated during the overlap; returns
        ``assigned`` with conflicting rows forced to -1.

        Checks, all vectorized: the pod row is still alive + Pending
        (deletes, fast-path binds, evictions, bind-failure resyncs all
        leave some other status), the target node row still exists, is
        alive and ready, and charging the surviving rows neither
        oversubscribes a node's allocatable nor its task slots (rows on
        a conflicted node are dropped wholesale — conservative, the
        next cycle re-places them).

        Constraint-sensitive rows cannot be re-checked cheaply, so they
        drop conservatively and re-place next cycle against fresh
        state: pods with inter-pod terms whenever ANY mutation landed
        (a peer's placement may have moved the affinity landscape), and
        pods with a node selector, node-affinity terms, or tolerations
        when ``node_churn`` says the node table itself changed (labels/
        taints the solve matched against are stale).

        Every dropped row is attributed to exactly ONE reason (first
        matching check, in the order below), counted into the cycle's
        flight record and the ``volcano_pipeline_stale_drop_rows_total``
        series — the per-reason totals sum exactly to the rows dropped:

        - ``deleted``              pod row no longer alive
        - ``competing-bind``       alive but no longer Pending (bound /
                                   evicted / resynced elsewhere)
        - ``constraint-sensitive`` inter-pod terms + any mutation
        - ``node-epoch-churn``     node-sensitive constraints under
                                   epoch churn, or the target node row
                                   gone / not ready
        - ``capacity-taken``       surviving charge would oversubscribe
                                   the node's allocatable or task slots

        One more exclusive reason joins this vocabulary downstream:
        ``topology-infeasible``, applied by the fabric gate
        (``_topology_gate``) that runs right after this guard — a
        require-contiguous gang whose SURVIVING rows span more than one
        fabric block drops wholesale there, so the attribution stays
        one-reason-per-row across both stages.

        Under the sharded control plane (``cross_shard=True``: another
        shard committed binds, or a queue steal landed, during the
        overlap — shard.py, ISSUE 16) the two reasons a sibling's binds
        produce — ``competing-bind`` and ``capacity-taken`` — are
        re-attributed as the single ``cross-shard-conflict`` reason and
        fed to ``volcano_shard_conflicts_total{outcome}`` by losing
        check.  The counts MOVE (never double-counted), so the
        per-reason totals still sum exactly to the rows dropped.
        """
        m = self.m
        nn = self.Nn
        live = assigned >= 0
        alive_m = m.p_alive[task_rows]
        pending_m = alive_m & (m.p_status[task_rows] == ST_PENDING)
        r_deleted = live & ~alive_m
        r_competing = live & alive_m & ~pending_m
        ok = live & pending_m
        has_ip = m.p_has_ip[task_rows]
        r_constraint = ok & has_ip
        ok &= ~has_ip
        r_churn = np.zeros(len(task_rows), bool)
        if node_churn:
            aff_lo, aff_hi = m.aff_ranges(task_rows)
            sensitive = m.p_has_tol[task_rows] | (aff_lo < aff_hi)
            er, _li = m.c_sel.gather(task_rows)
            has_sel = np.zeros(len(task_rows), bool)
            has_sel[er] = True
            r_churn |= ok & (sensitive | has_sel)
            ok &= ~(sensitive | has_sel)
        # Target node gone (row beyond today's table) or not ready:
        # the node table moved under the solve — churn.
        node_gone = assigned >= nn
        r_churn |= ok & node_gone
        ok &= ~node_gone
        node = np.clip(assigned, 0, max(nn - 1, 0))
        if nn:
            not_ready = ~self.n_ready[node]
            r_churn |= ok & not_ready
            ok &= ~not_ready
        r_capacity = np.zeros(len(task_rows), bool)
        if ok.any():
            # Capacity re-check against TODAY's derive: the req gather
            # is re-read (a pod update may have changed requests in
            # place).
            rows_ok = task_rows[ok]
            nodes_ok = assigned[ok]
            er, si, v = m.c_req.gather(rows_ok)
            add = np.bincount(
                nodes_ok[er].astype(np.int64) * self.R + si,
                weights=v, minlength=nn * self.R,
            ).reshape(nn, self.R).astype(F)
            ntasks_add = np.bincount(nodes_ok, minlength=nn).astype(I)
            bad = (
                ((self.n_used + add) > self.n_alloc + self.eps[None, :])
                .any(axis=1)
                | ((self.n_ntasks + ntasks_add) > self.n_maxtasks)
            )
            if bad.any():
                r_capacity = ok & bad[node]
                ok &= ~bad[node]
        drops = {
            "deleted": int(np.count_nonzero(r_deleted)),
            "competing-bind": int(np.count_nonzero(r_competing)),
            "constraint-sensitive": int(np.count_nonzero(r_constraint)),
            "node-epoch-churn": int(np.count_nonzero(r_churn)),
            "capacity-taken": int(np.count_nonzero(r_capacity)),
        }
        if cross_shard:
            n_comp = drops.pop("competing-bind")
            n_cap = drops.pop("capacity-taken")
            drops["cross-shard-conflict"] = n_comp + n_cap
            if n_comp:
                metrics.shard_conflicts.inc(
                    n_comp, outcome="competing-bind")
            if n_cap:
                metrics.shard_conflicts.inc(
                    n_cap, outcome="capacity-taken")
            if self.shard is not None:
                self.shard.conflicts += n_comp + n_cap
        self._count_drops(drops)
        # Journey: per-pod exclusive drop attribution (the why-pending
        # evidence chain).  Drop sets are churn-sized; cross-shard
        # conflicts carry the ownership-table handoff epoch so the
        # stitched timeline shows WHICH handoff generation lost.
        if getattr(self.store, "journey", None) is not None:
            epoch = (-1 if self.shard is None
                     else int(self.shard.table.epoch))
            for mask, reason in ((r_deleted, "deleted"),
                                 (r_competing, "competing-bind"),
                                 (r_constraint, "constraint-sensitive"),
                                 (r_churn, "node-epoch-churn"),
                                 (r_capacity, "capacity-taken")):
                if not mask.any():
                    continue
                if cross_shard and reason in ("competing-bind",
                                              "capacity-taken"):
                    reason = "cross-shard-conflict"
                self._journey_rows(task_rows[mask], "dropped",
                                   epoch=epoch, detail=reason)
        out = np.where(ok, assigned, -1)
        n_drop = int(np.count_nonzero(live & (out < 0)))
        if n_drop and not ok.any():
            log.info("in-flight solve fully invalidated by "
                     "concurrent mutations (%d rows)", n_drop)
        elif n_drop:
            log.info(
                "staleness guard dropped %d/%d in-flight rows "
                "(concurrent store mutations); survivors commit",
                n_drop, int(np.count_nonzero(live)),
            )
        return out

    # ------------------------------------------------------ topology gates

    def _topo_active(self) -> bool:
        """Cheap master gate for every fabric-topology hook: the kill
        switch is up, at least one job carries a constraint, and the
        cluster has fabric-labeled nodes.  An unlabeled cluster (or
        ``VOLCANO_TPU_TOPOLOGY=0``) short-circuits every hook, keeping
        the solve inputs — and the remote wire frames — byte-identical
        to the pre-topology build."""
        from .ops import topology as topo

        if not topo.topology_on():
            return False
        m = self.m
        if self.Jn == 0 or not m.j_topo[:self.Jn].any():
            return False
        return topo.has_fabric(m)

    def _topo_block_fit(self, jrow: int):
        """Per-fabric-block whole-gang fit of job ``jrow``'s pending
        tasks (ops/topology.gang_block_fit, fetched host-side), or None
        when the gang has nothing pending.  Returns a dict with the
        padded [Np] block-id plane, the per-block cfit/whole/score
        (trash row sliced off), and the profile counts."""
        import jax

        from .ops import topology as topo

        m = self.m
        _, block, n_blocks = topo.fabric_planes(m)
        if n_blocks == 0:
            return None
        Pn = self.Pn
        pend = np.flatnonzero(
            m.p_alive[:Pn] & (m.p_status[:Pn] == ST_PENDING)
            & ~m.p_be[:Pn] & (self.jobr == jrow)
        )
        if not len(pend):
            return None
        # Distinct profiles of the gang's pending tasks -> dense [U, R]
        # init-request table + per-profile counts (same interning
        # _plan_rebalance's prof_req uses).
        _, first, counts = np.unique(
            m.p_prof[pend], return_index=True, return_counts=True
        )
        order = np.argsort(first)
        urows = pend[first[order]]
        counts = counts[order]
        # Pow2 buckets on every static axis (profile rows, node rows,
        # block rows) so fabric growth and gang-shape churn share a
        # bounded set of compiled kernels (VCL204: planes are padded to
        # the _solve_inputs buckets).
        Up = _pow2(max(len(urows), 1), 4)
        prof_req = np.zeros((Up, self.R), F)
        er, si, v = m.c_init_req.gather(urows)
        prof_req[er, si] = v
        prof_cnt = np.zeros((Up,), I)
        prof_cnt[:len(urows)] = counts
        Np = _pow2(max(self.Nn, 1))

        def padN(a, fill=0):
            out = np.full((Np, *a.shape[1:]), fill, a.dtype)
            out[:len(a)] = a
            return out

        bid = np.full((Np,), -1, I)
        bid[:self.Nn] = block[:self.Nn]
        Bp = _pow2(max(n_blocks, 1), 4)
        bf = topo.gang_block_fit(
            padN(self.n_idle.astype(F)), padN(self.n_ready),
            padN(self.n_ntasks), padN(self.n_maxtasks), bid,
            prof_req, prof_cnt, self.eps, n_blocks=Bp,
        )
        cfit, whole, score = jax.device_get((bf.cfit, bf.whole, bf.score))
        return {
            "block": bid, "n_blocks": n_blocks,
            "cfit": cfit[:n_blocks], "whole": whole[:n_blocks],
            "score": score[:n_blocks], "prof_cnt": prof_cnt,
        }

    def _topology_pregate(self, solve_jobs: List[int],
                          task_rows: np.ndarray):
        """Require-contiguous gate ahead of the solve: a gang no fabric
        block can host WHOLE is excluded from the solve inputs — it
        reports the exclusive drop reason ``topology-infeasible``
        (journey + placement counter, on the gating transition) instead
        of scattering across blocks.  The starvation this creates is
        what the rebalance lane's fabric-defrag targeting relieves."""
        if not self._topo_active():
            return solve_jobs, task_rows
        m = self.m
        jt = m.j_topo
        req_jobs = [j for j in solve_jobs if jt[j] == TOPOLOGY_REQUIRE]
        if not req_jobs:
            return solve_jobs, task_rows
        gated = getattr(self.store, "_topo_gated", None)
        if gated is None:
            gated = self.store._topo_gated = set()
        drop: List[int] = []
        for j in req_jobs:
            tf = self._topo_block_fit(j)
            if tf is None:
                continue
            uid = m.j_uid[j]
            if tf["whole"].any():
                gated.discard(uid)
                continue
            drop.append(j)
            if uid not in gated:
                # Transition accounting only: the gang re-gates every
                # cycle until the fabric changes, and re-counting a
                # standing condition per cycle would swamp both series.
                gated.add(uid)
                metrics.topology_placements.inc(outcome="infeasible")
                self._journey_rows(
                    task_rows[self.jobr[task_rows] == j], "dropped",
                    detail="topology-infeasible",
                )
                log.info(
                    "gang %s requires contiguous placement but no "
                    "fabric block can host it whole; held out of the "
                    "solve (topology-infeasible)", uid,
                )
        if not drop:
            return solve_jobs, task_rows
        dropset = np.zeros(self.Jn, bool)
        dropset[drop] = True
        task_rows = task_rows[~dropset[self.jobr[task_rows]]]
        solve_jobs = [j for j in solve_jobs if not dropset[j]]
        return solve_jobs, task_rows

    def _topo_node_bias(self, solve_jobs, n_pad: int):
        """[n_pad] f32 node-order bias steering the FIRST constrained
        gang of the solve toward its selected fabric block
        (ops/topology.contig_bias), or None when no constraint is live
        — the None case keeps solve_args an 8-tuple, which is the
        wire-byte identity guarantee of the kill switch."""
        from .ops import topology as topo

        if not self._topo_active():
            return None
        jt = self.m.j_topo
        target = next((int(j) for j in solve_jobs if jt[j]), None)
        if target is None:
            return None
        tf = self._topo_block_fit(target)
        if tf is None:
            return None
        sel = topo.select_block(
            tf["whole"], tf["score"],
            require=int(jt[target]) == TOPOLOGY_REQUIRE,
        )
        if sel < 0:
            return None
        bias = topo.contig_bias(tf["block"], sel, n_pad)
        return bias if bias.any() else None

    def _topology_gate(self, task_rows: np.ndarray,
                       assigned: np.ndarray, *,
                       solve_id: int = 0) -> np.ndarray:
        """Post-solve fabric gate: decide each constrained gang's
        placement outcome by the block span of its assigned rows.

        ``require-contiguous`` gangs spanning more than one block (or
        landing off-fabric) are vetoed wholesale — rows drop to -1
        under the exclusive reason ``topology-infeasible`` before any
        commit, so a constrained gang is never bound scattered (the
        constraint's atomicity guarantee; ``gang_block_fit`` is only a
        per-profile upper bound, this is the exact enforcer).  Passing
        gangs count into ``volcano_topology_placements_total`` as
        ``contiguous`` or ``scattered``."""
        from .ops import topology as topo

        if not len(task_rows) or not self._topo_active():
            return assigned
        m = self.m
        jt = m.j_topo
        jobr_rows = self.jobr[task_rows]
        jobs_here = np.unique(jobr_rows)
        topo_jobs = [int(j) for j in jobs_here if j >= 0 and jt[j]]
        if not topo_jobs:
            return assigned
        _, block, _ = topo.fabric_planes(m)
        blk = np.full((max(self.Nn, 1),), -1, I)
        blk[:self.Nn] = block[:self.Nn]
        assigned = np.asarray(assigned).copy()
        veto = np.zeros(len(task_rows), bool)
        for j in topo_jobs:
            rows_mask = ((jobr_rows == j) & (assigned >= 0)
                         & (assigned < self.Nn))
            if not rows_mask.any():
                continue
            bsel = np.unique(blk[assigned[rows_mask]])
            contiguous = bool(len(bsel) == 1 and bsel[0] >= 0)
            if jt[j] == TOPOLOGY_REQUIRE and not contiguous:
                veto |= (jobr_rows == j) & (assigned >= 0)
                metrics.topology_placements.inc(outcome="infeasible")
            else:
                metrics.topology_placements.inc(
                    outcome="contiguous" if contiguous else "scattered"
                )
        if veto.any():
            assigned[veto] = -1
            self._count_drops({"topology-infeasible":
                               int(np.count_nonzero(veto))})
            self._journey_rows(task_rows[veto], "dropped",
                               solve_id=solve_id,
                               detail="topology-infeasible")
        return assigned

    def _solve_chunks(self, solve_jobs: List[int], task_rows: np.ndarray):
        """Split one solve call at job boundaries when the affinity count
        tensors would blow the device-memory budget.

        The solver carries two dense [E, D] int32 count tensors; at
        hyperscale with hostname-domain terms (50k nodes, 12k+ terms)
        that is tens of GB.  Terms active per chunk shrink with the
        chunk's job population, so solving in job-aligned chunks with a
        host commit in between bounds the footprint — and later chunks
        legitimately see earlier chunks' placements (the same state the
        reference's sequential walk would show them)."""
        m = self.m
        raw = os.environ.get("VOLCANO_TPU_AFF_BUDGET_MB", "1024")
        try:
            budget = float(raw) * 1e6
        except ValueError:
            budget = float("nan")
        if not (0 < budget < float("inf")):  # catches NaN, 0, negatives
            if raw != "1024":
                log.warning(
                    "VOLCANO_TPU_AFF_BUDGET_MB=%r is not a positive "
                    "number; using 1024", raw,
                )
            budget = 1024e6
        # Out-of-memory degradation (see _on_device_crash): smaller
        # chunks bound the device footprint after an exhaustion.
        budget *= getattr(self.store, "_aff_budget_scale", 1.0)
        # Footprint scales with the terms the PENDING rows actually touch
        # (the solver compacts [E, D] to active terms), not the mirror's
        # full interned term table.
        er_a, ei_a = m.c_ip_aff.gather(task_rows)
        er_n, ei_n = m.c_ip_anti.gather(task_rows)
        er_s, ei_s, _ = m.c_ip_soft.gather(task_rows)
        refs_row = np.concatenate([er_a, er_n, er_s])
        refs_term = np.concatenate([ei_a, ei_n, ei_s])
        from .ops.wave import bucket_pow2

        E = len(np.unique(refs_term)) if len(refs_term) else 0
        # Crash-recovery bookkeeping: only solves that actually carried
        # affinity terms count as "clean affinity cycles" for walking
        # the degraded chunk budget back up.
        self._chunks_had_terms = E > 0
        # Force domain interning BEFORE sizing (only when terms exist —
        # plain workloads skip the O(N x K) interning walk): the domain
        # table fills lazily in node_dom() (hostname domains intern per
        # node row), so a fresh store's first budget decision otherwise
        # sees D=1, estimates the count tensors at ~0.1 MB, and never
        # chunks — shipping an [E, D~N] int32 pair (6.5 GB at
        # 50k x 500k) that exhausts a 16 GB chip's memory.
        if E:
            # ``solve_prep:node_dom`` (child): the rebuild of a dirty
            # node-domain table, the cycle's first reader of which is
            # this call (a Python walk over every node and key).
            with (self.tracer.span("solve_prep:node_dom")
                  if m.node_dom_dirty() else contextlib.nullcontext()) as sp:
                dom = m.node_dom()
                if sp is not None:
                    sp.args = {"nodes": int(dom.shape[0]),
                               "keys": int(dom.shape[1]),
                               "domains": len(m.domains)}
        D = max(1, len(m.domains))
        # Two int32 [Ep, D] tensors; budget against the solver's actual
        # padded bucket (headroom + pow2 round-up reaches 2.5x raw).  On
        # a mesh the pair shards on the domain axis
        # (parallel/mesh.shard_wave_inputs), so what meets the budget is
        # one chip's share of it.
        per_cell = 8.0 / self._mesh_shards()
        cost = float(bucket_pow2(E, floor=1)) * D * per_cell if E else 0.0
        if cost <= budget or len(solve_jobs) <= 1:
            if cost > budget:
                log.warning(
                    "affinity count tensors ~%.0f MB exceed the %.0f MB "
                    "budget but a single job cannot be split",
                    cost / 1e6, budget / 1e6,
                )
            yield solve_jobs, task_rows
            return
        order = np.argsort(refs_row, kind="stable")
        refs_row = refs_row[order]
        refs_term = refs_term[order]
        # 2x factor: each chunk's term count re-pads to the next pow2
        # bucket (worst case ~2x its raw share), so splitting at the
        # raw cost alone leaves per-chunk tensors over budget.
        n_chunks = min(int(np.ceil(cost * 2.0 / budget)), len(solve_jobs))
        target = max(1, int(np.ceil(len(task_rows) / n_chunks)))
        jr = self.jobr[task_rows]
        # Job segment boundaries in the job-contiguous task_rows.
        seg_starts = np.flatnonzero(
            np.concatenate(([True], jr[1:] != jr[:-1]))
        )
        seg_ends = np.concatenate((seg_starts[1:], [len(task_rows)]))

        def emit(cjobs, lo, hi):
            i0, i1 = np.searchsorted(refs_row, [lo, hi])
            e_chunk = len(np.unique(refs_term[i0:i1]))
            padded = (
                bucket_pow2(e_chunk, floor=1) * D * per_cell
                if e_chunk else 0.0
            )
            if padded > budget:
                log.warning(
                    "solve chunk of %d jobs still carries ~%.0f MB of "
                    "affinity count tensors (budget %.0f MB)",
                    len(cjobs), padded / 1e6, budget / 1e6,
                )
            return cjobs, task_rows[lo:hi]

        chunk_jobs: List[int] = []
        lo = 0
        hi = 0
        ji = 0
        for s, e in zip(seg_starts, seg_ends):
            hi = int(e)
            chunk_jobs.append(solve_jobs[ji])
            ji += 1
            if hi - lo >= target and ji < len(solve_jobs):
                yield emit(chunk_jobs, lo, hi)
                chunk_jobs = []
                lo = hi
        if hi > lo or chunk_jobs:
            yield emit(chunk_jobs, lo, hi)

    def _schedulable_rows(self) -> List[int]:
        m = self.m
        srows = np.asarray(self.session_jobs, np.int64)
        if not len(srows):
            return []
        keep = self.j_phase[srows] != 1  # Inqueue gate: skip Pending groups
        # gang JobValid (gang.go:51-72): registered whenever the gang
        # plugin is configured (JobValid has no enable flag).
        if self._has("gang"):
            keep &= self.j_valid[srows] >= m.j_minav[srows]
        # Queue existence: q_of_job is -1 for unknown queues (derive).
        keep &= self.q_of_job[srows] >= 0
        return srows[keep].tolist()

    def _ordered_jobs(self) -> List[int]:
        """Namespace round-robin x queue order x job order, as sorted-list
        merging (allocate.go:107-153).  Returns job rows in processing
        order.

        Heap pops over total-ordered keys (the uid tie-break makes every
        comparator total) produce exactly sorted order, so the object
        path's PriorityQueues reduce to lexsorts over interned
        namespace/queue code columns; the final round-robin ("one job per
        namespace per round") is a second lexsort on (position-within-
        namespace, namespace-rank).

        Children of the ``order`` lane: ``order:shares``,
        ``order:queues``, ``order:jobs``."""
        m = self.m
        rows = self._schedulable_rows()
        if not rows:
            return []
        span = self.tracer.span
        with span("order:shares"):
            drf_share = self._drf_shares()
            jkeys = self._job_keys(rows, drf_share)
            ns_share = self._ns_shares(drf_share)
        with span("order:queues") as sp:
            overused = self._overused_fn()
            queue_order = self._queue_order_fn()
            ns_order = self._namespace_order_fn(ns_share)

            rows_arr = np.asarray(rows, np.int64)
            nsc = m.j_ns_code[rows_arr]
            qc = m.j_queue_code[rows_arr]
            qinfo = self.store.queues

            # Rank the few distinct namespaces/queues with the comparator
            # closures (the per-JOB work stays in numpy).
            # First-appearance order feeds the stable sorts so comparator
            # ties (if any plugin comparator were non-total) resolve
            # exactly as the object path's insertion-ordered scans did.
            ns_codes, ns_first = np.unique(nsc, return_index=True)
            ns_codes = ns_codes[np.argsort(ns_first, kind="stable")]
            ns_names = [m.ns_names.items[c] for c in ns_codes.tolist()]
            ns_sorted = sorted(ns_names, key=_cmp_key(ns_order))
            ns_rank_of = {n: i for i, n in enumerate(ns_sorted)}
            ns_rank_by_code = np.full(int(ns_codes.max()) + 1, -1, np.int64)
            for c, n in zip(ns_codes.tolist(), ns_names):
                ns_rank_by_code[c] = ns_rank_of[n]

            q_codes, q_first = np.unique(qc, return_index=True)
            q_codes = q_codes[np.argsort(q_first, kind="stable")]
            q_names = [m.qnames.items[c] for c in q_codes.tolist()]
            q_sorted = sorted(
                q_names,
                key=_cmp_key(lambda a, b: queue_order(qinfo[a], qinfo[b])))
            q_rank_of = {n: i for i, n in enumerate(q_sorted)}
            q_rank_by_code = np.full(int(q_codes.max()) + 1, -1, np.int64)
            for c, n in zip(q_codes.tolist(), q_names):
                # Overused queues drop out of this pass entirely
                # (allocate.go:126-143).
                q_rank_by_code[c] = (
                    -1 if overused(qinfo[n]) else q_rank_of[n])
            sp.args = {
                "queues": len(q_codes),
                "overused": int((q_rank_by_code[q_codes] < 0).sum()),
            }

        ns_r = ns_rank_by_code[nsc]
        q_r = q_rank_by_code[qc]
        keep = q_r >= 0
        rows_arr = rows_arr[keep]
        if not len(rows_arr):
            return []
        with span("order:jobs"):
            ns_r = ns_r[keep]
            q_r = q_r[keep]
            # Within a namespace: queues in queue order, jobs by job key.
            order1 = np.lexsort((jkeys[rows_arr], q_r, ns_r))
            seq = rows_arr[order1]
            ns_s = ns_r[order1]
            # Position within the namespace group (groups are contiguous
            # now).
            starts = np.concatenate(([True], ns_s[1:] != ns_s[:-1]))
            group_start = np.maximum.accumulate(
                np.where(starts, np.arange(len(seq)), 0)
            )
            k = np.arange(len(seq)) - group_start
            # Round-robin: k-th jobs of every namespace, namespaces in
            # order.
            final = np.lexsort((ns_s, k))
            return seq[final].tolist()

    def _pending_rows(self, ordered: List[int]):
        """Pending task rows in processing order (job-contiguous)."""
        # Read by the ``order:tasks`` span: did the cached order serve?
        self._pending_cache_hit = False
        m = self.m
        Pn = self.Pn
        status = m.p_status[:Pn]
        alive = m.p_alive[:Pn]
        pending = alive & (status == ST_PENDING) & ~m.p_be[:Pn]
        if not pending.any():
            return None
        rows_all = np.flatnonzero(pending)
        if self.store.bind_backoff:
            # Tasks inside their bind-failure backoff window sit out the
            # cycle (the rate-limited errTasks queue, cache.go:627-649).
            # O(backed-off) host work, not O(pending): each entry carries
            # its pod uid, mapped to a current row via the mirror.
            now = time.time()
            blocked = [
                m.p_row.get(uid, -1)
                for _, nb, uid in self.store.bind_backoff.values()
                if now < nb
            ]
            if blocked:
                rows_all = rows_all[
                    ~np.isin(rows_all, np.asarray(blocked, np.int64))
                ]
            if not len(rows_all):
                return None
        jr = self.jobr[rows_all]
        # Rank of each job in the processing order.
        jrank = np.full(self.Jn + 1, -1, np.int64)
        solve_jobs: List[int] = list(ordered)
        jrank[solve_jobs] = np.arange(len(solve_jobs))
        ranks = jrank[jr]
        keep = ranks >= 0
        rows_all = rows_all[keep]
        if not len(rows_all):
            return None
        ranks = ranks[keep]
        # Incremental reuse (ISSUE 8 order lane): the produced task
        # order is a pure function of (rows_all, ranks, the static
        # per-row prio/create/uid columns, the priority flag).  The
        # steady-state cycle re-pends the same rows in the same job
        # order, so the 100k-row lexsort + tie-break walk is skipped on
        # a content match; compaction renumbers rows, so the key pins
        # compact_gen.
        m_ = self.m
        prio_enabled = any(
            opt.name == "priority"
            for opt in self._tier_opts("enabled_task_order")
        )
        cache = (getattr(self.store, "_pending_order_cache", None)
                 if getattr(self, "_incr", True) else None)
        if (cache is not None
                and cache[0] == (m_.compact_gen, prio_enabled)
                and np.array_equal(cache[1], rows_all)
                and np.array_equal(cache[2], ranks)):
            kept_jobs, task_rows = cache[3]
            self._pending_cache_hit = True
            return list(kept_jobs), task_rows
        # Task order within a job: priority desc, creation asc, uid asc
        # (priority plugin task_order + session default tie-break).
        prio = m.p_prio[rows_all]
        prio_key = -prio if prio_enabled else np.zeros_like(prio)
        create = m.p_create[rows_all]
        # Numeric lexsort first; the uid tie-break (session default) only
        # matters within groups whose (rank, prio, create) triple repeats —
        # creation timestamps are unique monotonic counters, so such groups
        # are rare, and the 100k-element string-array build the full
        # string lexsort needed is skipped entirely.
        order = np.lexsort((create, prio_key, ranks))
        rs, ps, cs = ranks[order], prio_key[order], create[order]
        dup = np.flatnonzero(
            (rs[1:] == rs[:-1]) & (ps[1:] == ps[:-1]) & (cs[1:] == cs[:-1])
        )
        if len(dup):
            p_uid = m.p_uid
            starts = np.flatnonzero(np.concatenate(
                ([True], (rs[1:] != rs[:-1]) | (ps[1:] != ps[:-1])
                 | (cs[1:] != cs[:-1]))
            ))
            ends = np.concatenate((starts[1:], [len(order)]))
            for s, e in zip(starts.tolist(), ends.tolist()):
                if e - s > 1:
                    order[s:e] = sorted(
                        order[s:e], key=lambda i: p_uid[rows_all[i]]
                    )
        task_rows = rows_all[order]
        # Keep only jobs that actually have pending tasks, preserving order.
        present = np.unique(self.jobr[task_rows])
        present_set = set(int(j) for j in present)
        kept_jobs = [j for j in solve_jobs if j in present_set]
        if not kept_jobs:
            return None
        # Freeze + remember for the next cycle's content match (the
        # result rides read-only through encode/commit).
        task_rows.setflags(write=False)
        if getattr(self, "_incr", True):
            self.store._pending_order_cache = (
                (m_.compact_gen, prio_enabled), rows_all, ranks,
                (kept_jobs, task_rows),
            )
        return kept_jobs, task_rows

    # ------------------------------------------------------- solver inputs

    def _score_weights(self) -> ScoreWeights:
        import jax.numpy as jnp

        width = self.R
        merged = {
            "binpack_weight": 0.0,
            "binpack_res": [1.0] * width,
            "least_req_weight": 0.0,
            "most_req_weight": 0.0,
            "balanced_weight": 0.0,
            "node_affinity_weight": 0.0,
        }
        for opt in self._tier_opts("enabled_node_order"):
            if opt.name == "binpack":
                args = Arguments(opt.arguments)
                weight = max(args.get_int("binpack.weight", 1), 1)
                cpu_w = max(args.get_int("binpack.cpu", 1), 0)
                mem_w = max(args.get_int("binpack.memory", 1), 0)
                dense = [0.0] * width
                dense[0] = float(cpu_w)
                dense[1] = float(mem_w)
                for name in (args.get("binpack.resources") or "").split(","):
                    name = name.strip()
                    if not name:
                        continue
                    idx = self.m.scalar_slots.index.get(name)
                    if idx is not None:
                        dense[2 + idx] = float(max(
                            args.get_int(f"binpack.resources.{name}", 1), 0
                        ))
                merged["binpack_weight"] += float(weight)
                merged["binpack_res"] = dense
            elif opt.name == "nodeorder":
                args = Arguments(opt.arguments)
                merged["least_req_weight"] += float(
                    args.get_int("leastrequested.weight", 1))
                merged["most_req_weight"] += float(
                    args.get_int("mostrequested.weight", 0))
                merged["balanced_weight"] += float(
                    args.get_int("balancedresource.weight", 1))
                merged["node_affinity_weight"] += float(
                    args.get_int("nodeaffinity.weight", 1))
        return ScoreWeights(
            binpack_weight=float(merged["binpack_weight"]),
            binpack_res=jnp.asarray(merged["binpack_res"], jnp.float32),
            least_req_weight=float(merged["least_req_weight"]),
            most_req_weight=float(merged["most_req_weight"]),
            balanced_weight=float(merged["balanced_weight"]),
            node_affinity_weight=float(merged["node_affinity_weight"]),
        )

    def _tol_bits_for(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(elem_rows, taint_idx) pairs of tolerated taints per task row.

        Cached per pod feature blob, keyed by the taint-dictionary size
        (append-only: a grown dictionary only adds new taints to test)."""
        m = self.m
        taints = m.taints.items
        nt = len(taints)
        er: List[int] = []
        ti: List[int] = []
        # Tolerations are rare; the p_has_tol column turns the 100k-row
        # feature walk into a scan over just the tolerating rows.
        if not m.p_has_tol[rows].any():
            return np.array(er, np.int64), np.array(ti, np.int64)
        for local in np.flatnonzero(m.p_has_tol[rows]).tolist():
            r = rows[local]
            feat = m.p_feat[r]
            if feat is None or not feat.tol:
                continue
            cache = getattr(feat, "_tol_cache", None)
            if cache is None or cache[0] != nt:
                idxs = []
                for k, (tkey, tval, teff) in enumerate(taints):
                    for tol in feat.tol:
                        if tol.operator == "Exists":
                            key_ok = tol.key == "" or tol.key == tkey
                        else:
                            key_ok = tol.key == tkey and tol.value == tval
                        eff_ok = tol.effect == "" or tol.effect == teff
                        if key_ok and eff_ok:
                            idxs.append(k)
                            break
                cache = (nt, idxs)
                try:
                    feat._tol_cache = cache
                except Exception:
                    pass
            for k in cache[1]:
                er.append(local)
                ti.append(k)
        return np.array(er, np.int64), np.array(ti, np.int64)

    def _task_field_arrays(self, rows: np.ndarray):
        """Per-task solver feature arrays for the given mirror rows
        (leading dim = len(rows)): requests, selector/toleration/port
        bit planes, required/preferred node-affinity alternatives.

        Called with all pending rows on the non-slim (sequential parity)
        path, and with only the profile first-occurrence rows on the
        wave path — tasks sharing a store-interned profile id have
        identical spec-level features, so one row represents them all.
        """
        m = self.m
        P = len(rows)
        R = self.R
        LW = _pow2(max(1, (len(m.labels) + 31) // 32), 1)
        TW = _pow2(max(1, (len(m.taints) + 31) // 32), 1)
        PW = _pow2(max(1, (len(m.ports) + 31) // 32), 1)

        req = np.zeros((P, R), F)
        init_req = np.zeros((P, R), F)
        er, si, v = m.c_req.gather(rows)
        req[er, si] = v
        er, si, v = m.c_init_req.gather(rows)
        init_req[er, si] = v
        sel_bits = np.zeros((P, LW), np.uint32)
        er, li = m.c_sel.gather(rows)
        sel_bits[:P] = _pack_bits(P, LW, er, li)
        tol_bits = np.zeros((P, TW), np.uint32)
        er, ti = self._tol_bits_for(rows)
        if len(er):
            tol_bits[:P] = _pack_bits(P, TW, er, ti)
        port_bits = np.zeros((P, PW), np.uint32)
        er, pi = m.c_ports.gather(rows)
        if len(er):
            port_bits[:P] = _pack_bits(P, PW, er, pi)

        # Required node-affinity alternatives.
        aff_lo, aff_hi = m.aff_ranges(rows)
        n_alts = (aff_hi - aff_lo).astype(np.int64)
        A = _pow2(max(1, int(n_alts.max()) if P else 1), 1)
        aff_bits = np.zeros((P, A, LW), np.uint32)
        aff_terms = np.zeros((P,), I)
        aff_terms[:P] = n_alts
        if n_alts.any():
            alt_rows = np.concatenate([
                np.arange(lo, hi) for lo, hi in zip(aff_lo, aff_hi) if hi > lo
            ]).astype(np.int64)
            task_of_alt = np.repeat(np.arange(P), n_alts)
            slot_of_alt = np.concatenate([
                np.arange(h - l) for l, h in zip(aff_lo, aff_hi) if h > l
            ])
            er, li = m.c_aff_alt.gather(alt_rows)
            flat = _pack_bits(len(alt_rows), LW, er, li)
            aff_bits[task_of_alt, slot_of_alt] = flat

        # Preferred node affinity (normalized to [0,10] per task).
        pref_lo, pref_hi = m.pref_ranges(rows)
        n_pref = (pref_hi - pref_lo).astype(np.int64)
        AP = _pow2(max(1, int(n_pref.max()) if P else 1), 1)
        pref_bits = np.zeros((P, AP, LW), np.uint32)
        pref_w = np.zeros((P, AP), F)
        if n_pref.any():
            pr_rows = np.concatenate([
                np.arange(lo, hi) for lo, hi in zip(pref_lo, pref_hi) if hi > lo
            ]).astype(np.int64)
            task_of_pr = np.repeat(np.arange(P), n_pref)
            slot_of_pr = np.concatenate([
                np.arange(h - l) for l, h in zip(pref_lo, pref_hi) if h > l
            ])
            er, li = m.c_pref.gather(pr_rows)
            flat = _pack_bits(len(pr_rows), LW, er, li)
            pref_bits[task_of_pr, slot_of_pr] = flat
            w = np.array([m.pref_w[r] for r in pr_rows], F)
            totals = np.zeros(P, F)
            np.add.at(totals, task_of_pr, w)
            wn = np.where(totals[task_of_pr] > 0,
                          w / totals[task_of_pr] * 10.0, 0.0)
            pref_w[task_of_pr, slot_of_pr] = wn
        return (req, init_req, port_bits, sel_bits, aff_bits, aff_terms,
                tol_bits, pref_bits, pref_w)

    def _device_snapshot(self):
        """The store's persistent device-resident snapshot, or None on
        paths that ship numpy (remote solver frames — the child process
        owns its device state).
        A mesh store gets the mesh-sharded snapshot: node planes commit
        with the node-axis NamedSharding and delta scatters stay
        shard-local (ops/devsnap.py), so the mesh path no longer
        re-ships numpy planes every cycle."""
        if self._remote_solver is not None:
            return None
        from .ops.devsnap import for_store

        return for_store(self.store,
                         mesh=getattr(self.store, "solve_mesh", None))

    def _solve_inputs(self, solve_jobs: List[int], task_rows: np.ndarray,
                      slim: bool = False):
        self._flush_aggr()
        m = self.m
        P = len(task_rows)
        N = self.Nn
        Np = _pow2(max(N, 1))
        R = self.R
        J = len(solve_jobs)
        # The job axis follows the pending rows: held at its high-water
        # mark like the solve's other data-dependent buckets.
        from .ops.wave import settle
        Jp = settle(self.store._solve_shape_marks, "J", J,
                    _pow2(max(J, 1), 4))
        Qp = _pow2(max(self.Qn, 1), 4)

        LW = _pow2(max(1, (len(m.labels) + 31) // 32), 1)
        TW = _pow2(max(1, (len(m.taints) + 31) // 32), 1)
        PW = _pow2(max(1, (len(m.ports) + 31) // 32), 1)

        # ---- nodes
        # Label/taint bit planes change only on node-table edits or
        # interner growth: cache them on the mirror keyed by
        # (node epoch, word widths) instead of re-gathering the node
        # CSR every cycle (~10 ms at 10k nodes).
        n_label_bits = np.zeros((Np, LW), np.uint32)
        n_taint_bits = np.zeros((Np, TW), np.uint32)
        if N:
            def _build_bits():
                csr_rows = m.node_csr_rows(np.arange(N))
                er, li = m.c_n_labels.gather(csr_rows)
                lb = _pack_bits(N, LW, er, li)
                er, ti = m.c_n_taints.gather(csr_rows)
                return lb, _pack_bits(N, TW, er, ti)

            lbits, tbits = _epoch_cached(
                m, "_node_bits_cache", (m.epoch, N, LW, TW), _build_bits
            )
            n_label_bits[:N] = lbits
            n_taint_bits[:N] = tbits
        n_ports = np.zeros((Np, PW), np.uint32)
        rows_res = np.flatnonzero(self.resident)
        if len(rows_res):
            er, pi = m.c_ports.gather(rows_res)
            if len(er):
                nrows = m.p_node[:self.Pn][rows_res][er]
                n_ports[:N] = _pack_bits(N, PW, nrows, pi)

        def padN(a, fill=0.0):
            out = np.full((Np, *a.shape[1:]), fill, a.dtype)
            out[:len(a)] = a
            return out

        # Wave path: pipelined is identically zero at solve start and
        # releasing is usually all-zero outside eviction cycles; both
        # broadcast as [1, R] dummies in the kernel (FutureIdle adds /
        # subtracts them), skipping their [Np, R] upload.
        releasing_np = self.n_releasing.astype(F)
        if slim and not releasing_np.any():
            releasing_in = np.zeros((1, R), F)
        else:
            releasing_in = padN(releasing_np)
        # Device-resident snapshot (ops/devsnap.py): the node planes that
        # move only with the NODE table — allocatable, max-task counts,
        # readiness, label/taint bit planes — live on the device across
        # cycles, updated by per-row delta scatters from the mirror's
        # dirty set instead of full re-uploads.  Per-cycle planes (idle,
        # ntasks, ports) still ship fresh.  The host copies above stay
        # the taint-feature source (solve_wave must not fetch a device
        # array back to the host just to compute a static flag).
        self._taint_any = bool(n_taint_bits.any()) if slim else None
        snap = self._device_snapshot() if slim else None
        # Node-class compaction (two-phase solve, ops/nodeclass.py):
        # the class grouping is a pure function of the node table, so
        # it rides the same epoch-keyed mirror cache as the bit planes;
        # the wave solver gets the planes pre-built (it must never
        # fetch device-resident node planes back just to group them).
        node_classes = None
        cls_id_host = None
        cls_sig = ""
        from .ops import wave as _wave_mod

        use_classes = slim and N and _wave_mod._two_phase_on()
        if use_classes:
            def _build_classes():
                from .ops.nodeclass import build_node_classes

                cl, n_real, sig = build_node_classes(
                    n_label_bits, n_taint_bits, padN(self.n_ready),
                    padN(self.n_alloc.astype(F)), padN(self.n_maxtasks),
                )
                return (cl.class_id, cl.label_bits, cl.taint_bits,
                        cl.ready, np.array(sig), np.array(n_real))

            (cls_id_host, cls_lb, cls_tb, cls_rd, sig_arr,
             _n_real) = _epoch_cached(
                m, "_node_class_cache", (m.epoch, Np, R, LW, TW),
                _build_classes,
            )
            cls_sig = str(sig_arr)
        if snap is not None and N:
            build = {
                # rows=None -> full padded plane; rows array -> just
                # those rows (devsnap's delta scatter, so a one-node
                # change never materializes full [Np, *] host copies).
                "allocatable": lambda rows: (
                    padN(self.n_alloc.astype(F)) if rows is None
                    else self.n_alloc[rows].astype(F)),
                "max_tasks": lambda rows: (
                    padN(self.n_maxtasks) if rows is None
                    else self.n_maxtasks[rows]),
                "ready": lambda rows: (
                    padN(self.n_ready) if rows is None
                    else self.n_ready[rows]),
                "label_bits": lambda rows: (
                    n_label_bits if rows is None
                    else n_label_bits[rows]),
                "taint_bits": lambda rows: (
                    n_taint_bits if rows is None
                    else n_taint_bits[rows]),
            }
            if use_classes:
                # class_id is [Np] row-indexed, so it shares the node
                # planes' dirty-row delta machinery — valid exactly
                # while the class SET (tables_sig) held still, because
                # classes order by sorted signature (ops/nodeclass.py).
                # A changed set returns None for the delta rows, which
                # devsnap answers with a full upload of THIS plane only
                # ([Np] int32 — tiny); label/taint/capacity planes keep
                # their row scatters.
                prev_sig = getattr(snap, "_last_cls_sig", None)
                build["class_id"] = lambda rows: (
                    cls_id_host if rows is None
                    else (cls_id_host[rows] if prev_sig == cls_sig
                          else None))
            planes = snap.node_planes(m, (m.epoch, Np, R, LW, TW), build)
            if use_classes:
                snap._last_cls_sig = cls_sig
            alloc_in = planes["allocatable"]
            maxt_in = planes["max_tasks"]
            ready_in = planes["ready"]
            lbits_in = planes["label_bits"]
            tbits_in = planes["taint_bits"]
            if use_classes:
                from .ops.nodeclass import NodeClasses

                tables = snap.class_tables(
                    (cls_sig, cls_lb.shape, cls_tb.shape), {
                        "label_bits": lambda: cls_lb,
                        "taint_bits": lambda: cls_tb,
                        "ready": lambda: cls_rd,
                    })
                node_classes = NodeClasses(
                    class_id=planes["class_id"],
                    label_bits=tables["label_bits"],
                    taint_bits=tables["taint_bits"],
                    ready=tables["ready"],
                )
        else:
            alloc_in = padN(self.n_alloc.astype(F))
            maxt_in = padN(self.n_maxtasks)
            ready_in = padN(self.n_ready)
            lbits_in = n_label_bits
            tbits_in = n_taint_bits
            if use_classes:
                from .ops.nodeclass import NodeClasses

                node_classes = NodeClasses(
                    class_id=cls_id_host, label_bits=cls_lb,
                    taint_bits=cls_tb, ready=cls_rd,
                )
        nodes = SolveNodes(
            idle=padN(self.n_idle.astype(F)),
            allocatable=alloc_in,
            releasing=releasing_in,
            pipelined=(np.zeros((1, R), F) if slim
                       else np.zeros((Np, R), F)),
            ntasks=padN(self.n_ntasks),
            max_tasks=maxt_in,
            ports=n_ports,
            ready=ready_in,
            label_bits=lbits_in,
            taint_bits=tbits_in,
        )

        # ---- tasks
        sj = np.asarray(solve_jobs, np.int64)
        jrank = np.zeros(self.Jn + 1, I)
        jrank[sj] = np.arange(J, dtype=I)
        t_job = jrank[self.jobr[task_rows]]
        t_real = np.ones((P,), bool)

        if slim:
            # Wave-solver path: the kernel reads only job/real per-task
            # (req/init_req and every predicate input come from the
            # profile rows, ops/wave.py _solve_wave), so the dense
            # [P, ...] feature arrays are neither built (encode time)
            # nor shipped (upload time).  Profile rows are gathered
            # straight from the mirror at the first-occurrence task rows
            # (_profiles_from_rows).
            tasks = SolveTasks(
                req=np.zeros((1, R), F),
                init_req=np.zeros((1, R), F),
                job=t_job,
                real=t_real,
                ports=np.zeros((1, 1), np.uint32),
                sel_bits=np.zeros((1, 1), np.uint32),
                aff_bits=np.zeros((1, 1, 1), np.uint32),
                aff_terms=np.zeros((1,), I),
                tol_bits=np.zeros((1, 1), np.uint32),
                pref_bits=np.zeros((1, 1, 1), np.uint32),
                pref_w=np.zeros((1, 1), F),
            )
        else:
            (req, init_req, port_bits, sel_bits, aff_bits, aff_terms,
             tol_bits, pref_bits, pref_w) = self._task_field_arrays(
                task_rows)
            tasks = SolveTasks(
                req=req,
                init_req=init_req,
                job=t_job,
                real=t_real,
                ports=port_bits,
                sel_bits=sel_bits,
                aff_bits=aff_bits,
                aff_terms=aff_terms,
                tol_bits=tol_bits,
                pref_bits=pref_bits,
                pref_w=pref_w,
            )

        # ---- jobs
        j_min = np.full((Jp,), 1 << 30, I)
        j_queue = np.zeros((Jp,), I)
        j_ready_base = np.zeros((Jp,), I)
        j_min[:J] = m.j_minav[sj]
        j_queue[:J] = np.maximum(self.q_of_job[sj], 0)
        j_ready_base[:J] = self.j_ready_base[sj]
        jobs = SolveJobs(
            queue=j_queue, min_available=j_min, ready_base=j_ready_base
        )

        # ---- queues
        deserved = np.full((Qp, R), 3.0e38, F)
        q_alloc = np.zeros((Qp, R), F)
        deserved[:self.Qn] = self.q_deserved
        q_alloc[:self.Qn] = self.q_alloc
        queues = SolveQueues(deserved=deserved, allocated=q_alloc)

        # ``encode:affinity`` (child of ``encode``): only a store that
        # has interned an inter-pod term records it.
        with (self.tracer.span("encode:affinity") if len(m.terms)
              else contextlib.nullcontext()):
            aff, pid, profiles = self._affinity_and_profiles(
                task_rows, None if slim else tasks, Np
            )
        weights = self._score_weights()
        # Device-incremental key inputs (ISSUE 9): the class-table
        # content signature (or the identity marker — epoch-keyed) and
        # the padded node axis, read by _devincr_prepare.
        self._cls_sig = cls_sig if use_classes else ""
        self._solve_np = Np
        solve_args = (nodes, tasks, jobs, queues, weights, self.eps,
                      self.scalar_slot, aff)
        if slim:
            # Topology node-order bias (9th solve_args element, sharded
            # under mesh and framed over the remote wire like any node
            # plane).  Appended ONLY when a fabric constraint is live:
            # the 8-tuple form keeps frames and traces byte-identical
            # to the pre-topology build (the kill-switch guarantee).
            bias = self._topo_node_bias(solve_jobs, Np)
            if bias is not None:
                solve_args = solve_args + (bias,)
        return (
            solve_args,
            pid,
            profiles,
            node_classes,
        )

    def _encode_cache_key(self, P: int) -> tuple:
        """Validity key of the per-cycle encode cache: everything the
        cached profile/affinity structures are a function of EXCEPT the
        task-row content itself (compared by array equality).  Row ids
        pin ``compact_gen``; interner/membership sizes pin the static
        dictionaries (append-only, so a size match proves the cached
        rows' encodings are still current); ``epoch`` + domain/topo
        widths pin the node-domain table the counts index into."""
        m = self.m
        return (
            P, self.Pn, self.R, m.compact_gen, m.epoch,
            len(m.terms), m.term_members_total,
            len(m.labels), len(m.taints),
            len(m.ports), len(m.topo_keys), len(m.domains),
        )

    def _term_cnt0(self, active_members: List[np.ndarray],
                   term_key: np.ndarray, Ep: int) -> CountEntries:
        """The [Ep, D] resident-member counts per domain of the active
        terms, as their entries (the table is born on the device,
        ``ops/wave.solve_wave``) — the only piece of the affinity
        encoding that moves with pod placement, so it is recomputed each
        cycle even on an encode cache hit (the membership structures it
        walks are cached)."""
        m = self.m
        D = max(1, len(m.domains))
        node = m.p_node[:self.Pn]
        node_dom_raw = m.node_dom()
        terms, doms = [np.zeros(0, np.int64)], [np.zeros(0, np.int64)]
        for le, members in enumerate(active_members):
            if not len(members):
                continue
            residents = members[self.resident[members]]
            if len(residents):
                dom = node_dom_raw[node[residents], term_key[le]]
                dom = dom[dom >= 0]
                if len(dom):
                    terms.append(np.full(len(dom), le, np.int64))
                    doms.append(dom)
        return count_entries(np.concatenate(terms), np.concatenate(doms),
                             (Ep, D))

    def _affinity_and_profiles(self, task_rows: np.ndarray, tasks,
                               Np: int):
        """Affinity inputs + refined profile ids + the profile rows, all
        at profile granularity — nothing dense in [P, E] is ever built,
        and nothing dense in [U, Ep] or [Ep, D] either: the profile-term
        tables and the resident counts leave as the entries they are
        (``ops/wave.SparseProfiles``, ``arrays/affinity.CountEntries``)
        and ``solve_wave`` has the tables born on the device.

        - Active-term compaction: only terms some pending task is involved
          with enter the solve; inactive terms cannot influence it (their
          counts are neither gated on nor scored).
        - Profile refinement: store-interned profile ids split wherever
          per-cycle term membership differs within a profile (a sibling's
          topology-spread term matches every pod of the job).  Membership
          hashes are accumulated sparsely from the term member lists; the
          collision probability of the two independent 20-bit-coefficient
          hashes is ~2^-40 per pair.
        - Incremental (ISSUE 8 encode lane): on the wave path the whole
          profile/affinity encoding is a pure function of the task-row
          content and the append-only static dictionaries, so it is
          cached on the store and reused when both match — only the
          per-domain resident counts (``_term_cnt0``) and the padded
          node-domain plane rebuild each cycle.
        """
        m = self.m
        P = len(task_rows)

        # Profile content generation (ISSUE 9): a monotone token that
        # moves whenever the profile/affinity encoding is (re)built —
        # an encode-cache hit keeps it, so the device-incremental lane
        # can key its persistent [U, C] static planes and warm
        # shortlists on "the same profile rows as last solve".  Any
        # rebuild (even one producing identical content) bumps it:
        # conservative, the caches just recompute once.
        self._profile_gen = None

        if tasks is None and getattr(self, "_incr", True):
            cached = getattr(self.store, "_encode_cache", None)
            ckey = self._encode_cache_key(P)
            if (cached is not None and cached["key"] == ckey
                    and np.array_equal(cached["task_rows"], task_rows)):
                self._profile_gen = cached.get("gen")
                self._pid_out = cached["pid"]
                E = cached["E"]
                K = max(1, len(m.topo_keys))
                if E == 0:
                    return (empty_affinity(Np, 1), cached["pid"],
                            cached["profiles"])
                term_key = cached["term_key"]
                Ep = cached["Ep"]
                self._count_affinity(task_rows, E, Ep, Np)
                cnt0 = self._term_cnt0(cached["members"], term_key, Ep)
                node_dom_raw = m.node_dom()
                node_dom = np.full((Np, K), -1, I)
                node_dom[:len(node_dom_raw)] = node_dom_raw
                aff = AffinityArgs(
                    node_dom=node_dom,
                    term_key=term_key,
                    cnt0=cnt0,
                    t_req_aff=np.zeros((1, Ep), bool),
                    t_req_anti=np.zeros((1, Ep), bool),
                    t_matches=np.zeros((1, Ep), bool),
                    t_soft=np.zeros((1, Ep), F),
                )
                return aff, cached["pid"], cached["profiles"]

        pid_raw = m.p_prof[task_rows].astype(np.int64)

        # ---- active terms: union of pending tasks' involvement ----------
        er_a, ei_a = m.c_ip_aff.gather(task_rows)
        er_n, ei_n = m.c_ip_anti.gather(task_rows)
        er_s, ei_s, ev_s = m.c_ip_soft.gather(task_rows)
        active = np.unique(np.concatenate([ei_a, ei_n, ei_s]))
        E = len(active)
        gen = getattr(self.store, "_encode_gen", 0) + 1
        self.store._encode_gen = gen
        self._profile_gen = gen
        if E == 0:
            aff = empty_affinity(Np, 1)
            profiles = self._profiles_from_rows(
                tasks, task_rows, pid_raw, None, aff, P
            )
            if tasks is None and getattr(self, "_incr", True):
                self.store._encode_cache = {
                    "key": self._encode_cache_key(P),
                    "task_rows": task_rows.copy(),
                    "pid": self._pid_out, "E": 0,
                    "profiles": profiles, "gen": gen,
                }
            return aff, self._pid_out, profiles

        # Renumber active terms by first reference in task order so each
        # wave's terms form a narrow window (the solver slices every
        # [*, E] tensor to that window — wave.py _term_windows).
        local = np.full(self.Pn, -1, np.int64)
        local[task_rows] = np.arange(P)
        first_ref = np.full(len(m.terms), P, np.int64)
        if len(ei_a):
            np.minimum.at(first_ref, ei_a, er_a)
        if len(ei_n):
            np.minimum.at(first_ref, ei_n, er_n)
        if len(ei_s):
            np.minimum.at(first_ref, ei_s, er_s)
        for e in active:
            members = np.asarray(m.term_members[int(e)], np.int64)
            if len(members):
                loc = local[members[members < self.Pn]]
                loc = loc[loc >= 0]
                if len(loc):
                    first_ref[e] = min(first_ref[e], int(loc.min()))
        active = active[np.argsort(first_ref[active], kind="stable")]

        term_local = np.full(len(m.terms), -1, np.int64)
        term_local[active] = np.arange(E)
        from .ops.wave import settled_pow2

        # The padded term count keeps its high-water mark: a round's
        # active terms are a draw, and a bucket taken anew from each
        # draw flips at a power of two and lowers the solve again.
        Ep = settled_pow2(self.store._solve_shape_marks, "Ep", E, floor=1)
        self._count_affinity(task_rows, E, Ep, Np)

        # ---- sparse membership hash + per-term local membership ---------
        rng = np.random.RandomState(0x7A5E)
        coef = rng.randint(1, 1 << 20, size=(E, 2)).astype(np.int64)
        h1 = np.zeros(P, np.int64)
        h2 = np.zeros(P, np.int64)
        member_locs: List[np.ndarray] = []
        active_members: List[np.ndarray] = []
        node_dom_raw = m.node_dom()
        K = max(1, len(m.topo_keys))
        term_key = np.zeros((Ep,), I)
        for le in range(E):
            e = int(active[le])
            _sel, key, _ns = m.term_info[e]
            term_key[le] = m.topo_keys.index.get(key, 0)
            members = np.asarray(m.term_members[e], np.int64)
            members = members[members < self.Pn] if len(members) else members
            active_members.append(members)
            if len(members):
                loc = local[members]
                loc = loc[loc >= 0]
                if len(loc):
                    h1[loc] += coef[le, 0]
                    h2[loc] += coef[le, 1]
                member_locs.append(loc)
            else:
                member_locs.append(np.zeros(0, np.int64))
        cnt0 = self._term_cnt0(active_members, term_key, Ep)

        combo = (
            pid_raw * np.int64(1_000_003)
            + h1 * np.int64(8191)
            + h2
        )
        profiles = self._profiles_from_rows(
            tasks, task_rows, combo, (member_locs, term_local, Ep,
                                      er_a, ei_a, er_n, ei_n,
                                      er_s, ei_s, ev_s, pid_raw), None, P
        )
        node_dom = np.full((Np, K), -1, I)
        node_dom[:len(node_dom_raw)] = node_dom_raw
        aff = AffinityArgs(
            node_dom=node_dom,
            term_key=term_key,
            cnt0=cnt0,
            t_req_aff=np.zeros((1, Ep), bool),
            t_req_anti=np.zeros((1, Ep), bool),
            t_matches=np.zeros((1, Ep), bool),
            t_soft=np.zeros((1, Ep), F),
        )
        if tasks is None and getattr(self, "_incr", True):
            self.store._encode_cache = {
                "key": self._encode_cache_key(P),
                "task_rows": task_rows.copy(),
                "pid": self._pid_out, "E": E, "Ep": Ep,
                "term_key": term_key, "members": active_members,
                "profiles": profiles, "gen": gen,
            }
        return aff, self._pid_out, profiles

    def _verify_membership_grouping(self, pid, u, combo, term_parts, P):
        """Hash-collision guard: every task's term-membership set must
        equal its profile representative's (the coefficients are fixed per
        process, so an unchecked collision would repeat every cycle).
        Sparse O(memberships) check; exact regrouping on mismatch."""
        (member_locs, _tl, _Ep, _ea, _eia, _en, _ein, _es, _eis, _evs,
         pid_raw) = term_parts
        if not any(len(loc) for loc in member_locs):
            return pid, u
        t_all = np.concatenate([loc for loc in member_locs if len(loc)])
        e_all = np.concatenate([
            np.full(len(loc), le, np.int64)
            for le, loc in enumerate(member_locs) if len(loc)
        ])
        order = np.lexsort((e_all, t_all))
        pt, pe = t_all[order], e_all[order]
        counts = np.bincount(pt, minlength=P)
        offs = np.concatenate(([0], np.cumsum(counts)))
        rep = u[pid]
        ok = bool((counts == counts[rep]).all())
        if ok:
            sel = np.flatnonzero(counts > 0)
            if len(sel):
                lens = counts[sel]
                cum = np.concatenate(([0], np.cumsum(lens)[:-1]))
                base = np.arange(int(lens.sum())) - np.repeat(cum, lens)
                pos_t = base + np.repeat(offs[sel], lens)
                pos_r = base + np.repeat(offs[rep[sel]], lens)
                ok = bool((pe[pos_t] == pe[pos_r]).all())
        if ok:
            return pid, u
        log.warning("profile membership hash collision; exact regrouping")
        keys = {}
        pid2 = np.zeros(P, np.int64)
        u2 = []
        for t in range(P):
            key = (int(pid_raw[t]),
                   tuple(pe[offs[t]:offs[t + 1]].tolist()))
            got = keys.get(key)
            if got is None:
                got = len(u2)
                keys[key] = got
                u2.append(t)
            pid2[t] = got
        return pid2, np.asarray(u2, np.int64)

    def _profiles_from_rows(self, tasks, task_rows: np.ndarray,
                            combo: np.ndarray, term_parts, aff_empty,
                            P: int):
        """Renumber combo ids by first occurrence and gather one profile
        row per distinct id (plus the [U, E] term tables' entries)."""
        from .ops.wave import SparseProfiles, profile_term_entries

        _, first, inv = np.unique(combo, return_index=True,
                                  return_inverse=True)
        order = np.argsort(first, kind="stable")
        rank = np.empty(len(order), np.int64)
        rank[order] = np.arange(len(order))
        pid = rank[inv]
        u = first[order]  # local first-occurrence row per profile
        if term_parts is not None:
            pid, u = self._verify_membership_grouping(
                pid, u, combo, term_parts, P
            )
        self._pid_out = pid
        U = len(u)

        if tasks is None:
            # Slim (wave) path: build the U profile feature rows straight
            # from the mirror at the first-occurrence task rows — the
            # dense [P, ...] arrays were never built.
            (p_req, p_init_req, p_ports, p_sel, p_affb, p_afft, p_tol,
             p_prefb, p_prefw) = self._task_field_arrays(task_rows[u])

            def g(a):
                return a

            rows_by_field = (p_req, p_init_req, p_ports, p_sel, p_affb,
                             p_afft, p_tol, p_prefb, p_prefw)
        else:
            def g(a):
                return np.asarray(a)[u]

            rows_by_field = (tasks.req, tasks.init_req, tasks.ports,
                             tasks.sel_bits, tasks.aff_bits,
                             tasks.aff_terms, tasks.tol_bits,
                             tasks.pref_bits, tasks.pref_w)

        # The four [U, Ep] profile-term tables travel as the entries
        # they are (ops/wave.SparseProfiles): one (profile, term) cell
        # per membership and per reference of a profile's first row.
        rows, cols, flags, soft = [], [], [], []

        def cells(r, c, bit, w=None):
            rows.append(r)
            cols.append(c)
            flags.append(np.full(len(r), bit, np.int8))
            soft.append(np.zeros(len(r), F) if w is None else w)

        if term_parts is None:
            Ep = 1
            cells(np.zeros(0, np.int64), np.zeros(0, np.int64), 0)
        else:
            (member_locs, term_local, Ep, er_a, ei_a, er_n, ei_n,
             er_s, ei_s, ev_s, _pid_raw) = term_parts
            u_index = np.full(P, -1, np.int64)
            u_index[u] = np.arange(U)
            sel = u_index[np.concatenate(member_locs)]
            le = np.repeat(np.arange(len(member_locs)),
                           [len(loc) for loc in member_locs])
            cells(sel[sel >= 0], le[sel >= 0], 4)

            def refs(er, ei, bit, val=None):
                ur = u_index[er]
                keep = ur >= 0
                lei = term_local[ei[keep]]
                ok = lei >= 0
                cells(ur[keep][ok], lei[ok], bit,
                      None if val is None else val[keep][ok])

            refs(er_a, ei_a, 1)
            refs(er_n, ei_n, 2)
            refs(er_s, ei_s, 0, ev_s)
        terms = profile_term_entries(
            *(np.concatenate(x) for x in (rows, cols, flags, soft)),
            (U, Ep))

        return SparseProfiles(*[g(f) for f in rows_by_field], terms)

    # -------------------------------------------------------------- commit

    def _obj_arrays(self):
        """Object ndarrays over the mirror's pod / bind-key / node-name
        lists: fancy indexing + one ``tolist`` replaces 100k-iteration
        Python list comprehensions in the commit path.

        Persistent across cycles (ISSUE 8 commit lane): the arrays live
        on the STORE keyed by (compact_gen, pod_obj_gen) — rows never
        renumber between compactions and record slots only move on
        copy-on-write upserts/removals, so the steady state extends the
        tail for appended rows instead of re-walking 100k records."""
        arrs = getattr(self, "_obj_arr_cache", None)
        if arrs is not None:
            return arrs
        m = self.m
        store = self.store
        Pn, Nn = self.Pn, self.Nn
        # No epoch component: the object arrays read only the pod/key/
        # name LISTS, which are append-only (tail extension below) with
        # record slots versioned by pod_obj_gen — node upserts must not
        # invalidate the 100k-element walk this cache exists to avoid.
        key = (m.compact_gen, m.pod_obj_gen)
        cached = (getattr(store, "_objarr_cache", None)
                  if getattr(self, "_incr", True) else None)
        if cached is not None and cached[0] == key:
            _, built_pn, built_nn, pod_a, key_a, name_a = cached
            if built_pn == Pn and built_nn == Nn:
                arrs = self._obj_arr_cache = (pod_a, key_a, name_a)
                return arrs
            if built_pn <= Pn and built_nn <= Nn:
                # Appended rows/nodes only: extend the tails.
                if built_pn < Pn:
                    pod_a = np.concatenate((pod_a, np.fromiter(
                        m.p_pod[built_pn:Pn], dtype=object,
                        count=Pn - built_pn)))
                    key_a = np.concatenate((key_a, np.fromiter(
                        m.p_key[built_pn:Pn], dtype=object,
                        count=Pn - built_pn)))
                if built_nn < Nn:
                    name_a = np.concatenate((name_a, np.fromiter(
                        m.n_name[built_nn:Nn], dtype=object,
                        count=Nn - built_nn)))
                store._objarr_cache = (key, Pn, Nn, pod_a, key_a,
                                       name_a)
                arrs = self._obj_arr_cache = (pod_a, key_a, name_a)
                return arrs
        # np.fromiter, NOT ndarray slice-assign: the latter probes
        # every element for sequence-ness (60x slower on dataclass
        # records).
        pod_a = np.fromiter(m.p_pod[:Pn], dtype=object, count=Pn)
        key_a = np.fromiter(m.p_key[:Pn], dtype=object, count=Pn)
        name_a = np.fromiter(m.n_name[:Nn], dtype=object, count=Nn)
        if getattr(self, "_incr", True):
            # The kill switch disables persistence here too: a store in
            # VOLCANO_TPU_INCREMENTAL=0 mode must not pin 100k pod
            # records across cycles through a cache nothing will read.
            store._objarr_cache = (key, Pn, Nn, pod_a, key_a, name_a)
        arrs = self._obj_arr_cache = (pod_a, key_a, name_a)
        return arrs

    def _commit(self, solve_jobs: List[int], task_rows: np.ndarray,
                assigned: np.ndarray, never_ready: np.ndarray,
                fit_failed: np.ndarray, req_gather=None) -> bool:
        """Apply the assignment matrix in bulk (the vectorized _replay).

        Runs under a ``commit`` lane span (``commit`` /
        ``inflight_commit``); each block below is a child span of it
        (``commit:guard`` .. ``commit:notify``), one per block and
        never one per pod."""
        m = self.m
        store = self.store
        span = self.tracer.span
        committed = assigned >= 0
        if not committed.any():
            return False

        rows = task_rows[committed]
        nodes_c = assigned[committed]
        stats = getattr(self, "stats", None)
        if stats is not None:
            stats["bound"] = int(stats["bound"]) + len(rows)

        with span("commit:guard"):
            # Divergence guard (vectorized analog of the replay's
            # re-check): charged capacity must not exceed allocatable.
            if req_gather is not None:
                # Subset the caller's full-task gather (prepared while
                # the device solve ran) down to the committed rows —
                # identity when everything committed (the steady
                # north-star case).
                er_all, si_all, v_all = req_gather
                if committed.all():
                    er, si, v = er_all, si_all, v_all
                else:
                    em = committed[er_all]
                    new_idx = np.cumsum(committed) - 1
                    er = new_idx[er_all[em]]
                    si = si_all[em]
                    v = v_all[em]
            else:
                er, si, v = m.c_req.gather(rows)
            # bincount over flattened (node, slot) indices is several
            # times faster than np.add.at for 200k+ scatter entries.
            add = np.bincount(
                nodes_c[er].astype(np.int64) * self.R + si,
                weights=v, minlength=self.Nn * self.R,
            ).reshape(self.Nn, self.R).astype(F)
            new_used = self.n_used + add
            over = new_used > self.n_alloc + self.eps[None, :]
            if over.any() and bool((add[over.any(axis=1)] > 0).any()):
                bad = np.flatnonzero(over.any(axis=1))
                log.error(
                    "Device/host divergence: %d nodes oversubscribed; "
                    "falling back to object path this cycle", len(bad),
                )
                raise RuntimeError("fastpath divergence")

        # Journey: the placement landed (first-time rows record the
        # bind — and their time-to-bind — with the committing solve's
        # flow id; steady-state re-binds bulk-count).
        with span("commit:journey") as sp:
            sp.args = self._journey_rows(
                rows, "bound",
                solve_id=int(self.stats.get("committed_solve_id") or 0))

        with span("commit:state"):
            # Array state updates.  The rows change dynamic state, so
            # they enter the mirror's dirty set (the next derive's
            # delta refresh reconciles the persistent aggregates) and
            # the mutation counter moves with them — the dirty set and
            # the staleness guard must agree on what "changed" means
            # (commit runs before this cycle's dispatch captures its
            # sequence, so the guard semantics are unchanged).
            self._audit_flow_rows(rows, ST_BOUND, "commit-bind")
            m.p_status[rows] = ST_BOUND
            m.p_node[rows] = nodes_c
            m.mark_pods_dirty(rows)
            m.mutation_seq += 1
            if self.shard is not None:
                # Cross-shard commit gate (shard.py, ISSUE 16):
                # siblings whose overlapped solve raced these binds
                # attribute their voids as cross-shard-conflict.
                m.shard_commit_seq += 1
            self.n_used = new_used
            self.n_idle = self.n_idle - add
            self.n_ntasks += np.bincount(
                nodes_c, minlength=self.Nn
            ).astype(I)
            self.resident[rows] = True

            # Job counters (affects readiness for later rounds +
            # close).
            jr = self.jobr[rows]
            bc = np.bincount(jr, minlength=self.Jn).astype(I)
            self.j_cnt_alloc += bc
            self.j_cnt_pending -= bc
            self.j_ready_base = (
                self.j_cnt_alloc + self.j_cnt_succ
                + self.j_cnt_empty_pending
            )
            # (er, si, v) reused from the divergence guard's gather
            # above.  The j_alloc_res/j_pending_res/q_alloc scatter
            # updates are deferred (see _flush_aggr): later rounds and
            # the evict machinery flush before reading.
            if not hasattr(self, "_aggr_pending"):
                self._aggr_pending = []
            self._aggr_pending.append(
                (jr[er], si, v, self.q_of_job[jr][er]))

        # Pod records + bind dispatch (async in the reference,
        # cache.go:536-552; here one batched dispatch).
        binder = store.binder
        bind_keys = getattr(binder, "bind_keys", None)
        notify = store._watchers
        with span("commit:records"):
            pod_a, key_a, name_a = self._obj_arrays()
            # Bound hostnames land in the mirror as ONE batched column
            # write (the vectorized replacement for the 100k pod-record
            # setattr walk, which now only runs for record consumers —
            # deferred to the bind dispatcher or the sync-bind path
            # below).
            m.p_node_name[rows] = name_a[nodes_c]
            defer_records = (
                getattr(store, "async_bind", False)
                and not notify
                and not store.n_volume_pods
                and not m.p_pod_nones
            )
            if defer_records:
                # The reference sets pod.NodeName via the API server on
                # the async bind, observed later by informers — not
                # inside the scheduling cycle (cache.go:536-552).
                # Register the object ARRAYS with the store and ship
                # the entry to the bind dispatcher; its worker thread
                # does the 100k-element tolist + node_name walk
                # post-cycle (~45 ms off the commit lane at north-star
                # scale).  Cycle-visible state (mirror arrays) is
                # already updated above; any failure path about to read
                # pod records forces the walk first
                # (apply_pending_bind_records — registration at commit
                # time covers prior cycles' not-yet-processed batches
                # too).
                entry = store.defer_bind_records(
                    key_a[rows], name_a[nodes_c], pod_a[rows]
                )
                self._bind_batches.append((None, None, None, entry))
                store.mark_objects_stale()
                return True
            pod_l = pod_a[rows].tolist()
            host_l = name_a[nodes_c].tolist()
            # Tombstoned rows can't be committed in the common case;
            # the mirror counts them so the 100k-element defensive None
            # scan (identity, NOT `in`: `in` calls the dataclass
            # __eq__) only runs when one exists.
            if not m.p_pod_nones or not any(p is None for p in pod_l):
                # Common case: every committed row has a live pod
                # record.  Object-array gathers + one zip setattr walk
                # instead of four per-pod appends (this path covers
                # 100k rows at north-star scale).
                for pod, hostname in zip(pod_l, host_l):
                    pod.node_name = hostname
                keys = key_a[rows].tolist()
                hosts = host_l
                bound_pods = pod_l
                bound_rows = rows.tolist()
            else:
                keys = []
                hosts = []
                bound_pods = []
                bound_rows = []
                key_l = key_a[rows].tolist()
                for row, pod, hostname, key in zip(
                        rows.tolist(), pod_l, host_l, key_l):
                    if pod is None:
                        continue
                    pod.node_name = hostname
                    keys.append(key)
                    hosts.append(hostname)
                    bound_pods.append(pod)
                    bound_rows.append(row)
        from .cache.interface import BindFailure

        # Volume gate (statement.go allocate->AllocateVolumes, commit->
        # BindVolumes): pods carrying claims go through the volume binder
        # BEFORE their bind dispatches; a claim failure reverts exactly
        # that pod to Pending.  Volume-free clusters skip on the store's
        # exact O(1) counter (the 100k-pod truthiness scan is not free,
        # and gating on store.pvcs would bypass custom volume binders).
        if store.n_volume_pods:
            with span("commit:volumes"):
                keys, hosts, bound_pods, bound_rows = (
                    self._commit_volumes(keys, hosts, bound_pods,
                                         bound_rows))

        with span("commit:bind"):
            if getattr(store, "async_bind", False):
                # Async dispatch (cache.go:536-552): the cycle only
                # pays a list append (batches go to the dispatcher at
                # cycle end — see run()); failures surface via
                # drain_bind_failures at the next cycle's start and
                # re-enter Pending with backoff.
                self._bind_batches.append(
                    (keys, hosts, bound_pods, None))
            else:
                try:
                    if bind_keys is not None:
                        bind_keys(keys, hosts)
                    else:
                        failed = []
                        for pod, hostname, key in zip(
                                bound_pods, hosts, keys):
                            try:
                                binder.bind(pod, hostname)
                            except BindFailure:
                                failed.append(key)
                        if failed:
                            raise BindFailure(failed)
                except BindFailure as bf:
                    self._revert_failed_binds(bf.failed, keys,
                                              bound_rows, bound_pods)
                    failed = set(bf.failed)
                    bound_pods = [
                        pod for pod, key in zip(bound_pods, keys)
                        if key not in failed
                    ]
        if notify:
            with span("commit:notify"):
                for pod in bound_pods:
                    store._notify("Pod", "bind", pod)

        store.mark_objects_stale()
        return True

    def _commit_volumes(self, keys, hosts, bound_pods, bound_rows):
        """The volume gate of ``_commit`` (the ``commit:volumes``
        span): allocate + bind each claiming pod's volumes, revert the
        pods whose claim failed, return the surviving lists."""
        from .cache.interface import VolumeBindFailure

        store = self.store
        if not any(pod.volumes for pod in bound_pods):
            return keys, hosts, bound_pods, bound_rows
        vb = store.volume_binder
        vol_failed = []
        for pod, hostname, key in zip(bound_pods, hosts, keys):
            if not pod.volumes:
                continue
            try:
                vb.allocate_volumes(pod, hostname)
                vb.bind_volumes(pod)
            except VolumeBindFailure as e:
                store.record_event(f"Pod/{key}", "FailedScheduling",
                                   str(e))
                vol_failed.append(key)
        if vol_failed:
            self._revert_failed_binds(vol_failed, keys, bound_rows,
                                      bound_pods)
            fset = set(vol_failed)
            kept = [
                (k, h, p, r) for k, h, p, r
                in zip(keys, hosts, bound_pods, bound_rows)
                if k not in fset
            ]
            keys = [k for k, _, _, _ in kept]
            hosts = [h for _, h, _, _ in kept]
            bound_pods = [p for _, _, p, _ in kept]
            bound_rows = [r for _, _, _, r in kept]
        return keys, hosts, bound_pods, bound_rows

    def _revert_failed_binds(self, failed_keys, keys: List[str],
                             bound_rows: List[int],
                             bound_pods: List[object]) -> None:
        """Undo the commit bookkeeping for binds the binder reports
        failed (cache.go errTasks resync): the tasks return to Pending
        and the next cycle retries them.

        The revert is per-task, as in the reference: a gang whose member
        bind fails stays partially bound below min_available until the
        retry succeeds — the reference likewise leaves the other members
        bound while errTasks resyncs the failed one, with the gang
        plugin's session-close conditions and the job's lifecycle
        policies handling a persistently failing member."""
        failed = set(failed_keys)
        idx = [i for i, k in enumerate(keys) if k in failed]
        if not idx:
            return
        log.warning("%d binds failed; tasks resync to Pending", len(idx))
        self._unbind_rows(np.array([bound_rows[i] for i in idx], np.int64))
        for i in idx:
            bound_pods[i].node_name = None
        for i in idx:
            # Claims the failed pod pinned/bound roll back with it
            # (release only after every failed pod's node_name is
            # cleared, so shared claims held by co-failed pods free up).
            if bound_pods[i].volumes:
                self.store.release_claims_for(bound_pods[i])

    def _unbind_rows(self, rows_f: np.ndarray) -> None:
        """Return bound mirror rows to Pending, reversing the commit's
        bookkeeping (node capacity/task slots, job and queue counters) —
        the vectorized core shared by the bind-failure resync above and
        the steady-state workload feed (``store.cycle_feed``), which
        re-pends just-committed rows to emulate continuous pod arrival
        at constant backlog.  Pod RECORDS are not touched; callers that
        need ``pod.node_name`` cleared do it themselves."""
        m = self.m
        self._flush_aggr()
        R = self.R
        nodes_f = m.p_node[rows_f].astype(np.int64)
        # The steady-state feed re-pends the SAME rows every cycle; the
        # static-spec gather over 100k rows is content-cached (rows are
        # stable between compactions, specs immutable per row).
        cache = (getattr(self.store, "_unbind_gather_cache", None)
                 if getattr(self, "_incr", True) else None)
        if (cache is not None and cache[0] == m.compact_gen
                and np.array_equal(cache[1], rows_f)):
            er, si, v = cache[2]
        else:
            er, si, v = m.c_req.gather(rows_f)
            if getattr(self, "_incr", True):
                self.store._unbind_gather_cache = (
                    m.compact_gen, rows_f.copy(), (er, si, v))
        # Every scatter below is a bincount over flattened indices —
        # np.add.at at the feed's 100k-row scale was the single largest
        # host cost of the pipelined steady state (~50 ms/cycle).
        sub = np.bincount(
            nodes_f[er] * R + si, weights=v, minlength=self.Nn * R,
        ).reshape(self.Nn, R).astype(F)
        self.n_used = self.n_used - sub
        self.n_idle = self.n_idle + sub
        self.n_ntasks -= np.bincount(
            nodes_f, minlength=self.Nn
        )[:self.Nn].astype(I)
        self._audit_flow_rows(rows_f, ST_PENDING, "unbind")
        # Journey: bulk-count only — the feed's re-pend loop and the
        # bind-failure resync both leave the pods' first-bind latency
        # (already recorded) standing.
        self._journey_rows(rows_f, "unbound")
        m.p_status[rows_f] = ST_PENDING
        m.p_node[rows_f] = -1
        m.p_node_name[rows_f] = None
        m.mark_pods_dirty(rows_f)
        self.resident[rows_f] = False
        jr = self.jobr[rows_f]
        # Ungrouped bound pods (no job row) carry no job/queue
        # accounting — mask them out of the job-side scatters (the old
        # np.add.at silently folded index -1 into the LAST job row).
        jok = jr >= 0
        jbc = np.bincount(
            jr[jok], minlength=self.Jn
        )[:self.Jn].astype(I)
        self.j_cnt_alloc -= jbc
        self.j_cnt_pending += jbc
        self.j_ready_base = (
            self.j_cnt_alloc + self.j_cnt_succ + self.j_cnt_empty_pending
        )
        er_j = jok[er]
        jadd = np.bincount(
            jr[er][er_j].astype(np.int64) * R + si[er_j],
            weights=v[er_j], minlength=self.Jn * R,
        ).reshape(self.Jn, R).astype(F)
        self.j_alloc_res -= jadd
        self.j_pending_res += jadd
        q_of = np.where(jok, self.q_of_job[np.maximum(jr, 0)], -1)
        qmask = q_of >= 0
        if qmask.any():
            er_q = qmask[er]
            self.q_alloc -= np.bincount(
                q_of[er][er_q].astype(np.int64) * R + si[er_q],
                weights=v[er_q], minlength=self.Qn * R,
            ).reshape(self.Qn, R).astype(F)
        # Mirror state moved: an overlapping dispatch must re-validate.
        m.mutation_seq += 1

    # ------------------------------------------------------------ backfill

    def _backfill(self) -> bool:
        """Place zero-request pending tasks (backfill.go:39-88).
        Returns True when any row was bound (mirror state moved)."""
        m = self.m
        Pn = self.Pn
        status = m.p_status[:Pn]
        be_rows = np.flatnonzero(
            m.p_alive[:Pn] & (status == ST_PENDING) & m.p_be[:Pn]
        )
        if not len(be_rows):
            return False
        schedulable = set(self._schedulable_rows())
        # Node order: store insertion order (dict iteration in the object
        # path) == mirror row order.
        live_nodes = [i for i in range(self.Nn) if self.n_alive[i]]
        has_pred = self._has("predicates")
        bound_rows = []
        for row in be_rows:
            jrow = self.jobr[row]
            if jrow < 0 or jrow not in schedulable:
                continue
            feat = m.p_feat[row]
            placed = None
            for ni in live_nodes:
                if has_pred and not self._host_predicate(row, feat, ni):
                    continue
                placed = ni
                break
            if placed is not None:
                self._audit_flow(int(m.p_status[row]), ST_BOUND,
                                 "backfill-bind")
                self._journey_event(row, "bound", detail="backfill")
                m.p_status[row] = ST_BOUND
                m.p_node[row] = placed
                m.p_node_name[row] = m.n_name[placed]
                self.n_ntasks[placed] += 1
                self.resident[row] = True
                self.j_cnt_alloc[jrow] += 1
                self.j_cnt_pending[jrow] -= 1
                self.j_cnt_empty_pending[jrow] -= 1
                bound_rows.append(row)
        if bound_rows:
            # Direct mirror writes above: the dirty set must see them
            # (the caller stamps mutation_seq when this returns True).
            m.mark_pods_dirty(np.asarray(bound_rows, np.int64))
            # ready_base: empty-pending shrank, alloc grew -> net unchanged;
            # recompute for exactness.
            self.j_ready_base = (
                self.j_cnt_alloc + self.j_cnt_succ + self.j_cnt_empty_pending
            )
            store = self.store
            binder = store.binder
            bind_batch = getattr(binder, "bind_batch", None)
            pairs = []
            pair_rows = []
            for row in bound_rows:
                pod = store.pods.get(m.p_uid[row])
                if pod is None:
                    continue
                hostname = m.n_name[m.p_node[row]]
                pod.node_name = hostname
                pairs.append((pod, hostname))
                pair_rows.append(row)
            from .cache.interface import BindFailure

            failed_keys = set()
            try:
                if bind_batch is not None:
                    bind_batch(pairs)
                else:
                    for pod, hostname in pairs:
                        binder.bind(pod, hostname)
            except BindFailure as bf:
                failed_keys = set(bf.failed)
            if failed_keys:
                # BestEffort revert: no resource accounting to undo, only
                # status/placement/counters (errTasks resync semantics).
                log.warning(
                    "%d backfill binds failed; tasks resync to Pending",
                    len(failed_keys),
                )
                kept = []
                reverted = []
                for row, (pod, hostname) in zip(pair_rows, pairs):
                    key = f"{pod.namespace}/{pod.name}"
                    if key not in failed_keys:
                        kept.append((pod, hostname))
                        continue
                    jrow = self.jobr[row]
                    self._audit_flow(int(m.p_status[row]), ST_PENDING,
                                     "backfill-revert")
                    self._journey_event(row, "dropped",
                                        detail="bind-failed")
                    m.p_status[row] = ST_PENDING
                    self.n_ntasks[m.p_node[row]] -= 1
                    m.p_node[row] = -1
                    m.p_node_name[row] = None
                    self.resident[row] = False
                    reverted.append(row)
                    pod.node_name = None
                    if jrow >= 0:
                        self.j_cnt_alloc[jrow] -= 1
                        self.j_cnt_pending[jrow] += 1
                        self.j_cnt_empty_pending[jrow] += 1
                if reverted:
                    m.mark_pods_dirty(np.asarray(reverted, np.int64))
                pairs = kept
                self.j_ready_base = (
                    self.j_cnt_alloc + self.j_cnt_succ
                    + self.j_cnt_empty_pending
                )
            for pod, _ in pairs:
                if store._watchers:
                    store._notify("Pod", "bind", pod)
            store.mark_objects_stale()
            stats = getattr(self, "stats", None)
            if stats is not None:
                stats["bound"] = int(stats["bound"]) + len(pairs)
        return bool(bound_rows)

    def _host_predicate(self, row: int, feat, ni: int) -> bool:
        """Host predicates for best-effort tasks (predicates.go:144-293,
        minus resource fit)."""
        m = self.m
        if not self.n_ready[ni]:
            return False
        if self.n_maxtasks[ni] > 0 and self.n_ntasks[ni] >= self.n_maxtasks[ni]:
            return False
        node = m.node_objs[ni]
        labels = node.labels if node is not None else {}
        pod = self.store.pods.get(m.p_uid[row])
        if pod is None:
            return False
        if pod.node_selector and not all(
            labels.get(k) == v for k, v in pod.node_selector.items()
        ):
            return False
        terms = pod.required_node_affinity
        if terms and not any(
            all(labels.get(k) == v for k, v in t.items()) for t in terms
        ):
            return False
        for taint in (node.taints if node is not None else []):
            if taint.effect not in ("NoSchedule", "NoExecute"):
                continue
            ok = False
            for tol in pod.tolerations:
                if tol.operator == "Exists":
                    key_ok = tol.key == "" or tol.key == taint.key
                else:
                    key_ok = tol.key == taint.key and tol.value == taint.value
                if key_ok and (tol.effect == "" or tol.effect == taint.effect):
                    ok = True
                    break
            if not ok:
                return False
        if pod.host_ports:
            used = set()
            res_on_node = np.flatnonzero(
                self.resident & (m.p_node[:self.Pn] == ni)
            )
            for rr in res_on_node:
                f = m.p_feat[rr]
                if f is not None:
                    used.update(f.ports)
            my = {m.ports.index.get(p) for p in pod.host_ports}
            if used & my:
                return False
        return True

    # ----------------------------------------------------------- rebalance

    # Pipelined cycles see starvation one commit behind, so a gang must
    # stay starved this many consecutive rebalance passes before a plan
    # forms (gives the in-flight allocate dispatch its chance to bind).
    REBALANCE_STREAK_PIPELINED = 2
    # Cooldown (in rebalance passes) after a gang's plan is rejected:
    # a persistently starved gang whose what-if keeps failing must not
    # re-pay the frag kernel + what-if solve every cycle.  The world
    # changing enough to help (pods finishing, nodes joining) takes
    # many cycles anyway; a commit or leaving the starved set clears it.
    REBALANCE_REJECT_BACKOFF = 8

    def _rebalance(self) -> None:
        """Gang-aware defragmentation lane (ISSUE 5, docs/rebalance.md).

        Picks the most-starved schedulable gang, scores per-node
        fragmentation against its profile table (ops/rebalance.py — one
        kernel over the same planes the wave solver reads), selects a
        bounded drain set under per-PodGroup disruption budgets, and
        proves the migration with a what-if ``solve_wave`` over the
        hypothetically drained cluster (victims re-entered as pending
        alongside the gang, riding the exact allocate jit).  The plan
        commits — victims evicted through the ``fastpath_evict``
        machinery, restores registered with the migration ledger — only
        when the what-if shows strict improvement: the gang reaches
        ready AND every victim re-places.  Pipelined stores park the
        what-if as ``pipeline.InflightPlan`` and commit next cycle
        behind the staleness guard."""
        from .actions.rebalance import rebalance_enabled

        from . import whatif

        store = self.store
        if not rebalance_enabled():
            return
        remote = self._remote_solver
        if remote is not None:
            from . import whatif

            if not whatif.whatif_offload_on(remote):
                # Single-connection remote deployments keep the lane
                # off (the plan solve would contend for the one strict
                # request/reply connection); a solver POOL with an
                # idle non-primary replica offloads the plan solve
                # there instead (ISSUE 15).  A mesh is fine since
                # ISSUE 11: the engine's hypothetical patches touch
                # only per-cycle host planes, so the sharded devsnap
                # dispatch carries them unchanged.
                return
        ledger = store.migrations
        if ledger is not None and ledger.active(store, "rebalance"):
            # One REBALANCE wave at a time: budgets stay trivially
            # honest and a half-done wave never compounds.  (Preempt/
            # reclaim entries share the ledger but gate per gang —
            # their victims may legitimately stay Pending for a long
            # time and must not wedge this lane.)
            return
        if store._inflight_plan is not None:
            return
        jrow = self._find_starved_gang()
        if jrow is None:
            return
        plan = self._plan_rebalance(jrow)
        if plan is None:
            return
        whatif.dispatch_plan(self, plan)

    def _find_starved_gang(self) -> Optional[int]:
        """Most-starved schedulable gang (largest min_available
        shortfall, lowest row tie-break) whose starvation has persisted
        long enough (see REBALANCE_STREAK_PIPELINED)."""
        m = self.m
        store = self.store
        srows = np.asarray(self.session_jobs, np.int64)
        streaks = getattr(store, "_rebalance_streaks", None)
        if streaks is None:
            streaks = store._rebalance_streaks = {}
        if not len(srows):
            streaks.clear()
            return None
        mask = (
            (self.j_phase[srows] != 1)  # Inqueue gate, as _schedulable_rows
            & (self.j_cnt_pending[srows] > 0)
            & (self.j_ready_base[srows] < m.j_minav[srows])
            & (self.j_valid[srows] >= m.j_minav[srows])
            & (self.q_of_job[srows] >= 0)
        )
        cand = srows[mask]
        uids = {m.j_uid[int(r)] for r in cand}
        for uid in list(streaks):
            if uid not in uids:
                del streaks[uid]
        for uid in uids:
            streaks[uid] = streaks.get(uid, 0) + 1
        # Rejection cooldown: gangs whose last plan was rejected sit
        # out REBALANCE_REJECT_BACKOFF passes; leaving the starved set
        # clears the slate.
        backoff = getattr(store, "_rebalance_backoff", None)
        if backoff is None:
            backoff = store._rebalance_backoff = {}
        for uid in list(backoff):
            if uid not in uids:
                del backoff[uid]
            elif backoff[uid] > 0:
                backoff[uid] -= 1
        if not len(cand):
            return None
        need_streak = (self.REBALANCE_STREAK_PIPELINED
                       if self._pipeline_on else 1)
        need = (m.j_minav[cand] - self.j_ready_base[cand]).astype(np.int64)
        for r in cand[np.lexsort((cand, -need))]:
            uid = m.j_uid[int(r)]
            if streaks.get(uid, 0) >= need_streak \
                    and backoff.get(uid, 0) <= 0:
                return int(r)
        return None

    def _rebalance_backoff_set(self, gang_uid: str) -> None:
        backoff = getattr(self.store, "_rebalance_backoff", None)
        if backoff is None:
            backoff = self.store._rebalance_backoff = {}
        backoff[gang_uid] = self.REBALANCE_REJECT_BACKOFF

    def _plan_rebalance(self, jrow: int):
        """Score fragmentation and select a drain set for one starved
        gang; returns a ``whatif.WhatIfPlan`` (action "rebalance",
        victims re-solved) or None."""
        import jax

        from . import whatif
        from .actions.rebalance import drain_cap, max_unavailable_of
        from .ops.rebalance import frag_scores, select_drain_set

        m = self.m
        store = self.store
        Pn = self.Pn
        with self.tracer.span("rebalance_plan", cat="rebalance",
                              args={"gang": m.j_uid[jrow]}):
            need = int(m.j_minav[jrow] - self.j_ready_base[jrow])
            if need <= 0:
                return None
            pend = np.flatnonzero(
                m.p_alive[:Pn] & (m.p_status[:Pn] == ST_PENDING)
                & ~m.p_be[:Pn] & (self.jobr == jrow)
            )
            if not len(pend):
                return None
            gang_rows = pend[np.argsort(m.p_create[pend], kind="stable")]
            # Distinct profiles of the gang's pending tasks -> dense
            # [U, R] init-request table (the planner's notion of "a
            # gang task"; same profile interning _profile_tasks keys
            # on).
            _, first = np.unique(m.p_prof[gang_rows], return_index=True)
            urows = gang_rows[np.sort(first)]
            # Pad the profile axis to a pow2 bucket (all-zero rows are
            # inert: no requested slot -> fit 0) so gangs with varying
            # distinct-profile counts share one compiled kernel.
            Up = _pow2(max(len(urows), 1), 4)
            prof_req = np.zeros((Up, self.R), F)
            er, si, v = m.c_init_req.gather(urows)
            prof_req[er, si] = v
            # Migratable victims: Running residents with requests, not
            # critical (conformance-exempt), without inter-pod terms
            # (their what-if re-placement would need live term-count
            # surgery), and never the starved gang itself.
            vict = np.flatnonzero(
                self.resident[:Pn]
                & (m.p_status[:Pn] == ST_RUNNING)
                & ~m.p_critical[:Pn]
                & ~m.p_has_ip[:Pn]
                & (self.jobr >= 0)
                & (self.jobr != jrow)
            )
            if len(vict):
                vict = vict[m.c_req.lens(vict) > 0]
            # Node axis padded to the same pow2 bucket _solve_inputs
            # uses, so node churn (9999 -> 10000 nodes) does not
            # recompile the kernel on the cycle thread.  Padded rows
            # are not-ready (frag 0) and zero-capacity (fit 0).
            Np = _pow2(max(self.Nn, 1))
            evictable = np.zeros((Np, self.R), F)
            vnode = np.zeros(0, np.int64)
            if len(vict):
                vnode = m.p_node[:Pn][vict].astype(np.int64)
                er, si, v = m.c_req.gather(vict)
                np.add.at(evictable, (vnode[er], si), v)

            def padN(a, fill=0):
                out = np.full((Np, *a.shape[1:]), fill, a.dtype)
                out[:len(a)] = a
                return out

            fs = frag_scores(
                padN(self.n_idle.astype(F)),
                padN(self.n_alloc.astype(F)),
                padN(self.n_ready), evictable, prof_req, self.eps,
            )
            frag, fit_now, fit_freed = jax.device_get(
                (fs.frag, fs.fit_now, fs.fit_freed)
            )
            frag = frag[:self.Nn]
            fit_now = fit_now[:self.Nn]
            fit_freed = fit_freed[:self.Nn]
            alive = self.n_alive
            frag_mean = (float(frag[alive].mean())
                         if alive.any() else 0.0)
            metrics.rebalance_frag_score.set(frag_mean)
            # Fabric-defrag targeting (ops/topology): when the starved
            # gang carries a topology constraint, the drain set
            # concentrates on ONE target fabric block — the block whose
            # drains free the most gang capacity — so the migration
            # wave assembles a whole slice instead of shaving capacity
            # evenly across the fabric.  Outside the target block the
            # gain and frag signals are zeroed; select_drain_set (and
            # its disruption-budget charging) is unchanged.
            if m.j_topo[jrow] and self._topo_active():
                from .ops import topology as topo

                tf = self._topo_block_fit(jrow)
                if tf is not None:
                    frag_b = np.asarray(jax.device_get(topo.fabric_frag(
                        tf["cfit"], tf["whole"], tf["prof_cnt"]
                    )))
                    metrics.topology_frag_score.set(
                        float(frag_b.mean()) if len(frag_b) else 0.0)
                    blk = tf["block"][:self.Nn]
                    nb = tf["n_blocks"]
                    total_need = int(np.sum(tf["prof_cnt"]))
                    freed_sum = np.zeros(nb + 1, np.float64)
                    np.add.at(freed_sum,
                              np.where(blk >= 0, blk, nb), fit_freed)
                    freed_sum = freed_sum[:nb]
                    if (nb and total_need > 0
                            and freed_sum.max() >= total_need):
                        target = int(np.argmax(freed_sum))
                        on_blk = blk == target
                        # The drain wave only has to close the target
                        # block's SHORTFALL — its standing free
                        # capacity (cfit) already counts toward the
                        # gang; the classic need (minav - ready) would
                        # demand the whole gang out of drains alone
                        # and starve forever on a mostly-free block.
                        short = int(np.maximum(
                            np.asarray(tf["prof_cnt"], np.int64)
                            - np.asarray(tf["cfit"][target], np.int64),
                            0).sum())
                        if short <= 0:
                            # Block already whole: the pregate lifts
                            # next cycle; nothing to drain.
                            return None
                        need = short
                        frag = np.where(on_blk, frag, 0.0)
                        fit_freed = np.where(on_blk, fit_freed, fit_now)
                    elif m.j_topo[jrow] == TOPOLOGY_REQUIRE:
                        # No block gains capacity from any drain: no
                        # migration wave can make this gang contiguous.
                        whatif.count_plan(
                            self, "rebalance", "rejected-topology",
                            gang=m.j_uid[jrow], need=need,
                        )
                        self._rebalance_backoff_set(m.j_uid[jrow])
                        return None
            # Per-node victim lists only for DRAIN CANDIDATES (frag-
            # positive nodes whose drain gains capacity): the Python
            # walk is then bounded by the fragmentation hotspots, not
            # the cluster's whole Running population.
            victims_by_node: List[List[int]] = [
                [] for _ in range(self.Nn)
            ]
            victim_group: Dict[int, str] = {}
            if len(vict):
                cand_mask = (fit_freed > fit_now) & (frag > 0.0)
                on_cand = cand_mask[vnode]
                for row, n in zip(vict[on_cand].tolist(),
                                  vnode[on_cand].tolist()):
                    victims_by_node[n].append(row)
                    victim_group[row] = m.j_uid[int(self.jobr[row])]
            # Remaining per-group disruption budget after waves already
            # in flight (PDB max_unavailable equivalent).
            ledger = store.migrations
            budget_left: Dict[str, int] = {}
            for uid in set(victim_group.values()):
                row = m.j_row.get(uid, -1)
                pg = m.j_pg[row] if row >= 0 else None
                used = (ledger.disrupted(store, uid)
                        if ledger is not None else 0)
                budget_left[uid] = max_unavailable_of(pg) - used
            nodes, budget_blocked = select_drain_set(
                frag, fit_now, fit_freed, need, victims_by_node,
                victim_group, budget_left, drain_cap(),
            )
            if not nodes:
                if budget_blocked:
                    whatif.count_plan(
                        self, "rebalance", "rejected-budget",
                        gang=m.j_uid[jrow],
                        need=need, frag=round(frag_mean, 4),
                    )
                # Cooldown either way: no drain set can form until the
                # cluster moves, so re-scoring every cycle is waste.
                self._rebalance_backoff_set(m.j_uid[jrow])
                return None
            victim_rows = np.asarray(
                [r for n in nodes for r in victims_by_node[n]],
                np.int64,
            )
            budgets: Dict[str, int] = {}
            for r in victim_rows.tolist():
                g = victim_group[r]
                budgets[g] = budgets.get(g, 0) + 1
            return whatif.WhatIfPlan(
                action="rebalance",
                gang_job=int(jrow), gang_uid=m.j_uid[jrow],
                gang_rows=gang_rows, victim_rows=victim_rows,
                victim_jobs=self.jobr[victim_rows].astype(np.int64),
                drain_nodes=np.asarray(nodes, np.int64), need=need,
                frag_before=frag_mean, budgets=budgets,
                resolve_victims=True,
            )

    def _commit_inflight_plan(self) -> None:
        """Land (or void) the previous cycle's pipelined what-if plan —
        rebalance, preempt or reclaim — through the shared engine
        (``whatif.commit_inflight_plan``): any mutation/epoch/compaction
        /node-count drift voids the plan wholesale."""
        if self.shard is not None and not self.shard.runs_evictions:
            # The parked plan belongs to the evictor shard (shard 0);
            # a sibling popping it would commit evictions planned
            # against another shard's view.
            return
        from . import whatif

        whatif.commit_inflight_plan(self)

    # --------------------------------------------------------------- close

    def _close(self) -> None:
        """Gang OnSessionClose conditions + PodGroup status write-back
        (gang.go:140-183 + framework.go jobStatus).

        Change detection runs vectorized against the derive-time status
        snapshot (j_phase/j_st_*); Python touches only the rows that
        actually write back."""
        m = self.m
        store = self.store
        srows = np.asarray(self.session_jobs, np.int64)
        if not len(srows):
            if self._has("gang"):
                # An emptied session must not freeze the gauge at the
                # previous cycle's count.
                metrics.unschedule_job_count.set(0)
            self._phase_dirty.clear()
            return

        unsched_mask = np.zeros(self.Jn, bool)
        cond_changed = np.zeros(self.Jn, bool)
        if self._has("gang"):
            unready = srows[
                self.j_ready_base[srows] < m.j_minav[srows]
            ]
            unsched_mask[unready] = True
            gang_events = []
            gauge_pairs = []
            retry_keys = []
            set_gauges = True
            unready_counts = (
                m.j_minav[unready] - self.j_ready_base[unready]
            )
            if len(unready):
                counts = self._ensure_status_counts()
                csub = counts[unready]
                # Steady-state reuse (ISSUE 8 close lane): a
                # persistently-unready set whose live status breakdown
                # did not move produces the SAME signatures, messages,
                # gauge values, and retry keys as last cycle — reuse
                # the cached lists and skip the hash/group/list build
                # (retry counters still increment, gauges keep their
                # already-set values).  Any signature the mirror has
                # not persisted (external condition writers) falls
                # through to the full build.
                cache = (getattr(store, "_close_gang_cache", None)
                         if getattr(self, "_incr", True) else None)
                if (cache is not None and cache["jn"] == self.Jn
                        and np.array_equal(cache["unready"], unready)
                        and np.array_equal(cache["ucounts"],
                                           unready_counts)
                        and np.array_equal(cache["csub"], csub)
                        and bool((cache["sigs"]
                                  == m.j_cond_sig[unready]).all())):
                    retry_keys = cache["retry_keys"]
                    gauge_pairs = cache["gauge_pairs"]
                    set_gauges = False
                    unready_built = False
                else:
                    unready_built = True
            else:
                unready_built = False
            if unready_built:
                # Group-wise messages: jobs sharing (status counts,
                # minAvailable, unready, total) share the message text,
                # so one np.unique + one build per GROUP replaces 25k
                # per-row memo probes at config-4 scale.
                comp = np.concatenate([
                    csub,
                    m.j_minav[unready][:, None].astype(np.int64),
                    unready_counts[:, None].astype(np.int64),
                    self.j_cnt_total[unready][:, None].astype(np.int64),
                ], axis=1)
                # 1-D composite hash (np.unique axis=0 pays a 66 ms void
                # argsort at 25k rows): two independent wrapping dot
                # products; a colliding pair would merely share message
                # text, at ~2^-100 odds over the row space.
                rng = np.random.RandomState(0x5EED)
                with np.errstate(over="ignore"):
                    hv = (
                        comp * rng.randint(
                            1, 1 << 62, size=comp.shape[1]
                        ).astype(np.int64)[None, :]
                    ).sum(axis=1)
                    hv2 = (
                        comp * rng.randint(
                            1, 1 << 62, size=comp.shape[1]
                        ).astype(np.int64)[None, :]
                    ).sum(axis=1)
                    hv = hv * np.int64(1_000_003) + hv2
                _, reps, inv = np.unique(
                    hv, return_index=True, return_inverse=True
                )
                grp_msgs = [
                    self._gang_message(int(unready[ri])) for ri in reps
                ]
                # Same key shape as mirror.upsert_pod_group's refresh:
                # hash((reason, message)) — the two must match or the
                # throttle re-fires after every external status write.
                grp_sigs = np.array(
                    [hash(("NotEnoughResources", s)) & 0x7FFFFFFFFFFFFFFF
                     for s in grp_msgs],
                    np.int64,
                )
                sigs = grp_sigs[inv]
                # Condition refresh throttling (job_updater.go
                # isPodGroupConditionsUpdated): the mirror keeps the
                # hash of the Unschedulable condition last written, so
                # persistently-unschedulable jobs skip the per-object
                # scan/rewrite entirely.
                need = np.flatnonzero(sigs != m.j_cond_sig[unready])
                j_pgs = self.j_pgs
                uid_l = self.uid
                cond_sig = m.j_cond_sig
                for li in need.tolist():
                    row = int(unready[li])
                    pg = j_pgs[row]
                    if pg is None:
                        continue
                    msg = grp_msgs[inv[li]]
                    conditions = [
                        c for c in pg.status.conditions
                        if c.type != POD_GROUP_UNSCHEDULABLE
                    ]
                    conditions.append(PodGroupCondition(
                        type=POD_GROUP_UNSCHEDULABLE,
                        status="True",
                        transition_id=uid_l,
                        reason="NotEnoughResources",
                        message=msg,
                    ))
                    pg.status.conditions = conditions
                    cond_changed[row] = True
                    cond_sig[row] = sigs[li]
                    gang_events.append((
                        m.j_event_key[row]
                        or f"PodGroup/{pg.namespace}/{pg.name}",
                        "Unschedulable", msg,
                    ))
                jk = m.j_gauge_key
                uids = m.j_uid
                retry_keys = [
                    jk[row] or (("job_name", uids[row].split("/")[-1]),)
                    for row in unready.tolist()
                ]
                gauge_pairs = list(zip(retry_keys,
                                       unready_counts.tolist()))
                if getattr(self, "_incr", True):
                    store._close_gang_cache = {
                        "jn": self.Jn, "unready": unready,
                        "ucounts": unready_counts, "csub": csub,
                        "sigs": sigs, "retry_keys": retry_keys,
                        "gauge_pairs": gauge_pairs,
                    }
            if gang_events:
                store.record_events_deferred(gang_events)
            if set_gauges:
                metrics.unschedule_task_count.set_many(gauge_pairs)
            metrics.job_retry_counts.inc_many(retry_keys)
            metrics.unschedule_job_count.set(len(unready))

        # jobStatus write-back, skipping unchanged PodGroups
        # (framework.go jobStatus + job_updater.go
        # isPodGroupStatusUpdated: only changed statuses are written).
        cur_code = self.j_phase[srows]
        running_a = self.j_cnt_run[srows]
        failed_a = self.j_cnt_fail[srows]
        succ_a = self.j_cnt_succ[srows]
        alloc_a = self.j_cnt_alloc[srows] + succ_a
        new_code = np.where(
            (running_a != 0) & unsched_mask[srows],
            np.int8(4),  # Unknown
            np.where(
                alloc_a >= m.j_minav[srows],
                np.int8(3),  # Running
                np.where(cur_code != 2, np.int8(1), cur_code),
            ),
        )
        changed = (
            (new_code != cur_code)
            | (running_a != self.j_st_run[srows])
            | (failed_a != self.j_st_fail[srows])
            | (succ_a != self.j_st_succ[srows])
            | cond_changed[srows]
        ) & (cur_code != 0)  # code 0 = no PodGroup
        if self._phase_dirty:
            # In-place transitions (enqueue's Pending -> Inqueue) made
            # the snapshot match the mutated object; force those rows.
            j_row = m.j_row
            dirty = np.zeros(self.Jn, bool)
            Jn = self.Jn
            for uid in self._phase_dirty:
                row = j_row.get(uid, -1)
                if 0 <= row < Jn:
                    dirty[row] = True
            changed |= dirty[srows] & (cur_code != 0)
        idx = np.flatnonzero(changed)
        failed_status_uids = None
        if len(idx):
            rows_arr = srows[idx]
            codes = new_code[idx]
            rows_l = rows_arr.tolist()
            run_l = running_a[idx].tolist()
            fail_l = failed_a[idx].tolist()
            succ_l = succ_a[idx].tolist()
            # new_code only produces codes 1-4 (all named phases), so the
            # string lookup vectorizes; the snapshot arrays update in
            # four vector writes instead of per-row stores.
            phase_l = _PHASE_STR_BY_CODE[codes].tolist()
            self.j_phase[rows_arr] = codes
            self.j_st_run[rows_arr] = running_a[idx]
            self.j_st_fail[rows_arr] = failed_a[idx]
            self.j_st_succ[rows_arr] = succ_a[idx]
            j_pgs = self.j_pgs
            updater = store.status_updater
            batch_update = getattr(updater, "update_pod_groups", None)
            update = updater.update_pod_group
            written: List[object] = []
            watchers = store._watchers
            for row, ph, running, failed, succeeded in zip(
                    rows_l, phase_l, run_l, fail_l, succ_l):
                pg = j_pgs[row]
                if pg is None:
                    continue
                status = pg.status
                status.phase = ph
                status.running = running
                status.failed = failed
                status.succeeded = succeeded
                if batch_update is not None:
                    written.append(pg)
                else:
                    update(pg)
                if watchers:
                    store._notify("PodGroup", "status", pg)
            if written:
                # One write-back call per close (job_updater.go batches
                # its API writes the same way; a remote updater would
                # otherwise pay 12k round trips).
                try:
                    batch_update(written)
                except Exception:
                    # The local status already advanced, so the change
                    # detection would skip these rows forever; re-mark
                    # them dirty (after the clear below) so the next
                    # cycle rewrites the batch.
                    log.exception(
                        "status batch write failed; %d PodGroups "
                        "re-marked dirty for the next cycle",
                        len(written),
                    )
                    failed_status_uids = [pg.uid for pg in written]
        # Every pending in-place transition has now been persisted (or
        # superseded); a failure above leaves the set intact for the
        # next cycle.
        self._phase_dirty.clear()
        if failed_status_uids:
            self._phase_dirty.update(failed_status_uids)

    def _ensure_status_counts(self) -> np.ndarray:
        """[Jn, S+1] per-(job x status-class) counts over LIVE state —
        the persistent derive-time table adjusted by the rows the cycle
        itself dirtied (commit binds, evictions), instead of a full
        pod-axis scan per close (fastpath_incr.live_status_counts).
        Columns follow ``fastpath_incr.STATUS_VALUES`` order."""
        counts = getattr(self, "_status_counts", None)
        if counts is None:
            from .fastpath_incr import aggregates_of

            counts = self._status_counts = aggregates_of(
                self.m).live_status_counts(self.m, self.Pn)
        return counts

    def _gang_message(self, row: int) -> str:
        """Replicates gang.go's unschedulable message via job.fit_error()."""
        from .fastpath_incr import N_STATUS, STATUS_VALUES

        m = self.m
        counts = self._ensure_status_counts()
        unready = int(m.j_minav[row] - self.j_ready_base[row])
        total = int(self.j_cnt_total[row])
        key = (counts[row].tobytes(), int(m.j_minav[row]), unready, total)
        memo = getattr(self, "_gang_msg_memo", None)
        if memo is None:
            memo = self._gang_msg_memo = {}
        msg = memo.get(key)
        if msg is None:
            reasons = {
                TaskStatus(STATUS_VALUES[ci]).name: int(n)
                for ci, n in enumerate(counts[row][:N_STATUS])
                if n
            }
            reasons["minAvailable"] = int(m.j_minav[row])
            parts = sorted(f"{v} {k}" for k, v in reasons.items())
            fit = f"pod group is not ready, {', '.join(parts)}."
            msg = memo[key] = (
                f"{unready}/{total} tasks in gang unschedulable: {fit}"
            )
        return msg


def run_cycle_fast(store, conf, shard=None) -> bool:
    """Run one scheduling cycle on the fast path; False = not eligible
    (caller should fall back to the object-session path).  ``shard`` is
    the calling loop's shard.ShardContext under the sharded control
    plane (ISSUE 16) — cycles stay atomic under the store lock, so
    shards interleave at cycle granularity and only the PIPELINED
    overlap races across shards (the optimistic commit gate's domain)."""
    tracer = tracer_of(store, annotate=TraceAnnotation)
    with tracer.cycle(getattr(store, "flight", None)) as scope:
        # Still the cycle's ``prologue`` lane (scheduler.py opens it):
        # building the cycle, and the wait for the store lock.
        with scope.lane("prologue"):
            cycle = FastCycle(store, conf, shard=shard)
            if not cycle.eligible():
                return False
            store._lock.acquire()
        try:
            cycle.run()
        finally:
            store._lock.release()
    if shard is not None:
        shard.cycles += 1
    return True
