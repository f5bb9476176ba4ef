"""Sharded scheduler control plane (ISSUE 16).

Every lane below the cycle thread scales out (mesh-sharded solve, delta
wire frames, solver replica pool), leaving the cycle thread itself as
the last single-threaded bottleneck: one scheduler owns every queue, so
bind throughput is capped at one box no matter how fast the device lane
gets.  This module runs N ``FastCycle`` shards over ONE logical
``ClusterStore``:

- **Ownership** is queue-partitioned: a stable hash of the queue name
  maps each queue to a home shard (``ShardOwnershipTable``), so the
  partition survives restarts and queue churn without coordination.
  Each shard's cycle sees the SHARED node planes but only its owned
  queues' jobs — the existing ``session_jobs`` seam is the single
  filter point (``ShardContext.filter_session_jobs``); every downstream
  consumer (``_pending_rows``, enqueue, backfill, close) derives from
  it.
- **Commits are optimistic.**  Shards never lock queues against each
  other; each dispatches its pipelined solve against a point-in-time
  snapshot and commits at the top of its next cycle.  Commits serialize
  under ``store._lock`` and every commit bumps ``mirror.mutation_seq``,
  so of two racing shards the SECOND to commit always re-validates
  (fastpath's staleness guard) against node planes that already include
  the first shard's binds: the loser's conflicting rows are voided
  row-wise — never a double-bind — and re-place next cycle — never a
  lost pod.  The new ``mirror.shard_commit_seq`` + the table's handoff
  epoch (captured on ``InflightSolve.shard_seq`` at dispatch) tell the
  guard the race was CROSS-SHARD, so those voids are attributed as the
  ``cross-shard-conflict`` drop reason and counted in
  ``volcano_shard_conflicts_total{outcome}``.  The conservation auditor
  referees at runtime: pod flows stay balanced across shards or it
  raises an anomaly.
- **Work stealing** (phase b): an idle shard — zero pending rows across
  its owned queues — claims the most-starved foreign queue via an
  epoch-bumped handoff token (``ShardOwnershipTable.steal_queue``), but
  only when the donor retains at least one other pending queue, which
  makes the handoff ping-pong-stable.  A steal race (donor's in-flight
  solve covering the stolen queue) is resolved by the same optimistic
  machinery: whichever commit lands second re-validates and drops the
  conflicting rows.

``VOLCANO_TPU_SHARDS=1`` (the default) bypasses all of this —
``make_scheduler`` returns the plain single ``Scheduler`` and no shard
state is ever attached to the store, keeping the pre-sharding path
bind-for-bind and wire-byte identical.
"""

from __future__ import annotations

import logging
import os
import zlib
from typing import Dict, List, Optional

import numpy as np

from .api import TaskStatus
from .metrics import metrics
from .scheduler import Scheduler

log = logging.getLogger(__name__)

ST_PENDING = int(TaskStatus.Pending)


def shards_from_env() -> int:
    """The ``VOLCANO_TPU_SHARDS`` knob (docs/tuning.md): number of cycle
    threads over the one logical cluster.  1 (default) = the unsharded
    single-scheduler path."""
    raw = os.environ.get("VOLCANO_TPU_SHARDS", "1")
    try:
        return max(int(raw), 1)
    except ValueError:
        log.warning("VOLCANO_TPU_SHARDS=%r is not an integer; using 1", raw)
        return 1


def stable_shard(name: str, n_shards: int) -> int:
    """Stable queue-name -> home-shard hash (crc32: deterministic across
    processes and restarts, unlike ``hash()`` under PYTHONHASHSEED)."""
    return zlib.crc32(name.encode("utf-8")) % max(n_shards, 1)


class ShardOwnershipTable:
    """Queue -> shard ownership: a stable base hash plus a (small) steal
    override map.  Attached to the store (``store.shard_table``); the
    mutable state is guarded by the OWNING STORE's ``_lock`` — cycles
    read it under the cycle lock, and steals mutate it under the same
    lock, so a cycle can never observe a half-applied handoff."""

    def __init__(self, n_shards: int):
        self.n_shards = max(int(n_shards), 1)
        # Handoff token: bumped by every steal.  Captured (together with
        # mirror.shard_commit_seq) on InflightSolve.shard_seq at
        # dispatch; an advance at fetch time forces the full
        # re-validation even when nothing else moved, so a donor's
        # in-flight solve covering a just-stolen queue can never commit
        # unchecked.
        self.epoch = 0  # guarded-by: _lock
        # Steal overrides: queue name -> owning shard, for queues living
        # away from their base hash.  Empty in steady state.
        self._overrides: Dict[str, int] = {}  # guarded-by: _lock
        # Immutable snapshot for lock-free /debug/shards reads (replaced
        # wholesale on every steal; readers see old or new, never torn).
        self._debug = {"epoch": 0, "overrides": {}}
        # Runtime lockdep (obs/lockdep.py): arm this table when the
        # probe is active — the table outlives any one store walk.
        from .obs.lockdep import attach

        attach(self)

    # holds: _lock
    def owner_of(self, name: str) -> int:
        got = self._overrides.get(name)
        if got is not None:
            return got
        return stable_shard(name, self.n_shards)

    # holds: _lock
    def owners_of(self, names: List[str]) -> np.ndarray:
        """Vector of owning shard per queue name ([Q] int32)."""
        if not names:
            return np.zeros(0, np.int32)
        return np.fromiter(
            (self.owner_of(n) for n in names), np.int32, count=len(names)
        )

    # holds: _lock
    def steal_queue(self, name: str, to_shard: int) -> int:
        """Hand ``name`` to ``to_shard``; returns the new handoff epoch.
        Moving a queue back to its base owner clears the override so the
        table converges to empty under balanced load."""
        if stable_shard(name, self.n_shards) == to_shard:
            self._overrides.pop(name, None)
        else:
            self._overrides[name] = int(to_shard)
        self.epoch += 1
        self._debug = {
            "epoch": self.epoch, "overrides": dict(self._overrides),
        }
        return self.epoch

    def snapshot(self) -> dict:
        """Lock-free debug view (the immutable ``_debug`` replacement
        makes this safe from HTTP handler threads — /debug endpoints
        must never take the store lock)."""
        return self._debug


class ShardContext:
    """One shard's identity + per-shard cycle state, passed into
    ``Scheduler``/``FastCycle``.  Counters are plain ints written only
    by the owning cycle thread (under the store lock) and read
    lock-free by /debug/shards — single-writer, so torn reads are
    impossible."""

    def __init__(self, index: int, table: ShardOwnershipTable):
        self.index = int(index)
        self.table = table
        # Optional per-shard solver client (RemoteSolver/SolverPool):
        # overrides store.remote_solver so each shard can own its own
        # device lane.  Same ownership contract as the store slot —
        # dispatch/fetch only on this shard's cycle thread.
        self.remote_solver = None
        # Single-writer telemetry (the shard's own cycle thread).
        self.cycles = 0
        self.conflicts = 0
        self.steals = 0
        self.owned_pending = 0

    @property
    def count(self) -> int:
        return self.table.n_shards

    @property
    def runs_evictions(self) -> bool:
        """Evict actions (preempt/reclaim/rebalance) reason over the
        WHOLE cluster's victims, so exactly one shard may run them or
        two shards would plan overlapping evictions; shard 0 is the
        designated evictor."""
        return self.index == 0

    # ------------------------------------------------------ cycle filter

    # holds: _lock
    def filter_session_jobs(self, cycle, session_jobs: np.ndarray) -> np.ndarray:
        """Restrict a FastCycle's session job set to this shard's owned
        queues — the single seam the per-shard mirror view hangs off:
        ``_schedulable_rows``/``_pending_rows``/enqueue/backfill/close
        all derive from ``session_jobs``.  Jobs with an unknown queue
        (``q_of_job`` < 0) stay on shard 0 so their error-log semantics
        fire exactly once."""
        if self.table.n_shards <= 1 or len(session_jobs) == 0:
            return session_jobs
        owned_q = self.table.owners_of(cycle.queue_names) == self.index
        q = cycle.q_of_job[session_jobs]
        keep = np.zeros(len(session_jobs), bool)
        has_q = q >= 0
        keep[has_q] = owned_q[q[has_q]]
        if self.index == 0:
            keep[~has_q] = True
        return session_jobs[keep]

    # ---------------------------------------------------- work stealing

    def maybe_steal(self, store) -> bool:
        """Work stealing (tentpole phase b): when this shard has no
        pending work across its owned queues, claim the most-starved
        foreign queue so a hot queue cannot strand an idle cycle
        thread's capacity.  Runs on this shard's cycle thread just
        before its cycle.  Returns True when a queue was claimed."""
        if self.table.n_shards <= 1:
            return False
        with store._lock:
            return self._steal_starved(store)

    # holds: _lock
    def _steal_starved(self, store) -> bool:
        m = store.mirror
        Pn = m.n_pods
        if not Pn:
            return False
        jr = m.p_job[:Pn]
        pend = (
            m.p_alive[:Pn] & (m.p_status[:Pn] == ST_PENDING) & (jr >= 0)
        )
        if not pend.any():
            return False
        jrows = jr[pend]
        jrows = jrows[m.j_alive[jrows]]
        if not len(jrows):
            return False
        qcodes = m.j_queue_code[jrows]
        qcodes = qcodes[qcodes >= 0]
        if not len(qcodes):
            return False
        counts = np.bincount(qcodes, minlength=len(m.qnames.items))
        pending_codes = np.flatnonzero(counts)
        names = m.qnames.items
        owners = {
            int(c): self.table.owner_of(names[int(c)])
            for c in pending_codes
        }
        own_backlog = sum(
            int(counts[c]) for c, o in owners.items() if o == self.index
        )
        self.owned_pending = own_backlog
        if own_backlog:
            return False  # not idle: nothing to steal for
        # Pending-queue count per donor: a donor must RETAIN at least
        # one other pending queue or the steal just relocates the
        # starvation (and two idle shards would ping-pong the last
        # queue between them forever).
        donor_load: Dict[int, int] = {}
        for _c, o in owners.items():
            donor_load[o] = donor_load.get(o, 0) + 1
        order = sorted(
            (int(c) for c in pending_codes),
            key=lambda c: -int(counts[c]),
        )
        for c in order:
            donor = owners[c]
            if donor == self.index or donor_load.get(donor, 0) < 2:
                continue
            qname = names[c]
            epoch = self.table.steal_queue(qname, self.index)
            self.steals += 1
            metrics.shard_steals.inc(1)
            log.info(
                "shard %d stole starved queue %r from shard %d "
                "(backlog %d rows, handoff epoch %d)",
                self.index, qname, donor, int(counts[c]), epoch,
            )
            return True
        return False

    def debug_snapshot(self) -> dict:
        return {
            "index": self.index,
            "cycles": self.cycles,
            "conflicts": self.conflicts,
            "steals": self.steals,
            "owned_pending": self.owned_pending,
        }


class ShardedScheduler:
    """N per-shard ``Scheduler`` loops over one store: the drop-in
    front-end ``service.make_scheduler`` returns when
    ``VOLCANO_TPU_SHARDS`` > 1.  Mirrors the single ``Scheduler``'s
    lifecycle surface (run / run_once / stop / healthy) so Service and
    the endurance harness drive either interchangeably."""

    def __init__(self, store, conf_path: Optional[str] = None,
                 conf_str: Optional[str] = None,
                 schedule_period: float = 1.0, gate=None,
                 shards: int = 2):
        n = max(int(shards), 1)
        self.store = store
        with store._lock:
            table = getattr(store, "shard_table", None)
            if table is None or table.n_shards != n:
                table = ShardOwnershipTable(n)
                store.shard_table = table
        self.table = table
        self.shards = [ShardContext(i, table) for i in range(n)]
        self.schedulers = [
            Scheduler(
                store, conf_path=conf_path, conf_str=conf_str,
                schedule_period=schedule_period, gate=gate, shard=ctx,
            )
            for ctx in self.shards
        ]

    @property
    def n_shards(self) -> int:
        return self.table.n_shards

    def run(self) -> None:
        """Start every shard's periodic cycle thread."""
        for s in self.schedulers:
            s.run()

    def run_once(self) -> None:
        """One synchronous cycle per shard, in shard order (tests and the
        endurance harness drive this for determinism; the optimistic commit
        protocol engages all the same, because each shard's pipelined
        dispatch from call K commits during call K+1, AFTER its
        siblings' intervening commits)."""
        for s in self.schedulers:
            s.run_once()

    def stop(self, timeout: Optional[float] = None) -> None:
        for s in self.schedulers:
            s.stop(timeout)

    def healthy(self) -> bool:
        return all(s.healthy() for s in self.schedulers)

    def debug_snapshot(self) -> dict:
        """Lock-free state for /debug/shards."""
        return {
            "shards": self.n_shards,
            "table": self.table.snapshot(),
            "per_shard": [ctx.debug_snapshot() for ctx in self.shards],
        }


def make_scheduler(store, conf_path: Optional[str] = None,
                   conf_str: Optional[str] = None,
                   schedule_period: float = 1.0, gate=None,
                   shards: Optional[int] = None):
    """Scheduler factory honouring ``VOLCANO_TPU_SHARDS``.  The default
    (1) constructs the plain single ``Scheduler`` — not a 1-shard
    ShardedScheduler — so the kill switch is the pre-sharding code
    path itself, bitwise identical."""
    n = shards_from_env() if shards is None else max(int(shards), 1)
    if n <= 1:
        return Scheduler(
            store, conf_path=conf_path, conf_str=conf_str,
            schedule_period=schedule_period, gate=gate,
        )
    return ShardedScheduler(
        store, conf_path=conf_path, conf_str=conf_str,
        schedule_period=schedule_period, gate=gate, shards=n,
    )
