"""Rebalance action: gang-aware defragmentation with disruption budgets.

The sixth action (``actions: "enqueue, allocate, backfill, rebalance"``).
Unlike the other five it has no sequential object-path reference — the
reference family delegates defragmentation to a separate descheduler
process — so the object-session ``execute`` is a documented no-op and
the real implementation is the fast path's ``FastCycle._rebalance``
lane (plan = what-if ``solve_wave`` over a hypothetically drained
cluster, commit = evictions through the ``fastpath_evict`` machinery;
see docs/rebalance.md).

This module also owns the **migration planner** state that outlives a
single cycle:

- ``MigrationLedger`` — the store-attached record of in-flight
  migrations, shared since ISSUE 11 by every what-if engine action
  (rebalance, device-native preempt and reclaim — entries carry the
  evicting ``action`` and the beneficiary gang).  A committed plan
  registers every victim; when the evicted pod finishes terminating
  (``store.delete_pod``, driven by the simulator's graceful-termination
  ticks or a real kubelet), the ledger *restores* it: an identical
  Pending pod re-enters the store, playing the owning controller's
  recreate.  No pod is ever lost — rebalance proved a re-placement
  exists; a preempted/reclaimed pod waits its turn through the ordinary
  allocate lane.
- disruption budgets — the PDB equivalent.  ``max_unavailable_of``
  resolves a PodGroup's ceiling (``PodGroup.max_unavailable``, else the
  ``VOLCANO_TPU_REBALANCE_MAX_UNAVAIL`` default); the ledger's
  ``disrupted`` count (victims whose restored pod is not yet bound)
  is charged against it both at plan time and at commit re-check
  (``BudgetsLeft``: what is left of every group's, from one pass of the
  ledger).
"""

from __future__ import annotations

import collections
import copy
import logging
import os
from typing import Dict, Optional

log = logging.getLogger(__name__)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def rebalance_enabled() -> bool:
    """Master switch (the action string is the real opt-in; this kills
    the lane without a config rollout)."""
    return os.environ.get("VOLCANO_TPU_REBALANCE", "1") != "0"


def drain_cap() -> int:
    """Max nodes one plan may hypothetically drain."""
    return max(1, _env_int("VOLCANO_TPU_REBALANCE_DRAIN_CAP", 32))


def min_gain() -> int:
    """Min starved-gang tasks a plan must newly place to commit."""
    return max(1, _env_int("VOLCANO_TPU_REBALANCE_MIN_GAIN", 1))


def default_max_unavailable() -> int:
    """Per-PodGroup disruption ceiling when the group sets none."""
    return max(0, _env_int("VOLCANO_TPU_REBALANCE_MAX_UNAVAIL", 1))


def max_unavailable_of(pg) -> int:
    """Resolve a PodGroup's disruption budget (PDB max_unavailable
    equivalent).  ``None``/missing falls back to the env default."""
    v = getattr(pg, "max_unavailable", None) if pg is not None else None
    if v is None:
        return default_max_unavailable()
    return max(0, int(v))


class _Migration:
    """One victim's evict -> restore -> rebind lifecycle."""

    __slots__ = ("uid", "group_uid", "planned_node", "restored_uid",
                 "action", "for_gang")

    def __init__(self, uid: str, group_uid: str, planned_node: str,
                 action: str = "rebalance", for_gang: str = ""):
        self.uid = uid
        self.group_uid = group_uid
        self.planned_node = planned_node
        # uid of the restored Pending pod, set when the eviction's
        # termination completes and the ledger re-creates the pod.
        self.restored_uid: Optional[str] = None
        # Which engine action evicted this victim (ISSUE 11: preempt,
        # reclaim and rebalance share one ledger and one per-PodGroup
        # disruption-budget pool) and which starved gang the wave
        # served (``wave_pending`` keys re-plan suppression on it).
        self.action = action
        self.for_gang = for_gang


class MigrationLedger:
    """Store-attached in-flight migration record (``store.migrations``).

    Called from inside the store's lock (``delete_pod``) and from the
    fast-path cycle (which holds the same re-entrant lock), so no lock
    of its own is needed.
    """

    def __init__(self):
        self.entries: Dict[str, _Migration] = {}  # victim uid -> entry
        self._restore_seq = 0
        # Monotonic counters for the flight recorder / tests.
        self.committed_plans = 0
        self.restored_pods = 0

    # ------------------------------------------------------------ commit

    def register(self, uid: str, group_uid: str, planned_node: str,
                 action: str = "rebalance", for_gang: str = "") -> None:
        self.entries[uid] = _Migration(uid, group_uid, planned_node,
                                       action=action, for_gang=for_gang)

    def cancel(self, uid: str) -> None:
        """Drop a migration whose eviction never dispatched (the
        evictor failed and the pod reverted to Running —
        ``fastpath_evict.EvictState.flush``).  The pod was never
        unavailable, so it must not pin its group's budget nor be
        "restored" when it eventually terminates for ordinary
        reasons."""
        self.entries.pop(uid, None)

    # ----------------------------------------------------------- restore

    def pod_deleted(self, store, pod) -> None:
        """``store.delete_pod`` hook: a terminating migration victim is
        restored as a fresh Pending pod (the owning controller's
        recreate, played in-process so migration e2e is hermetic).

        Only an eviction-driven termination restores: a pod deleted
        while NOT marked ``deleting`` (an operator/controller delete),
        or whose PodGroup is gone (the workload itself was removed),
        must stay deleted — resurrecting it would both override an
        explicit delete and strand an unschedulable orphan that pins
        the ledger (and with it the lane) forever.  Either way the
        entry leaves the ledger."""
        entry = self.entries.get(pod.uid)
        if entry is None or entry.restored_uid is not None:
            return
        if not pod.deleting or store.pod_groups.get(
                entry.group_uid) is None:
            del self.entries[pod.uid]
            return
        restored = copy.copy(pod)
        self._restore_seq += 1
        restored.uid = f"{pod.uid}-mig{self._restore_seq}"
        restored.node_name = None
        restored.deleting = False
        from ..api import PodPhase

        restored.phase = PodPhase.Pending
        restored.exit_code = 0
        entry.restored_uid = restored.uid
        self.restored_pods += 1
        store.add_pod(restored)
        # Journey stitch: link the fresh uid's timeline back to the
        # evicted victim's, so the migration reads as ONE pod journey.
        journey = getattr(store, "journey", None)
        if journey is not None:
            journey.pod_restored(pod.uid, restored.uid)
        planned = (f" (planned node {entry.planned_node})"
                   if entry.planned_node else "")
        store.record_event(
            f"Pod/{pod.namespace}/{pod.name}", "MigrationRestored",
            f"restored as {restored.uid} after {entry.action} "
            f"eviction{planned}",
        )

    # ----------------------------------------------------------- budgets

    def _done(self, store, entry: _Migration) -> bool:
        """A migration is complete once its restored pod is bound."""
        # The workload itself was removed mid-migration: nothing left
        # to restore or re-bind; the entry must not pin the budget (or
        # the one-wave-at-a-time gate) forever.
        if store.pod_groups.get(entry.group_uid) is None:
            return True
        if entry.restored_uid is None:
            return False
        pod = store.pods.get(entry.restored_uid)
        # Restored pod deleted again (external actor): nothing left to
        # track; the ledger must not pin the budget forever.
        if pod is None:
            return True
        return pod.node_name is not None

    def prune(self, store) -> None:
        done = [uid for uid, e in self.entries.items()
                if self._done(store, e)]
        for uid in done:
            del self.entries[uid]

    def disrupted(self, store, group_uid: str) -> int:
        """Victims of the group still unavailable (evicted / terminating
        / restored-but-unbound)."""
        self.prune(store)
        return sum(1 for e in self.entries.values()
                   if e.group_uid == group_uid)

    def disrupted_by_group(self, store) -> Dict[str, int]:
        """``disrupted`` of every group that has an entry, in one
        ``prune`` and one pass of the ledger; a group without an entry
        is not in it (its count is 0)."""
        self.prune(store)
        return dict(collections.Counter(
            e.group_uid for e in self.entries.values()))

    def active(self, store, action: Optional[str] = None) -> bool:
        """True while any migration is incomplete — the rebalance
        planner runs one migration wave at a time.  ``action`` filters
        to one engine action's entries: a preempted batch pod may stay
        Pending indefinitely (its entry pins its group's budget, which
        is correct PDB accounting), and that must not wedge the
        rebalance lane's own single-wave gate."""
        self.prune(store)
        if action is None:
            return bool(self.entries)
        return any(e.action == action for e in self.entries.values())

    def wave_pending(self, store, gang_uid: str) -> bool:
        """True while a prior wave for ``gang_uid`` is still FREEING
        capacity (victims evicted but not yet terminated): planning
        another wave for the same gang before the capacity lands would
        double-evict for the same need.  Once the victims are restored
        the gang either binds or is legitimately starved again."""
        self.prune(store)
        return any(e.for_gang == gang_uid and e.restored_uid is None
                   for e in self.entries.values())


class BudgetsLeft:
    """Remaining per-PodGroup disruption budget by mirror job row, after
    waves already in flight across EVERY action sharing the ledger: the
    ledger is counted once, a group's ceiling read when asked.  ``get``
    is all of a mapping that ``ops.victim.select_victims`` calls."""

    def __init__(self, store, mirror):
        ledger = store.migrations
        self._m = mirror
        self._used = ({} if ledger is None
                      else ledger.disrupted_by_group(store))

    def get(self, jrow, default=0):
        return (max_unavailable_of(self._m.j_pg[jrow])
                - self._used.get(self._m.j_uid[jrow], 0))


def ledger_of(store) -> MigrationLedger:
    """The store's migration ledger, created on first use."""
    ledger = getattr(store, "migrations", None)
    if ledger is None:
        ledger = store.migrations = MigrationLedger()
    return ledger


class RebalanceAction:
    """Object-path registration for the ``rebalance`` action name.

    The device-native rebalance lane needs the array mirror, the
    profile tables and the wave solver — none of which exist on the
    object-session path.  Configurations that include ``rebalance``
    with fast-path-eligible plugins run it in ``FastCycle._rebalance``;
    on the object path the action is a no-op (matching the reference,
    where defragmentation lives in a separate descheduler, not the
    scheduler's action list).
    """

    name = "rebalance"

    def initialize(self):
        pass

    def un_initialize(self):
        pass

    def execute(self, ssn) -> None:
        log.debug(
            "rebalance is a device-native lane; the object-session "
            "path does not implement it (session %s)", ssn.uid,
        )
