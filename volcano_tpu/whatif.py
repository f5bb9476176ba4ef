"""The what-if engine: hypothetical solves, proven atomically.

Rebalance (ISSUE 5) introduced the expensive trick this module now owns
for every eviction-shaped action: patch the cycle arrays to a
hypothetical cluster, run the *exact* allocate jit over it (profile
dedup, devsnap planes, two-phase shortlists, mesh sharding all intact),
judge the verdict, and commit — evictions through the
``fastpath_evict`` machinery, restores through the shared
``MigrationLedger`` — only when the solve PROVED the outcome.  A plan
mutates nothing until commit, so rejecting (or stale-voiding) one is
free.

Three actions ride the engine (docs/preempt_reclaim.md):

- ``rebalance`` — drain fragmented nodes; victims re-enter the solve
  and must all re-place (capacity-neutral defragmentation).
- ``preempt`` — a starved higher-priority gang drains same-queue
  lower-priority victims (``ops/victim.py`` selects them under
  disruption budgets); victims do NOT re-enter the solve — they are
  restored as Pending by the ledger and wait their turn (zero lost
  pods unconditionally).
- ``reclaim`` — a gang in an under-deserved queue drains victims from
  OTHER queues that are ``Reclaimable`` and over their deserved share,
  never below deserved.

Pipelined stores park the what-if as ``pipeline.InflightPlan`` and
commit at the next cycle's top behind the staleness guard: ANY
``mutation_seq``/``epoch``/``compact_gen``/node-count drift voids the
plan wholesale.  The engine is mesh-aware — the hypothetical patches
touch only the per-cycle host planes (idle / ntasks / resident /
queue / readiness vectors), never the device-resident devsnap planes,
so the sharded dispatch path (``FastCycle._solve_mesh_dispatch``)
carries it unchanged.  Single-connection remote-solver deployments keep
the engine off (the plan solve would contend with the allocate lane on
the one strict request/reply connection); preempt/reclaim then fall
back to the host walk.  A solver *pool* (ISSUE 15,
``solver_pool.SolverPool``) lifts that: plan solves offload to an idle
non-primary replica and overlap the allocate lane — the staleness
guard and ``InflightPlan`` commit path are unchanged, and a lost plan
reply voids the plan (it mutated nothing; outcome ``lost-reply``).

Every function here runs on the cycle thread inside ``FastCycle.run``
(under ``run_cycle_fast``'s store lock).
"""

from __future__ import annotations

import logging
import os
from typing import Dict, NamedTuple, Optional, Tuple

import numpy as np

from .metrics import metrics

log = logging.getLogger(__name__)

F = np.float32
I = np.int32

ACTIONS = ("preempt", "reclaim", "rebalance")
# The numbers of a cycle record's ``whatif`` block, the cycle's own and
# no one outcome's: calls of ``_plan_evict_gang`` (both actions), the
# outcomes ``committed`` and ``rejected-*`` and the victims of the
# committed plans, ``prior`` included (docs/tracing.md; a plan that was
# rejected or voided took none: its size is in its ``whatif_solve``
# span's ``args``), victim tables built, tries that ran ``victim_scores``.
WALK_COUNTS = ("gangs_tried", "committed", "rejected", "victims",
               "tables_built", "kernel_calls")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def evict_device_enabled() -> bool:
    """Master switch for the device-native preempt/reclaim lanes.
    ``VOLCANO_TPU_EVICT_DEVICE=0`` restores the host-side victim walk
    (``fastpath_evict``) bind-for-bind."""
    return os.environ.get("VOLCANO_TPU_EVICT_DEVICE", "1") != "0"


def evict_cap() -> int:
    """Max victims one preempt/reclaim wave may take."""
    return max(1, _env_int("VOLCANO_TPU_EVICT_CAP", 64))


def whatif_offload_on(remote) -> bool:
    """True when ``remote`` is a solver pool with an idle non-primary
    replica that can take a plan-proving solve right now (ISSUE 15).
    A plain ``RemoteSolver`` has no offload capacity by construction."""
    avail = getattr(remote, "whatif_replica_available", None)
    return avail is not None and bool(avail())


def evict_device_on(store) -> bool:
    """True when this store's preempt/reclaim run the plan-prove-commit
    device lane.  Single-connection remote-solver deployments keep the
    host walk (the plan solve would contend for the one connection); a
    solver pool with an idle non-primary replica offloads the plan
    solve there instead; a mesh is fine (the engine dispatches through
    the sharded path)."""
    if not evict_device_enabled():
        return False
    remote = getattr(store, "remote_solver", None)
    return remote is None or whatif_offload_on(remote)


class WhatIfPlan(NamedTuple):
    """One hypothetical eviction wave, action-agnostic.

    ``resolve_victims`` decides the solve's task set: True re-enters the
    victims as pending rows alongside the gang (rebalance — every
    victim must re-place), False solves the gang alone (preempt /
    reclaim — victims restore as Pending and wait)."""

    action: str                  # "preempt" | "reclaim" | "rebalance"
    gang_job: int                # mirror job row of the starved gang
    gang_uid: str                # its PodGroup uid (events / ledger)
    gang_rows: np.ndarray        # [G] pending mirror rows entering the solve
    victim_rows: np.ndarray      # [V] running mirror rows to evict
    victim_jobs: np.ndarray      # [V] mirror job rows of the victims
    drain_nodes: np.ndarray      # [K] node rows drained (rebalance; else [])
    need: int                    # gang tasks outstanding at plan time
    frag_before: float           # mean frag score (rebalance; else 0.0)
    budgets: Dict[str, int]      # group uid -> victims this plan takes
    resolve_victims: bool        # victims re-enter the what-if solve


# --------------------------------------------------------------- ordering


def plan_task_order(plan: WhatIfPlan):
    """(solve_jobs, task_rows, victims-in-solve-order) for a plan's
    what-if solve: the starved gang's pending rows first (it is the
    point of the wave), then — only when the plan re-solves its victims
    — the victims job-contiguously, the order the assignment vector is
    aligned to."""
    if not plan.resolve_victims or not len(plan.victim_rows):
        return ([plan.gang_job], plan.gang_rows.astype(np.int64),
                np.zeros(0, np.int64))
    vorder = np.argsort(plan.victim_jobs, kind="stable")
    vr = plan.victim_rows[vorder]
    task_rows = np.concatenate(
        [plan.gang_rows, vr]).astype(np.int64)
    solve_jobs = [plan.gang_job]
    seen = {plan.gang_job}
    for j in plan.victim_jobs[vorder].tolist():
        if j not in seen:
            seen.add(j)
            solve_jobs.append(int(j))
    return solve_jobs, task_rows, vr


# ----------------------------------------------------------- input patch


# holds: _lock
def whatif_inputs(cyc, plan: WhatIfPlan):
    """Solver inputs for the hypothetically drained cluster: the
    drained victims' capacity returns to idle, their rows leave the
    resident set (ports / affinity counts / task slots), their jobs'
    ready counts drop and their queues' allocations shrink by the
    drained members.  When the plan re-solves its victims (rebalance),
    queue-deserved gating is lifted for the VICTIM queues only — a
    victim's re-placement frees exactly what it claims, so
    re-arbitrating its share would veto a capacity-neutral move; the
    starved gang's placement is a genuinely new allocation and keeps
    the live lane's gating either way (a share-capped gang must not
    trigger an eviction wave the live allocate would then veto).
    Everything else (devsnap planes, two-phase shortlists, profile
    dedup) rides ``FastCycle._solve_inputs`` unchanged, so the plan
    solve hits the same jit as the live allocate lane."""
    m = cyc.m
    # Deferred aggregate scatters must land on the REAL q_alloc before
    # it is copied, or they would be lost to the patch.
    cyc._flush_aggr()
    solve_jobs, task_rows, vr = plan_task_order(plan)
    vnode = m.p_node[:cyc.Pn][plan.victim_rows].astype(np.int64)
    er, si, v = m.c_req.gather(plan.victim_rows)
    idle_patch = cyc.n_idle.copy()
    np.add.at(idle_patch, (vnode[er], si), v)
    ntasks_patch = cyc.n_ntasks - np.bincount(
        vnode, minlength=cyc.Nn).astype(I)
    ready_patch = cyc.j_ready_base.copy()
    np.add.at(ready_patch, plan.victim_jobs, -1)
    resident_patch = cyc.resident.copy()
    resident_patch[plan.victim_rows] = False
    deserved_patch = cyc.q_deserved.copy()
    q_alloc_patch = cyc.q_alloc.copy()
    vq = cyc.q_of_job[plan.victim_jobs]
    vq_ok = vq >= 0
    if vq_ok.any():
        if plan.resolve_victims:
            deserved_patch[np.unique(vq[vq_ok])] = 3.0e38
        # Un-charge the drained victims so a gang sharing a victim's
        # queue is not double-gated against allocations the eviction
        # itself returns (and, for rebalance, that the solve will
        # re-charge on re-placement).
        er_q = vq_ok[er]
        np.add.at(q_alloc_patch,
                  (vq[er][er_q], si[er_q]), -v[er_q])
    saved = (cyc.n_idle, cyc.n_ntasks, cyc.j_ready_base,
             cyc.resident, cyc.q_deserved, cyc.q_alloc)
    (cyc.n_idle, cyc.n_ntasks, cyc.j_ready_base, cyc.resident,
     cyc.q_deserved, cyc.q_alloc) = (
        idle_patch, ntasks_patch, ready_patch, resident_patch,
        deserved_patch, q_alloc_patch)
    # The what-if's encode must not POLLUTE the allocate lane's encode
    # cache: its task rows differ, so caching its entry would (a) evict
    # the live entry and (b) bump the profile generation — needlessly
    # invalidating the device-incremental static planes and warm
    # candidates (ISSUE 9) on every cycle that plans a wave.
    # Save/restore both slots; the what-if entry would never hit for
    # the live lane anyway.
    store = cyc.store
    saved_cache = store._encode_cache
    saved_gen = getattr(store, "_encode_gen", 0)
    try:
        inputs, pid, profiles, ncls = cyc._solve_inputs(
            solve_jobs, task_rows, slim=True)
    finally:
        (cyc.n_idle, cyc.n_ntasks, cyc.j_ready_base,
         cyc.resident, cyc.q_deserved, cyc.q_alloc) = saved
        store._encode_cache = saved_cache
        store._encode_gen = saved_gen
    return inputs, pid, profiles, ncls


# ------------------------------------------------------ dispatch / commit


# holds: _lock
def dispatch_plan(cyc, plan: WhatIfPlan) -> None:
    """Run (or pipeline) the plan's what-if solve.  Mesh stores ride
    ``FastCycle._solve_mesh_dispatch`` — the patch touches only host
    planes, so the sharded devsnap path carries the hypothetical
    cluster unchanged."""
    from .ops.wave import solve_wave

    m = cyc.m
    store = cyc.store
    # No lanes= here: the action:<name> span already accumulates the
    # lane seconds; a second accumulation would double-count.
    with cyc.tracer.span(
            "whatif_solve", cat="whatif",
            args={"action": plan.action, "gang": plan.gang_uid,
                  "victims": len(plan.victim_rows),
                  "need": plan.need}):
        inputs, pid, profiles, ncls = whatif_inputs(cyc, plan)
        remote = getattr(store, "remote_solver", None)
        if remote is not None:
            # What-if offload (ISSUE 15): the plan solve ships to an
            # idle non-primary pool replica, overlapping the allocate
            # lane's in-flight solve instead of contending for the
            # single connection.  The child rebuilds node classes from
            # the frame; plan frames carry no devincr section.
            try:
                payload = remote.solve_whatif_async(inputs, pid,
                                                    profiles)
            except (OSError, ConnectionError, ValueError,
                    RuntimeError):
                # Every offload candidate died between the lane's
                # availability gate and this dispatch: the plan
                # mutated nothing — void it, let the pool's health
                # probes heal, and re-plan next cycle.
                log.warning(
                    "what-if offload dispatch failed; plan voided "
                    "(action=%s gang=%s)", plan.action, plan.gang_uid,
                    exc_info=True,
                )
                count_plan(cyc, plan.action, "lost-reply",
                           gang=plan.gang_uid,
                           victims=len(plan.victim_rows))
                return
            if cyc._pipeline_on:
                from .pipeline import InflightPlan

                store._solve_seq += 1
                store._inflight_plan = InflightPlan(
                    payload, plan, m.mutation_seq, m.epoch,
                    m.compact_gen, cyc.Nn, plan_id=store._solve_seq,
                    kind="remote",
                )
                return
            try:
                res = payload.fetch()
            except (OSError, ConnectionError, ValueError):
                # Lost plan reply (replica died mid-solve): the plan
                # mutated nothing — drop it and re-plan next cycle.
                count_plan(cyc, plan.action, "lost-reply",
                           gang=plan.gang_uid,
                           victims=len(plan.victim_rows))
                return
            assigned = np.asarray(res.assigned)
            never_ready = np.asarray(res.never_ready)
        else:
            mesh = cyc._solve_mesh()
            if mesh is not None:
                payload = cyc._solve_mesh_dispatch(
                    mesh, inputs, pid, profiles, ncls)
            else:
                payload = solve_wave(*inputs, pid=pid,
                                     profiles=profiles,
                                     taint_any=cyc._taint_any,
                                     node_classes=ncls)
            if cyc._pipeline_on:
                from .pipeline import InflightPlan

                for arr in (payload.assigned, payload.never_ready):
                    try:
                        arr.copy_to_host_async()
                    except AttributeError:
                        pass
                store._solve_seq += 1
                store._inflight_plan = InflightPlan(
                    payload, plan, m.mutation_seq, m.epoch,
                    m.compact_gen, cyc.Nn, plan_id=store._solve_seq,
                )
                return
            import jax

            assigned, never_ready = jax.device_get(
                (payload.assigned, payload.never_ready)
            )
    apply_plan(cyc, plan, np.asarray(assigned),
               np.asarray(never_ready))


# holds: _lock
def commit_inflight_plan(cyc) -> None:
    """Land (or void) the previous cycle's pipelined what-if plan.  A
    whole-cluster what-if has no per-row salvage, so ANY drift —
    mutation counter, node-table epoch, compaction generation, node
    count — voids the plan wholesale (it mutated nothing; the planner
    re-forms against fresh state)."""
    from .pipeline import take_inflight_plan

    # Top of the cycle, outside every action span: the taking is the
    # ``inflight`` lane, the landing a lane named after the action.
    with cyc.tracer.span("inflight", lanes=cyc.lanes):
        inflight = take_inflight_plan(cyc.store)
    if inflight is None:
        return
    m = cyc.m
    plan = inflight.plan
    with cyc.tracer.span(
            "whatif_commit", cat="whatif", lanes=cyc.lanes,
            lane=plan.action,
            args={"plan_id": inflight.plan_id,
                  "action": plan.action, "gang": plan.gang_uid,
                  "victims": len(plan.victim_rows)}):
        if (m.mutation_seq != inflight.mutation_seq
                or m.epoch != inflight.epoch
                or m.compact_gen != inflight.compact_gen
                or cyc.Nn != inflight.n_nodes):
            inflight.abandon()
            count_plan(cyc, plan.action, "stale-voided",
                       gang=plan.gang_uid,
                       victims=len(plan.victim_rows))
            return
        try:
            assigned, never_ready = inflight.fetch()
        except (OSError, ConnectionError, ValueError):
            if inflight.kind != "remote":
                raise
            # The offloaded plan solve's reply died with its replica
            # (ISSUE 15).  A plan mutates nothing until commit, so
            # this is free: drop it and let the planner re-form
            # against fresh state — the pool's health scoring routes
            # the next offload to a live replica.
            log.warning(
                "offloaded what-if plan reply lost; plan voided "
                "(action=%s gang=%s)", plan.action, plan.gang_uid,
                exc_info=True,
            )
            count_plan(cyc, plan.action, "lost-reply",
                       gang=plan.gang_uid,
                       victims=len(plan.victim_rows))
            return
        apply_plan(cyc, plan, assigned, never_ready)


# holds: _lock
def apply_plan(cyc, plan: WhatIfPlan, assigned: np.ndarray,
               never_ready: np.ndarray) -> None:
    """Judge the what-if verdict and commit iff the solve proved the
    wave's point: the gang reaches ready, and — when the plan re-solves
    its victims — every victim re-places and the gain clears the
    rebalance threshold."""
    from .actions.rebalance import min_gain

    m = cyc.m
    _, task_rows, vr_sorted = plan_task_order(plan)
    assigned = assigned[:len(task_rows)].astype(np.int64)
    G = len(plan.gang_rows)
    # The gang must still be the pending work the plan targeted (a
    # pipelined solve landing just above may have bound, or a delete
    # removed rows during the overlap).
    gr = plan.gang_rows
    from .api import TaskStatus

    st_pending = int(TaskStatus.Pending)
    if not bool((m.p_alive[gr]
                 & (m.p_status[gr] == st_pending)).all()):
        count_plan(cyc, plan.action, "stale-voided",
                   gang=plan.gang_uid,
                   victims=len(plan.victim_rows))
        return
    gang_assigned = int((assigned[:G] >= 0).sum())
    victims_ok = (bool((assigned[G:] >= 0).all())
                  if len(assigned) > G else True)
    gang_ready = (
        not bool(never_ready[0])
        and cyc.j_ready_base[plan.gang_job] + gang_assigned
        >= int(m.j_minav[plan.gang_job])
    )
    floor = min_gain() if plan.action == "rebalance" else 1
    if not (victims_ok and gang_ready and gang_assigned >= floor):
        count_plan(cyc, plan.action, "rejected-no-gain",
                   gang=plan.gang_uid, need=plan.need,
                   victims=len(plan.victim_rows),
                   gang_placed=gang_assigned,
                   frag=round(plan.frag_before, 4))
        # The identical plan would re-form (and re-fail) next cycle;
        # cool down until the cluster has had time to move.
        set_backoff(cyc.store, plan.action, plan.gang_uid,
                    cyc.REBALANCE_REJECT_BACKOFF)
        return
    if plan.resolve_victims:
        victim_nodes = assigned[G:]
    else:
        vr_sorted = plan.victim_rows.astype(np.int64)
        victim_nodes = np.full(len(vr_sorted), -1, np.int64)
    commit_plan(cyc, plan, vr_sorted, victim_nodes)


# holds: _lock
def commit_plan(cyc, plan: WhatIfPlan, victim_rows: np.ndarray,
                victim_nodes: np.ndarray) -> None:
    """Execute a proven plan: evict every victim through the
    ``fastpath_evict`` machinery (flushed to the store at cycle end,
    exactly as host-walk evictions are) and register each restore with
    the shared migration ledger so no pod is ever lost."""
    from .actions.rebalance import ledger_of, max_unavailable_of
    from .api import TaskStatus

    m = cyc.m
    store = cyc.store
    st_running = int(TaskStatus.Running)
    # Exact commit re-check behind the staleness guard: victims must
    # still be the Running residents the plan drained.
    ok = (m.p_alive[victim_rows]
          & (m.p_status[victim_rows] == st_running))
    if not bool(ok.all()):
        count_plan(cyc, plan.action, "stale-voided",
                   gang=plan.gang_uid, victims=len(victim_rows))
        return
    ledger = ledger_of(store)
    # Budget re-check at commit time, against the ledger's live
    # cross-action disrupted counts: preempt, reclaim and rebalance
    # share one disruption-budget pool per PodGroup.
    for uid, n_new in plan.budgets.items():
        row = m.j_row.get(uid, -1)
        pg = m.j_pg[row] if row >= 0 else None
        if (ledger.disrupted(store, uid) + n_new
                > max_unavailable_of(pg)):
            count_plan(cyc, plan.action, "rejected-budget",
                       gang=plan.gang_uid, victims=len(victim_rows))
            return
    ev = cyc._evict_machinery()
    st = ev.st
    events = []
    reason = ("Rebalance" if plan.action == "rebalance"
              else plan.action.capitalize())
    for row, tgt in zip(victim_rows.tolist(),
                        victim_nodes.tolist()):
        st.evict(int(row), None)
        st.evicted_rows.append(int(row))
        tgt_name = (m.n_name[int(tgt)]
                    if 0 <= int(tgt) < cyc.Nn else "")
        # Journey: the victim's timeline shows the planned target so
        # the later restore stitch reads as one migration.
        cyc._journey_event(int(row), "migration-planned",
                           detail=tgt_name)
        ledger.register(m.p_uid[row],
                        m.j_uid[int(cyc.jobr[row])], tgt_name,
                        action=plan.action,
                        for_gang=plan.gang_uid)
        events.append((
            f"Pod/{m.p_key[row]}", reason,
            f"evicted for gang {plan.gang_uid} "
            f"({plan.action} what-if plan"
            + (f", planned node {tgt_name})" if tgt_name else ")"),
        ))
    ledger.committed_plans += 1
    # Evictions moved mirror state: an overlapping solve dispatch must
    # re-validate (same stamp the host-walk actions apply).  Eviction
    # COUNTERS are bumped at the cycle-end evictor DISPATCH
    # (EvictState.flush), not here — a failed dispatch reverts the
    # victim, and a counter bumped at commit would overstate evictions
    # that never happened.
    m.mutation_seq += 1
    store.record_events_deferred(events)
    count_plan(cyc, plan.action, "committed", gang=plan.gang_uid,
               need=plan.need, victims=len(victim_rows),
               drain_nodes=len(plan.drain_nodes),
               frag=round(plan.frag_before, 4))


# ------------------------------------------------------------ accounting


def count_plan(cyc, action: str, outcome: str, **info) -> None:
    """Fold a plan outcome into the counter series and the cycle's
    flight-recorder accounting.  A cycle can see TWO outcomes — a
    pipelined plan voiding at the top AND a same-cycle re-plan — so
    earlier outcomes are preserved under ``prior`` (the record and the
    Prometheus counters must agree on totals).  Rebalance keeps its
    historical ``volcano_rebalance_plans_total`` series alongside the
    engine-wide ``volcano_whatif_plans_total``."""
    metrics.whatif_plans.inc(action=action, outcome=outcome)
    if action == "rebalance":
        metrics.rebalance_plans.inc(outcome=outcome)
        key = "rebalance"
        was = cyc.stats.get(key)
        d = {"outcome": outcome, **info}
    else:
        key = "whatif"
        # The cycle's numbers (``_plan_evict`` opens them) go on with
        # its newest outcome; what is left of the block, if anything,
        # is an earlier outcome.
        was = cyc.stats.get(key) or {}
        n = {k: was.pop(k, 0) for k in WALK_COUNTS}
        n["committed"] += outcome == "committed"
        n["rejected"] += outcome.startswith("rejected")
        if outcome == "committed":
            n["victims"] += info.get("victims", 0)
        d = {"action": action, "outcome": outcome, **info, **n}
    if was:
        d["prior"] = was.pop("prior", []) + [was]
    cyc.stats[key] = d


# --------------------------------------------------- streaks / backoffs


def _streak_maps(store) -> Tuple[dict, dict]:
    streaks = getattr(store, "_whatif_streaks", None)
    if streaks is None:
        streaks = store._whatif_streaks = {}
    backoff = getattr(store, "_whatif_backoff", None)
    if backoff is None:
        backoff = store._whatif_backoff = {}
    return streaks, backoff


def update_streaks(store, action: str, uids) -> Tuple[dict, dict]:
    """Per-(action, gang) starvation streaks + rejection backoffs,
    mirroring the rebalance lane's: a gang must stay starved across
    consecutive passes (pipelined cycles see starvation one commit
    behind), and a rejected plan cools the gang down instead of
    re-paying the kernel + what-if every cycle.  Leaving the starved
    set clears both."""
    streaks, backoff = _streak_maps(store)
    live = {(action, uid) for uid in uids}
    for key in list(streaks):
        if key[0] == action and key not in live:
            del streaks[key]
    for key in live:
        streaks[key] = streaks.get(key, 0) + 1
    for key in list(backoff):
        if key[0] != action:
            continue
        if key not in live:
            del backoff[key]
        elif backoff[key] > 0:
            backoff[key] -= 1
    return streaks, backoff


def set_backoff(store, action: str, uid: str, passes: int) -> None:
    if action == "rebalance":
        # The rebalance lane keeps its historical per-uid backoff map
        # (cleared by its own streak bookkeeping).
        backoff = getattr(store, "_rebalance_backoff", None)
        if backoff is None:
            backoff = store._rebalance_backoff = {}
        backoff[uid] = passes
        return
    _, backoff = _streak_maps(store)
    backoff[(action, uid)] = passes


# ------------------------------------------------------------- planners


def _starved_candidates(cyc):
    """Session job rows that are schedulable-but-unready gangs (same
    gate the rebalance planner uses)."""
    m = cyc.m
    srows = np.asarray(cyc.session_jobs, np.int64)
    if not len(srows):
        return srows
    mask = (
        (cyc.j_phase[srows] != 1)  # Inqueue gate, as _schedulable_rows
        & (cyc.j_cnt_pending[srows] > 0)
        & (cyc.j_ready_base[srows] < m.j_minav[srows])
        & (cyc.j_valid[srows] >= m.j_minav[srows])
        & (cyc.q_of_job[srows] >= 0)
    )
    return srows[mask]


def _gang_profile_table(cyc, jrow: int):
    """(gang_rows, [Up, R] init-request table) of a gang's pending
    non-best-effort tasks, profile-deduped and pow2-padded exactly as
    the rebalance planner builds it (all-zero pad rows are inert)."""
    from .fastpath import _pow2

    m = cyc.m
    Pn = cyc.Pn
    from .api import TaskStatus

    st_pending = int(TaskStatus.Pending)
    pend = np.flatnonzero(
        m.p_alive[:Pn] & (m.p_status[:Pn] == st_pending)
        & ~m.p_be[:Pn] & (cyc.jobr == jrow)
    )
    if not len(pend):
        return pend, None
    gang_rows = pend[np.argsort(m.p_create[pend], kind="stable")]
    _, first = np.unique(m.p_prof[gang_rows], return_index=True)
    urows = gang_rows[np.sort(first)]
    Up = _pow2(max(len(urows), 1), 4)
    prof_req = np.zeros((Up, cyc.R), F)
    er, si, v = m.c_init_req.gather(urows)
    prof_req[er, si] = v
    return gang_rows, prof_req


class VictimTable:
    """What a plan try reads of the Running residents, the same for
    every gang: the base victim rows BEFORE tier gating (Running
    residents with requests, not critical (conformance), without
    required inter-pod terms — their drain patches resident-derived
    counts conservatively — of a known job; a gang's own among them,
    which its try masks in ``v_ok``), ``victim_scores``'s padded columns
    and queue planes over them and ``may_be_eligible``'s reduction.  It
    serves one ``(cycle, m.mutation_seq)``: ``commit_plan`` stamps the
    counter when it evicts, so ``reclaim`` after a ``preempt`` that
    committed builds its own."""

    # holds: _lock
    def __init__(self, cyc):
        from .api import TaskStatus
        from .fastpath import _pow2
        from .ops.victim import queue_min_prio

        m, Pn = cyc.m, cyc.Pn
        self.seq = m.mutation_seq
        vict = np.flatnonzero(
            cyc.resident[:Pn]
            & (m.p_status[:Pn] == int(TaskStatus.Running))
            & ~m.p_critical[:Pn] & ~m.p_has_ip[:Pn] & (cyc.jobr >= 0))
        self.vict = vict = vict[m.c_req.lens(vict) > 0].astype(np.int64)
        V, Qp = len(vict), _pow2(max(cyc.Qn, 1), 4)
        Vp = _pow2(V)

        def padded(values, dtype, fill=0):
            out = np.full(Vp, fill, dtype)
            out[:V] = values
            return out

        vjobs = cyc.jobr[vict].astype(np.int64)
        vq = cyc.q_of_job[vjobs]
        self.vjobs = padded(vjobs, np.int64, -1)
        # A victim whose job has no known queue (q_of_job == -1: its
        # queue was deleted) has no share to gate on — excluded at the
        # base level rather than letting the kernel's index clip alias
        # it onto queue 0 (the oracle requires 0 <= q < Q the same way).
        self.v_ok = padded(vq >= 0, bool)
        self.v_jprio = padded(m.j_prio[vjobs], I)
        # Creation rank: larger = younger (evicted first among equals).
        crank = np.empty(V, I)
        crank[np.argsort(m.p_create[vict], kind="stable")] = np.arange(V)
        self.v_crank = padded(crank, I)
        self.v_tie = np.arange(Vp, dtype=I)
        self.v_queue = padded(vq, I)
        self.v_node = padded(m.p_node[:Pn][vict], I)
        self.v_req = np.zeros((Vp, cyc.R), F)
        er, si, vv = m.c_req.gather(vict)
        self.v_req[er, si] = vv
        self.q_alloc = np.zeros((Qp, cyc.R), F)
        self.q_alloc[:cyc.Qn] = cyc.q_alloc
        self.q_deserved = np.full((Qp, cyc.R), 3.0e38, F)
        self.q_deserved[:cyc.Qn] = cyc.q_deserved
        self.q_rec = np.zeros(Qp, bool)
        for name, qi in cyc.queue_index.items():
            q = cyc.store.queues.get(name)
            self.q_rec[qi] = bool(q is not None and q.reclaimable())
        self.node_zero = np.zeros((_pow2(max(cyc.Nn, 1)), cyc.R), F)
        self.q_minprio = queue_min_prio(
            self.v_ok, self.v_jprio, self.v_queue, Qp)


# holds: _lock
def _plan_evict(cyc, action: str) -> Optional[WhatIfPlan]:
    """Plan one preempt/reclaim wave: pick the starved gang, score and
    rank victims with the jitted kernel (ops/victim.py), select under
    budgets, and return the plan for the what-if solve to prove."""
    from .ops import victim as vk

    m = cyc.m
    store = cyc.store
    # Deferred aggregate scatters (same-cycle bind charges) must land
    # before ANY queue-share read below — the overuse gate and the
    # deserved-slack selection would otherwise see understated
    # allocations for queues the allocate action just charged.
    cyc._flush_aggr()
    cand = _starved_candidates(cyc)
    is_reclaim = action == "reclaim"
    q_share_host = None
    if is_reclaim and len(cand):
        q_share_host = vk.queue_shares(cyc.q_alloc, cyc.q_deserved)
        # Reclaim serves queues still UNDER their deserved share; a
        # gang in an overused queue must preempt within it instead.
        under = q_share_host[cyc.q_of_job[cand]] <= 1.0 + vk.SHARE_TOL
        cand = cand[under]
    uids = [m.j_uid[int(r)] for r in cand]
    streaks, backoff = update_streaks(store, action, uids)
    if not len(cand):
        return None
    need_streak = 2 if cyc._pipeline_on else 1
    ledger = store.migrations
    needs = (m.j_minav[cand] - cyc.j_ready_base[cand]).astype(np.int64)
    prios = m.j_prio[cand].astype(np.int64)
    # Highest-priority gang first (the point of preemption), then the
    # largest shortfall, then the lowest row for determinism.
    order = np.lexsort((cand, -needs, -prios))
    # The block is there in every cycle whose action walks its starved
    # gangs, whether or not a plan comes of it.
    cyc.stats.setdefault("whatif", dict.fromkeys(WALK_COUNTS, 0))
    with cyc.tracer.span(f"{action}_plan", cat="whatif"):
        for r in cand[order]:
            jrow = int(r)
            uid = m.j_uid[jrow]
            if streaks.get((action, uid), 0) < need_streak \
                    or backoff.get((action, uid), 0) > 0:
                continue
            if ledger is not None and ledger.wave_pending(store, uid):
                # A prior wave for this gang is still freeing capacity
                # (victims terminating); re-planning now would double-
                # evict for the same need.
                continue
            cyc.stats["whatif"]["gangs_tried"] += 1
            plan = _plan_evict_gang(cyc, action, jrow)
            if plan is not None:
                return plan
    return None


# holds: _lock
def _plan_evict_gang(cyc, action: str, jrow: int) -> Optional[WhatIfPlan]:
    """One gang's plan, in three phases under the action's plan span:
    ``plan:victims`` (the victim table fetched or built, the host gate
    and, past it, the gang's profile table), ``plan:scores`` (the kernel
    and the wait for its planes) and ``plan:select`` (where a victim is
    eligible: the host greedy and what follows it)."""
    import jax

    from .actions.rebalance import BudgetsLeft
    from .ops import victim as vk

    m, span = cyc.m, cyc.tracer.span
    mode = np.int32(vk.RECLAIM if action == "reclaim" else vk.PREEMPT)
    with span("plan:victims", cat="whatif"):
        need = int(m.j_minav[jrow] - cyc.j_ready_base[jrow])
        if need <= 0:
            return None
        tbl = cyc._victim_table
        if tbl is None or tbl.seq != m.mutation_seq:
            tbl = cyc._victim_table = VictimTable(cyc)
            cyc.stats["whatif"]["tables_built"] += 1
        gang_prio = np.int32(m.j_prio[jrow])
        gang_queue = np.int32(cyc.q_of_job[jrow])
        if not vk.may_be_eligible(tbl.q_minprio, tbl.q_rec, mode,
                                  gang_prio, gang_queue):
            return None
        gang_rows, prof_req = _gang_profile_table(cyc, jrow)
        if prof_req is None:
            return None
    with span("plan:scores", cat="whatif"):
        cyc.stats["whatif"]["kernel_calls"] += 1
        # Never the starved gang itself: its own rows leave ``v_ok``
        # (ineligible either way: its own priority, its own queue).
        planes = vk.victim_scores(
            tbl.v_ok & (tbl.vjobs != jrow), tbl.v_jprio, tbl.v_crank,
            tbl.v_tie, tbl.v_queue, tbl.v_node, tbl.v_req,
            gang_prio, gang_queue, tbl.q_alloc, tbl.q_deserved, tbl.q_rec,
            mode, tbl.node_zero,
        )
        eligible, order, evictable = jax.device_get(
            (planes.eligible, planes.order, planes.evictable))
    with span("plan:select", cat="whatif"):
        if not bool(eligible.any()):
            return None
        qa, qd = ((cyc.q_alloc, cyc.q_deserved) if action == "reclaim"
                  else (None, None))
        idle_p = tbl.node_zero.copy()
        idle_p[:cyc.Nn] = cyc.n_idle
        # A victim's group is its job row; pad rows are never reached.
        sel = vk.select_victims(
            order, eligible, tbl.v_node, tbl.v_req, tbl.vjobs,
            tbl.vjobs, tbl.v_queue, need, idle_p, evictable, prof_req,
            cyc.eps, cyc.j_ready_base, m.j_minav,
            BudgetsLeft(cyc.store, m), evict_cap(),
            q_alloc=qa, q_deserved=qd,
        )
        uid = m.j_uid[jrow]
        if not sel.feasible:
            if sel.budget_blocked:
                count_plan(cyc, action, "rejected-budget",
                           gang=uid, need=need)
            # Cooldown either way: no wave can form until the cluster
            # moves, so re-scoring every cycle is waste.
            set_backoff(cyc.store, action, uid,
                        cyc.REBALANCE_REJECT_BACKOFF)
            return None
        chosen = np.asarray(sel.chosen, np.int64)
        victim_jobs = tbl.vjobs[chosen]
        budgets: Dict[str, int] = {}
        for j in victim_jobs.tolist():
            g = m.j_uid[j]
            budgets[g] = budgets.get(g, 0) + 1
        return WhatIfPlan(
            action=action, gang_job=jrow, gang_uid=uid,
            gang_rows=gang_rows, victim_rows=tbl.vict[chosen],
            victim_jobs=victim_jobs,
            drain_nodes=np.zeros(0, np.int64), need=need,
            frag_before=0.0, budgets=budgets, resolve_victims=False,
        )


# holds: _lock
def run_evict_action(cyc, action: str) -> None:
    """The device-native preempt/reclaim lane body: plan, prove,
    commit (or park the proof for the next cycle's top).  One what-if
    wave is in flight at a time across ALL engine actions — the
    ``store._inflight_plan`` slot is shared."""
    store = cyc.store
    if store._inflight_plan is not None:
        return
    plan = _plan_evict(cyc, action)
    if plan is None:
        return
    dispatch_plan(cyc, plan)
