"""Victim-selection kernels: device-native preempt + reclaim scoring.

The host-side eviction walk (``fastpath_evict.py``) reproduces the
reference's sequential victim semantics exactly, but pays O(preemptor x
node) Python per cycle — the last hot lanes with no device lane at all.
This module is the planning half of the device-native alternative
(ISSUE 11, docs/preempt_reclaim.md): the what-if engine
(``volcano_tpu/whatif.py``) proves the resulting plan with the exact
allocate jit before anything is evicted.

- ``victim_scores`` — one jitted pass over the solver's existing planes
  (job priority, queue share = allocated/deserved, per-victim request
  rows, node ids) producing the tier-gated eligibility mask, the
  deterministic eviction order (an integer lexsort: job priority
  ascending, youngest victim first, input index tie-break — the same
  inverted task-order the host walk pops), and the per-node
  evictable-capacity plane (a scatter-add of eligible requests).
  Preempt gates victims to the preemptor's queue at strictly lower job
  priority; reclaim gates to OTHER queues that are ``Reclaimable`` and
  currently over their deserved share.  Critical (conformance-exempt)
  pods are excluded on both paths.
- ``queue_min_prio`` / ``may_be_eligible`` — the host gate a planner
  asks before it calls the kernel at all: whether ``eligible`` can be
  non-empty for a preemptor, from one reduction of the victim rows by
  queue.  Exact (integers and booleans); the share test is not in it.
- ``select_victims`` — the deterministic host-side greedy over the
  fetched planes: victims taken in kernel order, each charged against
  its PodGroup's remaining disruption budget and its job's gang floor
  (a victim whose eviction would push its job below ``minAvailable``
  is skipped unless ``minAvailable == 1``), reclaim victims
  additionally bounded by their queue's deserved-share slack
  (proportion semantics: a queue is never reclaimed below deserved).
  Selection stops once the freed capacity covers the starved gang's
  outstanding need (measured in whole gang tasks via the shared
  ``fit_counts`` spec) or the wave cap is hit.

``oracle.oracle_preempt`` / ``oracle.oracle_reclaim`` are the
deliberately naive Go-shaped re-derivations of both halves; tests
require exact agreement (tests/test_whatif_preempt.py).
"""

from __future__ import annotations

from typing import (Dict, Hashable, List, Mapping, NamedTuple, Optional,
                    Sequence)

import jax
import jax.numpy as jnp
import numpy as np

F = np.float32
I = np.int32

# Sentinel above which a deserved slot means "uncapped" (matches the
# 3.0e38 fill FastCycle._proportion writes for capless queues/slots).
DESERVED_UNCAPPED = 1.0e30
# Relative tolerance on the overuse test (f32 share arithmetic).
SHARE_TOL = 1e-6

PREEMPT = 0
RECLAIM = 1


class VictimPlanes(NamedTuple):
    """Fetched-together kernel outputs (device arrays until fetched)."""

    eligible: jnp.ndarray   # [V] bool tier-gated victim mask
    order: jnp.ndarray      # [V] i32 eviction order (eligible first)
    evictable: jnp.ndarray  # [N, R] f32 per-node eligible request sum
    q_share: jnp.ndarray    # [Q] f32 queue share = max alloc/deserved


def queue_shares(q_alloc: np.ndarray, q_deserved: np.ndarray) -> np.ndarray:
    """[Q] share plane from the cycle's queue planes: max over capped
    slots of allocated/deserved (0 when no slot is capped).  Host-side
    mirror of the kernel's formula so planners can pre-gate targets
    without a device round trip."""
    q_alloc = np.asarray(q_alloc, F)
    q_des = np.asarray(q_deserved, F)
    capped = q_des < DESERVED_UNCAPPED
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(capped, q_alloc / np.maximum(q_des, 1e-9), 0.0)
    return ratio.max(axis=-1).astype(F) if ratio.size else \
        np.zeros(len(q_alloc), F)


@jax.jit
def victim_scores(v_ok, v_jprio, v_crank, v_tie, v_queue, v_node, v_req,
                  p_prio, p_queue, q_alloc, q_deserved, q_reclaimable,
                  mode, node_zero):
    """Tier-gated victim eligibility + eviction order + evictable plane.

    ``v_ok``: [V] bool base validity (Running resident, non-empty
    request, not critical, job known, not the starved gang itself —
    the conformance tier and the structural filters, precomputed
    host-side); ``v_jprio``/``v_crank``/``v_tie``: [V] i32 job
    priority, creation rank (larger = younger) and deterministic
    tie-break; ``v_queue``/``v_node``: [V] i32; ``v_req``: [V, R] f32;
    ``p_prio``/``p_queue``: scalars for the preemptor gang;
    ``q_alloc``/``q_deserved``: [Q, R] f32 queue planes (the share is
    derived in-kernel — the queue-share tier reads the same planes the
    proportion plugin gates on); ``q_reclaimable``: [Q] bool;
    ``mode``: 0 = preempt, 1 = reclaim; ``node_zero``: [N, R] f32 zeros
    template fixing the scatter shape.

    Ineligible rows sort to the tail of ``order``; within the eligible
    prefix the order is (job priority asc, creation rank desc, tie
    asc) — lowest-priority youngest victims evict first, matching the
    host walk's inverted task-order pop.
    """
    v_ok = v_ok.astype(bool)
    v_jprio = v_jprio.astype(jnp.int32)
    capped = q_deserved < jnp.float32(DESERVED_UNCAPPED)
    ratio = jnp.where(capped,
                      q_alloc / jnp.maximum(q_deserved, 1e-9), 0.0)
    q_share = jnp.max(ratio, axis=-1).astype(jnp.float32)  # [Q]
    vq = jnp.clip(v_queue, 0, q_share.shape[0] - 1)
    same_q = v_queue == p_queue
    lower_prio = v_jprio < p_prio
    overused = q_share[vq] > jnp.float32(1.0 + SHARE_TOL)
    eligible = jnp.where(
        mode == PREEMPT,
        v_ok & same_q & lower_prio,
        v_ok & ~same_q & q_reclaimable[vq] & overused,
    )
    big = jnp.int32(np.iinfo(np.int32).max)
    prio_key = jnp.where(eligible, v_jprio, big)
    order = jnp.lexsort(
        (v_tie, -v_crank, prio_key, (~eligible).astype(jnp.int32))
    ).astype(jnp.int32)
    evictable = node_zero.at[jnp.clip(v_node, 0, node_zero.shape[0] - 1)]\
        .add(jnp.where(eligible[:, None], v_req, 0.0))
    return VictimPlanes(eligible=eligible, order=order,
                        evictable=evictable, q_share=q_share)


# ``queue_min_prio`` of a queue without a valid victim row.
NO_ROW = np.iinfo(np.int64).max


def queue_min_prio(v_ok: np.ndarray, v_jprio: np.ndarray,
                   v_queue: np.ndarray, n_queues: int) -> np.ndarray:
    """[Q] i64 least job priority over each queue's ``v_ok`` rows,
    ``NO_ROW`` where it has none: all that ``may_be_eligible`` reads of
    the victim rows, one reduction for every preemptor."""
    out = np.full(n_queues, NO_ROW)
    ok = np.asarray(v_ok, bool)
    np.minimum.at(out, v_queue[ok], v_jprio[ok])
    return out


def may_be_eligible(q_minprio: np.ndarray, q_reclaimable: np.ndarray,
                    mode: int, p_prio: int, p_queue: int) -> bool:
    """Host gate before ``victim_scores``, in integer and boolean
    compares alone: False only where the kernel's ``eligible`` is empty
    for this preemptor.  Preempt needs a valid row of its queue at
    strictly lower job priority; reclaim a valid row in ANOTHER queue
    that is Reclaimable.  Whether that queue is over its share (a float32
    division) stays the kernel's to say: ``queue_shares`` is no part of
    the verdict."""
    if mode == PREEMPT:
        return bool(q_minprio[p_queue] < p_prio)
    return bool(np.delete((q_minprio < NO_ROW) & q_reclaimable,
                          p_queue).any())


def fit_counts(plane: np.ndarray, prof_req: np.ndarray,
               eps: np.ndarray) -> np.ndarray:
    """[N] whole gang tasks each node row of ``plane`` can host: per
    (node, profile) the min over requested slots of
    ``floor((plane + eps) / req)`` (0 when the profile requests
    nothing), max over profiles — the same fit spec as
    ``ops.rebalance.frag_scores`` so the two planners agree on what "a
    freed slot" means."""
    plane = np.atleast_2d(np.asarray(plane, F))
    req = np.asarray(prof_req, F)
    eps = np.asarray(eps, F)
    requested = req > eps[None, :]  # [U, R]
    per = np.floor(
        (plane[:, None, :] + eps[None, None, :])
        / np.maximum(req[None, :, :], 1e-9)
    )
    per = np.where(requested[None, :, :], per, np.float32(2 ** 30))
    cnt = per.min(axis=-1)
    cnt = np.where(requested.any(axis=-1)[None, :], cnt, 0.0)
    return np.maximum(cnt, 0.0).max(axis=-1).astype(np.int64)


class VictimSelection(NamedTuple):
    """``select_victims`` verdict (host-side, deterministic)."""

    chosen: List[int]      # indices into the victim arrays, evict order
    feasible: bool         # freed capacity covers the need
    budget_blocked: bool   # budgets (not capacity/cap) blocked the plan
    gain: int              # gang tasks the chosen drain frees


def select_victims(
    order: np.ndarray,
    eligible: np.ndarray,
    v_node: np.ndarray,
    v_req: np.ndarray,
    v_job: np.ndarray,
    v_group: Sequence[Hashable],
    v_queue: np.ndarray,
    need: int,
    idle: np.ndarray,
    evictable: np.ndarray,
    prof_req: np.ndarray,
    eps: np.ndarray,
    j_ready: np.ndarray,
    j_minav: np.ndarray,
    budget_left: Mapping[Hashable, int],
    cap: int,
    q_alloc: Optional[np.ndarray] = None,
    q_deserved: Optional[np.ndarray] = None,
) -> VictimSelection:
    """Greedy ranked-victim selection under disruption budgets.

    Walks victims in kernel ``order``; a victim is taken iff its node
    can gain gang capacity at all (draining every eligible victim there
    beats the node's as-is fit), its job stays at/above
    ``minAvailable`` after the eviction (or ``minAvailable == 1`` —
    the gang tier), its PodGroup's remaining budget covers one more
    disruption, and (reclaim, ``q_alloc``/``q_deserved`` given) its
    queue's share stays at/above deserved after the eviction — a queue
    is never reclaimed below its deserved share.  Gain is
    measured in whole gang tasks (``fit_counts``); selection stops at
    ``need`` covered or ``cap`` victims.  Victims on nodes whose final
    fit never improved are pruned (their slot never completed — the
    eviction would free nothing the gang can use).  Mutates none of its
    inputs.

    ``v_group`` names each victim's PodGroup by any hashable key (a uid,
    or the job row itself: the planner passes ``v_job``).  Of
    ``budget_left`` only ``get(group, 0)`` is called, and only where a
    victim comes to the budget test, so a mapping that works a group's
    budget out when asked pays for the groups the walk meets.
    """
    order = np.asarray(order, np.int64)
    eligible = np.asarray(eligible, bool)
    v_node = np.asarray(v_node, np.int64)
    v_req = np.asarray(v_req, F)
    v_job = np.asarray(v_job, np.int64)
    idle = np.asarray(idle, F)
    ev = np.asarray(evictable, F)

    # Ineligible rows are sorted to the tail of ``order``: the walk is
    # over the eligible prefix, and only that much becomes a list.
    ranked = order[:int(np.count_nonzero(eligible))].tolist()
    touched = np.unique(v_node[eligible])
    base = fit_counts(idle[touched], prof_req, eps)
    drained = fit_counts(idle[touched] + ev[touched], prof_req, eps)
    fit0: Dict[int, int] = dict(zip(touched.tolist(), base.tolist()))
    gain_ok = set(touched[drained > base].tolist())

    def walk(left):
        """One greedy pass; ``left(group)`` is the group's budget before
        it, ``spent`` what the pass has charged."""
        freed: Dict[int, np.ndarray] = {}
        cur_fit: Dict[int, int] = {}
        occupancy: Dict[int, int] = {}
        spent: Dict[Hashable, int] = {}
        qa = None if q_alloc is None else np.array(q_alloc, F)
        chosen: List[int] = []
        gain = 0
        skipped_budget = False
        for idx in ranked:
            if gain >= need or len(chosen) >= cap:
                break
            n = int(v_node[idx])
            if n not in gain_ok:
                continue
            j = int(v_job[idx])
            cnt = occupancy.get(j)
            if cnt is None:
                cnt = int(j_ready[j]) if 0 <= j < len(j_ready) else 0
            minav = int(j_minav[j]) if 0 <= j < len(j_minav) else 1
            if not (minav <= cnt - 1 or minav == 1):
                continue  # gang tier: job would drop below minAvailable
            g = v_group[idx]
            if left(g) - spent.get(g, 0) < 1:
                skipped_budget = True
                continue
            if qa is not None:
                # Proportion tier: the victim queue must stay AT or
                # ABOVE its deserved share after the eviction — the
                # same share metric the kernel's overuse gate reads.
                # Unknown queues (defensive: eligibility already
                # excludes them) are never reclaimable.
                q = int(v_queue[idx])
                if not 0 <= q < len(qa):
                    continue
                after = queue_shares(
                    (qa[q] - v_req[idx])[None, :],
                    q_deserved[q][None, :])[0]
                if after < 1.0 - SHARE_TOL:
                    continue  # queue would drop below deserved
                qa[q] = qa[q] - v_req[idx]
            occupancy[j] = cnt - 1
            spent[g] = spent.get(g, 0) + 1
            f = freed.get(n)
            if f is None:
                f = freed[n] = np.zeros(v_req.shape[1], F)
            old = cur_fit.get(n, fit0[n])
            f += v_req[idx]
            new = int(fit_counts(idle[n] + f, prof_req, eps)[0])
            cur_fit[n] = new
            gain += new - old
            chosen.append(idx)
        # Prune whole nodes whose fit never improved: every victim
        # taken there freed a partial slot the gang cannot use.
        dead = {n for n in freed
                if cur_fit.get(n, fit0[n]) <= fit0[n]}
        if dead:
            chosen = [i for i in chosen if int(v_node[i]) not in dead]
        return chosen, gain, skipped_budget

    chosen, gain, skipped = walk(lambda g: budget_left.get(g, 0))
    if gain >= need:
        return VictimSelection(chosen=chosen, feasible=True,
                               budget_blocked=False, gain=gain)
    blocked = False
    if skipped:
        # Label the outcome honestly: budgets blocked the plan only if
        # the same greedy with unlimited budgets (same cap, same gang
        # floors, same queue slack) would have covered the need.
        _, ugain, _ = walk(lambda g: 1 << 30)
        blocked = ugain >= need
    return VictimSelection(chosen=[], feasible=False,
                           budget_blocked=blocked, gain=gain)
