"""Wave-batched allocate solver: W tasks per device iteration.

The sequential solver (``ops/allocate.py``) preserves Volcano's exact
per-task semantics but pays one device loop iteration per task — at
BASELINE's north-star shape (10k nodes x 100k pending pods) that is 100k
sequential steps and over ten seconds of device time.  This module trades a
small, documented amount of ordering fidelity for two orders of magnitude:
tasks are processed in *waves* of W (task order preserved across and within
waves), and each wave resolves with batched feasibility/score tensors plus
an O(W^2) prefix-acceptance pass that lands on the MXU as tiny matmuls.

**Profile dedup.** Pending pods are overwhelmingly replicas: a gang of 64
identical tasks shares one request vector, one node-selector bitset, one
affinity term set.  The expensive [*, N] tensors (resource fit, scores,
ports, affinity) are therefore computed once per *distinct task profile*
present in the wave (host-side ``np.unique`` over the per-task rows), and
every task just gathers its profile's row — the same collapse the array
schema performs on the reference's O(tasks x nodes x predicates) fan-out
(scheduler_helper.go:43-118), applied a second time within the solve.

Semantics relative to ``pkg/scheduler/actions/allocate/allocate.go:40-250``
(and to the sequential solver, which mirrors it step-for-step):

- predicates/scores for the tasks of one wave are evaluated against the
  cluster state at the start of the wave *attempt*, not after every single
  placement.  Within an attempt, capacity is still charged exactly, in task
  order, via per-node prefix sums: a task is only accepted if the requests
  of every earlier accepted wave-task on its chosen node still leave room.
  Tasks that lose the race re-enter the next attempt, where scores are
  recomputed on the updated state; each attempt is guaranteed to resolve at
  least the first unresolved task, so the attempt loop terminates.
- choice diversification: when many tasks of a wave argmax to the same
  node, the k-th contender is steered to its profile's k-th-best feasible
  node (scaled by how many replicas the best node can still hold).  The
  sequential reference reaches the same nodes one fill at a time (best node
  saturates, scores shift to the runner-up); the wave solver just gets
  there without serializing.  Tie-break stays lowest-node-index.
- gang discard (stmt.Discard, statement.go:324-367) is applied as one
  vectorized rollback after the scan instead of at each job boundary, so
  capacity held by a doomed job is not released to later jobs within the
  same solve call.  The allocate action re-runs the solver on the remaining
  pending tasks when any job was discarded (``actions/allocate.py``),
  which restores the freed capacity for the next pass — the same "later
  jobs see post-discard state" outcome, one round later.
- queue-overuse gating (proportion.go:217-229) is evaluated when the job's
  first task comes up in its wave, against live queue allocations at that
  attempt — the same point in task order where the reference evaluates it.
- a task with no feasible node marks its job fit-failed and aborts the
  job's remaining tasks (allocate.go:189-193): in-wave, later tasks of that
  job are masked from this attempt's acceptance and from every later
  attempt; tasks of the job accepted in earlier attempts stay (they are
  rolled back at the end unless the job still reached ready).

Everything else — epsilon resource semantics, pipeline (future-idle)
accounting surviving discard, port/pod-count/label/taint/inter-pod-affinity
predicates, additive scoring — is identical to the sequential solver, and
the two agree exactly on conflict-free workloads (tests/test_wave.py).

Bitset predicates (node selector / required+preferred node affinity /
taints / host ports) are evaluated as f32 matmuls over the unpacked bit
axis: "row bits all present in table row" == "popcount(row & ~table) == 0",
and the popcount of an AND is an inner product of 0/1 vectors — which puts
the predicate fan-out on the MXU instead of the vector units.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..arrays.affinity import AffinityArgs, count_entries_of
from .allocate import (
    NEG,
    AllocResult,
    SolveJobs,
    SolveNodes,
    SolveQueues,
    SolveTasks,
)
from .nodeclass import NodeClasses
from .resreq import less_equal
from .scoring import ScoreWeights, node_score

import functools as _functools
import math as _math
import os as _os
import time as _time


def _env_int(name: str, default: int) -> int:
    try:
        return int(_os.environ.get(name, default))
    except ValueError:
        return default


DEFAULT_WAVE = 2048
# cnt0 tables above this element count ship as sparse entries and are
# scattered on device (tests lower it to force the sparse path).
CNT0_SPARSE_MIN = 4_000_000
# Same for each profile-term table ([U, Ep]): past this element count
# the four tables ship as one sparse entry list.
PROF_SPARSE_MIN = 1_000_000
# diversification breadth: k-th contender takes its k-th best node
TOPK = _env_int("VOLCANO_TPU_TOPK", 256)
# In-attempt re-walk rounds for conflict losers.  Default 4: measured
# best at the north-star affinity mix in rounds 3 AND 4 (16 costs more
# per-attempt sub-round machinery than the attempt-count reduction it
# buys; acceptance stays exact either way — sub-rounds only change how
# much conflict retry happens inside one ranking).
SUBROUNDS = 4
# Flattened (term x domain) scatter keys index an [EW * D + 1] buffer
# with int32 device arithmetic (jax's default index width).  At the
# 100k-node x 1M-pod tier the PRODUCT crosses 2^31 while each axis
# stays far below it, so past this bound the conflict/count machinery
# switches to 2-D (term, domain) indexing — identical values,
# overflow-free.  Env-overridable so the 2-D form is exercised (and
# parity-tested) at small shapes.
def _keyspace_max() -> int:
    try:
        return int(
            _os.environ.get("VOLCANO_TPU_KEYSPACE_MAX", 2**31 - 2)
        )
    except ValueError:
        return 2**31 - 2


# The has_aff solve reads its count window at (term, the node's domain
# under the term's key): cv[n, e] = cnt[e, node_dom[n, key(e)]], by
# ``count_plane`` below from all three of its callers (the full-N and
# shortlist feasibility planes, the sub-round conflict filter): K row
# gathers from the window laid domain-major.  0.15 ms a call on a v5e
# chip for hyper-50k's 16,384 nodes x 128 terms a chip, where one
# scalar gather an element, which XLA serializes on TPU, is 2.1 M reads
# and 17.5 ms (PERF.md section 5, PR 39); 0.13 ms at affinity-10k's
# D = 10,016.
def count_plane(cnt, node_dom, term_key):
    """``cv[r, e] = cnt[e, node_dom[r, term_key[e]]]``, and 0 where that
    domain is -1: a count window ``cnt`` ``[E, D]`` read at every
    (row, term) for a set of node rows ``node_dom`` ``[R, K]`` (each
    row's domain id under each of the mirror's ``K`` topology keys) and
    the window's ``term_key`` ``[E]``.

    A term has one key, so for the terms of key ``k`` the domain index
    is the same ``[R]`` vector ``node_dom[:, k]``: the plane is ``K``
    gathers of whole rows of the window laid domain-major (``[D, E]``,
    ``E`` on the lanes), each kept where the term's key is ``k``, and
    not ``R x E`` scalar reads.  ``K`` is static.  Integer in, integer
    out, and every element is the one ``cnt[arange(E)[None, :],
    node_dom[:, term_key]]`` picks, so a consumer (``cv == 0``,
    ``cv > 0``, the soft score's matmul) sees bit-identical input.
    It needs nothing of the counts: a term counted outside its own
    key's domains is still read under its own key only."""
    cnt_t = cnt.T  # [D, E]
    cv = jnp.zeros((node_dom.shape[0], cnt.shape[0]), cnt.dtype)
    for k in range(node_dom.shape[1]):
        nd_k = node_dom[:, k]
        rows = jnp.take(cnt_t, jnp.maximum(nd_k, 0), axis=0)  # [R, E]
        cv = jnp.where(
            (term_key[None, :] == k) & (nd_k[:, None] >= 0), rows, cv
        )
    return cv

# ---- two-phase device solve (node-class compaction + shortlists) -----
# Phase 1 (coarse) collapses the node table into node classes and
# evaluates the static predicate planes once per (profile x class) in
# bf16, then ranks every node ONCE per solve on the initial state and
# keeps each profile's top-S candidates as a shortlist.  Phase 2 (fine)
# runs the attempt/sub-round wave machinery on the [UM, S] shortlist
# planes instead of [UM, N]; a profile whose shortlist has no live
# feasible candidate falls back to a full-N rescore for that attempt
# (counted per reason), so binding is never lost to pruning — the
# TPU-native analog of the reference's percentageOfNodesToFind sampling
# (scheduler_helper.go:37-62).  The knobs are read per call, so a test
# can flip them between two solves of one process.
def _two_phase_on() -> bool:
    return _os.environ.get("VOLCANO_TPU_TWOPHASE", "1") != "0"


def _fallback_cap() -> int:
    """Max shortlist-fallback rescores per solve (0 = unlimited)."""
    try:
        return max(0, int(_os.environ.get("VOLCANO_TPU_FB_CAP", 0)))
    except ValueError:
        return 0


def shortlist_size(n: int) -> int:
    """Phase-2 shortlist length per profile.  VOLCANO_TPU_TOPK pins it
    explicitly; the default mirrors the reference's adaptive
    percentageOfNodesToFind (50 - N/125 percent, floor 5%, at least 100
    nodes — scheduler_helper.go:37-62) and never drops below the walk
    ranking depth TOPK, so attempt-1 rankings keep their full prefix."""
    raw = _os.environ.get("VOLCANO_TPU_TOPK")
    if raw:
        try:
            return max(1, min(n, int(raw)))
        except ValueError:
            pass
    pct = max(5, 50 - n // 125)
    return min(n, max(100, TOPK, n * pct // 100))


# Coarse phase profile-chunk size: bounds the [chunk, N, R] fit
# broadcast (the only [*, N, R] tensor of the coarse pass) so hyperscale
# profile counts stream through lax.map instead of materializing
# [U, N, R] at once.
COARSE_CHUNK = 256

# Telemetry of the most recent two-phase solve on this host (the cycle
# driver folds it into the device_coarse/device_fine sub-lanes and the
# flight recorder; tests read the shortlist shape).  Keys: enabled,
# coarse_s, fine_s, shortlist ((U, S) or None), n_nodes,
# compacted_classes (bool: real class planes vs per-node identity),
# mesh_shards (effective node-axis shard count of the rankings; 1 off
# a mesh).
LAST_TWOPHASE: dict = {"enabled": False}


class SolveProfiles(NamedTuple):
    """Distinct task profiles ([U] rows): every per-task input that shapes
    the [*, N] feasibility/score tensors.  Tasks map to profiles via
    ``pid``; waves gather their present profiles via ``wave_prof``."""

    req: jnp.ndarray  # [U, R]
    init_req: jnp.ndarray  # [U, R]
    ports: jnp.ndarray  # [U, PW] uint32
    sel_bits: jnp.ndarray  # [U, LW]
    aff_bits: jnp.ndarray  # [U, A, LW]
    aff_terms: jnp.ndarray  # [U]
    tol_bits: jnp.ndarray  # [U, TW]
    pref_bits: jnp.ndarray  # [U, AP, LW]
    pref_w: jnp.ndarray  # [U, AP]
    t_req_aff: jnp.ndarray  # [U, E]
    t_req_anti: jnp.ndarray  # [U, E]
    t_matches: jnp.ndarray  # [U, E]
    t_soft: jnp.ndarray  # [U, E]


class ProfileTermEntries(NamedTuple):
    """The four ``[U, E]`` profile-term tables of ``SolveProfiles`` as
    the entries they are: one per (profile, term) cell that is nonzero
    in any of them, in (profile, term) order, no cell twice."""

    rows: np.ndarray  # [n] int32 profile row
    cols: np.ndarray  # [n] int32 term
    flags: np.ndarray  # [n] int8: t_req_aff | t_req_anti << 1 | t_matches << 2
    soft: np.ndarray  # [n] float32 t_soft
    shape: tuple  # (U, E) of the tables they stand for


class SparseProfiles(NamedTuple):
    """``SolveProfiles`` on its way from the fast path's encode to
    ``solve_wave`` (host only, never a jit argument): the nine small
    per-profile fields as rows, the four term tables as ``terms``.  A
    profile references its own gang's one or two terms, so the tables
    are a few thousand entries in ``U x E`` cells (10 M at 10,000 nodes
    x 100,000 pods); ``solve_wave`` has them born on the device."""

    req: np.ndarray
    init_req: np.ndarray
    ports: np.ndarray
    sel_bits: np.ndarray
    aff_bits: np.ndarray
    aff_terms: np.ndarray
    tol_bits: np.ndarray
    pref_bits: np.ndarray
    pref_w: np.ndarray
    terms: ProfileTermEntries


class GState(NamedTuple):
    """Cluster state threaded through waves and attempts."""

    idle: jnp.ndarray  # [N, R]
    pip_extra: jnp.ndarray  # [N, R]
    ntasks: jnp.ndarray  # [N] int32
    pip_ntasks: jnp.ndarray  # [N]
    nport_bits: jnp.ndarray  # [N, B] bool (unpacked, alloc side)
    pip_nport_bits: jnp.ndarray  # [N, B] bool
    cnt_alloc: jnp.ndarray  # [E, D] int32
    cnt_pip: jnp.ndarray  # [E, D] int32
    q_alloc: jnp.ndarray  # [Q, R]
    q_pip: jnp.ndarray  # [Q, R]
    alloc_cnt: jnp.ndarray  # [J] int32
    fit_failed: jnp.ndarray  # [J] bool
    job_skip: jnp.ndarray  # [J] bool (fit abort OR overuse skip)
    job_overskip: jnp.ndarray  # [J] bool (skipped for overuse only)
    assigned: jnp.ndarray  # [P] int32
    pipelined: jnp.ndarray  # [P] int32
    iters: jnp.ndarray  # [] int32 total attempt iterations
    fb_exhausted: jnp.ndarray  # [] int32 shortlist-fallback rescores
    fb_affinity: jnp.ndarray  # [] int32 ... for required-affinity profiles
    fb_rounds: jnp.ndarray  # [] int32 fallback rescore ROUNDS (cap unit)
    # [] int32 count-plane recomputes (``count_plane``: attempts that
    # found a count changed, plus fallback rescores of a live wave);
    # None in a trace without ``has_aff``, whose program it must not
    # touch.
    aff_reads: jnp.ndarray = None


def _unpack_bits(words):
    """[..., W] uint32 -> [..., W*32] bool, bit 0 of word 0 first."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], -1).astype(bool)


def _subset_mm(rows_bits, table_missing_f):
    """rows ⊆ table per pair, as a matmul.

    rows_bits: [..., B] bool; table_missing_f: [N, B] f32 of ~table.
    Result [..., N] bool: no bit of the row falls on a missing table bit.
    """
    viol = jnp.matmul(rows_bits.astype(jnp.float32), table_missing_f.T)
    return viol == 0


def _subset_mm_bf(rows_bits, table_missing_bf):
    """bf16 variant of ``_subset_mm`` for the coarse class planes: the
    products are 0/1 and the verdict reads ==0 vs >=1 — a bf16-rounded
    sum of non-negative integers can never land in (0, 0.5), so the
    classification is exact (the _aff_parts indicator argument) at ~4x
    the MXU rate."""
    viol = jnp.matmul(
        rows_bits.astype(jnp.bfloat16), table_missing_bf.T
    )
    return viol < 0.5


def _class_static(cls: NodeClasses, sel_bits, aff_bits, aff_terms,
                  tol_bits, pref_bits, pref_w, naff_weight,
                  has_taints: bool):
    """Phase-1 coarse planes: static (label/taint/ready) feasibility and
    preferred-affinity score once per (profile-row x node CLASS).

    Inputs are packed word rows for ``Ub`` profiles; result is
    ``(ok [Ub, C] bool, score [Ub, C] f32)``.  Class members share the
    static node planes byte-for-byte (nodeclass.build_node_classes), so
    expanding through ``class_id`` reproduces the node-level masks
    exactly; the bf16 indicator matmuls are exact for the ==0 / >=1
    classification and the score sums the exact booleans in f32, so the
    expanded score matches the node-level computation bit-for-bit."""
    bf = jnp.bfloat16
    f32 = jnp.float32
    Ub = sel_bits.shape[0]
    A = aff_bits.shape[1]
    AP = pref_bits.shape[1]
    C = cls.ready.shape[0]
    missing_bf = (~_unpack_bits(cls.label_bits)).astype(bf)  # [C, B]
    ok = cls.ready[None, :] & _subset_mm_bf(
        _unpack_bits(sel_bits), missing_bf
    )
    term_ok = _subset_mm_bf(
        _unpack_bits(aff_bits).reshape(Ub * A, -1), missing_bf
    ).reshape(Ub, A, C)
    term_real = jnp.arange(A)[None, :] < aff_terms[:, None]  # [Ub, A]
    ok &= (
        jnp.any(term_ok & term_real[:, :, None], axis=1)
        | (aff_terms == 0)[:, None]
    )
    if has_taints:
        untol = jnp.matmul(
            _unpack_bits(cls.taint_bits).astype(bf),
            (~_unpack_bits(tol_bits)).astype(bf).T,
        )  # [C, Ub]
        ok &= untol.T < 0.5
    pref_match = _subset_mm_bf(
        _unpack_bits(pref_bits).reshape(Ub * AP, -1), missing_bf
    ).reshape(Ub, AP, C)
    score = naff_weight * jnp.sum(
        pref_match.astype(f32) * pref_w[:, :, None], axis=1
    )
    return ok, score


def _identity_classes(nodes: SolveNodes) -> NodeClasses:
    """Per-node identity classes derived from the node planes (the
    automatic path when no compacted class planes were supplied): every
    node is its own class, so the class-axis machinery applies with the
    static matmuls staying at node granularity."""
    N = nodes.idle.shape[0]
    return NodeClasses(
        class_id=jnp.arange(N, dtype=jnp.int32),
        label_bits=nodes.label_bits,
        taint_bits=nodes.taint_bits,
        ready=nodes.ready,
    )


@partial(jax.jit, static_argnames=("chunk", "has_taints",
                                   "cls_identity"))
def _static_planes(nodes: SolveNodes, prof: SolveProfiles,
                   cls: NodeClasses, naff_weight, chunk: int,
                   has_taints: bool, cls_identity: bool):
    """Separately-jitted producer of the [U, C] static planes (ISSUE 9
    persistent statics): ``_class_static`` over the WHOLE padded profile
    table, cached across solves by ``ops/devincr.DeviceIncremental``
    keyed on (class-table content sig, profile content generation,
    epoch-relevant bits) — steady-state solves then skip static
    evaluation entirely, both in the coarse pass and per wave.

    Rows are computed independently (the matmuls contract over the bit
    axis only), so gathering rows of this result is bit-identical to
    calling ``_class_static`` on the gathered rows in-kernel — the
    property the DEVINCR=0 parity contract rests on.  Profiles stream
    through ``lax.map`` in ``chunk`` rows like the coarse pass."""
    if cls_identity:
        cls = _identity_classes(nodes)
    U = prof.sel_bits.shape[0]

    def body(rowset):
        sel_bits, aff_bits, aff_terms, tol_bits, pref_bits, pref_w = \
            rowset
        return _class_static(
            cls, sel_bits, aff_bits, aff_terms, tol_bits, pref_bits,
            pref_w, naff_weight, has_taints,
        )

    cols = (prof.sel_bits, prof.aff_bits, prof.aff_terms,
            prof.tol_bits, prof.pref_bits, prof.pref_w)
    if chunk >= U:
        return body(cols)
    resh = tuple(
        a.reshape(U // chunk, chunk, *a.shape[1:]) for a in cols
    )
    ok, sc = jax.lax.map(body, resh)
    C = ok.shape[-1]
    return ok.reshape(U, C), sc.reshape(U, C)


def _hier_pin() -> int:
    """The pinned ``VOLCANO_TPU_TOPK_BLOCKS`` value (0 = adaptive).
    Read OUTSIDE the jits — ``solve_wave`` resolves it per call and
    threads it through as a static argument, so flipping the knob
    in-process actually re-specializes the kernels (an env read at
    trace time would silently hit the jit cache instead)."""
    try:
        return max(0, int(_os.environ.get("VOLCANO_TPU_TOPK_BLOCKS",
                                          "0")))
    except ValueError:
        return 0


def _hier_blocks(n: int, k: int, n_shards: int = 1,
                 pin: Optional[int] = None) -> int:
    """Block count of the hierarchical block->shard->global top-k for
    an [*, n] ranking (trace-static; n, k, n_shards are static inside
    every caller's jit).

    ``pin`` is the resolved ``VOLCANO_TPU_TOPK_BLOCKS`` (0 = adaptive;
    ``None`` reads the env — only sound for EAGER callers, jitted
    callers must thread ``solve_wave``'s static through).  A pinned
    count is pow2-clamped to a divisor of ``n`` (1 disables the block
    stage).  The adaptive default engages the block stage only when
    each shard's node slice is large and the ranking depth is a small
    fraction of it — one top_k over [*, n] at 100k+ nodes sorts the
    whole plane, while per-block top_k + the winner merge sorts ~k
    rows per block.  Blocks are sized toward TOPK_BLOCK_ROWS (pow2,
    floor 4 * k so the merged candidate set stays well under n)."""
    n_sh = max(1, n_shards)
    if pin is None:
        pin = _hier_pin()
    if pin:
        p = 1
        while p * 2 <= pin:
            p *= 2
        nb = max(p, n_sh)
        while nb > n_sh and n % nb:
            nb //= 2
        if n % nb:
            # The pinned count (and the shard count) do not divide the
            # node axis: the global form is both correct and what GSPMD
            # would fall back to anyway.
            return 1
        return max(nb, 1)
    if n < TOPK_HIER_MIN or k * 4 > n // max(n_sh, 1):
        return max(n_sh, 1)
    rows = TOPK_BLOCK_ROWS
    while rows < 4 * k:
        rows *= 2
    nb = max(n_sh, 1)
    while n % (nb * 2) == 0 and n // nb > rows:
        nb *= 2
    return nb


# Node-axis thresholds of the adaptive hierarchical selection (see
# _hier_blocks): below TOPK_HIER_MIN nodes a single top_k wins; above,
# blocks aim at TOPK_BLOCK_ROWS rows each.
TOPK_HIER_MIN = _env_int("VOLCANO_TPU_TOPK_HIER_MIN", 65536)
TOPK_BLOCK_ROWS = 8192


def _merge_block_cands(cand_s, cand_i, k: int, n_shards: int = 1):
    """Merge per-block (score, global node id) candidate lists into the
    global top-``k`` id set — the shard->global tail of the
    block->shard->global hierarchy (arxiv 2002.07062's tiling, applied
    to the selection reduce).

    ``cand_s``/``cand_i`` are [U, B, klb] with blocks ascending-id node
    ranges and each block's list in local rank order.  When the blocks
    subdivide ``n_shards`` mesh shards evenly, the merge runs in two
    stages: a SHARD-LOCAL reduce of each shard's blocks (zero
    cross-chip traffic), then the cross-chip winner reduction over the
    [U, n_shards * min(k, ...)] survivors — communication stays at the
    two-stage form's volume no matter how many blocks subdivide a
    shard.  Otherwise one flat reduce over [U, B * klb].

    The result is EXACTLY the top-k of the blocks' union with
    ``jax.lax.top_k`` tie-breaking (lower node id first): within a
    block, equal-score candidates sit in ascending-id order (top_k's
    own tie-break); blocks (and shards) concatenate in ascending-id
    range order; every merge stage's top_k prefers the earlier
    position — so within any score class, position order is ascending
    node id order at every stage."""
    U, B, klb = cand_s.shape
    if n_shards > 1 and B > n_shards and B % n_shards == 0:
        bps = B // n_shards
        ksh = min(k, bps * klb)
        sh_s = cand_s.reshape(U, n_shards, bps * klb)
        sh_i = cand_i.reshape(U, n_shards, bps * klb)
        ms, pos = jax.lax.top_k(sh_s, ksh)  # shard-local block merge
        mi = jnp.take_along_axis(sh_i, pos, axis=2)
        flat_s = ms.reshape(U, n_shards * ksh)
        flat_i = mi.reshape(U, n_shards * ksh)
    else:
        flat_s = cand_s.reshape(U, B * klb)
        flat_i = cand_i.reshape(U, B * klb)
    kf = min(k, flat_s.shape[1])
    _s, pos = jax.lax.top_k(flat_s, kf)  # cross-chip winner reduction
    out = jnp.take_along_axis(flat_i, pos, axis=1)
    if kf < k:
        # Degenerate: fewer candidates than k (tiny blocks).  Pad by
        # repeating the last winner — callers either never hit this
        # (klb == min(k, nlb) keeps B*klb >= k whenever N >= k) or
        # tolerate duplicate trailing ids.
        out = jnp.concatenate(
            [out, jnp.broadcast_to(out[:, -1:], (U, k - kf))], axis=1
        )
    return out


def _topk_nodes(scores, k: int, n_shards: int = 1,
                pin: Optional[int] = None):
    """Top-``k`` node ids per profile row — hierarchical
    block->shard->global under a mesh and/or at large node counts.
    ``pin`` threads the resolved TOPK_BLOCKS static from jitted
    callers (see ``_hier_pin``); eager callers may leave it None.

    ``scores`` is [U, N] with the node axis optionally sharded over
    ``n_shards`` mesh devices.  The selection runs in up to three
    stages (each optional, all exact):

    1. per-BLOCK top_k inside each shard's slice (``_hier_blocks``
       picks the block count; blocks are ascending-id node ranges, so
       the reshape keeps every block within its owning shard and the
       stage runs with zero communication) — at the 100k-node tier this
       replaces one full-plane sort with ~k-deep sorts per block;
    2. a shard-local merge of each shard's block candidates;
    3. the cross-chip winner reduction over (score, global node id)
       pairs — the only cross-device communication (arxiv 2002.07062).

    The result is EXACTLY ``jax.lax.top_k(scores, k)``: a global top-k
    element is necessarily a top-k element of its own block (a block
    can contribute at most min(k, block_rows) winners), and the
    tie-break matches because candidate positions order by (block,
    local rank) — ascending node id within any score class at every
    stage (see ``_merge_block_cands``).
    """
    U, N = scores.shape
    if n_shards > 1 and N % n_shards:
        n_shards = 1
    nb = _hier_blocks(N, k, n_shards, pin)
    if nb <= 1 or N % nb:
        _s, idx = jax.lax.top_k(scores, k)
        return idx.astype(jnp.int32)
    nlb = N // nb
    klb = min(k, nlb)
    loc = scores.reshape(U, nb, nlb)
    loc_s, loc_i = jax.lax.top_k(loc, klb)  # block-local ranking
    gid = loc_i.astype(jnp.int32) + (
        jnp.arange(nb, dtype=jnp.int32) * nlb
    )[None, :, None]
    return _merge_block_cands(loc_s, gid, k, n_shards)


def _key_cols(key_rows, prof: SolveProfiles, extra_prof, score_prof,
              *stat):
    """What a shortlist pass reads of a profile row, at the key rows
    ([K, ...] each): the thirteen profile columns, the custom plugins'
    verdict and score rows ([K, 1] fillers where a solve has none) and
    the rows of the static planes a caller hands in."""
    K = key_rows.shape[0]
    return tuple(a[key_rows] for a in prof) + (
        jnp.ones((K, 1), bool) if extra_prof is None
        else extra_prof[key_rows],
        jnp.zeros((K, 1), jnp.float32) if score_prof is None
        else score_prof[key_rows],
    ) + tuple(a[key_rows] for a in stat)


@partial(jax.jit, static_argnames=("sl_k", "chunk", "features",
                                   "cnt0_any", "cls_identity",
                                   "mesh_shards", "n_blocks",
                                   "with_cand", "static_ext",
                                   "hier_pin"))
def _coarse_shortlist(nodes: SolveNodes, prof: SolveProfiles, extra_prof,
                      score_prof, cls: NodeClasses, aff: AffinityArgs,
                      weights: ScoreWeights, eps, scalar_slot,
                      key_rows, key_of,
                      sl_k: int, chunk: int, features: tuple,
                      cnt0_any: bool, cls_identity: bool,
                      mesh_shards: int = 1, n_blocks: int = 1,
                      with_cand: bool = False, static_ext: bool = False,
                      stat_ok=None, stat_score=None, hier_pin: int = 0):
    """Phase 1 + shortlist selection of the two-phase solve.

    Evaluates the wave-0-attempt-1 live mask + score for every KEY row
    over all N nodes ONCE (class-compacted statics, initial dynamic
    state) and keeps each key's top-``sl_k`` candidates.  A key row is
    one of the profile rows that differ in what this pass reads of them
    (``shortlist_keys``): ``key_rows`` [K] names one profile row per
    key, ``key_of`` [U] each profile row's key.  Rows are evaluated
    independently, so a row's ranking is its key's, and the result is
    ``sl_keys[key_of]``: the ``[U, sl_k]`` int32 node ids, each row
    sorted ASCENDING, that ranking every profile row would give, bit
    for bit — in-shortlist rankings then break score ties by node index
    exactly like the full path's top_k.  The masks are evaluated at
    solve-start state, which
    within a solve only loses capacity/ports/pod slots and only gains
    affinity counts — so a node pruned here stays infeasible for every
    non-required-affinity feature, and required-affinity drift is what
    the fine phase's fallback rescore exists for.

    When ``cnt0_any`` is False the inter-pod planes are skipped: with
    all-zero counts both the required/anti verdicts and the soft score
    are uniform per profile, and per-profile-uniform components cannot
    change top-k membership (a uniformly infeasible profile exhausts its
    shortlist on attempt 1 and resolves through the fallback rescore,
    reaching the identical no-node outcome).

    Key rows stream through ``lax.map`` in ``chunk`` rows so the
    [chunk, N, R] fit broadcast — the pass's only [*, N, R] tensor —
    bounds device memory at hyperscale key counts.

    ``mesh_shards`` > 1 (the node axis is sharded over that many mesh
    devices) makes the candidate selection shard-local: each chip ranks
    only its own node slice and the per-key winners reduce across
    chips as (score, global node id) pairs (``_topk_nodes``) — the
    shortlist membership is bit-identical to the single-device pass.

    ``with_cand`` (the device-incremental lane, ISSUE 9) restructures
    the selection into per-block top-k + winner merge over ``n_blocks``
    ascending-id node blocks and ALSO returns the per-block candidate
    lists ``(cand_s [K, B, klb], cand_i [K, B, klb])``, per key row —
    the warm-start state ``_warm_shortlist`` patches on later solves.
    The selected
    SET is identical to the direct top-k (a global top-k element is a
    top-k element of its own block, and candidate positions order by
    (block, local rank) — ascending node id within any score class, the
    ``_topk_nodes`` argument), and the returned shortlist sorts
    ascending, so the array is bit-identical either way.  ``static_ext``
    takes the (profile x class) static planes as PARAMS (``stat_ok`` /
    ``stat_score`` [U, C], their key rows threaded through the key
    stream) instead of evaluating ``_class_static`` in-kernel.
    """
    (has_ports, has_aff, has_taints, has_future, _has_overuse,
     has_extra, has_extra_score) = features
    f32 = jnp.float32
    bf = jnp.bfloat16
    N = nodes.idle.shape[0]
    K = key_rows.shape[0]
    chunk = min(chunk, K)
    if cls_identity:
        cls = _identity_classes(nodes)
    # Initial dynamic node state, shared by every chunk.
    if has_future:
        fi0 = nodes.idle + nodes.releasing - nodes.pipelined
    else:
        fi0 = nodes.idle
    pods_ok0 = (nodes.max_tasks <= 0) | (nodes.ntasks < nodes.max_tasks)
    if has_ports:
        nport_bf = _unpack_bits(nodes.ports).astype(bf)  # [N, B]
    if has_aff and cnt0_any:
        cv0 = count_plane(aff.cnt0, aff.node_dom, aff.term_key)  # [N, E]
        total0 = jnp.sum(aff.cnt0, axis=-1)  # [E]
        cv0_zero_bf = (cv0 == 0).astype(bf)
        cv0_pos_bf = (cv0 > 0).astype(bf)
        cv0_f = cv0.astype(f32)

    def body(rowset):
        (req, init_req, ports, sel_bits, aff_bits, aff_terms, tol_bits,
         pref_bits, pref_w, t_req_aff, t_req_anti, t_matches, t_soft,
         e_ok, e_score) = rowset[:15]
        if static_ext:
            # Persistent static planes (ISSUE 9): the chunk's rows of
            # the externally-produced [U, C] planes — bit-identical to
            # the in-kernel evaluation (rows are computed
            # independently; see _static_planes).
            ok_c, score_c = rowset[15], rowset[16]
        else:
            ok_c, score_c = _class_static(
                cls, sel_bits, aff_bits, aff_terms, tol_bits, pref_bits,
                pref_w, weights.node_affinity_weight, has_taints,
            )
        feas = ok_c[:, cls.class_id]  # [u, N] expand
        static_score = score_c[:, cls.class_id]
        if has_extra:
            feas &= e_ok
        if has_extra_score:
            static_score = static_score + e_score
        fit = less_equal(
            init_req[:, None, :], fi0[None, :, :], eps, scalar_slot
        )
        feas &= fit & pods_ok0[None, :]
        if has_ports:
            p_bits = _unpack_bits(ports)
            clash = jnp.matmul(p_bits.astype(bf), nport_bf.T)
            feas &= ~jnp.any(p_bits, axis=-1)[:, None] | (clash < 0.5)
        score = jax.vmap(node_score, in_axes=(0, None, None, None))(
            req, nodes.allocatable, nodes.idle, weights
        ) + static_score
        if has_aff and cnt0_any:
            selfok = (total0 == 0)[None, :] & t_matches  # [u, E]
            need = (t_req_aff & ~selfok).astype(bf)
            aff_viol = jnp.matmul(need, cv0_zero_bf.T)
            anti_viol = jnp.matmul(t_req_anti.astype(bf), cv0_pos_bf.T)
            feas &= (aff_viol < 0.5) & (anti_viol < 0.5)
            score = score + jnp.matmul(t_soft, cv0_f.T)
        masked = jnp.where(feas, score, NEG)
        if with_cand:
            # Per-block top-k + hierarchical winner merge (ISSUE 9 +
            # the 100k-node tier): identical membership to the direct
            # top-k (see the docstring), the block candidates become
            # the warm-start state, and under a mesh the merge reduces
            # shard-local before the cross-chip winner reduction
            # (_merge_block_cands — blocks subdivide shards because
            # the caller keeps n_blocks a multiple of the shard
            # count).
            u_ = masked.shape[0]
            nlb = N // n_blocks
            klb = min(sl_k, nlb)
            loc_s, loc_i = jax.lax.top_k(
                masked.reshape(u_, n_blocks, nlb), klb
            )
            gid = loc_i.astype(jnp.int32) + (
                jnp.arange(n_blocks, dtype=jnp.int32) * nlb
            )[None, :, None]
            idx = _merge_block_cands(loc_s, gid, sl_k, mesh_shards)
            return (jnp.sort(idx, axis=1).astype(jnp.int32), loc_s, gid)
        # Shard-local ranking + cross-chip winner reduction under a
        # mesh; identical membership to a global top_k (see _topk_nodes).
        idx = _topk_nodes(masked, sl_k, mesh_shards, hier_pin)
        return jnp.sort(idx, axis=1).astype(jnp.int32)

    cols = _key_cols(
        key_rows, prof,
        extra_prof if has_extra else None,
        score_prof if has_extra_score else None,
        *((stat_ok, stat_score) if static_ext else ()),
    )
    if chunk >= K:
        out = body(cols)
    else:
        out = jax.lax.map(body, tuple(
            a.reshape(K // chunk, chunk, *a.shape[1:]) for a in cols
        ))
    if with_cand:
        sl, cand_s, cand_i = out
        klb = cand_s.shape[-1]
        return (sl.reshape(K, sl_k)[key_of],
                cand_s.reshape(K, n_blocks, klb),
                cand_i.reshape(K, n_blocks, klb))
    return out.reshape(K, sl_k)[key_of]


@partial(jax.jit, static_argnames=("sl_k", "klb", "nlb", "chunk",
                                   "features", "cnt0_any",
                                   "cls_identity", "static_ext",
                                   "mesh_shards"))
def _warm_shortlist(nodes: SolveNodes, prof: SolveProfiles, extra_prof,
                    score_prof, cls: NodeClasses, aff: AffinityArgs,
                    weights: ScoreWeights, eps, scalar_slot,
                    key_rows, key_of,
                    stat_ok, stat_score, db_rows, cand_s, cand_i,
                    sl_k: int, klb: int, nlb: int, chunk: int,
                    features: tuple, cnt0_any: bool, cls_identity: bool,
                    static_ext: bool, mesh_shards: int = 1):
    """Warm-started shortlist selection (ISSUE 9): re-rank ONLY the node
    blocks whose rows are in the cycle's dirty set, patch their
    candidates into the carried per-block lists, and merge winners.

    ``db_rows`` is the [ndb] list of dirty block ids (padded with
    duplicates of the first — the scatter rewrites identical values, so
    padding is idempotent); ``cand_s``/``cand_i`` are the previous
    solve's per-block candidates, per key row ([K, B, klb], produced by
    ``_coarse_shortlist`` with ``with_cand`` or by an earlier warm
    pass over the same ``key_rows``).  The caller
    (``ops/devincr.DeviceIncremental``) proves every node OUTSIDE the
    dirty blocks has byte-identical solve inputs to the previous solve,
    and that the key rows are the previous solve's, so a key row's
    retained candidates equal what a fresh
    ranking would produce and the merged shortlist is bit-identical to
    a full ``_coarse_shortlist`` over today's state.  Same formulas as
    the coarse body, evaluated on the gathered dirty-block node rows
    ([K, ndb*nlb] instead of [K, N]).

    Returns ``(shortlists [U, sl_k], cand_s, cand_i)``: the key rows'
    shortlists at ``key_of``, and the updated candidates, the next
    solve's warm state."""
    (has_ports, has_aff, has_taints, has_future, _has_overuse,
     _has_extra, _has_extra_score) = features
    f32 = jnp.float32
    bf = jnp.bfloat16
    N = nodes.idle.shape[0]
    K = key_rows.shape[0]
    chunk = min(chunk, K)
    if cls_identity:
        cls = _identity_classes(nodes)
    ndb = db_rows.shape[0]
    rows = (
        db_rows[:, None] * nlb
        + jnp.arange(nlb, dtype=jnp.int32)[None, :]
    ).reshape(-1)  # [M] global node ids of the dirty blocks
    # Gathered node-side solve-start state (row subsets of the same
    # planes the coarse pass reads — values bitwise equal per node).
    idle_r = nodes.idle[rows]
    if has_future:
        rel = nodes.releasing
        rel_r = rel[rows] if rel.shape[0] == N else rel
        pip = nodes.pipelined
        pip_r = pip[rows] if pip.shape[0] == N else pip
        fi0_r = idle_r + rel_r - pip_r
    else:
        fi0_r = idle_r
    mt_r = nodes.max_tasks[rows]
    pods_ok0_r = (mt_r <= 0) | (nodes.ntasks[rows] < mt_r)
    cid_r = cls.class_id[rows]
    alloc_r = nodes.allocatable[rows]
    if has_ports:
        nport_bf_r = _unpack_bits(nodes.ports[rows]).astype(bf)
    if has_aff and cnt0_any:
        cv0_r = count_plane(
            aff.cnt0, aff.node_dom[rows], aff.term_key
        )  # [M, E]
        total0 = jnp.sum(aff.cnt0, axis=-1)
        cv0_zero_bf = (cv0_r == 0).astype(bf)
        cv0_pos_bf = (cv0_r > 0).astype(bf)
        cv0_f = cv0_r.astype(f32)

    def body(rowset):
        (req, init_req, ports, sel_bits, aff_bits, aff_terms, tol_bits,
         pref_bits, pref_w, t_req_aff, t_req_anti, t_matches,
         t_soft) = rowset[:13]
        if static_ext:
            ok_c, score_c = rowset[15], rowset[16]
        else:
            ok_c, score_c = _class_static(
                cls, sel_bits, aff_bits, aff_terms, tol_bits, pref_bits,
                pref_w, weights.node_affinity_weight, has_taints,
            )
        feas = ok_c[:, cid_r]  # [u, M] expand at the dirty rows
        static_score = score_c[:, cid_r]
        fit = less_equal(
            init_req[:, None, :], fi0_r[None, :, :], eps, scalar_slot
        )
        feas &= fit & pods_ok0_r[None, :]
        if has_ports:
            p_bits = _unpack_bits(ports)
            clash = jnp.matmul(p_bits.astype(bf), nport_bf_r.T)
            feas &= ~jnp.any(p_bits, axis=-1)[:, None] | (clash < 0.5)
        score = jax.vmap(node_score, in_axes=(0, None, None, None))(
            req, alloc_r, idle_r, weights
        ) + static_score
        if has_aff and cnt0_any:
            selfok = (total0 == 0)[None, :] & t_matches
            need = (t_req_aff & ~selfok).astype(bf)
            aff_viol = jnp.matmul(need, cv0_zero_bf.T)
            anti_viol = jnp.matmul(t_req_anti.astype(bf), cv0_pos_bf.T)
            feas &= (aff_viol < 0.5) & (anti_viol < 0.5)
            score = score + jnp.matmul(t_soft, cv0_f.T)
        masked = jnp.where(feas, score, NEG)
        u_ = masked.shape[0]
        loc_s, loc_i = jax.lax.top_k(
            masked.reshape(u_, ndb, nlb), klb
        )
        gid = loc_i.astype(jnp.int32) + db_rows[None, :, None] * nlb
        return loc_s, gid

    # The coarse pass's columns (a warm solve has no custom-plugin rows:
    # the two fillers keep the layout one).
    cols = _key_cols(
        key_rows, prof, None, None,
        *((stat_ok, stat_score) if static_ext else ()),
    )
    if chunk >= K:
        s_new, i_new = body(cols)
    else:
        resh = tuple(
            a.reshape(K // chunk, chunk, *a.shape[1:]) for a in cols
        )
        s_new, i_new = jax.lax.map(body, resh)
        s_new = s_new.reshape(K, ndb, klb)
        i_new = i_new.reshape(K, ndb, klb)
    # Patch the dirty blocks' candidates (duplicate padded block ids
    # rewrite identical values — idempotent) and merge winners exactly
    # like the coarse pass's with_cand tail: block->shard->global under
    # a mesh, one flat reduce otherwise (_merge_block_cands).
    cand_s = cand_s.at[:, db_rows].set(s_new)
    cand_i = cand_i.at[:, db_rows].set(i_new)
    idx = _merge_block_cands(cand_s, cand_i, sl_k, mesh_shards)
    sl = jnp.sort(idx, axis=1).astype(jnp.int32)
    return sl[key_of], cand_s, cand_i


@partial(jax.jit, static_argnames=("wave", "n_waves", "ew", "features",
                                   "terms_disjoint", "two_phase",
                                   "cls_identity", "fb_cap",
                                   "mesh_shards", "static_ext",
                                   "hier_pin", "flat_keys", "has_bias"))
def _solve_wave(
    nodes: SolveNodes,
    tasks: SolveTasks,
    jobs: SolveJobs,
    queues: SolveQueues,
    weights: ScoreWeights,
    eps,
    scalar_slot,
    aff: AffinityArgs,
    prof: SolveProfiles,
    extra_prof: jnp.ndarray,  # [U, N] bool custom verdicts ([1,1] if unused)
    score_prof: jnp.ndarray,  # [U, N] f32 custom scores ([1,1] if unused)
    pid: jnp.ndarray,  # [P] int32 global profile id per task
    wave_prof: jnp.ndarray,  # [NW, U_MAX] int32 profile ids present per wave
    wave_terms: jnp.ndarray,  # [NW, EW] int32 term ids per wave (pad=dummy)
    cls: NodeClasses,  # class planes ([1]-dummies unless compacted)
    shortlists: jnp.ndarray,  # [U, S] int32 ([1, 1] unless two_phase)
    wave: int,
    n_waves: int,
    ew: int,
    features: tuple = (True, True, True, True, True, False, False),
    terms_disjoint: bool = False,
    two_phase: bool = False,
    cls_identity: bool = False,
    fb_cap: int = 0,
    mesh_shards: int = 1,
    static_ext: bool = False,
    stat_ok=None,  # [U, C] bool persistent static planes (ISSUE 9)
    stat_score=None,  # [U, C] f32
    hier_pin: int = 0,  # resolved TOPK_BLOCKS (0 = adaptive)
    flat_keys: bool = True,  # (term x domain) key space fits int32
    node_bias=None,  # [N] f32 additive node-order bias (topology)
    has_bias: bool = False,  # static: bias add traced only when real
) -> AllocResult:
    # Static feature flags let XLA drop whole subsystems from the program
    # when the snapshot provably cannot exercise them (no host ports
    # anywhere, no affinity terms, no taints, no releasing capacity =>
    # no pipelining, no finite queue deserved => no overuse gating).
    (has_ports, has_aff, has_taints, has_future, has_overuse,
     has_extra, has_extra_score) = features

    # Per-task solver state lives in job/real/pid only; req/init_req are
    # gathered from the profile rows on device (tasks sharing a pid have
    # identical inputs by contract), so callers ship [1, ...] dummies for
    # every other SolveTasks field — at the north-star shape that keeps
    # ~5 MB of per-task arrays out of every solve's host->device upload.
    P = tasks.job.shape[0]
    R = prof.req.shape[1]
    pid = pid.astype(jnp.int32)
    N = nodes.idle.shape[0]
    J = jobs.min_available.shape[0]
    A = prof.aff_bits.shape[1]
    AP = prof.pref_bits.shape[1]
    E, D = aff.cnt0.shape
    Q = queues.deserved.shape[0]
    W = wave
    NW = n_waves
    UM = wave_prof.shape[1]
    EW = ew
    S = shortlists.shape[1] if two_phase else N
    K = min(TOPK, S)
    # int32 index audit (the 100k x 1M tier): flattened (term, domain)
    # keys are only sound while EW * D + 1 fits the int32 device index
    # space; past the gate every keyed scatter/gather below runs in
    # its 2-D form.  The verdict arrives as the ``flat_keys`` STATIC —
    # resolved by solve_wave outside the jit (_keyspace_max is an env
    # read; reading it at trace time would pin the first verdict into
    # the jit cache).
    flat_keys_ok = flat_keys
    JP = J + W  # job axis padded so any wave's window slice stays in range
    f32 = jnp.float32
    BIG = jnp.float32(1.0e9)

    # The device inner loop avoids every large sort and every wide
    # scatter/gather it can:
    #  - nodes are *ranked once per wave* (argsort of the per-profile score
    #    rows); attempts walk down the fixed ranking by live cumulative
    #    capacity instead of re-sorting (TPU TopK/sort is millisecond-slow
    #    at [U, 16k]);
    #  - job- and queue-indexed state reads/writes are [W, W]/[W, Q]
    #    one-hot matmuls over the wave's contiguous job window (TPU
    #    scatters serialize per row);
    #  - a stalled attempt (no placement and no new skip) leaves the state
    #    bit-identical, so the loop exits; the unresolved tasks stay
    #    Pending for the cycle (see attempt_cond).

    node_ready = nodes.ready
    if two_phase:
        if cls_identity:
            # No compacted classes supplied (knob off, or device-resident
            # nodes without caller-built planes): every node is its own
            # class — the shortlist machinery still applies, the static
            # matmuls just stay at node granularity.
            cls = _identity_classes(nodes)
    else:
        # Unpacked-bit tables (f32 complements feed the matmul subset
        # checks) — the two-phase path evaluates these per CLASS instead.
        label_missing_f = (~_unpack_bits(nodes.label_bits)).astype(f32)
        node_taint_bits_f = _unpack_bits(nodes.taint_bits).astype(f32)

    # Padded-row job sentinel J keeps wave windows ([jlo, jlo+W)) in the
    # padded job range without branching.
    tjob = jnp.where(tasks.real, tasks.job.astype(jnp.int32), J)
    prev_job = jnp.concatenate([jnp.int32([-1]), tjob[:-1]])
    is_first = tasks.real & (tjob != prev_job)
    queue_p = jnp.pad(jobs.queue, (0, W))

    job_seen = jnp.zeros((JP,), bool).at[tjob].max(tasks.real)

    # With wave-disjoint term sets the global count tables are
    # loop-INVARIANT (no wave reads another wave's writes, so the
    # write-back is skipped); carrying the 164 MB-at-scale tables
    # through the fori_loop makes XLA rematerialize them from the
    # sparse cnt0 entries inside the loop (measured ~0.4 s/cycle).
    # Keep them out of the carry and gather windows straight from the
    # input instead.
    cnt0_i32 = aff.cnt0.astype(jnp.int32)
    state = GState(
        idle=nodes.idle,
        pip_extra=jnp.zeros_like(nodes.idle),
        ntasks=nodes.ntasks,
        pip_ntasks=jnp.zeros_like(nodes.ntasks),
        nport_bits=_unpack_bits(nodes.ports),
        pip_nport_bits=jnp.zeros_like(_unpack_bits(nodes.ports)),
        cnt_alloc=(jnp.zeros((1, 1), jnp.int32) if terms_disjoint
                   else cnt0_i32),
        cnt_pip=(jnp.zeros((1, 1), jnp.int32) if terms_disjoint
                 else jnp.zeros_like(cnt0_i32)),
        q_alloc=queues.allocated,
        q_pip=jnp.zeros_like(queues.allocated),
        alloc_cnt=jnp.zeros((JP,), jnp.int32),
        fit_failed=jnp.zeros((JP,), bool),
        job_skip=jnp.zeros((JP,), bool),
        job_overskip=jnp.zeros((JP,), bool),
        assigned=jnp.full((P,), -1, jnp.int32),
        pipelined=jnp.full((P,), -1, jnp.int32),
        iters=jnp.int32(0),
        fb_exhausted=jnp.int32(0),
        fb_affinity=jnp.int32(0),
        fb_rounds=jnp.int32(0),
        aff_reads=jnp.int32(0) if has_aff else None,
    )

    tril = jnp.tril(jnp.ones((W, W), bool), k=-1)  # strictly-earlier mask

    def run_wave(w, state: GState) -> GState:
        off = w * W
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, W, axis=0)

        jraw = sl(tjob)
        real_w = sl(tasks.real)
        is_first_w = sl(is_first)
        # Index of each task's profile in this wave's presence list,
        # recomputed on device: every pid in the wave appears in
        # wave_prof[w] by construction, so the equality argmax is exact
        # — and a [W, UM] compare on device replaces a [P] vector in
        # the upload.
        pid_w = sl(pid)
        pid_l = jnp.argmax(
            pid_w[:, None] == wave_prof[w][None, :], axis=1
        ).astype(jnp.int32)

        # Job window: job ids of a wave form a contiguous range (tasks are
        # job-contiguous), so job state lives in [W]-sized locals.
        jlo = jnp.min(jnp.where(real_w, jraw, J))
        jw = jnp.clip(jraw - jlo, 0, W - 1)
        onehot_j = (
            (jw[:, None] == jnp.arange(W)[None, :]) & real_w[:, None]
        ).astype(f32)  # [W_task, W_job]
        queue_l = jax.lax.dynamic_slice_in_dim(queue_p, jlo, W)
        onehot_ql = (queue_l[:, None] == jnp.arange(Q)[None, :]).astype(f32)
        onehot_jq = jnp.matmul(onehot_j, onehot_ql)  # [W_task, Q]
        if has_overuse:
            # [W, W]: task w' stands before task w in this wave and in
            # the same queue (the overuse gate's in-wave prefix).
            ahead_q = (
                (jnp.matmul(onehot_jq, onehot_jq.T) > 0) & tril
            ).astype(f32)
        onehot_u = (pid_l[:, None] == jnp.arange(UM)[None, :]).astype(f32)
        same_pid = pid_l[:, None] == pid_l[None, :]
        jsl = lambda a: jax.lax.dynamic_slice_in_dim(a, jlo, W, axis=0)

        # Profiles present in this wave ([UM] global rows).
        pids = wave_prof[w]  # [UM]
        p_req = prof.req[pids]
        p_init_req = prof.init_req[pids]
        p_req_pos = p_req > 0
        # Per-task requests, reconstructed from the wave's profile rows
        # ([W] gather from [UM, R]) instead of a shipped [P, R] table.
        req_w = p_req[pid_l]
        init_req_w = p_init_req[pid_l]
        if has_ports:
            p_ports = _unpack_bits(prof.ports[pids])  # [UM, B]
            p_has_ports = jnp.any(p_ports, axis=-1)
            ports_w = p_ports[pid_l]  # [W, B] per-task view
        if has_aff:
            # Term window: gather this wave's referenced terms (tasks are
            # job-contiguous, terms per-jobish), so every [*, E] tensor
            # below is bounded by terms-per-wave — the tiling that keeps
            # the affinity machinery scalable to 50k x 500k (SURVEY.md
            # section 7 hard parts).
            wterms = wave_terms[w]  # [EW], padded with the dummy row
            # Waves whose window is entirely dummy padding neither consult
            # nor change any term count (matched tasks put their terms in
            # the window too); the per-attempt [N, EW] gather and the
            # [UM, EW] x [EW, N] violation/score matmuls are lax.cond-
            # skipped for them — with sparse affinity, most waves.
            # E here includes the appended dummy row, whose index (the
            # wave_terms pad value) is E - 1.
            wave_live = jnp.any(wterms != E - 1)
            tk_w = aff.term_key[wterms]
            node_dom_t = jnp.take(aff.node_dom, tk_w, axis=1)  # [N, EW]
            term_arange = jnp.arange(EW)
            esl = lambda a: jnp.take(a, wterms, axis=1)
            p_t_req_aff = esl(prof.t_req_aff[pids])  # [UM, EW]
            p_t_req_anti = esl(prof.t_req_anti[pids])
            p_t_matches = esl(prof.t_matches[pids])
            p_t_soft = esl(prof.t_soft[pids])
            t_matches_w = p_t_matches[pid_l]  # [W, EW]
            # Terms some wave profile REQUIRES (affinity or anti): the
            # conflict machinery keys off this set (soft-only spread
            # terms never feed it).
            term_req_w = jnp.any(p_t_req_aff | p_t_req_anti, axis=0)


        # ---- static predicate masks, hoisted out of the attempt loop ----
        if two_phase:
            # Phase-1 coarse: one bf16 evaluation per (profile x CLASS),
            # expanded to nodes through the class_id gather.  Class
            # members share the static planes byte-for-byte, so the
            # expanded masks/scores equal the node-level computation
            # exactly; the [UM, B] x [B, C] matmuls replace [UM, B] x
            # [B, N] — the N/C compaction of the static fan-out.
            if static_ext:
                # Persistent static planes (ISSUE 9): the wave's rows
                # of the externally-produced [U, C] planes replace the
                # per-wave _class_static evaluation entirely — the
                # steady-state win of the device-incremental lane (rows
                # compute independently, so the gather is bit-identical
                # to the in-kernel evaluation).
                cls_ok = stat_ok[pids]
                cls_pref = stat_score[pids]
            else:
                cls_ok, cls_pref = _class_static(
                    cls, prof.sel_bits[pids], prof.aff_bits[pids],
                    prof.aff_terms[pids], prof.tol_bits[pids],
                    prof.pref_bits[pids], prof.pref_w[pids],
                    weights.node_affinity_weight, has_taints,
                )
            p_ok = cls_ok[:, cls.class_id]  # [UM, N]
            if has_extra:
                p_ok &= extra_prof[pids]
            p_static_score = cls_pref[:, cls.class_id]
            if has_extra_score:
                p_static_score = p_static_score + score_prof[pids]
        else:
            p_ok = node_ready[None, :] & _subset_mm(
                _unpack_bits(prof.sel_bits[pids]), label_missing_f
            )
            if has_extra:
                # Custom-plugin verdicts, per profile (tasks sharing a
                # profile share a mask row by construction).
                p_ok &= extra_prof[pids]
            aff_bits_p = _unpack_bits(prof.aff_bits[pids])  # [UM, A, B]
            term_ok = _subset_mm(
                aff_bits_p.reshape(UM * A, -1), label_missing_f
            ).reshape(UM, A, N)
            n_terms = prof.aff_terms[pids]
            term_real = jnp.arange(A)[None, :] < n_terms[:, None]  # [UM, A]
            p_ok &= (
                jnp.any(term_ok & term_real[:, :, None], axis=1)
                | (n_terms == 0)[:, None]
            )
            if has_taints:
                # Taints: any node taint bit not tolerated kills the pair.
                untol = jnp.matmul(
                    node_taint_bits_f,
                    (~_unpack_bits(prof.tol_bits[pids])).astype(f32).T,
                )  # [N, UM]
                p_ok &= untol.T == 0

            pref_bits_p = _unpack_bits(prof.pref_bits[pids])  # [UM, AP, B]
            pref_match = _subset_mm(
                pref_bits_p.reshape(UM * AP, -1), label_missing_f
            ).reshape(UM, AP, N)
            p_static_score = weights.node_affinity_weight * jnp.sum(
                pref_match * prof.pref_w[pids][:, :, None], axis=1
            )  # [UM, N]
            if has_extra_score:
                # Attempt-invariant: hoisted out of the attempt loop (XLA
                # does not hoist out of while_loops).
                p_static_score = p_static_score + score_prof[pids]

        if has_bias:
            # Topology node-order bias (ops/topology.contig_bias): an
            # additive plane over nodes, identical for every profile.
            # Folding it here covers the full-N ranking, the two-phase
            # shortlist gather (static_sl below), and the fb-counted
            # full-N fallback rescore in one place.  Gated by the
            # STATIC flag — not a `+ 0.0` — so biasless solves trace
            # the exact pre-topology program (bitwise: -0.0 + 0.0
            # flips a sign bit).
            p_static_score = p_static_score + node_bias[None, :].astype(f32)

        if two_phase:
            # Phase-2 hoists: the wave's shortlist window and every
            # static plane gathered down to it.  sl rows are ascending
            # node ids, so in-shortlist top_k tie-breaks by node index
            # exactly like the full path.
            sl_w = shortlists[pids]  # [UM, S]
            p_ok_sl = jnp.take_along_axis(p_ok, sl_w, axis=1)
            static_sl = jnp.take_along_axis(p_static_score, sl_w, axis=1)
            mt_sl = nodes.max_tasks[sl_w]  # [UM, S]
            alloc_sl = nodes.allocatable[sl_w]  # [UM, S, R]

        def live_parts(s: GState, cw_a, cw_p, aff_ok_c, aff_soft_c,
                       aff_dirty_a):
            """Per-attempt dynamic feasibility [UM, N].

            The inter-pod affinity planes (required/anti feasibility +
            soft-term score) depend ONLY on the wave's term counts, so
            they are carried across attempts and recomputed solely when
            a sub-round actually changed a count (aff_dirty_a): the
            [N, EW] domain gather over cnt[EW, D~N] and the
            [UM, EW] x [EW, N] matmuls — the dominant per-attempt cost
            at the affinity-mix north-star shape — run once per count
            change instead of once per attempt.  Exact: same values,
            fewer recomputes."""
            if has_future:
                future_idle = (
                    s.idle + nodes.releasing - nodes.pipelined - s.pip_extra
                )
                walk_idle = future_idle
            else:
                future_idle = s.idle
                walk_idle = s.idle
            fit_future = less_equal(
                p_init_req[:, None, :], future_idle[None, :, :],
                eps, scalar_slot,
            )
            total_ntasks = s.ntasks + s.pip_ntasks
            pods_ok = (
                (nodes.max_tasks <= 0) | (total_ntasks < nodes.max_tasks)
            )[None, :]
            p_feasible = p_ok & fit_future & pods_ok
            if has_ports:
                used_port_f = (s.nport_bits | s.pip_nport_bits).astype(f32)
                port_clash = jnp.matmul(
                    p_ports.astype(f32), used_port_f.T
                )
                p_feasible &= ~p_has_ports[:, None] | (port_clash == 0)
            aff_ok, aff_soft = aff_ok_c, aff_soft_c
            if has_aff:
                def _aff_parts(cnt):
                    cv = count_plane(cnt, aff.node_dom, tk_w)  # [N, EW]
                    total = jnp.sum(cnt, axis=-1)  # [EW]
                    # Required affinity: every required term needs a
                    # resident match in the node's domain (or the
                    # self-match rule).
                    selfok = (total == 0)[None, :] & p_t_matches  # [UM, E]
                    # 0/1 indicator products feeding a zero/nonzero
                    # decision: bf16 is exact for the classification
                    # (true sums are integers; a bf16-rounded value >= 1
                    # can never land below 0.5, and true 0 stays 0) and
                    # runs ~4x faster on the MXU than f32.
                    bf = jnp.bfloat16
                    need = (p_t_req_aff & ~selfok).astype(bf)
                    aff_viol = jnp.matmul(need, (cv == 0).astype(bf).T)
                    anti_viol = jnp.matmul(
                        p_t_req_anti.astype(bf), (cv > 0).astype(bf).T
                    )
                    soft = jnp.matmul(p_t_soft, cv.T.astype(f32))
                    return (aff_viol < 0.5) & (anti_viol < 0.5), soft

                # Cache init is (all-true, zeros) and aff_dirty_a starts
                # at wave_live, so term-free waves never enter the
                # compute branch.
                aff_ok, aff_soft = jax.lax.cond(
                    aff_dirty_a, _aff_parts,
                    lambda cnt: (aff_ok_c, aff_soft_c), cw_a + cw_p
                )
                p_feasible &= aff_ok
            return p_feasible, future_idle, walk_idle, aff_ok, aff_soft

        def rank_nodes(s: GState, p_feasible, aff_soft):
            """Per-profile node ranking by live score ([UM, K] ids).

            One argsort per attempt.  Because infeasible nodes rank last
            (NEG-masked) and every live-feasible node holds at least one
            copy, the first unresolved candidate always lands on a node
            that accepts it — the attempt loop's progress guarantee.
            """
            p_score = jax.vmap(node_score, in_axes=(0, None, None, None))(
                p_req, nodes.allocatable, s.idle, weights
            )
            p_score = p_score + p_static_score
            if has_aff:
                # Soft-term component rides the attempt cache (zeros for
                # term-free waves).
                p_score = p_score + aff_soft
            p_score = jnp.where(p_feasible, p_score, NEG)
            # top_k is the partial sort: ties prefer lower node index,
            # matching the stable argsort it replaces.  Under a mesh the
            # ranking runs shard-local with only the (score, node id)
            # winner reduction crossing chips (_topk_nodes) — this is
            # the full-N path, so it also keeps the two-phase fallback
            # rescore shard-local.
            return _topk_nodes(p_score, K, mesh_shards, hier_pin)

        def live_parts_sl(s: GState, cw_a, cw_p, aff_ok_c, aff_soft_c,
                          aff_dirty_a):
            """Phase-2 fine ``live_parts``: per-attempt dynamic
            feasibility on the [UM, S] shortlist planes.

            Same formulas as ``live_parts`` evaluated only at each
            profile's candidate nodes — the fit broadcast, the port
            clash, and the affinity violation contractions all shrink by
            N/S.  The count-vector gather/matmul over [N, EW] stays
            shared (it is profile-independent); only the per-profile
            planes compact.  Values at shortlist nodes are bit-identical
            to the full computation's."""
            if has_future:
                future_idle = (
                    s.idle + nodes.releasing - nodes.pipelined - s.pip_extra
                )
                walk_idle = future_idle
            else:
                future_idle = s.idle
                walk_idle = s.idle
            fi_sl = future_idle[sl_w]  # [UM, S, R] row gather
            fit_sl = less_equal(
                p_init_req[:, None, :], fi_sl, eps, scalar_slot
            )
            nt_sl = (s.ntasks + s.pip_ntasks)[sl_w]
            pods_ok = (mt_sl <= 0) | (nt_sl < mt_sl)
            feas = p_ok_sl & fit_sl & pods_ok
            if has_ports:
                used = (s.nport_bits | s.pip_nport_bits)[sl_w]  # [UM,S,B]
                clash = jnp.einsum(
                    "ub,usb->us", p_ports.astype(f32), used.astype(f32)
                )
                feas &= ~p_has_ports[:, None] | (clash == 0)
            aff_ok, aff_soft = aff_ok_c, aff_soft_c
            if has_aff:
                def _aff_parts_sl(cnt):
                    cv = count_plane(cnt, aff.node_dom, tk_w)  # [N, EW]
                    cv_sl = cv[sl_w]  # [UM, S, EW] row gather
                    total = jnp.sum(cnt, axis=-1)  # [EW]
                    selfok = (total == 0)[None, :] & p_t_matches
                    bfl = jnp.bfloat16
                    need = (p_t_req_aff & ~selfok).astype(bfl)
                    aff_viol = jnp.einsum(
                        "ue,use->us", need, (cv_sl == 0).astype(bfl)
                    )
                    anti_viol = jnp.einsum(
                        "ue,use->us", p_t_req_anti.astype(bfl),
                        (cv_sl > 0).astype(bfl),
                    )
                    soft = jnp.einsum(
                        "ue,use->us", p_t_soft, cv_sl.astype(f32)
                    )
                    return (
                        (aff_viol < 0.5) & (anti_viol < 0.5), soft
                    )

                aff_ok, aff_soft = jax.lax.cond(
                    aff_dirty_a, _aff_parts_sl,
                    lambda cnt: (aff_ok_c, aff_soft_c), cw_a + cw_p
                )
                feas &= aff_ok
            return feas, future_idle, walk_idle, aff_ok, aff_soft

        def rank_shortlist(s: GState, feas_sl, aff_soft):
            """In-shortlist ranking: [UM, K] global node ids + their
            feasibility.  sl rows are ascending node ids, so top_k ties
            resolve to the lowest node index — the full path's
            tie-break."""
            p_score = jax.vmap(node_score, in_axes=(0, 0, 0, None))(
                p_req, alloc_sl, s.idle[sl_w], weights
            )
            p_score = p_score + static_sl
            if has_aff:
                p_score = p_score + aff_soft
            p_score = jnp.where(feas_sl, p_score, NEG)
            _scores, pos = jax.lax.top_k(p_score, K)
            ranked = jnp.take_along_axis(sl_w, pos, axis=1).astype(
                jnp.int32
            )
            feas_k = jnp.take_along_axis(feas_sl, pos, axis=1)
            return ranked, feas_k

        done0 = ~real_w

        def attempt_cond(carry):
            (_s, _cwa, _cwp, done, _al, _ff, skip_l, _ov, _aw, _pw, it,
             stalled, _aok, _asoft, _adirty, _fbe, _fba, _fbr,
             _ard) = carry
            skip_t = (
                jnp.matmul(onehot_j, skip_l.astype(f32)[:, None])[:, 0] > 0
            )
            # An attempt that resolves nothing leaves the state
            # bit-identical, so the next attempt would stall the same way:
            # exit on stall.  (Stall happens when every unresolved task's
            # feasible nodes sit beyond the top-K ranking prefix while the
            # prefix keeps live capacity claimed by earlier candidates —
            # those tasks stay Pending this cycle, the same outcome as the
            # reference's percentage-of-nodes-to-score cutoff,
            # scheduler_helper.go:43-62.)  The iteration bound is a
            # belt-and-braces guard on top.
            return jnp.any(~done & ~skip_t) & ~stalled & (it < 2 * W + 64)

        def attempt_body(carry):
            (s, cw_a, cw_p, done, alloc_l, fitf_l, skip_l, over_l,
             assigned_w, pipelined_w, it, _stalled,
             aff_ok_c, aff_soft_c, aff_dirty_a, fb_e, fb_a,
             fb_r, aff_rd) = carry
            skip_l0 = skip_l
            if has_aff:
                # This attempt's live_parts recomputes the count plane
                # iff a sub-round of the last one changed a count.
                aff_rd = aff_rd + aff_dirty_a.astype(jnp.int32)

            def of_job(flag_t):
                """[W_task] flag -> [W_job]: set by any task of the job."""
                return jnp.matmul(
                    onehot_j.T, flag_t.astype(f32)[:, None])[:, 0] > 0

            def to_tasks(flag_l):
                """[W_job] flag -> [W_task]: each task reads its job's."""
                return jnp.matmul(
                    onehot_j, flag_l.astype(f32)[:, None])[:, 0] > 0

            if has_overuse:
                # Queue-overuse gating at each job's first task, at the
                # point in task order where the reference evaluates it:
                # on what its queue holds once every earlier job is
                # through.  A queue that is over on its live allocation
                # refuses the job for good.  One that would be over once
                # the wave's earlier unresolved tasks of the same queue
                # (``ahead``) are placed holds the job back for this
                # attempt only: some of those may find no node, and the
                # next attempt reads their outcome from the live
                # allocation.  (Without ``ahead`` every job of a wave
                # passes on the allocation of the wave's start.)  A
                # queue's earliest open task has nothing ahead, so every
                # attempt keeps a candidate per queue.
                gate = is_first_w & ~done & real_w
                live_w = jnp.matmul(onehot_jq, s.q_alloc + s.q_pip)
                des_w = jnp.matmul(onehot_jq, queues.deserved)
                over_live = ~less_equal(live_w, des_w, eps, scalar_slot)
                gated = of_job(gate & over_live)
                skip_l = skip_l | gated
                over_l = over_l | gated

            skip_t = to_tasks(skip_l)
            cand = ~done & ~skip_t

            if has_overuse:
                ahead = jnp.matmul(ahead_q, req_w * cand[:, None])
                over_ahead = ~less_equal(
                    live_w + ahead, des_w, eps, scalar_slot)
                cand = cand & ~to_tasks(of_job(gate & cand & over_ahead))

            if two_phase:
                (feas_sl, future_idle, walk_idle, aff_ok_c,
                 aff_soft_c) = live_parts_sl(
                    s, cw_a, cw_p, aff_ok_c, aff_soft_c, aff_dirty_a
                )
                ranked, feas_k_att = rank_shortlist(s, feas_sl,
                                                    aff_soft_c)
                p_any = jnp.any(feas_sl, axis=1)
                # Shortlist exhaustion -> full-N rescore for the affected
                # profiles only (lax.cond: the [UM, N] planes are only
                # materialized when a live profile actually ran dry), so
                # binding is never lost to pruning.  Counted per reason:
                # required-affinity profiles exhaust when the live
                # domain landscape drifted from the solve-start counts
                # the shortlist was built on; everything else exhausts
                # when earlier waves claimed all S candidates.
                cand_u = (
                    jnp.matmul(
                        onehot_u.T, cand.astype(f32)[:, None]
                    )[:, 0] > 0
                )
                exhausted = cand_u & ~p_any
                if has_aff:
                    prof_req_terms = jnp.any(
                        p_t_req_aff | p_t_req_anti, axis=1
                    )
                else:
                    prof_req_terms = jnp.zeros((UM,), bool)
                need_fb = jnp.any(exhausted)
                if fb_cap:
                    # The cap counts rescore ROUNDS (one per attempt
                    # that fired); a round rescores every profile
                    # exhausting in that attempt, and the per-reason
                    # counters tally those profiles.
                    need_fb &= (s.fb_rounds + fb_r) < fb_cap

                def _fb_rescore(_):
                    # Fresh [UM, N] planes (the attempt-level affinity
                    # cache stays shortlist-shaped; the fallback
                    # recomputes — exact, just uncached).
                    aff_ok_d = jnp.ones((UM, N), bool)
                    aff_soft_d = jnp.zeros((UM, N), f32)
                    dirty = wave_live if has_aff else jnp.bool_(False)
                    p_full, _fi, _wi, _ao, soft_full = live_parts(
                        s, cw_a, cw_p, aff_ok_d, aff_soft_d, dirty
                    )
                    ranked_f = rank_nodes(s, p_full, soft_full)
                    feask_f = jnp.take_along_axis(p_full, ranked_f,
                                                  axis=1)
                    pany_f = jnp.any(p_full, axis=1)
                    mex = exhausted
                    return (
                        jnp.where(mex[:, None], ranked_f, ranked),
                        jnp.where(mex[:, None], feask_f, feas_k_att),
                        jnp.where(mex, pany_f, p_any),
                        jnp.sum(
                            (mex & ~prof_req_terms).astype(jnp.int32)
                        ),
                        jnp.sum(
                            (mex & prof_req_terms).astype(jnp.int32)
                        ),
                    )

                def _fb_skip(_):
                    return (ranked, feas_k_att, p_any, jnp.int32(0),
                            jnp.int32(0))

                ranked, feas_k_att, p_any, fbe_i, fba_i = jax.lax.cond(
                    need_fb, _fb_rescore, _fb_skip, None
                )
                fb_e = fb_e + fbe_i
                fb_a = fb_a + fba_i
                fb_r = fb_r + need_fb.astype(jnp.int32)
                if has_aff:
                    # ... and the rescore's own, uncached (its ``dirty``).
                    aff_rd = aff_rd + (need_fb & wave_live).astype(
                        jnp.int32)
            else:
                (p_feasible, future_idle, walk_idle, aff_ok_c,
                 aff_soft_c) = live_parts(
                    s, cw_a, cw_p, aff_ok_c, aff_soft_c, aff_dirty_a
                )
                ranked = rank_nodes(s, p_feasible, aff_soft_c)
                p_any = jnp.any(p_feasible, axis=1)
                feas_k_att = jnp.take_along_axis(p_feasible, ranked,
                                                 axis=1)

            any_feasible = (
                jnp.matmul(onehot_u, p_any.astype(f32)[:, None])[:, 0] > 0
            )
            no_node = cand & ~any_feasible

            # Abort-in-order: a no-node task masks later tasks of its job
            # from this attempt's acceptance (allocate.go:189-193).
            same_job = jw[:, None] == jw[None, :]
            aborted = jnp.any(same_job & tril & no_node[None, :], axis=1)

            # Hoisted per-attempt constants for the sub-round loop.
            mt_k = nodes.max_tasks[ranked]
            rows_rk = jnp.matmul(onehot_u, ranked.astype(f32))  # [W, K]

            # Contention groups: profiles whose rankings share most of
            # their top nodes compete for the same capacity; rank their
            # candidates jointly so the combined demand spreads over
            # enough nodes in one pass instead of one profile per
            # sub-round.  (Profiles with disjoint rankings keep
            # per-profile ranks — joint ranking would over-spread them.)
            TOPOV = min(16, K)
            top = ranked[:, :TOPOV]  # [UM, TOPOV]
            ov = jnp.sum(
                (top[:, None, :, None] == top[None, :, None, :]),
                axis=(-1, -2),
            )  # [UM, UM] shared-top-node counts
            grp = ov >= (TOPOV + 1) // 2
            grp_pair = (
                jnp.matmul(
                    jnp.matmul(onehot_u, grp.astype(f32)), onehot_u.T
                ) > 0
            )  # [W, W] same-contention-group mask
            if has_aff:
                # Only REQUIRED terms gate pair-wise conflicts: soft
                # (preferred/spread) terms influence scores, never
                # feasibility, so same-domain soft interactions place in
                # one pass with attempt-start scores.
                p_involved = p_t_req_aff | p_t_req_anti
                # Per-task activity masks for the sub-round lax.cond
                # gates: the [EW*D] scatter-min / count scatters only
                # matter while a candidate carries required terms (filter)
                # or an accepted task matches any windowed term (counts).
                involved_any_t = jnp.any(p_involved[pid_l], axis=1)  # [W]
                matches_any_t = jnp.any(t_matches_w, axis=1)  # [W]

            # ---- sub-rounds: rejected tasks re-walk against live capacity
            # within the attempt, reusing this attempt's feasibility and
            # ranking.  Capacity counts (c) and the fit checks always read
            # the LIVE state, so acceptance stays exact; only the node
            # *steering* uses attempt-start scores (the steering is already
            # a documented heuristic).  This collapses the cross-profile
            # conflict retries that previously cost one full attempt
            # (predicates + scoring + ranking) each.  Tasks with inter-pod
            # affinity terms only resolve in the first sub-round: their
            # feasibility depends on count state that live_parts refreshes
            # per attempt.
            def sub_cond(sc):
                (_s, _cwa, _cwp, _fk, done_sub, _al, _aw, _pw, si,
                 progressed, _cch) = sc
                return progressed & (si < SUBROUNDS) & jnp.any(
                    cand & ~done_sub & ~aborted
                )

            def sub_body(sc):
                (s_, cw_a_, cw_p_, feas_k, done_sub, alloc_l_,
                 assigned_w_, pipelined_w_, si, _progressed,
                 cnt_changed) = sc
                cand_s = cand & ~done_sub & ~aborted

                # Live capacity walk (copies of the profile per ranked node).
                if has_future:
                    walk_idle_ = (
                        s_.idle + nodes.releasing - nodes.pipelined
                        - s_.pip_extra
                    )
                else:
                    walk_idle_ = s_.idle
                walk_k = walk_idle_[ranked]  # [UM, K, R] small gather
                per = jnp.where(
                    p_req_pos[:, None, :],
                    walk_k / jnp.maximum(p_req[:, None, :], 1e-9),
                    jnp.inf,
                )
                c_res = jnp.clip(jnp.min(per, axis=-1), 0.0, BIG)
                nt_k = (s_.ntasks + s_.pip_ntasks)[ranked]
                c_pods = jnp.where(
                    mt_k > 0, (mt_k - nt_k).astype(f32), BIG
                )
                c = jnp.where(
                    feas_k, jnp.minimum(jnp.floor(c_res), c_pods), 0.0
                )
                if has_aff:
                    # A profile that anti-affines against its own labels
                    # holds at most one copy per domain; cap the walk at
                    # one per node so siblings spread instead of stacking
                    # on one node and serializing through reject/retry.
                    self_anti = jnp.any(p_t_req_anti & p_t_matches, axis=1)
                    c = jnp.where(self_anti[:, None], jnp.minimum(c, 1.0), c)
                cumcap = jnp.cumsum(c, axis=1)  # [UM, K]

                # m = my rank among the remaining candidates of my
                # contention group (>= my profile's own candidates).
                m = jnp.sum(
                    grp_pair & tril & cand_s[None, :], axis=1
                ).astype(f32)
                rows_cc = jnp.matmul(onehot_u, cumcap)  # [W, K]
                j = jnp.sum(
                    (rows_cc <= m[:, None]).astype(jnp.int32), axis=1
                )
                overflow = cand_s & any_feasible & (j >= K)
                j = jnp.clip(j, 0, K - 1)
                j1h = (j[:, None] == jnp.arange(K)[None, :]).astype(f32)
                choice = jnp.round(jnp.sum(rows_rk * j1h, axis=1)).astype(
                    jnp.int32
                )
                choice = jnp.clip(choice, 0, N - 1)
                live = cand_s & any_feasible & ~overflow

                # ---- prefix acceptance in task order -----------------------
                same_node = (choice[:, None] == choice[None, :]) & tril
                pre = (same_node & live[None, :]).astype(f32)
                cum_req = jnp.matmul(pre, req_w)  # [W, R]
                cum_cnt = jnp.sum(pre, axis=1).astype(jnp.int32)

                # One fused node gather for every per-choice read.
                cols = [
                    s_.idle,
                    (s_.ntasks + s_.pip_ntasks)[:, None].astype(f32),
                    nodes.max_tasks[:, None].astype(f32),
                ]
                if has_future:
                    cols.append(
                        s_.idle + nodes.releasing - nodes.pipelined
                        - s_.pip_extra
                    )
                g = jnp.concatenate(cols, axis=1)[choice]  # [W, C]
                idle_c = g[:, :R]
                ntasks_c = jnp.round(g[:, R]).astype(jnp.int32)
                maxt_c = jnp.round(g[:, R + 1]).astype(jnp.int32)

                fits_idle = less_equal(
                    init_req_w + cum_req, idle_c, eps, scalar_slot
                )
                tot_c = ntasks_c + cum_cnt
                pods_fit = (maxt_c <= 0) | (tot_c < maxt_c)
                clean = live & pods_fit
                if has_ports:
                    # Pair clash within this sub-round + live clash against
                    # everything already applied to the state.
                    pair_port = jnp.matmul(
                        ports_w.astype(f32), ports_w.astype(f32).T
                    )
                    port_conf = jnp.any(
                        same_node & live[None, :] & (pair_port > 0), axis=1
                    )
                    used_bits_c = (
                        s_.nport_bits | s_.pip_nport_bits
                    )[choice]  # [W, B]
                    port_live = jnp.any(ports_w & used_bits_c, axis=1)
                    clean &= ~port_conf & ~port_live
                if has_aff:
                    # Shared row-compaction machinery (TPU scatters and
                    # gathers serialize per element, so update count is
                    # the cost; the participants are few).
                    jidx_w = jnp.arange(W, dtype=jnp.int32)
                    GCAP = min(256, W)

                    def _earliest_rows(mask):
                        """Indices of the earliest <=GCAP rows in
                        ``mask`` (+ validity): top_k on the
                        descending-index score picks the smallest
                        indices first."""
                        score = jnp.where(mask, W - jidx_w, 0)
                        sc, idx_ = jax.lax.top_k(score, GCAP)
                        return idx_, sc > 0

                    # Live per-task recheck + pair-conflict filter, both
                    # lax.cond-skipped for waves with no real terms (the
                    # scatter-min runs over EW*D keys — millions of
                    # entries at hyperscale).
                    def _aff_filter(op):
                        clean_in, cwa, cwp = op
                        # A sibling placed in an earlier sub-round already
                        # satisfies (or violates) required terms here, so
                        # involved tasks resolve within the attempt
                        # instead of one per attempt.
                        dw = node_dom_t[choice]  # [W, EW]
                        cnt_live = cwa + cwp  # [EW, D]
                        total_live = jnp.sum(cnt_live, axis=-1)  # [EW]
                        cval_t = count_plane(
                            cnt_live, aff.node_dom[choice], tk_w
                        )  # [W, EW]
                        req_aff_t = p_t_req_aff[pid_l]  # [W, EW]
                        selfok_t = (total_live == 0)[None, :] & t_matches_w
                        aff_ok = ~jnp.any(
                            req_aff_t & ~selfok_t & (cval_t == 0), axis=1
                        )
                        anti_ok = ~jnp.any(
                            p_t_req_anti[pid_l] & (cval_t > 0), axis=1
                        )
                        out = clean_in & aff_ok & anti_ok
                        # Same-domain interaction with earlier tasks of
                        # THIS sub-round: only ANTI terms serialize (an
                        # earlier giver in my domain would violate my
                        # anti constraint once committed).  Required
                        # AFFINITY siblings landing in the same domain
                        # are mutually consistent — the earlier giver
                        # satisfies the later one, exactly what the
                        # sequential walk would produce — so they place
                        # in one pass.  A task relying on the self-match
                        # rule conflicts only with an earlier giver in a
                        # DIFFERENT domain (two "firsts" splitting the
                        # gang); an earlier same-domain giver makes its
                        # placement consistent.
                        anti_inv = (
                            p_t_req_anti[pid_l] & (dw >= 0)
                        )  # [W, EW]
                        gives = t_matches_w & (dw >= 0)
                        uses_selfok = (
                            req_aff_t & selfok_t & (cval_t == 0)
                        )  # [W, EW]
                        # Pair conflicts via scatter-min over (term,
                        # domain) keys instead of an O(W^2 * EW) pair
                        # tensor: the minimum live-giver index per key
                        # identifies the earliest giver in each domain;
                        # its per-term min (gt) the earliest giver in any
                        # domain.
                        jidx = jidx_w
                        # Only REQUIRED terms' givers feed the conflict
                        # reads (anti_inv / uses_selfok mask every
                        # consumer), so soft-only spread terms drop out
                        # of the scatter key space — exact.
                        gmask = (gives & live[:, None]
                                 & term_req_w[None, :])  # [W, EW]
                        grow = jnp.any(gmask, axis=1)  # [W]

                        # TPU scatters serialize per update: the full
                        # [W, EW] key scatter costs ~2 ms/sub-round at
                        # the north-star shape.  Giver rows are few, so
                        # compact to the earliest <=GCAP of them (min
                        # over a superset of rows with no giver entries
                        # is unchanged); overflow falls back exactly.
                        # Two address forms, identical values: the
                        # flattened [EW * D + 1] buffer (scratch slot
                        # EW * D for masked entries) while the key
                        # space fits int32, the 2-D [EW, D + 1] buffer
                        # (scratch COLUMN D) past it — the scale-tier
                        # int32 audit.
                        if flat_keys_ok:
                            keyv = (
                                term_arange[None, :] * D
                                + jnp.maximum(dw, 0)
                            )
                            scratch = EW * D

                            def _gm_full(_):
                                keys_g = jnp.where(gmask, keyv, scratch)
                                return (
                                    jnp.full((EW * D + 1,), W, jnp.int32)
                                    .at[keys_g.reshape(-1)]
                                    .min(jnp.broadcast_to(
                                        jidx[:, None], (W, EW)
                                    ).reshape(-1))
                                )

                            def _gm_compact(_):
                                gidx, gvalid = _earliest_rows(grow)
                                keys_c = jnp.where(
                                    gmask[gidx] & gvalid[:, None],
                                    keyv[gidx], scratch,
                                )
                                return (
                                    jnp.full((EW * D + 1,), W, jnp.int32)
                                    .at[keys_c.reshape(-1)]
                                    .min(jnp.broadcast_to(
                                        jidx[gidx][:, None], (GCAP, EW)
                                    ).reshape(-1))
                                )

                            def _gm_at(dwv):
                                kv = (
                                    term_arange[None, :] * D
                                    + jnp.maximum(dwv, 0)
                                )
                                return gm[kv]
                        else:
                            def _gm_full(_):
                                cols = jnp.where(
                                    gmask, jnp.maximum(dw, 0), D
                                )
                                return (
                                    jnp.full((EW, D + 1), W, jnp.int32)
                                    .at[jnp.broadcast_to(
                                        term_arange[None, :], (W, EW)
                                    ), cols]
                                    .min(jnp.broadcast_to(
                                        jidx[:, None], (W, EW)
                                    ))
                                )

                            def _gm_compact(_):
                                gidx, gvalid = _earliest_rows(grow)
                                cols = jnp.where(
                                    gmask[gidx] & gvalid[:, None],
                                    jnp.maximum(dw[gidx], 0), D,
                                )
                                return (
                                    jnp.full((EW, D + 1), W, jnp.int32)
                                    .at[jnp.broadcast_to(
                                        term_arange[None, :], (GCAP, EW)
                                    ), cols]
                                    .min(jnp.broadcast_to(
                                        jidx[gidx][:, None], (GCAP, EW)
                                    ))
                                )

                            def _gm_at(dwv):
                                return gm[term_arange[None, :],
                                          jnp.maximum(dwv, 0)]

                        gm = jax.lax.cond(
                            jnp.sum(grow) > GCAP, _gm_full, _gm_compact,
                            None,
                        )
                        # Earliest giver of each term in ANY domain:
                        # directly from the giver rows — identical to
                        # min-reducing gm over the [EW, D] key space,
                        # without touching the 1.28M-entry buffer.
                        jb = jnp.broadcast_to(jidx[:, None], (W, EW))
                        gt = jnp.min(jnp.where(gmask, jb, W), axis=0)

                        # Conflict reads compacted the same way: only
                        # rows carrying anti/selfok terms consult gm,
                        # so gather gm at <=GCAP involved rows instead
                        # of the full [W, EW] element gather.
                        # live-masked (like gmask): conflict is only
                        # consumed as `out & ~conflict` and out is
                        # already false for non-live rows, so dead
                        # involved rows must not inflate the count past
                        # the compaction cap.
                        inv_rows = live & jnp.any(
                            anti_inv | uses_selfok, axis=1
                        )  # [W]

                        def _conf_full(_):
                            gm_my = _gm_at(dw)  # [W, EW]
                            c_anti = jnp.any(
                                anti_inv & (gm_my < jidx[:, None]),
                                axis=1,
                            )
                            gm_my_self = jnp.where(dw >= 0, gm_my, W)
                            c_self = jnp.any(
                                uses_selfok
                                & (gt[None, :] < jidx[:, None])
                                & (gm_my_self > gt[None, :]), axis=1,
                            )
                            return c_anti | c_self

                        def _conf_compact(_):
                            ci, cvalid = _earliest_rows(inv_rows)
                            gm_my_c = _gm_at(dw[ci])  # [GCAP, EW]
                            ji_c = jidx[ci]
                            c_anti = jnp.any(
                                anti_inv[ci]
                                & (gm_my_c < ji_c[:, None]), axis=1,
                            )
                            gm_self_c = jnp.where(dw[ci] >= 0, gm_my_c,
                                                  W)
                            c_self = jnp.any(
                                uses_selfok[ci]
                                & (gt[None, :] < ji_c[:, None])
                                & (gm_self_c > gt[None, :]), axis=1,
                            )
                            return (
                                jnp.zeros((W,), bool)
                                .at[ci]
                                .set((c_anti | c_self) & cvalid)
                            )

                        # Domain-less nodes (dw < 0) have no "my
                        # domain": a selfok user there conflicts with
                        # ANY earlier giver (the committed count kills
                        # its selfok on the next attempt, as the
                        # sequential walk would) — gm_my_self = W keeps
                        # that rule in both branches.
                        conflict = jax.lax.cond(
                            jnp.sum(inv_rows) > GCAP,
                            _conf_full, _conf_compact, None,
                        )
                        return out & ~conflict

                    # The filter only modifies bits of tasks that carry
                    # required terms: with none of them in `clean` it is
                    # the identity, so the gate checks CLEAN (tasks
                    # actually placing this sub-round), not candidacy —
                    # unresolved affinity stragglers stop re-running the
                    # scatter-min machinery every sub-round.
                    clean = jax.lax.cond(
                        wave_live & jnp.any(clean & involved_any_t),
                        _aff_filter, lambda op: op[0],
                        (clean, cw_a_, cw_p_),
                    )

                acc_alloc = clean & fits_idle
                if has_future:
                    fut_c = g[:, R + 2:2 * R + 2]
                    fits_fut = less_equal(
                        init_req_w + cum_req, fut_c, eps, scalar_slot
                    )
                    acc_pipe = clean & ~fits_idle & fits_fut
                else:
                    acc_pipe = jnp.zeros_like(acc_alloc)

                # ---- apply --------------------------------------------------
                radd = req_w * acc_alloc[:, None]
                s_ = s_._replace(
                    idle=s_.idle.at[choice].add(-radd),
                    ntasks=s_.ntasks.at[choice].add(
                        acc_alloc.astype(jnp.int32)
                    ),
                    q_alloc=s_.q_alloc + jnp.matmul(onehot_jq.T, radd),
                )
                if has_future:
                    padd = req_w * acc_pipe[:, None]
                    s_ = s_._replace(
                        pip_extra=s_.pip_extra.at[choice].add(padd),
                        pip_ntasks=s_.pip_ntasks.at[choice].add(
                            acc_pipe.astype(jnp.int32)
                        ),
                        q_pip=s_.q_pip + jnp.matmul(onehot_jq.T, padd),
                    )
                if has_ports:
                    s_ = s_._replace(
                        nport_bits=s_.nport_bits.at[choice].max(
                            ports_w & acc_alloc[:, None]
                        )
                    )
                    if has_future:
                        s_ = s_._replace(
                            pip_nport_bits=s_.pip_nport_bits.at[choice].max(
                                ports_w & acc_pipe[:, None]
                            )
                        )
                if has_aff:
                    # Window-local count update: the wave only touches its
                    # own term rows, so updates stay on the [EW, D] window
                    # carried through the loops; the global state is
                    # written back once per wave.  lax.cond-skipped for
                    # waves with no real terms (nothing to count).
                    def _cnt_update(op):
                        cwa, cwp = op
                        dw = node_dom_t[choice]  # [W, EW]
                        inc_base = t_matches_w & (dw >= 0)

                        # Count-scatter address forms (the scale-tier
                        # int32 audit, see _gm_full): flattened keys
                        # while EW * D fits int32, 2-D (term, domain)
                        # indices past it.  Masked rows carry value 0
                        # and land on domain 0 — a no-op either way.
                        if flat_keys_ok:
                            def _cnt_add(cw, dwv, vals):
                                fd = (
                                    term_arange[None, :] * D
                                    + jnp.maximum(dwv, 0)
                                )
                                return (
                                    cw.reshape(-1)
                                    .at[fd.reshape(-1)]
                                    .add(vals.reshape(-1))
                                    .reshape(EW, D)
                                )
                        else:
                            def _cnt_add(cw, dwv, vals):
                                rows = vals.shape[0]
                                return cw.at[
                                    jnp.broadcast_to(
                                        term_arange[None, :], (rows, EW)
                                    ),
                                    jnp.maximum(dwv, 0),
                                ].add(vals)

                        def cnt_apply(cw, acc):
                            # Accepted matching tasks are few per
                            # sub-round: scatter-add from the earliest
                            # <=GCAP of them (value-0 masking for the
                            # padding) instead of all W x EW keys —
                            # exact, with the full scatter as overflow
                            # fallback.
                            rows_m = jnp.any(inc_base, axis=1) & acc

                            def _full(_):
                                return _cnt_add(
                                    cw, dw,
                                    (inc_base & acc[:, None])
                                    .astype(jnp.int32),
                                )

                            def _compact(_):
                                ci, cval = _earliest_rows(rows_m)
                                vals = (
                                    inc_base[ci]
                                    & acc[ci][:, None]
                                    & cval[:, None]
                                ).astype(jnp.int32)
                                return _cnt_add(cw, dw[ci], vals)

                            return jax.lax.cond(
                                jnp.sum(rows_m) > GCAP, _full, _compact,
                                None,
                            )

                        cwa = cnt_apply(cwa, acc_alloc)
                        if has_future:
                            cwp = cnt_apply(cwp, acc_pipe)
                        return cwa, cwp

                    did_cnt = wave_live & jnp.any(
                        (acc_alloc | acc_pipe) & matches_any_t
                    )
                    cw_a_, cw_p_ = jax.lax.cond(
                        did_cnt, _cnt_update, lambda op: op,
                        (cw_a_, cw_p_),
                    )
                    cnt_changed = cnt_changed | did_cnt

                alloc_l_ = alloc_l_ + jnp.round(
                    jnp.matmul(
                        onehot_j.T, acc_alloc.astype(f32)[:, None]
                    )[:, 0]
                ).astype(jnp.int32)
                assigned_w_ = jnp.where(acc_alloc, choice, assigned_w_)
                pipelined_w_ = jnp.where(acc_pipe, choice, pipelined_w_)
                resolved = acc_alloc | acc_pipe
                return (
                    s_, cw_a_, cw_p_, feas_k,
                    done_sub | resolved, alloc_l_,
                    assigned_w_, pipelined_w_, si + 1, jnp.any(resolved),
                    cnt_changed,
                )

            (s, cw_a, cw_p, _fk, done_sub, alloc_l, assigned_w,
             pipelined_w, subs, _prog, cnt_changed_out) = (
                jax.lax.while_loop(
                    sub_cond, sub_body,
                    (s, cw_a, cw_p, feas_k_att, done,
                     alloc_l, assigned_w, pipelined_w, jnp.int32(0),
                     jnp.bool_(True), jnp.bool_(False)),
                )
            )

            # Attempt-level job bookkeeping for fit failures.
            fit_upd = (
                jnp.matmul(
                    onehot_j.T, no_node.astype(f32)[:, None]
                )[:, 0] > 0
            )
            fitf_l = fitf_l | fit_upd
            skip_l = skip_l | fit_upd

            new_done = done_sub | no_node
            stalled = ~jnp.any(new_done & ~done) & jnp.all(
                skip_l == skip_l0
            )
            done = done | new_done

            return (
                s, cw_a, cw_p, done, alloc_l, fitf_l, skip_l, over_l,
                assigned_w, pipelined_w, it + jnp.maximum(subs, 1), stalled,
                aff_ok_c, aff_soft_c, cnt_changed_out, fb_e, fb_a, fb_r,
                aff_rd,
            )

        # Per-wave count windows (the wave only touches its own term rows).
        if has_aff:
            if terms_disjoint:
                cw_a0 = cnt0_i32[wterms]
                cw_p0 = jnp.zeros_like(cw_a0)
            else:
                cw_a0 = state.cnt_alloc[wterms]
                cw_p0 = state.cnt_pip[wterms]
            # Affinity attempt-cache init: all-feasible/zero-score with
            # the dirty flag at wave_live, so live waves compute on the
            # first attempt and term-free waves never do.  Two-phase
            # carries the cache at shortlist width.
            aff_ok0 = jnp.ones((UM, S if two_phase else N), bool)
            aff_soft0 = jnp.zeros((UM, S if two_phase else N), f32)
            aff_dirty0 = wave_live
        else:
            cw_a0 = jnp.zeros((1, 1), jnp.int32)
            cw_p0 = jnp.zeros((1, 1), jnp.int32)
            aff_ok0 = jnp.ones((1, 1), bool)
            aff_soft0 = jnp.zeros((1, 1), f32)
            aff_dirty0 = jnp.bool_(False)

        init = (
            state,
            cw_a0,
            cw_p0,
            done0,
            jsl(state.alloc_cnt),
            jsl(state.fit_failed),
            jsl(state.job_skip),
            jsl(state.job_overskip),
            jnp.full((W,), -1, jnp.int32),
            jnp.full((W,), -1, jnp.int32),
            jnp.int32(0),
            jnp.bool_(False),
            aff_ok0,
            aff_soft0,
            aff_dirty0,
            jnp.int32(0),
            jnp.int32(0),
            jnp.int32(0),
            jnp.int32(0) if has_aff else None,
        )
        (s, cw_a, cw_p, _done, alloc_l, fitf_l, skip_l, over_l, assigned_w,
         pipelined_w, _it, _stalled, _aok, _asoft, _adirty, _fbe, _fba,
         _fbr, _ard) = (
            jax.lax.while_loop(attempt_cond, attempt_body, init)
        )
        if has_aff and not terms_disjoint:
            # Real rows are unique in wterms; duplicate writes only hit
            # the dummy scratch row.  With wave-disjoint term sets (the
            # static flag) no later wave reads these counts and the
            # write-back — a full [E, D]-table rewrite per wave under
            # XLA's scatter lowering — is skipped.
            s = s._replace(
                cnt_alloc=s.cnt_alloc.at[wterms].set(cw_a),
                cnt_pip=s.cnt_pip.at[wterms].set(cw_p),
            )

        jupd_back = lambda g, l: jax.lax.dynamic_update_slice_in_dim(
            g, l, jlo, axis=0
        )
        s = s._replace(
            iters=s.iters + _it,
            fb_exhausted=s.fb_exhausted + _fbe,
            fb_affinity=s.fb_affinity + _fba,
            fb_rounds=s.fb_rounds + _fbr,
            aff_reads=s.aff_reads + _ard if has_aff else None,
        )
        return s._replace(
            alloc_cnt=jupd_back(s.alloc_cnt, alloc_l),
            fit_failed=jupd_back(s.fit_failed, fitf_l),
            job_skip=jupd_back(s.job_skip, skip_l),
            job_overskip=jupd_back(s.job_overskip, over_l),
            assigned=jax.lax.dynamic_update_slice_in_dim(
                s.assigned, assigned_w, off, axis=0
            ),
            pipelined=jax.lax.dynamic_update_slice_in_dim(
                s.pipelined, pipelined_w, off, axis=0
            ),
        )

    state = jax.lax.fori_loop(0, NW, run_wave, state)

    # ---- gang commit/discard, vectorized (stmt.Discard) --------------------
    min_av_p = jnp.pad(jobs.min_available, (0, W), constant_values=1 << 30)
    ready_base_p = jnp.pad(jobs.ready_base, (0, W))
    job_ready = ready_base_p + state.alloc_cnt >= min_av_p
    never_ready_p = job_seen & ~state.job_overskip & ~job_ready  # [JP]
    discard_t = never_ready_p[tjob] & tasks.real & (state.assigned >= 0)
    n_c = jnp.maximum(state.assigned, 0)
    rsub = jnp.take(prof.req, pid, axis=0) * discard_t[:, None]
    idle = state.idle.at[n_c].add(rsub)
    q_alloc = state.q_alloc.at[queue_p[tjob]].add(-rsub)
    assigned = jnp.where(discard_t, -1, state.assigned)

    pipelined = state.pipelined
    if N <= 32000:
        # Narrow the [P] result vectors on device: `assigned` is the
        # bulk of the device->host fetch (100k x 4B at north-star
        # scale), and node indices fit int16 whenever N does.  Hosts
        # consume them as indices, where numpy upcasts transparently.
        assigned = assigned.astype(jnp.int16)
        pipelined = pipelined.astype(jnp.int16)
    return AllocResult(
        assigned=assigned,
        pipelined=pipelined,
        never_ready=never_ready_p[:J],
        fit_failed=state.fit_failed[:J],
        idle=idle,
        q_alloc=q_alloc + state.q_pip,
        iters=state.iters,
        fb_exhausted=state.fb_exhausted,
        fb_affinity=state.fb_affinity,
        aff_count_reads=state.aff_reads,
        overuse_gated=jnp.sum(state.job_overskip[:J], dtype=jnp.int32),
    )


@partial(jax.jit, static_argnames=("e", "d"))
def _scatter_cnt0(rows, cols, vals, e, d):
    return jnp.zeros((e, d), jnp.int32).at[rows, cols].add(vals)


@_functools.lru_cache(maxsize=4)
def _scatter_cnt0_onto(sharding):
    """``_scatter_cnt0`` with its result born under ``sharding`` (a mesh
    caller's domain-axis sharding): every chip fills its own shard, and
    the dense table never stands whole on one of them."""
    return jax.jit(_scatter_cnt0.__wrapped__, static_argnames=("e", "d"),
                   out_shardings=sharding)


def _mesh_pad(d: int, sharding) -> int:
    """Columns to add to a ``d``-wide domain axis so that ``sharding``
    (a mesh caller's, or None) splits it evenly; domain ids only ever
    index the original range."""
    mesh = getattr(sharding, "mesh", None)
    return 0 if mesh is None else (-d) % mesh.devices.size


@partial(jax.jit, static_argnames=("u", "e"))
def _scatter_profile_tables(rows, cols, flags, soft, u, e):
    """Rebuild the dense [U, E] profile-term tables from their sparse
    entries on device (see solve_wave: the dense bool/f32 tables are
    tens of MB of mostly zeros to upload; the entries are tiny).
    Padded entries carry flags/soft of 0 at
    (0, 0) — add is a no-op there; real (u, e) pairs are unique."""
    zb = jnp.zeros((u, e), jnp.int8)
    aff = zb.at[rows, cols].add(flags & 1) > 0
    anti = zb.at[rows, cols].add((flags >> 1) & 1) > 0
    match = zb.at[rows, cols].add((flags >> 2) & 1) > 0
    soft_t = jnp.zeros((u, e), jnp.float32).at[rows, cols].add(soft)
    return aff, anti, match, soft_t


def _np(a):
    # ascontiguousarray: no-op for the usual numpy inputs; jax arrays
    # fetched from a sharded placement can materialize non-contiguous,
    # which breaks the profile-hash .view(uint8) reinterpret.
    return np.ascontiguousarray(a)


_HASH_SEED = np.random.RandomState(0x5EED)


def _profile_tasks(tasks: SolveTasks, aff: AffinityArgs, extra_ok=None,
                   extra_score=None):
    """Group tasks into distinct profiles (host, numpy).

    Returns (profiles, pid[P]) where profiles hold one row per distinct
    combination of every per-task solver input except job identity, and
    pid is ordered by first occurrence (so job-contiguous task order keeps
    per-wave profile ranges narrow).

    Grouping hashes each row with a random linear map and verifies the
    result exactly (every row compared against its representative); on the
    astronomically unlikely hash collision it falls back to exact grouping.
    """
    P = tasks.req.shape[0]
    cols = [
        _np(tasks.req).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.init_req).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.ports).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.sel_bits).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.aff_bits).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.aff_terms).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.tol_bits).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.pref_bits).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(tasks.pref_w).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(aff.t_req_aff).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(aff.t_req_anti).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(aff.t_matches).reshape(P, -1).view(np.uint8).reshape(P, -1),
        _np(aff.t_soft).reshape(P, -1).view(np.uint8).reshape(P, -1),
    ]
    if extra_ok is not None:
        # Custom per-task node masks split profiles: tasks of one profile
        # must share a mask row (the kernel applies it per profile).
        cols.append(np.packbits(_np(extra_ok), axis=1))
    if extra_score is not None:
        cols.append(
            _np(extra_score).astype(np.float32)
            .reshape(P, -1).view(np.uint8).reshape(P, -1)
        )
    raw = np.concatenate(cols, axis=1)  # [P, C] uint8
    # Three independent linear hashes with small coefficients: every dot
    # product stays below 2^33, so the float64 BLAS matmul is exact and two
    # distinct rows collide in one column with probability ~2^-20 (the
    # coefficients are random); across three columns ~2^-60 per pair.
    rnd = _HASH_SEED.randint(1, 1 << 20, size=(raw.shape[1], 3))
    h = (raw.astype(np.float64) @ rnd.astype(np.float64)).astype(np.int64)
    p1 = np.uint64(0x9E3779B97F4A7C15).astype(np.int64)
    p2 = np.uint64(0xC2B2AE3D27D4EB4F).astype(np.int64)
    with np.errstate(over="ignore"):
        hv = h[:, 0] + h[:, 1] * p1 + h[:, 2] * p2
    _, first_idx, inv = np.unique(
        hv, return_index=True, return_inverse=True
    )
    # Renumber profiles by first occurrence so pid follows task order.
    order = np.argsort(first_idx, kind="stable")
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order))
    pid = rank[inv].astype(np.int32)
    u = first_idx[order]

    if not np.array_equal(raw, raw[u][pid]):  # hash collision: exact path
        key = np.ascontiguousarray(raw)
        _, first_idx, inv = np.unique(
            key.view([("", np.uint8)] * key.shape[1]).ravel(),
            return_index=True,
            return_inverse=True,
        )
        order = np.argsort(first_idx, kind="stable")
        rank = np.empty(len(order), np.int64)
        rank[order] = np.arange(len(order))
        pid = rank[inv].astype(np.int32)
        u = first_idx[order]

    profiles = SolveProfiles(
        req=_np(tasks.req)[u],
        init_req=_np(tasks.init_req)[u],
        ports=_np(tasks.ports)[u],
        sel_bits=_np(tasks.sel_bits)[u],
        aff_bits=_np(tasks.aff_bits)[u],
        aff_terms=_np(tasks.aff_terms)[u],
        tol_bits=_np(tasks.tol_bits)[u],
        pref_bits=_np(tasks.pref_bits)[u],
        pref_w=_np(tasks.pref_w)[u],
        t_req_aff=_np(aff.t_req_aff)[u],
        t_req_anti=_np(aff.t_req_anti)[u],
        t_matches=_np(aff.t_matches)[u],
        t_soft=_np(aff.t_soft)[u],
    )
    extra_prof = _np(extra_ok)[u] if extra_ok is not None else None
    score_prof = (
        _np(extra_score).astype(np.float32)[u]
        if extra_score is not None else None
    )
    return profiles, pid, extra_prof, score_prof


def _renumber_pid(pid: np.ndarray):
    """Renumber profile ids by first occurrence; return (pid2, u_rows) where
    u_rows[k] is the first task row of profile k."""
    _, first_idx, inv = np.unique(pid, return_index=True, return_inverse=True)
    order = np.argsort(first_idx, kind="stable")
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order))
    return rank[inv].astype(np.int32), first_idx[order]


def _profiles_from_pid(tasks: SolveTasks, aff: AffinityArgs,
                       pid: np.ndarray):
    """Build SolveProfiles from caller-supplied profile ids (the store
    mirror interns them at pod-add time, so no per-cycle hashing)."""
    pid, u = _renumber_pid(pid)
    profiles = SolveProfiles(
        req=_np(tasks.req)[u],
        init_req=_np(tasks.init_req)[u],
        ports=_np(tasks.ports)[u],
        sel_bits=_np(tasks.sel_bits)[u],
        aff_bits=_np(tasks.aff_bits)[u],
        aff_terms=_np(tasks.aff_terms)[u],
        tol_bits=_np(tasks.tol_bits)[u],
        pref_bits=_np(tasks.pref_bits)[u],
        pref_w=_np(tasks.pref_w)[u],
        t_req_aff=_np(aff.t_req_aff)[u],
        t_req_anti=_np(aff.t_req_anti)[u],
        t_matches=_np(aff.t_matches)[u],
        t_soft=_np(aff.t_soft)[u],
    )
    return profiles, pid


def bucket_pow2(n: int, floor: int, min_pad: int = 8, room: int = 0) -> int:
    """Anti-recompile shape bucket: next power of two >= n plus 25%
    headroom (raw counts clustering at a power of two must not flip
    buckets cycle-to-cycle — each flip is a multi-second XLA recompile).
    ``floor`` bounds the smallest bucket per axis; ``room`` asks for
    more headroom than the quarter where that is larger."""
    target = n + max(n // 4, min_pad, room)
    b = max(floor, 1)
    while b < target:
        b *= 2
    return b


def draw_headroom(n: int) -> int:
    """Room for the next draw of a count that is one: a round's terms
    (and with them its constrained profiles) are a binomial draw of its
    gangs, so two rounds of the same traffic differ by a few sqrt(n).
    Five of them cover a window of rounds against the warm-up round's
    draw; past n = 400 ``bucket_pow2``'s own quarter is the larger."""
    return 5 * _math.isqrt(max(int(n), 0))


def settle(marks: Optional[dict], axis: str, n: int, fresh: int) -> int:
    """Shape bucket of ``axis`` kept at its high-water mark in ``marks``
    (one dict per store, for the store's life; None: no memory, the
    bucket is ``fresh``).  A count that fits the bucket it once got
    keeps it, so the shapes a round's terms give the jitted programs
    do not move between rounds of the same traffic: a bucket taken anew
    from every round's draw crosses a power of two at random, and each
    crossing lowers ``_static_planes``, ``_coarse_shortlist`` and
    ``_solve_wave`` again.  ``fresh`` is the bucket ``n`` would get on
    its own (headroom included); it is taken only when ``n`` outgrows
    the mark."""
    if marks is None:
        return fresh
    have = marks.get(axis)
    if have is None or n > have:
        marks[axis] = have = fresh
    return have


def settled_pow2(marks: Optional[dict], axis: str, n: int, floor: int,
                 min_pad: int = 8) -> int:
    """``bucket_pow2`` of a count that is a draw (``draw_headroom``),
    kept at its high-water mark (``settle``)."""
    return settle(marks, axis, n, bucket_pow2(
        n, floor, min_pad, room=draw_headroom(n)))


def _grow_profiles(sp: SparseProfiles, pad: int) -> SparseProfiles:
    """``sp`` with ``pad`` inert zero rows more: the nine per-profile
    fields are padded; the term tables have no entry there and only
    grow in shape (they take their height where they are born)."""
    if not pad:
        return sp
    U, E = sp.terms.shape
    rows = [_np(a) for a in sp[:9]]
    return SparseProfiles(
        *[np.concatenate([a, np.zeros((pad, *a.shape[1:]), a.dtype)])
          for a in rows],
        sp.terms._replace(shape=(U + pad, E)))


def _pad_profiles_rows(sp: SparseProfiles, marks=None) -> SparseProfiles:
    """Pad the profile table's row axis to a power of two (min 64) with
    inert zero rows.  The row count is data-dependent (distinct task
    profiles this cycle); unpadded it changes shape almost every cycle
    and forces an XLA recompile of the wave solver — ~7s per new shape,
    dwarfing the solve itself.  Padded rows are never referenced: pid and
    wave_prof only index real rows."""
    U = sp.terms.shape[0]
    return _grow_profiles(sp, settled_pow2(marks, "U", U, floor=64) - U)


def shortlist_keys(sp: SparseProfiles, extra_prof, score_prof,
                   own_terms: bool, marks=None):
    """The distinct rows of the profile table in what the shortlist
    passes read of a row, as ``(key_rows [K], key_of [U], n_keys,
    token)``.

    Two rows share a key only if every value ``_coarse_shortlist``'s
    body reads of them is bytewise equal: the nine per-profile fields
    (the static planes' rows are a function of them), the custom
    plugins' ``extra_prof`` / ``score_prof`` rows where a solve has them
    (None: it has not), and, where the body reads the four term tables
    (``own_terms``: ``has_aff and cnt0_any``), their rows: a row with no
    term entry has all-zero table rows and may share, a row with one
    keeps a key of its own (the entries decide; no dense table is
    compared).  In a burst round onto an empty cluster that is the
    cluster's few pod shapes and the all-zero padding row, whatever the
    count of constrained gangs (each a profile row for its own terms).

    ``key_rows`` holds each key's first profile row, in the keys' byte
    order (a function of the key set alone), padded with its first entry
    to the settled bucket of the count (``marks``, axis ``"K"``; never
    past ``U``: a table whose rows all differ is ranked row by row);
    ``key_of`` is each profile row's place in it.  ``token`` is a digest
    of the key table and of ``key_of``: two solves with equal tokens
    rank the same keys and hand them to the same rows."""
    import hashlib

    U = sp.terms.shape[0]
    cols = [_np(a) for a in sp[:9]]
    cols += [a for a in (extra_prof, score_prof) if a is not None]
    if own_terms:
        own = np.zeros(U, np.int32)
        own[sp.terms.rows] = sp.terms.rows + 1
        cols.append(own)
    table = np.concatenate(
        [np.ascontiguousarray(a).view(np.uint8).reshape(U, -1)
         for a in cols if a.size], axis=1)
    # ``np.unique`` of the byte rows by hand (it spends three quarters
    # of its time comparing void scalars): a stable sort, so a key's
    # first row leads its run.
    order = np.argsort(table.view(
        np.dtype((np.void, table.shape[1]))).ravel(), kind="stable")
    srt = table[order]
    leads = np.concatenate([[True], (srt[1:] != srt[:-1]).any(axis=1)])
    first = order[leads]
    key_of = np.empty(U, np.int32)
    key_of[order] = np.cumsum(leads) - 1
    n_keys = len(first)
    K = min(settled_pow2(marks, "K", n_keys, floor=16), U)
    key_rows = np.concatenate(
        [first, np.full(K - n_keys, first[0])]).astype(np.int32)
    h = hashlib.blake2b(digest_size=16)
    h.update(table[first].tobytes())
    h.update(key_of.tobytes())
    return key_rows, key_of, n_keys, h.hexdigest()


def profile_term_entries(rows, cols, flags, soft, shape) -> ProfileTermEntries:
    """Entries from (profile, term) cells named in any order and any
    number of times: a cell's flags are ORed and its soft weights summed
    in the order given (as ``np.add.at`` on the table sums them); a cell
    left all zero is no entry."""
    U, E = int(shape[0]), int(shape[1])
    key, inv = np.unique(
        np.asarray(rows, np.int64) * E + np.asarray(cols, np.int64),
        return_inverse=True)
    f = np.zeros(len(key), np.int8)
    np.bitwise_or.at(f, inv, np.asarray(flags, np.int8))
    w = np.zeros(len(key), np.float32)
    np.add.at(w, inv, np.asarray(soft, np.float32))
    keep = (f != 0) | (w != 0)
    key = key[keep]
    return ProfileTermEntries((key // E).astype(np.int32),
                              (key % E).astype(np.int32),
                              f[keep], w[keep], (U, E))


def _sparse_profiles(profiles):
    """``profiles`` with the term tables as entries, and the bytes of
    dense host tables read for that: ``SparseProfiles`` pass through;
    dense ``SolveProfiles`` (in-call profiling, the object path, tests)
    are scanned for their entries."""
    if isinstance(profiles, SparseProfiles):
        return profiles, 0
    t_aff, t_anti, t_mat, t_soft = (_np(t) for t in profiles[9:])
    ur, ec = np.nonzero(t_aff | t_anti | t_mat | (t_soft != 0))
    flags = (
        t_aff[ur, ec].astype(np.int8)
        | (t_anti[ur, ec].astype(np.int8) << 1)
        | (t_mat[ur, ec].astype(np.int8) << 2)
    )
    terms = ProfileTermEntries(
        ur.astype(np.int32), ec.astype(np.int32), flags,
        t_soft[ur, ec].astype(np.float32),
        (int(t_aff.shape[0]), int(t_aff.shape[1])))
    return (SparseProfiles(*profiles[:9], terms),
            t_aff.nbytes + t_anti.nbytes + t_mat.nbytes + t_soft.nbytes)


def _dense_profile_tables(terms: ProfileTermEntries, u: int, e: int):
    """The four ``[u, e]`` tables of ``terms`` on the host: how a small
    set goes up (``PROF_SPARSE_MIN``), cell for cell what
    ``_scatter_profile_tables`` gives a large one on the device."""
    r, c = terms.rows, terms.cols
    out = []
    for bit in range(3):
        t = np.zeros((u, e), bool)
        t[r, c] = (terms.flags >> bit) & 1
        out.append(t)
    soft = np.zeros((u, e), np.float32)
    soft[r, c] = terms.soft
    return (*out, soft)


def _pad_entries(k: int, *cols):
    """Entry columns zero-padded to ``k``: a padded entry adds 0 to
    cell (0, 0), a no-op."""
    return tuple(
        np.concatenate([c, np.zeros(k - len(c), c.dtype)]) if k > len(c)
        else c for c in cols)


def _term_windows(terms: ProfileTermEntries, wave_prof: np.ndarray,
                  n_waves: int, marks=None):
    """Per-wave lists of the affinity terms the wave's profiles reference.

    Every [*, E] tensor in the kernel is gathered down to the wave's term
    list, bounding the affinity machinery by terms-per-wave instead of
    total terms.  One dummy scratch row (index E; ``solve_wave`` appends
    it to every term axis) is the list padding, so the windowed count
    write-back scatters to unique real rows (duplicates only hit the
    dummy).  The lists come from ``terms`` grouped by profile: a walk
    over the entries of each wave's profiles, not over [UM, E] table
    rows.  Returns (wave_terms [NW, EW], EW, terms_disjoint).
    """
    U, E = terms.shape
    # Entries are in (profile, term) order: start[u]..start[u + 1] are
    # profile u's.
    start = np.searchsorted(terms.rows, np.arange(U + 1))
    wp = np.clip(_np(wave_prof), 0, U - 1).astype(np.int64)
    lo = start[wp].ravel()
    n = start[wp + 1].ravel() - lo
    total = int(n.sum())
    # One key per (wave, term) pair present, each once, in order.
    idx = np.repeat(lo - (np.cumsum(n) - n), n) + np.arange(total)
    wave_of = np.repeat(np.repeat(np.arange(n_waves), wp.shape[1]), n)
    key = np.unique(wave_of * (E + 1) + terms.cols[idx])
    wave_of, term = key // (E + 1), key % (E + 1)
    per_wave = np.bincount(wave_of, minlength=n_waves)
    ew = max(1, int(per_wave.max()))
    EW = settled_pow2(marks, "EW", ew, floor=16, min_pad=4)
    wave_terms = np.full((n_waves, EW), E, np.int32)  # pad = dummy row
    first = np.cumsum(per_wave) - per_wave
    wave_terms[wave_of, np.arange(len(key)) - first[wave_of]] = term
    # Term sets are usually wave-disjoint (terms select a job's own app
    # label and jobs never split across waves): no wave then reads a
    # count another wave wrote, and the per-wave window write-back into
    # the global [E, D] tables — a full-table rewrite per wave under
    # XLA's scatter lowering, ~2 s/cycle at the north-star affinity
    # shape — can be skipped wholesale.
    terms_disjoint = bool(len(term) == len(np.unique(term)))
    return wave_terms, int(EW), terms_disjoint


def _wave_profiles(pid: np.ndarray, n_waves: int, wave: int, marks=None,
                   has_terms: bool = False):
    """Per-wave lists of the profiles actually PRESENT in each wave.

    Shared profiles recur across the whole task list, so id *ranges* per
    wave degenerate to the full profile table at scale; explicit presence
    lists keep UM at (distinct profiles per wave), padded to a power of
    two across waves to bound recompilation.  Padding repeats the wave's
    first profile (read-only duplication).  Returns wave_prof [NW, UM];
    the per-task index into its wave's list is recomputed on device (a
    [W, UM] equality argmax per wave replaces a [P] vector in the
    upload).
    """
    seg = pid.reshape(n_waves, wave)
    lists = []
    um = 1
    for w in range(n_waves):
        u = np.unique(seg[w])
        lists.append(u)
        um = max(um, len(u))
    # Under terms every constrained gang is a profile of its own, so
    # the count is a draw like the terms' (draw_headroom); without
    # them it is the cluster's few request shapes.
    fresh = 1
    while fresh < um + (draw_headroom(um) if has_terms else 0):
        fresh *= 2
    UM = settle(marks, "UM", um, fresh)
    wave_prof = np.zeros((n_waves, UM), np.int32)
    for w, u in enumerate(lists):
        wave_prof[w, :len(u)] = u
        wave_prof[w, len(u):] = u[0]
    return wave_prof


def _pad_tasks(tasks: SolveTasks, pad: int) -> SolveTasks:
    def z(a):
        a = _np(a)
        return np.concatenate([a, np.zeros((pad, *a.shape[1:]), a.dtype)])

    return SolveTasks(
        req=z(tasks.req),
        init_req=z(tasks.init_req),
        job=np.concatenate(
            [_np(tasks.job), np.full((pad,), -1, np.int32)]
        ),
        real=np.concatenate([_np(tasks.real), np.zeros((pad,), bool)]),
        ports=z(tasks.ports),
        sel_bits=z(tasks.sel_bits),
        aff_bits=z(tasks.aff_bits),
        aff_terms=z(tasks.aff_terms),
        tol_bits=z(tasks.tol_bits),
        pref_bits=z(tasks.pref_bits),
        pref_w=z(tasks.pref_w),
    )


def _pad_aff(aff: AffinityArgs, pad: int) -> AffinityArgs:
    def z(a):
        a = _np(a)
        return np.concatenate([a, np.zeros((pad, *a.shape[1:]), a.dtype)])

    return AffinityArgs(
        node_dom=aff.node_dom,
        term_key=aff.term_key,
        cnt0=aff.cnt0,
        t_req_aff=z(aff.t_req_aff),
        t_req_anti=z(aff.t_req_anti),
        t_matches=z(aff.t_matches),
        t_soft=z(aff.t_soft),
    )


def _host_node_classes(nodes: SolveNodes):
    """Compact the node table into classes from HOST arrays.

    Only called when ``nodes.label_bits`` is numpy (direct callers, the
    remote solver child); device-resident callers (devsnap, mesh) build
    classes from their own host copies and pass ``node_classes`` in —
    this helper is deliberately outside the vclint hot registry because
    by contract it never sees a device array.

    The grouping is memoized on a content digest of the static planes
    (one entry): the remote solver child has no mirror epoch to key on,
    but its node table is just as epoch-stable cycle-to-cycle, and the
    digest (a linear byte hash) is an order of magnitude cheaper than
    re-running the structured-row unique sort every solve."""
    import hashlib

    from .nodeclass import build_node_classes

    h = hashlib.blake2b(digest_size=16)
    planes = (
        nodes.label_bits, nodes.taint_bits, np.asarray(nodes.ready),
        np.asarray(nodes.allocatable, np.float32),
        np.asarray(nodes.max_tasks, np.int32),
    )
    for a in planes:
        a = np.ascontiguousarray(a)
        h.update(repr((a.shape, a.dtype.str)).encode())
        h.update(memoryview(a).cast("B"))
    key = h.hexdigest()
    cached = _host_node_classes._cache
    if cached is not None and cached[0] == key:
        return cached[1]
    classes, _n, _sig = build_node_classes(*planes)
    _host_node_classes._cache = (key, classes)
    return classes


_host_node_classes._cache = None


def solve_wave(
    nodes: SolveNodes,
    tasks: SolveTasks,
    jobs: SolveJobs,
    queues: SolveQueues,
    weights: ScoreWeights,
    eps,
    scalar_slot,
    aff: AffinityArgs,
    node_bias=None,
    wave: int = DEFAULT_WAVE,
    pid=None,
    profiles: SolveProfiles = None,
    extra_ok=None,
    extra_score=None,
    taint_any=None,
    node_classes: NodeClasses = None,
    mesh_shards: int = 1,
    devincr=None,
    shape_marks: Optional[dict] = None,
    cnt0_sharding=None,
) -> AllocResult:
    """Wave-batched solve; same signature/result as ``allocate.solve``.

    Pads the task axis to whole waves, the shape bucket of this axis
    (padded rows are inert and stay in ``assigned`` / ``pipelined`` as
    -1 past the caller's rows: a slice there would be a program per row
    count), and deduplicates tasks into profiles host-side.  ``pid``
    (optional [P] int32) supplies precomputed profile ids — tasks with
    equal ids must have identical per-task solver inputs — and skips
    the feature-hashing pass.  With ``profiles`` also given (rows
    aligned to the pid numbering, which must be by first occurrence;
    ``SolveProfiles`` or ``SparseProfiles``),
    nothing per-task is recomputed here and ``aff``'s task-level fields
    may be dummies.

    ``node_bias`` (optional [N] f32, ops/topology.contig_bias) is an
    additive node-order bias folded into every profile's static score —
    the 9th element of the fast path's solve_args tuple, so remote
    frames and mesh sharding carry it like any other node plane, and
    the solver wire stays byte-identical when absent.

    ``extra_ok`` (optional [P, N] bool) carries custom-plugin predicate
    verdicts (session add_predicate_fn / add_device_mask_fn); it folds
    into the profile grouping so tasks sharing a profile share a mask
    row, and is only supported when profiles are computed in-call
    (custom plugins make a configuration fast-path-ineligible).

    ``mesh_shards`` (mesh callers: the device count the node axis is
    sharded over) restructures every node-axis ranking — the coarse
    shortlist selection, the per-attempt walk ranking, and the full-N
    fallback rescore — into the shard-local + winner-reduction form
    (``_topk_nodes``), keeping the per-profile (score, node id)
    all-reduce as the only cross-chip communication of the selection
    step.  Results are bit-identical to ``mesh_shards=1``; a node axis
    the shard count does not divide falls back to the global form.

    ``devincr`` (optional ``ops.devincr.DeviceIncremental``, ISSUE 9)
    makes the two-phase coarse machinery incremental ACROSS solves:
    persistent [U, C] static planes keyed on content versions replace
    the in-kernel ``_class_static`` passes, and the coarse shortlist
    warm-starts from the previous solve's per-block candidates when the
    caller proved (``begin_solve``) which node rows may have changed.
    Results are bit-for-bit equal to ``devincr=None``; custom-plugin
    solves (``extra_ok``/``extra_score``) and non-two-phase solves
    ignore the context.

    ``shape_marks`` (optional dict, the caller's for its store's life)
    keeps every data-dependent shape bucket of this call (profile rows,
    profiles and terms per wave, sparse entry lists) at its high-water
    mark (``settle``); padding is inert, so results do not depend on it.

    ``cnt0_sharding`` (mesh callers, ``parallel/mesh.shard_wave_inputs``:
    the domain-axis sharding of the count tensors) is where ``aff.cnt0``
    goes; the caller hands over what it has (entries, a host table) and
    places nothing.  A table past ``CNT0_SPARSE_MIN`` has its entries go
    up, and the dense ``[Ep + 1, D]`` table exists only as the mesh's
    shards; a small one is placed dense.  Either way the domain axis is
    zero-padded to a multiple of the mesh.

    The inter-pod term data comes as entries or as tables, by what the
    caller has: the fast path's encode hands ``profiles`` as
    ``SparseProfiles`` and ``aff.cnt0`` as ``CountEntries`` (nothing
    dense in [U, Ep] or [Ep, D] ever stands on the host); in-call
    profiling, the object path and tests hand dense tables, which are
    scanned for their entries once.  From there it is one path: the
    windows come from the entries, and a table goes up by its size —
    born on the device from the entries past ``PROF_SPARSE_MIN`` /
    ``CNT0_SPARSE_MIN``, densified here and uploaded under them.
    """
    P = int(tasks.job.shape[0])
    if (extra_ok is not None or extra_score is not None) and (
            pid is not None or profiles is not None):
        raise ValueError(
            "extra_ok/extra_score require in-call profile computation"
        )
    pad = (-P) % wave
    if pad:
        tasks = _pad_tasks(tasks, pad)
        if profiles is None:
            aff = _pad_aff(aff, pad)
        if extra_ok is not None:
            extra_ok = np.concatenate([
                _np(extra_ok),
                np.ones((pad, _np(extra_ok).shape[1]), bool),
            ])
        if extra_score is not None:
            extra_score = np.concatenate([
                _np(extra_score).astype(np.float32),
                np.zeros((pad, _np(extra_score).shape[1]), np.float32),
            ])
    n_waves = (P + pad) // wave
    if profiles is not None and pid is not None:
        pid = np.asarray(pid, np.int64)
        sp, host_dense = _sparse_profiles(profiles)
        if pad:
            # Padded rows are all-zero features: append a fresh profile.
            fresh = int(pid.max() + 1) if len(pid) else 0
            pid = np.concatenate([pid, np.full(pad, fresh, np.int64)])
            sp = _grow_profiles(sp, 1)
        pid = pid.astype(np.int32)
    else:
        if pid is not None:
            pid = np.asarray(pid, np.int64)
            if pad:
                fresh = (pid.max() + 1) if len(pid) else 0
                pid = np.concatenate([pid, np.full(pad, fresh, np.int64)])
            profiles, pid = _profiles_from_pid(tasks, aff, pid)
        else:
            profiles, pid, extra_prof, score_prof = _profile_tasks(
                tasks, aff, extra_ok, extra_score
            )
        sp, host_dense = _sparse_profiles(profiles)
    # From here the inter-pod term data is entries, whoever built it:
    # ``sp.terms`` for the four [U, Ep] profile-term tables, ``cnt`` for
    # the [Ep, D] count table.  ``host_dense`` adds up the bytes of
    # dense host tables of either kind that this solve read or builds
    # (the ``aff_host_dense_bytes`` solve count).
    u_before = sp.terms.shape[0]
    sp = _pad_profiles_rows(sp, shape_marks)
    terms = sp.terms
    U_rows, Ep = terms.shape
    u_pad = U_rows - u_before
    has_terms = bool((terms.flags & 3).any() or terms.soft.any())
    if extra_ok is not None:
        if u_pad:
            extra_prof = np.concatenate([
                extra_prof, np.ones((u_pad, extra_prof.shape[1]), bool),
            ])
    else:
        extra_prof = np.ones((1, 1), bool)
    if extra_score is not None:
        if u_pad:
            score_prof = np.concatenate([
                score_prof,
                np.zeros((u_pad, score_prof.shape[1]), np.float32),
            ])
    else:
        score_prof = np.zeros((1, 1), np.float32)
    wave_prof = _wave_profiles(pid, n_waves, wave, shape_marks, has_terms)
    # Input diet for the device call: the kernel reads only job/real
    # per-task (req/init_req come from profile gathers), so every other
    # per-task field ships as a [1, ...] dummy, and the three [P] id
    # vectors narrow to int16 when their value ranges allow — at
    # 10k x 100k this cuts the per-solve upload ~6 MB -> ~0.7 MB.
    R_ = int(sp.req.shape[1])
    job_in = tasks.job
    job_sh = getattr(job_in, "sharding", None)
    if job_sh is not None and not isinstance(job_in, np.ndarray):
        # Mesh / committed-array callers: dummies and narrowed ids must
        # land on the same device set or the jit sees incompatible
        # committed arguments (the cnt0 rebuild below has the same rule).
        _put = lambda x: jax.device_put(x, job_sh)
    else:
        _put = lambda x: x
    z1 = lambda shape, dt: _put(np.zeros(shape, dt))
    tasks = tasks._replace(
        req=z1((1, R_), np.float32),
        init_req=z1((1, R_), np.float32),
        ports=z1((1, 1), np.uint32),
        sel_bits=z1((1, 1), np.uint32),
        aff_bits=z1((1, 1, 1), np.uint32),
        aff_terms=z1((1,), np.int32),
        tol_bits=z1((1, 1), np.uint32),
        pref_bits=z1((1, 1, 1), np.uint32),
        pref_w=z1((1, 1), np.float32),
    )
    if U_rows < 32767:
        pid = _put(np.asarray(pid).astype(np.int16))
    if int(jobs.min_available.shape[0]) < 32767:
        job_h = _np(job_in)
        if job_h.dtype != np.int16:
            tasks = tasks._replace(job=_put(job_h.astype(np.int16)))
    cnt0_in = aff.cnt0
    cnt = count_entries_of(cnt0_in)
    if cnt is not cnt0_in:  # a dense table was read for them
        host_dense += int(cnt0_in.nbytes)
    # Where the count table, and whatever is rebuilt on the device
    # beside it, goes: the sharding a mesh caller named, else the
    # placement of a table that arrived committed.
    in_sharding = (cnt0_sharding if cnt0_sharding is not None
                   else getattr(cnt0_in, "sharding", None))
    cnt0_any = bool(len(cnt.rows))
    features = (
        bool(_np(sp.ports).any()),
        has_terms or cnt0_any,
        # Device-resident callers (ops/devsnap.py, the mesh plane cache)
        # pass the taint feature as a host-computed hint — fetching a
        # persistent device plane back just to .any() it would put a
        # blocking device->host round trip on every dispatch.
        (bool(taint_any) if taint_any is not None
         # vclint: disable=VCL201 -- numpy fallback; taint_any skips it
         # (device-resident callers always pass the host-computed hint)
         else bool(_np(nodes.taint_bits).any())),
        bool(_np(nodes.releasing).any() or _np(nodes.pipelined).any()),
        bool((_np(queues.deserved) < 1.0e38).any()),
        extra_ok is not None,
        extra_score is not None,
    )
    wave_terms, ew, terms_disjoint = _term_windows(
        terms, wave_prof, n_waves, marks=shape_marks)
    # Every term axis gets the dummy scratch row (index Ep) that
    # ``wave_terms`` pads with; the tables below are born with it.
    aff = aff._replace(term_key=np.concatenate(
        [_np(aff.term_key), np.zeros(1, np.int32)]))
    # Profile-term tables ([U, Ep] bool x3 + f32) reach ~75 MB at the
    # north-star affinity shape but are overwhelmingly zero (a profile
    # references only its own job's terms).  Past the threshold the
    # entries go up and the tables are born on the device; a small set
    # is densified here and uploaded.
    if U_rows * Ep > PROF_SPARSE_MIN:
        k = settled_pow2(shape_marks, "prof_entries", len(terms.rows),
                         floor=16)
        tables = _scatter_profile_tables(
            *_pad_entries(k, terms.rows, terms.cols, terms.flags,
                          terms.soft),
            U_rows, Ep + 1,
        )
        if in_sharding is not None:
            try:
                tables = tuple(
                    jax.device_put(x, in_sharding) for x in tables)
            except ValueError:
                # A partitioned sharding whose axis does not divide the
                # rebuilt [U, Ep+1] tables (mesh callers sharding the
                # term axis): replicate them instead — the [E, D] count
                # pair is the memory wall, not these.
                rep = jax.sharding.NamedSharding(
                    in_sharding.mesh, jax.sharding.PartitionSpec()
                )
                tables = tuple(jax.device_put(x, rep) for x in tables)
    else:
        tables = _dense_profile_tables(terms, U_rows, Ep + 1)
        host_dense += sum(t.nbytes for t in tables)
    profiles = SolveProfiles(*sp[:9], *tables)
    # The count table: ``[Ep + 1, D]`` with the domain axis zero-padded
    # to a multiple of a mesh caller's mesh.
    Ec, D = cnt.shape
    d_dev = D + _mesh_pad(D, cnt0_sharding)
    if Ec * D > CNT0_SPARSE_MIN:
        # Hyperscale [Ep, D] count tables reach hundreds of MB; the
        # resident entries (typically none on a fresh cycle) go up and
        # are scattered on device instead of the dense zeros.  Mesh
        # callers: the table is born under the placement the caller
        # named for (or gave) cnt0, or the jit below sees committed
        # arrays on incompatible device sets.
        k = settled_pow2(shape_marks, "cnt0_entries", len(cnt.rows),
                         floor=16)
        scatter = (_scatter_cnt0 if in_sharding is None
                   else _scatter_cnt0_onto(in_sharding))
        aff = aff._replace(cnt0=scatter(
            *_pad_entries(k, cnt.rows, cnt.cols, cnt.vals), Ec + 1, d_dev))
    else:
        # A table small enough to go up dense; a mesh caller's is placed
        # as it asked.
        dense = np.zeros((Ec + 1, d_dev), np.int32)
        dense[cnt.rows, cnt.cols] = cnt.vals
        host_dense += dense.nbytes
        aff = aff._replace(cnt0=dense if cnt0_sharding is None
                           else jax.device_put(dense, cnt0_sharding))
    # ---- two-phase solve prep (node classes + shortlists) ------------
    N_in = int(nodes.idle.shape[0])
    two_phase = _two_phase_on() and N_in > 0
    if two_phase and node_classes is None \
            and isinstance(nodes.label_bits, np.ndarray):
        node_classes = _host_node_classes(nodes)
    cls_identity = node_classes is None
    if two_phase and not cls_identity:
        cls_arg = node_classes
    else:
        # Inert dummies; the kernel derives identity classes from the
        # node planes when two_phase & cls_identity.
        cls_arg = NodeClasses(
            class_id=z1((1,), np.int32),
            label_bits=z1((1, 1), np.uint32),
            taint_bits=z1((1, 1), np.uint32),
            ready=z1((1,), bool),
        )
    sl_k = shortlist_size(N_in) if two_phase else 1
    # Effective shard count for the node-axis rankings: only when the
    # (padded) node axis divides evenly — otherwise the global form is
    # both correct and what GSPMD would fall back to anyway.
    n_sh = int(mesh_shards) if mesh_shards else 1
    if n_sh > 1 and (N_in % n_sh):
        n_sh = 1
    # Largest power of two <= COARSE_CHUNK: the profile axis is
    # pow2-padded, so a pow2 chunk always divides it (lax.map needs an
    # exact reshape).
    chunk = 1
    while chunk * 2 <= max(1, min(COARSE_CHUNK, U_rows)):
        chunk *= 2
    # Trace-static knob verdicts resolved OUTSIDE the jits (an env read
    # at trace time would pin the first verdict into the jit cache and
    # make in-process knob flips no-ops): the hierarchical-selection
    # pin, and the int32 key-space verdict for the kernel's windowed
    # [EW, D] (term x domain) scatters — ``ew`` and the domain width
    # are exactly the kernel's EW and D.
    hier_pin = _hier_pin()
    D_dev = int(aff.cnt0.shape[1])
    flat_keys = (ew * D_dev + 1) <= _keyspace_max()
    # Device-incremental context (ISSUE 9): only the two-phase slim
    # path qualifies — custom-plugin solves carry per-solve [U, N]
    # planes the cache keys cannot cover.
    dv = devincr
    if dv is not None and (not two_phase or features[5] or features[6]):
        dv = None
    # One ranking per distinct scoring key, not one per profile row.
    if two_phase:
        key_rows, key_of, n_keys, key_tok = shortlist_keys(
            sp, extra_prof if features[5] else None,
            score_prof if features[6] else None,
            own_terms=features[1] and cnt0_any, marks=shape_marks)
    # Exact f32 matmuls are load-bearing: the one-hot matmuls carry node
    # indices, resource sums, and 0/1 predicate counts that are compared
    # with == / <=; the TPU default (bf16 MXU passes) rounds node ids above
    # 256 and capacity sums, mis-routing placements and stalling the
    # attempt loop.
    t_coarse = 0.0
    stat = None
    with jax.default_matmul_precision("float32"):
        if two_phase:
            t0 = _time.perf_counter()
            if dv is not None:
                stat = dv.static_planes(
                    nodes, profiles, cls_arg,
                    weights.node_affinity_weight, chunk,
                    has_taints=features[2], cls_identity=cls_identity,
                )
                sl = dv.shortlist(
                    nodes, profiles, extra_prof, score_prof, cls_arg,
                    aff, weights, eps, scalar_slot, key_rows, key_of,
                    key_tok,
                    sl_k=sl_k, chunk=chunk, features=features,
                    cnt0_any=bool(cnt0_any), cls_identity=cls_identity,
                    mesh_shards=n_sh, stat=stat,
                )
            else:
                sl = _coarse_shortlist(
                    nodes, profiles, extra_prof, score_prof, cls_arg,
                    aff, weights, eps, scalar_slot, key_rows, key_of,
                    sl_k=sl_k, chunk=chunk,
                    features=features, cnt0_any=bool(cnt0_any),
                    cls_identity=cls_identity, mesh_shards=n_sh,
                    hier_pin=hier_pin,
                )
            t_coarse = _time.perf_counter() - t0
        else:
            sl = z1((1, 1), np.int32)
        t0 = _time.perf_counter()
        res = _solve_wave(
            nodes, tasks, jobs, queues, weights, eps, scalar_slot, aff,
            profiles, extra_prof, score_prof, pid, wave_prof,
            wave_terms, cls_arg, sl,
            wave=wave, n_waves=n_waves, ew=ew, features=features,
            terms_disjoint=terms_disjoint, two_phase=two_phase,
            cls_identity=cls_identity, fb_cap=_fallback_cap(),
            mesh_shards=n_sh,
            static_ext=stat is not None,
            stat_ok=stat[0] if stat is not None else None,
            stat_score=stat[1] if stat is not None else None,
            hier_pin=hier_pin,
            flat_keys=flat_keys,
            node_bias=node_bias,
            has_bias=node_bias is not None,
        )
        t_fine = _time.perf_counter() - t0
    # Dispatch-side sub-lane telemetry (the cycle driver folds it into
    # the device_coarse/device_fine lanes; with async device dispatch
    # these measure the host-side dispatch legs, the residual device
    # wait stays on the caller's fetch).
    LAST_TWOPHASE.clear()
    LAST_TWOPHASE.update({
        "enabled": two_phase,
        "coarse_s": t_coarse,
        "fine_s": t_fine,
        "shortlist": (U_rows, sl_k) if two_phase else None,
        # Profile rows the shortlist was handed to, and the distinct
        # scoring keys that were ranked for them (before bucket padding).
        "shortlist_rows": U_rows if two_phase else 0,
        "shortlist_keys": n_keys if two_phase else 0,
        "n_nodes": N_in,
        "compacted_classes": two_phase and not cls_identity,
        "mesh_shards": n_sh,
        "devincr": dv.solve_info() if dv is not None else None,
        # The hand-off of the inter-pod term data (None without terms):
        # real entries before bucket padding, and the bytes of dense
        # host tables this solve read or built for them.
        "terms": {"prof_entries": int(len(terms.rows)),
                  "cnt0_entries": int(len(cnt.rows)),
                  "host_dense_bytes": int(host_dense)}
        if features[1] else None,
    })
    if dv is not None:
        dv.end_solve()
    return res
