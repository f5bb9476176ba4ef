"""Device-lane incrementality: cross-cycle plane reuse + warm shortlists.

ISSUE 9.  PR 7 made the host lanes incremental over the mirror's dirty
set, but the device lane still recomputed everything from scratch each
solve: ``_class_static`` re-evaluated every static predicate/pref plane
per (profile x class) and ``_coarse_shortlist`` re-ranked all N nodes
once per solve — even in a steady-state cycle where the dirty set says
a few hundred rows changed.  ``DeviceIncremental`` is the device analog
of ``fastpath_incr``: the same subtract-old/add-new discipline, applied
to the two-phase solve's coarse machinery.

Three pieces (all bit-for-bit equal to a fresh solve, with a proven
fallback and the ``VOLCANO_TPU_DEVINCR`` kill switch):

1. **Persistent static planes** — ``ops.wave._static_planes`` (its own
   jit) produces the [U, C] per-(profile x class) feasibility/score
   planes ONCE; they stay device-resident here, keyed on (class-table
   content sig, profile content generation, epoch-relevant bits), and
   pass into ``solve_wave`` as params — steady-state solves skip static
   evaluation entirely, in the coarse pass AND per wave.  Any key
   component moving (class-set change, profile-set change, node churn)
   rebuilds them wholesale.

2. **Warm-started shortlists** — the coarse pass retains per-block
   (score, global node id) candidate lists per key row ([K, B, klb], the
   ``_topk_nodes`` two-stage structure at block granularity; ``K`` the
   distinct scoring keys of the profile table, ``ops.wave
   .shortlist_keys``: 8 B a candidate, so 32 keys x 16 blocks x 819 are
   3.4 MB at 10,000 nodes where a list per profile row, 4,096 of them
   under inter-pod terms, was 429 MB); on the
   next solve only blocks containing a dirty node row re-rank
   (``_warm_shortlist``), and the winners merge exactly like the full
   pass.  The caller proves the dirty superset via ``begin_solve``;
   any invalidation that can't be proven (cache key drift, dirty
   overflow, affinity-count content change — the cnt0 token rides the
   warm key — or another key set: the keys' digest rides it too)
   re-ranks fully, and the fine phase's full-N fallback still
   guarantees no binding is ever lost to pruning.

3. **Null-delta fast cycles** — ``skip_token`` (written by the fast
   path at dispatch) proves a later cycle's solve would see bit-equal
   inputs and produce the identical (empty) outcome, so the cycle skips
   the dispatch wholesale (``fastpath.FastCycle._allocate``).

The same object serves the local, mesh (replicated placement via
``set_mesh``), and remote paths — the solver child keeps one per
connection, keyed by the cache-generation tokens the scheduler sends in
the solve frame's manifest (``solver_service.py``).
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)


def devincr_on() -> bool:
    """The device-incremental kill switch (read per call, so a test can
    flip it between two solves of one process)."""
    return os.environ.get("VOLCANO_TPU_DEVINCR", "1") != "0"


def warm_blocks() -> int:
    """Node-axis block count of the warm-shortlist candidate retention
    (pow2; clamped to the padded node axis and raised to the mesh shard
    count by the caller)."""
    return 16


def warm_block_rows() -> int:
    """Upper bound on node rows per warm block (pow2).  At the 100k-node
    tier the fixed block count would leave 8k+ rows per block — one
    dirty node then re-ranks 8k rows; bounding rows/block instead
    keeps the warm re-rank cost proportional to churn, and the
    block->shard->global merge (ops.wave._merge_block_cands) keeps the
    extra blocks' reduce shard-local."""
    return 8192


# Past this fraction of blocks dirty, a full re-rank beats the gather +
# scatter machinery (and seeds fresh candidates anyway).
WARM_MAX_BLOCK_FRACTION = 0.5


class DeviceIncremental:
    """Persistent device-side caches for one solve stream (one per
    store on the scheduler side, one per connection in the solver
    child).  Not thread-safe by itself: the scheduler accesses it on
    the cycle thread under the store lock; the child on its single
    connection thread."""

    def __init__(self):
        # --- persistent static planes -------------------------------
        self._static_key = None
        self._static: Optional[Tuple] = None  # (ok [U,C], score [U,C])
        # --- warm shortlist candidates ------------------------------
        self._warm_key = None
        # (cand_s [K, B, klb], cand_i [K, B, klb], sl [U, sl_k])
        self._cand: Optional[Tuple] = None
        # --- host info for the CURRENT solve (begin_solve) ----------
        self._pend_static = None
        self._pend_warm = None
        self._pend_dirty: Optional[np.ndarray] = None
        # --- dirty-node accumulator between solves ------------------
        # Node rows whose derive-visible dynamic state changed since
        # the previous solve's inputs were built; None = poisoned
        # (a full derive ran, or nothing accumulated yet).
        self._acc_dirty: Optional[list] = None
        self._dirty_consumed = False
        # --- null-delta skip ----------------------------------------
        # Solve-input token captured at the previous dispatch; equality
        # at the next allocate proves the solve would reproduce the
        # previous (empty) outcome, so the dispatch is skipped.
        self.skip_token = None
        # --- mesh placement -----------------------------------------
        self._rep_shd = None
        self._place_tok = ("single",)
        # --- telemetry ----------------------------------------------
        self.last_mode = "off"  # warm | full | off (per solve)
        self.last_static = "off"  # hit | build | off
        self.last_blocks = (0, 0)  # (dirty blocks, total blocks)
        self.counts = {"warm": 0, "full": 0, "skip": 0}
        self.static_hits = 0
        self.static_builds = 0

    # ------------------------------------------------------- placement

    def set_mesh(self, mesh) -> None:
        """Replicated placement for the host-built delta inputs under a
        mesh (committed jit args must share a device set).  Changing
        the mesh voids both caches via the placement token."""
        if mesh is None:
            tok = ("single",)
            if tok != self._place_tok:
                self.invalidate()
            self._rep_shd = None
            self._place_tok = tok
            return
        from jax.sharding import NamedSharding, PartitionSpec

        tok = ("mesh", id(mesh), int(mesh.devices.size))
        if tok != self._place_tok:
            self.invalidate()
        self._rep_shd = NamedSharding(mesh, PartitionSpec())
        self._place_tok = tok

    def _place(self, a: np.ndarray):
        import jax

        if self._rep_shd is not None:
            return jax.device_put(a, self._rep_shd)
        return a

    # ------------------------------------------------- host-side state

    def accumulate_dirty(self, nodes: Optional[np.ndarray]) -> None:
        """Fold one derive's changed-node capture into the accumulator
        (the warm diff is against the previous SOLVE, which may be
        several derives back).  ``None`` poisons the accumulator — the
        next solve re-ranks fully and resets it."""
        if nodes is None:
            self._acc_dirty = None
            return
        if self._acc_dirty is None:
            # Poisoned: stays poisoned until the next solve resets the
            # anchor (take_dirty) — that solve re-ranks fully.
            return
        if len(nodes):
            self._acc_dirty.append(np.asarray(nodes, np.int64))

    def take_dirty(self, extra: Optional[np.ndarray]):
        """The dirty-node superset for the solve being dispatched
        (accumulated derive captures + the caller's still-unconsumed
        rows), or None when unprovable.  The accumulator reset is
        DEFERRED to ``end_solve``: a solve that crashes before its
        shortlist ran must not consume the set (the candidates were
        never updated, so the next solve still has to cover it)."""
        self._dirty_consumed = True
        acc = self._acc_dirty
        if acc is None or extra is None:
            return None
        parts = acc + [np.asarray(extra, np.int64)]
        cat = np.concatenate(parts) if parts else np.zeros(0, np.int64)
        cat = cat[cat >= 0]
        return np.unique(cat)

    def begin_solve(self, static_key, warm_key,
                    dirty_nodes: Optional[np.ndarray]) -> None:
        """Host-side validity info for the next ``solve_wave`` call:
        ``static_key`` pins the static-plane cache, ``warm_key`` the
        shortlist candidates, ``dirty_nodes`` the node rows whose
        dynamic state may have changed since the previous solve (None =
        unprovable -> full re-rank)."""
        self._pend_static = static_key
        self._pend_warm = warm_key
        self._pend_dirty = (None if dirty_nodes is None
                            else np.asarray(dirty_nodes, np.int64))

    def anchor_dirty(self) -> None:
        """Anchor the accumulator on a solve that demonstrably consumed
        the dirty superset: called by ``end_solve`` for in-process
        solves, and by the fast path after a SUCCESSFUL remote send
        (the child solves every frame it receives, so the frame's
        tokens+dirty list anchor the child's caches whether or not the
        reply survives; a failed send must NOT anchor — the child never
        saw the set)."""
        self._acc_dirty = []
        self._dirty_consumed = False

    def end_solve(self) -> None:
        """Consume the pending host info (a solve_wave call without a
        fresh ``begin_solve`` — e.g. the rebalance what-if — must not
        reuse a stale proof), and anchor the dirty accumulator on the
        solve that just COMPLETED (see ``take_dirty``)."""
        self._pend_static = None
        self._pend_warm = None
        self._pend_dirty = None
        if self._dirty_consumed:
            self.anchor_dirty()

    def invalidate(self) -> None:
        """Drop every cached plane and proof (close, compaction void,
        mesh change)."""
        self._static_key = None
        self._static = None
        self._warm_key = None
        self._cand = None
        self.skip_token = None
        self._dirty_consumed = False
        self.end_solve()
        self._acc_dirty = None

    def solve_info(self) -> dict:
        return {
            "mode": self.last_mode,
            "static": self.last_static,
            "blocks": self.last_blocks,
        }

    # -------------------------------------------------- solve services

    # Both methods below are called from inside solve_wave's
    # default_matmul_precision("float32") context — the producers must
    # trace under the same precision the in-kernel evaluation uses.

    def static_planes(self, nodes, prof, cls, naff_weight, chunk,
                      has_taints: bool, cls_identity: bool):
        """The persistent [U, C] static planes for this solve, produced
        on miss and reused on key match; None when the driver supplied
        no static key (kill switch / unprovable)."""
        if self._pend_static is None:
            self.last_static = "off"
            return None
        key = (self._pend_static, self._place_tok, bool(has_taints),
               bool(cls_identity), int(prof.sel_bits.shape[0]))
        if self._static is not None and self._static_key == key:
            self.static_hits += 1
            self.last_static = "hit"
            return self._static
        from .wave import _static_planes

        ok, sc = _static_planes(
            nodes, prof, cls, naff_weight, chunk=chunk,
            has_taints=bool(has_taints),
            cls_identity=bool(cls_identity),
        )
        self._static = (ok, sc)
        self._static_key = key
        self.static_builds += 1
        self.last_static = "build"
        return self._static

    def shortlist(self, nodes, prof, extra_prof, score_prof, cls, aff,
                  weights, eps, scalar_slot, key_rows, key_of,
                  key_tok: str, sl_k: int, chunk: int,
                  features: tuple, cnt0_any: bool, cls_identity: bool,
                  mesh_shards: int, stat):
        """The solve's [U, sl_k] shortlists, ranked per key row
        (``ops.wave.shortlist_keys``: ``key_rows``, ``key_of`` and the
        digest of both, ``key_tok``): warm-started when the warm
        key held and the dirty-block fraction is low, full re-rank
        (seeding fresh candidates) otherwise.  Bit-identical to
        ``_coarse_shortlist`` either way.  The candidates kept are the
        key rows' ([K, B, klb]); ``key_tok`` rides the warm key, so a
        warm pass never patches candidates ranked for another key set,
        and the shortlist a null delta hands back is the one expanded
        to these rows."""
        from . import wave as _w

        N = int(nodes.idle.shape[0])
        U = int(key_of.shape[0])
        K = int(key_rows.shape[0])
        n_sh = max(1, int(mesh_shards))
        B = max(warm_blocks(), n_sh)
        # Scale-tier growth: bound rows per block so the per-dirty-node
        # re-rank cost stays fixed as N grows (the merge stays cheap —
        # block->shard->global, ops.wave._merge_block_cands).  Doubling
        # from max(warm_blocks, n_sh) keeps B a multiple of the shard
        # count, so blocks always subdivide shards.
        max_rows = warm_block_rows()
        while N % (B * 2) == 0 and N // B > max_rows:
            B *= 2
        B = min(B, N)
        while N % B:  # N is pow2-padded in practice; belt and braces
            B //= 2
        B = max(B, 1)
        nlb = N // B
        klb = min(sl_k, nlb)
        meta = (self._place_tok, U, K, key_tok, N, B, klb, int(sl_k),
                tuple(features), bool(cnt0_any), bool(cls_identity),
                n_sh, stat is not None)
        key = ((self._pend_warm, meta)
               if self._pend_warm is not None else None)
        stat_ok, stat_sc = stat if stat is not None else (None, None)
        dirty = self._pend_dirty
        if (key is not None and self._cand is not None
                and self._warm_key == key and dirty is not None):
            db = np.unique(
                dirty[(dirty >= 0) & (dirty < N)].astype(np.int64)
                // nlb
            ).astype(np.int32)
            if len(db) == 0:
                # Null delta at shortlist granularity: every input is
                # byte-identical to the previous solve's — its
                # shortlist (and candidates) stand as-is.
                cand_s, cand_i, sl = self._cand
                self.last_mode = "warm"
                self.last_blocks = (0, B)
                self.counts["warm"] += 1
                return sl
            if len(db) <= max(1, int(B * WARM_MAX_BLOCK_FRACTION)):
                k = 1
                while k < len(db):
                    k *= 2
                if k > len(db):
                    db = np.concatenate(
                        [db, np.full(k - len(db), db[0], np.int32)]
                    )
                cand_s, cand_i, _sl = self._cand
                sl, cand_s, cand_i = _w._warm_shortlist(
                    nodes, prof, extra_prof, score_prof, cls, aff,
                    weights, eps, scalar_slot, key_rows, key_of,
                    stat_ok, stat_sc, self._place(db), cand_s, cand_i,
                    sl_k=int(sl_k), klb=klb, nlb=nlb, chunk=chunk,
                    features=tuple(features), cnt0_any=bool(cnt0_any),
                    cls_identity=bool(cls_identity),
                    static_ext=stat is not None, mesh_shards=n_sh,
                )
                self._cand = (cand_s, cand_i, sl)
                self.last_mode = "warm"
                self.last_blocks = (int(len(np.unique(db))), B)
                self.counts["warm"] += 1
                return sl
        # Full re-rank — also seeds the candidates for the next solve.
        sl, cand_s, cand_i = _w._coarse_shortlist(
            nodes, prof, extra_prof, score_prof, cls, aff, weights,
            eps, scalar_slot, key_rows, key_of, sl_k=int(sl_k),
            chunk=chunk,
            features=tuple(features), cnt0_any=bool(cnt0_any),
            cls_identity=bool(cls_identity), mesh_shards=n_sh,
            n_blocks=B, with_cand=True, static_ext=stat is not None,
            stat_ok=stat_ok, stat_score=stat_sc,
        )
        self._cand = (cand_s, cand_i, sl)
        self._warm_key = key
        self.last_mode = "full"
        self.last_blocks = (B, B)
        self.counts["full"] += 1
        return sl


def of_store(store) -> DeviceIncremental:
    """The store's device-incremental context, created on first use
    (``store._devincr_cache`` — a declared lock-guarded cache slot,
    cleared by ``store.close()``; see tools/vclint aggcheck's
    CACHE_REGISTRY for its invalidation contract)."""
    dv = getattr(store, "_devincr_cache", None)
    if dv is None:
        dv = store._devincr_cache = DeviceIncremental()
    return dv
