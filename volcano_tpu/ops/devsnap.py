"""Device-resident snapshot planes with delta uploads.

The synchronous cycle re-shipped every solver input each solve, although
most node-side planes — allocatable capacity, label/taint bit planes,
max-task counts, readiness, topology domains — change only when the NODE
table changes (the mirror's epoch key), not per cycle.  Those re-uploads sit
on the dispatch path of every cycle.

``DeviceSnapshot`` keeps one persistent per-device array per plane,
keyed by the mirror epoch + plane shape:

- key unchanged  -> the cached device array is handed straight to the
  jit call: zero upload, zero host copy;
- epoch advanced with shapes intact -> only the rows the mirror recorded
  dirty (``StoreMirror.node_delta_rows``) are uploaded and scattered
  into the DONATED persistent buffer (``donate_argnums`` on the scatter
  carry, so steady-state updates allocate nothing device-side);
- shape changed / delta unprovable -> full re-upload.

One snapshot instance lives per store (``store.device_snapshot``),
created by the fast path on first use.  It serves the single-process
wave path AND the mesh path: a mesh store's snapshot commits every node
plane with the node-axis ``NamedSharding`` (each chip holds only its
node shard) and the delta scatter then runs SHARD-LOCAL — node churn
costs one small scatter on the owning chip instead of a full
host->device re-upload of every plane on every chip.  Only the remote
split stays out (it ships numpy frames; the child process owns its own
device state).
"""

from __future__ import annotations

import logging
import os
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

log = logging.getLogger(__name__)

# Above this fraction of rows dirty, a full re-upload beats the scatter.
DELTA_MAX_FRACTION = 0.25


def budget_bytes() -> int:
    """Per-scatter host-staging budget for delta uploads
    (``VOLCANO_TPU_DEVSNAP_BUDGET_MB``, default 256 MB).

    The delta path materializes one host values array per plane before
    the device scatter; at the 100k-node tier a churn burst can mark a
    quarter of the table dirty, and building every plane's full delta
    at once would spike the host (and transfer-staging) footprint by
    the sum of the planes.  Chunking each plane's delta to this budget
    bounds the peak at (largest single chunk) instead — the same
    degrade-the-burst discipline as the affinity chunk budget
    (fastpath._solve_chunks)."""
    try:
        mb = float(os.environ.get("VOLCANO_TPU_DEVSNAP_BUDGET_MB", 256))
    except ValueError:
        mb = 256.0
    # Fractional MB are accepted so tests can force the chunked path at
    # toy shapes; the 4 KB floor keeps a hostile/typo'd value from
    # degenerating to row-at-a-time scatters.
    return max(4096, int(mb * 1_000_000))


def _chunk_rows_for(row_nbytes: int) -> int:
    """Rows per delta-scatter chunk under the budget (pow2 so repeated
    bursts reuse one compiled scatter per plane instead of one per
    distinct chunk length)."""
    rows = max(1, budget_bytes() // max(1, row_nbytes))
    p = 1
    while p * 2 <= rows:
        p *= 2
    return p


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(buf, rows, vals):
    """Write ``vals`` into ``buf`` at ``rows`` (leading axis), reusing the
    donated buffer in place.  Padded duplicate rows rewrite the same
    value — idempotent."""
    return buf.at[rows].set(vals)


def _pad_delta(rows: np.ndarray, vals: np.ndarray):
    """Pad a delta to a headroomed pow2 bucket (ops.wave.bucket_pow2:
    +25% so dirty-row counts hovering at a power of two don't flip
    buckets cycle-to-cycle — each flip recompiles the scatter) so the
    jit compiles per bucket, not per distinct dirty-row count
    (duplicates of row 0 are idempotent rewrites)."""
    from .wave import bucket_pow2

    k = bucket_pow2(len(rows), floor=8)
    pad = k - len(rows)
    if pad:
        rows = np.concatenate([rows, np.full(pad, rows[0], rows.dtype)])
        vals = np.concatenate(
            [vals, np.repeat(vals[:1], pad, axis=0)], axis=0
        )
    return rows.astype(np.int32), vals


class DeviceSnapshot:
    """Persistent per-device plane set for one store (see module doc).

    ``mesh`` (optional ``jax.sharding.Mesh``) makes the snapshot
    mesh-native: node planes commit with the node-axis NamedSharding
    (replicated only when the padded node axis does not divide the mesh
    — tiny clusters), the class tables replicate, and the dirty-row
    delta scatter inherits the sharded donated buffer, so each update
    touches only the owning shard.
    """

    def __init__(self, mesh=None):
        self.mesh = mesh
        self._node_shd = None
        self._rep_shd = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.mesh import NODES_AXIS

            self._node_shd = NamedSharding(mesh,
                                           PartitionSpec(NODES_AXIS))
            self._rep_shd = NamedSharding(mesh, PartitionSpec())
        # name -> device array, all planes sharing self._key.
        self._planes: Dict[str, object] = {}
        self._key: Optional[Tuple] = None
        # Two-phase class tables ([C, *], tiny), content-addressed.
        self._cls_planes: Dict[str, object] = {}
        self._cls_key: Optional[Tuple] = None
        # Telemetry (the flight record's ``solve`` counts, tests): full vs
        # delta vs hit counts.
        self.full_uploads = 0
        self.delta_uploads = 0
        self.hits = 0
        self.class_uploads = 0
        self.class_hits = 0
        # Host->device puts this snapshot made, and their bytes (the
        # nbytes of what was put; the cycle record's ``solve`` carries
        # each cycle's deltas).
        self.puts = 0
        self.put_bytes = 0
        # Extra scatter passes taken because a delta exceeded the
        # per-scatter staging budget (see budget_bytes).
        self.delta_chunks = 0

    # ------------------------------------------------------------ placement

    def _put_plane(self, a: np.ndarray):
        """Commit one full node plane: node-axis sharded on a mesh
        (when the axis divides), single default device otherwise."""
        self.puts += 1
        self.put_bytes += a.nbytes
        if self._node_shd is not None:
            n_dev = self.mesh.devices.size
            if a.ndim and a.shape[0] % n_dev == 0:
                return jax.device_put(a, self._node_shd)
            return jax.device_put(a, self._rep_shd)
        return jax.device_put(a)

    def _put_delta(self, rows: np.ndarray, vals: np.ndarray):
        """Commit a padded delta (replicated on a mesh: every chip needs
        the row ids to decide ownership; the values are tiny)."""
        self.puts += 2
        self.put_bytes += rows.nbytes + vals.nbytes
        if self._rep_shd is not None:
            return (jax.device_put(rows, self._rep_shd),
                    jax.device_put(vals, self._rep_shd))
        return rows, vals

    # ------------------------------------------------------------- planes

    # Called only from FastCycle._solve_inputs, inside the cycle's
    # ``with store._lock`` (holds: _lock) — the mirror delta reads and
    # resets below mutate store-guarded state.
    # holds: _lock
    def node_planes(self, m, key: Tuple,
                    build: Dict[str, Callable[[], np.ndarray]]):
        """Return ``{name: device_array}`` for the node-side planes.

        ``key`` is ``(epoch, shape components...)`` with the epoch FIRST;
        ``build[name](rows)`` returns the full padded host plane when
        ``rows`` is None, or just those rows' values for a delta scatter
        (only called on upload — a key hit touches no host memory).  All
        planes move together under one key."""
        if self._key == key and self._planes.keys() == build.keys():
            self.hits += 1
            return self._planes
        delta_rows = None
        if (
            self._key is not None
            and self._key[1:] == key[1:]
            and self._planes.keys() == build.keys()
        ):
            delta_rows = m.node_delta_rows(self._key[0])
            n_rows = key[1] if len(key) > 1 else 0
            if delta_rows is not None and (
                len(delta_rows) == 0
                or len(delta_rows) > max(1, int(n_rows))
                * DELTA_MAX_FRACTION
            ):
                delta_rows = None if len(delta_rows) else delta_rows
        if delta_rows is not None and len(delta_rows) == 0:
            # Epoch moved but no node rows recorded dirty (defensive —
            # epoch bumps outside the node table); planes are current.
            m.reset_node_delta()
            self._key = key
            self.hits += 1
            return self._planes
        if delta_rows is not None:
            for name, fn in build.items():
                # One-row probe sizes the plane's delta chunks (and
                # detects the delta-unprovable answer) without
                # materializing the full values array first.
                probe = fn(delta_rows[:1])
                if probe is None:
                    # Plane-level delta unprovable — a build fn returns
                    # None when its rows cannot be patched in place
                    # (class ids after the class SET changed: unrelated
                    # rows' ids shift under the sorted-signature
                    # ordering).  Re-upload just this plane; the others
                    # keep the scatter path.
                    self._planes[name] = self._put_plane(
                        np.asarray(fn(None))
                    )
                    continue
                # Chunked delta scatter (the scale-tier memory budget):
                # each chunk's host values stay under budget_bytes(),
                # so a churn burst at 100k nodes peaks at one chunk of
                # staging memory per plane, not the whole delta.
                row_nb = max(1, np.asarray(probe).nbytes)
                chunk = _chunk_rows_for(row_nb)
                if len(delta_rows) <= chunk:
                    dvals = probe if len(delta_rows) == 1 \
                        else fn(delta_rows)
                    rows, vals = _pad_delta(delta_rows,
                                            np.asarray(dvals))
                    rows, vals = self._put_delta(rows, vals)
                    self._planes[name] = _scatter_rows(
                        self._planes[name], rows, vals
                    )
                    continue
                # Multi-chunk: pad every chunk (incl. the last) to
                # exactly ``chunk`` rows with idempotent duplicates —
                # one compiled scatter per plane shape AND the staging
                # footprint stays AT the budget (_pad_delta's +25%
                # headroom bucket would double a full pow2 chunk past
                # it).
                n_chunks = 0
                for lo in range(0, len(delta_rows), chunk):
                    crows = delta_rows[lo:lo + chunk]
                    vals = np.asarray(fn(crows))
                    pad = chunk - len(crows)
                    if pad:
                        crows = np.concatenate(
                            [crows, np.full(pad, crows[0], crows.dtype)]
                        )
                        vals = np.concatenate(
                            [vals, np.repeat(vals[:1], pad, axis=0)],
                            axis=0,
                        )
                    rows, vals = self._put_delta(
                        crows.astype(np.int32), vals
                    )
                    self._planes[name] = _scatter_rows(
                        self._planes[name], rows, vals
                    )
                    n_chunks += 1
                self.delta_chunks += max(0, n_chunks - 1)
            m.reset_node_delta()
            self._key = key
            self.delta_uploads += 1
            return self._planes
        self._planes = {
            name: self._put_plane(np.asarray(fn(None)))
            for name, fn in build.items()
        }
        m.reset_node_delta()
        self._key = key
        self.full_uploads += 1
        return self._planes

    def resident_bytes(self) -> int:
        """Modeled device-resident footprint of the snapshot: the sum
        of every committed plane's (and class table's) nbytes.  The
        scale-tier budget test asserts this stays within the modeled
        envelope at 100k nodes, and peak TRANSIENT staging adds at most
        one ``budget_bytes()`` chunk on top (the chunked delta
        scatter)."""
        total = 0
        for group in (self._planes, self._cls_planes):
            for arr in group.values():
                size = int(np.prod(getattr(arr, "shape", ()) or (1,)))
                total += size * int(
                    np.dtype(getattr(arr, "dtype", np.uint8)).itemsize
                )
        return total

    def class_tables(self, key: Tuple,
                     build: Dict[str, Callable[[], np.ndarray]]):
        """Device-resident node-class tables for the two-phase solve
        ([C, *] rows — tiny next to the node planes).

        ``key`` is content-addressed (the nodeclass tables_sig digest +
        shape components), so epoch churn that leaves the class SET
        intact re-uploads nothing; a changed signature set re-uploads
        the tables wholesale.  The [N] ``class_id`` plane is NOT here:
        it rides ``node_planes``' dirty-row delta machinery, whose
        build fn answers None (-> single-plane full upload) whenever
        the signature set moved — the condition under which per-row
        class_id deltas would be unsound (see ops/nodeclass.py on the
        sorted-signature class ordering)."""
        if self._cls_key == key:
            self.class_hits += 1
            return self._cls_planes
        # Class tables are the COMPACTED [C, *] representation — tiny,
        # so a mesh replicates them (every chip classifies its own node
        # shard against the full table set).
        _put = (jax.device_put if self._rep_shd is None
                else (lambda a: jax.device_put(a, self._rep_shd)))
        tables = {name: np.asarray(fn()) for name, fn in build.items()}
        self.puts += len(tables)
        self.put_bytes += sum(a.nbytes for a in tables.values())
        self._cls_planes = {name: _put(a) for name, a in tables.items()}
        self._cls_key = key
        self.class_uploads += 1
        return self._cls_planes


def for_store(store, mesh=None) -> DeviceSnapshot:
    """The store's snapshot, created on first use.  ``mesh`` (the
    store's ``solve_mesh``) selects the mesh-sharded placement; a
    snapshot built for a different mesh (or none) is replaced wholesale
    — its planes live on the wrong device set."""
    snap = getattr(store, "device_snapshot", None)
    if snap is None or getattr(snap, "mesh", None) is not mesh:
        snap = store.device_snapshot = DeviceSnapshot(mesh=mesh)
    return snap
