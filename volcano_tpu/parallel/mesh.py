"""Device mesh + sharded allocate solve.

The framework's scale axis is the NODES dimension of the cluster arrays
(the reference scales with goroutine fan-out + adaptive node sampling,
scheduler_helper.go:43-118; we scale by sharding nodes over chips).  The
solver is pure SPMD-friendly: per-step work is elementwise over [N, R] with
one argmax reduction, so annotating the N-axis sharding lets GSPMD partition
the fori_loop body and insert the cross-chip reductions (the argmax becomes
a pmax tree over ICI).

Task/job/queue state stays replicated — it is tiny (O(P + J + Q) scalars)
next to the [N, R] node state, and every chip needs the winner of each step
anyway.

``dryrun_multichip`` in __graft_entry__.py drives this on a virtual CPU mesh;
``chip_smoke.py --chips 4`` drives it on the four chips of one TPU host.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

NODES_AXIS = "nodes"


def make_mesh(n_devices: Optional[int] = None, axis: str = NODES_AXIS,
              platform: Optional[str] = None) -> Mesh:
    """Build a 1-D device mesh over the nodes axis.

    ``platform`` pins the backend explicitly ("cpu", "tpu"); default is
    jax's default backend.  Callers that need the virtual CPU mesh (the
    multi-chip dryrun, the test suite) force the platform first with
    ``volcano_tpu.virtualcpu.force_virtual_cpu_platform``.  Raises when
    the backend has fewer than ``n_devices`` devices.
    """
    devices = jax.devices(platform) if platform is not None else jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise RuntimeError(
                f"mesh needs {n_devices} devices, backend has {len(devices)}"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


def mesh_from_env(store) -> Optional[Mesh]:
    """The store's solve mesh, or one built from ``VOLCANO_TPU_MESH=<n>``
    (the deploy-time enable knob: ``store.solve_mesh`` set explicitly
    always wins; unset/0/1 keeps the single-device path).  A value that
    is not an integer, or a backend with fewer than n devices, raises:
    a deployment that asked for n chips must not carry on on one."""
    mesh = getattr(store, "solve_mesh", None)
    if mesh is not None or getattr(store, "_mesh_env_checked", False):
        return mesh
    raw = os.environ.get("VOLCANO_TPU_MESH", "")
    try:
        n = int(raw) if raw else 0
    except ValueError:
        raise RuntimeError(
            f"VOLCANO_TPU_MESH={raw!r} is not an integer") from None
    if n >= 2:
        try:
            mesh = store.solve_mesh = make_mesh(n)
        except RuntimeError as e:
            raise RuntimeError(f"VOLCANO_TPU_MESH={raw}: {e}") from None
    store._mesh_env_checked = True
    return mesh


def shard_solve_args(mesh: Mesh, solve_args: Sequence, axis: str = NODES_AXIS):
    """Place solve() args on the mesh: every field of the SolveNodes group
    (and AffinityArgs.node_dom) is sharded on its leading N axis; task/job/
    queue state, weights, and the affinity count tensors are replicated
    (they are O(P + J + Q + E*D) scalars next to the [N, R] node state, and
    every chip needs the winner of each step anyway).

    solve()'s signature (ops/allocate.py): (nodes, tasks, jobs, queues,
    weights, eps, scalar_slot, aff).
    """
    node_sharded = NamedSharding(mesh, P(axis))  # leading dim = N
    replicated = NamedSharding(mesh, P())

    def rep(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(np.asarray(x), replicated), tree
        )

    node_bias = solve_args[8] if len(solve_args) > 8 else None
    nodes, tasks, jobs, queues, weights, eps, scalar_slot, aff = \
        solve_args[:8]
    nodes = type(nodes)(*[
        jax.device_put(np.asarray(x), node_sharded) for x in nodes
    ])
    aff = type(aff)(
        node_dom=jax.device_put(np.asarray(aff.node_dom), node_sharded),
        term_key=jax.device_put(np.asarray(aff.term_key), replicated),
        cnt0=jax.device_put(np.asarray(aff.cnt0), replicated),
        t_req_aff=jax.device_put(np.asarray(aff.t_req_aff), replicated),
        t_req_anti=jax.device_put(np.asarray(aff.t_req_anti), replicated),
        t_matches=jax.device_put(np.asarray(aff.t_matches), replicated),
        t_soft=jax.device_put(np.asarray(aff.t_soft), replicated),
    )
    out = (
        nodes, rep(tasks), rep(jobs), rep(queues), rep(weights),
        jax.device_put(np.asarray(eps), replicated),
        jax.device_put(np.asarray(scalar_slot), replicated),
        aff,
    )
    if node_bias is not None:
        out = out + (
            jax.device_put(np.asarray(node_bias), node_sharded),
        )
    return out


def sharded_solve(mesh: Mesh, solve_args: Sequence, axis: str = NODES_AXIS):
    """Run the sequential allocate solver with node state sharded over
    the mesh."""
    from ..ops.allocate import solve

    # Input shardings drive GSPMD partitioning; no explicit mesh context is
    # needed for jit with device_put-committed arguments.
    args = shard_solve_args(mesh, solve_args, axis)
    return solve(*args)


def sharded_solve_wave(mesh: Mesh, solve_args: Sequence,
                       axis: str = NODES_AXIS, wave: Optional[int] = None):
    """Run the production wave solver with node state sharded over the
    mesh: the per-attempt [UM, N] feasibility/score tensors partition on
    N, the top-k ranking becomes a cross-chip top-k over ICI, and the
    [W, W] prefix-acceptance matmuls stay replicated (W is mesh-size
    independent)."""
    from ..ops.wave import solve_wave

    args = shard_solve_args(mesh, solve_args, axis)
    kw = {} if wave is None else {"wave": wave}
    return solve_wave(*args, mesh_shards=int(mesh.devices.size), **kw)


# SolveNodes fields that move only with the NODE table (the mirror's
# epoch key), not per cycle.  On the fast path these now arrive as
# committed mesh-sharded arrays from the sharded devsnap
# (ops/devsnap.py — per-shard resident planes with shard-local delta
# scatters) and pass straight through; the plane cache below remains
# the fallback for callers that ship numpy planes, where it still
# skips the per-cycle device_put on an epoch hit.
_EPOCH_STABLE_NODE_FIELDS = frozenset(
    {"allocatable", "max_tasks", "ready", "label_bits", "taint_bits"}
)


def shard_wave_inputs(mesh: Mesh, solve_args: Sequence, pid, profiles,
                      axis: str = NODES_AXIS,
                      plane_cache: Optional[dict] = None,
                      epoch: Optional[int] = None,
                      node_classes=None):
    """Mesh placement for the fast path's pre-profiled wave inputs.

    Beyond the node-axis sharding of ``shard_solve_args``, the affinity
    COUNT tENSORS shard too — they are the hyperscale memory wall
    (an [E, D] int32 pair with D ~ N reaches GBs at 50k nodes; round-4
    root cause of the 16 GB-chip OOM), so replicating them would cap the
    cluster size one chip can hold regardless of mesh width:

    - ``aff.cnt0`` [E, D] shards on the DOMAIN axis (hostname domains
      are per-node, so D scales with N; XLA pads uneven shards),
    - the profile term tables (``t_req_aff``/``t_req_anti``/
      ``t_matches``/``t_soft`` [U, E]) shard on the TERM axis,
    - ``pid`` and the remaining profile rows are replicated (profile
      counts are tiny next to [*, N] and [E, D] state).

    The kernel's count-window contraction (cnt @ dom_ohT over D) then
    runs as partial products with an XLA-inserted reduce over ICI.

    ``plane_cache`` (with ``epoch``) keeps the epoch-stable node planes
    and ``aff.node_dom`` resident on the mesh across cycles: a hit skips
    their host->device transfer entirely (pass the same dict every
    cycle; the fast path parks one on the store).
    """
    node_sharded = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())
    col_sharded = NamedSharding(mesh, P(None, axis))

    # The slim fast path appends a 9th element (the [N] f32 topology
    # node-order bias, ops/topology.contig_bias) only when a fabric
    # constraint is live; it shards with the node axis like any other
    # node plane.  The 8-tuple form stays byte-identical to before.
    node_bias = solve_args[8] if len(solve_args) > 8 else None
    nodes, tasks, jobs, queues, weights, eps, scalar_slot, aff = \
        solve_args[:8]
    idle_in = nodes.idle
    n_nodes = int(idle_in.shape[0] if hasattr(idle_in, "shape")
                  else np.asarray(idle_in).shape[0])

    def put_node(x):
        # Mesh-resident planes (the sharded devsnap, ops/devsnap.py)
        # arrive committed with the node-axis sharding already: hand
        # them straight through — np.asarray here would be a full
        # device->host->device round trip of every plane every cycle,
        # exactly the re-shipping this path exists to remove.
        if isinstance(x, jax.Array) and not isinstance(x, np.ndarray):
            return x
        # The slim fast path ships [1, R] broadcast dummies for
        # releasing/pipelined; those replicate (a 1-row axis cannot
        # shard over the mesh).
        a = np.asarray(x)
        sh = node_sharded if (a.ndim and a.shape[0] == n_nodes
                              and a.shape[0] % mesh.devices.size == 0) \
            else replicated
        return jax.device_put(a, sh)

    def put_node_cached(name, x):
        # Committed mesh arrays (sharded devsnap) ARE the persistent
        # per-device planes — no cache entry needed.
        if isinstance(x, jax.Array) and not isinstance(x, np.ndarray):
            return x
        # Persistent per-device plane: re-ship only when the node table
        # (epoch) or the padded shape moved.  The mesh IDENTITY is part
        # of the key (not just its size): a store whose solve_mesh is
        # replaced by a different same-sized mesh must not hand the jit
        # arrays committed to the old mesh's sharding — the composed
        # profile swaps meshes within one process.
        if plane_cache is None or epoch is None:
            return put_node(x)
        a = np.asarray(x)
        key = (epoch, a.shape, a.dtype.str, mesh.devices.size, id(mesh))
        hit = plane_cache.get(name)
        if hit is not None and hit[0] == key:
            return hit[1]
        arr = put_node(a)
        plane_cache[name] = (key, arr)
        return arr

    n_mesh = mesh.devices.size

    def put_cols(x):
        # Shard axis 1, zero-padding it up to a mesh multiple (padded
        # domain/term columns are inert: domain ids and term windows
        # only ever index the original range).  Tables too small to
        # split stay replicated.
        a = np.asarray(x)
        if a.ndim < 2 or a.shape[1] < n_mesh:
            return jax.device_put(a, replicated)
        pad = (-a.shape[1]) % n_mesh
        if pad:
            a = np.concatenate(
                [a, np.zeros((a.shape[0], pad, *a.shape[2:]), a.dtype)],
                axis=1,
            )
        return jax.device_put(a, col_sharded)

    nodes = type(nodes)(*[
        put_node_cached(name, x)
        if name in _EPOCH_STABLE_NODE_FIELDS else put_node(x)
        for name, x in zip(type(nodes)._fields, nodes)
    ])
    aff = type(aff)(
        node_dom=put_node_cached("node_dom", aff.node_dom),
        term_key=jax.device_put(np.asarray(aff.term_key), replicated),
        cnt0=put_cols(aff.cnt0),
        t_req_aff=jax.device_put(np.asarray(aff.t_req_aff), replicated),
        t_req_anti=jax.device_put(np.asarray(aff.t_req_anti), replicated),
        t_matches=jax.device_put(np.asarray(aff.t_matches), replicated),
        t_soft=jax.device_put(np.asarray(aff.t_soft), replicated),
    )
    rep = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.device_put(np.asarray(x), replicated), tree
    )
    profiles = type(profiles)(
        req=jax.device_put(np.asarray(profiles.req), replicated),
        init_req=jax.device_put(np.asarray(profiles.init_req), replicated),
        ports=jax.device_put(np.asarray(profiles.ports), replicated),
        sel_bits=jax.device_put(np.asarray(profiles.sel_bits), replicated),
        aff_bits=jax.device_put(np.asarray(profiles.aff_bits), replicated),
        aff_terms=jax.device_put(np.asarray(profiles.aff_terms),
                                 replicated),
        tol_bits=jax.device_put(np.asarray(profiles.tol_bits), replicated),
        pref_bits=jax.device_put(np.asarray(profiles.pref_bits),
                                 replicated),
        pref_w=jax.device_put(np.asarray(profiles.pref_w), replicated),
        t_req_aff=put_cols(profiles.t_req_aff),
        t_req_anti=put_cols(profiles.t_req_anti),
        t_matches=put_cols(profiles.t_matches),
        t_soft=put_cols(profiles.t_soft),
    )
    args = (
        nodes, rep(tasks), rep(jobs), rep(queues), rep(weights),
        jax.device_put(np.asarray(eps), replicated),
        jax.device_put(np.asarray(scalar_slot), replicated),
        aff,
    )
    if node_bias is not None:
        args = args + (put_node(node_bias),)
    pid = jax.device_put(np.asarray(pid), replicated)
    if node_classes is not None:
        # Two-phase planes: the [N] class_id shards with the node axis
        # (it IS a node column); the [C, *] class tables and the [U, S]
        # shortlists the solver derives from them stay replicated —
        # they are the COMPACTED representations (C, S << N), which is
        # exactly why the mesh no longer has to move full [UM, N]
        # planes between chips per attempt.  class_id is epoch-stable,
        # so it rides the persistent plane cache.
        node_classes = type(node_classes)(
            class_id=put_node_cached("class_id", node_classes.class_id),
            label_bits=put_node_cached("cls_label_bits",
                                       node_classes.label_bits),
            taint_bits=put_node_cached("cls_taint_bits",
                                       node_classes.taint_bits),
            ready=put_node_cached("cls_ready", node_classes.ready),
        )
    return args, pid, profiles, node_classes


def sharded_solve_wave_cycle(mesh: Mesh, solve_args: Sequence, pid,
                             profiles, axis: str = NODES_AXIS,
                             wave: Optional[int] = None,
                             plane_cache: Optional[dict] = None,
                             epoch: Optional[int] = None,
                             taint_any=None,
                             node_classes=None,
                             devincr=None):
    """The fast path's solve dispatch on a mesh (FastCycle._allocate when
    ``store.solve_mesh`` is set): pre-profiled inputs, node axis + count
    tensors sharded per ``shard_wave_inputs``; epoch-stable planes
    (including the two-phase class planes) stay mesh-resident across
    cycles via ``plane_cache``.  ``devincr`` (ISSUE 9) threads the
    store's device-incremental context through — its persistent static
    planes and warm-shortlist candidates live replicated on this mesh
    (``DeviceIncremental.set_mesh``, called by the fast path before the
    dispatch), so a mesh change voids them via the placement token."""
    from ..ops.wave import solve_wave

    args, pid, profiles, node_classes = shard_wave_inputs(
        mesh, solve_args, pid, profiles, axis,
        plane_cache=plane_cache, epoch=epoch, node_classes=node_classes,
    )
    kw = {} if wave is None else {"wave": wave}
    return solve_wave(*args, pid=pid, profiles=profiles,
                      taint_any=taint_any, node_classes=node_classes,
                      mesh_shards=int(mesh.devices.size),
                      devincr=devincr, **kw)
