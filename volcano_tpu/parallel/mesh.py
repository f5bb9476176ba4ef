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

A deployment switches it on in its own ``volcano-scheduler.conf``: the
``allocate`` action's argument ``mesh: <n>`` (``mesh_from_env`` below; the
environment variable ``VOLCANO_TPU_MESH`` is the deploy-time override for
a conf that names none, an embedder's ``store.solve_mesh`` wins over both).

``dryrun_multichip`` in __graft_entry__.py drives this on a virtual CPU mesh;
``chip_smoke.py --chips 4`` and the benchmark's ``hyper-50k.burst`` (through
the scheduler's normal path) drive it on the four chips of one TPU host.
"""

from __future__ import annotations

import contextlib
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


def mesh_from_env(store, conf_mesh: Optional[str] = None) -> Optional[Mesh]:
    """The mesh a store's solves run on, or None for one device.

    Three places may name it, the first that does wins: an embedder's
    ``store.solve_mesh`` (a prebuilt ``Mesh``); the deployment's own
    ``volcano-scheduler.conf``, ``conf_mesh`` being the ``mesh``
    argument of its ``allocate`` action (``configurations: [{name:
    allocate, arguments: {mesh: 4}}]``; ``0`` / ``1`` say one device);
    and, where the conf names none, ``VOLCANO_TPU_MESH=<n>`` from the
    environment (unset/0/1: one device).  A value that is not an
    integer, or a backend with fewer than n devices, raises: a
    deployment that asked for n chips must not carry on on one.

    The mesh is built once per store and value.  When a conf reload
    (or the environment) changes the value, the mesh the resolver had
    built is replaced and the mesh plane cache dropped with it;
    devincr's placement token and the device snapshot follow the mesh's
    identity by themselves (``DeviceIncremental.set_mesh``,
    ``devsnap.for_store``)."""
    # An embedder's mesh is known from the resolver's own by identity
    # (JAX interns equal meshes: one set after the resolver built an
    # equal one reads as the resolver's, and follows the conf).
    mesh = getattr(store, "solve_mesh", None)
    resolved = getattr(store, "_mesh_resolved", None)  # (asked, mesh built)
    if mesh is not None and (resolved is None or mesh is not resolved[1]):
        return mesh  # the embedder's
    conf_mesh = "" if conf_mesh is None else str(conf_mesh).strip()
    asked = (conf_mesh, "" if conf_mesh
             else os.environ.get("VOLCANO_TPU_MESH", ""))
    if resolved is not None and resolved[0] == asked:
        store.solve_mesh = resolved[1]
        return resolved[1]
    raw = asked[0] or asked[1]
    said = (f"allocate argument mesh: {raw}" if asked[0]
            else f"VOLCANO_TPU_MESH={raw}")
    try:
        n = int(raw) if raw else 0
    except ValueError:
        raise RuntimeError(f"{said} is not an integer") from None
    mesh = None
    if n >= 2:
        try:
            mesh = make_mesh(n)
        except RuntimeError as e:
            raise RuntimeError(f"{said}: {e}") from None
    if resolved is not None:
        # The value moved under a live store: whatever was placed for
        # the old mesh (or for one device) must not meet the new one.
        cache = getattr(store, "_mesh_plane_cache", None)
        if cache:
            cache.clear()
    store.solve_mesh = mesh
    store._mesh_resolved = (asked, mesh)
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
                      node_classes=None,
                      placed: Optional[dict] = None):
    """Mesh placement for the fast path's pre-profiled wave inputs.

    Beyond the node-axis sharding of ``shard_solve_args``, the affinity
    COUNT tENSORS shard too — they are the hyperscale memory wall
    (an [E, D] int32 pair with D ~ N reaches GBs at 50k nodes; round-4
    root cause of the 16 GB-chip OOM), so replicating them would cap the
    cluster size one chip can hold regardless of mesh width:

    - ``aff.cnt0`` [E, D] shards on the DOMAIN axis (hostname domains
      are per-node, so D scales with N).  It is not placed here: it
      stays what the caller handed over (the fast path's
      ``CountEntries``, a host table), and ``solve_wave`` reads it
      (feature bit, entries) and then has the table born or placed on
      the mesh under the domain-axis sharding this function returns
      (``solve_wave``'s ``cnt0_sharding``).  A placement here was
      fetched straight back: 820 MB of zeros at 50,000 nodes;
    - ``pid`` and ``profiles`` (the fast path's ``SparseProfiles``: the
      per-profile rows and the term tables' entries) stay on the host
      as well: ``solve_wave`` pads and windows them and (past
      ``PROF_SPARSE_MIN``) has the term tables born on the device, so
      a placement here was fetched straight back; the jit replicates
      them (profile counts are tiny next to [*, N] and [E, D] state).

    The kernel's count-window read (``ops/wave.count_plane``: row
    gathers along the sharded D) then crosses chips by the collectives
    XLA's partitioner inserts.

    ``plane_cache`` (with ``epoch``) keeps the epoch-stable node planes
    and ``aff.node_dom`` resident on the mesh across cycles: a hit skips
    their host->device transfer entirely (pass the same dict every
    cycle; the fast path parks one on the store).

    ``placed`` (optional dict) receives what this call moved: ``arrays``
    / ``bytes`` handed to ``jax.device_put``, ``cache_hits`` of the
    plane cache, and ``resident_bytes`` found on the mesh already (those
    hits and the committed planes of the sharded devsnap).

    Returns ``(args, pid, profiles, node_classes, cnt0_sharding)``.
    """
    node_sharded = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())
    col_sharded = NamedSharding(mesh, P(None, axis))
    tally = {"arrays": 0, "bytes": 0, "cache_hits": 0, "resident_bytes": 0}

    def put(a, sharding):
        tally["arrays"] += 1
        tally["bytes"] += int(a.nbytes)
        return jax.device_put(a, sharding)

    def resident(x):
        tally["resident_bytes"] += int(x.nbytes)
        return x

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
            return resident(x)
        # The slim fast path ships [1, R] broadcast dummies for
        # releasing/pipelined; those replicate (a 1-row axis cannot
        # shard over the mesh).
        a = np.asarray(x)
        sh = node_sharded if (a.ndim and a.shape[0] == n_nodes
                              and a.shape[0] % mesh.devices.size == 0) \
            else replicated
        return put(a, sh)

    def put_node_cached(name, x):
        # Committed mesh arrays (sharded devsnap) ARE the persistent
        # per-device planes — no cache entry needed.
        if isinstance(x, jax.Array) and not isinstance(x, np.ndarray):
            return resident(x)
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
            tally["cache_hits"] += 1
            return resident(hit[1])
        arr = put_node(a)
        plane_cache[name] = (key, arr)
        return arr

    def rep(tree):
        return jax.tree_util.tree_map(
            lambda x: put(np.asarray(x), replicated), tree)

    nodes = type(nodes)(*[
        put_node_cached(name, x)
        if name in _EPOCH_STABLE_NODE_FIELDS else put_node(x)
        for name, x in zip(type(nodes)._fields, nodes)
    ])
    aff = type(aff)(
        node_dom=put_node_cached("node_dom", aff.node_dom),
        term_key=put(np.asarray(aff.term_key), replicated),
        cnt0=aff.cnt0,
        t_req_aff=put(np.asarray(aff.t_req_aff), replicated),
        t_req_anti=put(np.asarray(aff.t_req_anti), replicated),
        t_matches=put(np.asarray(aff.t_matches), replicated),
        t_soft=put(np.asarray(aff.t_soft), replicated),
    )
    args = (
        nodes, rep(tasks), rep(jobs), rep(queues), rep(weights),
        put(np.asarray(eps), replicated),
        put(np.asarray(scalar_slot), replicated),
        aff,
    )
    if node_bias is not None:
        args = args + (put_node(node_bias),)
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
    if placed is not None:
        placed.update(tally)
    return args, pid, profiles, node_classes, col_sharded


def sharded_solve_wave_cycle(mesh: Mesh, solve_args: Sequence, pid,
                             profiles, axis: str = NODES_AXIS,
                             wave: Optional[int] = None,
                             plane_cache: Optional[dict] = None,
                             epoch: Optional[int] = None,
                             taint_any=None,
                             node_classes=None,
                             devincr=None,
                             shape_marks: Optional[dict] = None,
                             shard_span=contextlib.nullcontext,
                             placed: Optional[dict] = None):
    """The fast path's solve dispatch on a mesh (FastCycle._allocate when
    the deployment's conf, ``store.solve_mesh`` or the environment names
    one, ``mesh_from_env``): pre-profiled inputs, node axis + count
    tensors sharded per ``shard_wave_inputs``; epoch-stable planes
    (including the two-phase class planes) stay mesh-resident across
    cycles via ``plane_cache``.  ``devincr`` (ISSUE 9) threads the
    store's device-incremental context through — its persistent static
    planes and warm-shortlist candidates live replicated on this mesh
    (``DeviceIncremental.set_mesh``, called by the fast path before the
    dispatch), so a mesh change voids them via the placement token.
    ``shape_marks`` is ``solve_wave``'s: the store's high-water shape
    buckets, as the one-device dispatch passes them.  ``shard_span``
    opens the caller's span over the placement alone (``device:shard``)
    and ``placed`` receives its counts (``shard_wave_inputs``)."""
    from ..ops.wave import solve_wave

    placed = {} if placed is None else placed
    with shard_span() as sp:
        args, pid, profiles, node_classes, cnt0_sharding = shard_wave_inputs(
            mesh, solve_args, pid, profiles, axis,
            plane_cache=plane_cache, epoch=epoch, node_classes=node_classes,
            placed=placed,
        )
        if sp is not None:
            sp.args = {k: placed[k]
                       for k in ("arrays", "bytes", "cache_hits")}
    kw = {} if wave is None else {"wave": wave}
    return solve_wave(*args, pid=pid, profiles=profiles,
                      taint_any=taint_any, node_classes=node_classes,
                      mesh_shards=int(mesh.devices.size),
                      devincr=devincr, shape_marks=shape_marks,
                      cnt0_sharding=cnt0_sharding, **kw)
