"""chip_smoke.py — does the scheduler still start on the chip?

One process drives the system's main path once through the entry points a
user calls, at the north-star width (10,000 nodes x 100,000 pending pods,
BASELINE.json's headline shape), and checks what comes out by the repo's
own means.  It asserts no speed; the host-clock times it prints are
information.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the four chips of one host (mesh)

Phases (each prints one JSON line naming the device and the versions):

  device     default backend is a TPU with the asked-for chip count; the
             native bridge is the one built from csrc/ as it stands
  parity     small seeded clusters: fast path on the chip vs the
             pure-Python object session, bind for bind
  service    a 3-replica gang Job through Service(simulate=True):
             admission -> controller -> scheduler thread -> bind -> Running
  north_sync one cold cycle (set-up seconds) then fresh-store cycles under
             CONF_BASE; each binds every pod
  north_pipe one store, store.pipeline=True, re-pend feed: donated devsnap
             buffer under an in-flight solve, warm shortlists, a node
             relabel between cycles, no compile after warm-up

With --chips 4 the two north phases run on one chip and then on
make_mesh(4), and the binds must be equal.  Any failed check raises, so
the exit status is non-zero and no result line follows.  The last stdout
line of a green run is {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
from dataclasses import dataclass


# The north star's scheduler conf (the benchmark's config files carry
# the same text).
CONF_BASE = """
actions: "enqueue, allocate, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""


@dataclass(frozen=True)
class Shape:
    """Cluster size of the north phases; tests shrink it."""

    n_nodes: int = 10000
    n_pods: int = 100000
    gang_size: int = 8
    zones: int = 16
    sync_cycles: int = 2
    pipe_cycles: int = 6
    touch_nodes: int = 4


class _LogCapture(logging.Handler):
    """WARNING+ records of the package, kept so a phase can refuse a run
    that logged a deleted-buffer use or a device recovery."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.messages = []

    def emit(self, record):
        self.messages.append(record.getMessage())


class _Compiles:
    """Names of the programs JAX lowered, via jax.monitoring (one event
    per new jit specialization, whether the backend then compiled it or
    loaded it from the persistent cache)."""

    _EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self._EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _emit(phase, env, **fields):
    print(json.dumps({"phase": phase, **fields, "device": env["device"],
                      "versions": env["versions"]}), flush=True)


def _check_same_binds(label, got, want, versus):
    """Bind-for-bind: the same pods bound, each to the same node."""
    moved = [k for k in got.keys() | want.keys() if got.get(k) != want.get(k)]
    _check(not moved, f"{label}: {len(moved)} binds differ from {versus}, "
                      f"e.g. {sorted(moved)[:3]}")


def _check_fast_cycle(rec, label):
    _check(rec.path == "fast" and rec.error is None,
           f"{label}: path {rec.path!r}, error {rec.error}")


def _lanes_ms(rec) -> dict:
    return {k: round(v * 1e3, 1) for k, v in rec.lanes.items() if v >= 5e-4}


def _mesh_size(mesh) -> int:
    return 1 if mesh is None else int(mesh.devices.size)


# ------------------------------------------------------------------ device


def phase_device(chips: int, platform: str = "tpu") -> dict:
    """Fail before any store is built unless the default backend is
    ``platform`` with ``chips`` devices and the native bridge is the
    library native.py built from the sources in csrc/."""
    from volcano_tpu import device, native

    info = device.device_info()
    _check(info["platform"] == platform,
           f"default backend is {info['platform']!r}, need {platform!r}")
    _check(info["count"] >= chips,
           f"{info['count']} {platform} device(s), need {chips}")
    _check(native.native_available(),
           "native bridge did not build (NumPy stand-in active)")
    _check(native.loaded_path() == native.built_lib_path(),
           f"native bridge loaded from {native.loaded_path()}, not the "
           f"library built from csrc/ ({native.built_lib_path()})")
    env = {"device": info, "versions": device.versions()}
    _emit("device", env, ok=True, native=native.loaded_path().name)
    return env


def _on_platform(arr, platform: str) -> bool:
    return all(d.platform == platform for d in arr.devices())


# ------------------------------------------------------------------ parity


def _small_cycle(fast: bool, seed: int, **kw) -> dict:
    from volcano_tpu.scheduler import Scheduler
    from volcano_tpu.synth import synthetic_cluster

    os.environ["VOLCANO_TPU_FASTPATH"] = "1" if fast else "0"
    try:
        store = synthetic_cluster(seed=seed, **kw)
        Scheduler(store).run_once()
        store.flush_binds()
        rec = store.flight.recent()[-1]
        _check(rec.path == ("fast" if fast else "object"),
               f"cycle ran on the {rec.path!r} path")
        binds = dict(store.binder.binds)
        store.close()
        return binds
    finally:
        os.environ.pop("VOLCANO_TPU_FASTPATH", None)


# The two shapes __graft_entry__._cycle pins on the virtual CPU mesh.
PARITY_SHAPES = (
    ("plain", 17, dict(n_nodes=64, n_pods=512, gang_size=4)),
    ("affinity", 23, dict(n_nodes=64, n_pods=256, gang_size=4, zones=4,
                          affinity_fraction=0.25,
                          anti_affinity_fraction=0.25,
                          spread_fraction=0.25)),
)


def phase_parity(env) -> None:
    """Fast path on the device vs the object session (pure Python):
    the bind dicts must be equal."""
    out = {}
    for label, seed, kw in PARITY_SHAPES:
        fast = _small_cycle(True, seed, **kw)
        _check_same_binds(label, fast, _small_cycle(False, seed, **kw),
                          "the object session")
        _check(len(fast) > 0, f"{label}: nothing bound")
        out[label] = len(fast)
    _emit("parity", env, ok=True, bound=out)


# ----------------------------------------------------------------- service


def phase_service(env) -> None:
    """BASELINE.json configs[0]'s path: a 3-replica gang Job submitted
    through admission reaches Running, scheduled by the service's own loop."""
    from volcano_tpu.api import Node
    from volcano_tpu.controllers.apis import Job, TaskSpec
    from volcano_tpu.service import Service

    svc = Service(simulate=True, schedule_period=0.01,
                  controller_period=0.005)
    for i in range(2):
        svc.store.add_node(Node(
            name=f"node-{i}",
            allocatable={"cpu": "8", "memory": "16Gi", "pods": 64}))
    job = Job(name="smoke-job", min_available=3, tasks=[TaskSpec(
        name="worker", replicas=3,
        containers=[{"cpu": "1", "memory": "1Gi"}])])
    svc.start(http_port=0)
    try:
        t0 = time.perf_counter()
        svc.admitted.add_batch_job(job)
        running = 0
        while time.perf_counter() - t0 < 300.0 and running < 3:
            time.sleep(0.005)
            running = sum(
                1 for p in list(svc.store.pods.values())
                if p.owner_job == job.key and p.phase == "Running")
        wall = time.perf_counter() - t0
    finally:
        svc.stop()
    _check(running >= 3, f"only {running}/3 pods Running after 300 s")
    recs = svc.store.flight.recent()
    _check(all(r.path == "fast" and r.error is None for r in recs),
           "a service cycle left the fast path or failed: "
           f"{[(r.path, r.error) for r in recs if r.path != 'fast' or r.error]}")
    _check(sum(r.pods_bound for r in recs) == 3,
           f"fast path bound {sum(r.pods_bound for r in recs)} pods, not 3")
    _check(any("device" in r.lanes for r in recs), "no device lane")
    _emit("service", env, ok=True, pods_running=running,
          submit_to_running_host_s=round(wall, 3))


# ------------------------------------------------------------- north star


def _north_store(shape: Shape, seed: int, mesh):
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(
        n_nodes=shape.n_nodes, n_pods=shape.n_pods,
        gang_size=shape.gang_size, zones=shape.zones, seed=seed)
    # Async bind dispatch, as in production.
    store.async_bind = True
    if mesh is not None:
        store.solve_mesh = mesh
    return store


def _check_placement(store, shape: Shape) -> dict:
    """Every pod bound, no node over its allocatable, every gang whole —
    recomputed here from the binder's record and the pod specs, not
    taken from the scheduler's own accounting."""
    store.flush_binds()
    binds = dict(store.binder.binds)
    _check(len(binds) == shape.n_pods,
           f"bound {len(binds)} of {shape.n_pods}")
    used = {}
    gangs = {}
    for key, pod in store.pods.items():
        host = binds.get(key)
        gang = gangs.setdefault(pod.job_id(), [0, 0])
        gang[1] += 1
        if host is None:
            continue
        gang[0] += 1
        req = pod.resource_request()
        u = used.setdefault(host, [0.0, 0.0, 0])
        u[0] += req.milli_cpu
        u[1] += req.memory
        u[2] += 1
    for host, (cpu, mem, n) in used.items():
        alloc = store.nodes[host].allocatable
        _check(cpu <= alloc.milli_cpu + 1e-3 and mem <= alloc.memory + 1e-3
               and n <= alloc.max_task_num,
               f"node {host} oversubscribed: cpu {cpu}/{alloc.milli_cpu} "
               f"mem {mem}/{alloc.memory} pods {n}/{alloc.max_task_num}")
    split = [g for g, (b, n) in gangs.items() if 0 < b < n]
    _check(not split, f"{len(split)} gangs split, e.g. {split[:3]}")
    return binds


def _check_device_side(store, env, mesh) -> None:
    """The planes the solve read and the arrays it produced live on the
    expected platform; under a mesh every node plane has one shard on
    each of its devices."""
    platform = env["device"]["platform"]
    snap = store.device_snapshot
    _check(snap is not None and snap._planes, "no device snapshot")
    for name, plane in snap._planes.items():
        _check(_on_platform(plane, platform),
               f"devsnap plane {name} on {plane.devices()}")
        if mesh is not None:
            devs = {s.device for s in plane.addressable_shards}
            _check(len(devs) == mesh.devices.size
                   and not plane.sharding.is_fully_replicated,
                   f"devsnap plane {name}: {len(devs)} device(s), "
                   f"sharding {plane.sharding}")
    dv = store._devincr_cache
    _check(dv is not None and dv._cand is not None,
           "two-phase shortlist never ran")
    for arr in dv._cand:
        _check(_on_platform(arr, platform),
               f"shortlist output on {arr.devices()}")


def _crash_recoveries() -> float:
    from volcano_tpu.metrics import metrics

    return sum(metrics.device_crash_recoveries.data.values())


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_north_sync(env, shape: Shape, seed: int, mesh=None) -> dict:
    """Scheduler(store, conf_str=CONF_BASE).run_once() on fresh
    stores: one cold cycle, then ``shape.sync_cycles`` more.  Returns
    {seed: binds} for the mesh-vs-one-chip comparison."""
    from volcano_tpu.scheduler import Scheduler

    crashes0 = _crash_recoveries()
    times = []
    lanes = []
    all_binds = {}
    for i in range(1 + shape.sync_cycles):
        store = _north_store(shape, seed + i, mesh)
        t0 = time.perf_counter()
        Scheduler(store, conf_str=CONF_BASE).run_once()
        times.append(time.perf_counter() - t0)
        all_binds[seed + i] = _check_placement(store, shape)
        rec = store.flight.recent()[-1]
        _check_fast_cycle(rec, f"cycle {i}")
        _check(rec.lanes.get("device", 0.0) > 0.0,
               f"cycle {i}: no device lane in {sorted(rec.lanes)}")
        _check(rec.pods_bound == shape.n_pods,
               f"cycle {i}: flight record bound {rec.pods_bound}")
        lanes.append(_lanes_ms(rec))
        _check_device_side(store, env, mesh)
        store.close()
        del store
    _check(_crash_recoveries() == crashes0, "device crash recovery ran")
    _emit("north_sync", env, ok=True,
          mesh=_mesh_size(mesh),
          nodes=shape.n_nodes, pods=shape.n_pods,
          bound_per_cycle=shape.n_pods,
          setup_host_s=round(times[0], 3),
          cycles_host_ms=[round(t * 1e3, 1) for t in times[1:]],
          lanes_host_ms=lanes[1:], peak_bytes_in_use=_peak_bytes())
    return all_binds


def phase_north_pipelined(env, shape: Shape, seed: int, compiles: _Compiles,
                          mesh=None) -> dict:
    """One store, pipelined, with a feed that re-pends every bound
    pod: every cycle commits the previous cycle's
    in-flight solve and dispatches the next.

    A few nodes' pods are held back from the re-pend for one cycle and
    released the next.  Either step changes the pending set, which
    re-ranks in full; the cycle after the release sees the same pending
    set with those nodes dirty, so the warm-shortlist kernel re-ranks
    only their block.  The five warm-up cycles (compile, pipeline fill,
    hold, release, warm) give every program its first use; the steady
    window repeats hold, release, warm, then relabels a few nodes through
    the event API — the delta scatter into the donated devsnap buffer,
    while the previous solve is still in flight — and runs on.
    """
    import numpy as np

    from volcano_tpu.api import Node, TaskStatus
    from volcano_tpu.scheduler import Scheduler

    st_bound = int(TaskStatus.Bound)
    platform = env["device"]["platform"]
    crashes0 = _crash_recoveries()
    store = _north_store(shape, seed, mesh)
    store.pipeline = True
    hold = {"rows": np.zeros(0, np.int64)}

    def feed(fc):
        m = fc.m
        rows = np.flatnonzero(
            (m.p_status[:fc.Pn] == st_bound) & m.p_alive[:fc.Pn])
        if len(hold["rows"]):
            rows = rows[~np.isin(m.p_node[rows], hold["rows"])]
        if len(rows):
            fc._unbind_rows(rows)

    store.cycle_feed = feed
    sched = Scheduler(store, conf_str=CONF_BASE)
    dv_modes = []

    def cycle():
        t0 = time.perf_counter()
        sched.run_once()
        dt = time.perf_counter() - t0
        rec = store.flight.recent()[-1]
        _check_fast_cycle(rec, "pipelined cycle")
        inflight = store._inflight_solve
        if inflight is not None:
            _check(_on_platform(inflight.payload.assigned, platform),
                   "in-flight result on "
                   f"{inflight.payload.assigned.devices()}")
        dv = store._devincr_cache
        dv_modes.append((dv.last_mode, dv.last_blocks[0]))
        return dt, rec

    _check(shape.pipe_cycles >= 6, "the steady window needs 6 cycles")
    none = np.zeros(0, np.int64)
    held = np.arange(shape.touch_nodes, dtype=np.int64)
    warm_times = []
    for rows in (none, none, held, none, none):
        hold["rows"] = rows
        warm_times.append(cycle()[0])

    snap = store.device_snapshot
    deltas0 = snap.delta_uploads
    mark = len(compiles.names)
    modes0 = len(dv_modes)
    times = []
    bound = []
    lanes = []
    for i in range(shape.pipe_cycles):
        hold["rows"] = held if i == 0 else none
        if i == 3:
            # Move a few nodes to the next zone: a label change that
            # keeps the node-class SET (so the padded class axis, and
            # with it every compiled shape, stays put) but bumps the
            # epoch and dirties those rows.
            # The feed re-pends mirror rows and leaves the pod RECORDS
            # bound, so the object model the event API rebuilds on this
            # call is stale by construction and says so per pod; the
            # fast path reads the mirror, not that model.
            store_log = logging.getLogger("volcano_tpu.cache.store")
            level = store_log.level
            store_log.setLevel(logging.CRITICAL)
            try:
                for n in range(shape.touch_nodes):
                    node = store.mirror.node_objs[n]
                    store.update_node(Node(
                        name=node.name,
                        allocatable=dict(node.allocatable),
                        labels={"zone":
                                f"zone-{(n + 1) % shape.zones}"}))
            finally:
                store_log.setLevel(level)
        dt, rec = cycle()
        times.append(dt)
        bound.append(rec.pods_bound)
        lanes.append(_lanes_ms(rec))
    # Drain: feed off, the last in-flight solve commits.
    store.cycle_feed = None
    sched.run_once()
    binds = _check_placement(store, shape)
    _check(store._inflight_solve is None, "a solve is still in flight")

    steady = compiles.names[mark:]
    extra = [n for n in steady if "_scatter_rows" not in n]
    _check(not extra, f"compiled after warm-up: {extra}")
    modes = dv_modes[modes0:]
    _check(any(m == "warm" and b > 0 for m, b in modes),
           f"warm-shortlist kernel never ran in the steady window: {modes}")
    _check(snap.delta_uploads > deltas0,
           "node relabel did not take the devsnap delta scatter "
           f"(full={snap.full_uploads} delta={snap.delta_uploads})")
    _check(store.device_snapshot is snap, "device snapshot was replaced")
    stats = store.auditor.audit_stats()
    _check(stats["anomalies"] == 0 and not store.auditor.anomalies(),
           f"auditor anomalies: {store.auditor.anomalies()}")
    _check(_crash_recoveries() == crashes0, "device crash recovery ran")
    _check_device_side(store, env, mesh)
    dv = store._devincr_cache
    _emit("north_pipe", env, ok=True,
          mesh=_mesh_size(mesh),
          nodes=shape.n_nodes, pods=shape.n_pods,
          setup_host_s=round(sum(warm_times), 3),
          cycles_host_ms=[round(t * 1e3, 1) for t in times],
          lanes_host_ms=lanes,
          bound_per_cycle=bound, devincr=dict(dv.counts),
          devincr_modes=modes,
          devsnap={"full": snap.full_uploads, "delta": snap.delta_uploads,
                   "hits": snap.hits},
          compiled_in_window=steady, audited_cycles=stats["cycles"],
          peak_bytes_in_use=_peak_bytes())
    store.close()
    return binds


# -------------------------------------------------------------------- main


def run(chips: int, seed: int, shape: Shape = Shape(),
        platform: str = "tpu") -> dict:
    """All phases in order; returns the final result object."""
    t_start = time.perf_counter()
    # A fast-path failure must fail the run, never fall back to the
    # object session (scheduler.py reads this per cycle).
    os.environ["VOLCANO_TPU_FALLBACK"] = "never"
    capture = _LogCapture()
    logging.getLogger("volcano_tpu").addHandler(capture)
    try:
        env = phase_device(chips, platform)
        compiles = _Compiles()
        phase_parity(env)
        phase_service(env)
        sync_one = phase_north_sync(env, shape, seed)
        pipe_one = phase_north_pipelined(env, shape, seed, compiles)
        if chips > 1:
            from volcano_tpu.parallel import make_mesh

            mesh = make_mesh(chips, platform=platform)
            sync_mesh = phase_north_sync(env, shape, seed, mesh)
            for s in sync_one:
                _check_same_binds(f"north_sync seed {s}", sync_mesh[s],
                                  sync_one[s], "one chip")
            pipe_mesh = phase_north_pipelined(env, shape, seed, compiles,
                                              mesh)
            _check_same_binds("north_pipe", pipe_mesh, pipe_one,
                              "one chip")
            _emit("mesh_parity", env, ok=True, mesh=chips,
                  binds_equal=len(pipe_mesh)
                  + sum(len(b) for b in sync_mesh.values()))
    finally:
        logging.getLogger("volcano_tpu").removeHandler(capture)
    bad = [m for m in capture.messages
           if "has been deleted" in m or "falling back" in m.lower()]
    _check(not bad, f"logged during the run: {bad[:3]}")
    _emit("summary", env, ok=True, warnings_logged=len(capture.messages),
          total_host_s=round(time.perf_counter() - t_start, 1))
    return {"ok": True, "device": env["device"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    result = run(args.chips, args.seed)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
