"""Remote-solver split e2e: store/controllers in THIS process, the wave
solver in a real child OS process, the session snapshot crossing as
C++-packed frames (the north-star store<->solver bridge; the reference's
planes likewise talk only through serialized API-server state,
cache.go:492-554)."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from volcano_tpu.scheduler import Scheduler
from volcano_tpu.solver_service import RemoteSolver, SolverServer
from volcano_tpu.synth import preempt_cluster, synthetic_cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_solver(port: int = 0):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "volcano_tpu.solver_service",
         "--port", str(port), "--announce"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=env, cwd=REPO, text=True,
    )
    line = proc.stdout.readline().strip()
    if not line.startswith("SOLVER "):
        proc.kill()
        raise RuntimeError(f"solver did not announce: {line!r}")
    return proc, int(line.split()[1])


@pytest.fixture(scope="module")
def solver_proc():
    proc, port = _spawn_solver()
    yield port
    proc.terminate()
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_two_process_bind_loop(solver_proc):
    """Pods bind through the full two-process loop: encode here, solve
    in the child, commit/bind here."""
    client = RemoteSolver(f"127.0.0.1:{solver_proc}")
    assert client.ping()["op"] == "pong"
    store = synthetic_cluster(n_nodes=12, n_pods=64, gang_size=4, seed=11)
    store.remote_solver = client
    Scheduler(store).run_once()
    store.flush_binds()
    assert len(store.binder.binds) == 64
    assert client.requests >= 1
    assert client.ping()["solves"] >= 1  # the CHILD actually solved
    # Overhead telemetry is collected.
    assert client.bytes_out > 0 and client.bytes_in > 0
    store.close()


def test_remote_matches_local_placements(solver_proc):
    """Same snapshot, same placements: the bridge is lossless."""
    local = synthetic_cluster(n_nodes=10, n_pods=40, gang_size=4, seed=3)
    Scheduler(local).run_once()
    local.flush_binds()

    remote = synthetic_cluster(n_nodes=10, n_pods=40, gang_size=4, seed=3)
    remote.remote_solver = RemoteSolver(f"127.0.0.1:{solver_proc}")
    Scheduler(remote).run_once()
    remote.flush_binds()

    loc = sorted((b[0], b[1]) for b in local.binder.binds)
    rem = sorted((b[0], b[1]) for b in remote.binder.binds)
    assert loc == rem
    local.close()
    remote.close()


def test_remote_solver_affinity_shape(solver_proc):
    """Affinity count tensors + profile term tables survive the wire."""
    store = synthetic_cluster(
        n_nodes=16, n_pods=96, gang_size=4, zones=4,
        affinity_fraction=0.25, anti_affinity_fraction=0.25, seed=5,
    )
    store.remote_solver = RemoteSolver(f"127.0.0.1:{solver_proc}")
    Scheduler(store).run_once()
    store.flush_binds()
    assert len(store.binder.binds) >= 90
    store.close()


def test_solver_restart_heals():
    """A restarted solver process heals via client reconnect: the cycle
    that hits the dead socket fails, the next one succeeds."""
    proc, port = _spawn_solver()
    client = RemoteSolver(f"127.0.0.1:{port}")
    store = synthetic_cluster(n_nodes=6, n_pods=24, gang_size=4, seed=9)
    store.remote_solver = client
    try:
        Scheduler(store).run_once()
        store.flush_binds()
        assert len(store.binder.binds) == 24
    finally:
        proc.terminate()
        proc.wait(timeout=10)
    # Dead solver: the client raises, the cycle fails, pods stay put.
    store2 = synthetic_cluster(n_nodes=6, n_pods=24, gang_size=4, seed=10)
    store2.remote_solver = client
    os.environ["VOLCANO_TPU_FALLBACK"] = "never"
    try:
        with pytest.raises(Exception):
            Scheduler(store2).run_once()
    finally:
        os.environ.pop("VOLCANO_TPU_FALLBACK", None)
    # New solver at a fresh port: retarget (operator restart semantics)
    proc2, port2 = _spawn_solver()
    try:
        client2 = RemoteSolver(f"127.0.0.1:{port2}")
        store2.remote_solver = client2
        Scheduler(store2).run_once()
        store2.flush_binds()
        assert len(store2.binder.binds) == 24
    finally:
        proc2.terminate()
        proc2.wait(timeout=10)
        store.close()
        store2.close()


def test_in_process_server_roundtrip():
    """SolverServer + RemoteSolver in one process (no subprocess): the
    wire path itself, incl. preempt-shape inputs with releasing
    capacity."""
    import threading

    server = SolverServer(port=0)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        store = preempt_cluster(n_nodes=8, n_pending=16, seed=4)
        store.remote_solver = RemoteSolver(f"127.0.0.1:{server.port}")
        conf = """
actions: "enqueue, allocate, preempt, reclaim, backfill"
tiers:
- plugins:
  - name: priority
  - name: gang
  - name: conformance
- plugins:
  - name: drf
  - name: predicates
  - name: proportion
  - name: nodeorder
  - name: binpack
"""
        Scheduler(store, conf_str=conf).run_once()
        store.flush_binds()
        assert len(store.evictor.evicts) > 0
        store.close()
    finally:
        server.shutdown()


# --------------------------------- protocol v2: delta wire (ISSUE 10)


def _wire_loop(port, *, cycles=6, seed=31, churn=False, client=None,
               feed_nodes=(0, 1)):
    """Pipelined remote loop over a real socket: returns (binds,
    per-cycle mirror states, per-cycle frame kinds, frame counts,
    fallback counts, client)."""
    import random

    from test_devincr import (
        _churn,
        _mirror_state,
        _partial_feed,
        _reset_uid_counters,
    )

    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=16, n_pods=48, gang_size=4,
                              seed=seed)
    store.pipeline = True
    if client is None:
        client = RemoteSolver(f"127.0.0.1:{port}")
    store.remote_solver = client
    store.cycle_feed = _partial_feed(list(feed_nodes))
    sched = Scheduler(store)
    rng = random.Random(7)
    states, kinds = [], []
    for step in range(cycles):
        sched.run_once()
        states.append(_mirror_state(store))
        kinds.append(client.last_frame_kind)
        if churn and step % 2 == 1:
            _churn(store, rng, step)
    store.flush_binds()
    binds = dict(store.binder.binds)
    counts = dict(client.frame_counts)
    fallbacks = dict(client.wire_fallbacks)
    store.close()
    client.close()
    return binds, states, kinds, counts, fallbacks


def _local_loop(*, cycles=6, seed=31, churn=False, feed_nodes=(0, 1)):
    """The in-process twin of ``_wire_loop`` (same seeds, same churn
    sequence, device solve in THIS process)."""
    import random

    from test_devincr import (
        _churn,
        _mirror_state,
        _partial_feed,
        _reset_uid_counters,
    )

    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=16, n_pods=48, gang_size=4,
                              seed=seed)
    store.pipeline = True
    store.cycle_feed = _partial_feed(list(feed_nodes))
    sched = Scheduler(store)
    rng = random.Random(7)
    states = []
    for step in range(cycles):
        sched.run_once()
        states.append(_mirror_state(store))
        if churn and step % 2 == 1:
            _churn(store, rng, step)
    store.flush_binds()
    binds = dict(store.binder.binds)
    store.close()
    return binds, states


def test_wire_delta_churn_parity_two_process(solver_proc, monkeypatch):
    """ISSUE 10 acceptance: the two-process pipelined remote loop stays
    bind-for-bind AND per-cycle-mirror-state equal to the in-process
    loop across a randomized-churn feed, with delta frames asserted
    engaged (and cheaper than full frames — REC_SAME slots ship no
    payload)."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    binds_r, states_r, kinds, counts, _fb = _wire_loop(
        solver_proc, cycles=10, churn=True)
    binds_l, states_l = _local_loop(cycles=10, churn=True)
    assert binds_r and binds_r == binds_l
    assert states_r == states_l
    assert counts["delta"] >= 2, (kinds, counts)
    assert "delta" in kinds and kinds[0] == "full"


def test_wire_kill_switch_full_frames(solver_proc, monkeypatch):
    """VOLCANO_TPU_WIRE=0: classic v1 frames only (no delta machinery),
    same binds; a delta frame is the smaller one."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "0")
    off = RemoteSolver(f"127.0.0.1:{solver_proc}")
    binds_off, states_off, kinds, counts, fallbacks = _wire_loop(
        solver_proc, cycles=6, client=off)
    assert counts["delta"] == 0 and counts["full"] >= 6
    assert set(kinds) == {"full"}
    assert fallbacks == {}
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    on = RemoteSolver(f"127.0.0.1:{solver_proc}")
    binds_on, states_on, _k, counts_on, _fb = _wire_loop(
        solver_proc, cycles=6, client=on)
    assert counts_on["delta"] >= 1
    assert binds_on and binds_on == binds_off
    assert states_on == states_off
    per_full = off.frame_bytes["full"] / counts["full"]
    per_delta = on.frame_bytes["delta"] / counts_on["delta"]
    assert per_delta < per_full / 2, (per_delta, per_full)


def test_wire_forced_fallback_lever(solver_proc, monkeypatch):
    """VOLCANO_TPU_WIRE=fallback: the v2 machinery runs but every frame
    ships full through the fallback path, counted reason=forced, with
    identical binds."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "fallback")
    binds_fb, states_fb, kinds, counts, fallbacks = _wire_loop(
        solver_proc, cycles=6)
    assert counts["delta"] == 0 and set(kinds) == {"full"}
    assert fallbacks.get("forced", 0) >= 5, fallbacks
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    binds_on, states_on, _k, _c, _fb = _wire_loop(solver_proc, cycles=6)
    assert binds_on and binds_on == binds_fb
    assert states_on == states_fb


def test_wire_child_restart_heals(monkeypatch):
    """A solver-child restart mid-stream heals via the full-frame
    fallback: the in-flight reply is lost (its rows re-place — never a
    stale solve), the reconnect voids the wire cache so the first frame
    to the new child ships full, and the delta lane re-engages — with
    zero lost pods."""
    from test_devincr import _partial_feed, _reset_uid_counters

    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    # Both children pick their own port (--port 0 + announce) so there
    # is never a probe-then-bind race: the restart derives the new port
    # from the new child's announce and repoints the client, instead of
    # racing other test processes for the freed port.
    proc, port = _spawn_solver()
    _reset_uid_counters()
    store = synthetic_cluster(n_nodes=16, n_pods=48, gang_size=4,
                              seed=37)
    store.pipeline = True
    client = RemoteSolver(f"127.0.0.1:{port}")
    store.remote_solver = client
    store.cycle_feed = _partial_feed([0, 1])
    sched = Scheduler(store)
    kinds = []
    try:
        for _ in range(5):
            sched.run_once()
            kinds.append(client.last_frame_kind)
        assert "delta" in kinds  # lane engaged before the restart
        # Kill the child MID-STREAM: a pipelined solve is in flight.
        proc.terminate()
        proc.wait(timeout=10)
        # Respawn on a fresh OS-assigned port (retry-bounded in case a
        # cold interpreter start flakes) and repoint the client: its
        # dead socket forces a reconnect, which dials host:port anew.
        for attempt in range(3):
            try:
                proc, port = _spawn_solver()
                break
            except RuntimeError:
                if attempt == 2:
                    raise
        client.host, client.port = "127.0.0.1", port
        pre_restart_delta = client.frame_counts["delta"]
        for _ in range(5):
            sched.run_once()
            kinds.append(client.last_frame_kind)
        # The reconnect was counted, the first post-restart frame was
        # full (the new child's mirror starts empty), and deltas
        # resumed against the re-mirrored base.
        assert client.wire_fallbacks.get("reconnect", 0) >= 1
        post = kinds[5:]
        assert post[0] == "full" and "delta" in post, kinds
        assert client.frame_counts["delta"] > pre_restart_delta
        # Zero lost pods: stop the churn feed and drain the pipeline —
        # every pod (including the rows whose in-flight reply died with
        # the old child) must land Bound on a node.
        store.cycle_feed = None
        for _ in range(3):
            sched.run_once()
        store.flush_binds()
        from volcano_tpu.api import TaskStatus

        m = store.mirror
        not_bound = [
            m.p_uid[r] for r in range(m.n_pods)
            if m.p_uid[r] is not None
            and int(m.p_status[r]) != int(TaskStatus.Bound)
        ]
        assert not_bound == [], f"pods lost to the restart: {not_bound}"
        assert all(p.node_name for p in store.pods.values())
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        store.close()
        client.close()


def test_wire_mirror_records_and_resync():
    """Child-side mirror unit: full -> REC_SAME/REC_FULL/REC_DELTA
    materialization, base mismatch -> resync, malformed delta poisons
    the mirror."""
    from volcano_tpu.cache import snapwire as sw
    from volcano_tpu.solver_service import _ResyncNeeded, _WireMirror

    mirror = _WireMirror()
    a0 = np.arange(40, dtype=np.int64).reshape(10, 4)
    a1 = np.zeros(6, np.float32)
    out = mirror.apply(sw, {"gen": 1}, [a0, a1], payload_shared=False)
    assert mirror.gen == 1 and len(out) == 2
    # Delta against a base the mirror does not hold -> resync.
    with pytest.raises(_ResyncNeeded) as ei:
        mirror.apply(sw, {"gen": 2, "base": 99, "recs": [[1], [1]]},
                     [], payload_shared=False)
    assert ei.value.have_gen == 1
    # Valid delta: slot 0 patches rows [2,4), slot 1 ships whole.
    new0 = a0.copy()
    new0[2:4] = -7
    ranges = sw.diff_rows(new0, a0)
    desc = sw.ranges_to_desc(ranges)
    rowpay = sw.gather_rows(new0, ranges)
    new1 = np.ones(6, np.float32)
    out = mirror.apply(
        sw, {"gen": 2, "base": 1,
             "recs": [[sw.REC_DELTA, 0, 1], [sw.REC_FULL, 2]]},
        [desc, rowpay, new1], payload_shared=False)
    assert mirror.gen == 2
    assert np.array_equal(out[0], new0)
    assert np.array_equal(out[1], new1)
    # REC_SAME reuses the mirrored arrays byte-for-byte.
    out2 = mirror.apply(
        sw, {"gen": 3, "base": 2,
             "recs": [[sw.REC_SAME], [sw.REC_SAME]]},
        [], payload_shared=False)
    assert np.array_equal(out2[0], new0)
    assert np.array_equal(out2[1], new1)
    # A malformed delta poisons the mirror; the NEXT delta resyncs.
    bad_desc = np.array([1, 5, 99], np.int64)  # stop past rows
    with pytest.raises(ValueError):
        mirror.apply(
            sw, {"gen": 4, "base": 3,
                 "recs": [[sw.REC_DELTA, 0, 1], [sw.REC_SAME]]},
            [bad_desc, np.zeros(0, np.uint8)], payload_shared=False)
    assert mirror.gen == -1
    with pytest.raises(_ResyncNeeded):
        mirror.apply(
            sw, {"gen": 5, "base": 4,
                 "recs": [[sw.REC_SAME], [sw.REC_SAME]]},
            [], payload_shared=False)


def test_wire_resync_and_ack_mismatch_drop_reply():
    """Client-side defense in depth: a resync reply and a wrong-ack
    reply each void the wire cache and raise ValueError (the pipelined
    fetch treats both as a lost reply — pods re-place, never a stale
    solve)."""
    from volcano_tpu.cache import snapwire as sw

    client = RemoteSolver("127.0.0.1:1")  # never connects
    client._wire.arrays = [np.zeros(4)]
    client._wire.spec = "spec"
    resync = sw.encode_frame([], {"op": "resync", "have_gen": 3})
    with pytest.raises(ValueError, match="resync"):
        client._decode_result(resync)
    assert client.wire_fallbacks.get("gen-mismatch") == 1
    assert client._wire.arrays is None

    arrays_out: list = []
    vals = tuple(np.int32(i) for i in range(7))
    tree = sw.flatten_tree(vals, arrays_out)
    good = sw.encode_frame(
        arrays_out, {"op": "result", "tree": tree, "ack_gen": 2})
    client._wire.arrays = [np.zeros(4)]
    with pytest.raises(ValueError, match="acked gen"):
        client._decode_result(good, expect_gen=3)
    assert client.wire_fallbacks.get("ack-mismatch") == 1
    assert client._wire.arrays is None
    # The SAME reply with the right expectation decodes fine.
    res = client._decode_result(
        sw.encode_frame(arrays_out,
                        {"op": "result", "tree": tree, "ack_gen": 3}),
        expect_gen=3)
    assert int(res.iters) == 4

    # A solver-side error reply ALSO voids the cache (the child
    # poisoned its mirror) — the next frame ships full instead of a
    # doomed delta paying a second lost cycle to the resync round trip.
    client._wire.arrays = [np.zeros(4)]
    err = sw.encode_frame([], {"op": "error", "message": "boom"})
    with pytest.raises(RuntimeError, match="boom"):
        client._decode_result(err)
    assert client.wire_fallbacks.get("child-error") == 1
    assert client._wire.arrays is None and client._wire.pending_reason is None
    # With no delta state mirrored (kill switch off), an error reply
    # does not count a delta-lane fallback.
    with pytest.raises(RuntimeError, match="boom"):
        client._decode_result(err)
    assert client.wire_fallbacks.get("child-error") == 1


def test_wire_v1_child_self_disables(monkeypatch):
    """Version skew (new scheduler, old solver): a reply with NO
    ack_gen means the child speaks protocol v1 — the delta lane
    self-disables for the client's life and frames degrade to classic
    v1 fulls instead of dropping every reply (a permanent outage)."""
    from volcano_tpu.cache import snapwire as sw

    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    client = RemoteSolver("127.0.0.1:1")  # never connects
    arrays_out: list = []
    vals = tuple(np.int32(i) for i in range(7))
    tree = sw.flatten_tree(vals, arrays_out)
    v1_reply = sw.encode_frame(
        arrays_out, {"op": "result", "tree": tree})  # no ack_gen
    # The frame that exposed the skew was full (first wire frame on
    # the connection always is): the solve is valid — keep it.
    client._wire.arrays = [np.zeros(4)]
    client.last_frame_kind = "full"
    res = client._decode_result(v1_reply, expect_gen=1)
    assert int(res.iters) == 4
    assert client._wire_v1_child
    assert client.wire_fallbacks.get("v1-child") == 1
    assert client._wire.arrays is None
    # Subsequent frames ship classic v1 (no wire section, no gen).
    total, parts, kind, gen = client._build_frame(
        (np.arange(4, dtype=np.int32),), np.int32(0), None, None, None)
    assert kind == "full" and gen is None
    man, _ = sw.decode_frame(b"".join(bytes(p) for p in parts))
    assert "wire" not in man
    # Defense in depth: had the skew surfaced on a DELTA frame, the
    # reply is dropped (a v1 child reads descriptors as solve args).
    client2 = RemoteSolver("127.0.0.1:1")
    client2.last_frame_kind = "delta"
    with pytest.raises(ValueError, match="protocol-v1"):
        client2._decode_result(v1_reply, expect_gen=1)
    assert client2._wire_v1_child


def test_wire_shm_v1_child_handshake(monkeypatch):
    """VOLCANO_TPU_SHM=1 against a protocol-v1 solver must not be a
    permanent outage: a v1 child never reads the manifest's shm
    section (it just errors on the empty array list, which is NOT an
    ShmUnavailable reply), so the client probes the pong's advertised
    wire version on connect and degrades to classic v1 TCP frames
    before the first shm payload ships."""
    import socket as socketlib
    import threading

    from volcano_tpu.cache import snapwire as sw
    from volcano_tpu.solver_service import recv_frame, send_frame

    srv = socketlib.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    arrays_out: list = []
    vals = tuple(np.int32(i) for i in range(7))
    tree = sw.flatten_tree(vals, arrays_out)
    result = sw.encode_frame(arrays_out, {"op": "result", "tree": tree})
    seen = {}

    def serve():
        conn, _ = srv.accept()
        ping, _ = sw.decode_frame(recv_frame(conn))
        seen["ping"] = ping.get("op")
        # v1 pong: no "wire" key at all.
        send_frame(conn, sw.encode_frame(
            [], {"op": "pong", "solves": 0, "backend": "cpu"}))
        solve, _ = sw.decode_frame(recv_frame(conn))
        seen["solve"] = solve
        send_frame(conn, result)
        conn.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    monkeypatch.setenv("VOLCANO_TPU_SHM", "1")
    client = RemoteSolver(f"127.0.0.1:{port}")
    res = client.solve((np.arange(4, dtype=np.int32),), np.int32(0),
                       None)
    t.join(timeout=10)
    assert int(res.iters) == 4
    assert client._wire_v1_child and client._shm is None
    assert client.wire_fallbacks.get("shm") == 1
    assert seen["ping"] == "ping"
    # The solve frame the v1 child received was pure v1: no wire or
    # shm sections, payload arrays on the socket.
    assert "wire" not in seen["solve"] and "shm" not in seen["solve"]
    client.close()
    srv.close()


def test_shm_lane_roundtrip_and_unavailable(monkeypatch):
    """Same-host shared-memory lane units: writer->reader view
    roundtrip (incl. segment growth), a bogus segment raises
    ShmUnavailable, and the client disables the lane on the child's
    error reply."""
    from volcano_tpu.cache import snapwire as sw
    from volcano_tpu.solver_service import (
        ShmUnavailable,
        _ShmLane,
        _ShmReader,
    )

    lane = _ShmLane()
    reader = _ShmReader()
    try:
        arrays = [np.arange(100, dtype=np.float32).reshape(10, 10),
                  np.array([3, -1], np.int64), np.zeros(0, np.uint8)]
        section = lane.write(arrays)
        out = reader.arrays(section)
        for a, b in zip(arrays, out):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b)
        # Growth reallocates a fresh segment; the reader re-attaches by
        # name.
        big = [np.full(1 << 18, 7, np.float64)]
        sec2 = lane.write(big)
        assert sec2["name"] != section["name"]
        out2 = reader.arrays(sec2)
        assert np.array_equal(out2[0], big[0])
        # Hostile slots: out-of-bounds offset must not view past the
        # segment.
        bad = dict(sec2)
        bad["slots"] = [[0, [1 << 24], 0]]
        with pytest.raises(ShmUnavailable):
            reader.arrays(bad)
        # Hostile dims whose int64 product wraps to 0 must not sail
        # through the bounds check (np.prod overflow).
        bad["slots"] = [[0, [1 << 32, 1 << 32], 0]]
        with pytest.raises(ShmUnavailable):
            reader.arrays(bad)
    finally:
        # Views into the segment must die before the mmap can close —
        # including the comparison loop's leaked iteration variables.
        del out, out2, a, b
        reader.close()
        lane.close()
    with pytest.raises(ShmUnavailable):
        _ShmReader().arrays({"name": "vtpu_bogus_nonexistent",
                             "slots": []})
    # Client side: an ShmUnavailable error reply disables the lane and
    # reads as a dropped frame.
    monkeypatch.setenv("VOLCANO_TPU_SHM", "1")
    client = RemoteSolver("127.0.0.1:1")
    assert client._shm is not None
    err = sw.encode_frame(
        [], {"op": "error",
             "message": "ShmUnavailable: cannot attach segment"})
    with pytest.raises(ValueError, match="dropped frame"):
        client._decode_result(err)
    assert client._shm is None
    assert client.wire_fallbacks.get("shm") == 1


def test_wire_shm_two_process_parity(solver_proc, monkeypatch):
    """VOLCANO_TPU_SHM=1 against a real same-host child: payloads ride
    the segment (socket frames shrink to manifests), binds match the
    TCP run, and the lane stays enabled throughout."""
    monkeypatch.setenv("VOLCANO_TPU_WIRE", "1")
    monkeypatch.setenv("VOLCANO_TPU_SHM", "1")
    shm_client = RemoteSolver(f"127.0.0.1:{solver_proc}")
    assert shm_client._shm is not None
    binds_shm, states_shm, kinds, counts, fallbacks = _wire_loop(
        solver_proc, cycles=6, client=shm_client)
    assert "shm" not in fallbacks, fallbacks
    assert counts["delta"] >= 1
    shm_bytes = dict(shm_client.frame_bytes)
    monkeypatch.delenv("VOLCANO_TPU_SHM")
    tcp_client = RemoteSolver(f"127.0.0.1:{solver_proc}")
    binds_tcp, states_tcp, _k, _c, _fb = _wire_loop(
        solver_proc, cycles=6, client=tcp_client)
    tcp_bytes = dict(tcp_client.frame_bytes)
    assert binds_shm == binds_tcp
    assert states_shm == states_tcp
    # The payload-bearing FULL frame shrinks to its manifest on the
    # socket (delta frames are mostly REC_SAME manifests either way).
    assert shm_bytes["full"] < tcp_bytes["full"] / 2, (
        shm_bytes, tcp_bytes)
    assert sum(shm_bytes.values()) < sum(tcp_bytes.values())
