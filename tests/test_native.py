"""Native serializer (csrc/vcsnap.cc) vs NumPy fallback equivalence.

Every vcsnap entry point must produce bit-identical output to the fallback
path; the snapshot encoder must produce the same ClusterArrays either way.
"""

import importlib

import numpy as np
import pytest

from volcano_tpu import native


requires_native = pytest.mark.skipif(
    not native.native_available(), reason="libvcsnap.so not built"
)


def _fallback(fn, *args, **kwargs):
    """Call a native.py entry point with the library disabled."""
    saved_lib, saved_tried = native._LIB, native._TRIED
    native._LIB, native._TRIED = None, True
    try:
        return fn(*args, **kwargs)
    finally:
        native._LIB, native._TRIED = saved_lib, saved_tried


@requires_native
@pytest.mark.parametrize("seed", range(5))
def test_pack_bits_matches_fallback(seed):
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(1, 200))
    words = int(rng.integers(1, 5))
    counts = rng.integers(0, 8, size=rows)
    idx = rng.integers(0, words * 32, size=int(counts.sum())).astype(np.int32)
    off = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    got = native.pack_bits_rows(idx, off, rows, words)
    want = _fallback(native.pack_bits_rows, idx, off, rows, words)
    np.testing.assert_array_equal(got, want)


@requires_native
@pytest.mark.parametrize("seed", range(5))
def test_scatter_matches_fallback(seed):
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(1, 200))
    width = int(rng.integers(2, 9))
    counts = rng.integers(0, width, size=rows)
    n = int(counts.sum())
    # Unique slots per row so duplicate-resolution order cannot differ.
    slot = np.concatenate(
        [rng.permutation(width)[:c] for c in counts]
    ).astype(np.int32) if n else np.zeros((0,), np.int32)
    val = rng.random(n).astype(np.float32)
    off = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    got = native.scatter_rows_f32(slot, val, off, rows, width)
    want = _fallback(native.scatter_rows_f32, slot, val, off, rows, width)
    np.testing.assert_array_equal(got, want)


@requires_native
def test_gather_matches_fallback():
    rng = np.random.default_rng(0)
    src = rng.random((50, 4)).astype(np.float32)
    order = np.array([3, -1, 49, 0, 7, -1, 12], np.int32)
    got = native.gather_rows_f32(src, order, 10)
    want = _fallback(native.gather_rows_f32, src, order, 10)
    np.testing.assert_array_equal(got, want)


@requires_native
@pytest.mark.parametrize("seed", range(3))
def test_less_equal_matches_fallback_and_host(seed):
    from volcano_tpu.api import Resource

    rng = np.random.default_rng(seed)
    rows, r = 64, 3
    eps = np.array([10.0, 10.0 * (1 << 20), 10.0], np.float32)
    scalar = np.array([False, False, True])
    l = (rng.random((rows, r)) * 100).astype(np.float32)
    rhs = (rng.random((r,)) * 100).astype(np.float32)
    got = native.less_equal_rows(l, rhs, eps, scalar)
    want = _fallback(native.less_equal_rows, l, rhs, eps, scalar)
    np.testing.assert_array_equal(got, want)


@requires_native
def test_encode_cluster_native_vs_fallback():
    from volcano_tpu.arrays import encode_cluster
    from volcano_tpu.api import TaskStatus
    from volcano_tpu.synth import synthetic_cluster

    store = synthetic_cluster(n_nodes=32, n_pods=64, gang_size=4, n_queues=2)
    snap = store.snapshot()
    job_ids = sorted(snap.jobs.keys())
    pending = []
    for jid in job_ids:
        pending.extend(
            sorted(
                snap.jobs[jid].task_status_index.get(
                    TaskStatus.Pending, {}
                ).values(),
                key=lambda t: t.name,
            )
        )
    a1, _ = encode_cluster(snap, pending, job_ids)
    a2, _ = _fallback(encode_cluster, snap, pending, job_ids)
    for grp1, grp2 in zip(a1, a2):
        if isinstance(grp1, np.ndarray):
            np.testing.assert_array_equal(grp1, grp2)
            continue
        for f1, f2 in zip(grp1, grp2):
            np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


def test_stale_library_in_csrc_is_never_loaded(tmp_path, monkeypatch):
    """The library that gets loaded is the one built from the sources:
    a left-over ``libvcsnap.so`` of unknown origin (here: not even an
    ELF file — loading it would fail loudly) and a library built from
    OLDER sources are both ignored, and a changed source changes the
    name that is loaded."""
    import shutil

    for name in native._BUILD_INPUTS:
        shutil.copy(native._CSRC / name, tmp_path / name)
    stale = tmp_path / "libvcsnap.so"
    stale.write_bytes(b"not a shared object")
    older = tmp_path / "libvcsnap-0123456789abcdef.so"
    older.write_bytes(b"built from sources that are gone")
    monkeypatch.setattr(native, "_CSRC", tmp_path)
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_LIB_PATH", None)
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.delenv("VOLCANO_TPU_VCSNAP", raising=False)
    monkeypatch.delenv("VOLCANO_TPU_NO_NATIVE", raising=False)

    assert native.native_available()
    loaded = native.loaded_path()
    assert loaded == native.built_lib_path()
    assert loaded.parent == tmp_path and loaded not in (stale, older)
    assert stale.read_bytes() == b"not a shared object"  # untouched
    assert not older.exists()  # swept by the build
    assert native.lib_or_none().vcsnap_version() > 0

    with open(tmp_path / "vcsnap.h", "a") as f:
        f.write("\n// edited\n")
    assert native.built_lib_path() != loaded
