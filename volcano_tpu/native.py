"""ctypes bridge to the native snapshot serializer (csrc/vcsnap.cc).

The C++ library owns the hot marshalling loops of the snapshot encoder —
CSR bitset packing, CSR resource-slot scatter, padded row gather, and the
epsilon LessEqual row check (resource_info.go:286-320).

The library that gets loaded is built from the sources in the tree: its
file name carries the content hash of ``csrc/vcsnap.cc``, ``vcsnap.h``
and ``Makefile`` (``csrc/libvcsnap-<hash>.so``), it is built on first use
with ``make -C csrc``, and a file of any other name lying in ``csrc/``
(a hand-built or left-over ``libvcsnap.so``) is never loaded.  If the
build fails every entry point falls back to a vectorized NumPy
implementation with identical semantics (cross-checked by
tests/test_native.py) and the failure is logged as an error; callers
that must not run on the stand-in check ``native_available()``.

Set VOLCANO_TPU_NO_NATIVE=1 to force the NumPy fallback;
VOLCANO_TPU_VCSNAP=/path/to/libvcsnap.so to use a prebuilt library (e.g.
the ASAN build from `make -C csrc asan`).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger(__name__)

_CSRC = Path(__file__).resolve().parent.parent / "csrc"
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_PATH: Optional[Path] = None
_TRIED = False

# Everything the build rule reads; their bytes key the output name.
_BUILD_INPUTS = ("vcsnap.cc", "vcsnap.h", "Makefile")

_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.vcsnap_version.restype = ctypes.c_int
    lib.vcsnap_pack_bits.argtypes = [
        _i32p, _i64p, ctypes.c_int64, ctypes.c_int32, _u32p,
    ]
    lib.vcsnap_scatter_f32.argtypes = [
        _i32p, _f32p, _i64p, ctypes.c_int64, ctypes.c_int32, _f32p,
    ]
    lib.vcsnap_gather_rows_f32.argtypes = [
        _f32p, _i32p, ctypes.c_int64, ctypes.c_int32, _f32p,
    ]
    lib.vcsnap_less_equal.argtypes = [
        _f32p, _f32p, _f32p, _u8p, ctypes.c_int64, ctypes.c_int32, _u8p,
    ]
    # Wire-frame codec (remote-solver snapshot bridge, cache/snapwire.py).
    lib.vcsnap_frame_bytes.restype = ctypes.c_int64
    lib.vcsnap_frame_bytes.argtypes = [
        _u8p, _i64p, ctypes.c_int32, ctypes.c_int64,
    ]
    lib.vcsnap_frame_pack.argtypes = [
        _u8p, _u8p, _i64p, _i64p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)), ctypes.c_int32,
        _u8p, ctypes.c_int64, _u8p,
    ]
    lib.vcsnap_frame_info.restype = ctypes.c_int32
    lib.vcsnap_frame_info.argtypes = [
        _u8p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.vcsnap_frame_unpack.restype = ctypes.c_int32
    lib.vcsnap_frame_unpack.argtypes = [
        _u8p, ctypes.c_int64, _u8p, _u8p, _i64p, _i64p, _i64p,
    ]
    # Delta records (protocol v2 remote-solver frames, ISSUE 10).
    lib.vcsnap_delta_check.restype = ctypes.c_int64
    lib.vcsnap_delta_check.argtypes = [
        _i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
    ]
    lib.vcsnap_delta_apply.restype = ctypes.c_int32
    lib.vcsnap_delta_apply.argtypes = [
        _u8p, ctypes.c_int64, ctypes.c_int64, _i64p, ctypes.c_int64,
        _u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
    ]
    # Reclaim engine: all stable pointers are captured once into a C-side
    # context; the hot per-reclaimer call takes raw addresses (c_void_p)
    # to keep ctypes marshalling off the 20k-calls-per-cycle path.
    vp = ctypes.c_void_p
    ll = ctypes.c_longlong
    lib.vcreclaim_ctx_new.restype = vp
    lib.vcreclaim_ctx_new.argtypes = (
        [vp] * 20 + [vp, ll] + [vp] * 4 + [ll, ll, ll, ll]
        # batch-mode tail: n_pipelined n_ntasks n_maxtasks pipe_node
        # j_cnt_pending j_waiting j_version q_version Qn j_prio j_rank
        # p_node total_res job_order job_order_len reclaim_gated
        + [vp] * 8 + [ll] + [vp] * 5 + [ll, ll]
    )
    lib.vcreclaim_ctx_free.argtypes = [vp]
    lib.vcreclaim_step.restype = ll
    lib.vcreclaim_step.argtypes = [
        vp, ll, ll,  # ctx prow qid
        vp,  # cursor
        vp, vp, vp, vp,  # anym feas stat slots
        vp, vp, ll,  # out_evicted out_n max
    ]
    lib.vcreclaim_drive_mq.restype = ll
    lib.vcreclaim_drive_mq.argtypes = [
        vp, ll,  # ctx has_pred
        vp, ll,  # qs_ids n_queues
        vp, vp, vp, ll,  # q_create q_uid_rank q_named has_prop
        vp, vp,  # q_overused out_q_dropped
        vp, ll, vp,  # job_ids n_jobs job_qslot
        vp, vp, vp,  # task_ptr task_rows task_cursor
        vp,  # row_maskidx
        ll,  # n_masks
        vp, vp, vp, vp, vp,  # anym feas stat slots initreq ptr arrays
        vp,  # mask_qids
        vp,  # mask_cursors
        vp, vp, ll,  # out_evicted out_n max_ev
        vp, vp, vp,  # out_pipe_rows out_pipe_nodes out_n_pipe
        vp, vp, ll,  # out_touched out_n_touched max_touched
        vp,  # out_yield_job
        vp,  # out_job_dropped
    ]
    return lib


def built_lib_path() -> Path:
    """``csrc/libvcsnap-<hash>.so`` for the sources as they are now."""
    h = hashlib.sha256()
    for name in _BUILD_INPUTS:
        data = (_CSRC / name).read_bytes()
        h.update(f"{name}:{len(data)}:".encode())
        h.update(data)
    return _CSRC / f"libvcsnap-{h.hexdigest()[:16]}.so"


def _build(target: Path) -> None:
    """``make`` the hash-named library (the rule writes to a temporary
    name and renames, so a concurrent loader never maps a half-written
    file), then drop libraries built from older sources."""
    subprocess.run(
        ["make", "-s", "-C", str(_CSRC), f"LIB={target.name}"],
        check=True, capture_output=True, timeout=120,
    )
    for old in _CSRC.glob("libvcsnap-*.so"):
        if old != target:
            old.unlink(missing_ok=True)


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_PATH, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("VOLCANO_TPU_NO_NATIVE"):
            return None
        override = os.environ.get("VOLCANO_TPU_VCSNAP")
        try:
            if override:
                path = Path(override)
            else:
                path = built_lib_path()
                if not path.is_file():
                    _build(path)
                    log.info("built native vcsnap serializer %s",
                             path.name)
            _LIB = _bind(ctypes.CDLL(str(path)))
            _LIB_PATH = path
        except (OSError, AttributeError, subprocess.SubprocessError) as err:
            # AttributeError: an override library missing a newer symbol.
            detail = getattr(err, "stderr", b"") or b""
            log.error(
                "native vcsnap library unavailable, using the NumPy "
                "stand-in: %s %s", err,
                detail.decode(errors="replace")[-2000:],
            )
        return _LIB


def native_available() -> bool:
    return _load() is not None


def loaded_path() -> Optional[Path]:
    """File the bound library was loaded from (None: NumPy stand-in)."""
    _load()
    return _LIB_PATH


def lib_or_none() -> Optional[ctypes.CDLL]:
    """The bound native library, or None (NumPy fallbacks apply)."""
    return _load()


# --------------------------------------------------------------------- API


def _csr(indices, offsets) -> Tuple[np.ndarray, np.ndarray]:
    idx = np.ascontiguousarray(indices, np.int32)
    off = np.ascontiguousarray(offsets, np.int64)
    return idx, off


def pack_bits_rows(indices, offsets, rows: int, words: int) -> np.ndarray:
    """CSR -> [rows, words] uint32 bitsets."""
    idx, off = _csr(indices, offsets)
    out = np.zeros((rows, words), np.uint32)
    lib = _load()
    if lib is not None and rows:
        lib.vcsnap_pack_bits(idx, off, rows, words, out)
        return out
    if len(idx):
        counts = np.diff(off)
        row_of = np.repeat(np.arange(rows, dtype=np.int64), counts)
        valid = (idx >= 0) & (idx < words * 32)
        r, b = row_of[valid], idx[valid].astype(np.int64)
        np.bitwise_or.at(out, (r, b >> 5), (1 << (b & 31)).astype(np.uint32))
    return out


def scatter_rows_f32(slots, values, offsets, rows: int, width: int) -> np.ndarray:
    """CSR (slot, value) pairs -> [rows, width] float32."""
    slot = np.ascontiguousarray(slots, np.int32)
    val = np.ascontiguousarray(values, np.float32)
    off = np.ascontiguousarray(offsets, np.int64)
    out = np.zeros((rows, width), np.float32)
    lib = _load()
    if lib is not None and rows:
        lib.vcsnap_scatter_f32(slot, val, off, rows, width, out)
        return out
    if len(slot):
        counts = np.diff(off)
        row_of = np.repeat(np.arange(rows, dtype=np.int64), counts)
        valid = (slot >= 0) & (slot < width)
        out[row_of[valid], slot[valid]] = val[valid]
    return out


def gather_rows_f32(src: np.ndarray, order, rows: int) -> np.ndarray:
    """out[i] = src[order[i]] (order < 0 -> zero row), padded to rows."""
    src = np.ascontiguousarray(src, np.float32)
    order = np.ascontiguousarray(order, np.int32)
    if len(order) < rows:  # short order rows are padding (-1 = zero row)
        order = np.concatenate(
            [order, np.full((rows - len(order),), -1, np.int32)]
        )
    width = src.shape[1] if src.ndim == 2 else 1
    out = np.zeros((rows, width), np.float32)
    lib = _load()
    if lib is not None and rows:
        lib.vcsnap_gather_rows_f32(src.reshape(-1), order, rows, width, out)
        return out
    n = min(rows, len(order))
    sel = order[:n]
    ok = sel >= 0
    out[np.arange(n)[ok]] = src[sel[ok]]
    return out


def less_equal_rows(l: np.ndarray, rhs: np.ndarray, eps: np.ndarray,
                    scalar_slot: np.ndarray) -> np.ndarray:
    """Epsilon LessEqual of each row of ``l`` against the single row
    ``rhs`` -> [rows] bool (host-side fit checks at replay/commit time)."""
    l = np.ascontiguousarray(l, np.float32)
    rhs = np.ascontiguousarray(rhs, np.float32)
    eps = np.ascontiguousarray(eps, np.float32)
    ss = np.ascontiguousarray(np.asarray(scalar_slot, bool).view(np.uint8))
    rows = l.shape[0]
    lib = _load()
    if lib is not None and rows:
        out = np.zeros((rows,), np.uint8)
        lib.vcsnap_less_equal(l, rhs, eps, ss, rows, l.shape[1], out)
        return out.astype(bool)
    per = (l < rhs[None, :]) | (np.abs(l - rhs[None, :]) < eps[None, :])
    per |= (np.asarray(scalar_slot, bool)[None, :] & (l <= eps[None, :]))
    return np.all(per, axis=-1)


def reclaim_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library with ``vcreclaim_step`` bound, or None
    (caller falls back to the Python walk in fastpath_evict)."""
    lib = _load()
    if lib is None or not hasattr(lib, "vcreclaim_step"):
        return None
    return lib
