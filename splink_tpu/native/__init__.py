"""ctypes loader for the native host kernels, with pure-numpy fallback.

The library is built on first use (g++, a one-second compile) next to the
sources, under a name that carries a hash of the source text, the compiler
flags and the host CPU's feature set — ``-march=native`` code from another
machine, or from an older source revision, has another name and is never
loaded. Every entry point has a Python fallback so the package works on
machines without a toolchain — ``available()`` reports which path is
active, and the loader says so once at warning level.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

from ..utils.envfp import cpu_target_fingerprint

logger = logging.getLogger("splink_tpu")

_DIR = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(_DIR, "src", "host_kernels.cpp")
_CXX = os.environ.get("CXX", "g++")
_CXXFLAGS = ("-O3", "-march=native", "-fPIC", "-shared", "-std=c++17", "-Wall")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
_built_here = False

_i64p = ctypes.POINTER(ctypes.c_int64)
_u8p = ctypes.POINTER(ctypes.c_uint8)
_i32p = ctypes.POINTER(ctypes.c_int32)


def library_path() -> str:
    """Where the library for THIS source, these flags and this CPU lives."""
    h = hashlib.sha256()
    with open(SOURCE, "rb") as fh:
        h.update(fh.read())
    h.update(" ".join((_CXX, *_CXXFLAGS, cpu_target_fingerprint())).encode())
    return os.path.join(_DIR, f"libsplink_host-{h.hexdigest()[:16]}.so")


def _build(path: str) -> None:
    """Compile SOURCE to ``path`` (temp file + rename: concurrent first
    uses in several processes never load a half-written library), then
    sweep libraries built from other sources, flags or machines."""
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [_CXX, *_CXXFLAGS, "-o", tmp, SOURCE],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for stale in glob.glob(os.path.join(_DIR, "libsplink_host*.so")):
        if stale != path:
            try:
                os.remove(stale)
            except OSError:
                pass


def _load() -> ctypes.CDLL | None:
    global _lib, _tried, _built_here
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            path = library_path()
            if not os.path.exists(path):
                _build(path)
                _built_here = True
            _lib = _bind(ctypes.CDLL(path))
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            detail = getattr(e, "stderr", b"") or b""
            logger.warning(
                "native host kernels unavailable (%s: %s %s); host encode "
                "and the host join run on the numpy fallbacks",
                type(e).__name__, e, detail.decode(errors="replace")[-300:],
            )
            _lib = None
        return _lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare signatures; raises AttributeError on a missing symbol."""
    lib.encode_fixed_width.argtypes = [
        _u8p, _i64p, ctypes.c_int64, ctypes.c_int64, _u8p, _i32p,
    ]
    lib.count_self_pairs.restype = ctypes.c_int64
    lib.count_self_pairs.argtypes = [_i64p, ctypes.c_int64]
    lib.emit_self_pairs.argtypes = [_i64p] * 3 + [ctypes.c_int64, _i64p, _i64p]
    lib.emit_self_pairs_i32.argtypes = [
        _i32p, _i64p, _i64p, ctypes.c_int64, _i32p, _i32p,
    ]
    lib.count_cross_pairs.restype = ctypes.c_int64
    lib.count_cross_pairs.argtypes = [_i64p, _i64p, ctypes.c_int64]
    lib.emit_cross_pairs.argtypes = [_i64p] * 6 + [ctypes.c_int64, _i64p, _i64p]
    lib.emit_cross_pairs_i32.argtypes = [
        _i32p, _i64p, _i64p, _i32p, _i64p, _i64p,
        ctypes.c_int64, _i32p, _i32p,
    ]
    return lib


def available() -> bool:
    return _load() is not None


def build_info() -> dict:
    """Which host path is active, and where the library came from."""
    lib = _load()
    return {
        "available": lib is not None,
        "library": os.path.basename(lib._name) if lib is not None else None,
        "built_in_this_process": _built_here,
        "source": os.path.relpath(SOURCE, os.path.dirname(os.path.dirname(_DIR))),
    }


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctype)


def encode_fixed_width(data: np.ndarray, offsets: np.ndarray, width: int):
    """(flat uint8 buffer, int64 offsets) -> ((n, width) uint8, (n,) int32)."""
    n = len(offsets) - 1
    out_bytes = np.zeros((n, width), np.uint8)
    out_lens = np.zeros(n, np.int32)
    lib = _load()
    if lib is not None and data.flags.c_contiguous:
        lib.encode_fixed_width(
            _ptr(data, _u8p), _ptr(offsets, _i64p), n, width,
            _ptr(out_bytes, _u8p), _ptr(out_lens, _i32p),
        )
        return out_bytes, out_lens
    for i in range(n):  # numpy fallback
        row = data[offsets[i] : offsets[i + 1]][:width]
        out_bytes[i, : len(row)] = row
        out_lens[i] = len(row)
    return out_bytes, out_lens


def self_join_pairs(rows_sorted: np.ndarray, starts: np.ndarray, sizes: np.ndarray):
    """Emit all unordered within-group pairs; None -> caller uses numpy path.

    Output dtype follows the rows dtype: int32 rows emit int32 pairs (the
    preferred path — at billions of pairs the index buffers dominate host
    memory), anything else goes through the int64 kernel.
    """
    lib = _load()
    if lib is None:
        return None
    starts = np.ascontiguousarray(starts, np.int64)
    sizes = np.ascontiguousarray(sizes, np.int64)
    total = lib.count_self_pairs(_ptr(sizes, _i64p), len(sizes))
    if rows_sorted.dtype == np.int32:
        rows32 = np.ascontiguousarray(rows_sorted, np.int32)
        out_i = np.empty(total, np.int32)
        out_j = np.empty(total, np.int32)
        lib.emit_self_pairs_i32(
            _ptr(rows32, _i32p), _ptr(starts, _i64p), _ptr(sizes, _i64p),
            len(sizes), _ptr(out_i, _i32p), _ptr(out_j, _i32p),
        )
        return out_i, out_j
    rows64 = np.ascontiguousarray(rows_sorted, np.int64)
    out_i = np.empty(total, np.int64)
    out_j = np.empty(total, np.int64)
    lib.emit_self_pairs(
        _ptr(rows64, _i64p), _ptr(starts, _i64p), _ptr(sizes, _i64p),
        len(sizes), _ptr(out_i, _i64p), _ptr(out_j, _i64p),
    )
    return out_i, out_j


def cross_join_pairs(l_rows, l_starts, l_sizes, r_rows, r_starts, r_sizes):
    """Emit all cross-table pairs for matched key groups; None -> numpy path.

    Like self_join_pairs, int32 row arrays use the int32 kernel."""
    lib = _load()
    if lib is None:
        return None
    l_starts = np.ascontiguousarray(l_starts, np.int64)
    l_sizes = np.ascontiguousarray(l_sizes, np.int64)
    r_starts = np.ascontiguousarray(r_starts, np.int64)
    r_sizes = np.ascontiguousarray(r_sizes, np.int64)
    total = lib.count_cross_pairs(
        _ptr(l_sizes, _i64p), _ptr(r_sizes, _i64p), len(l_sizes)
    )
    if l_rows.dtype == np.int32 and r_rows.dtype == np.int32:
        lr = np.ascontiguousarray(l_rows, np.int32)
        rr = np.ascontiguousarray(r_rows, np.int32)
        out_i = np.empty(total, np.int32)
        out_j = np.empty(total, np.int32)
        lib.emit_cross_pairs_i32(
            _ptr(lr, _i32p), _ptr(l_starts, _i64p), _ptr(l_sizes, _i64p),
            _ptr(rr, _i32p), _ptr(r_starts, _i64p), _ptr(r_sizes, _i64p),
            len(l_sizes), _ptr(out_i, _i32p), _ptr(out_j, _i32p),
        )
        return out_i, out_j
    lr = np.ascontiguousarray(l_rows, np.int64)
    rr = np.ascontiguousarray(r_rows, np.int64)
    out_i = np.empty(total, np.int64)
    out_j = np.empty(total, np.int64)
    lib.emit_cross_pairs(
        _ptr(lr, _i64p), _ptr(l_starts, _i64p), _ptr(l_sizes, _i64p),
        _ptr(rr, _i64p), _ptr(r_starts, _i64p), _ptr(r_sizes, _i64p),
        len(l_sizes), _ptr(out_i, _i64p), _ptr(out_j, _i64p),
    )
    return out_i, out_j
