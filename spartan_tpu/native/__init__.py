"""ctypes loader for the native C++ runtime components.

Compiles ``spartan_native.cpp`` on first use (g++) into a .so named by
the hash of the source, so a copied tree (which keeps no mtimes)
rebuilds exactly when the source changed, and exposes typed wrappers.
Falls back gracefully (``lib() is None``) when no toolchain is
available; callers keep their pure-Python paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "spartan_native.cpp")


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libspartan_native-{digest}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build(so: str) -> bool:
    # build beside the target and rename: concurrent builders (test
    # workers) never load a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except Exception:
        if os.path.exists(tmp):
            os.remove(tmp)
        return False


def lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            l = ctypes.CDLL(so)
        except OSError:
            return None
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        l.extent_intersect_batch.restype = ctypes.c_int64
        l.extent_intersect_batch.argtypes = [
            i64p, i64p, ctypes.c_int64, ctypes.c_int64, i64p, i64p,
            i64p, i64p, u8p]
        l.extent_any_overlap.restype = ctypes.c_int32
        l.extent_any_overlap.argtypes = [i64p, i64p, ctypes.c_int64,
                                         ctypes.c_int64]
        l.extent_total_volume.restype = ctypes.c_int64
        l.extent_total_volume.argtypes = [i64p, i64p, ctypes.c_int64,
                                          ctypes.c_int64]
        charpp = ctypes.POINTER(ctypes.c_char_p)
        l.blob_write_parallel.restype = ctypes.c_int32
        l.blob_write_parallel.argtypes = [
            charpp, ctypes.POINTER(u8p), i64p, ctypes.c_int64,
            ctypes.c_int32]
        l.blob_read_parallel.restype = ctypes.c_int32
        l.blob_read_parallel.argtypes = [
            charpp, ctypes.POINTER(u8p), i64p, ctypes.c_int64,
            ctypes.c_int32]
        _lib = l
        return _lib


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def intersect_batch(uls: np.ndarray, lrs: np.ndarray,
                    q_ul: Sequence[int], q_lr: Sequence[int]
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched box intersection. uls/lrs: (n, nd) int64. Returns
    (mask (n,) bool, out_ul (n, nd), out_lr (n, nd))."""
    l = lib()
    uls = np.ascontiguousarray(uls, np.int64)
    lrs = np.ascontiguousarray(lrs, np.int64)
    n, nd = uls.shape
    q_ul = np.ascontiguousarray(q_ul, np.int64)
    q_lr = np.ascontiguousarray(q_lr, np.int64)
    out_ul = np.empty_like(uls)
    out_lr = np.empty_like(lrs)
    mask = np.zeros(n, np.uint8)
    if l is not None:
        l.extent_intersect_batch(
            _i64p(uls), _i64p(lrs), n, nd, _i64p(q_ul), _i64p(q_lr),
            _i64p(out_ul), _i64p(out_lr),
            mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    else:  # NumPy fallback
        iul = np.maximum(uls, q_ul)
        ilr = np.minimum(lrs, q_lr)
        out_ul, out_lr = iul, ilr
        mask = (iul < ilr).all(axis=1).astype(np.uint8)
    return mask.astype(bool), out_ul, out_lr


def any_overlap(uls: np.ndarray, lrs: np.ndarray) -> bool:
    l = lib()
    uls = np.ascontiguousarray(uls, np.int64)
    lrs = np.ascontiguousarray(lrs, np.int64)
    n, nd = uls.shape
    if l is not None:
        return bool(l.extent_any_overlap(_i64p(uls), _i64p(lrs), n, nd))
    for i in range(n):
        iul = np.maximum(uls[i], uls[i + 1:])
        ilr = np.minimum(lrs[i], lrs[i + 1:])
        if len(iul) and (iul < ilr).all(axis=1).any():
            return True
    return False


def total_volume(uls: np.ndarray, lrs: np.ndarray) -> int:
    l = lib()
    uls = np.ascontiguousarray(uls, np.int64)
    lrs = np.ascontiguousarray(lrs, np.int64)
    n, nd = uls.shape
    if l is not None:
        return int(l.extent_total_volume(_i64p(uls), _i64p(lrs), n, nd))
    return int((lrs - uls).prod(axis=1).sum())


def write_blobs(paths: List[str], arrays: List[np.ndarray],
                nthreads: int = 8) -> None:
    """Write each array's raw bytes to its path, concurrently in C++."""
    l = lib()
    arrays = [np.ascontiguousarray(a) for a in arrays]
    if l is None:
        for p, a in zip(paths, arrays):
            with open(p, "wb") as f:
                f.write(a.tobytes())
        return
    n = len(paths)
    c_paths = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
    u8p = ctypes.POINTER(ctypes.c_uint8)
    c_ptrs = (u8p * n)(*[a.ctypes.data_as(u8p) for a in arrays])
    c_sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    rc = l.blob_write_parallel(c_paths, c_ptrs, c_sizes, n, nthreads)
    if rc != 0:
        raise IOError(f"native blob write failed (rc={rc})")


def read_blobs(paths: List[str], arrays: List[np.ndarray],
               nthreads: int = 8) -> None:
    """Fill each (preallocated, contiguous) array from its path."""
    l = lib()
    if l is None:
        for p, a in zip(paths, arrays):
            with open(p, "rb") as f:
                buf = f.read(a.nbytes)
            a[...] = np.frombuffer(buf, a.dtype).reshape(a.shape)
        return
    n = len(paths)
    for a in arrays:
        if not a.flags["C_CONTIGUOUS"]:
            raise ValueError("read_blobs needs contiguous targets")
    c_paths = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
    u8p = ctypes.POINTER(ctypes.c_uint8)
    c_ptrs = (u8p * n)(*[a.ctypes.data_as(u8p) for a in arrays])
    c_sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    rc = l.blob_read_parallel(c_paths, c_ptrs, c_sizes, n, nthreads)
    if rc != 0:
        raise IOError(f"native blob read failed (rc={rc})")
