"""ctypes bindings for the native host runtime (native/chunkcopy.cpp).

The C++ side parallelizes the strided chunk↔global copies that the host
paths of the framework perform around device scatters (DArray-from-init
assembly, ``from_chunks``, checkpoint restore).  The library is
compiled on first use with the system g++ into ``build/`` and bound via
ctypes; every caller has a pure-numpy fallback, so the framework works
identically without a toolchain — the native path is a performance tier,
not a dependency.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

__all__ = ["available", "assemble", "scatter_chunks", "worth_using", "tier"]

_REPO = Path(__file__).resolve().parents[2]
_SRC = _REPO / "native" / "chunkcopy.cpp"
_BUILD = _REPO / "build"
_SO = _BUILD / "libchunkcopy.so"

_lock = threading.Lock()
_lib = None
_tried = False
_built_here = False


def _load():
    global _lib, _tried, _built_here
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            if (not _SO.exists()
                    or (_SRC.exists()
                        and _SO.stat().st_mtime < _SRC.stat().st_mtime)):
                _BUILD.mkdir(exist_ok=True)
                # compile to a process-unique temp path and atomically
                # rename, so concurrent processes never dlopen a
                # half-written .so
                tmp = _SO.with_suffix(f".{os.getpid()}.tmp.so")
                subprocess.run(  # dalint: disable=DAL008 — one-shot native build; the lock exists precisely to make every caller wait for the .so
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                     "-o", str(tmp), str(_SRC)],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, _SO)
                _built_here = True
            try:
                lib = ctypes.CDLL(str(_SO))
            except OSError:
                # racing writer may have just replaced the file; retry once
                lib = ctypes.CDLL(str(_SO))
            lib.chunk_copy.restype = ctypes.c_int
            lib.chunk_copy.argtypes = [
                ctypes.c_char_p,                      # dst
                ctypes.POINTER(ctypes.c_int64),       # dst_dims
                ctypes.c_int,                         # ndim
                ctypes.POINTER(ctypes.c_char_p),      # chunks
                ctypes.POINTER(ctypes.c_int64),       # shapes
                ctypes.POINTER(ctypes.c_int64),       # offsets
                ctypes.c_int64,                       # n_chunks
                ctypes.c_int64,                       # itemsize
                ctypes.c_int,                         # scatter
                ctypes.c_int,                         # n_threads
            ]
            _lib = lib
        except Exception:
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def tier() -> str:
    """Which copy tier this process runs: ``"native-built"`` (compiled
    from ``native/chunkcopy.cpp`` by this process), ``"native-prebuilt"``
    (a ``build/libchunkcopy.so`` that was already on disk) or
    ``"numpy"`` (no toolchain; the pure-numpy fallback)."""
    if _load() is None:
        return "numpy"
    return "native-built" if _built_here else "native-prebuilt"


def worth_using(total_bytes: int, n_chunks: int) -> bool:
    """Engage the native path only where thread-parallel copies can win:
    multi-core hosts moving enough data to amortize the ctypes marshalling.
    On a single-core host numpy's serial memcpy is already bandwidth-bound
    and the native path is pure overhead."""
    return (available() and (os.cpu_count() or 1) > 1
            and n_chunks > 1 and total_bytes >= 32 * 1024 * 1024)


def _call(dst: np.ndarray, chunks, offsets, scatter: bool,
          n_threads: int | None) -> bool:
    lib = _load()
    if lib is None:
        return False
    if not dst.flags.c_contiguous or dst.dtype.hasobject:
        return False
    for c, o in zip(chunks, offsets):
        if not (isinstance(c, np.ndarray) and c.flags.c_contiguous
                and c.dtype == dst.dtype and c.ndim == dst.ndim):
            return False
        # bounds: the C side memcpys blindly; a bad region must fail the
        # same way the numpy fallback does, not corrupt the heap
        for d in range(dst.ndim):
            if o[d] < 0 or o[d] + c.shape[d] > dst.shape[d]:
                raise ValueError(
                    f"chunk at offset {tuple(o)} with shape {c.shape} "
                    f"exceeds destination dims {dst.shape}")
    n = len(chunks)
    if n == 0:
        return True
    nd = dst.ndim
    dims_arr = (ctypes.c_int64 * max(nd, 1))(*(dst.shape or (1,)))
    ptr_arr = (ctypes.c_char_p * n)()
    for i, c in enumerate(chunks):
        ptr_arr[i] = ctypes.cast(ctypes.c_void_p(c.ctypes.data),
                                 ctypes.c_char_p)
    shp = (ctypes.c_int64 * (n * max(nd, 1)))()
    off = (ctypes.c_int64 * (n * max(nd, 1)))()
    for i, (c, o) in enumerate(zip(chunks, offsets)):
        for d in range(nd):
            shp[i * nd + d] = c.shape[d]
            off[i * nd + d] = o[d]
    if n_threads is None:
        n_threads = min(n, os.cpu_count() or 1)
    rc = lib.chunk_copy(
        dst.ctypes.data_as(ctypes.c_char_p), dims_arr, nd,
        ptr_arr, shp, off, n, dst.dtype.itemsize, int(scatter),
        int(n_threads))
    return rc == 0


def assemble(dst: np.ndarray, chunks, offsets, n_threads=None) -> np.ndarray:
    """Copy contiguous row-major ``chunks`` into ``dst`` at elementwise
    ``offsets`` (one origin tuple per chunk).  Falls back to numpy slicing
    when the native library is unavailable or inputs are non-contiguous."""
    if not _call(dst, list(chunks), list(offsets), scatter=False,
                 n_threads=n_threads):
        for c, o in zip(chunks, offsets):
            sl = tuple(slice(o[d], o[d] + c.shape[d]) for d in range(dst.ndim))
            dst[sl] = c
    return dst


def scatter_chunks(src: np.ndarray, shapes, offsets, n_threads=None) -> list:
    """Slice ``src`` apart into freshly-allocated contiguous chunks of the
    given shapes at the given origins (inverse of assemble)."""
    chunks = [np.empty(tuple(s), dtype=src.dtype) for s in shapes]
    if not _call(src, chunks, list(offsets), scatter=True,
                 n_threads=n_threads):
        for c, o in zip(chunks, offsets):
            sl = tuple(slice(o[d], o[d] + c.shape[d]) for d in range(src.ndim))
            c[...] = src[sl]
    return chunks
