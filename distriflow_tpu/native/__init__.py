"""Native host-kernel loader: C++ fast paths built from the committed source.

The reference has no native layer (SURVEY.md §2.1); this framework's
host-side hot paths — batch assembly (gather) and federated gradient
aggregation (mean over client buffers) — get multi-threaded C++ kernels
(``src/distriflow_native.cpp``) compiled on first use with g++ and loaded
via ctypes.

The shared library is never committed (``*.so`` is git-ignored), so the
loader ties it to the source it was built from: the file name carries the
content hash of ``src/distriflow_native.cpp`` and only that name is ever
opened. A library left behind by another revision of the source has another
name, is never loaded, and is removed at the next build. A build that fails
raises with the compiler's output; only a machine with no ``g++`` at all
runs the numpy implementations (``AVAILABLE`` stays False).

Public surface:
- :func:`gather_rows(src, idx)` — ``src[idx]`` into a fresh contiguous array;
- :func:`mean_buffers(bufs)` — elementwise float32 mean over equal-shape arrays;
- ``AVAILABLE`` / :func:`ensure_built` — introspection and explicit build.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import shutil
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src", "distriflow_native.cpp")
_LIB_STEM = "libdistriflow_native"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_no_compiler = False  # decided once: no g++ on PATH, numpy paths serve

AVAILABLE = False

_N_THREADS = min(8, os.cpu_count() or 1)


def _lib_path() -> str:
    """The one library file this source may load: named by the content
    hash of the C++ source as it is on disk now."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"{_LIB_STEM}-{digest}.so")


def _build(lib_path: str) -> None:
    """Compile the shared library to ``lib_path``; raises on failure.

    Compiles to a per-process temp path then ``os.replace``s into place
    (atomic on POSIX) so concurrent first-use builds across processes never
    expose a partially written .so. Libraries of other source revisions
    (any other ``libdistriflow_native*.so`` here) are removed."""
    tmp_path = f"{lib_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-fPIC", "-shared", "-pthread", "-std=c++17",
        _SRC, "-o", tmp_path,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build failed ({' '.join(cmd)}):\n"
                f"{proc.stderr.decode(errors='replace')}")
        os.replace(tmp_path, lib_path)
    finally:
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)
    for stale in glob.glob(os.path.join(_DIR, f"{_LIB_STEM}*.so")):
        if stale != lib_path:
            os.unlink(stale)


def _load(lib_path: str) -> ctypes.CDLL:
    lib = ctypes.CDLL(lib_path)
    lib.df_gather_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.c_void_p, ctypes.c_int,
    ]
    lib.df_gather_rows.restype = None
    lib.df_mean_f32.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_void_p, ctypes.c_int,
    ]
    lib.df_mean_f32.restype = None
    return lib


def ensure_built(force: bool = False) -> bool:
    """Build (if needed) and load the library for the source on disk.

    True: the C++ kernels are loaded. False: this machine has no ``g++``
    and the numpy implementations serve. A compiler that is present and
    fails, or a library that will not load, raises."""
    global _lib, _no_compiler, AVAILABLE
    with _lock:
        if _lib is not None and not force:
            return True
        if _no_compiler and not force:
            return False
        lib_path = _lib_path()
        if force or not os.path.exists(lib_path):
            if shutil.which("g++") is None:
                _no_compiler = True
                return False
            _build(lib_path)
        _lib = _load(lib_path)
        AVAILABLE = True
        return True


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """``src[idx]`` (leading-axis gather) into a fresh contiguous array."""
    src = np.asarray(src)
    idx = np.ascontiguousarray(idx, np.int64)
    if idx.ndim != 1:
        raise ValueError(f"idx must be 1-D, got shape {idx.shape}")
    if len(idx) and (idx.min() < 0 or idx.max() >= len(src)):
        raise IndexError(f"index out of range for {len(src)} rows")
    # a strided view would need a full contiguous copy of the source to use
    # the C kernel — numpy fancy indexing copies only the batch rows instead
    if not ensure_built() or not src.flags["C_CONTIGUOUS"]:
        return np.ascontiguousarray(src[idx])
    out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    _lib.df_gather_rows(
        src.ctypes.data, row_bytes, idx.ctypes.data, len(idx),
        out.ctypes.data, _N_THREADS,
    )
    return out


def mean_buffers(bufs: Sequence[np.ndarray]) -> np.ndarray:
    """Elementwise float32 mean over equal-shape arrays (aggregation path)."""
    if not bufs:
        raise ValueError("mean_buffers needs at least one buffer")
    arrs: List[np.ndarray] = [np.ascontiguousarray(b, np.float32) for b in bufs]
    shape = arrs[0].shape
    if any(a.shape != shape for a in arrs):
        raise ValueError("mean_buffers requires equal shapes")
    if not ensure_built():
        return np.mean(np.stack(arrs), axis=0, dtype=np.float32)
    out = np.empty(shape, np.float32)
    ptrs = (ctypes.c_void_p * len(arrs))(*[a.ctypes.data for a in arrs])
    _lib.df_mean_f32(ptrs, len(arrs), arrs[0].size, out.ctypes.data, _N_THREADS)
    return out
