"""Native (C++/OpenMP) runtime components, loaded via ctypes.

TPU-native counterpart of the reference's host-side C++: the OpenMP
refine (neighbors/detail/refine_host-inl.hpp) and the binary dataset
reader (cpp/bench/ann/src/common/dataset.hpp).  The library builds
lazily with g++ on first use; consumers fall back to pure-numpy paths
when the toolchain is unavailable (``available()`` reports which).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "raft_tpu_native.cpp")
# Portable flags only: a binary tuned to the building CPU (-march=native)
# can die of an illegal instruction on another host of the same arch.
_FLAGS = ("-O3", "-shared", "-fPIC", "-fopenmp", "-std=c++17")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _so_path() -> str:
    """The library's file name is keyed by a hash of the source, the
    flags and the machine architecture, so only a binary built from
    this very source, for this architecture, is ever loaded — a stale or
    foreign build under the same directory has a different name."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(platform.machine().encode())
    return os.path.join(_HERE, f"libraft_tpu_native-{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    # build to a private name, then rename: concurrent builders (test
    # workers) never load a half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *_FLAGS, _SRC, "-o", tmp], check=True,
                       capture_output=True, timeout=300)
        os.replace(tmp, so)
        return True
    except (subprocess.CalledProcessError, OSError,
            subprocess.TimeoutExpired):
        if os.path.exists(tmp):
            os.remove(tmp)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            _build_failed = True
            return None
        lib = ctypes.CDLL(so)
        lib.refine_host_f32.restype = ctypes.c_int
        lib.refine_host_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.bin_header.restype = ctypes.c_int
        lib.bin_header.argtypes = [ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.bin_read.restype = ctypes.c_int
        lib.bin_read.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                                 ctypes.c_void_p, ctypes.c_int32]
        lib.bin_write.restype = ctypes.c_int
        lib.bin_write.argtypes = [ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int32,
                                  ctypes.c_int32, ctypes.c_int32]
        lib.native_num_threads.restype = ctypes.c_int
        lib.native_num_threads.argtypes = []
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native library is built and loadable."""
    return _load() is not None


_METRIC_CODES = {"sqeuclidean": 0, "inner_product": 1, "euclidean": 2, "cosine": 3}


def refine_host(dataset: np.ndarray, queries: np.ndarray,
                candidate_ids: np.ndarray, k: int,
                metric: str = "sqeuclidean"):
    """Exact host-side candidate re-ranking (reference:
    refine_host-inl.hpp).  Raises RuntimeError if the native library is
    unavailable — callers use neighbors.refine (device) as fallback."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable (g++ build failed)")
    if metric not in _METRIC_CODES:
        raise ValueError(f"unsupported metric {metric!r}")
    ds = np.ascontiguousarray(dataset, np.float32)
    q = np.ascontiguousarray(queries, np.float32)
    cand = np.ascontiguousarray(candidate_ids, np.int32)
    if ds.ndim != 2 or q.ndim != 2 or cand.ndim != 2:
        raise ValueError("dataset/queries/candidate_ids must be 2-D")
    if q.shape[1] != ds.shape[1]:
        raise ValueError(f"query dim {q.shape[1]} != dataset dim {ds.shape[1]}")
    if cand.shape[0] != q.shape[0]:
        raise ValueError(
            f"candidate rows {cand.shape[0]} != query rows {q.shape[0]}")
    if k > cand.shape[1]:
        raise ValueError(f"k={k} > n_candidates={cand.shape[1]}")
    n_q, n_cand = cand.shape
    out_ids = np.empty((n_q, k), np.int32)
    out_d = np.empty((n_q, k), np.float32)
    rc = lib.refine_host_f32(
        ds.ctypes.data, ds.shape[0], ds.shape[1],
        q.ctypes.data, n_q,
        cand.ctypes.data, n_cand,
        k, _METRIC_CODES[metric],
        out_ids.ctypes.data, out_d.ctypes.data,
    )
    if rc != 0:
        raise RuntimeError(f"refine_host_f32 failed: rc={rc}")
    return out_d, out_ids


def bin_header(path: str):
    """(n_rows, dim) of a .fbin/.ibin file."""
    lib = _load()
    if lib is None:
        with open(path, "rb") as f:
            hdr = np.fromfile(f, np.int32, 2)
        return int(hdr[0]), int(hdr[1])
    n = ctypes.c_int32()
    d = ctypes.c_int32()
    rc = lib.bin_header(path.encode(), ctypes.byref(n), ctypes.byref(d))
    if rc != 0:
        raise IOError(f"bin_header({path}) rc={rc}")
    return int(n.value), int(d.value)


def bin_read(path: str, dtype, offset: int = 0, count: int = -1) -> np.ndarray:
    """Read rows [offset, offset+count) of a .fbin/.ibin file."""
    n, d = bin_header(path)
    if count < 0:
        count = n - offset
    if offset < 0 or offset + count > n:
        raise IOError(
            f"bin_read({path}): rows [{offset}, {offset + count}) out of "
            f"range for file with {n} rows"
        )
    dtype = np.dtype(dtype)
    out = np.empty((count, d), dtype)
    lib = _load()
    if lib is None:  # numpy fallback
        with open(path, "rb") as f:
            f.seek(8 + offset * d * dtype.itemsize)
            raw = np.fromfile(f, dtype, count * d)
        if raw.size != count * d:
            raise IOError(f"bin_read({path}): short read")
        return raw.reshape(count, d)
    rc = lib.bin_read(path.encode(), offset, count, out.ctypes.data, dtype.itemsize)
    if rc != 0:
        raise IOError(f"bin_read({path}) rc={rc}")
    return out


def bin_write(path: str, arr: np.ndarray) -> None:
    """Write a 2-D array as .fbin/.ibin."""
    a = np.ascontiguousarray(arr)
    lib = _load()
    if lib is None:
        with open(path, "wb") as f:
            np.asarray(a.shape, np.int32).tofile(f)
            a.tofile(f)
        return
    rc = lib.bin_write(path.encode(), a.ctypes.data, a.shape[0], a.shape[1],
                       a.dtype.itemsize)
    if rc != 0:
        raise IOError(f"bin_write({path}) rc={rc}")


def num_threads() -> int:
    lib = _load()
    return int(lib.native_num_threads()) if lib is not None else 1
