"""Native (C++) runtime core, loaded via ctypes.

Builds lazily with g++ on first use (no pybind11 in the image; plain C ABI)
from ``ffnative.cpp`` and nothing else: the shared object is named by the
hash of that source, so an object built from other source is never loaded.
Every entry point has a pure-Python twin (the tests' reference); when the
build fails the twins take over and a warning says so, once. The native
path is the default where it matters (dataloader gather, search-time
task-graph simulation).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ffnative.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_HERE, f"libffnative-{tag}.so")


def _build(so: str) -> None:
    """Compile ``ffnative.cpp`` to ``so``; atomic, so concurrent first
    uses (pytest workers, replicas) never load a half-written object."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
             _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def implementation() -> str:
    """Which implementation serves this process: "native" or "python"."""
    return "native" if get_lib() is not None else "python"


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if the build
    failed (said once, as a warning)."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        so = _so_path()
        try:
            if not os.path.exists(so):
                _build(so)
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.CalledProcessError,
                subprocess.TimeoutExpired) as e:
            _build_failed = True
            detail = getattr(e, "stderr", b"") or b""
            warnings.warn(
                f"flexflow_tpu.native: building {os.path.basename(_SRC)} "
                f"failed ({type(e).__name__}: {e}); the pure-Python "
                f"implementations are in use. "
                f"{detail.decode(errors='replace')[-400:]}")
            return None
        lib.gather_rows.restype = ctypes.c_int
        lib.gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
        lib.simulate_taskgraph.restype = ctypes.c_double
        lib.simulate_taskgraph.argtypes = [
            ctypes.c_int64, ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        lib.pipeline_create.restype = ctypes.c_void_p
        lib.pipeline_create.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
        lib.pipeline_next.restype = ctypes.c_int64
        lib.pipeline_next.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_void_p)]
        lib.pipeline_destroy.restype = None
        lib.pipeline_destroy.argtypes = [ctypes.c_void_p]
        lib.imm_dominators_native.restype = ctypes.c_int
        lib.imm_dominators_native.argtypes = [
            ctypes.c_int32, ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32)]
        _lib = lib
        return _lib


def gather_rows(src: np.ndarray, indices: np.ndarray,
                n_threads: int = 4) -> np.ndarray:
    """dst[i] = src[indices[i]] — native multithreaded gather with numpy
    fallback (the dataloader's shuffled-batch staging hot loop)."""
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(indices, dtype=np.int64)
    n = src.shape[0]
    if idx.size:
        lo, hi = int(idx.min()), int(idx.max())
        if lo < -n or hi >= n:
            raise IndexError(
                f"gather_rows: index out of range for {n} rows "
                f"(min {lo}, max {hi})")
        if lo < 0:  # numpy negative-index semantics on both paths
            idx = np.where(idx < 0, idx + n, idx)
    lib = get_lib()
    if lib is None:
        return src[idx]
    out_shape = (len(idx),) + src.shape[1:]
    dst = np.empty(out_shape, dtype=src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], initial=1))
    rc = lib.gather_rows(
        src.ctypes.data_as(ctypes.c_void_p),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        dst.ctypes.data_as(ctypes.c_void_p),
        len(idx), row_bytes, n_threads)
    if rc != 0:
        return src[idx]
    return dst


def simulate_taskgraph(costs: np.ndarray, device: np.ndarray,
                       n_devices: int, edges_src: np.ndarray,
                       edges_dst: np.ndarray) -> float:
    """Event-driven task-graph makespan (native; Python fallback)."""
    costs = np.ascontiguousarray(costs, dtype=np.float64)
    device = np.ascontiguousarray(device, dtype=np.int32)
    esrc = np.ascontiguousarray(edges_src, dtype=np.int32)
    edst = np.ascontiguousarray(edges_dst, dtype=np.int32)
    lib = get_lib()
    if lib is not None:
        r = lib.simulate_taskgraph(
            len(costs), costs.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            device.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n_devices, len(esrc),
            esrc.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            edst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if r < 0:
            raise ValueError(
                "simulate_taskgraph: invalid task graph "
                "(cycle, bad edge, or device id out of range)")
        return float(r)
    return _simulate_py(costs, device, n_devices, esrc, edst)


class BatchPipeline:
    """Double-buffered shuffled-batch staging with a native gather thread:
    batch b+1 is assembled in C++ while Python ships batch b to the device
    (the reference overlaps its zcmem->fbmem batch copy with compute the same
    way).

    With ``copy=True`` (default) each yielded batch is an owned array, safe to
    retain. ``copy=False`` yields zero-copy views into the native double
    buffer — only valid until the next batch is pulled and only for consumers
    that ship the batch to the device before advancing.

    Falls back to synchronous numpy gather when the native library is
    unavailable."""

    def __init__(self, arrays, indices: np.ndarray, batch_size: int,
                 n_threads: int = 4, copy: bool = True):
        self.copy = copy
        self.arrays = [np.ascontiguousarray(a) for a in arrays]
        self.indices = np.ascontiguousarray(indices, dtype=np.int64)
        self.batch_size = int(batch_size)
        self.num_batches = len(self.indices) // self.batch_size
        self._lib = get_lib()
        self._h = None
        if self._lib is not None and self.num_batches > 0:
            n = len(self.arrays)
            self._src_ptrs = (ctypes.c_void_p * n)(
                *[a.ctypes.data_as(ctypes.c_void_p).value
                  for a in self.arrays])
            self._row_bytes = (ctypes.c_int64 * n)(
                *[a.dtype.itemsize * int(np.prod(a.shape[1:], initial=1))
                  for a in self.arrays])
            self._h = self._lib.pipeline_create(
                n, self._src_ptrs, self._row_bytes,
                self.indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(self.indices), self.batch_size, n_threads)

    def __iter__(self):
        if self._h is None:  # fallback: synchronous gather
            for b in range(self.num_batches):
                sl = self.indices[b * self.batch_size:(b + 1) *
                                  self.batch_size]
                yield [a[sl] for a in self.arrays]
            return
        n = len(self.arrays)
        out_ptrs = (ctypes.c_void_p * n)()
        try:
            while True:
                b = self._lib.pipeline_next(self._h, out_ptrs)
                if b < 0:
                    break
                views = []
                for i, a in enumerate(self.arrays):
                    shape = (self.batch_size,) + a.shape[1:]
                    buf = (ctypes.c_char * (
                        self.batch_size * self._row_bytes[i])).from_address(
                        out_ptrs[i])
                    v = np.frombuffer(buf, dtype=a.dtype).reshape(shape)
                    views.append(v.copy() if self.copy else v)
                yield views
        finally:
            self.close()

    def close(self):
        if self._h is not None:
            self._lib.pipeline_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def imm_dominators_edges(n: int, edges):
    """Immediate dominators of an int-id DAG. edges: iterable of (src, dst).
    Returns an int32 array with -1 for roots, or None when the native library
    is unavailable. Raises ValueError on cycles."""
    lib = get_lib()
    if lib is None:
        return None
    esrc = np.ascontiguousarray([e[0] for e in edges], dtype=np.int32)
    edst = np.ascontiguousarray([e[1] for e in edges], dtype=np.int32)
    out = np.empty(n, dtype=np.int32)
    rc = lib.imm_dominators_native(
        n, len(esrc),
        esrc.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        edst.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc == -2:
        raise ValueError("imm_dominators: graph has a cycle")
    if rc != 0:
        raise ValueError("imm_dominators: invalid edge list")
    return out


def _simulate_py(costs, device, n_devices, esrc, edst) -> float:
    import heapq

    n = len(costs)
    out = [[] for _ in range(n)]
    indeg = [0] * n
    for s, d in zip(esrc, edst):
        out[s].append(int(d))
        indeg[d] += 1
    if any(int(d) < 0 or int(d) >= n_devices for d in device):
        raise ValueError("simulate_taskgraph: device id out of range")
    ready = [0.0] * n
    dev_free = [0.0] * max(n_devices, 1)
    q = [(0.0, i) for i in range(n) if indeg[i] == 0]
    heapq.heapify(q)
    makespan = 0.0
    done = 0
    while q:
        rt, t = heapq.heappop(q)
        dev = int(device[t])
        start = max(rt, dev_free[dev])
        finish = start + float(costs[t])
        dev_free[dev] = finish
        makespan = max(makespan, finish)
        done += 1
        for c in out[t]:
            ready[c] = max(ready[c], finish)
            indeg[c] -= 1
            if indeg[c] == 0:
                heapq.heappush(q, (ready[c], c))
    if done != n:
        raise ValueError("simulate_taskgraph: task graph has a cycle")
    return makespan
