"""Native hostops loader.

(ref: python/libraft/libraft/load.py:15-30 — the dlopen shim for
libraft.so. Same role here: locate/build cpp/build/libraft_tpu_hostops.so,
bind via ctypes (no pybind11 in this environment), and degrade to
pure-python fallbacks when no toolchain is available.)
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_CPP_DIR = os.path.join(_REPO_ROOT, "cpp")
_SO_PATH = os.path.join(_CPP_DIR, "build", "libraft_tpu_hostops.so")

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()
_load_attempted = False


def _stale() -> bool:
    """Missing, or older than its source: the build output is not
    committed, so a copied tree may carry one built elsewhere."""
    src = os.path.join(_CPP_DIR, "hostops.cpp")
    return (not os.path.exists(_SO_PATH)
            or os.path.getmtime(_SO_PATH) < os.path.getmtime(src))


def _try_build() -> bool:
    """Build into a per-process file, then rename over the library:
    concurrent test workers never load a half-written one."""
    tmp = os.path.join("build", f".hostops.{os.getpid()}.so")
    try:
        subprocess.run(["make", "-B", "-C", _CPP_DIR, f"OUT={tmp}"],
                       check=True, capture_output=True, timeout=120)
        os.replace(os.path.join(_CPP_DIR, tmp), _SO_PATH)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def load() -> Optional[ctypes.CDLL]:
    """dlopen the hostops library, building it on first use."""
    global _lib, _load_attempted
    with _lib_lock:
        if _lib is not None or _load_attempted:
            return _lib
        _load_attempted = True
        if _stale() and not _try_build():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            return None
        lib.pcg32_fill_uint32.argtypes = [
            ctypes.c_uint64, ctypes.c_uint64,
            np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
            ctypes.c_int64]
        lib.pcg32_fill_uniform.argtypes = [
            ctypes.c_uint64, ctypes.c_uint64,
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int64]
        lib.host_select_k.argtypes = [
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
        lib.host_pairwise_l2.argtypes = [
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")]
        lib.host_coo_coalesce.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int32,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")]
        lib.host_coo_coalesce.restype = ctypes.c_int64
        lib.tiled_layout_sizes.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
        lib.tiled_layout_fill.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")]
        lib.tiled_layout_v2_sizes.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
        lib.tiled_layout_v2_fill.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")]
        lib.pair_layout_sizes.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
        lib.pair_layout_fill.argtypes = [
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


# ---------------- PCG32 (native or pure-python fallback) ----------------
def _pcg32_python(seed: int, stream: int, n: int) -> np.ndarray:
    """Bit-exact python rendering of the same PCG32 XSH-RR stream."""
    mask64 = (1 << 64) - 1
    state = 0
    inc = ((stream << 1) | 1) & mask64

    def step(state):
        return (state * 6364136223846793005 + inc) & mask64

    def output(old):
        xorshifted = (((old >> 18) ^ old) >> 27) & 0xFFFFFFFF
        rot = old >> 59
        return ((xorshifted >> rot) | (xorshifted << ((32 - rot) & 31))) & 0xFFFFFFFF

    state = step(state)
    state = (state + seed) & mask64
    state = step(state)
    out = np.empty(n, np.uint32)
    for i in range(n):
        old = state
        state = step(state)
        out[i] = output(old)
    return out


def pcg32_uint32(seed: int, n: int, stream: int = 0) -> np.ndarray:
    """PCG32 random uint32 stream (reference-compatible semantics).
    (ref: thirdparty/pcg/pcg_basic.c stream behavior; GenPC in
    random/rng_state.hpp)"""
    lib = load()
    if lib is not None:
        out = np.empty(n, np.uint32)
        lib.pcg32_fill_uint32(seed, stream, out, n)
        return out
    return _pcg32_python(seed, stream, n)


def pcg32_uniform(seed: int, n: int, stream: int = 0) -> np.ndarray:
    """Uniform [0,1) floats from the PCG32 stream (top 24 bits)."""
    lib = load()
    if lib is not None:
        out = np.empty(n, np.float32)
        lib.pcg32_fill_uniform(seed, stream, out, n)
        return out
    bits = _pcg32_python(seed, stream, n)
    return ((bits >> 8).astype(np.float32) * (1.0 / 16777216.0)).astype(np.float32)


# ---------------- host verification kernels ----------------
def host_select_k(values: np.ndarray, k: int, select_min: bool = True):
    """Host reference top-k (native when available).
    (ref: the naive host loops in cpp/tests/test_utils)"""
    values = np.ascontiguousarray(values, np.float32)
    n_rows, row_len = values.shape
    k = min(k, row_len)  # clamp; keeps native and fallback shapes identical
    lib = load()
    if lib is not None:
        out_v = np.empty((n_rows, k), np.float32)
        out_i = np.empty((n_rows, k), np.int32)
        lib.host_select_k(values, n_rows, row_len, k, int(select_min),
                          out_v, out_i)
        return out_v, out_i
    order = np.argsort(values if select_min else -values, axis=1, kind="stable")
    idx = order[:, :k].astype(np.int32)
    return np.take_along_axis(values, idx, axis=1), idx


def host_pairwise_l2(x: np.ndarray, y: np.ndarray, sqrt: bool = False):
    x = np.ascontiguousarray(x, np.float32)
    y = np.ascontiguousarray(y, np.float32)
    lib = load()
    if lib is not None:
        out = np.empty((x.shape[0], y.shape[0]), np.float32)
        lib.host_pairwise_l2(x, y, x.shape[0], y.shape[0], x.shape[1],
                             int(sqrt), out)
        return out
    d2 = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    return np.sqrt(d2) if sqrt else d2


def host_coo_coalesce(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                      n_cols: int):
    """Sort + sum-duplicates on host (native fast path for the sparse
    coalesce used by add/symmetrize/laplacian)."""
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    lib = load()
    if lib is not None:
        out_r = np.empty_like(rows)
        out_c = np.empty_like(cols)
        out_v = np.empty_like(vals)
        n = lib.host_coo_coalesce(rows, cols, vals, len(rows), n_cols,
                                  out_r, out_c, out_v)
        return out_r[:n], out_c[:n], out_v[:n]
    keys = rows.astype(np.int64) * n_cols + cols
    uniq, inverse = np.unique(keys, return_inverse=True)
    out_v = np.zeros(len(uniq), np.float32)
    np.add.at(out_v, inverse, vals)
    return ((uniq // n_cols).astype(np.int32), (uniq % n_cols).astype(np.int32),
            out_v)


def tiled_layout(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 n_rows: int, n_cols: int, C: int, R: int, E: int):
    """Native tiled-ELL layout (see cpp/hostops.cpp tiled_layout_*).
    Returns the same tuple the numpy path in sparse/tiled.py builds, or
    None when the native library is unavailable."""
    lib = load()
    if lib is None or len(rows) == 0:
        return None
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    # the C++ pass indexes histograms by id/tile with no bounds checks —
    # validate HERE so bad input raises instead of corrupting the heap
    if (rows.min() < 0 or cols.min() < 0
            or rows.max() >= n_rows or cols.max() >= n_cols):
        raise ValueError(
            "tiled_layout: row/col ids out of range for shape "
            f"({n_rows}, {n_cols})")
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    nnz = len(rows)
    sizes = np.zeros(2, np.int64)
    lib.tiled_layout_sizes(rows, cols, nnz, n_rows, n_cols, C, R, E, sizes)
    gp, sp = int(sizes[0]), int(sizes[1])
    n_row_tiles = max(1, -(-n_rows // R))
    pv = np.empty(gp, np.float32)
    pc = np.empty(gp, np.int32)
    cct = np.empty(gp // E, np.int32)
    perm = np.empty(sp, np.int32)
    rloc = np.empty(sp, np.int32)
    crt = np.empty(sp // E, np.int32)
    visited = np.zeros(n_row_tiles, np.uint8)
    lib.tiled_layout_fill(rows, cols, vals, nnz, n_rows, n_cols, C, R, E,
                          pv, pc, cct, perm, rloc, crt, visited)
    return pv, pc, cct, perm, rloc, crt, visited.astype(bool)


def tiled_layout_v2(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                    n_rows: int, n_cols: int, C: int, R: int, E: int):
    """Native v2 tiled-ELL layout (8-aligned buckets, ROW-granular perm
    — see cpp/hostops.cpp tiled_layout_v2_*). Returns (pv, pc, cct,
    perm_rows, rloc, crt, visited) bit-identical to the numpy v2 branch
    in sparse/tiled.py, or None when the native library is
    unavailable."""
    lib = load()
    if lib is None or len(rows) == 0:
        return None
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    # the C++ pass indexes by id/tile with no bounds checks — validate
    # HERE so bad input raises instead of corrupting the heap
    if (rows.min() < 0 or cols.min() < 0
            or rows.max() >= n_rows or cols.max() >= n_cols):
        raise ValueError(
            "tiled_layout_v2: row/col ids out of range for shape "
            f"({n_rows}, {n_cols})")
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    nnz = len(rows)
    sizes = np.zeros(2, np.int64)
    lib.tiled_layout_v2_sizes(rows, cols, nnz, n_rows, n_cols, C, R, E,
                              sizes)
    gp, sp = int(sizes[0]), int(sizes[1])
    n_row_tiles = max(1, -(-n_rows // R))
    pv = np.empty(gp, np.float32)
    pc = np.empty(gp, np.int32)
    cct = np.empty(gp // E, np.int32)
    perm_rows = np.empty(sp // 8, np.int32)
    rloc = np.empty(sp, np.int32)
    crt = np.empty(sp // E, np.int32)
    visited = np.zeros(n_row_tiles, np.uint8)
    lib.tiled_layout_v2_fill(rows, cols, vals, nnz, n_rows, n_cols,
                             C, R, E, gp, sp,
                             pv, pc, cct, perm_rows, rloc, crt, visited)
    return pv, pc, cct, perm_rows, rloc, crt, visited.astype(bool)


def pair_layout(rows: np.ndarray, cols: np.ndarray, n_rows: int,
                n_cols: int, R: int, C: int, E: int):
    """Native pair-tiled layout (see cpp/hostops.cpp pair_layout_*).
    Returns (rloc, cloc, chunk_row_tile, chunk_col_tile, pos) — the same
    arrays the numpy path in sparse/tiled.py builds — or None when the
    native library is unavailable."""
    lib = load()
    if lib is None or len(rows) == 0:
        return None
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    # the C++ pass indexes by id/tile with no bounds checks — validate
    # HERE so bad input raises instead of corrupting the heap
    if (rows.min() < 0 or cols.min() < 0
            or rows.max() >= n_rows or cols.max() >= n_cols):
        raise ValueError(
            "pair_layout: row/col ids out of range for shape "
            f"({n_rows}, {n_cols})")
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    nnz = len(rows)
    size = np.zeros(1, np.int64)
    lib.pair_layout_sizes(rows, cols, nnz, n_cols, R, C, E, size)
    p = int(size[0])
    rloc = np.empty(p, np.int32)
    cloc = np.empty(p, np.int32)
    crt = np.empty(p // E, np.int32)
    cct = np.empty(p // E, np.int32)
    pos = np.empty(nnz, np.int32)
    lib.pair_layout_fill(rows, cols, nnz, n_cols, R, C, E,
                         rloc, cloc, crt, cct, pos)
    return rloc, cloc, crt, cct, pos
