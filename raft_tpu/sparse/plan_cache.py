"""Persistent sparse tile-plan cache.

The tiled-ELL / pair layouts (:mod:`raft_tpu.sparse.tiled`) are one-time
HOST conversions — 62.7 s cold / 39.8 s for the pairs layout at a
2M-nnz scale — that previously amortized only
within one process. This module persists prepared plans to disk, keyed
by a SPARSITY-STRUCTURE fingerprint (shape + tiling params + a digest
of the row/col id streams), so a spectral job restarted tomorrow pays a
~ms ``np.load`` instead of a minute of sorting.

Contract:

- The fingerprint covers everything the LAYOUT depends on: layout kind
  + version, matrix shape, (C, R, E), and the exact nnz id streams.
  Two matrices with the same structure share a plan.
- Plans whose arrays bake VALUES in (tiled-ELL ``vals``) also store a
  values digest in the sidecar metadata; a lookup with different values
  is an honest MISS (recompute + overwrite) — never a silently wrong
  hit. The pair layout is structure-only, so it hits regardless of
  values.
- Loads/saves NEVER raise into the conversion path: any I/O or format
  problem degrades to a miss (save: a logged warning). Writes go
  through the shared :mod:`raft_tpu.core.diskio` atomic-write helper
  (tmp + fsync + rename + parent-dir fsync), so a killed process — or
  a power loss right after the rename — cannot leave a torn plan.

Config (env):

- ``RAFT_TPU_TILE_PLAN_CACHE`` — cache directory; ``0``/``off``
  disables; unset defaults to ``~/.cache/raft_tpu/tile_plans``.
- ``RAFT_TPU_TILE_PLAN_CACHE_MIN_NNZ`` — persistence threshold
  (default 200000): tiny conversions are cheaper than the disk round
  trip and would litter the cache (the tier-1 suite's matrices stay
  below it unless a test opts in).
- ``RAFT_TPU_TILE_PLAN_CACHE_MAX_MB`` — total on-disk size cap
  (default 2048 MB; ``0``/negative = unbounded). Enforced after every
  save with least-recently-USED eviction: a hit touches its file's
  mtime, so long-lived structures survive and one-off fingerprints age
  out — without the cap the cache grows without bound across
  processes. Evictions are counted
  (``raft_tpu_tile_plan_cache_evictions_total``).

Hits/misses are counted in the observability registry
(``raft_tpu_tile_plan_cache_{hits,misses}_total``). Reads carry the
``plan_cache_read`` fault-injection site: an injected ``corrupt`` read
degrades to a miss (recompute), exactly like a real torn file.
"""

from __future__ import annotations

import hashlib
import os
from typing import Dict, Optional, Tuple

import numpy as np

PLAN_VERSION = 1
_DEFAULT_MIN_NNZ = 200_000
_DEFAULT_MAX_MB = 2048

HITS = "raft_tpu_tile_plan_cache_hits_total"
MISSES = "raft_tpu_tile_plan_cache_misses_total"
EVICTIONS = "raft_tpu_tile_plan_cache_evictions_total"


def cache_dir() -> Optional[str]:
    """The active cache directory, or None when disabled."""
    env = os.environ.get("RAFT_TPU_TILE_PLAN_CACHE")
    if env is not None:
        if env.strip().lower() in ("", "0", "off", "false"):
            return None
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "raft_tpu",
                        "tile_plans")


def min_nnz() -> int:
    try:
        return int(os.environ.get("RAFT_TPU_TILE_PLAN_CACHE_MIN_NNZ",
                                  _DEFAULT_MIN_NNZ))
    except ValueError:
        return _DEFAULT_MIN_NNZ


def enabled_for(nnz: int) -> bool:
    return cache_dir() is not None and nnz >= min_nnz()


def max_cache_bytes() -> Optional[int]:
    """Size cap in bytes, or None (unbounded) for a non-positive /
    unparseable ``RAFT_TPU_TILE_PLAN_CACHE_MAX_MB``... 0 disables the
    cap, not the cache."""
    raw = os.environ.get("RAFT_TPU_TILE_PLAN_CACHE_MAX_MB")
    try:
        mb = float(raw) if raw is not None else float(_DEFAULT_MAX_MB)
    except ValueError:
        mb = float(_DEFAULT_MAX_MB)
    if mb <= 0:
        return None
    return int(mb * (1 << 20))


def _digest(*parts) -> str:
    h = hashlib.sha256()
    for part in parts:
        if isinstance(part, np.ndarray):
            a = np.ascontiguousarray(part)
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        else:
            h.update(repr(part).encode())
        h.update(b"|")
    return h.hexdigest()[:32]


def structure_fingerprint(kind: str, shape: Tuple[int, int],
                          params: Tuple, rows: np.ndarray,
                          cols: np.ndarray) -> str:
    """Layout-plan key: kind + plan version + shape + tiling params +
    the exact id streams (the CSR indptr/indices decompose into exactly
    these row/col streams — hashing the streams keys both input
    formats identically)."""
    return _digest(kind, PLAN_VERSION, tuple(shape), tuple(params),
                   np.asarray(rows, np.int64), np.asarray(cols, np.int64))


def values_digest(vals) -> str:
    return _digest(np.asarray(vals, np.float32))


def _count(hit: bool) -> None:
    try:
        from raft_tpu.observability import get_registry

        reg = get_registry()
        if not reg.enabled:
            return
        if hit:
            reg.counter(HITS, help="Tile plans served from the "
                                   "persistent cache").inc()
        else:
            reg.counter(MISSES, help="Tile-plan cache lookups that "
                                     "recomputed").inc()
    except Exception:
        pass


def load_plan(fingerprint: str,
              vals_digest: Optional[str] = None) -> Optional[Dict]:
    """The cached plan arrays for ``fingerprint``, or None (miss). When
    ``vals_digest`` is given, a stored plan with a different values
    digest is a miss (the plan's arrays bake those values in). A hit
    touches the file's mtime (the LRU clock for the size cap)."""
    d = cache_dir()
    if d is None:
        return None
    path = os.path.join(d, f"{fingerprint}.npz")
    try:
        from raft_tpu.resilience import fault_point

        if fault_point("plan_cache_read") == "corrupt":
            _count(False)
            return None     # injected torn read → honest miss
    except ImportError:
        pass
    try:
        with np.load(path, allow_pickle=False) as z:
            meta_ver = int(z["__version__"])
            if meta_ver != PLAN_VERSION:
                _count(False)
                return None
            if vals_digest is not None:
                stored = str(z["__vals_digest__"])
                if stored != vals_digest:
                    _count(False)
                    return None
            out = {k: z[k] for k in z.files
                   if not k.startswith("__")}
    except Exception:
        _count(False)
        return None
    _count(True)
    try:
        os.utime(path)          # LRU touch: a hit keeps the plan young
    except OSError:
        pass
    return out


def save_plan(fingerprint: str, arrays: Dict[str, np.ndarray],
              vals_digest: Optional[str] = None) -> bool:
    """Persist a plan atomically; returns False (with a logged warning)
    on any failure — persistence is an optimization, never an error."""
    d = cache_dir()
    if d is None:
        return False
    try:
        os.makedirs(d, exist_ok=True)
        payload = {k: np.asarray(v) for k, v in arrays.items()}
        payload["__version__"] = np.asarray(PLAN_VERSION)
        if vals_digest is not None:
            payload["__vals_digest__"] = np.asarray(vals_digest)
        from raft_tpu.core.diskio import atomic_write

        atomic_write(os.path.join(d, f"{fingerprint}.npz"),
                     lambda f: np.savez(f, **payload))
        _enforce_cap(d)
        return True
    except Exception as e:
        try:
            from raft_tpu.core.logger import log_warn

            log_warn("tile-plan cache: failed to persist %s (%s: %s)",
                     fingerprint, type(e).__name__, e)
        except Exception:
            pass
        return False


def _enforce_cap(d: str) -> int:
    """Evict least-recently-used plans until the directory fits the
    size cap; returns the number evicted. Never raises — a racing
    process deleting a file concurrently is fine."""
    cap = max_cache_bytes()
    if cap is None:
        return 0
    evicted = 0
    try:
        entries = []
        with os.scandir(d) as it:
            for e in it:
                if not e.name.endswith(".npz"):
                    continue
                try:
                    st = e.stat()
                    entries.append((st.st_mtime, st.st_size, e.path))
                except OSError:
                    continue
        total = sum(size for _, size, _ in entries)
        entries.sort()               # oldest mtime (least recently used) first
        for _, size, path in entries:
            if total <= cap:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            evicted += 1
        if evicted:
            try:
                from raft_tpu.observability import get_registry

                reg = get_registry()
                if reg.enabled:
                    reg.counter(EVICTIONS,
                                help="Tile plans evicted by the LRU "
                                     "size cap").inc(evicted)
            except Exception:
                pass
            from raft_tpu.core.logger import log_info

            log_info("tile-plan cache: evicted %d LRU plan(s) to fit "
                     "the %d-byte cap", evicted, cap)
    except Exception:
        pass
    return evicted
