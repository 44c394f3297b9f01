"""Histogram — multi-strategy, like the reference.

(ref: cpp/include/raft/stats/histogram.cuh + detail/histogram.cuh (487
LoC): ``HistType`` selects among global-atomics and shared-memory-bits
strategies. TPU has no atomics; the strategy space re-designed TPU-first:

- ``SegmentSum`` — binning + ``bincount`` (XLA sorted scatter-add): the
  general path, any n_bins, the global-atomics role.
- ``OneHot`` — row-chunked one-hot compare + reduce, pure dense VPU work,
  no scatter at all; wins when n_bins is small enough that the
  [chunk, n_bins, batch] compare is cheaper than a scatter pass.
- ``Blocked`` — the Pallas VMEM-accumulator kernel
  (raft_tpu.ops.histogram_pallas): the smem-histogram role — the
  [n_bins, batch] counter block stays resident in VMEM across the
  row-block grid.

``Auto`` mirrors the reference's selection heuristic mechanism with a
TPU rule: small bin spaces take the dense strategies (Blocked on TPU,
OneHot elsewhere), everything else SegmentSum.)
"""

from __future__ import annotations

import enum
from typing import Callable, Optional

import jax
import jax.numpy as jnp


class HistType(enum.Enum):
    """(ref: stats/histogram.cuh ``HistType`` — strategy selector; the
    legacy names alias their TPU role-equivalents.)"""

    Auto = "auto"
    SegmentSum = "segment_sum"
    OneHot = "one_hot"
    Blocked = "blocked"
    # reference-name compat aliases
    GlobalAtomics = "segment_sum"
    SmemBits = "blocked"


class IdentityBinner:
    """(ref: stats/histogram.cuh ``IdentityBinner`` — data are bin ids)"""

    def __call__(self, x, row):
        return x.astype(jnp.int32)


# dense strategies hold an [n_bins, chunk-or-SUB, batch] one-hot temp;
# past this bin count the scatter path wins (measured envelope)
_DENSE_MAX_BINS = 1024
# Blocked kernel VMEM budget for the one-hot temp + accumulator + input
# block; past it Mosaic would fail to fit the kernel
_BLOCKED_VMEM_BYTES = 4 << 20


def _choose_hist_type(n: int, batch: int, n_bins: int) -> HistType:
    """(ref: detail/histogram.cuh strategy pick; TPU rule.)"""
    if batch == 1:
        # 1-D (the value_histogram ravel path): the dense strategies use
        # 1 of 128 lanes; XLA's fused bincount handles this shape well
        return HistType.SegmentSum
    if n_bins <= _DENSE_MAX_BINS:
        from raft_tpu.ops.histogram_pallas import _SUB

        fits_vmem = (n_bins * batch * (_SUB + 2) * 4 + 1024 * batch * 4
                     <= _BLOCKED_VMEM_BYTES)
        if jax.default_backend() == "tpu" and n >= 4096 and fits_vmem:
            return HistType.Blocked
        return HistType.OneHot
    return HistType.SegmentSum


def _hist_segment_sum(bins, n_bins: int):
    def col_hist(b):
        return jnp.bincount(b, length=n_bins)

    return jax.vmap(col_hist, in_axes=1, out_axes=1)(bins)


def _hist_one_hot(bins, n_bins: int, chunk: Optional[int] = None):
    """Row-chunked dense count: counts[b, c] = Σ_r [bins[r, c] = b]."""
    n, batch = bins.shape
    if chunk is None:
        # bound the [n_bins, chunk, batch] compare temp to ~16 MB int32
        chunk = max(8, min(2048, (1 << 22) // max(n_bins * batch, 1)))
    pad = (-n) % chunk
    if pad:  # pad id -1 matches no bin
        bins = jnp.concatenate([bins, jnp.full((pad, batch), -1, jnp.int32)])
    blocks = bins.reshape(-1, chunk, batch)
    ids = jnp.arange(n_bins, dtype=jnp.int32)[:, None, None]

    def body(carry, blk):
        onehot = (blk[None, :, :] == ids).astype(jnp.int32)
        return carry + jnp.sum(onehot, axis=1), None

    init = jnp.zeros((n_bins, batch), jnp.int32)
    counts, _ = jax.lax.scan(body, init, blocks)
    return counts


def histogram(res, data, n_bins: int, binner: Optional[Callable] = None,
              hist_type: HistType = HistType.Auto):
    """Batched histogram: data [n, batch] → counts [n_bins, batch].
    1-D input gives [n_bins]. (ref: stats/histogram.cuh ``histogram`` —
    same column-batched layout and strategy-enum contract.)"""
    data = jnp.asarray(data)
    one_d = data.ndim == 1
    if one_d:
        data = data[:, None]
    if binner is None:
        binner = IdentityBinner()
    cols = jnp.arange(data.shape[1])
    bins = binner(data, cols[None, :])
    bins = jnp.clip(bins, 0, n_bins - 1)

    ht = hist_type
    if ht is HistType.Auto:
        ht = _choose_hist_type(bins.shape[0], bins.shape[1], n_bins)
    if ht.value == "blocked":
        from raft_tpu.ops.histogram_pallas import histogram_blocked

        out = histogram_blocked(bins, n_bins)
    elif ht.value == "one_hot":
        out = _hist_one_hot(bins, n_bins)
    else:
        out = _hist_segment_sum(bins, n_bins)
    return out[:, 0] if one_d else out


def value_histogram(res, values, n_bins: int, lo=None, hi=None):
    """Convenience equal-width binning over a value range."""
    values = jnp.asarray(values)
    lo = jnp.min(values) if lo is None else lo
    hi = jnp.max(values) if hi is None else hi
    width = jnp.maximum((hi - lo) / n_bins, 1e-30)
    bins = jnp.clip(((values - lo) / width).astype(jnp.int32), 0, n_bins - 1)
    return histogram(res, bins, n_bins)
