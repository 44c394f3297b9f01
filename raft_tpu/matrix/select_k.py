"""Batched top-k selection — the flagship matrix primitive.

(ref: cpp/include/raft/matrix/select_k.cuh:75 public API;
matrix/detail/select_k-inl.cuh:38 ``choose_select_k_algorithm`` learned
decision tree, applied at :244; radix impl matrix/detail/select_radix.cuh;
warpsort impl matrix/detail/select_warpsort.cuh.)

Semantics preserved from the reference: batched rows, optional input
indices (defaults to 0..len-1 per row), ``select_min`` choosing smallest or
largest, sorted output, stable on the XLA path.

TPU-first algorithm space (no warp shuffles / SM histograms here):
``XLA_TOPK`` lowers to XLA's fused sort/top-k; ``SLOTTED`` is the
certified slot-fold (sort-free, bandwidth-bound, always exact —
select_k_slotted.py) — it plays the reference warpsort family's ROLE
(bandwidth-bound selection keeping per-bucket running minima in
registers) with folds instead of queues; ``CHUNKED`` is the exact
per-chunk+merge large-k algorithm (select_k_chunked.py). The literal
Pallas radix kernel was DELETED in round 3 after never winning any of
66 measured cells over two rounds (a VPU-bound digit histogram loses
to compare/select folds), and a literal bitonic lane-queue is an
anti-fit (every compare-exchange needs cross-lane relayouts) — so the
``RADIX``/``BITONIC`` reference names dispatch to CHUNKED/SLOTTED,
the algorithms serving their roles. The AUTO heuristic is
table-driven off measured TPU timings the way the reference's learned
tree is generated from benchmark sweeps.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.error import expects
from raft_tpu.matrix.select_k_types import SelectAlgo
from raft_tpu.observability import instrument
from raft_tpu.resilience import fault_point


def _load_select_k_table():
    """Load the measured algorithm table (SELECT_K_MATRIX.json, a sweep
    measured on the chip), if one has been committed. Returns a list of
    (log-coords, SelectAlgo) cells, or None."""
    import json
    import math
    import os

    from raft_tpu.native import _REPO_ROOT

    path = os.environ.get("RAFT_TPU_SELECTK_TABLE") or os.path.join(
        _REPO_ROOT, "SELECT_K_MATRIX.json")
    try:
        with open(path) as f:
            data = json.load(f)
        cells = []
        for row in data.get("rows", []):
            # RADIX is deliberately NOT a candidate: its kernel was
            # deleted (round 3) and the name now aliases CHUNKED, so
            # historical radix timings must not label cells
            timings = {name: row[name] for name in
                       ("XLA_TOPK", "SLOTTED", "CHUNKED")
                       if isinstance(row.get(name), (int, float))
                       and not isinstance(row.get(name), bool)
                       # 0.0 is a measurement artifact (sub-RTT clamp in
                       # Fixture.run), not a real timing — a cell must
                       # never be labeled off an artifact
                       and row[name] > 0.0}
            if not timings:
                continue
            best = min(timings, key=timings.get)
            cells.append(((math.log2(row["batch"]), math.log2(row["len"]),
                           math.log2(row["k"])), SelectAlgo[best]))
        return cells or None
    except Exception:
        # a malformed hand-edited table must never crash AUTO select_k —
        # degrade to the no-table default
        return None


_SELECT_K_TABLE = ...   # lazy-loaded sentinel


def _algo_in_envelope(algo: SelectAlgo, length: int, k: int,
                      dtype=None) -> bool:
    """Whether (length, k, dtype) is inside ``algo``'s implementation
    envelope — the same predicates whose violation makes the impls
    raise NotImplementedError. AUTO consults this BEFORE the table
    lookup so it never dispatches into a guaranteed internal fallback
    (wasted dispatch + mislabeled measurement)."""
    if algo in (SelectAlgo.SLOTTED, SelectAlgo.CHUNKED):
        from raft_tpu.matrix.select_k_types import f32_comparable_keys

        if dtype is not None and not f32_comparable_keys(dtype):
            return False
    if algo == SelectAlgo.SLOTTED:
        from raft_tpu.matrix.select_k_slotted import slotted_envelope

        return k <= slotted_envelope(length, k)[2]
    if algo == SelectAlgo.CHUNKED:
        from raft_tpu.matrix.select_k_chunked import chunked_envelope

        return chunked_envelope(length)
    return True


def choose_select_k_algorithm(n_rows: int, length: int, k: int,
                              dtype=None) -> SelectAlgo:
    """Heuristic algorithm choice. (ref: select_k-inl.cuh:38 — a learned
    decision tree over (rows, cols, k), generated from benchmark sweeps.)

    The TPU analog is table-driven the same way: when a measured
    ``SELECT_K_MATRIX.json`` exists (measured on real TPU — never from
    CPU timings), AUTO picks
    the measured-fastest algorithm of the nearest grid cell in
    (log batch, log len, log k), restricted to algorithms whose
    envelope admits (length, k, dtype) — AUTO never returns a choice
    that would raise internally. Without a table the only
    measurement-justified choice is XLA's top-k (round-1 anchor: XLA
    ≈4.7ms vs Pallas radix ≈43ms on [16,1M] f32, k=64 — the radix
    histogram is VPU-bound; SLOTTED had no TPU numbers yet)."""
    global _SELECT_K_TABLE
    if _SELECT_K_TABLE is ...:
        _SELECT_K_TABLE = _load_select_k_table()
    if _SELECT_K_TABLE:
        import math

        q = (math.log2(max(n_rows, 1)), math.log2(max(length, 1)),
             math.log2(max(k, 1)))
        ok = {a: _algo_in_envelope(a, length, k, dtype)
              for a in {cell[1] for cell in _SELECT_K_TABLE}}
        eligible = [cell for cell in _SELECT_K_TABLE if ok[cell[1]]]
        if eligible:
            _, algo = min(
                eligible,
                key=lambda cell: sum((a - b) ** 2
                                     for a, b in zip(cell[0], q)))
            return algo
    return SelectAlgo.XLA_TOPK


def _xla_select_k(in_val, in_idx, k: int, select_min: bool):
    vals = -in_val if select_min else in_val
    top_v, top_pos = jax.lax.top_k(vals, k)
    out_val = -top_v if select_min else top_v
    out_idx = jnp.take_along_axis(in_idx, top_pos, axis=1)
    return out_val, out_idx


@instrument("matrix.select_k")
def select_k(
    res,
    in_val,
    in_idx=None,
    k: int = 1,
    select_min: bool = True,
    sorted: bool = True,  # noqa: A002
    algo: SelectAlgo = SelectAlgo.AUTO,
    recall_target: float = 0.95,
) -> Tuple[jax.Array, jax.Array]:
    """Select the k smallest (or largest) entries per row.

    Returns ``(out_val [batch, k], out_idx [batch, k])``.
    (ref: matrix/select_k.cuh:75) ``recall_target`` applies to
    ``SelectAlgo.APPROX`` only (inexact by contract; see select_k_types).

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.matrix import select_k
    >>> vals, idx = select_k(None, np.array([[3.0, 1.0, 2.0]]), k=2)
    >>> np.asarray(vals).tolist(), np.asarray(idx).tolist()
    ([[1.0, 2.0]], [[1, 2]])
    """
    fault_point("select_k")
    in_val = jnp.asarray(in_val)
    expects(in_val.ndim == 2, "select_k: in_val must be [batch, len]")
    batch, length = in_val.shape
    expects(0 < k <= length, "select_k: k=%d out of range for len=%d", k, length)
    if in_idx is None:
        in_idx = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None, :],
                                  (batch, length))
    else:
        in_idx = jnp.asarray(in_idx)
        expects(in_idx.shape == in_val.shape, "select_k: in_idx shape mismatch")

    explicit = algo != SelectAlgo.AUTO
    if not explicit:
        algo = choose_select_k_algorithm(batch, length, k,
                                         dtype=in_val.dtype)

    if algo in (SelectAlgo.RADIX, SelectAlgo.BITONIC):
        # the Pallas radix kernel was DELETED in round 3: across two
        # measured matrices (66 cells) it never won a single cell —
        # 5-40× behind XLA/SLOTTED everywhere, including the large-k
        # regime it nominally served (SELECT_K_MATRIX.json; CHANGELOG).
        # The reference names keep dispatching to the algorithms that
        # play their ROLES: radix (large-k filtering) → CHUNKED,
        # warp-queue → SLOTTED.
        algo = (SelectAlgo.CHUNKED if algo == SelectAlgo.RADIX
                else SelectAlgo.SLOTTED)

    if algo == SelectAlgo.SLOTTED:
        from raft_tpu.matrix.select_k_slotted import select_k_slotted

        try:
            return select_k_slotted(in_val, in_idx, k, select_min)
        except NotImplementedError as e:
            # AUTO (nearest-cell lookup) may land outside the envelope —
            # that fallback is silent by design; only an EXPLICIT request
            # warns, because silently measuring the XLA path instead
            # would invalidate benchmarks/tests of the named algorithm
            if explicit:
                import warnings

                warnings.warn(
                    f"select_k: explicit algo=SLOTTED outside its "
                    f"envelope ({e}); falling back to XLA top-k",
                    RuntimeWarning, stacklevel=2)

    if algo == SelectAlgo.CHUNKED:
        from raft_tpu.matrix.select_k_chunked import select_k_chunked

        try:
            return select_k_chunked(in_val, in_idx, k, select_min)
        except NotImplementedError as e:
            if explicit:
                import warnings

                warnings.warn(
                    f"select_k: explicit algo=CHUNKED outside its "
                    f"envelope ({e}); falling back to XLA top-k",
                    RuntimeWarning, stacklevel=2)

    if algo == SelectAlgo.APPROX:
        # XLA's TPU-hardware aggregate top-k (recall-targeted, INEXACT —
        # see select_k_types). Returns positions; gather the caller ids.
        fn = jax.lax.approx_min_k if select_min else jax.lax.approx_max_k
        vals_a, pos = fn(in_val, k, recall_target=float(recall_target))
        return vals_a, jnp.take_along_axis(in_idx, pos, axis=1)

    return _xla_select_k(in_val, in_idx, k, select_min)
