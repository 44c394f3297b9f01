"""IVF-Flat: inverted-list ANN over the fused KNN primitives.

(ref: neighbors/ivf_flat.cuh + detail/ivf_flat_build.cuh /
ivf_flat_search.cuh — the reference's interleaved-list IVF index, the
headline ANN capability that migrated to cuVS. BASELINE's "critical
scoping fact": past the streamed-HBM roofline the only speedup left is
reading LESS of the database; IVF-Flat reads ``n_probes/n_lists`` of
it, trading tracked recall.)

Index layout — the **padded ragged slab** (build_ivf_flat):

- database rows are bucketed by nearest coarse centroid (balanced
  k-means, :mod:`raft_tpu.cluster` — balance keeps per-probe cost
  uniform and pad waste bounded);
- each inverted list is padded up to a multiple of the **row quantum**
  (default 8 — the fused pipeline's sublane multiple), then the lists
  are laid back-to-back in ONE [R, d] slab: ``offsets [L+1]`` row
  offsets, ``sizes [L]`` real lengths, global ids carried alongside in
  ``ids [R]`` (−1 on pad rows). Memory is Σ padded sizes — ragged, not
  L·max;
- the slab's pad rows are exactly the ragged ``rows_valid`` layout
  ``distance.knn_fused._prepare_ops`` now takes: the degenerate exact
  path runs the CERTIFIED packed fused kernel over the whole slab with
  interspersed pads carried as never-wins sentinels.

Search (search_ivf_flat):

1. **coarse probe**: top-``n_probes`` nearest centroids per query via
   the existing fused-L2 top-k machinery
   (:func:`raft_tpu.distance.fused_l2nn.knn`, streamed sweep — the
   fusedL2NN lineage);
2. **fine scan**: the probed lists' slab windows are gathered per
   query and scored with the exact expanded-L2 form (f32 HIGHEST — the
   same score the fused pipeline's rescore evaluates, so the
   ``n_probes = n_lists`` result is id-for-id the brute-force oracle),
   then one top-k over the ``n_probes·window`` candidates;
3. ``n_probes ≥ n_lists`` (or ``k`` beyond the probed capacity)
   **degrades to exact search** with a logged reason — the certified
   fused pipeline over the ragged slab — so the speed/recall knob can
   never silently return worse-than-exact results at exact cost.

``shard="lists"`` (shard_ivf_lists + the sharded search path): WHOLE
lists distribute over a mesh axis via shard_map — each shard scans the
probed lists it owns and the per-shard top-k candidates (global ids)
merge with the PR-4 rank-ordered machinery
(:func:`raft_tpu.distance.knn_sharded._merge_allgather` /
``_merge_tournament``, strategy picked by the ICI cost model).

Observability: build and search are ``@instrument``-ed, carry the
``ivf_build`` / ``ivf_search`` fault sites, emit ``marker`` flight
events (probed-rows fraction rides the search event), and the fine
scan's XLA cost is captured through ``res.profiler.capture_fn`` once
per bucket shape at warm-up (:func:`warm_fine_scan`). Each host
boundary of a search is a span nested under ``ann.search_ivf_flat``:
``ann.coarse_probe``, ``ann.probe_fetch`` (the probe table's wait and
copy to the host), ``ann.fine_scan_plan`` (schedule resolution and the
per-chunk list schedule), ``ann.fine_scan`` (one per chunk's scan
dispatch), ``ann.certificate_sync`` (the per-chunk rerun decision's
host sync) and ``ann.fine_scan_rerun``. The probe table the plan
already holds counts :data:`PROBED_ROWS` with no device work.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core import env
from raft_tpu.core.error import DeviceError, expects
from raft_tpu.core.resources import ensure_resources
from raft_tpu.observability import explain, instrument, span
from raft_tpu.observability.flight import get_flight_recorder
from raft_tpu.observability.quality import (record_certificate,
                                            record_pending)
from raft_tpu.observability.timeline import emit_marker
from raft_tpu.resilience import fault_point
from raft_tpu.resilience.policy import record_degradation

#: database rows the fine scan's probe tables name (the sum of the
#: probed lists' real sizes), counted on the host
PROBED_ROWS = "raft_tpu_ivf_probed_rows_total"

#: inverted-list row quantum: every list pads to a multiple of this
#: (the fused pipeline's 8-row sublane multiple — a slab built at this
#: quantum stays gatherable in whole sublanes). Env override:
#: ``RAFT_TPU_IVF_ROW_QUANTUM``.
DEFAULT_ROW_QUANTUM = 8


def _env_int(name: str, default: int, lo: int = 1) -> int:
    import os

    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return max(lo, int(raw))
    except (TypeError, ValueError):
        from raft_tpu.core.logger import log_warn

        log_warn("%s=%r is not an int — using %d", name, raw, default)
        return default

#: fine-scan gather budget: queries chunk so the [nq, P·W, d] candidate
#: tile stays under ~256 MB f32
_FINE_TILE = 1 << 26


def _query_major_rows(P: int, W: int, d: int) -> int:
    """Query rows of one query-major gather tile (:data:`_FINE_TILE`)."""
    return max(8, _FINE_TILE // max(1, P * W * max(d, 1)))


#: IVF storage dtypes for the fine-scan slab: "f32" gathers full rows;
#: "int8" gathers the per-list symmetric-scale quantized slab (~¼ the
#: probed bytes), prunes to a certified candidate pool and exact-
#: rescoring it from the f32 rows — chunks whose certificate fails
#: rerun the f32 scan, so returned ids never degrade
IVF_DB_DTYPES = ("f32", "int8")

#: rescue-pool oversampling of the quantized fine scan (candidates
#: exact-rescored per query beyond k)
_IVF_RESCORE_PAD = 32

#: fine-scan schedules: "query" = per-query probe-window gather (the
#: PR-8 XLA path), "list" = list-major stream-once Pallas kernels
#: (each probed list read ONCE per query chunk for all queries probing
#: it), "auto" = the resolve_fine_scan cost-model crossover on the
#: index's actual probed-list histogram. Env: RAFT_TPU_IVF_FINE_SCAN.
FINE_SCANS = ("auto", "query", "list")

#: list-major envelope: k must leave headroom inside the 2×128-slot
#: candidate pool or the completeness certificate would fail every
#: query straight into the query-major rerun
_LIST_K_MAX = 96

# compiled sharded-search programs, keyed by full static geometry
# (same pattern as knn_sharded._SHARDED_FUSED_CACHE)
_SHARDED_IVF_CACHE: dict = {}


class IvfFlatIndex:
    """The padded ragged IVF-Flat index (see the module doc). Built by
    :func:`build_ivf_flat`; queried by :func:`search_ivf_flat`. The
    coarse centroids, slab geometry and metric are frozen at build.

    ``Qb`` is the serving-bucket hint (the fused pipeline's tuned query
    block) so the serving engine's bucket ladder derives the same way
    it does for a brute-force :class:`~raft_tpu.distance.knn_fused.
    KnnIndex` snapshot."""

    def __init__(self, centroids, slab, ids, yy_slab, offsets, sizes,
                 padded_sizes, n_rows: int, d_orig: int,
                 row_quantum: int, n_probes_default: int, Qb: int,
                 kmeans_iters: int = 0, balanced: bool = True,
                 db_dtype: str = "f32", slab_q=None, row_scale=None,
                 yy_q=None, eq_rows=None):
        self.centroids = centroids          # [L, d] f32
        self.slab = slab                    # [R, d] f32 (pad rows zero)
        self.ids = ids                      # [R] int32 global ids, -1 pads
        self.yy_slab = yy_slab              # [R] f32 row norms (pads 0)
        self.offsets = offsets              # [L+1] int32 slab row offsets
        self.sizes = sizes                  # [L] int32 real list lengths
        self.padded_sizes = padded_sizes    # [L] int32 quantum-padded
        self.n_rows = n_rows
        self.d_orig = d_orig
        self.row_quantum = row_quantum
        self.n_probes_default = n_probes_default
        self.Qb = Qb
        self.kmeans_iters = kmeans_iters
        self.balanced = balanced
        self.metric = "l2"
        # quantized fine-scan state (db_dtype="int8"): per-LIST
        # symmetric int8 slab + per-row scale/Eq (rows of a list share
        # its scale — stored per row so the probe-window gather pulls
        # them alongside the codes), and the DEQUANTIZED row norms the
        # approximate scorer uses. The f32 slab stays: it is the exact-
        # rescore (and degenerate-exact / sharded) data plane.
        self.db_dtype = db_dtype
        self.slab_q = slab_q                # [R, d] int8 or None
        self.row_scale = row_scale          # [R] f32
        self.yy_q = yy_q                    # [R] f32 (‖ŷ‖², pads 0)
        self.eq_rows = eq_rows              # [R] f32 per-row Eq bound
        # host copies of the geometry (numpy — search wrappers index
        # them without device sync) + the lazy ragged fused operands
        self._np_offsets = np.asarray(offsets)
        self._np_sizes = np.asarray(sizes)
        self._np_padded = np.asarray(padded_sizes)
        self._fused_ops = None
        # lazy per-list host/device geometry for the list-major fine
        # scan (per-list scale + Eq + max row norms)
        self._list_host = None

    @property
    def n_lists(self) -> int:
        return int(self.sizes.shape[0])

    @property
    def probe_window(self) -> int:
        """Static per-probe gather window: the largest padded list."""
        return max(int(self._np_padded.max()), self.row_quantum)

    @property
    def slab_rows(self) -> int:
        return int(self.slab.shape[0])

    def __repr__(self):
        return (f"IvfFlatIndex(n_rows={self.n_rows}, "
                f"n_lists={self.n_lists}, d={self.d_orig}, "
                f"slab_rows={self.slab_rows}, "
                f"window={self.probe_window})")

    def layout(self):
        """This index's slab as the shared explicit
        :class:`~raft_tpu.mutable.layout.IndexLayout` struct — the
        degenerate-exact plane, the mutable subsystem and the brute
        plane all drive the same pure ops over it."""
        from raft_tpu.mutable.layout import IndexLayout

        return IndexLayout(
            self.slab, self.ids, np.asarray(self.ids) >= 0,
            n_rows=self.n_rows, d_orig=self.d_orig,
            offsets=self._np_offsets, sizes=self._np_sizes,
            padded_sizes=self._np_padded, row_quantum=self.row_quantum,
            db_dtype=self.db_dtype if self.db_dtype == "int8" else "f32",
            slab_q=self.slab_q, row_scale=self.row_scale,
            eq_rows=self.eq_rows)


@instrument("ann.build_ivf_flat")
def build_ivf_flat(res, y, n_lists: int, n_probes: Optional[int] = None,
                   max_iter: int = 10, seed: int = 0,
                   balanced: bool = True,
                   row_quantum: Optional[int] = None,
                   max_train_rows: Optional[int] = None,
                   db_dtype: str = "f32") -> IvfFlatIndex:
    """Build an :class:`IvfFlatIndex` over ``y`` [m, d].

    (ref: ivf_flat::build — coarse-train on a sub-sample, assign every
    row, bucket into interleaved lists.) Coarse training runs balanced
    k-means (:func:`raft_tpu.cluster.kmeans_fit`) on at most
    ``max_train_rows`` rows (default ``max(32·n_lists, 4096)`` — the
    trainset_fraction idea), full assignment runs the fusedL2NN argmin
    sweep, and the host lays the lists out as the padded ragged slab
    described in the module doc.

    ``db_dtype="int8"`` (:data:`IVF_DB_DTYPES`) additionally packs the
    slab with per-list symmetric int8 scales (the cuVS int8 IVF-Flat
    shape): the fine scan gathers ~¼ the probed bytes, prunes to a
    certified candidate pool and exact-rescoring it from the kept f32
    rows — id sets never degrade (failed certificates rerun the f32
    scan)."""
    from raft_tpu.cluster import kmeans_fit, kmeans_predict

    fault_point("ivf_build")
    res = ensure_resources(res)
    if db_dtype not in IVF_DB_DTYPES:
        raise ValueError(f"build_ivf_flat: db_dtype must be one of "
                         f"{IVF_DB_DTYPES}, got {db_dtype!r}")
    if row_quantum is None:
        row_quantum = _env_int("RAFT_TPU_IVF_ROW_QUANTUM",
                               DEFAULT_ROW_QUANTUM)
    y = np.asarray(y, np.float32)
    m, d = y.shape
    L = int(n_lists)
    expects(L >= 1, "build_ivf_flat: n_lists must be >= 1, got %d", L)
    expects(L <= m, "build_ivf_flat: n_lists=%d > %d rows", L, m)
    expects(row_quantum >= 1,
            "build_ivf_flat: row_quantum must be >= 1")
    cap = max_train_rows or max(32 * L, 4096)
    if m > cap:
        rng = np.random.default_rng(seed)
        train = y[rng.choice(m, cap, replace=False)]
    else:
        train = y
    km = kmeans_fit(res, train, L, max_iter=max_iter, seed=seed,
                    balanced=balanced)
    labels = np.asarray(kmeans_predict(res, km.centroids, y))

    # ---- host-side ragged layout: the shared IndexLayout op (the
    # mutable subsystem and this builder spell the padded ragged slab
    # through ONE function — raft_tpu.mutable.layout) ----------------
    from raft_tpu.mutable.layout import (quantize_layout,
                                         ragged_layout_from_lists)

    lay = ragged_layout_from_lists(y, labels, L, row_quantum)
    sizes, padded, offsets = lay.sizes, lay.padded_sizes, lay.offsets
    R = lay.slab_rows
    slab, ids = lay.slab, lay.ids

    from raft_tpu.distance.knn_fused import fused_config

    n_probes_default = int(n_probes) if n_probes else max(
        1, min(L, 1 + L // 8))
    q8_kw = {}
    if db_dtype == "int8":
        fault_point("quantize_index")
        lay = quantize_layout(lay)
        deq = lay.slab_q.astype(jnp.float32) * lay.row_scale[:, None]
        q8_kw = dict(db_dtype="int8", slab_q=lay.slab_q,
                     row_scale=lay.row_scale,
                     yy_q=jnp.sum(deq * deq, axis=1),
                     eq_rows=lay.eq_rows)
    idx = IvfFlatIndex(
        centroids=km.centroids,
        slab=jnp.asarray(slab),
        ids=jnp.asarray(ids),
        yy_slab=jnp.sum(jnp.asarray(slab) ** 2, axis=1),
        offsets=jnp.asarray(offsets),
        sizes=jnp.asarray(sizes),
        padded_sizes=jnp.asarray(padded),
        n_rows=m, d_orig=d, row_quantum=int(row_quantum),
        n_probes_default=n_probes_default,
        Qb=fused_config(3).Qb,
        kmeans_iters=km.n_iter, balanced=balanced, **q8_kw)
    emit_marker("ivf_build", n_rows=m, n_lists=L, slab_rows=R,
                window=idx.probe_window,
                pad_frac=round(float(R - m) / max(m, 1), 4),
                size_min=int(sizes.min()), size_max=int(sizes.max()),
                kmeans_iters=km.n_iter, balanced=bool(balanced),
                db_dtype=db_dtype)
    return idx


# --------------------------------------------------------- fine scan
@partial(jax.jit, static_argnames=("k", "P", "W"))
def _fine_scan(x, slab, ids, yy_slab, starts, psizes,
               k: int, P: int, W: int):
    """Score the probed slab windows and select top-k.

    ``starts [nq, P]`` are slab row offsets of the probed lists,
    ``psizes [nq, P]`` their padded lengths (0 = unowned/empty probe).
    The expanded-L2 score is evaluated in f32 HIGHEST — the same form
    (and therefore bitwise the same candidate values) the fused
    pipeline's exact rescore computes, which is what makes the
    ``n_probes = n_lists`` id sets match the oracle exactly."""
    nq = x.shape[0]
    ar = jnp.arange(W, dtype=jnp.int32)
    rows = starts[:, :, None] + ar[None, None, :]          # [nq, P, W]
    within = ar[None, None, :] < psizes[:, :, None]
    rows = jnp.clip(rows, 0, slab.shape[0] - 1).reshape(nq, P * W)
    within = within.reshape(nq, P * W)
    cid = jnp.take(ids, rows)
    valid = within & (cid >= 0)
    yc = jnp.take(slab, rows, axis=0)                      # [nq, PW, d]
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    d2 = (xx + jnp.take(yy_slab, rows)
          - 2.0 * jnp.einsum("qd,qcd->qc", x, yc,
                             precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.where(valid, jnp.maximum(d2, 0.0), jnp.inf)
    neg, pos = jax.lax.top_k(-d2, k)
    vals = -neg
    out_ids = jnp.take_along_axis(cid, pos, axis=1)
    return vals, jnp.where(jnp.isfinite(vals), out_ids, -1)


@partial(jax.jit, static_argnames=("k", "P", "W", "C"))
def _fine_scan_q8(x, slab, slab_q, row_scale, ids, yy_q, starts, psizes,
                  k: int, P: int, W: int, C: int, eq_rows=None):
    """Quantized fine scan: gather the probed windows from the INT8
    slab (+ per-row scale/norm/Eq — ~(d+12)/(4d+8) of the f32 gather
    bytes), score approximately against the dequantized rows ŷ, keep
    the top ``C = k + pad`` candidates, exact-rescore THEM from the f32
    slab, and certify per query that the true top-k cannot hide outside
    the pool: every non-candidate has d2(x, ŷ) ≥ B (the C-th approx
    score), so a violator with true d2 < θ would need
    B ≤ (√θ + Eq)² + e_num — Eq the max quantization bound among the
    probed rows, e_num a conservative f32-accumulation envelope.
    Returns (vals, ids, certified, margin) — the caller reruns failed
    queries through the exact f32 scan, so ids never degrade; margin
    (bound − θ − widen, pre-rerun) feeds the explain plane."""
    nq = x.shape[0]
    ar = jnp.arange(W, dtype=jnp.int32)
    rows = starts[:, :, None] + ar[None, None, :]          # [nq, P, W]
    within = ar[None, None, :] < psizes[:, :, None]
    rows = jnp.clip(rows, 0, slab_q.shape[0] - 1).reshape(nq, P * W)
    within = within.reshape(nq, P * W)
    cid = jnp.take(ids, rows)
    valid = within & (cid >= 0)
    yq = jnp.take(slab_q, rows, axis=0).astype(jnp.float32)
    scl = jnp.take(row_scale, rows)
    yc = yq * scl[:, :, None]                              # ŷ [nq, PW, d]
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    yyq = jnp.take(yy_q, rows)
    d2h = (xx + yyq
           - 2.0 * jnp.einsum("qd,qcd->qc", x, yc,
                              precision=jax.lax.Precision.HIGHEST))
    d2h = jnp.where(valid, jnp.maximum(d2h, 0.0), jnp.inf)
    neg_c, cpos = jax.lax.top_k(-d2h, C)                   # approx pool
    bound = -neg_c[:, C - 1]
    crow = jnp.take_along_axis(rows, cpos, axis=1)
    ccid = jnp.take_along_axis(cid, cpos, axis=1)
    cvalid = jnp.take_along_axis(valid, cpos, axis=1)
    # exact f32 rescore of the C survivors — bitwise the same score
    # the f32 fine scan computes for these rows
    ycf = jnp.take(slab, crow, axis=0)                     # [nq, C, d]
    d2 = (xx + jnp.sum(ycf * ycf, axis=2)
          - 2.0 * jnp.einsum("qd,qcd->qc", x, ycf,
                             precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.where(cvalid, jnp.maximum(d2, 0.0), jnp.inf)
    neg_k, kpos = jax.lax.top_k(-d2, k)
    vals = -neg_k
    out_ids = jnp.take_along_axis(ccid, kpos, axis=1)
    out_ids = jnp.where(jnp.isfinite(vals), out_ids, -1)
    # ---- certificate ----
    theta = vals[:, k - 1]
    eqg = jnp.take(eq_rows, rows)
    eq_w = jnp.max(jnp.where(valid, eqg, 0.0), axis=1)
    yymax = jnp.max(jnp.where(valid, yyq, 0.0), axis=1)
    d_feat = x.shape[1]
    e_num = (d_feat * 2.0 ** -22) * (
        jnp.sqrt(xx[:, 0]) + jnp.sqrt(yymax)) ** 2
    sq_t = jnp.sqrt(jnp.maximum(theta, 0.0))
    widen = 2.0 * sq_t * eq_w + eq_w * eq_w + e_num
    # a pool that covers every probed candidate is trivially complete
    n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)
    certified = (bound >= theta + widen) | (n_valid <= C) \
        | ~jnp.isfinite(bound)
    # explain-plane margin: non-finite where the certificate was
    # trivially complete (finalize filters those out)
    margin = bound - (theta + widen)
    return vals, out_ids, certified, margin


# ----------------------------------------- list-major fine scan
# (ISSUE 14: stream each probed list ONCE per query chunk for every
# query probing it — the inverted-index batching trade, run through
# the ops.fine_scan_pallas kernel family. Ids stay bit-identical to
# the query-major oracle: pooled candidates are exact-rescored with
# the query-major scorer's own formula, reordered into its probe-slot
# candidate order (so ties break identically), and a per-query
# completeness certificate reruns any uncovered query query-major.)

class _ListSchedule:
    """Host-built list-major schedule for one query chunk: the
    transposed probe table. ``sched [4, Lp]`` int32 rows are (clamped
    window start, real rows in the window, their offset within the
    window, list id) — one entry per kernel window of a probed list;
    Lp is padded to the 8-entry cell quantum with the cell count
    rounded to a power of two (capped at the index's own cell count),
    so one compiled program serves a whole probes sweep. The
    [L_probed, q_max] query-group table (q_max padded to 8) + its
    never-wins mask ride along for the cost model and tests — the
    kernel itself consumes the resident probe table directly."""

    __slots__ = ("sched", "scale_l", "n_lists_probed", "q_max",
                 "group", "group_mask", "stream_rows")

    def __init__(self, sched, scale_l, n_lists_probed, q_max, group,
                 group_mask, stream_rows):
        self.sched = sched
        self.scale_l = scale_l
        self.n_lists_probed = n_lists_probed
        self.q_max = q_max
        self.group = group
        self.group_mask = group_mask
        self.stream_rows = stream_rows


def _list_cells(n_entries: int, max_entries: int) -> int:
    """Schedule cell count: entries bucket into 8-entry cells, rounded
    up to a power of two (compile-cache stability across batches) and
    capped at the cells of the whole index (:func:`_max_entries`)."""
    from raft_tpu.ops.fine_scan_pallas import LISTS_PER_CELL

    cells = max(1, -(-n_entries // LISTS_PER_CELL))
    cap = max(1, -(-max_entries // LISTS_PER_CELL))
    return min(1 << (cells - 1).bit_length(), cap)


def _list_segments(index: IvfFlatIndex, lists) -> np.ndarray:
    """Schedule entries per list: one per kernel window of its rows
    (an empty list still takes one)."""
    from raft_tpu.ops.fine_scan_pallas import pad_window

    Wk = pad_window(index.probe_window)
    return np.maximum(1, -(-index._np_sizes[lists] // Wk))


def _max_entries(index: IvfFlatIndex,
                 n_probed: Optional[int] = None) -> int:
    """Most schedule entries of a chunk that probes at most
    ``n_probed`` distinct lists (default: every list)."""
    seg = np.sort(_list_segments(index, np.arange(index.n_lists)))
    return int(seg[::-1][:n_probed].sum())


def build_list_schedule(index: IvfFlatIndex, probes_np) -> _ListSchedule:
    """Invert a chunk's per-query probe lists [nq, P] into the
    per-list query-group schedule (see :class:`_ListSchedule`); a list
    longer than the kernel window takes one entry per window of rows.
    Host-side numpy — the probe table is tiny next to the slab."""
    from raft_tpu.ops.fine_scan_pallas import (LISTS_PER_CELL,
                                               pad_window)

    probes_np = np.asarray(probes_np)
    plist = np.unique(probes_np.ravel())
    plist = plist[plist >= 0].astype(np.int64)
    Lp = int(plist.size)
    Wk = pad_window(index.probe_window)
    R = index.slab_rows
    nseg = _list_segments(index, plist)
    lid = np.repeat(plist, nseg)
    seg = np.arange(lid.size) - np.repeat(np.cumsum(nseg) - nseg, nseg)
    n_ent = int(lid.size)
    Lp_pad = _list_cells(n_ent, _max_entries(index)) * LISTS_PER_CELL
    sched = np.zeros((4, Lp_pad), np.int32)
    sched[3, :] = -1
    starts = index._np_offsets[lid].astype(np.int64) + seg * Wk
    clamped = np.clip(np.minimum(starts, R - Wk), 0, None)
    sched[0, :n_ent] = clamped
    sched[1, :n_ent] = np.clip(index._np_sizes[lid] - seg * Wk, 0, Wk)
    sched[2, :n_ent] = starts - clamped
    sched[3, :n_ent] = lid
    scale_l = np.ones(Lp_pad, np.float32)
    if index.db_dtype == "int8":
        scale_l[:n_ent] = _list_host(index)["scale"][lid]
    # the transposed [L_probed, q_max] query-group table: group g holds
    # the query indices probing plist[g] in query order, padded to the
    # 8-row quantum with the never-wins mask marking real entries
    qi, pj = np.nonzero(probes_np >= 0)          # (query, slot) order
    grp = np.searchsorted(plist, probes_np[qi, pj])
    order = np.argsort(grp, kind="stable")
    grp, qi = grp[order], qi[order]
    counts = np.bincount(grp, minlength=Lp)
    q_max = -(-int(counts.max(initial=1)) // 8) * 8
    group = np.zeros((max(Lp, 1), q_max), np.int32)
    gmask = np.zeros((max(Lp, 1), q_max), bool)
    col = np.arange(grp.size) - np.repeat(np.cumsum(counts) - counts,
                                          counts)
    group[grp, col] = qi
    gmask[grp, col] = True
    stream_rows = int(index._np_padded[plist].sum())
    return _ListSchedule(sched, scale_l, Lp, int(q_max), group, gmask,
                         stream_rows)


def _list_host(index: IvfFlatIndex) -> dict:
    """Lazy per-list host geometry for the list-major path: the
    symmetric int8 scale, the Eq quantization bound and the max
    (dequantized) row norm of each list — certificate inputs gathered
    per probe at search time. Computed once per index."""
    if index._list_host is not None:
        return index._list_host
    offs = index._np_offsets
    L = index.n_lists
    padded = index._np_padded
    yy = np.asarray(index.yy_q if index.db_dtype == "int8"
                    else index.yy_slab)
    yy_lmax = np.zeros(L, np.float32)
    for l in range(L):
        w = int(padded[l])
        if w:
            yy_lmax[l] = yy[int(offs[l]):int(offs[l]) + w].max()
    host = {"yy_lmax": jnp.asarray(yy_lmax)}
    if index.db_dtype == "int8":
        scale = np.asarray(index.row_scale)
        eq = np.asarray(index.eq_rows)
        scale_list = np.ones(L, np.float32)
        eq_list = np.zeros(L, np.float32)
        for l in range(L):
            if int(padded[l]):
                scale_list[l] = scale[int(offs[l])]
                eq_list[l] = eq[int(offs[l])]
        host["scale"] = scale_list
        host["eq_list"] = jnp.asarray(eq_list)
    index._list_host = host
    return host


def _schedule_len(index: IvfFlatIndex) -> int:
    """The index's longest list-major schedule, in whole cells: the
    length every kernel call's schedule is padded to."""
    from raft_tpu.ops.fine_scan_pallas import LISTS_PER_CELL

    return -(-_max_entries(index) // LISTS_PER_CELL) * LISTS_PER_CELL


def _kernel_schedule(index: IvfFlatIndex, sched: _ListSchedule):
    """One chunk's list-major kernel operands: the schedule (and the
    int8 scales) padded to :func:`_schedule_len`, so every chunk of one
    row count runs one compiled program, and the grid cells that hold
    the chunk's entries — all the kernel streams."""
    from raft_tpu.ops.fine_scan_pallas import LISTS_PER_CELL

    n = _schedule_len(index)
    Lp = sched.sched.shape[1]
    full = np.zeros((4, n), np.int32)
    full[3] = -1
    full[:, :Lp] = sched.sched
    scale = np.ones(n, np.float32)
    scale[:Lp] = sched.scale_l
    n_ent = int(np.count_nonzero(sched.sched[3] >= 0))
    return full, scale, max(1, -(-n_ent // LISTS_PER_CELL))


def _pool_finish(x, xx, rows, slab, ids, yy_slab, starts_qm, psizes,
                 k: int, P: int, W: int):
    """Exact-rescore the pooled candidate rows with the query-major
    scorer's own formula (bitwise the values :func:`_fine_scan`
    computes for the same rows), reorder them into the query-major
    candidate order — probe slot × window column, so ``top_k``'s
    lowest-index tie-breaking picks the same winners — and select
    top-k."""
    valid = rows >= 0
    rc = jnp.maximum(rows, 0)
    yc = jnp.take(slab, rc, axis=0)                    # [nq, C2, d]
    d2 = (xx + jnp.take(yy_slab, rc)
          - 2.0 * jnp.einsum("qd,qcd->qc", x, yc,
                             precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.where(valid, jnp.maximum(d2, 0.0), jnp.inf)
    # canonical query-major position of each pooled row: its probe
    # slot p and column within that window
    w = rows[:, :, None] - starts_qm[:, None, :]       # [nq, C2, P]
    match = ((w >= 0) & (w < psizes[:, None, :])
             & valid[:, :, None])
    slot = jnp.argmax(match, axis=2).astype(jnp.int32)
    col = jnp.take_along_axis(w, slot[:, :, None], axis=2)[:, :, 0]
    key = jnp.where(jnp.any(match, axis=2),
                    slot * W + col.astype(jnp.int32), P * W)
    order = jnp.argsort(key, axis=1)
    d2s = jnp.take_along_axis(d2, order, axis=1)
    rs = jnp.take_along_axis(rows, order, axis=1)
    cid = jnp.where(rs >= 0, jnp.take(ids, jnp.maximum(rs, 0)), -1)
    neg, pos = jax.lax.top_k(-d2s, k)
    vals = -neg
    out_ids = jnp.take_along_axis(cid, pos, axis=1)
    return vals, jnp.where(jnp.isfinite(vals), out_ids, -1)


def _pad_kernel_operands(x, probes):
    """Query block + probe table padded to the kernel envelope: rows
    to the 8-sublane quantum (pad probes −2 — matches no list id, so
    pad queries pool nothing) and the probe table to the 128-lane
    tile."""
    nq, P = probes.shape
    nqp = -(-nq // 8) * 8
    xp = jnp.concatenate(
        [x, jnp.zeros((nqp - nq, x.shape[1]), jnp.float32)]) \
        if nqp > nq else x
    pp = jnp.full((nqp, 128), -2, jnp.int32)
    pp = jax.lax.dynamic_update_slice(pp, probes.astype(jnp.int32),
                                      (0, 0))
    return xp, pp, nqp


def _kernel_envelope(bound, theta, widen):
    """certified ⇔ no probed row outside the pool can beat the exact
    k-th value: every excluded row scored ≥ its slot's 3rd-min ≥
    ``bound``; an +inf bound means every slot kept all its rows (the
    pool is trivially complete)."""
    return bound >= theta + widen


def _probe_windows(offsets, padded_sizes, probes):
    """Query-major slab windows of a probe table: each probed list's
    start row and padded length, [nq, P] each."""
    return jnp.take(offsets, probes), jnp.take(padded_sizes, probes)


@partial(jax.jit, static_argnames=("k", "P", "W", "Wk"))
def _fine_scan_list(x, sched, n_cells, probes, slab, ids, yy_slab,
                    offsets, padded_sizes, yy_lmax, k: int, P: int,
                    W: int, Wk: int):
    """List-major fine scan over the f32 slab (see the block comment):
    kernel pools → exact rescore + canonical reorder → certificate.
    Returns (vals, ids, certified, margin) like :func:`_fine_scan_q8`
    — the caller reruns failed queries query-major, so ids never
    drift. The query-major windows the reorder needs come from the
    index's ``offsets`` / ``padded_sizes`` here, on the device."""
    from raft_tpu.ops.fine_scan_pallas import fine_scan_list_major

    nq, d = x.shape
    starts_qm, psizes = _probe_windows(offsets, padded_sizes, probes)
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    xp, pp, nqp = _pad_kernel_operands(x, probes)
    xxp = jnp.concatenate(
        [xx, jnp.zeros((nqp - nq, 1), jnp.float32)]) if nqp > nq else xx
    a1, i1, a2, i2, a3 = fine_scan_list_major(sched, n_cells, xp, xxp,
                                              pp, slab, Wk=Wk)
    rows = jnp.concatenate([i1[:nq], i2[:nq]], axis=1)   # [nq, 256]
    vals, out_ids = _pool_finish(x, xx, rows, slab, ids, yy_slab,
                                 starts_qm, psizes, k, P, W)
    theta = vals[:, k - 1]
    bound = jnp.min(a3[:nq], axis=1)
    # kernel-precision envelope: bf16 hi/lo cross term + the in-kernel
    # MXU-contracted row norms (2⁻¹⁶-grade splits) + f32 accumulation
    yymax = jnp.max(jnp.take(yy_lmax, probes), axis=1)
    span = (jnp.sqrt(xx[:, 0]) + jnp.sqrt(yymax)) ** 2
    widen = (2.0 ** -13 + d * 2.0 ** -22) * span
    certified = _kernel_envelope(bound, theta, widen)
    return vals, out_ids, certified, bound - (theta + widen)


@partial(jax.jit, static_argnames=("k", "P", "W", "Wk"))
def _fine_scan_list_q8(x, sched, scale_l, n_cells, probes, slab_q, slab,
                       ids, yy_slab, yy_lmax, eq_list, offsets,
                       padded_sizes, k: int, P: int, W: int, Wk: int):
    """INT8 list-major fine scan: streams the quantized slab (~¼ the
    probed bytes) through :func:`ops.fine_scan_pallas.
    fine_scan_list_major_q8` with per-list dequant-in-register scales,
    then the same exact-rescore/reorder/certificate pipeline — the
    certificate additionally widens by the probed lists' recorded Eq
    bound exactly like the query-major :func:`_fine_scan_q8`."""
    from raft_tpu.ops.fine_scan_pallas import fine_scan_list_major_q8

    nq, d = x.shape
    starts_qm, psizes = _probe_windows(offsets, padded_sizes, probes)
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    xp, pp, nqp = _pad_kernel_operands(x, probes)
    xxp = jnp.concatenate(
        [xx, jnp.zeros((nqp - nq, 1), jnp.float32)]) if nqp > nq else xx
    a1, i1, a2, i2, a3 = fine_scan_list_major_q8(
        sched, scale_l, n_cells, xp, xxp, pp, slab_q, Wk=Wk)
    rows = jnp.concatenate([i1[:nq], i2[:nq]], axis=1)
    vals, out_ids = _pool_finish(x, xx, rows, slab, ids, yy_slab,
                                 starts_qm, psizes, k, P, W)
    theta = vals[:, k - 1]
    bound = jnp.min(a3[:nq], axis=1)
    yymax = jnp.max(jnp.take(yy_lmax, probes), axis=1)
    eq_w = jnp.max(jnp.take(eq_list, probes), axis=1)
    span = (jnp.sqrt(xx[:, 0]) + jnp.sqrt(yymax)) ** 2
    e_k = (2.0 ** -13 + d * 2.0 ** -22) * span
    sq_t = jnp.sqrt(jnp.maximum(theta, 0.0))
    widen = 2.0 * sq_t * eq_w + eq_w * eq_w + e_k
    certified = _kernel_envelope(bound, theta, widen)
    return vals, out_ids, certified, bound - (theta + widen)


def _list_major_chunk(index: IvfFlatIndex, nq: int) -> int:
    """Query rows of one list-major kernel call for an ``nq``-row
    search: the largest power-of-two multiple of 8 rows whose cell fits
    the scoped-VMEM budget at the index's kernel window, width and slab
    dtype, capped at ``nq`` rounded up to 8. The kernel holds the query
    block and its pools resident and streams each probed list once per
    call, so a search inside that envelope runs as one chunk: one
    schedule, one dispatch, one certificate sync."""
    from raft_tpu.ops.fine_scan_pallas import (fine_scan_vmem_footprint,
                                               pad_window)
    from raft_tpu.ops.fused_l2_topk_pallas import vmem_budget

    Wk = pad_window(index.probe_window)
    q8 = index.db_dtype == "int8"
    cap = -(-max(1, nq) // 8) * 8
    budget = vmem_budget()
    rows = 8
    while rows < cap and fine_scan_vmem_footprint(
            Wk, 2 * rows, index.d_orig, q8) <= budget:
        rows *= 2
    return min(rows, cap)


def resolve_fine_scan(index: IvfFlatIndex, nq: int, k: int, P: int,
                      W: int, requested: Optional[str] = None,
                      probes_np=None, chunk: Optional[int] = None
                      ) -> str:
    """EFFECTIVE fine-scan schedule for a call — decided (and logged)
    in the non-jitted wrapper like ``resolve_grid_order``. ``None``
    reads ``RAFT_TPU_IVF_FINE_SCAN`` (default ``auto``).

    Envelope (outside it every request runs query-major, with a
    logged downgrade for an explicit ``list``): the slab must cover
    one kernel window, k the candidate pool, the probe count the
    128-lane probe table, the cell fit the scoped-VMEM budget, and on
    real TPUs the feature width must be lane-aligned and an int8 slab's
    row quantum a multiple of 8. ``chunk`` is the query rows of one
    kernel call (:func:`_list_major_chunk` for a search).

    ``auto`` consults the schema-5 ``fine_scan`` tune-table column
    (:func:`raft_tpu.tune.ivf.fine_scan_config`) first, then falls to
    the cost-model crossover on the index's ACTUAL probed-list-size
    histogram (:func:`~raft_tpu.observability.costmodel.
    choose_fine_scan` over :func:`~raft_tpu.observability.costmodel.
    ivf_traffic_model`)."""
    from raft_tpu.observability.costmodel import (DB_DTYPE_BYTES,
                                                  FINE_SCAN_MARGIN,
                                                  choose_fine_scan,
                                                  ivf_traffic_model)
    from raft_tpu.ops.fine_scan_pallas import (fine_scan_vmem_footprint,
                                               pad_window)
    from raft_tpu.ops.fused_l2_topk_pallas import vmem_budget
    from raft_tpu.ops.utils import interpret_mode

    req = requested if requested is not None \
        else env.get("RAFT_TPU_IVF_FINE_SCAN")
    if req not in FINE_SCANS:
        raise ValueError(f"fine_scan must be one of {FINE_SCANS}, "
                         f"got {req!r}")
    if req == "query":
        return "query"
    Wk = pad_window(W)
    d = index.d_orig
    quant = index.db_dtype == "int8"
    nqp = -(-min(nq, chunk or nq) // 8) * 8
    reason = None
    if index.slab_rows < Wk:
        reason = (f"slab rows {index.slab_rows} < kernel window {Wk}")
    elif k > _LIST_K_MAX:
        reason = f"k={k} > {_LIST_K_MAX} exceeds the candidate pool"
    elif P > 128:
        reason = f"n_probes={P} > 128 exceeds the probe table"
    elif fine_scan_vmem_footprint(Wk, nqp, d, quant) > vmem_budget():
        reason = "cell footprint over the scoped-VMEM budget"
    elif not interpret_mode() and d % 128:
        reason = f"d={d} is not lane-aligned on a real TPU"
    elif not interpret_mode() and quant and index.row_quantum % 8:
        reason = (f"row quantum {index.row_quantum} leaves int8 windows "
                  f"off the 8-row tiling on a real TPU")
    if reason is not None:
        if req == "list":
            from raft_tpu.core.logger import log_warn

            log_warn("fine_scan='list' outside the list-major envelope "
                     "(%s) — using 'query' for this call", reason)
        return "query"
    if req == "list":
        return "list"
    # auto — tuned table first, then the cost-model crossover
    from raft_tpu.tune.ivf import fine_scan_config

    tuned = fine_scan_config(index.n_lists, P)
    if tuned in ("query", "list"):
        return tuned
    sizes = index._np_sizes
    padded = index._np_padded
    if probes_np is not None:
        # the actual probe table: exact per-chunk union of probed
        # lists vs the exact gather, same margin as the model path
        probes_np = np.asarray(probes_np)
        step = max(1, int(chunk or nq))
        bpe = DB_DTYPE_BYTES[index.db_dtype
                             if quant else "f32"]
        per_row = d * bpe + 8 + (8 if quant else 0)
        stream = 0.0
        for s in range(0, probes_np.shape[0], step):
            u = np.unique(probes_np[s:s + step].ravel())
            stream += float(padded[u[u >= 0]].sum()) * per_row
        stream += float(nq) * min(256, P * W) * d * 4.0
        gather = float(nq) * P * W * per_row
        if quant:
            gather += float(nq) * min(k + _IVF_RESCORE_PAD, P * W) \
                * d * 4.0
        return "list" if gather > FINE_SCAN_MARGIN * max(stream, 1.0) \
            else "query"
    model = ivf_traffic_model(
        nq, index.n_rows, d, k, index.n_lists, P, W, index.slab_rows,
        db_dtype=index.db_dtype if quant else "f32",
        list_sizes=sizes, padded_sizes=padded)
    return choose_fine_scan(model)


def warm_fine_scan(res, index: IvfFlatIndex, nq: int, k: int,
                   n_probes: int) -> int:
    """Pre-compile BOTH fine-scan schedules a serving bucket of ``nq``
    queries can reach: the query-major gather programs (through the
    public wrapper, so its chunking/rerun programs warm too) and, at
    each list-major chunk size the bucket runs (:func:`_list_major_chunk`),
    the one list-major program every schedule of that chunk runs
    (:func:`_kernel_schedule` pads them all to one length and streams
    only their own cells), plus the chunk's certificate-failure rerun
    (its tile operands and row merge). Called from the snapshot warmup
    so a live request can never pay a compile whichever way the
    :func:`resolve_fine_scan` crossover lands. Returns the count of
    list-major scan programs warmed (0 = the bucket is outside the
    list-major envelope).

    The query-major chunk's XLA cost is captured here, once per bucket
    shape, through ``res.profiler.capture_fn`` — never on a live
    search."""
    from raft_tpu.ops.fine_scan_pallas import pad_window

    P = min(max(1, int(n_probes)), index.n_lists)
    if P >= index.n_lists or nq < 1:
        return 0            # the degenerate-exact plane — one schedule
    res = ensure_resources(res)
    W = index.probe_window
    Wk = pad_window(W)
    d = index.d_orig
    x0 = np.zeros((nq, d), np.float32)
    out = search_ivf_flat(res, index, x0, k, n_probes=P,
                          fine_scan="query")
    jax.block_until_ready(out)
    nq_c = min(nq, _query_major_rows(P, W, d))
    try:
        zeros = jnp.zeros((nq_c, P), jnp.int32)
        res.profiler.capture_fn(
            "ann.ivf_fine_scan", _fine_scan,
            jnp.zeros((nq_c, d), jnp.float32), index.slab, index.ids,
            index.yy_slab, zeros, zeros, k=k, P=P, W=W)
    except Exception:
        pass
    chunk = _list_major_chunk(index, nq)
    if resolve_fine_scan(index, nq, k, P, W, "list", chunk=chunk) \
            != "list":
        return 0
    host = _list_host(index)
    n = _schedule_len(index)
    sched = np.zeros((4, n), np.int32)
    sched[3] = -1
    sizes = sorted({min(nq, chunk), nq % chunk or min(nq, chunk)})
    for rows in sizes:
        xc = jnp.zeros((rows, d), jnp.float32)
        probes0 = jnp.zeros((rows, P), jnp.int32)
        if index.db_dtype == "int8":
            out = _fine_scan_list_q8(
                xc, jnp.asarray(sched), jnp.ones(n, jnp.float32), 1,
                probes0, index.slab_q, index.slab, index.ids,
                index.yy_slab, host["yy_lmax"], host["eq_list"],
                index.offsets, index.padded_sizes, k=k, P=P, W=W, Wk=Wk)
        else:
            out = _fine_scan_list(
                xc, jnp.asarray(sched), 1, probes0, index.slab,
                index.ids, index.yy_slab, index.offsets,
                index.padded_sizes, host["yy_lmax"], k=k, P=P, W=W, Wk=Wk)
        # the rerun of a chunk whose first and last rows fail: both
        # tile shapes it can cut, and the merge at each
        vals, ids_c, ok, _ = out
        ok_h = np.ones(rows, bool)
        ok_h[[0, -1]] = False
        jax.block_until_ready(_rerun_failed_tiles(
            index, xc, probes0, ok, ok_h, vals, ids_c, k, P, W))
    return len(sizes)


def _coarse_probe(res, centroids, x, n_probes: int):
    """Top-``n_probes`` nearest coarse centroids per query through the
    existing fused-L2 top-k machinery (the streamed sweep — centroid
    counts are small, so the threshold-gated merge path is the right
    tool on every backend)."""
    from raft_tpu.distance.fused_l2nn import knn as _knn

    _, lists = _knn(res, centroids, x, n_probes, metric="sqeuclidean",
                    algo="streamed")
    return lists


# ------------------------------------------------- exact degradation
def _slab_fused_geometry(index: IvfFlatIndex):
    """Lazy certified-fused operands for the WHOLE slab with the ragged
    ``rows_valid`` mask — the degenerate-exact data plane. Re-expressed
    over the shared layout ops (:func:`raft_tpu.mutable.layout.
    fused_ops_for_layout` — ONE spelling of the packed ragged geometry
    for this plane, the brute plane and the mutable subsystem); the
    exact plane always prepares the f32 slab (it IS the rescore
    source), whatever the index streams."""
    if index._fused_ops is not None:
        return index._fused_ops
    from raft_tpu.mutable.layout import fused_ops_for_layout

    fops = fused_ops_for_layout(index.layout(), passes=3, metric="l2",
                                db_dtype=None)
    index._fused_ops = (fops.ops, fops.rv, fops.T, fops.Qb, fops.g,
                        fops.pbits)
    return index._fused_ops


def _exact_search(res, index: IvfFlatIndex, x, k: int):
    """Exact top-k over the ragged slab through the certified packed
    fused pipeline (``rows_valid`` mask), slab positions mapped back to
    global ids — bitwise the oracle's values (same exact-f32 rescore
    score function over the same rows)."""
    from raft_tpu.distance.knn_fused import (_LANES, _POOL_PAD,
                                             _Q_CHUNK, _knn_fused_core)

    ops, rv, T, Qb, g, pbits = _slab_fused_geometry(index)
    yp, y_hi, y_lo, yyh_k, yy_raw = ops
    M = yp.shape[0]
    n_tiles = M // T
    S_pool = -(-n_tiles // g) * _LANES
    expects(k <= 2 * S_pool,
            "search_ivf_flat: k=%d too large for the exact-path pool "
            "%d (shrink k or grow the index)", k, 2 * S_pool)
    x = jnp.asarray(x, jnp.float32)
    nq = x.shape[0]
    if nq > _Q_CHUNK:
        outs = [_exact_search(res, index, x[s:s + _Q_CHUNK], k)
                for s in range(0, nq, _Q_CHUNK)]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))
    dpad = y_hi.shape[1] - x.shape[1]
    if dpad:
        x = jnp.concatenate(
            [x, jnp.zeros((nq, dpad), jnp.float32)], axis=1)
    Qb_eff = min(Qb, ((nq + 7) // 8) * 8)
    qpad = (-nq) % Qb_eff
    if qpad:
        x = jnp.concatenate(
            [x, jnp.zeros((qpad, x.shape[1]), jnp.float32)])
    vals, pos, n_fail, margin, n_over = _knn_fused_core(
        x, yp, y_hi, y_lo, yyh_k, yy_raw, k=k, T=T, Qb=Qb_eff, g=g,
        passes=3, metric="l2", m=M, rescore=True, pbits=pbits,
        with_stats=True, rows_valid=rv)
    # certificate telemetry for the degenerate-exact plane (device
    # scalar — resolved at the next quality.drain())
    from raft_tpu.distance.knn_fused import (fixup_tiers_for,
                                             rescore_pool_width)

    record_pending("ann.ivf_exact", n_fail, n_queries=x.shape[0],
                   pool_width=rescore_pool_width(k, S_pool, True),
                   fix_tiers=fixup_tiers_for(M), n_over=n_over)
    if explain.active() is not None:
        explain.note_margin("ann.ivf_exact",
                            margin[:nq] if qpad else margin)
    vals, pos = vals[:nq], pos[:nq]
    gids = jnp.where(pos >= 0,
                     jnp.take(index.ids, jnp.maximum(pos, 0)), -1)
    return vals, gids


# ------------------------------------------------------------ search
def _query_major_chunk(index: IvfFlatIndex, xs, st, ps, k: int,
                       P: int, W: int, nested: bool = False):
    """One query-major chunk: the per-query probe-window gather scan
    (f32, or the certified int8 gather with its f32 rerun) — the PR-8
    path, now shared by the query-major schedule and the list-major
    certificate-failure rerun. ``nested`` (that rerun, already inside
    its ``ann.fine_scan_rerun`` span) opens no spans of its own, so no
    scan is timed twice."""

    def stage(name: str):
        return contextlib.nullcontext() if nested else span(name)

    if index.db_dtype != "int8":
        # exact f32 scan over the probed rows — no certificate, hence
        # no margin to note (the scan IS the oracle for its pool)
        with stage("ann.fine_scan"):
            return _fine_scan(xs, index.slab, index.ids, index.yy_slab,
                              st, ps, k=k, P=P, W=W)
    C = min(k + _IVF_RESCORE_PAD, P * W)
    with stage("ann.fine_scan"):
        vals, ids_c, ok, margin = _fine_scan_q8(
            xs, index.slab, index.slab_q, index.row_scale, index.ids,
            index.yy_q, st, ps, k=k, P=P, W=W, C=C,
            eq_rows=index.eq_rows)
    explain.note_margin("ann.search_ivf_flat", margin)
    with stage("ann.certificate_sync"):
        n_fail = int(jnp.sum(~ok))
        # quality telemetry: this path ALREADY syncs (the int() above
        # decides the rerun), so the counters cost nothing extra —
        # the IVF slice of the certificate/fixup evidence plane
        record_certificate("ann.search_ivf_flat",
                           n_queries=int(xs.shape[0]), n_fail=n_fail,
                           pool_width=C, fixup_rows=n_fail or None,
                           rerun=bool(n_fail), db_dtype="int8",
                           n_probes=P)
    if n_fail:
        # quantization certificate failed for some queries: the
        # true top-k may extend past the rescored pool — rerun the
        # chunk through the exact f32 scan and keep certified rows
        # from the quantized pass (bytes saved stand; correctness
        # never rides on the margin)
        with stage("ann.fine_scan_rerun"):
            emit_marker("ivf_q8_fallback", n_fail=n_fail,
                        nq=int(xs.shape[0]))
            explain.note(rerun="q8_exact", rerun_rows=n_fail)
            fv, fi = _fine_scan(xs, index.slab, index.ids,
                                index.yy_slab, st, ps, k=k, P=P, W=W)
            okc = ok[:, None]
            vals = jnp.where(okc, vals, fv)
            ids_c = jnp.where(okc, ids_c, fi)
    return vals, ids_c


@partial(jax.jit, static_argnames=("rows",))
def _tile_operands(x, probes, offsets, padded_sizes, r0, rows: int):
    """The query block and probe windows of the ``rows``-row tile of a
    chunk starting at row ``r0`` (traced: one program per tile shape)."""
    xs = jax.lax.dynamic_slice_in_dim(x, r0, rows)
    pr = jax.lax.dynamic_slice_in_dim(probes, r0, rows)
    st, ps = _probe_windows(offsets, padded_sizes, pr)
    return xs, st, ps


@jax.jit
def _merge_rows(vals, ids, ok, fv, fi, r0):
    """Rows ``r0 …`` of (vals, ids) whose certificate failed (``ok``
    false) take the rerun's (fv, fi); certified rows keep their own."""
    n = fv.shape[0]
    okc = jax.lax.dynamic_slice_in_dim(ok, r0, n)[:, None]
    v = jnp.where(okc, jax.lax.dynamic_slice_in_dim(vals, r0, n), fv)
    i = jnp.where(okc, jax.lax.dynamic_slice_in_dim(ids, r0, n), fi)
    return (jax.lax.dynamic_update_slice_in_dim(vals, v, r0, 0),
            jax.lax.dynamic_update_slice_in_dim(ids, i, r0, 0))


def _rerun_failed_tiles(index: IvfFlatIndex, x, probes, ok, ok_h, vals,
                        ids_c, k: int, P: int, W: int):
    """Rerun query-major only the query-major tiles
    (:func:`_query_major_rows`) of a list-major chunk that hold a row
    whose certificate failed (``ok_h`` false, on the host), and merge
    the failed rows back — the tile shapes, and their gather memory,
    are the query-major schedule's own."""
    nq = x.shape[0]
    tile = _query_major_rows(P, W, x.shape[1])
    for r0 in range(0, nq, tile):
        rows = min(tile, nq - r0)
        if ok_h[r0:r0 + rows].all():
            continue
        xs, st, ps = _tile_operands(x, probes, index.offsets,
                                    index.padded_sizes, r0, rows=rows)
        fv, fi = _query_major_chunk(index, xs, st, ps, k, P, W,
                                    nested=True)
        vals, ids_c = _merge_rows(vals, ids_c, ok, fv, fi, r0)
    return vals, ids_c


def _search_list_major(res, index: IvfFlatIndex, x, probes,
                       probes_host, k: int, P: int, W: int, chunk: int):
    """The list-major driver: per chunk of ``chunk`` rows
    (:func:`_list_major_chunk`), invert the probe table into the list
    schedule, run the stream-once kernel, and rerun the query-major
    tiles that hold a certificate-failing row through the query-major
    scan — the returned ids are bit-identical to the query-major oracle
    either way. A search the chunk covers passes its operands whole."""
    from raft_tpu.ops.fine_scan_pallas import pad_window

    Wk = pad_window(W)
    host = _list_host(index)
    quant = index.db_dtype == "int8"
    nq = x.shape[0]

    def run_chunk(s0: int, s1: int):
        with span("ann.fine_scan_plan"):
            sched = build_list_schedule(index, probes_host[s0:s1])
            full, scale, n_cells = _kernel_schedule(index, sched)
            if s0 == 0:
                emit_marker("ivf_fine_scan_schedule", schedule="list",
                            lists_probed=sched.n_lists_probed,
                            q_max=sched.q_max, cells=n_cells,
                            stream_rows=sched.stream_rows,
                            chunk_rows=s1 - s0,
                            db_dtype=index.db_dtype)
            sched_d = jnp.asarray(full)
            scale_d = jnp.asarray(scale) if quant else None
        with span("ann.fine_scan"):
            if s1 - s0 == nq:
                xs, pr = x, probes
            else:
                xs, pr = x[s0:s1], probes[s0:s1]
            if quant:
                vals, ids_c, ok, margin = _fine_scan_list_q8(
                    xs, sched_d, scale_d, n_cells, pr, index.slab_q,
                    index.slab, index.ids, index.yy_slab,
                    host["yy_lmax"], host["eq_list"], index.offsets,
                    index.padded_sizes, k=k, P=P, W=W, Wk=Wk)
            else:
                vals, ids_c, ok, margin = _fine_scan_list(
                    xs, sched_d, n_cells, pr, index.slab, index.ids,
                    index.yy_slab, index.offsets, index.padded_sizes,
                    host["yy_lmax"], k=k, P=P, W=W, Wk=Wk)
        explain.note_margin("ann.search_ivf_flat", margin)
        with span("ann.certificate_sync"):
            ok_h = np.asarray(ok)
            n_fail = int(ok_h.size - np.count_nonzero(ok_h))
            # same host sync the q8 gather path already pays — the
            # list-major slice of the certificate/fixup evidence plane
            record_certificate("ann.search_ivf_flat",
                               n_queries=int(ok_h.size),
                               n_fail=n_fail, pool_width=256,
                               fixup_rows=n_fail or None,
                               rerun=bool(n_fail),
                               db_dtype=index.db_dtype,
                               fine_scan="list")
        if n_fail:
            # pool-completeness certificate failed: the true top-k
            # (or one of its ties) may hide outside the 256-slot pool
            # — rerun those rows' tiles query-major, keep certified rows
            with span("ann.fine_scan_rerun"):
                emit_marker("ivf_list_fallback", n_fail=n_fail,
                            nq=int(ok_h.size))
                explain.note(rerun="list_query_major",
                             rerun_rows=n_fail)
                vals, ids_c = _rerun_failed_tiles(
                    index, xs, pr, ok, ok_h, vals, ids_c, k, P, W)
        return vals, ids_c

    if nq <= chunk:
        return run_chunk(0, nq)
    outs = [run_chunk(s, min(s + chunk, nq))
            for s in range(0, nq, chunk)]
    return (jnp.concatenate([o[0] for o in outs]),
            jnp.concatenate([o[1] for o in outs]))


@instrument("ann.search_ivf_flat")
def search_ivf_flat(res, index, queries, k: int,
                    n_probes: Optional[int] = None,
                    merge: str = "auto",
                    fine_scan: Optional[str] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Approximate top-k against an IVF-Flat index.

    (ref: ivf_flat::search — coarse probe, gather the probed lists,
    list-local select, merge.) Returns (d2 [nq, k] ascending, global
    ids [nq, k]); entries beyond the probed candidates carry
    (+inf, −1) — recall vs the exact oracle is what the
    ``sift1m-ivf`` benchmark cell (``bench_suite/``) reports.

    ``index`` is an :class:`IvfFlatIndex` or a :class:`ShardedIvfIndex`
    (:func:`shard_ivf_lists` — whole lists over the mesh, per-shard
    local top-k + the PR-4 rank-ordered merge picked by ``merge``).

    ``fine_scan`` picks the fine-scan schedule (:data:`FINE_SCANS`;
    ``None`` reads ``RAFT_TPU_IVF_FINE_SCAN``, default ``auto``):
    ``query`` gathers each query's probe windows independently,
    ``list`` streams each probed list ONCE per query chunk for all the
    queries probing it (the ``ops.fine_scan_pallas`` kernels — f32 ids
    certified bit-identical to the query-major scan; int8 id sets
    identical, ties canonicalized to f32 position order), ``auto`` runs
    the :func:`resolve_fine_scan` cost-model crossover on the index's
    actual probed-list histogram. A list-major dispatch that raises a
    :class:`~raft_tpu.core.error.DeviceError` (an injected fault or a
    classified device failure) degrades back to query-major with a
    logged degradation (fault site ``fine_scan_list``); any other
    error, such as a kernel the compiler refuses, propagates. The
    sharded path keeps the query-major shard-local scan.

    ``n_probes ≥ n_lists`` (or ``k`` beyond the probed capacity)
    degrades to EXACT search with a logged reason — the certified
    fused pipeline over the ragged slab; the returned id set then
    matches the brute-force oracle exactly (the degenerate-exact
    invariant the tests pin)."""
    fault_point("ivf_search")
    res = ensure_resources(res)
    sharded = isinstance(index, ShardedIvfIndex)
    base = index.base if sharded else index
    x = jnp.asarray(queries, jnp.float32)
    expects(x.ndim == 2 and x.shape[1] == base.d_orig,
            "search_ivf_flat: query width %s != index %d",
            x.shape[1:], base.d_orig)
    expects(k >= 1, "search_ivf_flat: k must be >= 1")
    expects(k <= base.n_rows,
            "search_ivf_flat: k=%d > index size %d", k, base.n_rows)
    nq = x.shape[0]
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    L = base.n_lists
    if n_probes is None:
        # fleet-wide recall knob: RAFT_TPU_ANN_NPROBES retunes every
        # default-probes caller (serving planes included) without a
        # rebuild — read per call
        P = _env_int("RAFT_TPU_ANN_NPROBES", base.n_probes_default)
    else:
        P = int(n_probes)
    expects(P >= 1, "search_ivf_flat: n_probes must be >= 1, got %d", P)
    W = index.probe_window
    reason = None
    if P >= L:
        reason = f"n_probes={P} >= n_lists={L}"
    elif k > P * W:
        reason = (f"k={k} exceeds the probed candidate capacity "
                  f"{P}x{W}={P * W}")
    if reason is not None:
        from raft_tpu.core.logger import log_warn

        log_warn("search_ivf_flat: %s — degrading to exact search "
                 "over the full index for this call", reason)
        emit_marker("ivf_exact_degrade", reason=reason, k=k,
                    n_probes=P, n_lists=L)
        explain.note(plane="ivf_flat", exact_degrade=reason,
                     n_probes=P, n_lists=L, k=k)
        return _exact_search(res, base, x, k)

    with span("ann.coarse_probe"):
        probes = _coarse_probe(res, base.centroids, x, P)   # [nq, P]

    # the probe table comes to the host once, for the schedule
    # (resolve_fine_scan's crossover, build_list_schedule) and the
    # probed-rows count; an explicitly query-major or sharded call
    # keeps it on the device
    req = fine_scan if fine_scan is not None \
        else env.get("RAFT_TPU_IVF_FINE_SCAN")
    probes_host = probed_rows = None
    if req != "query" and not sharded:
        with span("ann.probe_fetch"):
            probes_host = np.asarray(probes)
        probed_rows = int(base._np_sizes[probes_host].sum())
        res.metrics.counter(
            PROBED_ROWS, help="Database rows named by the IVF fine "
                              "scan's probe tables").inc(probed_rows)

    if explain.active() is not None:
        # explain capture: probed list ids (first query's probe set —
        # the record is per-request-batch) + the probed-size histogram
        # and pool width; the host transfer only happens under capture
        pr_np = (probes_host if probes_host is not None
                 else np.asarray(probes))
        sz = np.asarray(base.sizes)[pr_np]
        explain.note(plane="ivf_flat", n_probes=P, n_lists=L, k=k,
                     db_dtype=base.db_dtype,
                     probed_lists=pr_np[0].tolist(),
                     probed_rows=int(sz.sum()),
                     probed_size_hist={
                         "min": int(sz.min()), "p50": float(
                             np.percentile(sz, 50)),
                         "max": int(sz.max())},
                     pool_width=(min(k + _IVF_RESCORE_PAD,
                                     P * index.probe_window)
                                 if base.db_dtype == "int8" else k))

    if get_flight_recorder().enabled:
        # the probed fraction rides the marker only where the host
        # already holds the probe table: no sync of its own
        frac = ({} if probed_rows is None else
                {"probed_frac": round(
                    probed_rows / max(1, nq * base.n_rows), 6)})
        emit_marker("ivf_search", nq=nq, k=k, n_probes=P, n_lists=L,
                    sharded=bool(sharded), **frac)

    if sharded:
        return _search_sharded(res, index, x, probes, k, P, W, merge)

    # fine-scan schedule: env/arg request resolved against the
    # list-major envelope + the cost-model crossover on the ACTUAL
    # probe table (resolve_fine_scan). A list-major failure — real or
    # injected at the fine_scan_list site — degrades back to the
    # query-major scan for this call, with identical ids. The
    # list-major chunk follows its own kernel's VMEM envelope; the
    # query-major one, the gather tile.
    with span("ann.fine_scan_plan"):
        chunk = _list_major_chunk(index, nq)
        schedule = resolve_fine_scan(index, nq, k, P, W, req,
                                     probes_np=probes_host, chunk=chunk)
        if schedule != "list":
            starts, psizes = _probe_windows(index.offsets,
                                            index.padded_sizes, probes)
    explain.note(fine_scan=schedule)
    if schedule == "list":
        try:
            fault_point("fine_scan_list")
            return _search_list_major(res, index, x, probes,
                                      probes_host, k, P, W, chunk)
        except DeviceError as e:
            # injected faults and classified device failures only: a
            # kernel that fails to compile or lower propagates
            from raft_tpu.core.logger import log_warn

            record_degradation("fine_scan_list", "query")
            emit_marker("fine_scan_degrade",
                        reason=f"{type(e).__name__}: {e}"[:160])
            explain.note(fine_scan_degrade=f"{type(e).__name__}"[:64])
            log_warn("list-major fine scan failed (%s: %s) — "
                     "degrading to the query-major scan for this "
                     "call", type(e).__name__, e)
        starts, psizes = _probe_windows(index.offsets,
                                        index.padded_sizes, probes)

    chunk = _query_major_rows(P, W, x.shape[1])
    if nq <= chunk:
        return _query_major_chunk(index, x, starts, psizes, k, P, W)
    outs = [_query_major_chunk(index, x[s:s + chunk],
                               starts[s:s + chunk],
                               psizes[s:s + chunk], k, P, W)
            for s in range(0, nq, chunk)]
    return (jnp.concatenate([o[0] for o in outs]),
            jnp.concatenate([o[1] for o in outs]))


# ----------------------------------------------------------- sharded
class ShardedIvfIndex:
    """Whole inverted lists distributed over a mesh axis (the
    ``shard="lists"`` layout): shard ``r`` owns the contiguous list
    block [r·Ll, (r+1)·Ll) laid out in its own local slab; list→shard
    routing is pure arithmetic. Build with :func:`shard_ivf_lists`;
    query through :func:`search_ivf_flat` (type-dispatched)."""

    def __init__(self, base: IvfFlatIndex, mesh, axis: str,
                 slab_s, ids_s, yy_s, starts_g, psizes_g,
                 lists_per: int, rows_per: int, slab_qs=None,
                 scale_s=None, yyq_s=None, eq_s=None):
        self.base = base
        self.mesh, self.axis = mesh, axis
        self.slab_s = slab_s        # [p·rows_per, d] sharded P(axis)
        self.ids_s = ids_s          # [p·rows_per] global ids, -1 pads
        self.yy_s = yy_s            # [p·rows_per] row norms
        self.starts_g = starts_g    # [Lg] LOCAL start row per list
        self.psizes_g = psizes_g    # [Lg] padded sizes (0 = empty)
        self.lists_per = lists_per
        self.rows_per = rows_per
        # int8 sidecar, sharded in the same block layout as the f32
        # slab (PR-9 parity gap closed: the shard-local fine scan
        # streams the quantized rows, certifies, and exact-rescoring
        # rides the f32 slab that is already resident per shard)
        self.slab_qs = slab_qs      # [p·rows_per, d] int8 or None
        self.scale_s = scale_s      # [p·rows_per] f32 per-row scale
        self.yyq_s = yyq_s          # [p·rows_per] ‖ŷ‖²
        self.eq_s = eq_s            # [p·rows_per] per-row Eq bound

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def probe_window(self) -> int:
        return self.base.probe_window


def shard_ivf_lists(index: IvfFlatIndex, mesh, axis: str = "x"
                    ) -> ShardedIvfIndex:
    """Lay an :class:`IvfFlatIndex` out list-sharded over
    ``mesh[axis]``: lists pad to ``p`` equal blocks (virtual empty
    lists), every shard's local slab pads to the max shard row count
    (shard_map needs equal shards), and the shards land via ONE
    sharded ``device_put`` — the slab never materializes replicated on
    any device. Global ids ride inside each local slab, so the merged
    results need no offset arithmetic."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    expects(axis in mesh.axis_names,
            "shard_ivf_lists: axis %r not in mesh axes %s", axis,
            tuple(mesh.axis_names))
    p = int(mesh.shape[axis])
    L = index.n_lists
    Lg = -(-L // p) * p
    Ll = Lg // p
    offsets, padded = index._np_offsets, index._np_padded
    slab = np.asarray(index.slab)
    ids = np.asarray(index.ids)
    # yy is GATHERED from the base index, not recomputed — the sharded
    # and unsharded fine scans must score bitwise-identical d2 per
    # candidate, and a host-side re-summation could round differently
    yy = np.asarray(index.yy_slab)
    d = slab.shape[1]
    # per-shard row counts (sum of its lists' padded sizes)
    shard_rows = [int(padded[r * Ll:min((r + 1) * Ll, L)].sum())
                  for r in range(p)]
    S = max(max(shard_rows), index.row_quantum)
    slab_g = np.zeros((p * S, d), np.float32)
    ids_g = np.full(p * S, -1, np.int32)
    yy_g = np.zeros(p * S, np.float32)
    starts_g = np.zeros(Lg, np.int32)
    psizes_g = np.zeros(Lg, np.int32)
    psizes_g[:L] = padded
    for r in range(p):
        cursor = 0
        for gl in range(r * Ll, min((r + 1) * Ll, L)):
            w = int(padded[gl])
            starts_g[gl] = cursor
            if w:
                src = int(offsets[gl])
                dst = r * S + cursor
                slab_g[dst:dst + w] = slab[src:src + w]
                ids_g[dst:dst + w] = ids[src:src + w]
                yy_g[dst:dst + w] = yy[src:src + w]
            cursor += w
    q8_kw = {}
    if index.db_dtype == "int8":
        # the PR-9 sidecar, laid out in the SAME per-shard block
        # geometry (gathered from the base arrays, not recomputed —
        # the sharded and unsharded quantized scans must score the
        # same ŷ bit-for-bit)
        slab_q = np.asarray(index.slab_q)
        scale = np.asarray(index.row_scale)
        yyq = np.asarray(index.yy_q)
        eqr = np.asarray(index.eq_rows)
        slab_qg = np.zeros((p * S, d), np.int8)
        scale_g = np.ones(p * S, np.float32)
        yyq_g = np.zeros(p * S, np.float32)
        eq_g = np.zeros(p * S, np.float32)
        for r in range(p):
            cursor = 0
            for gl in range(r * Ll, min((r + 1) * Ll, L)):
                w = int(padded[gl])
                if w:
                    src = int(offsets[gl])
                    dst = r * S + cursor
                    slab_qg[dst:dst + w] = slab_q[src:src + w]
                    scale_g[dst:dst + w] = scale[src:src + w]
                    yyq_g[dst:dst + w] = yyq[src:src + w]
                    eq_g[dst:dst + w] = eqr[src:src + w]
                cursor += w
        q8_kw = dict(slab_qs=slab_qg, scale_s=scale_g, yyq_s=yyq_g,
                     eq_s=eq_g)
    sh = NamedSharding(mesh, P(axis))
    return ShardedIvfIndex(
        index, mesh, axis,
        slab_s=jax.device_put(slab_g, sh),
        ids_s=jax.device_put(ids_g, sh),
        yy_s=jax.device_put(yy_g, sh),
        starts_g=jnp.asarray(starts_g),
        psizes_g=jnp.asarray(psizes_g),
        lists_per=Ll, rows_per=S,
        **{key: jax.device_put(val, sh)
           for key, val in q8_kw.items()})


def _search_sharded(res, index: ShardedIvfIndex, x, probes, k: int,
                    P: int, W: int, merge: str):
    """List-sharded fine scan + rank-ordered merge. Every shard scans
    the probed lists IT owns (unowned probes masked), selects its local
    top-k with global ids, and the per-shard candidates merge with the
    PR-4 machinery — deterministic rank-major pools, so the result is
    replicated bit-for-bit and matches the unsharded scan's id set."""
    from jax.sharding import NamedSharding, PartitionSpec as Pspec

    from raft_tpu.comms import MeshComms
    from raft_tpu.distance.knn_sharded import (_merge_allgather,
                                               _merge_tournament,
                                               resolve_merge_strategy)
    from raft_tpu.parallel import replicated

    mesh, axis = index.mesh, index.axis
    p = index.n_shards
    expects(merge in ("auto", "allgather", "tournament"),
            "search_ivf_flat: merge must be 'auto', 'allgather' or "
            "'tournament', got %r", merge)
    nq = x.shape[0]
    merge_eff = resolve_merge_strategy(merge, p, nq, k)
    if merge_eff == "host":     # not a rung here — auto never picks it
        merge_eff = "allgather"
    # fault sites fire in the WRAPPER (per call), like knn_sharded's
    # resilience driver — a trace-time site inside shard_map would fire
    # once per compile and lie for every cached dispatch after
    if merge_eff == "tournament":
        fault_point("merge_permute")
    else:
        fault_point("merge_allgather")
    d = x.shape[1]
    chunk = _query_major_rows(P, W, d)
    if nq > chunk:
        outs = [_search_sharded(res, index, x[s:s + chunk],
                                probes[s:s + chunk], k, P, W, merge)
                for s in range(0, nq, chunk)]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))

    Ll, S = index.lists_per, index.rows_per
    quant = index.base.db_dtype == "int8" and index.slab_qs is not None
    repl = replicated(mesh)
    common = (jax.device_put(x, repl), jax.device_put(probes, repl),
              jax.device_put(index.starts_g, repl),
              jax.device_put(index.psizes_g, repl))

    def _f32_fn():
        key = (mesh, axis, k, P, W, S, Ll, merge_eff, d, nq, "f32")
        fn = _SHARDED_IVF_CACHE.get(key)
        if fn is None:
            comms = MeshComms(axis, size=p)
            merge_fn = {"allgather": _merge_allgather,
                        "tournament": _merge_tournament}[merge_eff]

            def shard_fn(slab_l, ids_l, yy_l, xq, pr, starts_g, psz_g):
                r = jax.lax.axis_index(axis).astype(jnp.int32)
                owned = (pr >= r * Ll) & (pr < (r + 1) * Ll)
                starts = jnp.take(starts_g, pr)
                psz = jnp.where(owned, jnp.take(psz_g, pr), 0)
                vals, gids = _fine_scan(xq, slab_l, ids_l, yy_l,
                                        starts, psz, k=k, P=P, W=W)
                return merge_fn(comms, p, k, vals, gids)

            fn = jax.jit(jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(Pspec(axis), Pspec(axis), Pspec(axis),
                          Pspec(), Pspec(), Pspec(), Pspec()),
                out_specs=(Pspec(), Pspec()), check_vma=False))
            _SHARDED_IVF_CACHE[key] = fn
        return fn

    if not quant:
        return _f32_fn()(index.slab_s, index.ids_s, index.yy_s,
                         *common)

    # int8 shard-local fine scan (the PR-9 sharded parity gap): each
    # shard streams ITS quantized rows (~¼ the probed bytes), prunes
    # to the certified pool, exact-rescoring from its resident f32
    # slab — certificates come out per shard ([p, nq] over the axis),
    # and any query a shard could not certify reruns the whole chunk
    # through the f32 program, so merged ids never degrade.
    C = min(k + _IVF_RESCORE_PAD, P * W)
    key = (mesh, axis, k, P, W, S, Ll, merge_eff, d, nq, "int8")
    fn = _SHARDED_IVF_CACHE.get(key)
    if fn is None:
        comms = MeshComms(axis, size=p)
        merge_fn = {"allgather": _merge_allgather,
                    "tournament": _merge_tournament}[merge_eff]

        def shard_fn_q8(slab_l, slabq_l, scale_l, yyq_l, eq_l, ids_l,
                        xq, pr, starts_g, psz_g):
            r = jax.lax.axis_index(axis).astype(jnp.int32)
            owned = (pr >= r * Ll) & (pr < (r + 1) * Ll)
            starts = jnp.take(starts_g, pr)
            psz = jnp.where(owned, jnp.take(psz_g, pr), 0)
            # margin (4th output) is DCE'd — per-shard margins would
            # need their own out_spec the explain plane doesn't ask for
            vals, gids, ok, _ = _fine_scan_q8(
                xq, slab_l, slabq_l, scale_l, ids_l, yyq_l, starts,
                psz, k=k, P=P, W=W, C=C, eq_rows=eq_l)
            mv, mi = merge_fn(comms, p, k, vals, gids)
            return mv, mi, ok[None, :]

        fn = jax.jit(jax.shard_map(
            shard_fn_q8, mesh=mesh,
            in_specs=(Pspec(axis),) * 6
            + (Pspec(), Pspec(), Pspec(), Pspec()),
            out_specs=(Pspec(), Pspec(), Pspec(axis)),
            check_vma=False))
        _SHARDED_IVF_CACHE[key] = fn

    vals, gids, ok_p = fn(index.slab_s, index.slab_qs, index.scale_s,
                          index.yyq_s, index.eq_s, index.ids_s,
                          *common)
    ok = np.asarray(ok_p).all(axis=0)                       # [nq]
    n_fail = int((~ok).sum())
    record_certificate("ann.search_ivf_flat", n_queries=nq,
                       n_fail=n_fail, pool_width=C,
                       fixup_rows=n_fail or None, rerun=bool(n_fail),
                       db_dtype="int8", sharded=True)
    if n_fail:
        emit_marker("ivf_q8_fallback", n_fail=n_fail, nq=nq,
                    sharded=True)
        fv, fi = _f32_fn()(index.slab_s, index.ids_s, index.yy_s,
                           *common)
        okd = jnp.asarray(ok)[:, None]
        vals = jnp.where(okd, vals, fv)
        gids = jnp.where(okd, gids, fi)
    return vals, gids
