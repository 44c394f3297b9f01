"""IndexLayout — the one slab description every index plane shares.

Until this PR each plane carried its own private spelling of "a slab of
rows, some of them real": ``prepare_knn_index`` kept a trailing-pad
prefix count, the IVF builder kept (offsets, sizes, padded_sizes, ids)
around its padded ragged slab, and the quantized planes bolted their
scale/Eq sidecars onto whichever of the two they rode. The mutable
subsystem (:mod:`raft_tpu.mutable.index`) needs all three shapes to be
the SAME thing — a base snapshot, a delta tail and a tombstoned slab
are all just layouts with different ``rows_valid`` masks — so the
struct is extracted here and the build/search machinery re-expressed
as pure ops over it:

- :class:`IndexLayout` — slab (f32 rows, pads zero), ids (slab row →
  global id, −1 pad), ``rows_valid`` (the live mask — pads AND
  tombstones), optional IVF geometry (offsets/sizes/padded_sizes) and
  optional per-row int8 sidecar (codes, scale, Eq).
- :func:`dense_layout` — a flat matrix as a layout (the brute plane /
  the mutable delta slab).
- :func:`ragged_layout_from_lists` — the padded-ragged-slab
  construction extracted from ``ann.build_ivf_flat`` (host-side
  bucketing by label, each list padded to the row quantum).
- :func:`quantize_layout` — the per-list int8 sidecar (PR-9
  ``quantize_rows_q8`` / Eq machinery) over a ragged layout.
- :func:`fused_ops_for_layout` / :func:`run_fused_ops` — prepared
  certified-fused operands over ANY layout (the ragged ``rows_valid``
  sentinel path) and the chunked core driver over them. ``ann.
  _slab_fused_geometry`` (the IVF degenerate-exact plane) and the
  mutable base/delta planes all call these two — one spelling of the
  geometry, no drifting copies.

Everything here is functional: a layout never mutates. The mutable
index expresses a tombstone as a NEW ``rows_valid`` (plus the matching
never-wins sentinel scatter on the prepared carrier) — the slab is
untouched, which is what makes deletes O(changed) instead of O(index).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import numpy as np

#: slab row quantum every layout pads its row groups to — the fused
#: pipeline's 8-row sublane multiple (mirrors ann.DEFAULT_ROW_QUANTUM)
ROW_QUANTUM = 8


class IndexLayout:
    """One slab of index rows + the masks/sidecars every plane needs.

    ``slab`` [R, d] f32 (pad rows zero), ``ids`` [R] int32 (slab row →
    global row id, −1 on pads), ``rows_valid`` [R] bool (live rows —
    False on pads AND tombstones). ``offsets``/``sizes``/
    ``padded_sizes`` carry the IVF inverted-list geometry when the
    layout is ragged-by-list (None for flat layouts). The int8 sidecar
    (``slab_q``/``row_scale``/``eq_rows``) is per-ROW — the IVF shape;
    the brute plane's per-certificate-group quantization re-derives
    from the f32 slab in ``_prepare_ops_q8``."""

    __slots__ = ("slab", "ids", "rows_valid", "offsets", "sizes",
                 "padded_sizes", "row_quantum", "d_orig", "n_rows",
                 "db_dtype", "slab_q", "row_scale", "eq_rows",
                 "pq_codes", "pq_yy", "pq_eq_rows", "pq_rot",
                 "pq_meta")

    def __init__(self, slab, ids, rows_valid, n_rows: int, d_orig: int,
                 offsets=None, sizes=None, padded_sizes=None,
                 row_quantum: int = ROW_QUANTUM, db_dtype: str = "f32",
                 slab_q=None, row_scale=None, eq_rows=None,
                 pq_codes=None, pq_yy=None, pq_eq_rows=None,
                 pq_rot=None, pq_meta=None):
        self.slab = slab
        self.ids = ids
        self.rows_valid = rows_valid
        self.n_rows = int(n_rows)
        self.d_orig = int(d_orig)
        self.offsets = offsets
        self.sizes = sizes
        self.padded_sizes = padded_sizes
        self.row_quantum = int(row_quantum)
        self.db_dtype = db_dtype
        self.slab_q = slab_q
        self.row_scale = row_scale
        self.eq_rows = eq_rows
        # product-quantized sidecar (ann.ivf_pq — the compressed tier):
        # the packed codes slab + reconstructed norms ride the SAME
        # padded-ragged row geometry as the f32 slab, so tombstones /
        # compaction treat them as one more per-row column
        self.pq_codes = pq_codes
        self.pq_yy = pq_yy
        self.pq_eq_rows = pq_eq_rows
        # the OPQ learned rotation ([d, d] orthogonal, None for plain
        # PQ) — per-INDEX, not per-row: compaction and tombstone folds
        # carry it through unchanged
        self.pq_rot = pq_rot
        self.pq_meta = pq_meta

    @property
    def slab_rows(self) -> int:
        return int(self.slab.shape[0])

    @property
    def ragged(self) -> bool:
        return self.offsets is not None

    def __repr__(self):
        return (f"IndexLayout(rows={self.n_rows}, slab={self.slab_rows}, "
                f"d={self.d_orig}, ragged={self.ragged}, "
                f"db_dtype={self.db_dtype})")


def dense_layout(y, ids=None, rows_valid=None,
                 row_quantum: int = ROW_QUANTUM) -> IndexLayout:
    """A flat [m, d] matrix as an :class:`IndexLayout`: rows pad up to
    the row quantum (pad rows zero, ids −1, invalid). ``ids`` defaults
    to ``arange(m)``; ``rows_valid`` (over the INPUT rows) marks
    tombstoned/garbage rows out — the mutable delta slab passes its
    occupancy mask here. Host-side (numpy in, numpy out) — the device
    transfer happens once, in :func:`fused_ops_for_layout`."""
    y = np.asarray(y, np.float32)
    m, d = y.shape
    R = max(row_quantum, -(-m // row_quantum) * row_quantum)
    slab = np.zeros((R, d), np.float32)
    slab[:m] = y
    out_ids = np.full(R, -1, np.int32)
    out_ids[:m] = (np.arange(m, dtype=np.int32) if ids is None
                   else np.asarray(ids, np.int32))
    valid = np.zeros(R, np.bool_)
    valid[:m] = True if rows_valid is None else \
        np.asarray(rows_valid, np.bool_).reshape(-1)
    valid &= out_ids >= 0
    return IndexLayout(slab, out_ids, valid, n_rows=m, d_orig=d,
                       row_quantum=row_quantum)


def ragged_layout_from_lists(y, labels, n_lists: int,
                             row_quantum: int = ROW_QUANTUM
                             ) -> IndexLayout:
    """The padded ragged slab: rows of ``y`` bucketed by ``labels``
    into ``n_lists`` inverted lists, each list padded up to the row
    quantum, lists back-to-back in one [R, d] slab with offsets/sizes/
    global ids alongside — the host-side layout block extracted from
    ``ann.build_ivf_flat`` (memory is Σ padded, not L·max; empty lists
    cost 0 rows). Host-side numpy throughout."""
    y = np.asarray(y, np.float32)
    labels = np.asarray(labels)
    m, d = y.shape
    L = int(n_lists)
    sizes = np.bincount(labels, minlength=L).astype(np.int32)
    padded = ((sizes + row_quantum - 1) // row_quantum
              * row_quantum).astype(np.int32)
    padded[sizes == 0] = 0                     # empty lists cost nothing
    offsets = np.concatenate(
        [[0], np.cumsum(padded, dtype=np.int64)]).astype(np.int32)
    R = int(offsets[-1])
    slab = np.zeros((R, d), np.float32)
    ids = np.full(R, -1, np.int32)
    order = np.argsort(labels, kind="stable")
    sorted_labels = labels[order]
    # rank of each row within its list (order is label-sorted, so the
    # rank is position minus the first position of that label)
    first = np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)[:-1]])
    rank = np.arange(m) - first[sorted_labels]
    dest = offsets[sorted_labels] + rank
    slab[dest] = y[order]
    ids[dest] = order.astype(np.int32)
    return IndexLayout(slab, ids, ids >= 0, n_rows=m, d_orig=d,
                       offsets=offsets, sizes=sizes, padded_sizes=padded,
                       row_quantum=row_quantum)


def quantize_layout(layout: IndexLayout) -> IndexLayout:
    """Per-list symmetric int8 sidecar over a RAGGED layout (the PR-9
    machinery: ``quantize_rows_q8`` grouped by inverted list, per-row
    scale/Eq gathered alongside the codes — the cuVS int8 IVF-Flat
    shape). Returns a new layout; the f32 slab stays (it is the exact-
    rescore data plane)."""
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import q8_eq_bound, quantize_rows_q8

    if not layout.ragged:
        raise ValueError("quantize_layout: per-list quantization needs "
                         "a ragged (IVF) layout — the brute plane "
                         "quantizes per certificate group in "
                         "_prepare_ops_q8")
    L = int(layout.sizes.shape[0])
    gid = jnp.asarray(np.repeat(np.arange(L, dtype=np.int32),
                                np.asarray(layout.padded_sizes)))
    slab_j = jnp.asarray(layout.slab)
    valid = jnp.asarray(np.asarray(layout.ids) >= 0)
    slab_q, list_scale = quantize_rows_q8(slab_j, gid, L, valid=valid)
    eq_lists = q8_eq_bound(list_scale, layout.slab.shape[1])
    row_scale = jnp.take(list_scale, gid)
    return IndexLayout(layout.slab, layout.ids, layout.rows_valid,
                       n_rows=layout.n_rows, d_orig=layout.d_orig,
                       offsets=layout.offsets, sizes=layout.sizes,
                       padded_sizes=layout.padded_sizes,
                       row_quantum=layout.row_quantum, db_dtype="int8",
                       slab_q=slab_q, row_scale=row_scale,
                       eq_rows=jnp.take(eq_lists, gid))


class FusedOps(NamedTuple):
    """Prepared certified-fused operands over one layout: everything
    :func:`run_fused_ops` needs to drive ``_knn_fused_core`` with the
    ragged ``rows_valid`` sentinel path. ``ops`` is the positional
    operand tuple (f32: yp/y_hi/y_lo/yyh_k/yy_raw; int8:
    yp/y_q/scale_k/yyh_k/yy_raw/eq_groups); ``rv`` is the PREPARED
    (row-padded) live mask; ``ids`` maps slab positions back to the
    layout's global ids (−1 pads), padded to the prepared row count."""

    db_dtype: str
    ops: Tuple
    rv: object
    ids: object
    T: int
    Qb: int
    g: int
    pbits: int
    grid_order: str
    passes: int
    metric: str

    @property
    def slab_rows(self) -> int:
        """PREPARED (padded) row count."""
        return int(self.ops[0].shape[0])

    @property
    def yyh_index(self) -> int:
        """Position of the sentinel carrier in ``ops`` — the one
        operand a tombstone scatter replaces."""
        return 3 if self.db_dtype == "int8" else 3

    @property
    def pool_width(self) -> int:
        n_tiles = self.slab_rows // self.T
        return 2 * (-(-n_tiles // self.g)) * 128


def fused_geometry(slab_rows: int, d: int, passes: int = 3,
                   T: Optional[int] = None, Qb: Optional[int] = None,
                   g: Optional[int] = None
                   ) -> Tuple[int, int, int, int]:
    """(T, Qb, g, pbits) for a certified-fused program over a slab of
    ``slab_rows`` × ``d`` — the ONE spelling of the packed ragged
    geometry (tuned config → scoped-VMEM fit → auto pack width →
    packed-envelope clamp), shared by the IVF degenerate-exact plane
    and the mutable base/delta planes. The ragged ``rows_valid`` mask
    is packed-only, so ``g`` is clamped into the code space."""
    from raft_tpu.distance.knn_fused import (_LANES, _PACK_BITS,
                                             _PBITS_MAX, auto_pack_bits,
                                             fit_config, fused_config)

    cfg = fused_config(passes)
    T = cfg.T if T is None else T
    Qb = cfg.Qb if Qb is None else Qb
    T, Qb = fit_config(T, Qb, d, passes, g or cfg.g, "query")
    n_tiles_est = max(1, -(-slab_rows // T))
    if g is None:
        g = max(cfg.g,
                (1 << auto_pack_bits(n_tiles_est, T)) // (T // _LANES))
    n_ch = T // _LANES
    pbits = min(_PBITS_MAX, max(_PACK_BITS, int(math.ceil(math.log2(
        max(g * n_ch, 2))))))
    if g * n_ch > (1 << pbits):
        g = max(1, (1 << pbits) // n_ch)   # ragged mask is packed-only
    return T, Qb, g, pbits


def fused_ops_for_layout(layout: IndexLayout, passes: int = 3,
                         metric: str = "l2",
                         T: Optional[int] = None,
                         Qb: Optional[int] = None,
                         g: Optional[int] = None,
                         db_dtype: Optional[str] = None) -> FusedOps:
    """Prepare the certified-fused operands for ``layout`` — the pure
    build op every plane shares: d-pad the slab, resolve the packed
    geometry (:func:`fused_geometry`), run ``_prepare_ops`` (or the
    int8 ``_prepare_ops_q8``) with the layout's ``rows_valid`` as the
    ragged never-wins mask, and return the operand bundle + the padded
    id map. ``db_dtype`` "int8" streams the slab quantized per
    certificate group (database-major, mandatory exact f32 rescore —
    the PR-9 contract); default follows the layout (ragged int8
    sidecars still rescore from the f32 slab here)."""
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import (_LANES, _prepare_ops,
                                             _prepare_ops_q8)

    slab = jnp.asarray(layout.slab, jnp.float32)
    R, d = slab.shape
    T, Qb, g, pbits = fused_geometry(R, d, passes, T=T, Qb=Qb, g=g)
    dpad = (-d) % _LANES
    if dpad:
        slab = jnp.concatenate(
            [slab, jnp.zeros((R, dpad), jnp.float32)], axis=1)
    valid = jnp.asarray(np.asarray(layout.rows_valid), jnp.bool_)
    quant = (db_dtype or "f32") == "int8"
    grid_order = "db" if quant else "query"
    if quant:
        ops = _prepare_ops_q8(slab, T, g, metric, pbits=pbits,
                              grid_order=grid_order, rows_valid=valid)
    else:
        ops = _prepare_ops(slab, T, g, metric, pbits=pbits,
                           grid_order=grid_order, rows_valid=valid)
    M = ops[0].shape[0]
    ids = jnp.asarray(np.asarray(layout.ids), jnp.int32)
    rv = valid
    if M > R:
        pad = M - R
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, jnp.int32)])
        rv = jnp.concatenate([rv, jnp.zeros((pad,), jnp.bool_)])
    try:
        from raft_tpu.observability.timeline import emit_marker

        emit_marker("layout_fused_ops", slab_rows=int(M), d=int(d),
                    T=T, Qb=Qb, g=g, pbits=pbits,
                    db_dtype="int8" if quant else "f32",
                    ragged=layout.ragged)
    except Exception:
        pass
    return FusedOps(db_dtype="int8" if quant else "f32", ops=tuple(ops),
                    rv=rv, ids=ids, T=T, Qb=Qb, g=g, pbits=pbits,
                    grid_order=grid_order, passes=passes, metric=metric)


def run_fused_ops(fops: FusedOps, x, k: int, rows_valid=None,
                  yyh_k=None) -> Tuple:
    """Drive ``_knn_fused_core`` over prepared layout operands — the
    pure search op. Handles query d-padding, Qb row padding and the
    ``_Q_CHUNK`` workspace bound exactly like ``knn_fused``'s wrapper.

    ``rows_valid``/``yyh_k`` override the prepared mask/carrier: the
    mutable planes pass their tombstone-updated pair (same shapes →
    the jit cache serves every mutation generation from ONE compiled
    program). Returns ``(vals [nq, k], pos [nq, k] slab positions,
    n_fail device scalar)`` — callers map positions through
    ``fops.ids`` and report ``n_fail`` to the quality plane."""
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import _Q_CHUNK, _knn_fused_core

    x = jnp.asarray(x, jnp.float32)
    nq = x.shape[0]
    rv = fops.rv if rows_valid is None else rows_valid
    ops = list(fops.ops)
    if yyh_k is not None:
        ops[fops.yyh_index] = yyh_k
    if nq == 0:
        z = jnp.zeros((0, k), jnp.float32)
        return z, jnp.zeros((0, k), jnp.int32), jnp.int32(0)
    if nq > _Q_CHUNK:
        outs = [run_fused_ops(fops, x[s:s + _Q_CHUNK], k,
                              rows_valid=rows_valid, yyh_k=yyh_k)
                for s in range(0, nq, _Q_CHUNK)]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]),
                sum(o[2] for o in outs))
    M = fops.slab_rows
    if k > fops.pool_width:
        raise NotImplementedError(
            f"run_fused_ops: k={k} too large for the layout's candidate "
            f"pool {fops.pool_width} (shrink k or grow the slab)")
    dpad = ops[0].shape[1] - x.shape[1]
    if dpad:
        x = jnp.concatenate(
            [x, jnp.zeros((nq, dpad), jnp.float32)], axis=1)
    Qb_eff = min(fops.Qb, ((nq + 7) // 8) * 8)
    qpad = (-nq) % Qb_eff
    if qpad:
        x = jnp.concatenate(
            [x, jnp.zeros((qpad, x.shape[1]), jnp.float32)])
    common = dict(k=k, T=fops.T, Qb=Qb_eff, g=fops.g, passes=fops.passes,
                  metric=fops.metric, m=M, rescore=True,
                  pbits=fops.pbits, with_stats=True, rows_valid=rv,
                  grid_order=fops.grid_order)
    # margin and the select-overflow flag (4th and 5th with_stats
    # outputs) are discarded inside this jitted view — the mutable
    # plane's explain and fixup stories ride the base-search sites;
    # XLA DCEs the unused outputs
    if fops.db_dtype == "int8":
        yp, y_q, scale_k, yyh, yy_raw, eq = ops
        vals, pos, n_fail, _, _ = _knn_fused_core(
            x, yp, None, None, yyh, yy_raw, db_dtype="int8", y_q=y_q,
            y_scale_k=scale_k, eq_groups=eq, **common)
    else:
        yp, y_hi, y_lo, yyh, yy_raw = ops
        vals, pos, n_fail, _, _ = _knn_fused_core(
            x, yp, y_hi, y_lo, yyh, yy_raw, **common)
    vals, pos = vals[:nq], pos[:nq]
    # rows short of k come back (+inf, <raw column>) from the fixup's
    # unmasked top_k — an id consumers would happily map to a TOMBSTONED
    # row; normalize every non-finite slot to the −1 sentinel here
    pos = jnp.where(jnp.isfinite(vals), pos, -1)
    return vals, pos, n_fail
