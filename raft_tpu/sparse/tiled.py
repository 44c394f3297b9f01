"""Tiled-ELL sparse format — the TPU-native SpMV preprocessing.

(ref: the cusparse SpMV/SpMM surface
cpp/include/raft/sparse/detail/cusparse_wrappers.h:1 and the Lanczos SpMV
dispatch cpp/include/raft/sparse/solver/detail/lanczos.cuh:263-271. The
reference leans on cusparse's CSR kernels; TPU has no hardware
gather/scatter worth leaning on, so the format is re-thought: nonzeros are
re-laid-out ONCE, host-side, into fixed-size chunks whose column (resp.
row) footprint is a single tile — turning SpMV's irregular access into
per-chunk lane-select folds that Mosaic lowers to plain VPU compare/
select/reduce. See raft_tpu.ops.spmv_pallas for the kernels.)

Layout produced by :func:`tile_csr`:

- nonzeros grouped by (column tile, row tile) bucket, column-tile-major —
  within a bucket they keep stable INPUT order (a single-key stable sort
  on the bucket id; they are NOT sorted by row within a tile, which no
  consumer requires — the fold is order-insensitive within a bucket) —
  padded per column tile to a multiple of ``E`` (pad entries carry value
  0 → contribute nothing); stored as ``[n_chunks, E]`` arrays of values,
  LOCAL column ids (col % C) and global row ids. ``chunk_col_tile
  [n_chunks]`` maps each chunk to its x-tile (the Pallas scalar-prefetch
  block index).
- the same nonzeros re-grouped by row-tile bucket (stable ⇒
  column-tile-minor within a row tile, input order within a bucket), with
  ``perm [n_chunks·E]`` being the gather permutation from col-grouped
  contribution order to row-grouped order, ``row_local`` the in-tile row
  ids, and ``chunk_row_tile`` the per-chunk output tile index.

Conversion is one-time host work (like the reference's native cusparse
conversion routines): the default path is the C++ layout pass in
cpp/hostops.cpp (bucket-by-tile + per-tile sorts), with a bit-identical
numpy fallback when no toolchain is available; the arrays then live on
device.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core.sparse_types import COOMatrix, CSRMatrix
from raft_tpu.observability import instrument
from raft_tpu.resilience import fault_point


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TiledELL:
    """Device-resident tiled layout for one sparse matrix (see module doc).
    Registered as a pytree (array fields are leaves, geometry is static)
    so it can flow through jitted solver loops like the other sparse
    types."""

    shape: Tuple[int, int]
    C: int                      # column tile width (x tile length)
    R: int                      # row tile width (y tile length)
    E: int                      # chunk length (nonzeros per grid step)
    # --- gather phase (col-sorted) ---
    vals: jax.Array             # [n_chunks, E] f32
    col_local: jax.Array        # [n_chunks, E] int32, in [0, C)
    chunk_col_tile: jax.Array   # [n_chunks] int32
    # --- scatter phase (row-sorted) ---
    # perm bridges the two orderings. Two granularities:
    #   perm_rows [m_chunks·E/8] int32 — indices of 8-slot ROWS of the
    #     flat col-order (the default numpy layout buckets elements by
    #     (row tile, col tile) padded to 8-multiples so the bridge is a
    #     ROW gather: XLA's scalar gather measured 0.5 GB/s — 15.4 of
    #     the 17.1 ms SpMV at 2M nnz — while row gathers run ~50 GB/s);
    #     value n_chunks·E/8 points at an appended zero row (pads).
    #   perm [m_chunks, E] int32 — legacy scalar indices (the native C++
    #     layout pass); slower bridge, kept for fast host conversion.
    # Exactly one of the two is used by ops.spmv_pallas.spmv_tiled.
    perm: Optional[jax.Array]
    perm_rows: Optional[jax.Array]
    row_local: jax.Array        # [m_chunks, E] int32 in [0, R), pad = R
    chunk_row_tile: jax.Array   # [m_chunks] int32
    visited_row_tiles: jax.Array  # [n_row_tiles] bool — tiles with any nnz
    n_col_tiles: int
    n_row_tiles: int

    @property
    def n_chunks(self) -> int:
        return self.vals.shape[0]

    @property
    def m_chunks(self) -> int:
        return self.row_local.shape[0]

    _LEAVES = ("vals", "col_local", "chunk_col_tile", "perm", "perm_rows",
               "row_local", "chunk_row_tile", "visited_row_tiles")

    def tree_flatten(self):
        leaves = tuple(getattr(self, f) for f in self._LEAVES)
        aux = (self.shape, self.C, self.R, self.E,
               self.n_col_tiles, self.n_row_tiles)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        shape, C, R, E, nct, nrt = aux
        return cls(shape, C, R, E, *leaves, n_col_tiles=nct,
                   n_row_tiles=nrt)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TiledPairs:
    """Device-resident (row tile × col tile)-bucketed layout of a sparsity
    STRUCTURE — the operand of the blocked SDDMM kernel
    (raft_tpu.ops.sddmm_pallas). Each chunk's E entries share one
    [R, C] block of the output, so the kernel can form that block's dense
    A·Bᵀ tile ON the MXU and fold the entries out of VMEM. ``pos`` maps
    each ORIGINAL structure entry to its chunk-flat slot, restoring the
    caller's nnz order after the kernel. ``rows``/``cols`` keep the
    original structure so the result can be returned as a sparse matrix."""

    shape: Tuple[int, int]
    R: int
    C: int
    E: int
    row_local: jax.Array        # [m_chunks, E] int32 in [0, R), pad = R
    col_local: jax.Array        # [m_chunks, E] int32 in [0, C), pad = 0
    chunk_row_tile: jax.Array   # [m_chunks] int32
    chunk_col_tile: jax.Array   # [m_chunks] int32
    pos: jax.Array              # [nnz] int32 into chunk-flat order
    rows: jax.Array             # [nnz] int32 — original structure
    cols: jax.Array             # [nnz] int32
    n_row_tiles: int
    n_col_tiles: int

    @property
    def nnz(self) -> int:
        return self.rows.shape[0]

    @property
    def m_chunks(self) -> int:
        return self.row_local.shape[0]

    _LEAVES = ("row_local", "col_local", "chunk_row_tile", "chunk_col_tile",
               "pos", "rows", "cols")

    def tree_flatten(self):
        leaves = tuple(getattr(self, f) for f in self._LEAVES)
        aux = (self.shape, self.R, self.C, self.E,
               self.n_row_tiles, self.n_col_tiles)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        shape, R, C, E, nrt, nct = aux
        return cls(shape, R, C, E, *leaves, n_row_tiles=nrt,
                   n_col_tiles=nct)


def _checked_coo_parts(A, C: int, R: int, E: int, name: str):
    """Shared validation + extraction for the tiled conversions: kernel
    alignment check, CSR/COO (rows, cols, vals, shape) extraction, and
    id-range validation."""
    if E % 512 or C % 128 or R % 8:
        raise ValueError(f"{name}: need E % 512 == 0, C % 128 == 0, "
                         f"R % 8 == 0 (kernel fold/tile alignment)")
    if isinstance(A, CSRMatrix):
        rows = np.asarray(A.row_ids())
        cols = np.asarray(A.indices)
        vals = np.asarray(A.values, np.float32)
        shape = A.shape
    elif isinstance(A, COOMatrix):
        rows = np.asarray(A.rows)
        cols = np.asarray(A.cols)
        vals = np.asarray(A.values, np.float32)
        shape = A.shape
    else:
        raise TypeError(f"{name}: expected sparse matrix, got {type(A)}")
    if len(rows) and (
            int(rows.min()) < 0 or int(cols.min()) < 0
            or int(rows.max()) >= shape[0] or int(cols.max()) >= shape[1]):
        raise ValueError(
            f"{name}: row/col ids out of range for shape {shape}")
    return rows, cols, vals, shape


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TiledPairsSpmv:
    """Pair-tiled SpMV operand: a :class:`TiledPairs` structure layout
    plus the matrix VALUES in chunk-flat order and the row-tile visited
    mask. Consumed by raft_tpu.ops.spmv_pallas.spmv_pair_tiled — ONE
    fused gather·multiply·scatter kernel with no permutation pass (the
    TiledELL pipeline's XLA scalar permutation measured 15.4 of its
    17.1 ms at 2M nnz on v5e). Build with :func:`tile_csr_pairs`."""

    pairs: TiledPairs
    vals: jax.Array             # [m_chunks, 1, E] f32, pad entries 0
    visited: jax.Array          # [n_row_tiles] bool — tiles the grid writes

    @property
    def shape(self):
        return self.pairs.shape

    def tree_flatten(self):
        return (self.pairs, self.vals, self.visited), ()

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@instrument("sparse.tile_csr_pairs")
def tile_csr_pairs(A, R: int = 256, C: int = 512, E: int = 2048,
                   impl: str = "auto") -> TiledPairsSpmv:
    """One-time conversion of a sparse MATRIX (values included) to the
    pair-tiled SpMV operand (see :class:`TiledPairsSpmv`)."""
    pairs = tile_pairs(A, R=R, C=C, E=E, impl=impl)
    # values come straight from the matrix in the SAME entry order
    # tile_pairs' pos maps (no second O(nnz) extraction pass)
    vals = np.asarray(A.values, np.float32)
    flat = jnp.zeros(pairs.m_chunks * pairs.E, jnp.float32)
    if len(vals):
        flat = flat.at[pairs.pos].set(jnp.asarray(vals))
    visited = jnp.zeros(pairs.n_row_tiles, bool).at[
        pairs.chunk_row_tile].set(True)
    blowup = pairs.m_chunks * pairs.E / max(1, pairs.nnz)
    if pairs.nnz > 0 and blowup > 4:
        from raft_tpu.core.logger import log_warn

        log_warn(
            "tile_csr_pairs: %.0fx pad blowup (%d slots for %d nnz) — "
            "the pair layout only wins for block-clustered structures; "
            "use prepare_spmv(layout='ell') for scattered matrices",
            blowup, pairs.m_chunks * pairs.E, pairs.nnz)
    return TiledPairsSpmv(pairs=pairs,
                          vals=flat.reshape(pairs.m_chunks, 1, pairs.E),
                          visited=visited)


def tile_pairs(structure, R: int = 256, C: int = 512,
               E: int = 2048, impl: str = "auto") -> TiledPairs:
    """Bucket a sparsity structure by (row tile, col tile) — one-time host
    conversion for the blocked SDDMM kernel. (ref: the preprocessing role
    of cusparse's SDDMM descriptors, cusparse_wrappers.h sddmm.)

    ``impl``: "auto" uses the native C++ layout pass when available,
    "numpy" forces the fallback; both produce BIT-IDENTICAL layouts
    (tested).

    Plans for large structures persist ACROSS PROCESSES through
    :mod:`raft_tpu.sparse.plan_cache` (the 39.8 s pairs prepare at a
    2M-nnz scale becomes a ~ms ``np.load`` on the second
    process), keyed purely by the sparsity structure — the pair layout
    carries no values."""
    if impl not in ("auto", "numpy"):
        raise ValueError(f"tile_pairs: impl must be 'auto' or 'numpy', "
                         f"got {impl!r}")
    rows, cols, _, shape = _checked_coo_parts(structure, C, R, E,
                                              "tile_pairs")
    from raft_tpu.sparse import plan_cache

    fp = None
    if plan_cache.enabled_for(len(rows)):
        fp = plan_cache.structure_fingerprint("pairs", shape, (R, C, E),
                                              rows, cols)
        plan = plan_cache.load_plan(fp)
        if plan is not None:
            m_chunks = plan["row_local"].shape[0] // E
            return TiledPairs(
                shape=shape, R=R, C=C, E=E,
                row_local=jnp.asarray(plan["row_local"].reshape(
                    m_chunks, E)),
                col_local=jnp.asarray(plan["col_local"].reshape(
                    m_chunks, E)),
                chunk_row_tile=jnp.asarray(plan["chunk_row_tile"]),
                chunk_col_tile=jnp.asarray(plan["chunk_col_tile"]),
                pos=jnp.asarray(plan["pos"]),
                rows=jnp.asarray(rows, jnp.int32),
                cols=jnp.asarray(cols, jnp.int32),
                n_row_tiles=max(1, -(-shape[0] // R)),
                n_col_tiles=max(1, -(-shape[1] // C)))
    out = _tile_pairs_impl(rows, cols, shape, R, C, E, impl)
    if fp is not None:
        plan_cache.save_plan(fp, {
            "row_local": np.asarray(out.row_local).reshape(-1),
            "col_local": np.asarray(out.col_local).reshape(-1),
            "chunk_row_tile": np.asarray(out.chunk_row_tile),
            "chunk_col_tile": np.asarray(out.chunk_col_tile),
            "pos": np.asarray(out.pos),
        })
    return out


def _tile_pairs_impl(rows, cols, shape, R: int, C: int, E: int,
                     impl: str) -> TiledPairs:
    n_row_tiles = max(1, -(-shape[0] // R))
    n_col_tiles = max(1, -(-shape[1] // C))

    if impl == "auto" and len(rows):
        from raft_tpu import native

        out = native.pair_layout(rows, cols, shape[0], shape[1], R, C, E)
        if out is not None:
            rloc, cloc, crt, cct, pos = out
            m_chunks = len(rloc) // E
            return TiledPairs(
                shape=shape, R=R, C=C, E=E,
                row_local=jnp.asarray(rloc.reshape(m_chunks, E)),
                col_local=jnp.asarray(cloc.reshape(m_chunks, E)),
                chunk_row_tile=jnp.asarray(crt),
                chunk_col_tile=jnp.asarray(cct),
                pos=jnp.asarray(pos),
                rows=jnp.asarray(rows, jnp.int32),
                cols=jnp.asarray(cols, jnp.int32),
                n_row_tiles=n_row_tiles, n_col_tiles=n_col_tiles)

    key = (rows // R).astype(np.int64) * n_col_tiles + cols // C
    order = np.lexsort((cols, rows, key))
    pad_idx, chunk_key = _pad_groups(order, key, E)
    gr, gc = rows, cols                          # gather targets
    if len(pad_idx) == 0:                        # empty structure
        pad_idx = np.full(E, -1, np.int64)
        chunk_key = np.zeros(1, np.int32)
        gr = np.zeros(1, np.int64)               # dummy targets for the
        gc = np.zeros(1, np.int64)               # all-pad chunk
    safe = np.maximum(pad_idx, 0)
    rloc = np.where(pad_idx >= 0, gr[safe] % R, R).astype(np.int32)
    cloc = np.where(pad_idx >= 0, gc[safe] % C, 0).astype(np.int32)
    pos = np.empty(len(rows), np.int32)
    real = pad_idx >= 0
    pos[pad_idx[real]] = np.flatnonzero(real).astype(np.int32)
    m_chunks = len(pad_idx) // E
    return TiledPairs(
        shape=shape, R=R, C=C, E=E,
        row_local=jnp.asarray(rloc.reshape(m_chunks, E)),
        col_local=jnp.asarray(cloc.reshape(m_chunks, E)),
        chunk_row_tile=jnp.asarray(
            (chunk_key // n_col_tiles).astype(np.int32)),
        chunk_col_tile=jnp.asarray(
            (chunk_key % n_col_tiles).astype(np.int32)),
        pos=jnp.asarray(pos),
        rows=jnp.asarray(rows, jnp.int32),
        cols=jnp.asarray(cols, jnp.int32),
        n_row_tiles=n_row_tiles, n_col_tiles=n_col_tiles,
    )


def _pad_groups(order, keys, E):
    """Given sort order and group key per nnz (keys[order] nondecreasing),
    pad each group's entries to a multiple of E. Returns (padded index
    array with -1 for pads, group id per chunk). Vectorized — conversion
    must stay O(nnz) numpy time, not Python-loop time."""
    n = len(order)
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int32)
    sorted_keys = np.asarray(keys)[order]
    uniq, starts = np.unique(sorted_keys, return_index=True)
    counts = np.diff(np.append(starts, n))
    padded_counts = -(-counts // E) * E
    out_starts = np.concatenate([[0], np.cumsum(padded_counts)[:-1]])
    total = int(padded_counts.sum())
    idx = np.full(total, -1, np.int64)
    # destination of each real entry: its group's padded start + rank
    ranks = np.arange(n) - np.repeat(starts, counts)
    idx[np.repeat(out_starts, counts) + ranks] = order
    chunk_tile = np.repeat(uniq, padded_counts // E).astype(np.int32)
    return idx, chunk_tile


@functools.partial(
    jax.jit, static_argnames=("C", "R", "E", "n_ct", "n_rt", "NG", "NM"))
def _tile_csr_device_core(rows, cols, vals, C: int, R: int, E: int,
                          n_ct: int, n_rt: int, NG: int, NM: int):
    """Device-side v2 tiled-ELL layout, mirroring the numpy pass above
    step for step (same stable sort keys ⇒ identical layout). Output
    arrays are sized to the STATIC worst-case bounds NG/NM (jit needs
    static shapes; padding inflates only by ≤7 slots per occupied
    bucket + one E-chunk per tile group); the wrapper fetches the two
    true sizes (the only host sync) and slices. Exists to keep the
    conversion off the device↔host link.

    Ids are range-validated ON DEVICE, with the verdict fetched in the
    same host sync as the output sizes — the host paths' ValueError
    contract is preserved at no extra round trip."""
    nnz = rows.shape[0]
    ct = cols // C
    rt = rows // R
    bucket = ct * n_rt + rt                          # ct-major key
    # single-key stable sort (vs the old 3-key lexsort = 3 sort passes):
    # conversion was config 4's dominant cost — 0.89 s warm vs ~0.6 s
    # solve at 2M nnz (round-3 profile); within-bucket order is the
    # input order in all three layout passes
    order_g = jnp.argsort(bucket, stable=True)
    bsorted = bucket[order_g]
    first = jnp.concatenate([jnp.ones((1,), bool),
                             bsorted[1:] != bsorted[:-1]])
    bidx = jnp.cumsum(first.astype(jnp.int32)) - 1   # dense bucket index
    nb = bidx[-1] + 1                                # traced bucket count
    barange = jnp.arange(nnz, dtype=jnp.int32)
    bvalid = barange < nb
    counts = jax.ops.segment_sum(jnp.ones((nnz,), jnp.int32), bidx,
                                 num_segments=nnz)
    bstart = jax.ops.segment_min(barange, bidx, num_segments=nnz)
    padded = (counts + 7) // 8 * 8
    b_off8 = jnp.cumsum(padded) - padded             # exclusive cumsum
    within = barange - bstart[bidx]
    g_slot8 = b_off8[bidx] + within                  # per element

    ub = jax.ops.segment_max(bsorted, bidx, num_segments=nnz)
    ub_ct = jnp.where(bvalid, ub // n_rt, n_ct - 1)
    # per-col-tile 8-padded sizes → E-padded group offsets
    ct_sizes8 = jax.ops.segment_sum(jnp.where(bvalid, padded, 0), ub_ct,
                                    num_segments=n_ct)
    ct_start8 = jnp.cumsum(ct_sizes8) - ct_sizes8
    grp_padded = -(-ct_sizes8 // E) * E
    grp_foff = jnp.cumsum(grp_padded) - grp_padded
    n_gather = jnp.sum(grp_padded)
    elem_final = grp_foff[ct[order_g]] + (g_slot8 - ct_start8[ct[order_g]])

    pv = jnp.zeros((NG,), vals.dtype).at[elem_final].set(vals[order_g])
    pc = jnp.zeros((NG,), jnp.int32).at[elem_final].set(
        (cols[order_g] % C).astype(jnp.int32))
    # chunk j's col tile: the group that owns slot j·E
    ch_arange = jnp.arange(NG // E, dtype=jnp.int32)
    chunk_col_tile = jnp.searchsorted(
        jnp.cumsum(grp_padded), ch_arange * E, side="right"
    ).astype(jnp.int32)

    # per-bucket start row in the FINAL gather stream
    bucket_final0 = grp_foff[ub_ct] + (b_off8 - ct_start8[ub_ct])
    bucket_row0 = bucket_final0 // 8

    # scatter stream: buckets rt-major (stable ⇒ ct-minor within rt)
    key2 = jnp.where(bvalid, (ub % n_rt) * n_ct + ub // n_rt,
                     jnp.iinfo(jnp.int32).max)
    order_b = jnp.argsort(key2, stable=True)         # invalid sort last
    sc_sizes = jnp.where(bvalid, padded, 0)[order_b]
    sc_rows = sc_sizes // 8
    sc_rt = jnp.where(bvalid[order_b], ub[order_b] % n_rt, n_rt - 1)
    rt_slots = jax.ops.segment_sum(sc_sizes, sc_rt, num_segments=n_rt)
    rt_padded = -(-rt_slots // E) * E
    rt_foff = jnp.cumsum(rt_padded) - rt_padded
    m_slots = jnp.sum(rt_padded)
    chunk_row_tile = jnp.searchsorted(
        jnp.cumsum(rt_padded), jnp.arange(NM // E, dtype=jnp.int32) * E,
        side="right").astype(jnp.int32)

    # per-bucket (scatter order) destination slot
    csc = jnp.cumsum(sc_sizes) - sc_sizes            # excl. cumsum
    rt_bstart_slots = jax.ops.segment_min(
        jnp.where(bvalid[order_b], csc, jnp.iinfo(jnp.int32).max),
        sc_rt, num_segments=n_rt)
    dst_slot0 = rt_foff[sc_rt] + (csc - rt_bstart_slots[sc_rt])
    dst_row0 = dst_slot0 // 8
    src_row0 = bucket_row0[order_b]

    # perm_rows: virtual scatter 8-row v belongs to scatter-bucket
    # searchsorted(cumsum(sc_rows), v, right); rows beyond the data or
    # in pad gaps point at the appended zero row
    zero_row = n_gather // 8
    csr_rows = jnp.cumsum(sc_rows)
    v8 = jnp.arange(NM // 8, dtype=jnp.int32)
    owner = jnp.searchsorted(csr_rows, v8, side="right").astype(jnp.int32)
    owner_c = jnp.minimum(owner, nnz - 1)
    within_rows = v8 - (csr_rows[owner_c] - sc_rows[owner_c])
    dstr = dst_row0[owner_c] + within_rows
    srcr = src_row0[owner_c] + within_rows
    have = (owner < nnz) & bvalid[order_b][owner_c]
    perm_rows = jnp.full((NM // 8,), zero_row, jnp.int32)
    perm_rows = perm_rows.at[jnp.where(have, dstr, NM // 8)].set(
        jnp.where(have, srcr, zero_row).astype(jnp.int32), mode="drop")

    # row_local: element destinations (bucket dst + within-bucket slot)
    inv_sc = jnp.zeros((nnz,), jnp.int32).at[order_b].set(
        jnp.arange(nnz, dtype=jnp.int32))
    elem_dst = dst_slot0[inv_sc[bidx]] + within
    rloc = jnp.full((NM,), R, jnp.int32).at[elem_dst].set(
        (rows[order_g] % R).astype(jnp.int32))

    visited = jnp.zeros((n_rt,), bool).at[
        jnp.where(bvalid, ub % n_rt, n_rt)].set(True, mode="drop")
    return (pv, pc, chunk_col_tile, perm_rows, rloc, chunk_row_tile,
            visited, n_gather, m_slots)


@jax.jit
def _ids_in_range(rows, cols, n_rows, n_cols):
    return (jnp.all((rows >= 0) & (rows < n_rows))
            & jnp.all((cols >= 0) & (cols < n_cols)))


def tile_csr_device(A, C: int = 512, R: int = 256,
                    E: int = 2048) -> TiledELL:
    """Device-side tiled-ELL conversion (see _tile_csr_device_core):
    the big arrays never cross the host boundary — only two size
    scalars sync. Produces the SAME layout as the numpy/native host
    passes (identical stable sort keys; asserted in tests)."""
    if isinstance(A, CSRMatrix):
        rows = A.row_ids()
        cols, vals, shape = A.indices, A.values, A.shape
    elif isinstance(A, COOMatrix):
        rows, cols, vals, shape = A.rows, A.cols, A.values, A.shape
    else:
        raise TypeError(f"tile_csr_device: expected sparse matrix, "
                        f"got {type(A)}")
    if E % 512 or C % 128 or R % 8:
        raise ValueError("tile_csr_device: need E % 512 == 0, "
                         "C % 128 == 0, R % 8 == 0")
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    vals = jnp.asarray(vals, jnp.float32)
    nnz = int(rows.shape[0])
    n_ct = max(1, -(-shape[1] // C))
    n_rt = max(1, -(-shape[0] // R))
    if nnz == 0 or n_ct * n_rt >= 2 ** 31:
        return tile_csr(A, C=C, R=R, E=E, impl="numpy")
    # static worst-case stream bounds: ≤7 pad slots per occupied bucket
    # plus up to one E-chunk of pad per OCCUPIED tile group — empty
    # tiles contribute zero pad in the core (their segment sums round
    # up to 0), so the bound uses min(tiles, nnz), not the raw tile
    # count: a 10M×10M shape with 1k nnz must not allocate one E-chunk
    # for each of its ~20k col tiles
    nb_max = min(nnz, n_ct * n_rt)
    ns8 = nnz + 7 * nb_max
    occ_ct = min(n_ct, nnz)
    occ_rt = min(n_rt, nnz)
    NG = (-(-(ns8 + (E - 8) * occ_ct) // E)) * E
    NM = (-(-(ns8 + (E - 8) * occ_rt) // E)) * E
    out = _tile_csr_device_core(rows, cols, vals, C, R, E, n_ct, n_rt,
                                NG, NM)
    (pv, pc, cct, perm_rows, rloc, crt, visited, n_gather, m_slots) = out
    ok = _ids_in_range(rows, cols, shape[0], shape[1])
    # the ONLY host sync: two size scalars + the validation verdict
    ok, n_gather, m_slots = (bool(ok), int(n_gather), int(m_slots))
    if not ok:
        raise ValueError(
            f"tile_csr_device: row/col ids out of range for shape "
            f"{shape}")
    n_chunks = n_gather // E
    m_chunks = m_slots // E
    return TiledELL(
        shape=shape, C=C, R=R, E=E,
        vals=pv[:n_gather].reshape(n_chunks, E),
        col_local=pc[:n_gather].reshape(n_chunks, E),
        chunk_col_tile=cct[:n_chunks],
        perm=None,
        perm_rows=perm_rows[:m_slots // 8],
        row_local=rloc[:m_slots].reshape(m_chunks, E),
        chunk_row_tile=crt[:m_chunks],
        visited_row_tiles=visited,
        n_col_tiles=n_ct, n_row_tiles=n_rt)


@instrument("sparse.tile_csr")
def tile_csr(A, C: int = 512, R: int = 256, E: int = 2048,
             impl: str = "auto") -> TiledELL:
    """Convert a CSR/COO matrix to the tiled-ELL layout (one-time, host).

    ``impl``: "auto" builds the v2 8-aligned-bucket layout (ROW-gather
    bridge — runtime-optimal: the legacy scalar-permutation bridge
    measured 15.4 of the 17.1 ms SpMV at 2M nnz on v5e): ON DEVICE
    when an accelerator backend is active (tile_csr_device — no
    device↔host transfer of the matrix), else via the native C++ pass,
    else numpy —
    all three BIT-IDENTICAL (tested); "device"/"numpy" force those;
    "native" forces the LEGACY scalar-perm C++ layout (kept for
    comparison/compat). All layouts produce identical SpMV results
    (tested)."""
    fault_point("tile_csr")
    if impl not in ("auto", "device", "numpy", "native"):
        raise ValueError(f"tile_csr: impl must be 'auto', 'device', "
                         f"'numpy' or 'native', got {impl!r}")
    if impl == "device" or (
            impl == "auto" and jax.default_backend() != "cpu"):
        # the device conversion exists because HOST↔device transfers
        # dominate it — a disk cache would reintroduce the host round
        # trip, so only the host layout passes persist
        return tile_csr_device(A, C=C, R=R, E=E)
    coo_rows, coo_cols, vals, shape = _checked_coo_parts(A, C, R, E,
                                                         "tile_csr")
    # persistent plan cache: keyed by the sparsity STRUCTURE; the
    # tiled-ELL arrays bake values in, so the stored plan carries a
    # values digest and a different-values lookup is an honest miss
    from raft_tpu.sparse import plan_cache

    fp = vd = None
    if plan_cache.enabled_for(len(coo_rows)):
        kind = "ell-legacy" if impl == "native" else "ell-v2"
        fp = plan_cache.structure_fingerprint(kind, shape, (C, R, E),
                                              coo_rows, coo_cols)
        vd = plan_cache.values_digest(vals)
        plan = plan_cache.load_plan(fp, vals_digest=vd)
        if plan is not None:
            return _tiled_ell_from_plan(plan, shape, C, R, E)
    out = _tile_csr_host(coo_rows, coo_cols, vals, shape, C, R, E, impl)
    if fp is not None:
        plan_cache.save_plan(fp, _tiled_ell_plan_arrays(out),
                             vals_digest=vd)
    return out


def _tiled_ell_plan_arrays(t: TiledELL) -> dict:
    arrays = {
        "vals": np.asarray(t.vals).reshape(-1),
        "col_local": np.asarray(t.col_local).reshape(-1),
        "chunk_col_tile": np.asarray(t.chunk_col_tile),
        "row_local": np.asarray(t.row_local).reshape(-1),
        "chunk_row_tile": np.asarray(t.chunk_row_tile),
        "visited_row_tiles": np.asarray(t.visited_row_tiles),
    }
    if t.perm is not None:
        arrays["perm"] = np.asarray(t.perm).reshape(-1)
    if t.perm_rows is not None:
        arrays["perm_rows"] = np.asarray(t.perm_rows)
    return arrays


def _tiled_ell_from_plan(plan: dict, shape, C: int, R: int,
                         E: int) -> TiledELL:
    n_chunks = plan["vals"].size // E
    m_chunks = plan["row_local"].size // E
    return TiledELL(
        shape=shape, C=C, R=R, E=E,
        vals=jnp.asarray(plan["vals"].reshape(n_chunks, E)),
        col_local=jnp.asarray(plan["col_local"].reshape(n_chunks, E)),
        chunk_col_tile=jnp.asarray(plan["chunk_col_tile"]),
        perm=(jnp.asarray(plan["perm"].reshape(m_chunks, E))
              if "perm" in plan else None),
        perm_rows=(jnp.asarray(plan["perm_rows"])
                   if "perm_rows" in plan else None),
        row_local=jnp.asarray(plan["row_local"].reshape(m_chunks, E)),
        chunk_row_tile=jnp.asarray(plan["chunk_row_tile"]),
        visited_row_tiles=jnp.asarray(plan["visited_row_tiles"]),
        n_col_tiles=max(1, -(-shape[1] // C)),
        n_row_tiles=max(1, -(-shape[0] // R)))


def _tile_csr_host(coo_rows, coo_cols, vals, shape, C: int, R: int,
                   E: int, impl: str) -> TiledELL:
    """The host layout passes of :func:`tile_csr` (native v2 / native
    legacy / numpy v2), split out so the plan cache wraps all three
    return points at once."""
    if impl == "auto" and len(coo_rows):
        from raft_tpu import native

        out = native.tiled_layout_v2(coo_rows, coo_cols, vals, shape[0],
                                     shape[1], C, R, E)
        if out is not None:
            pv, pc, cct, perm_rows, rloc, crt, visited = out
            return TiledELL(
                shape=shape, C=C, R=R, E=E,
                vals=jnp.asarray(pv.reshape(-1, E)),
                col_local=jnp.asarray(pc.reshape(-1, E)),
                chunk_col_tile=jnp.asarray(cct),
                perm=None,
                perm_rows=jnp.asarray(perm_rows),
                row_local=jnp.asarray(rloc.reshape(-1, E)),
                chunk_row_tile=jnp.asarray(crt),
                visited_row_tiles=jnp.asarray(visited),
                n_col_tiles=max(1, -(-shape[1] // C)),
                n_row_tiles=max(1, -(-shape[0] // R)))

    if impl == "native" and len(coo_rows):
        from raft_tpu import native

        out = native.tiled_layout(coo_rows, coo_cols, vals, shape[0],
                                  shape[1], C, R, E)
        if out is not None:
            pv, pc, cct, perm, rloc, crt, visited = out
            return TiledELL(
                shape=shape, C=C, R=R, E=E,
                vals=jnp.asarray(pv.reshape(-1, E)),
                col_local=jnp.asarray(pc.reshape(-1, E)),
                chunk_col_tile=jnp.asarray(cct),
                perm=jnp.asarray(perm.reshape(-1, E)),
                perm_rows=None,
                row_local=jnp.asarray(rloc.reshape(-1, E)),
                chunk_row_tile=jnp.asarray(crt),
                visited_row_tiles=jnp.asarray(visited),
                n_col_tiles=max(1, -(-shape[1] // C)),
                n_row_tiles=max(1, -(-shape[0] // R)))

    # --- v2 numpy layout: (col tile, row tile)-bucketed, 8-ALIGNED ---
    # Elements are grouped into (col tile, row tile) buckets padded to
    # 8-slot multiples; the gather stream concatenates buckets ct-major,
    # the scatter stream rt-major — the SAME 8-slot rows in both — so
    # the gather→scatter bridge is a ROW gather (perm_rows). XLA's
    # scalar gather measured 0.5 GB/s (15.4 of 17.1 ms at 2M nnz);
    # 8-wide row gathers run ~50 GB/s. Scatter order adds the ct key
    # (legal: scatter-chunk internal order is irrelevant to the one-hot
    # accumulation).
    n_col_tiles = max(1, -(-shape[1] // C))
    n_row_tiles = max(1, -(-shape[0] // R))
    if len(coo_rows) == 0:                       # empty matrix
        return TiledELL(
            shape=shape, C=C, R=R, E=E,
            vals=jnp.zeros((1, E), jnp.float32),
            col_local=jnp.zeros((1, E), jnp.int32),
            chunk_col_tile=jnp.zeros(1, jnp.int32),
            perm=None,
            perm_rows=jnp.full(E // 8, E // 8, jnp.int32),  # all zero-row
            row_local=jnp.full((1, E), R, jnp.int32),
            chunk_row_tile=jnp.zeros(1, jnp.int32),
            visited_row_tiles=jnp.zeros(n_row_tiles, bool),
            n_col_tiles=n_col_tiles, n_row_tiles=n_row_tiles)

    ct = (coo_cols // C).astype(np.int64)
    rt = (coo_rows // R).astype(np.int64)
    bucket = ct * n_row_tiles + rt               # ct-major bucket key
    # stable single-key sort: within-bucket order = input order (chunk-
    # internal order is irrelevant to both SpMV phases) — one sort pass
    # instead of lexsort's three, same key in all three layout passes
    order_g = np.argsort(bucket, kind="stable")
    bsorted = bucket[order_g]
    ub, bstart = np.unique(bsorted, return_index=True)
    counts = np.diff(np.append(bstart, len(bsorted)))
    padded = ((counts + 7) // 8) * 8             # 8-aligned bucket sizes
    b_off8 = np.concatenate(([0], np.cumsum(padded)))[:-1]
    total8 = int(padded.sum())
    # element slot in the 8-padded (pre-chunk-pad) gather stream
    within = np.arange(len(bsorted)) - np.repeat(bstart, counts)
    g_slot8 = np.repeat(b_off8, counts) + within

    # chunk-pad the gather stream per col tile to E boundaries (E is a
    # multiple of 8, so 8-row alignment survives)
    slot_ct = np.repeat(ub // n_row_tiles, padded)
    grp_ids, grp_start = np.unique(slot_ct, return_index=True)
    grp_sizes = np.diff(np.append(grp_start, total8))
    grp_padded = ((grp_sizes + E - 1) // E) * E
    grp_foff = np.concatenate(([0], np.cumsum(grp_padded)))[:-1]
    grp_of_slot8 = np.repeat(np.arange(len(grp_ids)), grp_sizes)
    final_of_slot8 = (grp_foff[grp_of_slot8]
                      + (np.arange(total8) - grp_start[grp_of_slot8]))
    n_gather_slots = int(grp_padded.sum())
    n_chunks = n_gather_slots // E

    elem_final = final_of_slot8[g_slot8]
    pv = np.zeros(n_gather_slots, np.float32)
    pv[elem_final] = vals[order_g]
    pc = np.zeros(n_gather_slots, np.int32)
    pc[elem_final] = (coo_cols[order_g] % C).astype(np.int32)
    chunk_col_tile = np.repeat(grp_ids, grp_padded // E).astype(np.int32)

    # per-bucket start ROW in the final gather stream
    bucket_final_start = final_of_slot8[b_off8]
    bucket_row0 = bucket_final_start // 8        # 8-aligned by design

    # scatter stream: buckets reordered rt-major, then rt groups padded
    # to E with whole zero rows
    key2 = (ub % n_row_tiles) * n_col_tiles + (ub // n_row_tiles)
    order_b = np.argsort(key2, kind="stable")
    sc_sizes = padded[order_b]                   # per-bucket slot counts
    sc_rows = sc_sizes // 8
    sc_rt = (ub[order_b] % n_row_tiles).astype(np.int64)
    # per-rt-group sizes in the bucket-concat scatter stream
    rt_ids, rt_start = np.unique(sc_rt, return_index=True)
    # rt_start indexes buckets; convert to slot counts per rt group
    slots_per_rt = np.add.reduceat(sc_sizes, rt_start)
    rt_padded = ((slots_per_rt + E - 1) // E) * E
    m_chunks = int(rt_padded.sum()) // E
    chunk_row_tile = np.repeat(rt_ids, rt_padded // E).astype(np.int32)

    zero_row = n_gather_slots // 8               # appended zero 8-row
    perm_rows = np.full(m_chunks * E // 8, zero_row, np.int32)
    rloc = np.full(m_chunks * E, R, np.int32)
    # destination offsets: per rt group start + running position of each
    # bucket inside its group
    rt_foff = np.concatenate(([0], np.cumsum(rt_padded)))[:-1]
    rt_of_bucket = np.repeat(np.arange(len(rt_ids)),
                             np.diff(np.append(rt_start, len(order_b))))
    within_rt = (np.concatenate(([0], np.cumsum(sc_sizes)))[:-1]
                 - np.repeat(np.concatenate(
                     ([0], np.cumsum(sc_sizes)))[:-1][rt_start],
                     np.diff(np.append(rt_start, len(order_b)))))
    dst_slot0 = rt_foff[rt_of_bucket] + within_rt    # per bucket
    # fill perm_rows: bucket b (scatter order) occupies rows
    # dst_slot0//8 .. +sc_rows, sourcing gather rows bucket_row0[order_b]
    dst_row0 = dst_slot0 // 8
    src_row0 = bucket_row0[order_b]
    row_fill = np.repeat(dst_row0, sc_rows) + (
        np.arange(int(sc_rows.sum()))
        - np.repeat(np.concatenate(([0], np.cumsum(sc_rows)))[:-1],
                    sc_rows))
    src_fill = np.repeat(src_row0, sc_rows) + (
        np.arange(int(sc_rows.sum()))
        - np.repeat(np.concatenate(([0], np.cumsum(sc_rows)))[:-1],
                    sc_rows))
    perm_rows[row_fill] = src_fill.astype(np.int32)
    # row_local: real elements land at (bucket dst + within-bucket slot)
    inv_bucket_pos = np.empty(len(ub), np.int64)
    inv_bucket_pos[order_b] = np.arange(len(order_b))
    elem_dst = (dst_slot0[inv_bucket_pos][np.searchsorted(ub, bsorted)]
                + within)
    rloc[elem_dst] = (coo_rows[order_g] % R).astype(np.int32)

    visited = np.zeros(n_row_tiles, bool)
    visited[np.asarray(chunk_row_tile, np.int64)] = True
    return TiledELL(
        shape=shape, C=C, R=R, E=E,
        vals=jnp.asarray(pv.reshape(n_chunks, E)),
        col_local=jnp.asarray(pc.reshape(n_chunks, E)),
        chunk_col_tile=jnp.asarray(chunk_col_tile),
        perm=None,
        perm_rows=jnp.asarray(perm_rows),
        row_local=jnp.asarray(rloc.reshape(m_chunks, E)),
        chunk_row_tile=jnp.asarray(chunk_row_tile),
        visited_row_tiles=jnp.asarray(visited),
        n_col_tiles=n_col_tiles, n_row_tiles=n_row_tiles,
    )


