"""Tiled-ELL SpMV kernels (Pallas/Mosaic) — the cusparse-SpMV role on TPU.

(ref: cpp/include/raft/sparse/detail/cusparse_wrappers.h:1 — the SpMV/SpMM
surface the reference gets from cusparse — and the Lanczos matvec dispatch
cpp/include/raft/sparse/solver/detail/lanczos.cuh:263-271.)

TPU-first re-design: GPUs do SpMV with hardware-threaded gather + atomic
scatter; TPUs have neither. Instead the matrix is re-laid-out ONCE
(raft_tpu.sparse.tiled.tile_csr) into fixed-size nonzero chunks whose
column (resp. row) footprint is a single tile, and both irregular sides
become per-chunk LANE-SELECT FOLDS — broadcast-compare + select + reduce,
all plain VPU ops every Mosaic version lowers:

- gather kernel: chunk c holds E nonzeros of one column tile; the x-tile
  for that chunk is chosen by a scalar-prefetched block index (data-
  dependent BlockSpec index_map — the Pallas idiom replacing pointer
  chasing). ``contrib[e] = val[e] · Σ_c [col[e] = c]·x_tile[c]``.
- a static permutation (XLA take) reorders contributions to row order —
  the permutation is precomputed host-side at conversion.
- scatter kernel: chunk c holds E contributions of one row tile; the
  output block (again scalar-prefetch-indexed) is zero-initialized on
  first visit and accumulated across the tile's consecutive chunks —
  Mosaic's sequential grid makes the revisited VMEM block the TPU
  replacement for CUDA's atomicAdd.

Layout note: x tiles and y tiles are carried as [n_tiles, C, 1] /
[n_tiles, R, 1] and the chunk arrays as [n_chunks, 1, E]: the leading axis
is grid-blocked and every block's trailing two dims EQUAL the array's or
are (8, 128)-divisible (Mosaic's block-shape rule). The E axis is ALSO
grid-blocked in ``_EB`` sub-blocks — slicing a loaded vector in-kernel
leaves a lane offset in its layout (e.g. ``{*, 512}``) that Mosaic's
apply-vector-layout pass rejects for ``vector.broadcast`` (caught on real
v5e by the TPU smoke lane); full-block loads are always offset-0, so the
sub-blocking lives in the grid, not the kernel body.

Pad entries carry value 0 (gather side) / row_local = R (scatter side), so
they contribute nothing. Row tiles with no nonzeros are never visited by
the grid; the caller zero-fills them via the conversion's visited mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.utils import interpret_mode

_EB = 512    # DEFAULT sub-block of the chunk folded per grid step; the
             # kernels take the actual width as the ``eb`` parameter
             # (grid steps = padded_nnz/eb, VMEM one-hot = [C|R, eb])


def _gather_kernel(col_tile_ref, vals_ref, cols_ref, xt_ref, out_ref,
                   *, C: int, eb: int):
    xt = xt_ref[0]                                     # [C, 1]
    cols = cols_ref[0]                                 # [1, eb]
    onehot = (jnp.broadcast_to(cols, (C, eb))
              == jax.lax.broadcasted_iota(jnp.int32, (C, eb), 0))
    contrib = jnp.sum(jnp.where(onehot, xt, 0.0), axis=0,
                      keepdims=True)                   # [1, eb]
    out_ref[0] = vals_ref[0] * contrib


def _scatter_kernel(row_tile_ref, contrib_ref, rloc_ref, y_ref,
                    *, R: int, eb: int):
    c = pl.program_id(0)
    b = pl.program_id(1)
    cur = row_tile_ref[c]
    prev = row_tile_ref[jnp.maximum(c - 1, 0)]
    first = (((c == 0) | (cur != prev))) & (b == 0)

    rloc = rloc_ref[0]                                 # [1, eb], pad = R
    contrib = contrib_ref[0]                           # [1, eb]
    onehot = (jnp.broadcast_to(rloc, (R, eb))
              == jax.lax.broadcasted_iota(jnp.int32, (R, eb), 0))
    acc = jnp.sum(jnp.where(onehot, contrib, 0.0), axis=1,
                  keepdims=True)                       # [R, 1]

    @pl.when(first)
    def _():
        y_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _():
        y_ref[0] = y_ref[0] + acc


@functools.partial(jax.jit, static_argnames=("C", "R", "E", "n_col_tiles",
                                             "n_row_tiles", "eb"))
def _spmv_tiled_impl(vals, col_local, chunk_col_tile, perm, perm_rows,
                     row_local, chunk_row_tile, x_padded,
                     C: int, R: int, E: int,
                     n_col_tiles: int, n_row_tiles: int,
                     eb: int = _EB) -> jax.Array:
    n_chunks = vals.shape[0]
    m_chunks = row_local.shape[0]
    nb = E // eb
    xt = x_padded.reshape(n_col_tiles, C, 1)           # [n_tiles, C, 1]

    contrib = pl.pallas_call(
        functools.partial(_gather_kernel, C=C, eb=eb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks, nb),
            in_specs=[
                pl.BlockSpec((1, 1, eb), lambda c, b, m: (c, 0, b),
                             memory_space=pltpu.VMEM),   # vals
                pl.BlockSpec((1, 1, eb), lambda c, b, m: (c, 0, b),
                             memory_space=pltpu.VMEM),   # cols
                pl.BlockSpec((1, C, 1), lambda c, b, m: (m[c], 0, 0),
                             memory_space=pltpu.VMEM),   # x tile
            ],
            out_specs=pl.BlockSpec((1, 1, eb), lambda c, b, m: (c, 0, b),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(chunk_col_tile, vals[:, None, :], col_local[:, None, :], xt)

    if perm_rows is not None:
        # 8-aligned bucket layout: the bridge is a ROW gather (fast XLA
        # path) with an appended zero row for pad slots; the scalar
        # variant below measured 15.4 ms of the 17.1 ms SpMV at 2M nnz
        contrib8 = jnp.concatenate(
            [contrib.reshape(-1, 8), jnp.zeros((1, 8), jnp.float32)])
        contrib_sorted = jnp.take(contrib8, perm_rows,
                                  axis=0).reshape(m_chunks, 1, E)
    else:
        contrib_sorted = jnp.take(
            contrib.reshape(-1), perm.reshape(-1)).reshape(m_chunks, 1, E)

    y3d = pl.pallas_call(
        functools.partial(_scatter_kernel, R=R, eb=eb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_chunks, nb),
            in_specs=[
                pl.BlockSpec((1, 1, eb), lambda c, b, m: (c, 0, b),
                             memory_space=pltpu.VMEM),   # contrib
                pl.BlockSpec((1, 1, eb), lambda c, b, m: (c, 0, b),
                             memory_space=pltpu.VMEM),   # row_local
            ],
            out_specs=pl.BlockSpec((1, R, 1), lambda c, b, m: (m[c], 0, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((n_row_tiles, R, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_mode(),
    )(chunk_row_tile, contrib_sorted, row_local[:, None, :])
    return y3d[:, :, 0]                                # [n_row_tiles, R]


def spmv_tiled(tiled, x, eb=None) -> jax.Array:
    """y = A @ x for a :class:`raft_tpu.sparse.tiled.TiledELL` operand.
    ``eb`` is the per-grid-step sub-block of each chunk (must divide E);
    larger eb = fewer grid steps (less per-step overhead) at more VMEM
    per step — the one-hot intermediates are [C, eb] / [R, eb].
    Default: the whole chunk (measured best at 2M nnz on v5e: 4.9 ms at
    eb=2048 vs 6.1 at eb=512)."""
    n_rows, n_cols = tiled.shape
    if eb is None:
        # largest divisor of E ≤ 2048 (E is a 512-multiple, so one of
        # these always divides it)
        eb = next(w for w in (2048, 1024, 512)
                  if w <= tiled.E and tiled.E % w == 0)
    if tiled.E % eb:
        raise ValueError(f"spmv_tiled: eb={eb} must divide E={tiled.E}")
    x = jnp.asarray(x, jnp.float32)
    pad = tiled.n_col_tiles * tiled.C - n_cols
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
    y2dt = _spmv_tiled_impl(
        tiled.vals, tiled.col_local, tiled.chunk_col_tile, tiled.perm,
        tiled.perm_rows, tiled.row_local, tiled.chunk_row_tile, x,
        C=tiled.C, R=tiled.R, E=tiled.E,
        n_col_tiles=tiled.n_col_tiles, n_row_tiles=tiled.n_row_tiles,
        eb=eb)
    # zero row tiles the grid never visited (rows with no nonzeros)
    y2d = jnp.where(tiled.visited_row_tiles[:, None], y2dt, 0.0)
    return y2d.reshape(-1)[:n_rows]


def _spmv_pair_kernel(row_tile_ref, col_tile_ref, vals_ref, cloc_ref,
                      rloc_ref, xt_ref, y_ref, *, R: int, C: int):
    """ONE fused gather·multiply·scatter step over a pair-tiled chunk
    sub-block: no HBM contribution intermediate and — the measured
    killer — no XLA scalar permutation between gather and scatter (15.4
    of the two-kernel pipeline's 17.1 ms at 2M nnz ran in `jnp.take`,
    XLA's scalar gather being ~0.5 GB/s on TPU)."""
    c = pl.program_id(0)
    b = pl.program_id(1)
    cur = row_tile_ref[c]
    prev = row_tile_ref[jnp.maximum(c - 1, 0)]
    first = ((c == 0) | (cur != prev)) & (b == 0)

    xt = xt_ref[0]                                     # [C, 1]
    cols = cloc_ref[0]                                 # [1, EB]
    oh_c = (jnp.broadcast_to(cols, (C, _EB))
            == jax.lax.broadcasted_iota(jnp.int32, (C, _EB), 0))
    xs = jnp.sum(jnp.where(oh_c, xt, 0.0), axis=0,
                 keepdims=True)                        # [1, EB]
    contrib = vals_ref[0] * xs
    rloc = rloc_ref[0]                                 # [1, EB], pad = R
    oh_r = (jnp.broadcast_to(rloc, (R, _EB))
            == jax.lax.broadcasted_iota(jnp.int32, (R, _EB), 0))
    acc = jnp.sum(jnp.where(oh_r, contrib, 0.0), axis=1,
                  keepdims=True)                       # [R, 1]

    @pl.when(first)
    def _():
        y_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _():
        y_ref[0] = y_ref[0] + acc


@jax.jit
def spmv_pair_tiled(t, x) -> jax.Array:
    """y = A @ x for a :class:`raft_tpu.sparse.tiled.TiledPairsSpmv`
    operand — the single-kernel pair-tiled SpMV (see _spmv_pair_kernel).
    Chunks arrive sorted row-tile-major (tile_pairs' lexsort key), so
    the output block is revisited across a row tile's consecutive
    chunks and written to HBM once per tile."""
    p = t.pairs
    n_rows, n_cols = p.shape
    x = jnp.asarray(x, jnp.float32)
    pad = p.n_col_tiles * p.C - n_cols
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
    xt = x.reshape(p.n_col_tiles, p.C, 1)
    nb = p.E // _EB
    m_chunks = p.m_chunks

    y3d = pl.pallas_call(
        functools.partial(_spmv_pair_kernel, R=p.R, C=p.C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                     # row tiles, col tiles
            grid=(m_chunks, nb),
            in_specs=[
                pl.BlockSpec((1, 1, _EB), lambda c, b, mr, mc: (c, 0, b),
                             memory_space=pltpu.VMEM),   # vals
                pl.BlockSpec((1, 1, _EB), lambda c, b, mr, mc: (c, 0, b),
                             memory_space=pltpu.VMEM),   # col_local
                pl.BlockSpec((1, 1, _EB), lambda c, b, mr, mc: (c, 0, b),
                             memory_space=pltpu.VMEM),   # row_local
                pl.BlockSpec((1, p.C, 1),
                             lambda c, b, mr, mc: (mc[c], 0, 0),
                             memory_space=pltpu.VMEM),   # x tile
            ],
            out_specs=pl.BlockSpec((1, p.R, 1),
                                   lambda c, b, mr, mc: (mr[c], 0, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((p.n_row_tiles, p.R, 1),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_mode(),
    )(p.chunk_row_tile, p.chunk_col_tile, t.vals,
      p.col_local[:, None, :], p.row_local[:, None, :], xt)
    # zero row tiles the grid never visited (rows with no nonzeros)
    y2d = jnp.where(t.visited[:, None], y3d[:, :, 0], 0.0)
    return y2d.reshape(-1)[:n_rows]


# ---------------------------------------------------------------------------
# SpMM: multi-vector operand — the one-hot select becomes an MXU matmul
# ---------------------------------------------------------------------------


def _gather_mm_kernel(col_tile_ref, vals_ref, cols_ref, x_ref, out_ref,
                      *, C: int, V: int, eb: int):
    """contrib[e, :] = val[e] · x_tile[col[e], :] via onehotᵀ @ x — for
    V ≥ ~8 columns the MXU does the selection (the one-hot rows are
    exactly representable in bf16, so with HIGHEST precision the gather
    error is the bf16x3 split residual of x, ~2⁻¹⁶ relative)."""
    x = x_ref[0]                                         # [C, V]
    cols = cols_ref[0]                                   # [1, eb]
    onehot = (jnp.broadcast_to(cols, (C, eb))
              == jax.lax.broadcasted_iota(jnp.int32, (C, eb), 0)
              ).astype(jnp.float32)                      # [C, eb]
    g = jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [EB, V]
    out_ref[0] = vals_ref[0] * g                         # vals [EB, 1]


def _scatter_mm_kernel(row_tile_ref, contrib_ref, rloc_ref, y_ref,
                       *, R: int, V: int, eb: int):
    c = pl.program_id(0)
    b = pl.program_id(1)
    cur = row_tile_ref[c]
    prev = row_tile_ref[jnp.maximum(c - 1, 0)]
    first = ((c == 0) | (cur != prev)) & (b == 0)

    rloc = rloc_ref[0]                                   # [1, eb], pad = R
    onehot = (jnp.broadcast_to(rloc, (R, eb))
              == jax.lax.broadcasted_iota(jnp.int32, (R, eb), 0)
              ).astype(jnp.float32)                      # [R, eb]
    contrib = contrib_ref[0]                             # [eb, V]
    acc = jax.lax.dot_general(
        onehot, contrib, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # [R, V]

    @pl.when(first)
    def _():
        y_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _():
        y_ref[0] = y_ref[0] + acc


@functools.partial(jax.jit, static_argnames=("C", "R", "E", "V",
                                             "n_col_tiles", "n_row_tiles",
                                             "eb"))
def _spmm_tiled_impl(vals, col_local, chunk_col_tile, perm, perm_rows,
                     row_local, chunk_row_tile, B_padded,
                     C: int, R: int, E: int, V: int,
                     n_col_tiles: int, n_row_tiles: int,
                     eb: int = _EB) -> jax.Array:
    n_chunks = vals.shape[0]
    m_chunks = row_local.shape[0]
    nb = E // eb
    x3d = B_padded.reshape(n_col_tiles, C, V)
    vals3 = vals.reshape(n_chunks, E, 1)                 # [EB, 1] blocks

    contrib = pl.pallas_call(
        functools.partial(_gather_mm_kernel, C=C, V=V, eb=eb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks, nb),
            in_specs=[
                pl.BlockSpec((1, eb, 1), lambda c, b, m: (c, b, 0),
                             memory_space=pltpu.VMEM),   # vals
                pl.BlockSpec((1, 1, eb), lambda c, b, m: (c, 0, b),
                             memory_space=pltpu.VMEM),   # cols
                pl.BlockSpec((1, C, V), lambda c, b, m: (m[c], 0, 0),
                             memory_space=pltpu.VMEM),   # x tile
            ],
            out_specs=pl.BlockSpec((1, eb, V), lambda c, b, m: (c, b, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((n_chunks, E, V), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(chunk_col_tile, vals3, col_local[:, None, :], x3d)

    if perm_rows is not None:
        # 8-aligned bucket layout: gather 8-slot row groups ([8·V]-wide)
        c8 = jnp.concatenate(
            [contrib.reshape(-1, 8 * V),
             jnp.zeros((1, 8 * V), jnp.float32)])
        contrib_sorted = jnp.take(c8, perm_rows,
                                  axis=0).reshape(m_chunks, E, V)
    else:
        contrib_sorted = jnp.take(contrib.reshape(-1, V), perm.reshape(-1),
                                  axis=0).reshape(m_chunks, E, V)

    y3d = pl.pallas_call(
        functools.partial(_scatter_mm_kernel, R=R, V=V, eb=eb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_chunks, nb),
            in_specs=[
                pl.BlockSpec((1, eb, V), lambda c, b, m: (c, b, 0),
                             memory_space=pltpu.VMEM),   # contrib
                pl.BlockSpec((1, 1, eb), lambda c, b, m: (c, 0, b),
                             memory_space=pltpu.VMEM),   # row_local
            ],
            out_specs=pl.BlockSpec((1, R, V), lambda c, b, m: (m[c], 0, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((n_row_tiles, R, V), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_mode(),
    )(chunk_row_tile, contrib_sorted, row_local[:, None, :])
    return y3d


def spmm_tiled(tiled, B) -> jax.Array:
    """Y = A @ B for a TiledELL operand and dense B [n_cols, V] — the
    cusparse-SpMM role with the one-hot selects running on the MXU.
    (ref: sparse/linalg/spmm.hpp:42 / cusparse_wrappers.h SpMM.)"""
    n_rows, n_cols = tiled.shape
    B = jnp.asarray(B, jnp.float32)
    if B.ndim != 2 or B.shape[0] != n_cols:
        raise ValueError(f"spmm_tiled: B must be [{n_cols}, V]")
    V = B.shape[1]
    if V > 512:
        # the [1, C, V] x-tile and [1, EB, V] contribution blocks are
        # VMEM-resident; past this width Mosaic fails to fit them with an
        # opaque error — fail early with an actionable one instead
        raise NotImplementedError(
            f"spmm_tiled targets V <= 512 dense columns (VMEM tile); got "
            f"{V} — chunk B column-wise or use the COO/CSR path")
    pad = tiled.n_col_tiles * tiled.C - n_cols
    if pad:
        B = jnp.concatenate([B, jnp.zeros((pad, V), jnp.float32)])
    # sub-block sized so BOTH the [eb, V] contrib tile and the
    # dominant [max(C,R), eb] one-hot buffers stay ≤ ~2/4 MB (same
    # grid-step-overhead logic as spmv_tiled's whole-chunk default);
    # falls back to the 512 floor for tilings where nothing larger fits
    cr = max(tiled.C, tiled.R)
    eb = next((w for w in (2048, 1024, 512)
               if w <= tiled.E and tiled.E % w == 0
               and w * max(V, 1) * 4 <= (2 << 20)
               and cr * w * 4 <= (4 << 20)), 512)
    y3d = _spmm_tiled_impl(
        tiled.vals, tiled.col_local, tiled.chunk_col_tile, tiled.perm,
        tiled.perm_rows, tiled.row_local, tiled.chunk_row_tile, B,
        C=tiled.C, R=tiled.R, E=tiled.E, V=V,
        n_col_tiles=tiled.n_col_tiles, n_row_tiles=tiled.n_row_tiles,
        eb=eb)
    y2d = jnp.where(tiled.visited_row_tiles[:, None, None], y3d, 0.0)
    return y2d.reshape(-1, V)[:n_rows]
