"""Certified fused KNN — the flagship TPU pipeline.

(ref: the reference's fused distance→select path: brute-force knn =
pairwise distance + matrix::select_k, with select_radix.cuh /
select_warpsort.cuh consuming distance tiles; BASELINE config 2.)

Pipeline (all one jit program):

1. ``ops.fused_l2_topk_pallas.fused_l2_group_topk`` streams index tiles
   through VMEM: MXU contraction + an IN-KERNEL top-2+3rd-min fold per
   (lane-class, tile-group) — output blocks are revisited across ``g``
   consecutive index tiles, so the fold accumulates in VMEM and the
   distance tiles never touch HBM; only the [Q, 2·S'] group summary does
   (S' = ceil(n_tiles/g)·128 slots). (Round-2 profile: the earlier
   XLA-side group fold re-read ~1 GB of per-(tile,lane) slot arrays and
   cost 3× the kernel itself.)
2. TWIN-POOL selection (packed path): ``top_k`` picks Ca = k + pad
   winners from the a1 (per-group best) array alone — XLA's TopK is
   superlinear in pool width inside the composite program, so the
   2·S'-wide concat pool is never built — then each winner's a2 TWIN
   is pulled by position and the 2·Ca candidates are pruned back to C
   by kernel order; the C survivors are rescored EXACTLY (f32, HIGHEST
   precision) and the final top-k is taken on exact values.
3. EXACTNESS CERTIFICATE, per query: every point outside the candidate
   set has kernel-distance ≥ B = min(group-3rd-min, Ca-th a1 value,
   C-th pruned kernel value) — an a1 loser is ≥ the Ca-th a1 value, an
   a2 twin of an a1 loser is ≥ its own a1 (merge invariant a2 ≥ a1),
   a pruned candidate is ≥ the C-th pruned value, and anything outside
   a bucket's top-2 is ≥ that bucket's 3rd-min. With |kernel − exact|
   ≤ E, ``B − E ≥ θ*`` (θ* = exact k-th candidate distance) proves no
   point can beat the returned top-k. Every term is ≥ the whole-pool
   C-th value the round-2 design used, so the bound only tightened.
   The bound needs NO second distance pass — it falls out of the fold.
4. Queries that fail the certificate (THREE true neighbors sharing a
   (lane, group): ~k³/6S'² per query — single digits per 2048 queries
   at production scale; certify="f32"'s wider margin can fail
   hundreds) are re-solved exactly and scattered back: tiered static
   batches (16/128/512/1024, each eligible only while its [F, M] tile
   fits the fixup budget) that materialize an [F, M] distance tile
   and select over it once — through a threshold block filter where
   the tile is long (``fixup_select``: the rows' own candidates bound
   their k-th distance, so only the few blocks that can hold an answer
   pass through a TopK); a full streamed fallback covers pathological
   batches (cond) and the empty-ladder regime (M too large for any
   tile).

Modes:
- ``passes=3`` (exact): bf16 hi/lo split contraction (hi·hi + hi·lo +
  lo·hi) ⇒ f32-grade kernel distances; E is a rigorous norm-based bound,
  so the result is certified exact w.r.t. f32 distances.
- ``passes=1`` (fast): single bf16 contraction; E = 0, so the certificate
  guarantees exactness w.r.t. the bf16 score function; recall vs f32 is
  empirical (≥0.99 typical).

Precision contract: the score function is the EXPANDED squared L2,
``‖x‖² + ‖y‖² − 2x·y``, evaluated in f32 — the same functional form the
reference's fusedL2NN/pairwise kernels evaluate on GPU. Like the
reference, expanded f32 carries cancellation noise of order
``ulp(‖x‖² + ‖y‖²)`` when true distances are tiny relative to the norms
(near-duplicate points); "certified exact" means exact top-k of THAT
score function, with returned values within ulp-noise of the infinite-
precision expanded scores (validated in tests against an f64 oracle).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.observability import instrument
from raft_tpu.resilience import fault_point

from raft_tpu.ops.fused_l2_topk_pallas import (
    _LANES, _PACK_BITS, _PACK_MASK, _PACK_PAD, _PBITS_MAX,
    fused_l2_group_topk, fused_l2_group_topk_dchunk,
    fused_l2_group_topk_packed, fused_l2_group_topk_packed_db,
    fused_l2_group_topk_packed_db_q8, fused_l2_group_topk_packed_dbuf,
    fused_l2_group_topk_packed_dbuf_q8,
    fused_l2_group_topk_packed_dchunk, split_hi_lo, vmem_budget,
    vmem_footprint)

# grid iteration orders for the packed fused kernel (see the
# DATABASE-MAJOR block comment in ops.fused_l2_topk_pallas):
#   "query" — grid (nq, n_tiles): y re-fetched per query block
#             (y HBM traffic nq·M·d bytes — the historical default);
#   "db"    — super-blocked grid (n_groups, nq): each [g·T, d] group
#             VMEM-resident, y streams from HBM once (M·d·2 bytes);
#   "dbuf"  — grid (n_groups,): explicit 2-slot double-buffered y-tile
#             DMA, y streams once and only 2 tiles are VMEM-resident.
GRID_ORDERS = ("query", "db", "dbuf")

# storage dtypes for the STREAMED database slab:
#   "bf16" — the historical hi(/lo) bf16 split: M·d·2 (p1) or M·d·4
#            (p3) bytes per stream;
#   "int8" — per-certificate-group symmetric-scale quantization:
#            M·d·1 bytes per stream regardless of passes, with the
#            twin-pool certificate widened by the recorded per-group
#            quantization bound Eq and candidates ALWAYS exact-rescored
#            in f32 from the original rows — returned ids are certified
#            identical to the f32 oracle's (ROADMAP item 2).
DB_DTYPES = ("bf16", "int8")

# int8 quantization geometry: symmetric (zero_point = 0), code range
# ±_Q8_LEVELS; the per-element round-trip error bound is
# scale · _Q8_ERR (½ ulp of the code grid + headroom for the f32
# divide/round/multiply chain — the property test drives adversarial
# scale-boundary values at it)
_Q8_LEVELS = 127
_Q8_ERR = 0.5 * (1.0 + 2.0 ** -10)

# past this feature width the single-shot kernel's [Qb/T, d] VMEM tiles
# stop fitting; the d-chunked kernel (VMEM scratch accumulator) takes over
_D_SINGLE_SHOT = 512
_DC = 256          # d-chunk width for the wide-feature kernel

# static fixup batches: queries whose certificate failed re-run exactly
# against the whole index. Tiered (16 first) because the cond pays the
# whole static tier even for one failed query; with the group kernel's
# top-2-per-group certificate the typical failure count is single-digit
# per 2048 queries, so the small tier almost always suffices. The
# larger tiers exist for certify="f32" (adaptive precision), whose
# wider margin can fail hundreds of queries — without them anything
# past the 128 tier hit the catastrophic full streamed fallback. A
# tier is only eligible when its [F, M] f32 distance tile fits the
# budget (at 10M rows the 512+ tiers would be 20+ GB).
_FIXUP_TIERS = (16, 128, 512, 1024)
# budget for ONE [F, M] f32 tile; the materialized branch holds ~2 live
# copies (d2 + the negated top_k input), so peak ≈ 2× this + operands —
# 4.2 GB keeps the 1024 tier at the 1M driver shape (2·4.1 GB + ~2 GB
# of index operands < 16 GB v5e HBM) and sheds it past ~1.05M rows
_FIXUP_TILE_BUDGET = 4_200_000_000
# columns per block of the fixup's threshold filter (fixup_select), and
# the most blocks its exact fallback (blocked_top_k) merges at a time
_FIXUP_BLOCK = 512
_FIXUP_GROUP = 256
# pool oversampling beyond k before exact rescoring
_POOL_PAD = 32
# query-chunk bound: the [Q, S] slot arrays + [Q, C, d] rescore gather are
# sized by Q — queries are processed in chunks of this many (≈1 GB peak at
# the 1M×128 BASELINE shape), the fused path's analog of the streamed
# path's workspace-budgeted tile
_Q_CHUNK = 2048


def _err_bound_coeff(d: int) -> float:
    """Analytic upper bound on |d2_kernel − d2_exact| / (‖x‖·‖y‖) for the
    bf16x3 mode. Components (unit roundoffs: bf16 2⁻⁸ — 7 stored
    mantissa bits, round-to-nearest — and f32 2⁻²⁴):
      - dropped lo·lo term: Σ|lo(x)||lo(y)| ≤ 2⁻¹⁶·‖x‖‖y‖
      - bf16 re-rounding of the lo factors (x = hi + lo + δ,
        |δ| ≤ 2⁻¹⁶|x|): ≤ 2·2⁻¹⁶·‖x‖‖y‖
      - f32 accumulation, textbook bound d·2⁻²⁴·Σ|x·y| per matmul, three
        matmuls: ≤ 3d·2⁻²⁴·‖x‖‖y‖
    S_err ≤ (3·2⁻¹⁶ + 3d·2⁻²⁴)·‖x‖‖y‖; doubled for d2 = 2·S_err and
    doubled again as safety margin ⇒ ≤ (1.5·2⁻¹³ + 1.5·d·2⁻²¹)·‖x‖‖y‖,
    rounded UP to a clean power of two. The margin's only cost is fixup
    rate, but the BOUND ITSELF must hold for the exactness certificate
    to be sound. (Round 4: the first version assumed bf16 u = 2⁻⁹ and
    shipped 2⁻¹⁵ — understated ~4× against the adversarial worst case,
    though ~30× above errors observed on random/clustered data.)"""
    return 2.0 ** -12 + d * 2.0 ** -20


def _err_bound_coeff_p1(d: int) -> float:
    """|d2_kernel − d2_f32| / (‖x‖·‖y‖) bound for the ONE-pass bf16
    contraction — the margin behind ``certify="f32"`` at passes=1
    (adaptive precision: p1 speed, f32-exact certificate, failures
    re-solved by the exact fixup). Components (bf16 u = 2⁻⁸):
      - bf16 rounding of both factors: ≤ (2·2⁻⁸ + 2⁻¹⁶)·‖x‖‖y‖
      - f32 accumulation: ≤ d·2⁻²⁴·‖x‖‖y‖
    Doubled for d2 = 2·S_err and doubled again as safety margin ⇒
    ≤ (2⁻⁵ + 2⁻¹⁴ + d·2⁻²²)·‖x‖‖y‖ — the 2⁻¹⁴ is the doubled 2⁻¹⁶
    cross term, kept so every component is rounded UP like
    _err_bound_coeff's (a loose margin only raises fixup rate; the
    bound itself must hold)."""
    return 2.0 ** -5 + 2.0 ** -14 + d * 2.0 ** -22


def _pool_smallest(a, c: int):
    """Exact c smallest per row of the candidate pool ``a`` →
    (values ascending, positions), by XLA's TopK. Exactness is
    non-negotiable here: the twin-pool certificate's bound_a1 /
    C-th-pruned terms assume exact selection (an approximate selector
    leaves skipped bucket-top-2 entries with no floor — the a3 term does
    not cover them). Negation flips only the sign bit, so packed
    mantissa codes survive bit-exactly."""
    neg, pos = jax.lax.top_k(-a, c)
    return -neg, pos


def fixup_filter_geometry(M: int, k: int) -> Optional[Tuple[int, int]]:
    """``(B, nb)`` of :func:`fixup_select` over an ``[F, M]`` tile: the
    block width and the number of blocks kept (the power of two ≥ 2k),
    or None where the filter cannot shrink the top_k (M ≤ nb·B), B does
    not divide M or k > B — the flat top_k then stays."""
    nb = 1 << max(0, 2 * k - 1).bit_length()
    B = _FIXUP_BLOCK
    if M % B or M <= nb * B or k > B:
        return None
    return B, nb


def fixup_select(d2b, cand, k: int, nb: int):
    """``(vals, ids, exact)``: the k smallest of each row of a masked
    ``[F, M]`` distance tile, ascending, given as its blocked view
    ``d2b [M/B, F, B]`` (``d2b[j, f, b]`` = column ``j·B + b`` of row
    f). Where ``exact`` (a bool scalar) holds they are bit for bit
    ``top_k(-d2, k)`` of the flat tile, ties to the lowest column.

    XLA's TPU TopK costs time in proportion to its element count
    (``SELECT_K_MATRIX.json``), and the fixup knows almost the whole
    answer before it selects: ``cand [F, k]`` are each row's current
    candidate columns (−1 for none), so ``t = max d2[f, cand[f]]`` bounds
    the row's k-th value whenever they are k distinct real columns. The
    block minima (a reduction the tile's producer fuses) mark the blocks
    holding a value ≤ t; the first nb of them, in ascending block order,
    are gathered and only those ``nb·B`` columns go through a TopK.
    Every column left out is > t, so where each row kept all its marked
    blocks and its k-th kept value is ≤ t the result IS the flat top_k.
    A row that fails that (more than nb marked blocks; fewer than k
    distinct real candidates, so t = +inf; a NaN) makes ``exact`` false,
    and :func:`blocked_top_k` then selects over the whole tile.

    Blocks lead the view so that the tile's producer writes it, and the
    gathers read it, in one TPU layout: ``[F, M/B, B]`` costs a
    relayout copy of the whole tile."""
    nblk, F, B = d2b.shape
    rows = jnp.arange(F, dtype=jnp.int32)[:, None]
    c = jnp.clip(cand, 0, nblk * B - 1)
    at = d2b[c // B, rows, c % B]                                  # [F, k]
    t = jnp.max(jnp.where(cand >= 0, at, jnp.inf), axis=1)          # [F]
    hit = jnp.min(d2b, axis=2).T <= t[:, None]                     # [F, nblk]
    n_hit = jnp.sum(hit, axis=1, dtype=jnp.int32)
    # marked blocks first, each group in ascending block order; the
    # keys are distinct, so no tie is left to the TopK
    bid = jnp.arange(nblk, dtype=jnp.int32)
    _, sel = jax.lax.top_k(
        jnp.where(hit, nblk - bid, -bid).astype(jnp.float32), nb)
    sub = d2b[sel, rows].reshape(F, nb * B)                        # [F, nb·B]
    cols = (sel[:, :, None] * B
            + jnp.arange(B, dtype=jnp.int32)).reshape(F, nb * B)
    nt, pos = jax.lax.top_k(-sub, k)
    vals = -nt
    ids = jnp.take_along_axis(cols, pos, axis=1)
    # NaN-safe: a comparison with NaN fails
    exact = jnp.all((n_hit <= nb) & (vals[:, k - 1] <= t))
    return vals, ids, exact


def blocked_top_k(d2b, k: int):
    """``top_k(-d2, k)`` of the flat tile, negated back, from its
    blocked view ``d2b [M/B, F, B]`` (:func:`fixup_select`, B ≥ k)
    without relaying the whole tile out: a running top k merged with
    one group of blocks at a time, in column order, so ties go to the
    lowest column as the flat top_k breaks them. The group (≤
    ``_FIXUP_GROUP`` blocks) bounds the relayout to one group's
    columns."""
    nblk, F, B = d2b.shape
    G = math.gcd(nblk, _FIXUP_GROUP)

    def group(j):
        g = jax.lax.dynamic_slice_in_dim(d2b, j * G, G, axis=0)
        cols = j * G * B + jnp.arange(G * B, dtype=jnp.int32)
        return (jnp.transpose(g, (1, 0, 2)).reshape(F, G * B),
                jnp.broadcast_to(cols, (F, G * B)))

    def merge(v, c):
        nt, pos = jax.lax.top_k(-v, k)
        return -nt, jnp.take_along_axis(c, pos, axis=1)

    def body(j, carry):
        v, c = group(j)
        return merge(jnp.concatenate([carry[0], v], axis=1),
                     jnp.concatenate([carry[1], c], axis=1))

    return jax.lax.fori_loop(1, nblk // G, body, merge(*group(0)))


def decode_packed_pool(cand_p, pos, S_: int, T: int, g: int,
                       pbits: int = _PACK_BITS):
    """Candidate columns from (packed value, pool position) — THE
    decode for the packed kernel's mantissa codes, shared by the
    production pipeline and the profiler so they cannot drift. Returns
    -1 for sentinel/empty entries."""
    n_ch = T // _LANES
    slot = pos % S_
    local = (jax.lax.bitcast_convert_type(cand_p, jnp.int32)
             & ((1 << pbits) - 1))
    col = ((slot // _LANES) * g + local // n_ch) * T \
        + (local % n_ch) * _LANES + (slot % _LANES)
    return jnp.where(cand_p < _PACK_PAD * 0.25, col, -1)


def auto_pack_bits(n_tiles: int, T: int) -> int:
    """Pack-code width for an index of ``n_tiles`` tiles of length T:
    the candidate pool (and the certificate's bucket count) is
    M/2^pbits wide, so pick the widest codes that keep ≥ ~2.5k buckets
    (fixup rate ∝ 1/buckets²), clamped to [8, 13] (value perturbation
    2^(pbits−23) must stay well under the error margins). ONE
    definition, which prepare_knn_index calls."""
    return min(_PBITS_MAX, max(_PACK_BITS, int(math.floor(
        math.log2(max(n_tiles * T / 2560.0, 256.0))))))


def _pad_rows_to(y, mult: int):
    from raft_tpu.distance.fused_l2nn import _pad_rows

    return _pad_rows(y, mult)[0]


def pad_query_rows(x, rows: int):
    """Pad a RAGGED query batch up to a fixed ``rows`` count with zero
    rows — the serving engine's bucket shapes (raft_tpu.serving) and the
    AOT ``knn_query`` runtime entry both route ragged request batches
    through this so every dispatch hits a pre-compiled shape. Zero-row
    queries are inert through the whole pipeline (their top-k is
    computed and discarded — the certificate and fixup maths are
    per-query, so pads cannot perturb real rows); callers slice the
    first ``n`` result rows back out. Raises when the batch is LARGER
    than the bucket: silently truncating requests is exactly the
    failure mode the serving ladder's reject path exists to prevent."""
    n = x.shape[0]
    if n > rows:
        raise ValueError(f"pad_query_rows: batch of {n} rows does not "
                         f"fit the {rows}-row bucket")
    if n == rows:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((rows - n, x.shape[1]), x.dtype)], axis=0)


@functools.partial(jax.jit, static_argnames=("T", "g", "metric",
                                             "pbits", "grid_order"))
def _prepare_ops(y, T: int, g: int, metric: str,
                 pbits: int = _PACK_BITS, grid_order: str = "query",
                 n_valid=None, rows_valid=None):
    """Index-side operand prep: row padding, bf16 hi/lo split, norms and
    the [8, M] half-norm sentinel carrier. ~3 ms at 1M×128 on v5e —
    hoisted out of the query path so a prepared index (KnnIndex) pays
    it ONCE instead of per query batch.

    Database-major grid orders pad the index to WHOLE certificate
    groups (g·T rows — each super-block is one resident y block /
    one DMA group); padded columns carry the same never-wins sentinel
    either way, so the extra rows are certificate-invisible.

    ``n_valid`` overrides the real-row count when the caller passes an
    ALREADY-PADDED matrix (the sharded index prep pads globally to a
    whole number of equal shards before splitting, so the trailing
    rows of ``y`` itself are pads that must carry the sentinel). It may
    be a plain int or a TRACED scalar — inside the sharded prep's
    shard_map one traced program serves every shard, and each shard's
    real-row count is a value (a function of its mesh coordinate), not
    a shape.

    ``rows_valid`` is the RAGGED generalization of ``n_valid``: a [m]
    bool mask over the INPUT rows marking which are real — pads may be
    interspersed anywhere, not just trailing. This is the layout the
    IVF-Flat inverted lists (raft_tpu.ann — each list padded to a row
    quantum, so pads sit at every list tail) and the serving engine's
    bucket padding share. Masked-out rows carry the same never-wins
    sentinel trailing pads do, so they are invisible to the fold and
    the certificate; rows appended here to reach the tile multiple are
    masked too. Mutually exclusive with ``n_valid``."""
    if rows_valid is not None:
        m = y.shape[0]       # geometric row count; masking is per-row
    else:
        m = y.shape[0] if n_valid is None else n_valid
    yp = _pad_rows_to(y, g * T if grid_order in ("db", "dbuf") else T)
    M = yp.shape[0]
    yy_raw = jnp.sum(yp * yp, axis=1)[None, :]                  # [1,M] f32
    n_ch = T // _LANES
    packed = g * n_ch <= (1 << pbits)
    pad_sentinel = _PACK_PAD if packed else jnp.inf
    if rows_valid is not None:
        rv = jnp.asarray(rows_valid, jnp.bool_).reshape(-1)
        pad = M - rv.shape[0]
        if pad:
            rv = jnp.concatenate([rv, jnp.zeros((pad,), jnp.bool_)])
        valid = rv[None, :]
    else:
        valid = (jnp.arange(M, dtype=jnp.int32) < m)[None, :]
    if metric == "ip":
        # r = 0/2 − x·(y/2) = −x·y/2 → score −x·y = 2·r (+ xx_r = 0)
        y_hi, y_lo = split_hi_lo(yp * 0.5)
        yyh_k = jnp.where(valid, 0.0, pad_sentinel)
    else:
        y_hi, y_lo = split_hi_lo(yp)
        yyh_k = jnp.where(valid, 0.5 * yy_raw, pad_sentinel)
    # [8, M] sublane-replicated carrier (see fused_l2_group_topk)
    yyh_k = jnp.broadcast_to(yyh_k, (8, M))
    return yp, y_hi, y_lo, yyh_k, yy_raw


def quantize_rows_q8(z, gid, n_groups: int, valid=None):
    """Per-group symmetric int8 quantization of the stream operand
    ``z`` [M, d] (group of row i = ``gid[i]``): scale_g =
    max|z_group| / 127 (zero_point 0 — L2/IP operands are centered by
    construction), codes clipped to ±127 so an f32 divide landing
    epsilon past the last level can never overflow the int8 range.
    Returns (y_q int8 [M, d], scales f32 [n_groups]). ``valid`` masks
    rows out of the scale computation (pad/garbage rows must not
    inflate a group's scale); their codes are still produced but every
    consumer hides them behind the never-wins sentinel."""
    absz = jnp.abs(z)
    if valid is not None:
        absz = jnp.where(valid.reshape(-1, 1), absz, 0.0)
    row_max = jnp.max(absz, axis=1)
    gmax = jax.ops.segment_max(row_max, gid, num_segments=n_groups)
    gmax = jnp.maximum(gmax, 0.0)          # empty segment → -inf → 0
    scales = jnp.where(gmax > 0, gmax / _Q8_LEVELS, 1.0)
    srow = jnp.take(scales, gid).reshape(-1, 1)
    q = jnp.clip(jnp.round(z / srow), -_Q8_LEVELS, _Q8_LEVELS)
    return q.astype(jnp.int8), scales


def q8_eq_bound(scales, d: int):
    """Per-group quantization error bound Eq: an upper bound on the
    ROW-VECTOR L2 error ‖z_row − dequant(quant(z_row))‖ for any row of
    a group with scale ``scales[g]`` — per element the round-trip error
    is ≤ scale·_Q8_ERR (½ code step + f32 divide/round/multiply
    headroom; clipped boundary values err by ≤ scale·127·2⁻²³, well
    inside), so the row bound is scale·_Q8_ERR·√d. Padded feature
    columns are exactly zero → quantize exactly → contribute 0, so the
    padded √d is simply a looser-but-sound bound. This is the margin
    the twin-pool certificate is widened by (see _knn_fused_core), and
    the bound the property test attacks with adversarial
    scale-boundary values."""
    return scales * (_Q8_ERR * math.sqrt(max(d, 1)))


@functools.partial(jax.jit, static_argnames=("T", "g", "metric",
                                             "pbits", "grid_order"))
def _prepare_ops_q8(y, T: int, g: int, metric: str,
                    pbits: int = _PACK_BITS, grid_order: str = "db",
                    n_valid=None, rows_valid=None):
    """INT8 sibling of :func:`_prepare_ops` — index-side operand prep
    for the quantized-streaming kernels: row padding to WHOLE
    certificate groups, per-group symmetric int8 quantization of the
    stream operand (y for l2, y/2 for ip), the group-scale tile, and
    carriers computed from the DEQUANTIZED rows ŷ so the kernel's
    folded value is exactly d2(x, ŷ)/2 (l2) — the codes, decode and
    certificate algebra downstream are untouched.

    Returns ``(yp, y_q, scale_k, yyh_k, yy_raw, eq_groups)``:
    yp [M, d] f32 row-padded ORIGINAL rows (the exact-rescore source —
    int8 indexes always store it), y_q [M, d] int8, scale_k
    [G, 8, 128] f32 group-replicated, yyh_k [8, M] the dequantized
    half-norm sentinel carrier, yy_raw [1, M] the dequantized
    full-scale norms (the bf16 error bound's ymax), eq_groups [G] the
    per-group quantization bound (see :func:`q8_eq_bound`).

    ``n_valid``/``rows_valid`` follow _prepare_ops' contract (trailing
    vs ragged pads). Packed/database-major only — the quantized
    kernels are the stream-once ones."""
    if grid_order not in ("db", "dbuf"):
        raise ValueError("_prepare_ops_q8: int8 streaming is "
                         "database-major only (grid_order 'db'/'dbuf')")
    n_ch = T // _LANES
    if g * n_ch > (1 << pbits):
        raise ValueError("_prepare_ops_q8: int8 streaming needs the "
                         "packed-code envelope (g·(T/128) ≤ 2^pbits)")
    if rows_valid is not None:
        m = y.shape[0]
    else:
        m = y.shape[0] if n_valid is None else n_valid
    yp = _pad_rows_to(y, g * T)
    M, d = yp.shape
    G = M // (g * T)
    if rows_valid is not None:
        rv = jnp.asarray(rows_valid, jnp.bool_).reshape(-1)
        pad = M - rv.shape[0]
        if pad:
            rv = jnp.concatenate([rv, jnp.zeros((pad,), jnp.bool_)])
        valid_row = rv
    else:
        valid_row = jnp.arange(M, dtype=jnp.int32) < m
    z = yp * 0.5 if metric == "ip" else yp
    gid = jnp.arange(M, dtype=jnp.int32) // (g * T)
    y_q, scales = quantize_rows_q8(z, gid, G, valid=valid_row)
    eq_groups = q8_eq_bound(scales, d)
    # dequantized stream operand ẑ — the rows the kernel actually
    # scores; its norms ride the carrier so kernel values are exactly
    # d2(x, ẑ) (l2) and the Eq widening is the ONLY new error term
    zq = y_q.astype(jnp.float32) * jnp.take(scales, gid).reshape(-1, 1)
    valid = valid_row[None, :]
    if metric == "ip":
        yyh_k = jnp.where(valid, 0.0, _PACK_PAD)
        yhat_full = 2.0 * zq       # full-scale dequantized ŷ (= 2·ẑ)
    else:
        yy_hat = jnp.sum(zq * zq, axis=1)[None, :]
        yyh_k = jnp.where(valid, 0.5 * yy_hat, _PACK_PAD)
        yhat_full = zq
    yy_raw = jnp.sum(yhat_full * yhat_full, axis=1)[None, :]
    yyh_k = jnp.broadcast_to(yyh_k, (8, M))
    scale_k = jnp.broadcast_to(scales.reshape(G, 1, 1), (G, 8, _LANES))
    return yp, y_q, scale_k, yyh_k, yy_raw, eq_groups


@functools.partial(jax.jit,
                   static_argnames=("k", "T", "Qb", "g", "passes", "metric",
                                    "m", "rescore", "pbits", "certify",
                                    "grid_order", "db_dtype",
                                    "_diag", "with_stats"))
def _knn_fused_core(x, yp, y_hi, y_lo, yyh_k, yy_raw,
                    k: int, T: int, Qb: int, g: int, passes: int,
                    metric: str, m: int, rescore: bool = True,
                    pbits: int = _PACK_BITS, certify: str = "kernel",
                    grid_order: str = "query", db_dtype: str = "bf16",
                    _diag: bool = False, with_stats: bool = False,
                    m_valid=None, rows_valid=None,
                    y_q=None, y_scale_k=None,
                    eq_groups=None) -> Tuple[jax.Array, ...]:
    """Certified fused KNN on PREPARED operands (see _prepare_ops).

    ``m_valid`` (optional TRACED scalar) overrides the static ``m`` in
    every real-row mask (kernel column mask, rescore id clamp, fixup
    column masks). The sharded pipeline (distance.knn_sharded) needs it:
    one shard_map-traced program serves every shard, but each shard owns
    a different number of real rows — a value, not a shape. ``m`` keeps
    sizing the static fixup-tier geometry.

    ``rows_valid`` (optional TRACED [M] bool, M = the PREPARED row
    count) is the RAGGED mask: real rows may be interspersed with pads
    (the IVF-Flat slab layout — every inverted list tail is padding).
    The operands must have been prepared with the SAME mask (the
    sentinel carrier is what hides pads from the kernel fold); here it
    only replaces the prefix column masks in the fixup sweeps and
    widens the rescore clamp to the whole slab. Packed-path only: the
    unpacked kernels prefix-mask in-kernel by ``m_real`` and cannot
    honor an arbitrary mask.

    x [Q, d] f32 (Q % Qb == 0, d % 128 == 0 — caller pads), y [m, d] f32
    un-padded rows; returns exact (score [Q, k] ascending, ids [Q, k]).
    ``metric="l2"`` scores expanded squared L2; ``metric="ip"`` scores
    ``−x·y`` (so ascending = best inner products first) by feeding the
    SAME kernel zeros for xx/yy and the hi/lo split of y/2:
    d2 = 0 + 0 − 2·x·(y/2) = −x·y. The certificate algebra is
    metric-blind (it only needs "every non-candidate ≥ its slot's
    2nd-min"); the bf16x3 error bound uses the TRUE operand norms.

    The kernel folds the HALF-SCORE r = yy/2 − x·y (a positive-scale +
    per-row-shift of d2, so per-row ordering is identical — one fewer
    live [Qb, T] buffer in-kernel); padded index columns carry a
    "never wins" sentinel so they lose every strict < in the fold (no
    in-kernel masking). True distances are recovered as 2·r + xx on
    the tiny [Q, S'] outputs.

    PACKED path (production whenever the per-group slot count fits the
    _PACK_BITS code space): candidate ids ride in the low mantissa
    bits of the half-scores — no id selects in the merge, no id output
    arrays, no pool-id gather; the candidate column reconstructs from
    (pool position, embedded code). Packing perturbs values by
    ≤ |v|·2⁻¹⁵, absorbed into the certificate margin e_pack.
    """
    Q, d = x.shape
    quant = db_dtype == "int8"
    M = (y_q if quant else y_hi).shape[0]
    n_ch = T // _LANES
    packed = g * n_ch <= (1 << pbits)
    if quant:
        # the quantized-streaming contract (prepare_knn_index resolves
        # requests outside it down to bf16 BEFORE the core): packed
        # database-major kernels only, and the exact f32 rescore is
        # mandatory — lite int8 results would be exact w.r.t. ŷ, a
        # score function no caller asked for
        if not packed or grid_order not in ("db", "dbuf"):
            raise ValueError(
                "_knn_fused_core: db_dtype='int8' needs the packed "
                "database-major envelope (grid_order 'db'/'dbuf', "
                "g·(T/128) ≤ 2^pbits)")
        if not rescore or yp is None:
            raise ValueError(
                "_knn_fused_core: db_dtype='int8' requires the exact "
                "f32 rescore (store_yp=True) — returned ids are "
                "certified against the ORIGINAL rows, not ŷ")
        if y_q is None or y_scale_k is None or eq_groups is None:
            raise ValueError(
                "_knn_fused_core: db_dtype='int8' needs y_q, "
                "y_scale_k and eq_groups (prepare with "
                "_prepare_ops_q8)")

    xx = jnp.sum(x * x, axis=1, keepdims=True)                  # [Q,1] f32
    if metric == "ip":
        xx_r = jnp.zeros((Q, 1), jnp.float32)
    else:
        xx_r = xx
    # m_eff: the real-row count every mask uses — static m, or the
    # traced per-shard override (see the m_valid contract above). The
    # ragged rows_valid mode has no prefix count: m_eff covers the whole
    # slab (pads are hidden by the sentinel carrier + the mask gathers
    # below), and the unpacked kernels — which prefix-mask in-kernel —
    # are out of envelope.
    if rows_valid is not None:
        if not packed:
            raise ValueError(
                "_knn_fused_core: rows_valid (ragged mask) needs the "
                "packed kernel envelope (g·(T/128) ≤ 2^pbits) — the "
                "unpacked kernels mask by prefix count in-kernel")
        rows_valid = jnp.asarray(rows_valid, jnp.bool_).reshape(-1)
        m_eff = jnp.int32(M)
        m_real = jnp.full((1,), M, jnp.int32)
    else:
        m_eff = m if m_valid is None else \
            jnp.asarray(m_valid, jnp.int32).reshape(())
        m_real = (jnp.full((1,), m, jnp.int32) if m_valid is None
                  else jnp.reshape(m_eff, (1,)))

    if packed:
        if quant:
            kern = (fused_l2_group_topk_packed_db_q8
                    if grid_order == "db"
                    else fused_l2_group_topk_packed_dbuf_q8)
            kw = {"pbits": pbits,
                  "pair": passes == 1 and (T // _LANES) % 2 == 0}
        elif d > _D_SINGLE_SHOT:
            kern, kw = fused_l2_group_topk_packed_dchunk, {
                "dc": _DC, "pbits": pbits}
        elif grid_order in ("db", "dbuf"):
            # database-major: y streams from HBM once instead of nq
            # times (see GRID_ORDERS / the DATABASE-MAJOR block comment
            # in ops.fused_l2_topk_pallas); same outputs, codes and
            # certificate semantics, so everything downstream of the
            # kernel call is untouched
            kern = (fused_l2_group_topk_packed_db if grid_order == "db"
                    else fused_l2_group_topk_packed_dbuf)
            kw = {"pbits": pbits,
                  "pair": passes == 1 and (T // _LANES) % 2 == 0}
        else:
            # streamed chunk contraction (MXU/VPU co-issue — measured
            # p1 10.9→4.4 ms, p3 15.6→9.8 ms at 2048×1M×128); the pair
            # pre-reduction pays only in p1 (p3 is matmul-floor-bound)
            # and T/128 must be even for it
            kern = fused_l2_group_topk_packed
            kw = {"stream": True, "pbits": pbits,
                  "pair": passes == 1 and (T // _LANES) % 2 == 0}
        # the query half-norm rides INTO the kernel: packed values are
        # then d2/2 (l2) — small, so pack perturbation is relative to
        # the distances compared, not to the norm-dominated half-score
        # (measured at clustered 10M×256: the norm-scaled error failed
        # the certificate for ~80% of queries at pbits=11)
        xxh = 0.5 * xx if metric != "ip" else jnp.zeros_like(xx)
        if quant:
            a1p, a2p, a3p = kern(x, y_q, yyh_k, y_scale_k, m_real,
                                 T=T, Qb=Qb, passes=passes, tpg=g,
                                 xxh=xxh, **kw)
        else:
            a1p, a2p, a3p = kern(x, y_hi, y_lo, yyh_k, m_real, T=T,
                                 Qb=Qb, passes=passes, tpg=g, xxh=xxh,
                                 **kw)
        S_ = a1p.shape[1]
        # TWIN-POOL selection (round-3 redesign): top_k over a1p ONLY —
        # the XLA TopK measured ~2.5× superlinear in pool width inside
        # the composite program (14.8 ms at 7936 wide vs 3.8 at 3968) —
        # then pull each winner's a2p TWIN by position (the only a2
        # entries that can matter: a2 ≥ a1 elementwise, so an a2 whose
        # a1-twin lost to the C-th a1 value is itself ≥ that value),
        # and prune the 2C candidates back to C by kernel order.
        # Certificate terms per non-candidate class:
        #   a1 beyond top-C           ≥ C-th a1 value
        #   a2 twin of unselected a1  ≥ its a1 ≥ C-th a1 value
        #   pruned candidate          ≥ C-th pruned kernel value
        #   outside any bucket top-2  ≥ a3_min
        # Each term is ≥ the old whole-pool C-th value, so this bound
        # is ≥ the round-2 bound — fewer or equal fixups.
        # Ca MUST oversample beyond k: bound_a1 is the Ca-th smallest
        # bucket-min, and when the true top-k spread over k distinct
        # buckets the k-th bucket-min IS θ — with Ca = k the margin
        # check bound ≥ θ + err then fails for EVERY query (measured:
        # n_fail 2048/2048 at 10M×256, a 14 s full-fallback). The +pad
        # buys bound_a1 ≈ the (k+pad)-th neighbor value instead.
        Ca = min(k + _POOL_PAD, S_)
        # the envelope admits k up to 2·S_ (both twins of every bucket):
        # the pruned candidate count must cover k even when S_ < k+pad
        C = min(k + _POOL_PAD, 2 * Ca)
        # packed f32 order == value order (negation flips only the sign
        # bit, so codes survive the top_k round-trip)
        a1_sel, pos1 = _pool_smallest(a1p, Ca)
        a2_sel = jnp.take_along_axis(a2p, pos1, axis=1)
        cands = jnp.concatenate([a1_sel, a2_sel], axis=1)       # [Q, 2Ca]
        cpos = jnp.concatenate([pos1, pos1], axis=1)
        neg_top, sel = jax.lax.top_k(-cands, C)
        cand_p = -neg_top
        pos = jnp.take_along_axis(cpos, sel, axis=1)
        cand_pid = decode_packed_pool(cand_p, pos, S_, T, g, pbits)
        cand_v_hat = 2.0 * cand_p                       # = d2 (xx folded)
        bound_a1 = 2.0 * a1_sel[:, Ca - 1]
        a3_half_min = jnp.min(a3p, axis=1)
        a3_min = jnp.minimum(2.0 * a3_half_min, bound_a1)
        # packing error margin, PER QUERY from the actual magnitudes in
        # play: each compared value v = 2·half + xx carries
        # |Δv| ≤ 2·|half|·2^(pbits−23); bound and θ each contribute one
        # perturbed half, and the largest |half| among the used values
        # (candidate heads/tails, the a3 minimum, the Ca-th a1) bounds
        # both. ×2 for the two sides, ×2 safety. The round-2 formula
        # used the GLOBAL worst case (xx + 2·yymax)/2 — at clustered
        # 10M×256 scale that margin (~2× the true bound−θ gap) failed
        # the certificate for every query (measured).
        # SENTINEL terms are excluded from the magnitude: a pool with
        # fewer than C real rows (the mutable delta tail, tiny ragged
        # slabs) puts the 2^125 never-wins pad in the C-th/Ca-th slot,
        # and folding ITS magnitude into e_pack blew the margin to
        # ~2^105 — every query failed into the fixup. Sound because a
        # sentinel-valued term only ever appears inside bound's min()
        # — either it is discarded by a finite term whose perturbation
        # the finite magnitudes below already cover, or bound itself is
        # sentinel-scale and exceeds θ + err by ~2^100 even after its
        # own (≤ |v|·2^−10) perturbation.
        def _real_half(v):
            return jnp.where(v < _PACK_PAD * 0.25, jnp.abs(v), 0.0)

        # the θ-slot magnitude stays UNMASKED: lite-mode θ is a cleaned
        # packed value whose own perturbation must be covered, and the
        # ascending order no longer bounds it by the (masked) C-th
        # term. When the k-th slot IS a sentinel (< k real rows) the
        # blown margin just forces the fixup θ = inf forces anyway.
        half_mag = jnp.maximum(
            jnp.maximum(_real_half(cand_p[:, 0]),
                        _real_half(cand_p[:, C - 1])),
            jnp.maximum(
                jnp.maximum(_real_half(a3_half_min),
                            _real_half(a1_sel[:, Ca - 1])),
                jnp.abs(cand_p[:, k - 1])))
        e_pack = 8.0 * half_mag * 2.0 ** (pbits - 23)
    else:
        if d > _D_SINGLE_SHOT:
            a1, id1, a2, id2, a3 = fused_l2_group_topk_dchunk(
                x, y_hi, y_lo, yyh_k, m_real, T=T, Qb=Qb, passes=passes,
                tpg=g, dc=_DC)
        else:
            a1, id1, a2, id2, a3 = fused_l2_group_topk(
                x, y_hi, y_lo, yyh_k, m_real, T=T, Qb=Qb, passes=passes,
                tpg=g)
        # recover kernel-score space (d2 for l2, −x·y for ip); +inf
        # stays +inf, ids untouched
        a1 = 2.0 * a1 + xx_r
        a2 = 2.0 * a2 + xx_r
        pool_v = jnp.concatenate([a1, a2], axis=1)              # [Q, 2S']
        pool_id = jnp.concatenate([id1, id2], axis=1)
        C = min(k + _POOL_PAD, pool_v.shape[1])
        cand_v_hat, pos = _pool_smallest(pool_v, C)  # ascending
        cand_pid = jnp.take_along_axis(pool_id, pos, axis=1)    # point ids
        cand_pid = jnp.where(jnp.isfinite(cand_v_hat), cand_pid, -1)
        a3_min = 2.0 * jnp.min(a3, axis=1) + xx_r[:, 0]
        e_pack = jnp.zeros((Q,), jnp.float32)

    if rescore:
        if yp is None:
            raise ValueError("_knn_fused_core: rescore=True needs the "
                             "stored f32 index (prepare with "
                             "store_yp=True)")
        # exact f32 rescore of the C candidates (gather + HIGHEST
        # contraction; safe_pid is clamped to real rows, so gathering
        # from the row-padded yp returns identical data to the original
        # matrix)
        safe_pid = jnp.minimum(jnp.maximum(cand_pid, 0),
                               jnp.maximum(m_eff, 1) - 1)
        yc = jnp.take(yp, safe_pid, axis=0)                     # [Q, C, d]
        if metric == "ip":
            d2c = -jnp.einsum("qd,qcd->qc", x, yc,
                              precision=jax.lax.Precision.HIGHEST)
        else:
            d2c = (xx + jnp.sum(yc * yc, axis=2)
                   - 2.0 * jnp.einsum("qd,qcd->qc", x, yc,
                                      precision=jax.lax.Precision.HIGHEST))
            d2c = jnp.maximum(d2c, 0.0)
        d2c = jnp.where(cand_pid >= 0, d2c, jnp.inf)
        neg_k, ord_k = jax.lax.top_k(-d2c, k)
        vals = -neg_k                                           # exact, asc
        ids = jnp.take_along_axis(cand_pid, ord_k, axis=1)
    else:
        # LITE mode: the returned top-k is the exact top-k of the
        # KERNEL score function (bf16 for passes=1, bf16x3 for 3) —
        # candidates are already sorted ascending by kernel order, so
        # the head IS the result; values only need the embedded code
        # bits cleared (≤ |v|·2^(pbits−23) perturbation, 2⁻¹⁵..2⁻¹⁰
        # over the allowed pbits range — already inside the
        # e_pack certificate margin). No yp, no rescore gather: the
        # mode that serves f32-index-larger-than-HBM scales (10M×256).
        if packed:
            clean = jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(cand_p, jnp.int32)
                & ~((1 << pbits) - 1), jnp.float32)
            cand_v_clean = 2.0 * clean                  # = d2 (xx folded)
        else:
            cand_v_clean = cand_v_hat
        vals = cand_v_clean[:, :k]
        if metric != "ip":
            vals = jnp.maximum(vals, 0.0)
        vals = jnp.where(cand_pid[:, :k] >= 0, vals, jnp.inf)
        ids = cand_pid[:, :k]

    # ---- certificate ----
    theta = vals[:, k - 1]
    # every point outside its group's kept top-2 is ≥ that group's a3;
    # every pool entry not among the C candidates is ≥ the C-th pool value
    bound = jnp.minimum(a3_min, cand_v_hat[:, C - 1])
    if quant or passes == 3 or certify == "f32":
        # ONE margin construction for both f32-certified modes; only
        # the coefficient differs. certify="f32" at passes=1 is
        # ADAPTIVE PRECISION: θ is the exact-f32 k-th candidate value
        # (rescore mode) and every non-candidate's bf16 kernel score is
        # ≥ bound, hence its f32 score ≥ bound − E1; bound − E1 ≥ θ
        # proves the f32 top-k lives inside the exactly-rescored
        # candidate set, and margin failures pay the exact-f32 fixup.
        coeff = (_err_bound_coeff(d) if passes == 3
                 else _err_bound_coeff_p1(d))
        ymax = jnp.sqrt(jnp.max(yy_raw))   # finite norms (padded rows: 0)
        err = coeff * jnp.sqrt(xx[:, 0]) * ymax + e_pack
    else:
        err = e_pack
    if quant:
        # QUANTIZATION widening: kernel scores are exact-w.r.t.-ŷ (the
        # dequantized rows — their norms ride the carrier), so a
        # non-candidate j has d2(x, ŷ_j) ≥ bound − err. If its TRUE
        # d2(x, y_j) were < θ then ‖x − y_j‖ < √θ and
        # d2(x, ŷ_j) ≤ d2(x, y_j) + 2‖x−y_j‖‖e_j‖ + ‖e_j‖²
        #            < (√θ + Eq)², Eq = max_g eq_groups[g] —
        # so bound − err ≥ (√θ + Eq)² = θ + 2√θ·Eq + Eq² excludes every
        # violator. For IP the score is linear in y: |Δ| = |x·(ŷ−y)| ≤
        # ‖x‖·2·Eq (Eq bounds the HALVED stream operand ŷ/2).
        # The bf16 coeff·√xx·ymax term above covers the kernel-vs-ŷ
        # arithmetic error (y_q is exact in bf16, so the p1/p3 bounds —
        # which budget both factors rounding — safely envelope the
        # x-only rounding plus the post-matmul scale multiply).
        eq_max = jnp.max(eq_groups)
        if metric == "ip":
            err = err + 2.0 * jnp.sqrt(xx[:, 0]) * eq_max
        else:
            sq_theta = jnp.sqrt(jnp.maximum(theta, 0.0))
            err = err + 2.0 * sq_theta * eq_max + eq_max * eq_max
    certified = bound >= theta + err                            # [Q] bool
    failed = ~certified
    n_fail = jnp.sum(failed.astype(jnp.int32))
    # per-query certificate margin (pre-fixup): how much headroom the
    # certificate had — negative exactly where the fixup runs. Rides
    # out on the with_stats/_diag paths for the explain plane
    # (observability.explain); computed either way, so with_stats adds
    # one [Q] f32 output and zero extra compute.
    margin = bound - (theta + err)                              # [Q] f32

    # ---- fixup: exact sweep for failed queries ----
    # shape-aware tier ladder: only tiers whose [F, M] f32 tile fits
    # the budget are built (static — M is known at trace time). An
    # EMPTY ladder (M > ~65M rows) routes every failure to the
    # streamed full fallback — never a budget-busting tile
    fix_tiers = tuple(t for t in _FIXUP_TIERS
                      if t * M * 4 <= _FIXUP_TILE_BUDGET)

    def exact_rows(xq, cq):
        """Exact top-k for a [F, d] query block whose current candidate
        ids are ``cq`` [F, k], and whether its select overflowed
        (:func:`fixup_select`).

        rescore mode: f32 HIGHEST against the stored yp — exact w.r.t.
        f32 scores. Lite mode (yp is None): the SAME bf16(x3)
        contraction the kernel runs, against y_hi/y_lo — exact w.r.t.
        the kernel score function, which is what lite results are
        certified against.

        Small blocks materialize the whole [F, M] distance tile and
        select over it once, through the threshold block filter that
        ``cq`` bounds: MEASURED (v5e, 2048×1M×128) the old per-tile
        merge loop (489 sequential top_k's on [F, k+T]) cost ~90 ms —
        3× the entire rest of the pipeline — and ran on nearly every
        batch because the certificate fires for a handful of queries at
        production scale. Tile size is bounded by the ladder filter:
        fix_tiers[-1]·M·4 ≤ _FIXUP_TILE_BUDGET (≤ ~4 GB — e.g.
        [128, 1M] = 512 MB single-digit ms; [1024, 1M] = 4 GB, the
        certify="f32" deep-failure regime)."""
        F = xq.shape[0]
        xs = jnp.sum(xq * xq, axis=1)
        nt_dims = (((1,), (1,)), ((), ()))

        def scores(yt_f32, yt_hi, yt_lo, yy_seg):
            # rows [n, d] give [F, n]; rows blocked [n/B, B, d] give the
            # blocked view [n/B, F, B] (fixup_select)
            blocked = (yt_hi if yp is None else yt_f32).ndim == 3

            def dot(a, b, **kw):
                if blocked:
                    return jnp.einsum("fd,jbd->jfb", a, b, **kw)
                return jax.lax.dot_general(a, b, nt_dims, **kw)

            if yp is not None:
                s = dot(xq, yt_f32, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
            else:
                xhi = xq.astype(jnp.bfloat16)
                s = dot(xhi, yt_hi, preferred_element_type=jnp.float32)
                if passes == 3:
                    # barrier: XLA:TPU's bf16 pass folds the split
                    # (see split_hi_lo) — lo would collapse to ~0
                    xhi_b = jax.lax.optimization_barrier(xhi)
                    xlo = (xq - xhi_b.astype(jnp.float32)
                           ).astype(jnp.bfloat16)
                    s = s + dot(xhi, yt_lo,
                                preferred_element_type=jnp.float32)
                    s = s + dot(xlo, yt_hi,
                                preferred_element_type=jnp.float32)
            if metric == "ip":
                # lite operands are the hi/lo split of y/2 (the kernel
                # feeds them to the same scorer) — recover -x·y with
                # the ×2 the packed pipeline applies; the stored-yp
                # path contracts the full-scale y
                return -s if yp is not None else -2.0 * s
            if blocked:
                return jnp.maximum(
                    xs[None, :, None] + yy_seg[:, None, :] - 2.0 * s, 0.0)
            return jnp.maximum(
                xs[:, None] + yy_seg[None, :] - 2.0 * s, 0.0)

        if fix_tiers and F <= fix_tiers[-1]:
            # the index's stored norms are the f32 rows' own, except
            # int8's, which are the dequantized rows' (_prepare_ops_q8)
            yy_all = (jnp.sum(yp * yp, axis=1) if quant
                      else yy_raw[0])
            col_ok = (rows_valid if rows_valid is not None
                      else jnp.arange(M, dtype=jnp.int32) < m_eff)

            def tile(B=None):
                """The masked [F, M] tile, or with a block width B its
                blocked view [M/B, F, B] (fixup_select)."""
                def rows(a):
                    return (a if a is None or B is None
                            else a.reshape(M // B, B, *a.shape[1:]))

                d2 = scores(rows(yp), rows(y_hi), rows(y_lo),
                            rows(yy_all))
                ok = rows(col_ok)
                return jnp.where(ok[None, :] if B is None
                                 else ok[:, None, :], d2, jnp.inf)

            geometry = fixup_filter_geometry(M, k)
            if geometry is None:
                nt, ni = jax.lax.top_k(-tile(), k)
                return -nt, ni, jnp.int32(0)
            B, nb = geometry
            d2b = tile(B)
            sv, si, exact = fixup_select(d2b, cq, k, nb)
            fv, fi = jax.lax.cond(exact, lambda _: (sv, si),
                                  lambda d: blocked_top_k(d, k), d2b)
            return fv, fi, (~exact).astype(jnp.int32)

        # full-batch fallback: streamed per-tile merge (the [Q, M] tile
        # would not fit HBM); rare — needs > fix_tiers[-1] failures
        n_tiles = M // T

        def body(j, carry):
            bv, bi = carry
            if yp is not None:
                yt = jax.lax.dynamic_slice_in_dim(yp, j * T, T, axis=0)
                yth = ytl = None
                yy_seg = jnp.sum(yt * yt, axis=1)
            else:
                yt = None
                yth = jax.lax.dynamic_slice_in_dim(y_hi, j * T, T, axis=0)
                ytl = (jax.lax.dynamic_slice_in_dim(y_lo, j * T, T, axis=0)
                       if passes == 3 else None)
                yy_seg = jax.lax.dynamic_slice_in_dim(yy_raw[0], j * T, T)
            d2 = scores(yt, yth, ytl, yy_seg)
            col = j * T + jnp.arange(T, dtype=jnp.int32)
            col_ok = (jax.lax.dynamic_slice_in_dim(
                rows_valid, j * T, T)[None, :]
                if rows_valid is not None else col[None, :] < m_eff)
            d2 = jnp.where(col_ok, d2, jnp.inf)
            av = jnp.concatenate([bv, d2], axis=1)
            ai = jnp.concatenate(
                [bi, jnp.broadcast_to(col[None, :], d2.shape)], axis=1)
            nt, np_ = jax.lax.top_k(-av, k)
            return -nt, jnp.take_along_axis(ai, np_, axis=1)

        bv = jnp.full((F, k), jnp.inf, jnp.float32)
        bi = jnp.full((F, k), -1, jnp.int32)
        return (*jax.lax.fori_loop(0, n_tiles, body, (bv, bi)),
                jnp.int32(0))

    def no_fixup(operand):
        vals, ids = operand
        return vals, ids, jnp.int32(0)

    def make_fixup(F):
        def fixup(operand):
            vals, ids = operand
            _, fidx = jax.lax.top_k(failed.astype(jnp.int32), F)
            fv, fi, over = exact_rows(x[fidx], ids[fidx])
            # padded rows of fidx are healthy queries — recomputing them
            # exactly and writing back is harmless (same answer)
            return vals.at[fidx].set(fv), ids.at[fidx].set(fi), over
        return fixup

    def full_fallback(operand):
        return exact_rows(x, operand[1])

    if _diag:
        # measurement-only: the certified pipeline WITHOUT the fixup
        # cascade, plus the failure count and the certificate internals
        # (bound, θ, err) — the tests' oracle for WHY queries fail;
        # NOT a valid exactness contract
        return vals, ids, n_fail, bound, theta, err

    # tiered cascade: n_fail==0 → no-op; else the smallest tier that
    # covers n_fail; else the full fallback
    branch = full_fallback
    for t in [t for t in reversed(fix_tiers) if t < Q]:
        branch = (lambda op, t=t, nxt=branch: jax.lax.cond(
            n_fail <= t, make_fixup(t), nxt, op))
    vals, ids, n_over = jax.lax.cond(n_fail == 0, no_fixup, branch,
                                     (vals, ids))
    if with_stats:
        # ``with_stats``: the certificate-failure count rides out as a
        # third (scalar) output so the NON-jitted wrappers can report
        # fixup-rate telemetry host-side (observability.quality), the
        # PRE-FIXUP per-query margin as a fourth so the explain plane
        # can histogram it, and as a fifth 1 where the fixup's select
        # overflowed its block filter into the whole-tile select (the
        # count raft_tpu_cert_fixup_overflow_total sums) — two int32
        # scalars + one [Q] f32 per program, no extra compute, fixup
        # semantics untouched
        return vals, ids, n_fail, margin, n_over
    return vals, ids


def rescore_pool_width(k: int, S_pool: int, packed: bool) -> int:
    """The candidate-pool width C the core exact-rescores — the HOST
    mirror of the static pool geometry inside ``_knn_fused_core``
    (packed: twin-pool Ca oversample then prune to C; unpacked: one
    pick over the 2·S' concat pool). Quality telemetry reports it so
    q8 rescore pool widths are observable without re-deriving kernel
    geometry (observability.quality)."""
    if packed:
        ca = min(k + _POOL_PAD, S_pool)
        return min(k + _POOL_PAD, 2 * ca)
    return min(k + _POOL_PAD, 2 * S_pool)


def fixup_tiers_for(m_padded: int) -> Tuple[int, ...]:
    """The eligible static fixup tiers at a PREPARED (padded) row count
    — the host mirror of the ladder filter in ``_knn_fused_core``
    (a tier is eligible only while its [F, M] f32 tile fits the
    budget). Quality telemetry maps a drained failure count back to
    the tier that absorbed it (quality.fixup_tier_for)."""
    return tuple(t for t in _FIXUP_TIERS
                 if t * m_padded * 4 <= _FIXUP_TILE_BUDGET)


_TUNED = ...   # lazy sentinel: {passes: (T, Qb, g)} once loaded


def fit_config(T: int, Qb: int, d: int, passes: int,
               g: Optional[int] = None, grid_order: str = "query",
               db_dtype: str = "bf16"):
    """Scoped-VMEM guard: shrink (T, Qb) until the kernel footprint fits
    Mosaic's stack budget — a config over it is a guaranteed compile
    failure (observed: the tuned-at-passes=1 winner OOMs at passes=3).
    Shrinks Qb first (pure throughput knob), then T (weakens the
    certificate's slot count, so last). Shared by knn_fused and the
    measurement scripts so they can never profile a config production
    would silently shrink. (For grid_order="dbuf" the Qb loop is a
    no-op — its footprint prices the whole query batch — so the T loop
    carries the shrink.)"""
    budget = vmem_budget()
    while (footprint_for(T, Qb, d, passes, g, grid_order,
                         db_dtype) > budget and Qb > 8):
        Qb = max(8, (Qb // 2) // 8 * 8)
    while (footprint_for(T, Qb, d, passes, g, grid_order,
                         db_dtype) > budget and T > 2 * _LANES):
        T = max(2 * _LANES, (T // 2) // _LANES * _LANES)
    return T, Qb


def footprint_for(T: int, Qb: int, d: int, passes: int,
                  g: Optional[int] = None,
                  grid_order: str = "query",
                  db_dtype: str = "bf16") -> int:
    """Scoped-VMEM footprint of the fused kernel at a RAW (unpadded)
    feature width — applies the same d-padding / d-chunk routing AND
    packed-vs-unpacked kernel choice ``knn_fused`` itself uses, so
    callers (the tune sweep's skip predicate, the in-call shrink guard)
    can't diverge from it. ``g`` (tiles per group) decides the packed
    envelope; None assumes UNPACKED — the larger footprint, so an
    uninformed caller fails safe (over-shrinks) rather than shipping a
    Mosaic scoped-VMEM reject.

    ``grid_order`` routes to the database-major models; "dbuf" prices
    the worst-case padded query batch (_Q_CHUNK) instead of Qb, because
    its one-cell-per-group design holds the whole batch's fold state
    (the wrapper chunks queries at _Q_CHUNK, so that IS the bound)."""
    d_eff = d + (-d) % (_DC if d > _D_SINGLE_SHOT else _LANES)
    # the auto pack-width clamp makes any g ≤ 2^_PBITS_MAX codes
    # packed; the single-shot packed path is the STREAM kernel (no
    # [Qb, T] buffer)
    packed = g is not None and g * (T // _LANES) <= (1 << _PBITS_MAX)
    dchunk = d_eff > _D_SINGLE_SHOT
    if packed and not dchunk and grid_order in ("db", "dbuf"):
        q8 = db_dtype == "int8"
        kern = ("stream_db_q8" if q8 else "stream_db") \
            if grid_order == "db" \
            else ("stream_dbuf_q8" if q8 else "stream_dbuf")
        if grid_order == "dbuf":
            Qb = _Q_CHUNK
        return vmem_footprint(T, Qb, d_eff, passes, kernel=kern,
                              g=g or 16)
    kern = ("packed" if dchunk else "stream") if packed else "group"
    return vmem_footprint(T, Qb, d_eff, passes, dchunk=dchunk,
                          kernel=kern)


def resolve_grid_order(grid_order: str, d: int, packed: bool) -> str:
    """EFFECTIVE grid order for a call — decided (and logged) in the
    non-jitted wrapper, so a downgraded request is visible per call
    instead of silently mislabeling what ran. The
    database-major kernels are packed-only and single-shot-only
    (d ≤ _D_SINGLE_SHOT); anything outside that envelope runs the
    query-major pipeline."""
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"grid_order must be one of {GRID_ORDERS}, "
                         f"got {grid_order!r}")
    if grid_order == "query":
        return grid_order
    reason = None
    if d > _D_SINGLE_SHOT:
        reason = f"d={d} > {_D_SINGLE_SHOT} takes the d-chunked kernel"
    elif not packed:
        reason = "config is outside the packed-code envelope"
    if reason is None:
        return grid_order
    from raft_tpu.core.logger import log_warn

    log_warn("grid_order=%r outside the database-major envelope (%s) — "
             "using 'query' for this call", grid_order, reason)
    return "query"


def resolve_db_dtype(db_dtype: str, d: int, packed: bool,
                     grid_order: str, store_yp: bool = True) -> str:
    """EFFECTIVE database storage dtype for an index build — decided
    (and logged) in the non-jitted prepare path like
    :func:`resolve_grid_order`, so a downgraded request is visible per
    build instead of silently mislabeling what streams. int8 needs the
    packed database-major envelope (the quantized kernels exist for
    "db"/"dbuf" only) and the stored f32 rows for the mandatory exact
    rescore; requests outside it downgrade to "bf16" with a logged
    reason. A lite int8 index is an ERROR, not a downgrade — the
    caller asked for two contradictory contracts."""
    if db_dtype not in DB_DTYPES:
        raise ValueError(f"db_dtype must be one of {DB_DTYPES}, "
                         f"got {db_dtype!r}")
    if db_dtype == "bf16":
        return db_dtype
    if not store_yp:
        raise ValueError(
            "db_dtype='int8' requires store_yp=True: quantized results "
            "are certified by exact-rescoring candidates from the "
            "original f32 rows — a lite index has nothing to rescore "
            "from")
    reason = None
    if d > _D_SINGLE_SHOT:
        reason = f"d={d} > {_D_SINGLE_SHOT} takes the d-chunked kernel"
    elif not packed:
        reason = "config is outside the packed-code envelope"
    elif grid_order not in ("db", "dbuf"):
        reason = f"grid_order={grid_order!r} is not database-major"
    if reason is None:
        return db_dtype
    from raft_tpu.core.logger import log_warn

    log_warn("db_dtype='int8' outside the quantized-streaming envelope "
             "(%s) — storing bf16 for this index", reason)
    return "bf16"


def _valid_cfg(T, Qb, g, grid_order: str = "query") -> bool:
    # semantic validation, not just parseability: bad values would crash
    # every knn() call downstream; g = tiles-per-group ≥ 1
    return (T > 0 and T % _LANES == 0 and Qb > 0 and Qb % 8 == 0
            and 0 < g <= 4096 and grid_order in GRID_ORDERS)


class FusedConfig(Tuple[int, int, int, str]):
    """(T, Qb, g, grid_order) — the fused pipeline's tiling config."""

    __slots__ = ()

    def __new__(cls, T: int, Qb: int, g: int, grid_order: str = "query"):
        return tuple.__new__(cls, (T, Qb, g, grid_order))

    T = property(lambda s: s[0])
    Qb = property(lambda s: s[1])
    g = property(lambda s: s[2])
    grid_order = property(lambda s: s[3])


_BUILTIN_CONFIG = FusedConfig(2048, 256, 16, "query")


def _row_db_dtype(row) -> Optional[str]:
    """The row's database storage dtype: absent (schema ≤ 3 rows were
    all bf16-streamed) → "bf16"; an unknown value → None (the row is
    rejected with a logged reason — serving an int4 row nobody measured
    would route production to an unswept point)."""
    dt = row.get("db_dtype", "bf16")
    if dt not in DB_DTYPES:
        from raft_tpu.tune.fused import table_degraded

        table_degraded("fused", "row_rejected",
                       f"row db_dtype={dt!r} is not one of {DB_DTYPES}")
        return None
    return dt


def _row_config(row, d: Optional[int], passes: int) -> Optional[FusedConfig]:
    """A validated FusedConfig from one table row, or None. Beyond
    parseability, the config must (a) pass _valid_cfg and (b) survive
    fit_config UNshrunk at the table's feature width — a config the
    scoped-VMEM guard would shrink was never actually measured as
    written, so serving it would route production to an unswept point
    (the round-2 failure mode, now rejected at load instead of
    shipped)."""
    try:
        cfg = FusedConfig(int(row["T"]), int(row["Qb"]), int(row["g"]),
                          str(row.get("grid_order", "query")))
    except (KeyError, TypeError, ValueError):
        return None
    if not _valid_cfg(*cfg):
        return None
    db_dtype = _row_db_dtype(row)
    if db_dtype is None:
        return None
    if d is not None and fit_config(cfg.T, cfg.Qb, d, passes, cfg.g,
                                    cfg.grid_order,
                                    db_dtype) != (cfg.T, cfg.Qb):
        from raft_tpu.tune.fused import table_degraded

        table_degraded(
            "fused", "row_rejected",
            f"row (T={cfg.T}, Qb={cfg.Qb}, g={cfg.g}, "
            f"{cfg.grid_order}, passes={passes}, {db_dtype}) fails "
            f"the scoped-VMEM fit at d={d}")
        return None
    return cfg


def _load_tuned() -> dict:
    """Parse + validate the tune table → {passes: FusedConfig}. Any
    corrupt, stale or future-schema table degrades to {} (built-in
    defaults) with a logged reason — it must never break knn. Every
    degraded load is counted under ``tune.table_degraded{table=fused,
    reason=...}`` (WARN once per process — see
    :func:`raft_tpu.tune.fused.table_degraded`); the read carries the
    ``tune_table_read`` fault site so a torn/corrupt table is
    injectable."""
    import json
    import os

    from raft_tpu.core.logger import log_info
    from raft_tpu.native import _REPO_ROOT
    from raft_tpu.tune.fused import (TUNE_SCHEMA_VERSION, table_degraded,
                                     validate_tune_table)

    path_env = os.environ.get("RAFT_TPU_TUNE_FUSED")
    path = path_env or os.path.join(_REPO_ROOT, "TUNE_FUSED.json")
    if fault_point("tune_table_read") == "corrupt":
        table_degraded("fused", "unreadable",
                       f"{path}: injected corrupt table read")
        return {}
    tuned: dict = {}
    try:
        with open(path) as f:
            tbl = json.load(f)
    except FileNotFoundError:
        if path_env:   # an explicitly-named table that is absent IS
            #            a degradation; the default path missing is
            #            just the untuned state
            table_degraded("fused", "missing", path)
        return {}
    except Exception as e:
        table_degraded("fused", "unreadable",
                       f"{path}: {type(e).__name__}: {e}")
        return {}
    try:
        errors = validate_tune_table(tbl)
        if errors:
            table_degraded("fused", "invalid",
                           f"{path}: " + "; ".join(errors))
            return {}
        if int(tbl.get("schema", 1)) > TUNE_SCHEMA_VERSION:
            table_degraded(
                "fused", "future_schema",
                f"{path}: schema {tbl.get('schema')} (this build "
                f"understands ≤ {TUNE_SCHEMA_VERSION})")
            return {}
        shape = tbl.get("shape")
        d = (int(shape[2]) if isinstance(shape, (list, tuple))
             and len(shape) >= 3 else None)
        # per-(passes, db_dtype) winners from the measured rows; the
        # legacy single "best" entry seeds any mode its passes matches
        # (or both, for tables that never recorded passes). Rows
        # without a db_dtype (every schema ≤ 3 table, incl. the
        # committed measured v5e one) are bf16 — that loading stays
        # byte-identical to the schema-3 behavior.
        for row in sorted((r for r in tbl.get("rows", [])
                           if "seconds" in r),
                          key=lambda r: r["seconds"], reverse=True):
            p = int(row.get("passes", 0)) or None
            dt = _row_db_dtype(row)
            cfg = _row_config(row, d, p or 3)
            if cfg is not None and dt is not None:
                tuned[(p, dt)] = cfg
        # explicit winners: schema ≥ 4 keys "passes:db_dtype", schema 3
        # keys bare "passes" (bf16); both take precedence over the
        # legacy single "best"
        best_by = dict(tbl.get("best_by_passes") or {})
        best_by.update(tbl.get("best_by_passes_dtype") or {})
        for key_str, row in best_by.items():
            try:
                p_str, _, dt_str = str(key_str).partition(":")
                p = int(p_str)
            except (TypeError, ValueError):
                continue
            dt = dt_str or _row_db_dtype(row)
            if dt not in DB_DTYPES:
                continue
            cfg = _row_config(row, d, p)
            if cfg is not None:
                tuned.setdefault((p, dt), cfg)
        best = tbl.get("best")
        if best:
            dt = _row_db_dtype(best)
            for p in (1, 3):
                if dt is not None and int(best.get("passes", p)) == p:
                    cfg = _row_config(best, d, p)
                    if cfg is not None:
                        tuned.setdefault((p, dt), cfg)
        prov = tbl.get("provenance", {})
        log_info("fused_defaults: loaded %s (schema %s, chip=%s, "
                 "commit=%s, measured=%s, written=%s)", path,
                 tbl.get("schema", "legacy"),
                 prov.get("chip", "unknown"),
                 prov.get("git_commit", "unknown"),
                 prov.get("measured", "unknown"),
                 prov.get("timestamp", "unknown"))
    except Exception:
        return {}  # malformed table must never break knn
    return tuned


def fused_config(passes: int = 3, db_dtype: str = "bf16") -> FusedConfig:
    """(T, Qb, g, grid_order) for the fused pipeline: the measured-best
    point from ``TUNE_FUSED.json`` (produced by the
    :mod:`raft_tpu.tune` autotuner — the analog of the reference's
    fitted select_k heuristic) when one is committed, else the
    hand-chosen defaults. The table is schema-validated and its rows
    re-checked against the scoped-VMEM fit at load; a corrupt/stale/
    future table degrades to the built-ins with a logged reason.

    Best rows are keyed by ``passes``: the score-tile VMEM footprint
    differs ~2× between the modes (see ops.fused_l2_topk_pallas.
    vmem_footprint), so the passes=1 winner can be a passes=3 compile
    failure — round 2's driver bench hit exactly that. ``passes`` itself
    is never taken from the table — it is an exactness contract, not a
    tuning knob."""
    global _TUNED
    if _TUNED is ...:
        _TUNED = _load_tuned()
    hit = (_TUNED.get((passes, db_dtype))
           or _TUNED.get((None, db_dtype)))
    if hit is not None:
        return hit
    if db_dtype != "bf16":
        # no tuned int8 row yet: start from the bf16 winner's geometry
        # (the stream-once shape is the same; only the y byte width
        # changed), forcing a database-major order — "query" has no
        # quantized kernel to run
        base = fused_config(passes, "bf16")
        if base.grid_order == "query":
            return FusedConfig(base.T, base.Qb, base.g, "db")
        return base
    return _BUILTIN_CONFIG


def fused_defaults(passes: int = 3) -> Tuple[int, int, int]:
    """(T, Qb, g) — :func:`fused_config` without the grid order (the
    historical surface; callers that route kernels want fused_config)."""
    return tuple(fused_config(passes)[:3])


def fused_eligible(n_rows: int, d: int) -> bool:
    """THE fused-pipeline eligibility gate (backend + shape envelope),
    shared by knn()'s auto-routing and models.NearestNeighbors.fit's
    prepare decision — one predicate, no drifting copies."""
    return (jax.default_backend() == "tpu"
            and n_rows >= 4096 and d <= 4096)


class KnnIndex:
    """Prepared fused-KNN index: the index-side operands (row/feature
    padding, bf16 hi/lo split, norms + sentinel carrier — ~3 ms at
    1M×128 on v5e) computed ONCE at build time, the build/query split
    of the reference ecosystem's index objects. Build with
    :func:`prepare_knn_index`; query via ``knn_fused(x, index)`` or
    ``distance.knn(res, index, queries, ...)``. The tiling config and
    metric are frozen at build time."""

    def __init__(self, yp, y_hi, y_lo, yyh_k, yy_raw, n_rows: int,
                 T: int, Qb: int, g: int, passes: int, metric: str,
                 d_orig: int, pbits: int = _PACK_BITS,
                 grid_order: str = "query", db_dtype: str = "bf16",
                 y_q=None, y_scale_k=None, eq_groups=None,
                 rows_valid=None, ids=None):
        # yp is the ROW-PADDED index; the original matrix is yp[:n_rows]
        # (NOT stored separately — at 1M×128 that would pin a redundant
        # ~512 MB f32 copy in HBM for the index lifetime)
        self.yp = yp
        self.y_hi, self.y_lo = y_hi, y_lo
        self.yyh_k, self.yy_raw = yyh_k, yy_raw
        self.n_rows = n_rows
        self.T, self.Qb, self.g = T, Qb, g
        self.passes, self.metric = passes, metric
        self.d_orig = d_orig
        self.pbits = pbits
        # frozen at build: database-major indexes are row-padded to
        # whole [g·T] groups, so the grid order cannot change per query
        self.grid_order = grid_order
        # quantized-streaming state (db_dtype="int8"): the int8 slab
        # the kernel streams, the group-scale tile, and the per-group
        # quantization bound Eq the certificate is widened by; y_hi /
        # y_lo are None (nothing bf16 is streamed — the HBM win)
        self.db_dtype = db_dtype
        self.y_q = y_q
        self.y_scale_k = y_scale_k
        self.eq_groups = eq_groups
        # RAGGED layout state (built from an IndexLayout / rows_valid):
        # the live-row mask over the PREPARED slab (pads may be
        # interspersed anywhere — the PR-8 never-wins sentinel path)
        # and the slab-position → global-id map queries decode through
        self.rows_valid = rows_valid
        self.ids = ids

    @property
    def stream_width(self) -> int:
        """Feature width of the operand the kernel streams (row-padded
        d) — the shape queries must be padded to."""
        src = self.y_q if self.db_dtype == "int8" else self.y_hi
        return src.shape[1]


@instrument("distance.prepare_knn_index")
def prepare_knn_index(y, passes: int = 3, metric: str = "l2",
                      T: Optional[int] = None, Qb: Optional[int] = None,
                      g: Optional[int] = None,
                      store_yp: bool = True,
                      grid_order: Optional[str] = None,
                      db_dtype: str = "bf16",
                      rows_valid=None, ids=None) -> KnnIndex:
    """Build a :class:`KnnIndex` for repeated queries against ``y``.

    ``store_yp=False`` builds a LITE index: the f32 row-padded matrix
    (and, for passes=1, the unused bf16 lo split) is dropped, ~3×
    smaller HBM residency — the only index kind that fits f32-larger-
    than-HBM scales (10M×256 ≈ 10 GB f32 vs ~5.5 GB lite). Queries
    against a lite index run ``rescore=False``: results are the exact
    top-k of the KERNEL score function (bf16 / bf16x3), values within
    2^(pbits−23) relative of those scores (2⁻¹⁵ at the minimum pack
    width, up to 2⁻¹⁰ at the auto-pack maximum pbits=13).

    ``db_dtype="int8"`` (:data:`DB_DTYPES`) packs the STREAMED slab
    int8 with per-certificate-group symmetric scales: the kernel
    streams M·d·1 bytes instead of bf16's M·d·2(·2), the twin-pool
    certificate is widened by the recorded per-group bound Eq, and
    candidates are exact-rescored in f32 from the original rows —
    returned ids are certified identical to the f32 oracle's.
    Requires ``store_yp=True``; requests outside the packed
    database-major envelope downgrade to bf16 with a logged reason
    (RAFT_TPU_DB_DTYPE env sets the fleet-wide default at call sites
    that pass none — see the serving engine).

    ``y`` may also be an :class:`~raft_tpu.mutable.layout.IndexLayout`
    — the explicit slab struct the mutable subsystem shares with the
    IVF plane — in which case its slab/ids/``rows_valid`` drive a
    RAGGED build: pads (and tombstones) may be interspersed anywhere,
    carried through the PR-8 never-wins sentinel path, and queries
    decode slab positions back through ``ids``. Ragged builds force
    the packed-code envelope (the unpacked kernels mask by prefix
    count only). ``rows_valid``/``ids`` may equally be passed
    directly with a raw matrix."""
    try:
        from raft_tpu.mutable.layout import IndexLayout

        if isinstance(y, IndexLayout):
            rows_valid = y.rows_valid if rows_valid is None else rows_valid
            ids = y.ids if ids is None else ids
            y = y.slab
    except ImportError:
        pass
    if metric not in ("l2", "ip"):
        raise ValueError(f"prepare_knn_index: metric must be 'l2' or "
                         f"'ip', got {metric!r}")
    if db_dtype not in DB_DTYPES:
        raise ValueError(f"prepare_knn_index: db_dtype must be one of "
                         f"{DB_DTYPES}, got {db_dtype!r}")
    y = jnp.asarray(y, jnp.float32)
    m, d = y.shape
    dcfg = fused_config(passes, db_dtype)
    T = dcfg.T if T is None else T
    Qb = dcfg.Qb if Qb is None else Qb
    grid_order = dcfg.grid_order if grid_order is None else grid_order
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"prepare_knn_index: grid_order must be one of "
                         f"{GRID_ORDERS}, got {grid_order!r}")
    if db_dtype == "int8" and grid_order == "query":
        # the quantized kernels are database-major; an int8 request on
        # a query-major (tuned or explicit) geometry takes the
        # stream-once order — that is the configuration the dtype
        # exists to accelerate
        grid_order = "db"
    T, Qb = fit_config(T, Qb, d, passes, g or dcfg.g, grid_order,
                       db_dtype)
    n_tiles_est = max(1, -(-m // T))
    if g is None:
        g = max(dcfg.g, (1 << auto_pack_bits(n_tiles_est, T))
                // (T // _LANES))
    # codes beyond 13 bits would perturb values past the margins the
    # certificate budgets for — such a g simply routes to the UNPACKED
    # kernel (g·n_ch > 2^pbits ⇒ packed=False, +inf sentinels), the
    # same fallback the core and _prepare_ops agree on
    pbits = min(_PBITS_MAX, max(_PACK_BITS, int(math.ceil(math.log2(
        max(g * (T // _LANES), 2))))))
    if rows_valid is not None and g * (T // _LANES) > (1 << pbits):
        # the ragged mask rides the packed sentinel carrier only — the
        # unpacked kernels prefix-mask in-kernel and cannot honor it
        g = max(1, (1 << pbits) // (T // _LANES))
    # the database-major kernels are packed-only/single-shot-only:
    # resolve the EFFECTIVE order now so the index rows are padded for
    # the kernel that will actually run (a db-padded index serves the
    # query-major kernel fine, but not vice versa)
    packed = g * (T // _LANES) <= (1 << pbits)
    grid_order = resolve_grid_order(grid_order, d, packed)
    db_dtype = resolve_db_dtype(db_dtype, d, packed, grid_order,
                                store_yp)
    dpad = (-d) % (_DC if d > _D_SINGLE_SHOT else _LANES)
    if dpad:
        y = jnp.concatenate([y, jnp.zeros((m, dpad), jnp.float32)], axis=1)
    rv_in = (None if rows_valid is None
             else jnp.asarray(rows_valid, jnp.bool_).reshape(-1))

    def _ragged_state(M: int):
        """(rows_valid, ids) padded to the PREPARED row count M."""
        if rv_in is None:
            return None, None
        rv = rv_in
        if M > rv.shape[0]:
            rv = jnp.concatenate(
                [rv, jnp.zeros((M - rv.shape[0],), jnp.bool_)])
        id_map = None
        if ids is not None:
            id_map = jnp.asarray(ids, jnp.int32).reshape(-1)
            if M > id_map.shape[0]:
                id_map = jnp.concatenate(
                    [id_map,
                     jnp.full((M - id_map.shape[0],), -1, jnp.int32)])
        return rv, id_map

    if db_dtype == "int8":
        fault_point("quantize_index")
        yp, y_q, scale_k, yyh_k, yy_raw, eq = _prepare_ops_q8(
            y, T, g, metric, pbits=pbits, grid_order=grid_order,
            rows_valid=rv_in)
        try:
            from raft_tpu.core.resources import ensure_resources
            from raft_tpu.observability.timeline import emit_marker

            emit_marker("quantize_index", n_rows=m, d=d,
                        n_groups=int(eq.shape[0]),
                        eq_max=float(jnp.max(eq)),
                        db_dtype=db_dtype)
            ensure_resources(None).profiler.capture_fn(
                "distance.quantize_index", _prepare_ops_q8, y, T, g,
                metric, pbits=pbits, grid_order=grid_order)
        except Exception:
            pass
        rv, id_map = _ragged_state(yp.shape[0])
        return KnnIndex(yp, None, None, yyh_k, yy_raw, m, T, Qb, g,
                        passes, metric, d, pbits=pbits,
                        grid_order=grid_order, db_dtype="int8",
                        y_q=y_q, y_scale_k=scale_k, eq_groups=eq,
                        rows_valid=rv, ids=id_map)
    yp, y_hi, y_lo, yyh_k, yy_raw = _prepare_ops(y, T, g, metric,
                                                 pbits=pbits,
                                                 grid_order=grid_order,
                                                 rows_valid=rv_in)
    rv, id_map = _ragged_state(yp.shape[0])
    if not store_yp:
        yp = None
        if passes == 1:
            y_lo = None    # the 1-pass kernel and lite fixup never read it
    return KnnIndex(yp, y_hi, y_lo, yyh_k, yy_raw, m, T, Qb, g, passes,
                    metric, d, pbits=pbits, grid_order=grid_order,
                    rows_valid=rv, ids=id_map)


@instrument("distance.knn_fused")
def knn_fused(x, y, k: int, passes: int = 3,
              T: Optional[int] = None, Qb: Optional[int] = None,
              g: Optional[int] = None, metric: str = "l2",
              rescore: Optional[bool] = None, certify: str = "kernel",
              grid_order: Optional[str] = None,
              db_dtype: Optional[str] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Certified fused brute-force KNN.

    ``y`` may be a raw [m, d] index matrix (operands prepared inline per
    call) or a :class:`KnnIndex` (prepared once — preferred for repeated
    query batches; its frozen T/Qb/g/passes/metric override the
    corresponding arguments).

    ``rescore`` — None (default) rescores exactly in f32 when the index
    stores yp (regular indexes) and falls back to lite results on a
    ``store_yp=False`` index; True forces rescoring (error on a lite
    index); False forces lite results (exact top-k of the kernel score
    function, values within 2^(pbits−23) of those scores — 2⁻¹⁵..2⁻¹⁰
    over the allowed pbits range).

    ``metric="l2"`` (default): (d2 [Q, k] f32 exact ascending, ids).
    ``metric="ip"``: (scores = x·y [Q, k] f32 exact DESCENDING, ids) —
    the same kernel fed zeros for the norm terms and y/2 operands (see
    _knn_fused_core). ``passes=3`` is certified-exact w.r.t. f32 scores;
    ``passes=1`` trades that for ~3× contraction speed (exact w.r.t.
    bf16 scores). ``T``/``Qb``/``g`` default to :func:`fused_defaults`
    (measured-best when a tuning table is committed); ``g`` is the
    number of consecutive index tiles folded into one certificate
    group inside the kernel (tpg), so the candidate pool holds
    ``2 · ceil(n_tiles/g) · 128`` entries.

    ``certify="f32"`` (ADAPTIVE PRECISION, passes=1 + rescore only):
    p1 kernel cost with the p3 guarantee — the certificate margin is
    widened by the one-pass bf16 error bound (_err_bound_coeff_p1), so
    certified queries are provably exact w.r.t. f32 scores and only
    margin failures pay the exact-f32 fixup. At passes=3 it is a no-op
    (p3 is already f32-certified).

    ``grid_order`` selects the kernel's grid iteration order (see
    :data:`GRID_ORDERS`): "query" re-fetches the database per query
    block; "db"/"dbuf" stream it from HBM ~once (the round-6 roofline
    work). None takes the tuned default; requests outside the
    database-major envelope (unpacked configs, d > 512) downgrade to
    "query" with a logged reason. A :class:`KnnIndex` freezes the
    order at build time.
    """
    fault_point("knn_fused")
    idx: Optional[KnnIndex] = y if isinstance(y, KnnIndex) else None
    if idx is not None:
        T, Qb, g = idx.T, idx.Qb, idx.g
        passes, metric = idx.passes, idx.metric
        m, d = idx.n_rows, idx.d_orig
        grid_order = idx.grid_order
        db_dtype = idx.db_dtype
    elif db_dtype is None:
        db_dtype = "bf16"
    if db_dtype not in DB_DTYPES:
        raise ValueError(f"knn_fused: db_dtype must be one of "
                         f"{DB_DTYPES}, got {db_dtype!r}")
    if metric not in ("l2", "ip"):
        raise ValueError(f"knn_fused: metric must be 'l2' or 'ip', "
                         f"got {metric!r}")
    if certify not in ("kernel", "f32"):
        raise ValueError(f"knn_fused: certify must be 'kernel' or "
                         f"'f32', got {certify!r}")
    if certify == "f32" and rescore is False:
        raise ValueError("knn_fused: certify='f32' needs the exact "
                         "rescore (θ must be an f32 value) — a lite "
                         "index cannot carry the f32 certificate")
    if passes == 3:
        certify = "kernel"   # p3 is already f32-certified — normalize
        #                      so the static arg doesn't fork the jit
        #                      cache with an identical program
    x = jnp.asarray(x, jnp.float32)
    Q, d_x = x.shape
    if idx is None:
        y = jnp.asarray(y, jnp.float32)
        m, d = y.shape
        dcfg = fused_config(passes, db_dtype)
        T = dcfg.T if T is None else T
        Qb = dcfg.Qb if Qb is None else Qb
        g = dcfg.g if g is None else g
        grid_order = dcfg.grid_order if grid_order is None else grid_order
        if grid_order not in GRID_ORDERS:
            raise ValueError(f"knn_fused: grid_order must be one of "
                             f"{GRID_ORDERS}, got {grid_order!r}")
        T, Qb = fit_config(T, Qb, d, passes, g, grid_order, db_dtype)
    if d_x != d:
        raise ValueError(f"knn_fused: query width {d_x} != index {d}")
    if k > m:
        raise ValueError(f"knn_fused: k={k} > index size {m}")
    if Q == 0:
        return (jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    if g < 1:
        raise ValueError(f"knn_fused: g={g} must be ≥ 1 (tiles per group)")
    # the group fold iterates T // 128 lane-chunks and the carriers
    # reshape Qb // 8 — a non-multiple T would silently skip the tail
    # columns (no pool entry AND no certificate coverage)
    if T % _LANES:
        raise ValueError(f"knn_fused: T={T} must be a multiple of {_LANES}")
    if Qb % 8:
        raise ValueError(f"knn_fused: Qb={Qb} must be a multiple of 8")
    n_tiles = (max(m, T) + T - 1) // T
    pool = 2 * (-(-n_tiles // g)) * _LANES
    if k > pool:
        raise NotImplementedError(
            f"knn_fused: k={k} too large for pool size {pool} "
            f"(shrink g or T, or use the streamed path)")
    if Q > _Q_CHUNK:
        # bound the [Q, S] slot arrays / rescore gather: chunk the
        # queries (prepare once so chunks share the index operands)
        if idx is None:
            idx = prepare_knn_index(y, passes=passes, metric=metric,
                                    T=T, Qb=Qb, g=g,
                                    grid_order=grid_order,
                                    db_dtype=db_dtype)
        outs = [knn_fused(x[s:s + _Q_CHUNK], idx, k, rescore=rescore,
                          certify=certify)
                for s in range(0, Q, _Q_CHUNK)]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))
    # pad query feature dim to the index's padded width, queries to the
    # block size
    if idx is None:
        idx = prepare_knn_index(y, passes=passes, metric=metric,
                                T=T, Qb=Qb, g=g, grid_order=grid_order,
                                db_dtype=db_dtype)
    # the EFFECTIVE order/dtype (prepare resolves the database-major
    # and quantized envelopes and pads the index rows accordingly)
    grid_order = idx.grid_order
    db_dtype = idx.db_dtype
    dpad = idx.stream_width - d
    if dpad:
        x = jnp.concatenate(
            [x, jnp.zeros((Q, dpad), jnp.float32)], axis=1)
    Qb = min(Qb, ((Q + 7) // 8) * 8)
    qpad = (-Q) % Qb
    if qpad:
        x = jnp.concatenate([x, jnp.zeros((qpad, x.shape[1]), x.dtype)])
    if rescore is None:
        rescore = idx.yp is not None
    if certify == "f32" and not rescore:
        raise ValueError("knn_fused: certify='f32' needs a yp-storing "
                         "index (store_yp=True) for the exact rescore")
    if db_dtype == "int8" and not rescore:
        raise ValueError("knn_fused: an int8-streamed index is always "
                         "exact-rescored (rescore=False would return "
                         "top-k of the QUANTIZED score function)")
    # the core's static pool geometry reproduced exactly
    # (S' = ceil(n_tiles/g)·128), for the rescore pool width below
    S_pool = -(-n_tiles // g) * _LANES
    packed_env = g * (T // _LANES) <= (1 << idx.pbits)
    vals, ids, n_fail, margin, n_over = _knn_fused_core(
        x, idx.yp, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw,
        k=k, T=T, Qb=Qb, g=g, passes=passes, metric=metric, m=m,
        rescore=rescore, pbits=idx.pbits, certify=certify,
        grid_order=grid_order,
        db_dtype=db_dtype, with_stats=True, y_q=idx.y_q,
        y_scale_k=idx.y_scale_k, eq_groups=idx.eq_groups,
        rows_valid=idx.rows_valid)
    # certificate/fixup telemetry: the failure count is a device scalar
    # — queue it UNRESOLVED (quality.drain() converts later, after the
    # program's results have been consumed; no sync on this path).
    # The margin likewise stays a device-array REFERENCE: the explain
    # plane resolves it at finalize (post-response-sync) or drops it
    # unreferenced when no capture is active.
    try:
        from raft_tpu.observability import explain
        from raft_tpu.observability.quality import record_pending

        record_pending(
            "distance.knn_fused", n_fail, n_queries=x.shape[0],
            pool_width=rescore_pool_width(k, S_pool, packed_env),
            fix_tiers=fixup_tiers_for(idx.yyh_k.shape[1]),
            n_over=n_over, db_dtype=db_dtype, passes=passes,
            certify=certify)
        if explain.active() is not None:
            # pad rows carry vacuous margins — slice them off (the
            # slice dispatch only happens under an active capture)
            explain.note_margin("distance.knn_fused",
                                margin[:Q] if qpad else margin)
    except Exception:
        pass
    if vals.shape[0] != Q:
        vals, ids = vals[:Q], ids[:Q]
    # else: identity slices would still cost an eager dispatch each
    # — skip when Q needed no pad
    if idx.ids is not None:
        # ragged-layout index: slab positions decode to global ids;
        # non-finite rows (fewer live rows than k) carry raw columns
        # out of the fixup's unmasked top_k — sentinel them to −1
        ids = jnp.where((ids >= 0) & jnp.isfinite(vals),
                        jnp.take(idx.ids, jnp.maximum(ids, 0)), -1)
    if metric == "ip":
        return -vals, ids           # internal −x·y ascending → IP desc
    return vals, ids
