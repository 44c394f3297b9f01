"""Fused L2 distance + slotted top-k candidate kernel (Pallas/Mosaic).

The TPU rendering of the reference's fused distance→select pipeline:
(ref: cpp/include/raft/matrix/detail/select_radix.cuh:639 radix_kernel,
select_warpsort.cuh:752 warpsort queues, and the tiling substrate
cpp/include/raft/linalg/detail/contractions.cuh:1 — the role "distance
tiles are consumed by the selector without round-tripping global memory").

Design (TPU-first, not a translation):

- Grid ``(n_query_blocks, n_tiles)``; the index tile loop is the inner,
  sequential grid dimension, so VMEM-revisited output blocks accumulate
  across tiles (the Mosaic idiom replacing CUDA's global-memory atomics).
- Each cell contracts ``X_block[Qb,d] @ Y_tile[T,d]ᵀ`` on the MXU in
  bfloat16 (1 pass, ``passes=1``) or with a hi/lo bf16 split
  (``passes=3``: hi·hi + hi·lo + lo·hi — f32-grade accuracy at 3× bf16
  cost, the TPU replacement for fp32 SGEMM), then forms
  ``d2 = xx + yy − 2S`` with exact f32 norm corrections.
- The [Qb, T] distance tile NEVER leaves VMEM. It is folded lane-chunk by
  lane-chunk into per-slot running (min, argmin, 2nd-min) — a "slot" is a
  (tile, lane-class) bucket; the fold is pure VPU compare/selects, the
  scan-free replacement for warp-shuffle insertion sorts.
- Outputs: per-slot min ``m1 [Q, S]`` + its index ``i1 [Q, S]``, plus a
  per-query running min over slots of the slot 2nd-min (``m2min [Q, LANES]``
  — folded over tiles in-place). ``m2min`` powers the EXACTNESS
  CERTIFICATE in raft_tpu.distance.knn_fused: every non-candidate point is
  ≥ its slot's 2nd-min, so ``min_slots m2 ≥ θ`` proves the candidate top-k
  is the true top-k (see knn_fused for the fixup path when it fails).

Padded index rows are masked to +inf inside the kernel (the caller passes
the real row count); padded rows therefore never pollute slots.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.utils import interpret_mode

_LANES = 128

_PACK_BITS = 8                   # default code width; kernels take the
                                 # actual width as the ``pbits`` static
                                 # (more codes = wider groups = narrower
                                 # pool, at 2^(pbits-23) value error)
_PACK_MASK = (1 << _PACK_BITS) - 1
_PBITS_MAX = 13                  # widest allowed codes: value error
                                 # 2^(13-23) must stay under the
                                 # certificate margins (ONE definition —
                                 # auto_pack_bits, prepare_knn_index and
                                 # footprint_for all consume this)
_PACK_PAD = float(2.0 ** 125)    # finite "never wins" sentinel


# Mosaic's scoped-VMEM stack limit on current TPU generations (the
# compiler rejects kernels whose live VMEM exceeds it); budget leaves
# headroom for temporaries the estimator can't see.
VMEM_LIMIT = 16 * 2 ** 20
VMEM_BUDGET = 15 * 2 ** 20


def vmem_budget() -> int:
    """The scoped-VMEM fit budget ``fit_config``/``footprint_for``
    validate against. ``RAFT_TPU_VMEM_BUDGET_MB`` (env) overrides the
    built-in :data:`VMEM_BUDGET` — the derate knob for a generation
    whose Mosaic limit differs from the calibrated v5e one, or for
    operators who keep hitting real compile OOMs at configs the model
    passes (the footprint factors are estimates; shrinking the budget
    makes every fit predicate — production routing, the tune sweeps'
    pruning, and the resilience degradation ladder's rung validation —
    conservative in one place)."""
    raw = os.environ.get("RAFT_TPU_VMEM_BUDGET_MB")
    if raw:
        try:
            return int(float(raw) * (1 << 20))
        except ValueError:
            pass
    return VMEM_BUDGET


def vmem_footprint(T: int, Qb: int, d: int, passes: int,
                   dchunk: bool = False, kernel: str = "group",
                   g: int = 16) -> int:
    """Estimated scoped-VMEM bytes of one fused-kernel grid cell.

    Calibrated against measured Mosaic compiles/rejections on v5e:
    - slot kernel: (T=2048, Qb=1024, d=128, p3) rejected at 20.35 MB
      vs the 16 MB limit; same shape at p1 compiled; (4096, 512, p3)
      rejected. Model: [Qb, T] f32 score tile × ~1.25 (p1) / ~2.25 (p3)
      live copies incl. the col-iota mask temporaries.
    - group kernel (production): (2048, 512, d=128, p1) rejected at
      16.36 MB WITH in-kernel masking; masking is since removed (yy
      carries +inf — two fewer [Qb, T] buffers) but the in-kernel merge
      holds more fold state, so its factors stay higher than the slot
      kernel's: ~2.2 (p1) / ~3.2 (p3).

    ``g`` (tiles per group) only enters the database-major models —
    "stream_db" holds a whole [g·T, d] y super-block resident,
    "stream_dbuf" holds 2 DMA tile slots but the fold state of the
    WHOLE query batch (callers pass the padded query count as Qb)."""
    if kernel == "stream_db":
        # database-major super-blocked cell: the y group block
        # [g·T, d] is VMEM-resident (double-buffered by the standard
        # Pallas pipeline so the next super-block DMA overlaps the
        # last cell of this one); fold state matches "stream"
        bytes_ = g * T * d * 2 * 2 * (2 if passes == 3 else 1)
        bytes_ += Qb * d * 6 + Qb * 8                 # x f32+bf16, xxh
        bytes_ += 8 * g * T * 4 * 2                   # yyh carrier
        bytes_ += Qb * _LANES * 4 * 20                # fold state + temps
        return bytes_
    if kernel == "stream_db_q8":
        # int8-quantized database super-block: one [g·T, d] int8 slab
        # (double-buffered by the standard pipeline) replaces the bf16
        # hi(/lo) pair — 1 byte/element streamed regardless of passes
        # (passes only splits the QUERY operand; y_q is exact in bf16).
        # The per-group [8, 128] f32 scale tile is noise next to it.
        bytes_ = g * T * d * 1 * 2
        bytes_ += Qb * d * 6 + Qb * 8                 # x f32+bf16, xxh
        bytes_ += 8 * g * T * 4 * 2                   # yyh carrier
        bytes_ += 8 * _LANES * 4 * 2                  # scale tile
        bytes_ += Qb * _LANES * 4 * 20                # fold state + temps
        return bytes_
    if kernel == "stream_dbuf_q8":
        # int8 explicit double-buffered streaming: 2 int8 DMA tile
        # slots; fold state covers the whole padded query batch like
        # "stream_dbuf" (callers pass that as Qb)
        bytes_ = 2 * T * d * 1                        # 2 int8 DMA slots
        bytes_ += Qb * d * 6 + Qb * 8                 # x f32+bf16, xxh
        bytes_ += 8 * g * T * 4 * 2                   # yyh carrier
        bytes_ += 8 * _LANES * 4 * 2                  # scale tile
        bytes_ += Qb * _LANES * 4 * 12                # fold state + temps
        return bytes_
    if kernel == "stream_dbuf":
        # explicit double-buffered streaming: y tiles ride a 2-slot
        # manual-DMA scratch (only 2 tiles resident, whatever g is) but
        # the cell covers the WHOLE query batch — Qb here is the padded
        # query count, so the fold-state term dominates. Factor 12 ≈
        # 3 accumulators + ~6 transient merge temps + pack/cast copies;
        # UNCALIBRATED estimate (no Mosaic compile/reject measured yet
        # for this kernel — the first TPU round recalibrates it the way
        # v5e rejections calibrated the factors above).
        bytes_ = 2 * T * d * 2 * (2 if passes == 3 else 1)  # 2 DMA slots
        bytes_ += Qb * d * 6 + Qb * 8                 # x f32+bf16, xxh
        bytes_ += 8 * g * T * 4 * 2                   # yyh carrier
        bytes_ += Qb * _LANES * 4 * 12                # fold state + temps
        return bytes_
    if kernel == "stream":
        # the streamed packed kernel (single-shot only — the d-chunked
        # packed kernel models as "packed") never materializes a
        # [Qb, T] score buffer: per-chunk [Qb, 128] temporaries only
        # (fold state + pack temps, ~20 live [Qb, 128]
        # f32-equivalents, conservative vs the ~14 the fold holds)
        assert not dchunk, "stream models the single-shot kernel"
        bytes_ = T * d * 2 * 2 * (2 if passes == 3 else 1)  # y hi(/lo)
        bytes_ += Qb * d * 6 + Qb * 8                 # x f32+bf16, xxh
        bytes_ += 8 * T * 4 * 2                       # yyh carrier
        bytes_ += Qb * _LANES * 4 * 20                # fold state + temps
        return bytes_
    if kernel == "group":
        d2_bufs = 2.2 if passes == 1 else 3.2
        n_out = 5
    elif kernel == "packed":
        # no i32 id carriers in the merge and 3 f32 outputs — measured
        # compiles at (1024, 256) both passes; factors kept conservative
        d2_bufs = 1.8 if passes == 1 else 2.8
        n_out = 3
    else:
        d2_bufs = 1.25 if passes == 1 else 2.25
        n_out = 3
    dc = min(d, 256) if dchunk else d
    bytes_ = int(Qb * T * 4 * d2_bufs)
    bytes_ += T * dc * 2 * 2 * (2 if passes == 3 else 1)  # y hi(/lo), 2 bufs
    bytes_ += Qb * dc * (4 + 2)                           # x f32 + bf16 cast
    bytes_ += T * 4 * 2 + Qb * 4                          # yy (2 bufs), xx
    bytes_ += Qb * _LANES * 4 * n_out * 2                 # out blocks + temps
    if dchunk:
        bytes_ += Qb * T * 4                              # score accumulator
    return bytes_


def _contract(x, yhi, ylo):
    """bf16 (ylo None) or bf16x3 MXU contraction of an f32 x block with a
    bf16-split y tile → f32 [Qb, T] partial scores.

    The ((1,),(1,)) NT contraction is used directly: a pre-transposed
    [d, T] y layout was A/B-measured on v5e (2048×1M×128) and LOST
    (5.29 vs 4.72 ms p1) — Mosaic handles NT natively and the XLA-side
    transpose costs more than it saves, so the knob was removed."""
    dims = (((1,), (1,)), ((), ()))
    xhi = x.astype(jnp.bfloat16)
    s = jax.lax.dot_general(
        xhi, yhi, dims, preferred_element_type=jnp.float32)
    if ylo is not None:
        # unbarriered ON PURPOSE: this body lowers through Mosaic, not
        # the XLA bf16-propagation pass that folds the split in
        # split_hi_lo (see its barrier note) — audited on hardware: the
        # fuzz battery's big-norm p3 rows exercise this exact split and
        # the kernel matched the numpy bf16x3 emulation bit-for-bit
        xlo = (x - xhi.astype(jnp.float32)).astype(jnp.bfloat16)
        s = s + jax.lax.dot_general(
            xhi, ylo, dims, preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(
            xlo, yhi, dims, preferred_element_type=jnp.float32)
    return s


def _contract_q8(x, yq, passes: int):
    """MXU contraction of an f32 x block with an INT8-quantized y tile
    → f32 [Qb, T] partial scores in QUANTIZED units (the caller
    multiplies by the group scale AFTER accumulation — cheaper and more
    accurate than a per-element dequantize: int8 magnitudes ≤ 127 are
    EXACT in bf16's 8-bit mantissa, so the y factor carries zero
    rounding; only x is rounded). ``passes=3`` adds the x_lo pass
    (x ≈ hi + lo to ~2⁻¹⁶), halving the x-side error at 2× MXU cost —
    there is no y_lo: the quantization error is handled by the
    certificate's Eq widening, not by extra precision."""
    dims = (((1,), (1,)), ((), ()))
    xhi = x.astype(jnp.bfloat16)
    yqb = yq.astype(jnp.bfloat16)
    s = jax.lax.dot_general(
        xhi, yqb, dims, preferred_element_type=jnp.float32)
    if passes == 3:
        # unbarriered like _contract: Mosaic lowering, not the XLA
        # bf16-propagation pass that folds the split
        xlo = (x - xhi.astype(jnp.float32)).astype(jnp.bfloat16)
        s = s + jax.lax.dot_general(
            xlo, yqb, dims, preferred_element_type=jnp.float32)
    return s


def _fold_and_write(d2, j, m_real_ref, m1_ref, i1_ref, m2min_ref,
                    T: int, Qb: int, mask: bool = True, track: bool = True):
    """Mask padded index rows, fold the [Qb, T] distance tile into LANES
    slots (per-slot top-2 + argmin-1), and write/accumulate the outputs.
    Shared by the single-shot and d-chunked kernels.

    ``mask=False`` / ``track=False`` are MEASUREMENT-ONLY knobs
    (benchmarks/profile_fused.py bounds the cost of the mask and of the
    index/2nd-min bookkeeping with them): mask=False requires pre-masked
    operands; track=False returns i1 = 0 and m2min = the slot MIN — not
    valid certificate inputs."""
    n_chunks = T // _LANES
    if mask:
        # mask padded index rows (global col ≥ m_real) to +inf
        col = j * T + jax.lax.broadcasted_iota(jnp.int32, (Qb, T), 1)
        d2 = jnp.where(col < m_real_ref[0], d2, jnp.inf)

    # slot class c collects columns {c, c+128, c+256, ...} of this tile
    # (chunk r contributes its lane c as global column j*T + r*128 + c).
    inf = jnp.full((Qb, _LANES), jnp.inf, jnp.float32)
    if not track:
        a1 = inf
        for r in range(n_chunks):
            a1 = jnp.minimum(a1, d2[:, r * _LANES:(r + 1) * _LANES])
        a2 = a1
        i1 = jnp.zeros((Qb, _LANES), jnp.int32)
    else:
        a1, a2 = inf, inf
        i1 = jnp.full((Qb, _LANES), -1, jnp.int32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Qb, _LANES), 1)
        for r in range(n_chunks):
            c = d2[:, r * _LANES:(r + 1) * _LANES]
            ci = j * T + r * _LANES + lane
            lt1 = c < a1
            a2 = jnp.where(lt1, a1, jnp.minimum(a2, c))
            a1 = jnp.where(lt1, c, a1)
            i1 = jnp.where(lt1, ci, i1)

    m1_ref[...] = a1
    i1_ref[...] = i1
    # running min over slots of the slot-2nd-min (certificate input);
    # the m2min output block is revisited by every tile of this q-block
    @pl.when(j == 0)
    def _():
        m2min_ref[...] = a2

    @pl.when(j != 0)
    def _():
        m2min_ref[...] = jnp.minimum(m2min_ref[...], a2)


def _fused_kernel(m_real_ref, x_ref, yhi_ref, xx_ref, yy_ref,
                  m1_ref, i1_ref, m2min_ref,
                  *, T: int, Qb: int, ylo_ref=None,
                  mask: bool = True, track: bool = True):
    """One (query-block, index-tile) cell. ``ylo_ref`` present ⇒ bf16x3."""
    j = pl.program_id(1)
    s = _contract(x_ref[...], yhi_ref[...],
                  None if ylo_ref is None else ylo_ref[...])
    d2 = xx_ref[...] + yy_ref[...] - 2.0 * s         # [Qb,1]+[1,T]-[Qb,T]
    _fold_and_write(d2, j, m_real_ref, m1_ref, i1_ref, m2min_ref,
                    T=T, Qb=Qb, mask=mask, track=track)


def _fused_kernel_dchunk(m_real_ref, x_ref, yhi_ref, xx_ref, yy_ref,
                         m1_ref, i1_ref, m2min_ref, acc_ref,
                         *, T: int, Qb: int, ylo_ref=None):
    """d-chunked cell (grid (nq, n_tiles, n_dchunks), d innermost): the
    partial contraction accumulates into a VMEM scratch [Qb, T]; the
    mask+fold runs only on the LAST d-chunk. Lifts the d ≤ 512 envelope
    — the d2 tile still never touches HBM."""
    j = pl.program_id(1)
    l = pl.program_id(2)
    n_dc = pl.num_programs(2)
    s = _contract(x_ref[...], yhi_ref[...],
                  None if ylo_ref is None else ylo_ref[...])

    @pl.when(l == 0)
    def _():
        acc_ref[...] = s

    @pl.when(l != 0)
    def _():
        acc_ref[...] = acc_ref[...] + s

    @pl.when(l == n_dc - 1)
    def _():
        d2 = xx_ref[...] + yy_ref[...] - 2.0 * acc_ref[...]
        _fold_and_write(d2, j, m_real_ref, m1_ref, i1_ref, m2min_ref,
                        T=T, Qb=Qb)


# --- scaffolding shared by the single-shot and d-chunked calls (the
# out-spec index maps take (i, j, *rest) so the same lambdas serve both
# grid arities; *rest swallows the extra grid index + prefetch refs) ---

def _slot_out_specs(Qb: int):
    return [
        pl.BlockSpec((Qb, _LANES), lambda i, j, *_: (i, j),
                     memory_space=pltpu.VMEM),          # m1
        pl.BlockSpec((Qb, _LANES), lambda i, j, *_: (i, j),
                     memory_space=pltpu.VMEM),          # i1
        pl.BlockSpec((Qb, _LANES), lambda i, j, *_: (i, 0),
                     memory_space=pltpu.VMEM),          # m2min (revisited)
    ]


def _slot_out_shape(Q: int, S: int):
    return [
        jax.ShapeDtypeStruct((Q, S), jnp.float32),
        jax.ShapeDtypeStruct((Q, S), jnp.int32),
        jax.ShapeDtypeStruct((Q, _LANES), jnp.float32),
    ]


def _slot_cost(Q: int, M: int, d: int, S: int, passes: int):
    return pl.CostEstimate(
        flops=2 * Q * M * d * passes,
        bytes_accessed=(Q * d * 4 + M * d * 2 * (2 if passes == 3 else 1)
                        + Q * S * 8),
        transcendentals=0,
    )


def _check_tiling(T: int, Qb: int):
    """The folds iterate T // LANES lane-chunks and the 3-D carriers
    reshape Qb // 8: a non-multiple T would SILENTLY skip the tail
    columns of every tile (no pool entry, no certificate coverage), so
    the invariant is enforced at the kernel entry points, not just in
    knn_fused."""
    if T % _LANES:
        raise ValueError(f"T={T} must be a multiple of {_LANES}")
    if Qb % 8:
        raise ValueError(f"Qb={Qb} must be a multiple of 8")


def _check_pack_envelope(T: int, tpg: int, pbits: int = _PACK_BITS):
    if tpg * (T // _LANES) > (1 << pbits):
        raise ValueError(
            f"packed group kernel: tpg*T/128 = {tpg * T // _LANES} "
            f"exceeds the {1 << pbits}-code packing envelope")


def _check_pair_envelope(n_chunks: int):
    # silently falling back to the non-pair loop would make a benchmark
    # row labelled "pair" measure the baseline kernel
    if n_chunks % 2:
        raise ValueError(
            f"pair=True requires an even chunk count, got T/128 = "
            f"{n_chunks}")


def _make_kernel(base, passes: int, T: int, Qb: int, **fold_kw):
    """Bind the base kernel for the passes mode; for passes == 3 reorder
    the y_lo ref out of the positional stream (*rest carries the output
    refs and, for the d-chunked kernel, the scratch ref)."""
    if passes != 3:
        return functools.partial(base, T=T, Qb=Qb, ylo_ref=None, **fold_kw)

    def kernel(m_real_ref, x_ref, yhi_ref, ylo_ref, xx_ref, yy_ref, *rest):
        base(m_real_ref, x_ref, yhi_ref, xx_ref, yy_ref, *rest,
             T=T, Qb=Qb, ylo_ref=ylo_ref, **fold_kw)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "mask", "track"))
def fused_l2_slot_topk(x, y_hi, y_lo, xx, yy, m_real,
                       T: int, Qb: int, passes: int,
                       mask: bool = True, track: bool = True
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Run the fused kernel. ``mask``/``track`` are measurement-only
    knobs (see _fold_and_write) — production callers use the defaults.

    Args:
      x: [Q, d] f32 queries (Q a multiple of Qb).
      y_hi, y_lo: [M, d] bf16 hi/lo split of the padded index (M a multiple
        of T); ``y_lo`` is only DMA'd/read when passes == 3.
      xx, yy: exact f32 squared norms, [Q, 1] and [1, M] (padded rows'
        yy = 0 — they are masked in-kernel anyway).
      m_real: [1] int32 — real (unpadded) index row count.
      T: index tile length; Qb: query block; passes: 1 (bf16) or 3 (bf16x3).

    Returns:
      m1 [Q, S] f32, i1 [Q, S] int32, m2min [Q, LANES] f32 with
      S = (M // T) * LANES; slot s = (tile = s // LANES) × (lane-class =
      s % LANES); i1 holds GLOBAL index-row ids; padded-only slots keep
      m1 = +inf, i1 = -1.
    """
    _check_tiling(T, Qb)
    Q, d = x.shape
    M = y_hi.shape[0]
    n_tiles = M // T
    nq = Q // Qb
    S = n_tiles * _LANES

    y_spec = pl.BlockSpec((T, d), lambda i, j, *_: (j, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((Qb, d), lambda i, j, *_: (i, 0),
                     memory_space=pltpu.VMEM),          # x
        y_spec,                                         # y_hi
        pl.BlockSpec((Qb, 1), lambda i, j, *_: (i, 0),
                     memory_space=pltpu.VMEM),          # xx
        pl.BlockSpec((1, T), lambda i, j, *_: (0, j),
                     memory_space=pltpu.VMEM),          # yy
    ]
    operands = [x, y_hi, xx, yy]
    if passes == 3:
        in_specs.insert(2, y_spec)                      # y_lo
        operands.insert(2, y_lo)
    kernel = _make_kernel(_fused_kernel, passes, T, Qb,
                          mask=mask, track=track)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, n_tiles),
        in_specs=in_specs,
        out_specs=_slot_out_specs(Qb),
    )
    m1, i1, m2min = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_slot_out_shape(Q, S),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=_slot_cost(Q, M, d, S, passes),
        interpret=interpret_mode(),
        name="fused_l2_slot_topk",
    )(m_real, *operands)
    return m1, i1, m2min


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "dc"))
def fused_l2_slot_topk_dchunk(x, y_hi, y_lo, xx, yy, m_real,
                              T: int, Qb: int, passes: int, dc: int = 256
                              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """d-chunked variant of :func:`fused_l2_slot_topk` for wide features
    (d > 512): grid (nq, n_tiles, d/dc) with the score tile accumulated
    in VMEM scratch across d-chunks (see _fused_kernel_dchunk). Same
    contract and outputs; caller pads d to a multiple of ``dc``."""
    _check_tiling(T, Qb)
    Q, d = x.shape
    M = y_hi.shape[0]
    if d % dc:
        raise ValueError(
            f"fused_l2_slot_topk_dchunk: d={d} must be a multiple of "
            f"dc={dc} (the tail would be silently dropped)")
    n_tiles = M // T
    nq = Q // Qb
    n_dc = d // dc
    S = n_tiles * _LANES

    y_spec = pl.BlockSpec((T, dc), lambda i, j, l, *_: (j, l),
                          memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((Qb, dc), lambda i, j, l, *_: (i, l),
                     memory_space=pltpu.VMEM),          # x
        y_spec,                                         # y_hi
        pl.BlockSpec((Qb, 1), lambda i, j, *_: (i, 0),
                     memory_space=pltpu.VMEM),          # xx
        pl.BlockSpec((1, T), lambda i, j, *_: (0, j),
                     memory_space=pltpu.VMEM),          # yy
    ]
    operands = [x, y_hi, xx, yy]
    if passes == 3:
        in_specs.insert(2, y_spec)                      # y_lo
        operands.insert(2, y_lo)
    kernel = _make_kernel(_fused_kernel_dchunk, passes, T, Qb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, n_tiles, n_dc),
        in_specs=in_specs,
        out_specs=_slot_out_specs(Qb),
        scratch_shapes=[pltpu.VMEM((Qb, T), jnp.float32)],  # score acc
    )
    m1, i1, m2min = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_slot_out_shape(Q, S),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=_slot_cost(Q, M, d, S, passes),
        interpret=interpret_mode(),
        name="fused_l2_slot_topk_dchunk",
    )(m_real, *operands)
    return m1, i1, m2min


# --- in-kernel group fold: top-2 (+3rd-min) per (lane, tile-group) ---
#
# The slot kernel above writes one (min, argmin) per (tile, lane) slot —
# [Q, n_tiles·128] outputs that an XLA group-fold then compresses.
# MEASURED (v5e, 2048×1M×128): that fold alone costs 15.6 ms — 3× the
# whole Pallas kernel — because XLA re-reads the ~1 GB slot arrays from
# HBM. This variant keeps the fold INSIDE the kernel: output blocks are
# revisited across `tpg` CONSECUTIVE index tiles (block index j // tpg —
# consecutive, so Mosaic keeps the block VMEM-resident and writes it to
# HBM once per group), accumulating per-(lane, group) top-2 values+ids
# and the group 3rd-min. Outputs shrink ~tpg/2.5× and the XLA fold
# disappears. Keeping top-2 per group also upgrades the exactness
# certificate: a query now only fails when THREE true top-k share a
# (lane, group) — O(k³/S²) instead of O(k²/S) — so the fixup path runs
# orders of magnitude more rarely.


def _merge_chunk_top2(c, ci, a1, id1, a2, id2, a3):
    """Merge candidate chunk (values c, ids ci — [Qb, LANES]) into the
    running per-(lane, group) (top-2 + 3rd-min) accumulators. Pure VPU
    compare/selects; ~13 ops per element (vs 5 for the top-1 fold)."""
    lt1 = c < a1
    b1 = jnp.where(lt1, a1, c)          # loser of the round-1 compare
    bid1 = jnp.where(lt1, id1, ci)
    a1 = jnp.where(lt1, c, a1)
    id1 = jnp.where(lt1, ci, id1)
    lt2 = b1 < a2
    b2 = jnp.where(lt2, a2, b1)         # loser of the round-2 compare
    a2 = jnp.where(lt2, b1, a2)
    id2 = jnp.where(lt2, bid1, id2)
    a3 = jnp.minimum(a3, b2)
    return a1, id1, a2, id2, a3


def _group_fold_and_write(s, j, yyh_ref, a1_ref, id1_ref, a2_ref,
                          id2_ref, a3_ref, *, T: int, Qb: int, tpg: int):
    """Merge the [Qb, T] score tile ``s = x·y`` into the group
    accumulators (initialized at the first tile of each group), folding
    the half-score ``c = yy/2 − s`` chunk by chunk.

    VMEM discipline (every full [Qb, T] f32 live buffer is ~25% of the
    Mosaic 16 MB scoped stack at production tiles — measured 16.36 MB
    rejections at T=2048, Qb=512 before these cuts):
    - NO in-kernel padded-row masking: callers pass yy/2 = +inf for
      padded columns; +inf loses every strict `<`, so padded-only slots
      keep a=+inf, id=-1 (the old mask cost a col-iota + a masked copy).
    - the half-score is computed per [Qb, LANES] chunk from the [1, T]
      yy/2 block — never materialized at [Qb, T].
    - candidate ids enter the merge as broadcast [1, LANES] rows, not
      [Qb, LANES] tiles."""
    @pl.when(j % tpg == 0)
    def _():
        inf = jnp.full((Qb, _LANES), jnp.inf, jnp.float32)
        neg = jnp.full((Qb, _LANES), -1, jnp.int32)
        a1_ref[...] = inf
        a2_ref[...] = inf
        a3_ref[...] = inf
        id1_ref[...] = neg
        id2_ref[...] = neg

    # 3-D carriers [Qb/8, 8, LANES]: the [8, LANES] yy/2 slices and id
    # rows broadcast legally against them (numpy rules) and Mosaic keeps
    # native (8, 128) trailing tiles (a [1, N] source is an invalid-
    # layout broadcast; a full [Qb, T] materialization is a live-buffer
    # we can't afford)
    q8 = Qb // 8
    a1 = a1_ref[...].reshape(q8, 8, _LANES)
    id1 = id1_ref[...].reshape(q8, 8, _LANES)
    a2 = a2_ref[...].reshape(q8, 8, _LANES)
    id2 = id2_ref[...].reshape(q8, 8, _LANES)
    a3 = a3_ref[...].reshape(q8, 8, _LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, _LANES), 1)
    yyh = yyh_ref[...]                                   # [8, T]
    for r in range(T // _LANES):
        sl = slice(r * _LANES, (r + 1) * _LANES)
        c = yyh[:, sl] - s[:, sl].reshape(q8, 8, _LANES)
        ci = j * T + r * _LANES + lane                   # [8, LANES]
        a1, id1, a2, id2, a3 = _merge_chunk_top2(
            c, ci, a1, id1, a2, id2, a3)
    a1_ref[...], id1_ref[...] = (a1.reshape(Qb, _LANES),
                                 id1.reshape(Qb, _LANES))
    a2_ref[...], id2_ref[...] = (a2.reshape(Qb, _LANES),
                                 id2.reshape(Qb, _LANES))
    a3_ref[...] = a3.reshape(Qb, _LANES)


# --- PACKED group fold: candidate code embedded in the value mantissa ---
#
# The unpacked merge spends ~half its VPU ops and register pressure on
# i32 id selects. Instead, the low _PACK_BITS mantissa bits of each
# half-score are REPLACED by the candidate's within-group code
# (tile-offset-in-group · chunks + chunk — the lane and group are
# implicit in the output position), so the merge is 3 compares + 4
# selects on f32 only, ids travel for free through every compare,
# top_k, and negation downstream, and the id output arrays + the pool
# id gather disappear. Cost: values carry a ≤ |v|·2⁻¹⁵ packing error —
# absorbed into the certificate's analytic bound (rescoring is exact
# f32 regardless). Envelope: tpg·(T/128) ≤ 2^_PACK_BITS slots per
# group (the measured-optimal configs sit exactly at 256), and padded
# columns use the finite _PACK_PAD sentinel (+inf would become NaN
# when id bits are OR'd into its mantissa).



def _merge_chunk_top2_packed(cp, a1, a2, a3):
    """5-op packed merge: top-2 + 3rd-min by packed-f32 order.

    Pure min/max network (no compare+select pairs — min/max are single
    VPU ops where lt+where is two): with the invariant a1 ≤ a2, the
    round-1 loser max(a1, cp) either stays ≥ a2 (cp wins nothing) or
    becomes the new 2nd; the round-2 loser max(a2, ·) is exactly the
    3rd-smallest seen, which feeds the certificate bound."""
    b1 = jnp.maximum(a1, cp)
    a1 = jnp.minimum(a1, cp)
    b2 = jnp.maximum(a2, b1)
    a2 = jnp.minimum(a2, b1)
    a3 = jnp.minimum(a3, b2)
    return a1, a2, a3


def _group_fold_and_write_packed(s, j, yyh_ref, a1_ref, a2_ref, a3_ref,
                                 *, T: int, Qb: int, tpg: int,
                                 pair: bool = False,
                                 pbits: int = _PACK_BITS, xxh_ref=None):
    """Packed variant of _group_fold_and_write: same VMEM discipline
    (per-chunk half-scores, 3-D carriers, no masking — callers pass
    yy/2 = _PACK_PAD on padded columns), but the merge runs on packed
    values only (see the block comment above).

    ``pair=True`` inserts a pairwise pre-reduction: adjacent chunks are
    min-combined BEFORE packing/merging (the pack + top-2 merge then run
    on half the stream — ~8 effective VPU ops/element vs ~10), and each
    pair's loser feeds the 3rd-min tracker directly, so the certificate
    stays sound: every value discarded anywhere still lower-bounds into
    a3. Cost: a query now also needs fixup when TWO true top-k collide
    in one (lane, chunk-pair) — ~2× the three-share-a-group rate, still
    single-digit per 2048 queries at production scale (measured)."""
    n_chunks = T // _LANES

    @pl.when(j % tpg == 0)
    def _():
        big = jnp.full((Qb, _LANES), _PACK_PAD, jnp.float32)
        a1_ref[...] = big
        a2_ref[...] = big
        a3_ref[...] = big

    q8 = Qb // 8
    a1 = a1_ref[...].reshape(q8, 8, _LANES)
    a2 = a2_ref[...].reshape(q8, 8, _LANES)
    a3 = a3_ref[...].reshape(q8, 8, _LANES)
    yyh = yyh_ref[...]                                   # [8, T]
    xxh = (None if xxh_ref is None
           else xxh_ref[...].reshape(q8, 8, 1))          # [Qb, 1] → 3-D

    def half_score(r):
        sl = slice(r * _LANES, (r + 1) * _LANES)
        c = yyh[:, sl] - s[:, sl].reshape(q8, 8, _LANES)
        # with the query half-norm folded in, c = d2/2 — SMALL, so the
        # pack perturbation is relative to the distances being
        # compared, not to the (often 10×) norm-dominated half-score
        return c if xxh is None else c + xxh

    def pack(c, code):
        return jax.lax.bitcast_convert_type(
            (jax.lax.bitcast_convert_type(c, jnp.int32)
             & ~((1 << pbits) - 1)) | code, jnp.float32)

    if pair:
        _check_pair_envelope(n_chunks)
        for r in range(0, n_chunks, 2):
            c0, c1 = half_score(r), half_score(r + 1)
            mn = jnp.minimum(c0, c1)
            a3 = jnp.minimum(a3, jnp.maximum(c0, c1))
            base = (j % tpg) * n_chunks + r              # even → bit0 free
            cp = pack(mn, jnp.where(mn == c1, base + 1, base))
            a1, a2, a3 = _merge_chunk_top2_packed(cp, a1, a2, a3)
    else:
        for r in range(n_chunks):
            local = (j % tpg) * n_chunks + r             # scalar code
            cp = pack(half_score(r), local)
            a1, a2, a3 = _merge_chunk_top2_packed(cp, a1, a2, a3)
    a1_ref[...] = a1.reshape(Qb, _LANES)
    a2_ref[...] = a2.reshape(Qb, _LANES)
    a3_ref[...] = a3.reshape(Qb, _LANES)


def _group_kernel_packed(m_real_ref, x_ref, yhi_ref, yyh_ref,
                         a1_ref, a2_ref, a3_ref,
                         *, T: int, Qb: int, tpg: int, pair: bool = False,
                         pbits: int = _PACK_BITS, ylo_ref=None,
                         xxh_ref=None):
    j = pl.program_id(1)
    s = _contract(x_ref[...], yhi_ref[...],
                  None if ylo_ref is None else ylo_ref[...])
    _group_fold_and_write_packed(s, j, yyh_ref, a1_ref, a2_ref, a3_ref,
                                 T=T, Qb=Qb, tpg=tpg, pair=pair,
                                 pbits=pbits, xxh_ref=xxh_ref)


def _group_kernel_packed_stream(m_real_ref, x_ref, yhi_ref, yyh_ref,
                                a1_ref, a2_ref, a3_ref,
                                *, T: int, Qb: int, tpg: int,
                                pair: bool = False,
                                pbits: int = _PACK_BITS, ylo_ref=None,
                                xxh_ref=None):
    """Streamed variant: the [Qb, T] contraction is split into T/LANES
    [Qb, LANES] chunk contractions interleaved with the fold of the
    PREVIOUS chunk. The big-matmul kernel serializes MXU (contract) then
    VPU (fold) per cell; emitting them as independent small ops lets
    Mosaic's VLIW scheduler co-issue fold(r) with contract(r+1) — the
    in-kernel analog of double-buffering, targeting
    max(matmul, fold) instead of matmul + fold per cell. Also drops the
    live [Qb, T] f32 score buffer (only [Qb, LANES] chunks live)."""
    j = pl.program_id(1)
    n_chunks = T // _LANES

    @pl.when(j % tpg == 0)
    def _():
        big = jnp.full((Qb, _LANES), _PACK_PAD, jnp.float32)
        a1_ref[...] = big
        a2_ref[...] = big
        a3_ref[...] = big

    q8 = Qb // 8
    a1 = a1_ref[...].reshape(q8, 8, _LANES)
    a2 = a2_ref[...].reshape(q8, 8, _LANES)
    a3 = a3_ref[...].reshape(q8, 8, _LANES)
    x = x_ref[...]
    yhi = yhi_ref[...]
    ylo = None if ylo_ref is None else ylo_ref[...]
    yyh = yyh_ref[...]                                   # [8, T]
    xxh = (None if xxh_ref is None
           else xxh_ref[...].reshape(q8, 8, 1))          # [Qb, 1] → 3-D

    def chunk_score(r):
        sl = slice(r * _LANES, (r + 1) * _LANES)
        s_r = _contract(x, yhi[sl, :], None if ylo is None else ylo[sl, :])
        c = yyh[:, sl] - s_r.reshape(q8, 8, _LANES)
        # c + xx/2 = d2/2 (see _group_fold_and_write_packed)
        return c if xxh is None else c + xxh

    def pack(c, code):
        return jax.lax.bitcast_convert_type(
            (jax.lax.bitcast_convert_type(c, jnp.int32)
             & ~((1 << pbits) - 1)) | code, jnp.float32)

    if pair:
        _check_pair_envelope(n_chunks)
        for r in range(0, n_chunks, 2):
            c0, c1 = chunk_score(r), chunk_score(r + 1)
            mn = jnp.minimum(c0, c1)
            a3 = jnp.minimum(a3, jnp.maximum(c0, c1))
            base = (j % tpg) * n_chunks + r              # even → bit0 free
            cp = pack(mn, jnp.where(mn == c1, base + 1, base))
            a1, a2, a3 = _merge_chunk_top2_packed(cp, a1, a2, a3)
    else:
        for r in range(n_chunks):
            cp = pack(chunk_score(r), (j % tpg) * n_chunks + r)
            a1, a2, a3 = _merge_chunk_top2_packed(cp, a1, a2, a3)
    a1_ref[...] = a1.reshape(Qb, _LANES)
    a2_ref[...] = a2.reshape(Qb, _LANES)
    a3_ref[...] = a3.reshape(Qb, _LANES)


def _group_kernel_packed_dchunk(m_real_ref, x_ref, yhi_ref, yyh_ref,
                                a1_ref, a2_ref, a3_ref, acc_ref,
                                *, T: int, Qb: int, tpg: int,
                                pair: bool = False,
                                pbits: int = _PACK_BITS, ylo_ref=None,
                                xxh_ref=None):
    j = pl.program_id(1)
    l = pl.program_id(2)
    n_dc = pl.num_programs(2)
    s = _contract(x_ref[...], yhi_ref[...],
                  None if ylo_ref is None else ylo_ref[...])

    @pl.when(l == 0)
    def _():
        acc_ref[...] = s

    @pl.when(l != 0)
    def _():
        acc_ref[...] = acc_ref[...] + s

    @pl.when(l == n_dc - 1)
    def _():
        _group_fold_and_write_packed(acc_ref[...], j, yyh_ref, a1_ref,
                                     a2_ref, a3_ref, T=T, Qb=Qb, tpg=tpg,
                                     pair=pair, pbits=pbits,
                                     xxh_ref=xxh_ref)


# --- DATABASE-MAJOR variants: stream y from HBM ~once ----------------
#
# The query-major grid (nq, n_tiles) re-fetches EVERY y tile for every
# query block: y HBM traffic = nq · M · d bytes. At the driver shape
# (2048×1M×128, Qb=256 ⇒ nq=8) that re-fetch alone accounts for most of
# the measured 460-vs-820 GB/s roofline gap (round 5). These variants
# invert the loop so the database streams ~once:
#
# - "db" (super-blocked): grid (n_groups, nq) with the WHOLE certificate
#   group [tpg·T, d] as one resident y block, index (sidx, i) → (sidx,)
#   — constant across the inner query loop, so Mosaic fetches each
#   super-block exactly once (y traffic = M·d·2 bytes total) and its
#   standard pipeline DMAs super-block sidx+1 while the last query block
#   of sidx computes (one cell ≈ Qb·tpg·T·d·2 MXU flops ≈ 2× the
#   super-block DMA time at production tiles — the prefetch hides).
#   Each cell folds the full group in one shot, so the group outputs are
#   written ONCE per (i, sidx) — no revisited-output accumulation to
#   keep legal under the inverted order. x blocks are re-fetched once
#   per super-block (n_groups · Q · d · 4 bytes — the traffic the
#   autotuner trades against the saved y stream; see
#   observability.costmodel.fused_traffic_model).
# - "dbuf" (explicit double-buffered): grid (n_groups,) with y in
#   ANY/HBM and a manual 2-slot async-copy pipeline: tile jj+1's DMA is
#   issued before tile jj's fold runs, so the HBM stream overlaps the
#   MXU/VPU work at TILE granularity and only 2 tiles are VMEM-resident
#   (the tpg envelope is no longer VMEM-bound). The cell covers the
#   WHOLE query batch (fold state [Q, 128] — the VMEM cost that
#   replaces the resident super-block), x is resident and fetched once:
#   y traffic = M·d·2, x traffic = Q·d·4, both single-stream.
#
# Both are packed-only (the production path): same outputs, codes and
# certificate semantics as fused_l2_group_topk_packed — group sidx maps
# to output columns [sidx·128, (sidx+1)·128), the within-group code is
# jj·(T/128) + chunk — so decode_packed_pool and the twin-pool
# certificate in knn_fused work unchanged. Callers pad the index to a
# whole number of groups (tpg·T rows); padded columns carry the
# _PACK_PAD sentinel in yy_half exactly as before.


def _fold_tile_packed(acc, x, ythi, ytlo, yyh_t, xxh, jj: int,
                      *, T: int, Qb: int, pair: bool, pbits: int,
                      scale=None, passes: int = 1):
    """Fold ONE y tile (rows [T, d], half-norms yyh_t [8, T]) into the
    packed (a1, a2, a3) carriers with within-group tile offset ``jj`` —
    the per-tile body shared by the database-major kernels. Chunk
    contractions are emitted individually (the "stream" idiom) so
    Mosaic co-issues fold(r) with contract(r+1).

    ``scale`` (an [8, LANES] group-replicated f32 tile) switches the
    tile to the INT8 path: ``ythi`` is then the int8 tile, ``ytlo`` is
    unused, the contraction runs through :func:`_contract_q8` (passes
    splits the x operand only) and the quantized partial scores are
    rescaled after accumulation — the in-register dequantize of the
    quantized-streaming design. The half-norm carrier must hold the
    DEQUANTIZED rows' norms, so the folded value is exactly
    d2(x, ŷ)/2 and every downstream consumer (codes, certificate,
    decode) is untouched."""
    a1, a2, a3 = acc
    n_chunks = T // _LANES
    q8 = Qb // 8

    def chunk_score(r):
        sl = slice(r * _LANES, (r + 1) * _LANES)
        if scale is None:
            s_r = _contract(x, ythi[sl, :],
                            None if ytlo is None else ytlo[sl, :])
            s3 = s_r.reshape(q8, 8, _LANES)
        else:
            s_r = _contract_q8(x, ythi[sl, :], passes)
            s3 = s_r.reshape(q8, 8, _LANES) * scale
        c = yyh_t[:, sl] - s3
        # c + xx/2 = d2/2 (see _group_fold_and_write_packed)
        return c if xxh is None else c + xxh

    def pack(c, code):
        return jax.lax.bitcast_convert_type(
            (jax.lax.bitcast_convert_type(c, jnp.int32)
             & ~((1 << pbits) - 1)) | code, jnp.float32)

    if pair:
        _check_pair_envelope(n_chunks)
        for r in range(0, n_chunks, 2):
            c0, c1 = chunk_score(r), chunk_score(r + 1)
            mn = jnp.minimum(c0, c1)
            a3 = jnp.minimum(a3, jnp.maximum(c0, c1))
            base = jj * n_chunks + r                     # even → bit0 free
            cp = pack(mn, jnp.where(mn == c1, base + 1, base))
            a1, a2, a3 = _merge_chunk_top2_packed(cp, a1, a2, a3)
    else:
        for r in range(n_chunks):
            cp = pack(chunk_score(r), jj * n_chunks + r)
            a1, a2, a3 = _merge_chunk_top2_packed(cp, a1, a2, a3)
    return a1, a2, a3


def _group_kernel_packed_db(m_real_ref, x_ref, yhi_ref, yyh_ref,
                            a1_ref, a2_ref, a3_ref,
                            *, T: int, Qb: int, tpg: int,
                            pair: bool = False, pbits: int = _PACK_BITS,
                            ylo_ref=None, xxh_ref=None):
    """Database-major super-blocked cell: the resident [tpg·T, d] y
    block is folded whole (static tile loop), outputs written once."""
    q8 = Qb // 8
    big = jnp.full((q8, 8, _LANES), _PACK_PAD, jnp.float32)
    acc = (big, big, big)
    x = x_ref[...]
    yyh = yyh_ref[...]                                   # [8, tpg·T]
    xxh = (None if xxh_ref is None
           else xxh_ref[...].reshape(q8, 8, 1))
    for jj in range(tpg):
        rs = slice(jj * T, (jj + 1) * T)
        acc = _fold_tile_packed(
            acc, x, yhi_ref[rs, :],
            None if ylo_ref is None else ylo_ref[rs, :],
            yyh[:, rs], xxh, jj, T=T, Qb=Qb, pair=pair, pbits=pbits)
    a1_ref[...] = acc[0].reshape(Qb, _LANES)
    a2_ref[...] = acc[1].reshape(Qb, _LANES)
    a3_ref[...] = acc[2].reshape(Qb, _LANES)


def _group_kernel_packed_dbuf(m_real_ref, x_ref, yhi_ref, yyh_ref,
                              a1_ref, a2_ref, a3_ref,
                              *, T: int, Qb: int, tpg: int,
                              pair: bool = False, pbits: int = _PACK_BITS,
                              ylo_ref=None, xxh_ref=None):
    """Explicit double-buffered database streaming: y_hi (and y_lo)
    stay in ANY/HBM; tiles ride a 2-slot VMEM scratch whose next-tile
    async copy is issued BEFORE the current tile's fold, so the DMA
    overlaps the MXU contraction. Grid (n_groups,) — one cell covers
    the whole query batch (Qb == padded Q)."""
    sidx = pl.program_id(0)
    d = yhi_ref.shape[1]
    q8 = Qb // 8

    def body(scratch_hi, sem_hi, scratch_lo=None, sem_lo=None):
        def dma(ref, scr, sem, slot, jj):
            return pltpu.make_async_copy(
                ref.at[pl.ds((sidx * tpg + jj) * T, T), :],
                scr.at[slot], sem.at[slot])

        def start(slot, jj):
            dma(yhi_ref, scratch_hi, sem_hi, slot, jj).start()
            if scratch_lo is not None:
                dma(ylo_ref, scratch_lo, sem_lo, slot, jj).start()

        def wait(slot, jj):
            dma(yhi_ref, scratch_hi, sem_hi, slot, jj).wait()
            if scratch_lo is not None:
                dma(ylo_ref, scratch_lo, sem_lo, slot, jj).wait()

        start(0, 0)
        big = jnp.full((q8, 8, _LANES), _PACK_PAD, jnp.float32)
        acc = (big, big, big)
        x = x_ref[...]
        yyh = yyh_ref[...]                               # [8, tpg·T]
        xxh = (None if xxh_ref is None
               else xxh_ref[...].reshape(q8, 8, 1))
        for jj in range(tpg):
            slot = jj % 2
            if jj + 1 < tpg:
                start((jj + 1) % 2, jj + 1)              # prefetch next
            wait(slot, jj)
            acc = _fold_tile_packed(
                acc, x, scratch_hi[slot],
                None if scratch_lo is None else scratch_lo[slot],
                yyh[:, jj * T:(jj + 1) * T], xxh, jj,
                T=T, Qb=Qb, pair=pair, pbits=pbits)
        a1_ref[...] = acc[0].reshape(Qb, _LANES)
        a2_ref[...] = acc[1].reshape(Qb, _LANES)
        a3_ref[...] = acc[2].reshape(Qb, _LANES)

    scoped = dict(scratch_hi=pltpu.VMEM((2, T, d), jnp.bfloat16),
                  sem_hi=pltpu.SemaphoreType.DMA((2,)))
    if ylo_ref is not None:
        scoped.update(scratch_lo=pltpu.VMEM((2, T, d), jnp.bfloat16),
                      sem_lo=pltpu.SemaphoreType.DMA((2,)))
    pl.run_scoped(body, **scoped)


def _group_kernel_packed_db_q8(m_real_ref, x_ref, yq_ref, yyh_ref,
                               scl_ref, xxh_ref,
                               a1_ref, a2_ref, a3_ref,
                               *, T: int, Qb: int, tpg: int, passes: int,
                               pair: bool = False,
                               pbits: int = _PACK_BITS):
    """INT8 database-major super-blocked cell: the resident [tpg·T, d]
    y block is the QUANTIZED int8 slab (half the bf16 stream, a quarter
    of the bf16x3 one); the per-group scale tile rescales the quantized
    partial scores in-register after the MXU contraction (see
    _contract_q8). Same outputs/codes/certificate semantics as
    _group_kernel_packed_db."""
    q8 = Qb // 8
    big = jnp.full((q8, 8, _LANES), _PACK_PAD, jnp.float32)
    acc = (big, big, big)
    x = x_ref[...]
    yyh = yyh_ref[...]                                   # [8, tpg·T]
    scale = scl_ref[0]                                   # [8, LANES]
    xxh = xxh_ref[...].reshape(q8, 8, 1)
    for jj in range(tpg):
        rs = slice(jj * T, (jj + 1) * T)
        acc = _fold_tile_packed(
            acc, x, yq_ref[rs, :], None, yyh[:, rs], xxh, jj,
            T=T, Qb=Qb, pair=pair, pbits=pbits, scale=scale,
            passes=passes)
    a1_ref[...] = acc[0].reshape(Qb, _LANES)
    a2_ref[...] = acc[1].reshape(Qb, _LANES)
    a3_ref[...] = acc[2].reshape(Qb, _LANES)


def _group_kernel_packed_dbuf_q8(m_real_ref, x_ref, yq_ref, yyh_ref,
                                 scl_ref, xxh_ref,
                                 a1_ref, a2_ref, a3_ref,
                                 *, T: int, Qb: int, tpg: int,
                                 passes: int, pair: bool = False,
                                 pbits: int = _PACK_BITS):
    """INT8 explicit double-buffered database streaming: like
    _group_kernel_packed_dbuf but the manual 2-slot DMA pipeline moves
    int8 tiles (1 byte/element on the wire; the dequantize is the
    post-accumulation rescale, never a widened copy in VMEM)."""
    sidx = pl.program_id(0)
    d = yq_ref.shape[1]
    q8 = Qb // 8

    def body(scratch_q, sem_q):
        def dma(slot, jj):
            return pltpu.make_async_copy(
                yq_ref.at[pl.ds((sidx * tpg + jj) * T, T), :],
                scratch_q.at[slot], sem_q.at[slot])

        dma(0, 0).start()
        big = jnp.full((q8, 8, _LANES), _PACK_PAD, jnp.float32)
        acc = (big, big, big)
        x = x_ref[...]
        yyh = yyh_ref[...]                               # [8, tpg·T]
        scale = scl_ref[0]                               # [8, LANES]
        xxh = xxh_ref[...].reshape(q8, 8, 1)
        for jj in range(tpg):
            slot = jj % 2
            if jj + 1 < tpg:
                dma((jj + 1) % 2, jj + 1).start()        # prefetch next
            dma(slot, jj).wait()
            acc = _fold_tile_packed(
                acc, x, scratch_q[slot], None,
                yyh[:, jj * T:(jj + 1) * T], xxh, jj,
                T=T, Qb=Qb, pair=pair, pbits=pbits, scale=scale,
                passes=passes)
        a1_ref[...] = acc[0].reshape(Qb, _LANES)
        a2_ref[...] = acc[1].reshape(Qb, _LANES)
        a3_ref[...] = acc[2].reshape(Qb, _LANES)

    pl.run_scoped(body, scratch_q=pltpu.VMEM((2, T, d), jnp.int8),
                  sem_q=pltpu.SemaphoreType.DMA((2,)))


def _group_kernel(m_real_ref, x_ref, yhi_ref, yyh_ref,
                  a1_ref, id1_ref, a2_ref, id2_ref, a3_ref,
                  *, T: int, Qb: int, tpg: int, ylo_ref=None):
    """Folds the HALF-SCORE r = yy/2 − s (NOT the full distance): per
    query row, d2 = 2·r + xx is a positive-scale + per-row-shift of r,
    so per-row top-2 ordering is identical and the caller recovers true
    distances on the tiny [Q, S'] outputs. Dropping xx and the ·2 from
    the kernel removes one live [Qb, T] f32 buffer from the broadcast
    chain — the difference between 16.36 MB (scoped-VMEM reject at
    T=2048, Qb=512) and fitting."""
    j = pl.program_id(1)
    s = _contract(x_ref[...], yhi_ref[...],
                  None if ylo_ref is None else ylo_ref[...])
    _group_fold_and_write(s, j, yyh_ref, a1_ref, id1_ref, a2_ref,
                          id2_ref, a3_ref, T=T, Qb=Qb, tpg=tpg)


def _group_kernel_dchunk(m_real_ref, x_ref, yhi_ref, yyh_ref,
                         a1_ref, id1_ref, a2_ref, id2_ref, a3_ref, acc_ref,
                         *, T: int, Qb: int, tpg: int, ylo_ref=None):
    j = pl.program_id(1)
    l = pl.program_id(2)
    n_dc = pl.num_programs(2)
    s = _contract(x_ref[...], yhi_ref[...],
                  None if ylo_ref is None else ylo_ref[...])

    @pl.when(l == 0)
    def _():
        acc_ref[...] = s

    @pl.when(l != 0)
    def _():
        acc_ref[...] = acc_ref[...] + s

    @pl.when(l == n_dc - 1)
    def _():
        _group_fold_and_write(acc_ref[...], j, yyh_ref, a1_ref, id1_ref,
                              a2_ref, id2_ref, a3_ref, T=T, Qb=Qb, tpg=tpg)


def _make_group_kernel(base, passes: int, T: int, Qb: int,
                       has_xxh: bool = False, **fold_kw):
    """Bind the group-kernel base for the passes mode, pulling the
    optional y_lo (passes == 3) and xxh (packed kernels with the query
    half-norm folded in) refs out of the positional operand stream."""

    def kernel(m_real_ref, x_ref, yhi_ref, *rest0):
        rest = list(rest0)
        ylo_ref = rest.pop(0) if passes == 3 else None
        yyh_ref = rest.pop(0)
        kw = dict(fold_kw)
        if has_xxh:
            kw["xxh_ref"] = rest.pop(0)
        base(m_real_ref, x_ref, yhi_ref, yyh_ref, *rest,
             T=T, Qb=Qb, ylo_ref=ylo_ref, **kw)

    return kernel


def _group_out_specs(Qb: int, tpg: int):
    spec = pl.BlockSpec((Qb, _LANES), lambda i, j, *_: (i, j // tpg),
                        memory_space=pltpu.VMEM)
    return [spec] * 5


def _group_out_shape(Q: int, Sg: int):
    return [
        jax.ShapeDtypeStruct((Q, Sg), jnp.float32),   # a1
        jax.ShapeDtypeStruct((Q, Sg), jnp.int32),     # id1
        jax.ShapeDtypeStruct((Q, Sg), jnp.float32),   # a2
        jax.ShapeDtypeStruct((Q, Sg), jnp.int32),     # id2
        jax.ShapeDtypeStruct((Q, Sg), jnp.float32),   # a3
    ]


def _packed_out_shape(Q: int, Sg: int):
    return [jax.ShapeDtypeStruct((Q, Sg), jnp.float32)] * 3


def _group_pallas_call(kernel_base, packed: bool,
                       x, y_hi, y_lo, yy_half, m_real,
                       *, name: str, T: int, Qb: int, passes: int,
                       tpg: int, dc=None, xxh=None, **fold_kw):
    """Shared scaffolding for the four group-fold entry points
    ((un)packed × (single-shot | d-chunked)) — specs, operands, grid and
    pallas_call in ONE place so the variants cannot drift. ``name`` is
    the kernel's op name in a device trace (the entry point's own)."""
    _check_tiling(T, Qb)
    Q, d = x.shape
    M = y_hi.shape[0]
    n_tiles = M // T
    nq = Q // Qb
    G = -(-n_tiles // tpg)
    if dc is None:
        y_spec = pl.BlockSpec((T, d), lambda i, j, *_: (j, 0),
                              memory_space=pltpu.VMEM)
        x_spec = pl.BlockSpec((Qb, d), lambda i, j, *_: (i, 0),
                              memory_space=pltpu.VMEM)
        grid = (nq, n_tiles)
        semantics = ("parallel", "arbitrary")
        scratch = []
    else:
        if d % dc:
            raise ValueError(
                f"fused_l2_group_topk*_dchunk: d={d} must be a multiple "
                f"of dc={dc} (the tail would be silently dropped)")
        y_spec = pl.BlockSpec((T, dc), lambda i, j, l, *_: (j, l),
                              memory_space=pltpu.VMEM)
        x_spec = pl.BlockSpec((Qb, dc), lambda i, j, l, *_: (i, l),
                              memory_space=pltpu.VMEM)
        grid = (nq, n_tiles, d // dc)
        semantics = ("parallel", "arbitrary", "arbitrary")
        scratch = [pltpu.VMEM((Qb, T), jnp.float32)]  # score accumulator

    in_specs = [
        x_spec,
        y_spec,                                         # y_hi
        pl.BlockSpec((8, T), lambda i, j, *_: (0, j),
                     memory_space=pltpu.VMEM),          # yy_half
    ]
    operands = [x, y_hi, yy_half]
    if passes == 3:
        in_specs.insert(2, y_spec)                      # y_lo
        operands.insert(2, y_lo)
    if xxh is not None:
        in_specs.append(pl.BlockSpec((Qb, 1), lambda i, j, *_: (i, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(xxh)
    kernel = _make_group_kernel(kernel_base, passes, T, Qb, tpg=tpg,
                                has_xxh=xxh is not None, **fold_kw)

    n_out = 3 if packed else 5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=_group_out_specs(Qb, tpg)[:n_out],
        scratch_shapes=scratch,
    )
    out_shape = (_packed_out_shape if packed else _group_out_shape)(
        Q, G * _LANES)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
        ),
        cost_estimate=_slot_cost(Q, M, d, G * _LANES, passes),
        interpret=interpret_mode(),
        name=name,
    )(m_real, *operands)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg"))
def fused_l2_group_topk(x, y_hi, y_lo, yy_half, m_real,
                        T: int, Qb: int, passes: int, tpg: int = 16):
    """Fused kernel with the IN-KERNEL group fold (see block comment).

    Folds the HALF-SCORE ``r = yy/2 − x·y`` (see _group_kernel): callers
    pass ``yy_half`` as an ``[8, M]`` sublane-replicated carrier (8 =
    native vreg sublane count; Mosaic rejects [1, N]→[Qb, N] broadcasts
    of sliced rows) holding ‖y‖²/2 with +inf on padded index columns (no
    in-kernel mask; ``m_real`` stays as a prefetch operand for interface
    stability but is not read) and recover true squared distances as
    ``2·a + xx`` on the outputs. ``tpg`` = index tiles per group.
    Returns ``(a1, id1, a2, id2, a3)``, each ``[Q, G·LANES]`` with
    ``G = ceil(n_tiles / tpg)``: per (lane-class, tile-group) the two
    smallest half-scores with their GLOBAL index-row ids, and the
    3rd-smallest (certificate input: every point outside a group's
    top-2 is ≥ that group's a3). Padded-only groups keep a=+inf,
    id=-1."""
    return _group_pallas_call(_group_kernel, False, x, y_hi, y_lo,
                              yy_half, m_real, name="fused_l2_group_topk",
                              T=T, Qb=Qb, passes=passes, tpg=tpg)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg", "dc"))
def fused_l2_group_topk_dchunk(x, y_hi, y_lo, yy_half, m_real,
                               T: int, Qb: int, passes: int, tpg: int = 16,
                               dc: int = 256):
    """d-chunked variant of :func:`fused_l2_group_topk` (wide features):
    grid (nq, n_tiles, d/dc), score accumulated in VMEM scratch, the
    group fold runs on the last d-chunk only. Same (half-score)
    outputs."""
    return _group_pallas_call(_group_kernel_dchunk, False, x, y_hi, y_lo,
                              yy_half, m_real,
                              name="fused_l2_group_topk_dchunk",
                              T=T, Qb=Qb, passes=passes, tpg=tpg, dc=dc)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg", "pair",
                                    "stream", "pbits"))
def fused_l2_group_topk_packed(x, y_hi, y_lo, yy_half, m_real,
                               T: int, Qb: int, passes: int,
                               tpg: int = 16, pair: bool = False,
                               stream: bool = False,
                               pbits: int = _PACK_BITS, xxh=None):
    """Packed-id variant of :func:`fused_l2_group_topk` (see the PACKED
    block comment): returns ``(a1p, a2p, a3p)``, each ``[Q, G·LANES]``
    f32 whose low _PACK_BITS mantissa bits hold the candidate's
    within-group code ``tile_offset·(T/LANES) + chunk`` (a3p's code is
    meaningless — only its value is used). ``yy_half`` must carry the
    finite ``_PACK_PAD`` sentinel (NOT +inf) on padded columns.
    Requires tpg·(T/LANES) ≤ 2^_PACK_BITS. ``pair`` enables the
    pairwise pre-reduction (see _group_fold_and_write_packed);
    ``stream`` the chunked MXU/VPU-overlap contraction (see
    _group_kernel_packed_stream)."""
    _check_pack_envelope(T, tpg, pbits)
    base = _group_kernel_packed_stream if stream else _group_kernel_packed
    return _group_pallas_call(base, True, x, y_hi, y_lo,
                              yy_half, m_real,
                              name="fused_l2_group_topk_packed",
                              T=T, Qb=Qb, passes=passes, tpg=tpg,
                              pair=pair, pbits=pbits, xxh=xxh)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg", "dc",
                                    "pair", "pbits"))
def fused_l2_group_topk_packed_dchunk(x, y_hi, y_lo, yy_half, m_real,
                                      T: int, Qb: int, passes: int,
                                      tpg: int = 16, dc: int = 256,
                                      pair: bool = False,
                                      pbits: int = _PACK_BITS, xxh=None):
    """d-chunked packed variant (wide features): same contract as
    :func:`fused_l2_group_topk_packed`."""
    _check_pack_envelope(T, tpg, pbits)
    return _group_pallas_call(_group_kernel_packed_dchunk, True, x, y_hi,
                              y_lo, yy_half, m_real,
                              name="fused_l2_group_topk_packed_dchunk",
                              T=T, Qb=Qb,
                              passes=passes, tpg=tpg, dc=dc, pair=pair,
                              pbits=pbits, xxh=xxh)


def _group_pallas_call_db(dbuf: bool, x, y_hi, y_lo, yy_half, m_real,
                          *, T: int, Qb: int, passes: int, tpg: int,
                          pair: bool, pbits: int, xxh, scale_k=None):
    """Scaffolding for the database-major packed entry points (specs,
    grid, pallas_call in ONE place, mirroring _group_pallas_call).

    ``scale_k`` ([n_groups, 8, LANES] f32, group-replicated) switches
    the call to the INT8 kernels: ``y_hi`` is then the int8 slab,
    ``y_lo`` must be None and ``xxh`` is required (the quantized path
    always folds the query half-norm — it is the production packed
    configuration)."""
    _check_tiling(T, Qb)
    _check_pack_envelope(T, tpg, pbits)
    Q, d = x.shape
    M = y_hi.shape[0]
    q8_mode = scale_k is not None
    if q8_mode and (y_lo is not None or xxh is None):
        raise ValueError("db-major q8 fused kernel: int8 mode takes no "
                         "y_lo and requires xxh")
    if M % (tpg * T):
        raise ValueError(
            f"database-major fused kernel: index rows M={M} must be a "
            f"whole number of [tpg·T = {tpg * T}]-row groups — pad the "
            f"index (knn_fused's _prepare_ops does when grid_order is "
            f"'db'/'dbuf')")
    n_groups = M // (tpg * T)
    if dbuf:
        # one cell spans the whole query batch (fold state [Q, 128])
        Qb = Q
    if Q % Qb:
        raise ValueError(f"db-major fused kernel: Q={Q} must be a "
                         f"multiple of Qb={Qb}")
    nq = Q // Qb

    if dbuf:
        grid = (n_groups,)
        x_spec = pl.BlockSpec((Qb, d), lambda s, *_: (0, 0),
                              memory_space=pltpu.VMEM)
        y_spec = pl.BlockSpec(memory_space=pl.ANY)   # manual DMA
        yy_spec = pl.BlockSpec((8, tpg * T), lambda s, *_: (0, s),
                               memory_space=pltpu.VMEM)
        xx_spec = pl.BlockSpec((Qb, 1), lambda s, *_: (0, 0),
                               memory_space=pltpu.VMEM)
        scl_spec = pl.BlockSpec((1, 8, _LANES), lambda s, *_: (s, 0, 0),
                                memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((Qb, _LANES), lambda s, *_: (0, s),
                                memory_space=pltpu.VMEM)
        base = _group_kernel_packed_dbuf_q8 if q8_mode \
            else _group_kernel_packed_dbuf
    else:
        grid = (n_groups, nq)
        x_spec = pl.BlockSpec((Qb, d), lambda s, i, *_: (i, 0),
                              memory_space=pltpu.VMEM)
        # the WHOLE group as one resident block: constant over the
        # inner query loop ⇒ fetched once per group (the stream-once
        # invariant), double-buffered by the standard pipeline
        y_spec = pl.BlockSpec((tpg * T, d), lambda s, i, *_: (s, 0),
                              memory_space=pltpu.VMEM)
        yy_spec = pl.BlockSpec((8, tpg * T), lambda s, i, *_: (0, s),
                               memory_space=pltpu.VMEM)
        xx_spec = pl.BlockSpec((Qb, 1), lambda s, i, *_: (i, 0),
                               memory_space=pltpu.VMEM)
        scl_spec = pl.BlockSpec((1, 8, _LANES),
                                lambda s, i, *_: (s, 0, 0),
                                memory_space=pltpu.VMEM)
        out_spec = pl.BlockSpec((Qb, _LANES), lambda s, i, *_: (i, s),
                                memory_space=pltpu.VMEM)
        base = _group_kernel_packed_db_q8 if q8_mode \
            else _group_kernel_packed_db

    if q8_mode:
        in_specs = [x_spec, y_spec, yy_spec, scl_spec, xx_spec]
        operands = [x, y_hi, yy_half, scale_k, xxh]
        kernel = functools.partial(base, T=T, Qb=Qb, tpg=tpg,
                                   passes=passes, pair=pair, pbits=pbits)
    else:
        in_specs = [x_spec, y_spec, yy_spec]
        operands = [x, y_hi, yy_half]
        if passes == 3:
            in_specs.insert(2, y_spec)                  # y_lo
            operands.insert(2, y_lo)
        if xxh is not None:
            in_specs.append(xx_spec)
            operands.append(xxh)
        kernel = _make_group_kernel(base, passes, T, Qb, tpg=tpg,
                                    has_xxh=xxh is not None,
                                    pair=pair, pbits=pbits)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec] * 3,
    )
    cost = _slot_cost(Q, M, d, n_groups * _LANES, passes)
    if q8_mode:
        # the y stream is 1 byte/element (int8), not bf16 hi(/lo)
        cost = pl.CostEstimate(
            flops=2 * Q * M * d * (2 if passes == 3 else 1),
            bytes_accessed=(Q * d * 4 + M * d
                            + Q * n_groups * _LANES * 8),
            transcendentals=0)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_packed_out_shape(Q, n_groups * _LANES),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
        ),
        cost_estimate=cost,
        interpret=interpret_mode(),
        name=("fused_l2_group_topk_packed_" + ("dbuf" if dbuf else "db")
              + ("_q8" if q8_mode else "")),
    )(m_real, *operands)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg", "pair",
                                    "pbits"))
def fused_l2_group_topk_packed_db(x, y_hi, y_lo, yy_half, m_real,
                                  T: int, Qb: int, passes: int,
                                  tpg: int = 16, pair: bool = False,
                                  pbits: int = _PACK_BITS, xxh=None):
    """Database-major super-blocked packed fused kernel (see the
    DATABASE-MAJOR block comment): same contract and outputs as
    :func:`fused_l2_group_topk_packed`, but the grid is
    ``(n_groups, nq)`` with the whole [tpg·T, d] certificate group
    VMEM-resident — y streams from HBM exactly once instead of
    ``nq`` times. Requires the index padded to whole groups
    (``M % (tpg·T) == 0``) and the packed envelope."""
    return _group_pallas_call_db(False, x, y_hi, y_lo, yy_half, m_real,
                                 T=T, Qb=Qb, passes=passes, tpg=tpg,
                                 pair=pair, pbits=pbits, xxh=xxh)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg", "pair",
                                    "pbits"))
def fused_l2_group_topk_packed_dbuf(x, y_hi, y_lo, yy_half, m_real,
                                    T: int, Qb: int, passes: int,
                                    tpg: int = 16, pair: bool = False,
                                    pbits: int = _PACK_BITS, xxh=None):
    """Explicitly double-buffered database-major packed fused kernel
    (see the DATABASE-MAJOR block comment): y stays in HBM and tiles
    ride a manual 2-slot async-copy pipeline (tile jj+1's DMA issued
    before tile jj's fold), so only two tiles are VMEM-resident and the
    HBM stream overlaps compute at tile granularity. One grid cell
    covers the whole query batch: ``Qb`` is accepted for interface
    parity but the effective query block is the padded Q (the VMEM
    footprint model prices the [Q, 128] fold state — see
    ``vmem_footprint(kernel="stream_dbuf")``)."""
    return _group_pallas_call_db(True, x, y_hi, y_lo, yy_half, m_real,
                                 T=T, Qb=Qb, passes=passes, tpg=tpg,
                                 pair=pair, pbits=pbits, xxh=xxh)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg", "pair",
                                    "pbits"))
def fused_l2_group_topk_packed_db_q8(x, y_q, yy_half, scale_k, m_real,
                                     T: int, Qb: int, passes: int,
                                     tpg: int = 16, pair: bool = False,
                                     pbits: int = _PACK_BITS, xxh=None):
    """INT8 database-major super-blocked packed fused kernel: the
    contract of :func:`fused_l2_group_topk_packed_db` with the database
    streamed as a QUANTIZED int8 slab — M·d·1 bytes instead of
    M·d·2(·2), the quantized-index-streaming tentpole.

    ``y_q`` [M, d] int8 is the per-certificate-group symmetric-scale
    quantization of the index (see knn_fused._prepare_ops_q8);
    ``scale_k`` [n_groups, 8, LANES] f32 carries each group's scale
    replicated to a native tile; ``yy_half`` must hold the DEQUANTIZED
    rows' half-norms (+ the _PACK_PAD sentinel on pads) so folded
    values are exactly d2(x, ŷ)/2 and the codes/certificate decode
    unchanged. ``passes`` splits only the x operand (int8 is exact in
    bf16); ``xxh`` is required."""
    return _group_pallas_call_db(False, x, y_q, None, yy_half, m_real,
                                 T=T, Qb=Qb, passes=passes, tpg=tpg,
                                 pair=pair, pbits=pbits, xxh=xxh,
                                 scale_k=scale_k)


@functools.partial(jax.jit,
                   static_argnames=("T", "Qb", "passes", "tpg", "pair",
                                    "pbits"))
def fused_l2_group_topk_packed_dbuf_q8(x, y_q, yy_half, scale_k, m_real,
                                       T: int, Qb: int, passes: int,
                                       tpg: int = 16, pair: bool = False,
                                       pbits: int = _PACK_BITS,
                                       xxh=None):
    """INT8 explicitly double-buffered database-major packed fused
    kernel: :func:`fused_l2_group_topk_packed_dbuf`'s manual 2-slot DMA
    pipeline moving int8 tiles — same contract as
    :func:`fused_l2_group_topk_packed_db_q8`."""
    return _group_pallas_call_db(True, x, y_q, None, yy_half, m_real,
                                 T=T, Qb=Qb, passes=passes, tpg=tpg,
                                 pair=pair, pbits=pbits, xxh=xxh,
                                 scale_k=scale_k)


def split_hi_lo(y: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Split f32 into bf16 hi + bf16 lo with y ≈ hi + lo (bf16x3 operand
    prep; the dropped lo·lo term is O(2⁻¹⁸·‖x‖‖y‖)).

    The optimization_barrier is LOAD-BEARING: without it, XLA:TPU's
    bf16-propagation pass simplifies the convert/subtract chain so lo
    collapses to ~0 (MEASURED on v5e: split residual 0.062 = one full
    bf16 ulp at 25-magnitude data, i.e. the whole lo term — which
    silently voided the bf16x3 certificate's error bound on
    norm-offset inputs; caught by the hardware fuzz battery, invisible
    to CPU interpret tests)."""
    y = jnp.asarray(y, jnp.float32)
    hi = y.astype(jnp.bfloat16)
    hi_f32 = jax.lax.optimization_barrier(hi).astype(jnp.float32)
    lo = (y - hi_f32).astype(jnp.bfloat16)
    return hi, lo
