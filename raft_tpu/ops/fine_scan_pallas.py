"""List-major IVF fine-scan kernels (Pallas/Mosaic).

The inverted-index batching trade applied to the TPU streaming kernels:
the query-major fine scan (`raft_tpu.ann.ivf_flat._fine_scan`) gathers
each query's probe windows independently, so a hot list probed by q
queries is read q times from HBM — the exact nq× re-read pathology the
PR-3 database-major grid re-order removed from brute force, recorded
per frontier point as ``gather_overread`` by
:func:`raft_tpu.observability.costmodel.ivf_traffic_model`.

These kernels invert the schedule: the grid walks the PROBED LISTS
(8 lists per cell — the schedule builder buckets the probed-list table
to the 8-row quantum; the grid's cell count is a traced operand, so one
compiled program serves every schedule of one query block), each cell
streams its lists' slab windows from HBM ONCE through a manual 2-slot
double-buffered DMA pipeline (the ``_group_kernel_packed_dbuf`` idiom)
while the WHOLE query block stays VMEM-resident, and a per-(query,
list) membership test against the resident probe table masks queries
that did not probe the list to the never-wins +inf. Every scored row
folds into a
per-query 128-slot candidate pool (per lane-class top-2 values + global
slab-row ids, plus the running 3rd-min — the same certificate shape the
fused brute kernels carry): outputs are revisited [nqp, 128] blocks, so
HBM sees each probed list once and the pools once.

Scores are APPROXIMATE (bf16 hi/lo MXU contraction; the int8 variant
reuses the PR-9 dequant-in-register idea — per-list scale applied to
the accumulated quantized partials, never a widened copy in VMEM). The
caller exact-rescores the pooled candidates from the f32 slab with the
query-major scorer's own formula and certifies completeness via the
pooled 3rd-min (`a3`): every probed row outside the pool scored ≥ its
slot's a3 ≥ min-over-slots a3, so
``min_slots a3 ≥ θ + (kernel-precision + quantization envelope)``
proves the true top-k cannot hide outside the pool. Failed queries
rerun the query-major scan — returned f32 ids are therefore
BIT-IDENTICAL to the query-major oracle in every case, and int8 id
SETS are identical (the quantized gather's own ordering of exact f32
value ties is quantization-noise-dependent — the PR-9 contract; see
``ann.ivf_flat._fine_scan_list``).

In-kernel norms: the slab tile's row norms are contracted on the MXU
(``ones · split_hi_lo(y²)`` — two extra passes) instead of streaming a
precomputed carrier; the 2⁻¹⁶-grade reconstruction error is part of the
certificate envelope, and the HBM stream stays exactly the slab bytes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.utils import interpret_mode, round_up

_LANES = 128
#: lists per grid cell — the schedule builder pads the probed-list
#: table to a multiple of this (the 8-row sublane quantum)
LISTS_PER_CELL = 8
#: per-query candidate pool width: 128 lane-class slots × top-2
POOL_SLOTS = _LANES
POOL_WIDTH = 2 * POOL_SLOTS


def fine_scan_vmem_footprint(Wk: int, nqp: int, d: int,
                             q8: bool = False) -> int:
    """Estimated scoped-VMEM bytes of one list-major fine-scan cell:
    2 DMA window slots (f32 or int8), the resident query block (f32 +
    the bf16 hi/lo split), the resident probe table, ~3 live [nqp, Wk]
    f32 score temporaries (d2 + mask/select intermediates), and the
    5-buffer fold state. Conservative: at the SIFT-1M window (Wk=2048,
    d=128) it admits nqp ≤ 256, and nqp 16–256 compile for a described
    v5e, f32 and int8 (tests/test_tpu_aot.py); no reject has calibrated
    it."""
    bytes_ = 2 * Wk * d * (1 if q8 else 4)        # 2 DMA window slots
    bytes_ += nqp * d * (4 + 2 + 2)               # x f32 + hi/lo bf16
    bytes_ += nqp * _LANES * 4                    # probe table (Pp=128)
    bytes_ += 3 * nqp * Wk * 4                    # d2 + temporaries
    bytes_ += Wk * d * (4 + 2 + 2)                # y², y² hi/lo split
    bytes_ += 5 * nqp * _LANES * 4 * 2            # fold state + temps
    return bytes_


def _split_hi_lo(v):
    """bf16 hi/lo split of an f32 value (reconstruction error ≤ 2⁻¹⁶
    relative — the certificate envelope's kernel-precision term)."""
    hi = v.astype(jnp.bfloat16)
    lo = (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


_NT = (((1,), (1,)), ((), ()))


def _scores_f32(xhi, xlo, ones_b, y):
    """Approximate ``yy − 2·x·y`` for an f32 y window: bf16x3 MXU
    contraction for the cross term plus two ``ones · split(y²)`` passes
    for the row norms — the norm rides the MXU so nothing but the slab
    itself streams from HBM."""
    yhi, ylo = _split_hi_lo(y)
    s = jax.lax.dot_general(xhi, yhi, _NT,
                            preferred_element_type=jnp.float32)
    s = s + jax.lax.dot_general(xhi, ylo, _NT,
                                preferred_element_type=jnp.float32)
    s = s + jax.lax.dot_general(xlo, yhi, _NT,
                                preferred_element_type=jnp.float32)
    y2hi, y2lo = _split_hi_lo(y * y)
    yy = jax.lax.dot_general(ones_b, y2hi, _NT,
                             preferred_element_type=jnp.float32)
    yy = yy + jax.lax.dot_general(ones_b, y2lo, _NT,
                                  preferred_element_type=jnp.float32)
    return yy - 2.0 * s


def _scores_q8(xhi, xlo, ones_b, yq, scale, passes: int):
    """Approximate ``‖ŷ‖² − 2·x·ŷ`` for an int8 window with per-list
    symmetric scale (ŷ = scale·yq): int8 magnitudes ≤ 127 are EXACT in
    bf16, so only x carries rounding (halved by the passes=3 x_lo pass,
    exactly :func:`ops.fused_l2_topk_pallas._contract_q8`'s argument);
    the scale rescales the ACCUMULATED partials in-register — the
    dequant-in-register path, never a widened copy in VMEM."""
    yqb = yq.astype(jnp.bfloat16)
    s = jax.lax.dot_general(xhi, yqb, _NT,
                            preferred_element_type=jnp.float32)
    if passes == 3:
        s = s + jax.lax.dot_general(xlo, yqb, _NT,
                                    preferred_element_type=jnp.float32)
    yqf = yq.astype(jnp.float32)
    y2hi, y2lo = _split_hi_lo(yqf * yqf)
    yy = jax.lax.dot_general(ones_b, y2hi, _NT,
                             preferred_element_type=jnp.float32)
    yy = yy + jax.lax.dot_general(ones_b, y2lo, _NT,
                                  preferred_element_type=jnp.float32)
    return (scale * scale) * yy - 2.0 * scale * s


def _fold_pool(acc, d2, base_row, nqp: int, Wk: int):
    """Fold a masked [nqp, Wk] score window into the per-query 128-slot
    pool: per lane class the two smallest scores with their GLOBAL slab
    rows, plus the running 3rd-min (certificate input — every row
    outside a slot's top-2 scored ≥ that slot's a3)."""
    a1, i1, a2, i2, a3 = acc
    lane = jax.lax.broadcasted_iota(jnp.int32, (nqp, _LANES), 1)
    for r in range(Wk // _LANES):
        c = d2[:, r * _LANES:(r + 1) * _LANES]
        ci = base_row + r * _LANES + lane
        lt1 = c < a1
        lt2 = c < a2
        lt3 = c < a3
        a3 = jnp.where(lt2, a2, jnp.where(lt3, c, a3))
        a2 = jnp.where(lt1, a1, jnp.where(lt2, c, a2))
        i2 = jnp.where(lt1, i1, jnp.where(lt2, ci, i2))
        a1 = jnp.where(lt1, c, a1)
        i1 = jnp.where(lt1, ci, i1)
    return a1, i1, a2, i2, a3


def _list_kernel_body(sched_ref, scale_ref, x_ref, xx_ref, probes_ref,
                      slab_ref, a1_ref, i1_ref, a2_ref, i2_ref, a3_ref,
                      *, Wk: int, q8: bool, passes: int):
    """One grid cell: stream LISTS_PER_CELL probed lists' windows
    through the 2-slot DMA pipeline, score the resident query block
    against each, mask non-member queries (probe-table comparison) and
    out-of-list window columns to the never-wins +inf, and fold into
    the revisited per-query pools."""
    s = pl.program_id(0)
    nqp, d = x_ref.shape
    inf = jnp.full((nqp, _LANES), jnp.inf, jnp.float32)
    neg1 = jnp.full((nqp, _LANES), -1, jnp.int32)

    @pl.when(s == 0)
    def _():
        a1_ref[...] = inf
        i1_ref[...] = neg1
        a2_ref[...] = inf
        i2_ref[...] = neg1
        a3_ref[...] = inf

    def body(scratch, sem):
        def dma(slot, j):
            start = sched_ref[0, j]
            if q8:
                # the int8 slab's HBM tiling is 8 rows: Mosaic slices it
                # only at a start it can prove 8-aligned, which every
                # window start is at a row quantum of 8 (the caller's
                # envelope)
                start = pl.multiple_of(start, 8)
            return pltpu.make_async_copy(
                slab_ref.at[pl.ds(start, Wk), :],
                scratch.at[slot], sem.at[slot])

        j0 = s * LISTS_PER_CELL
        dma(0, j0).start()
        x = x_ref[...]
        xx = xx_ref[...]                                    # [nqp, 1]
        probes = probes_ref[...]                            # [nqp, Pp]
        xhi, xlo = _split_hi_lo(x)
        ones_b = jnp.ones((nqp, d), jnp.bfloat16)
        colv = jax.lax.broadcasted_iota(jnp.int32, (nqp, Wk), 1)
        acc = (a1_ref[...], i1_ref[...], a2_ref[...], i2_ref[...],
               a3_ref[...])
        for jj in range(LISTS_PER_CELL):
            j = j0 + jj
            slot = jj % 2
            if jj + 1 < LISTS_PER_CELL:
                dma((jj + 1) % 2, j + 1).start()         # prefetch next
            dma(slot, j).wait()
            st = sched_ref[0, j]
            lsize = sched_ref[1, j]
            off = sched_ref[2, j]
            lid = sched_ref[3, j]
            y = scratch[slot]
            if q8:
                r = _scores_q8(xhi, xlo, ones_b, y, scale_ref[j],
                               passes)
            else:
                r = _scores_f32(xhi, xlo, ones_b, y)
            d2 = xx + r
            # never-wins masks: queries whose probe table does not
            # contain this list, and window columns outside the list's
            # real rows (quantum pads, clamp slack, empty pad cells)
            member = jnp.sum((probes == lid).astype(jnp.float32),
                             axis=1, keepdims=True)         # [nqp, 1]
            d2 = jnp.where(member > 0.0, d2, jnp.inf)
            valid = (colv >= off) & (colv < off + lsize)
            d2 = jnp.where(valid, d2, jnp.inf)
            acc = _fold_pool(acc, d2, st, nqp, Wk)
        a1_ref[...], i1_ref[...], a2_ref[...], i2_ref[...], \
            a3_ref[...] = acc

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((2, Wk, x_ref.shape[1]),
                           jnp.int8 if q8 else jnp.float32),
        sem=pltpu.SemaphoreType.DMA((2,)))


def _pool_out_shape(nqp: int):
    return [
        jax.ShapeDtypeStruct((nqp, POOL_SLOTS), jnp.float32),  # a1
        jax.ShapeDtypeStruct((nqp, POOL_SLOTS), jnp.int32),    # i1
        jax.ShapeDtypeStruct((nqp, POOL_SLOTS), jnp.float32),  # a2
        jax.ShapeDtypeStruct((nqp, POOL_SLOTS), jnp.int32),    # i2
        jax.ShapeDtypeStruct((nqp, POOL_SLOTS), jnp.float32),  # a3
    ]


def _fine_scan_pallas_call(kernel, n_prefetch: int, n_cells, Lp: int,
                           nqp: int, Wk: int, d: int, q8: bool,
                           operands):
    """The list-major pallas_call over ``n_cells`` grid cells (a traced
    bound: one compiled kernel serves every schedule length up to
    ``Lp``); its op name in a device trace is ``fine_scan_list_major``
    (``_q8`` for the int8 slab)."""
    out_spec = pl.BlockSpec((nqp, POOL_SLOTS), lambda s, *_: (0, 0),
                            memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(n_cells,),
        in_specs=[
            pl.BlockSpec((nqp, d), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),          # x
            pl.BlockSpec((nqp, 1), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),          # xx
            pl.BlockSpec((nqp, _LANES), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),          # probes
            pl.BlockSpec(memory_space=pl.ANY),           # slab (DMA)
        ],
        out_specs=[out_spec] * 5,
    )
    L = Lp            # the most the grid can stream
    cost = pl.CostEstimate(
        # 3 bf16 cross passes + 2 norm passes (q8: ≤ 2 + 2)
        flops=2 * nqp * L * Wk * d * (4 if q8 else 5),
        bytes_accessed=(L * Wk * d * (1 if q8 else 4) + nqp * d * 4
                        + nqp * POOL_SLOTS * 8 * 5),
        transcendentals=0)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_pool_out_shape(nqp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=cost,
        interpret=interpret_mode(),
        name="fine_scan_list_major_q8" if q8 else "fine_scan_list_major",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("Wk",))
def fine_scan_list_major(sched, n_cells, x, xx, probes, slab, Wk: int
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array, jax.Array]:
    """List-major fine scan over the f32 slab.

    Args:
      sched: [4, Lp] int32 schedule rows — (window start row, real list
        length, list-start offset within the window, list id); Lp a
        multiple of :data:`LISTS_PER_CELL`; pad entries carry
        ``(0, 0, 0, -1)``. Window starts are clamp-adjusted by the
        schedule builder so every [start, start+Wk) window stays inside
        the slab.
      n_cells: int32 scalar, the grid cells to stream (the first
        ``n_cells·LISTS_PER_CELL`` entries, ≤ Lp); traced, so one
        compiled program serves every schedule a query block can have.
      x: [nqp, d] f32 resident query block (nqp a multiple of 8; pad
        rows zero).
      xx: [nqp, 1] exact f32 query squared norms.
      probes: [nqp, 128] int32 probe table (each query's probed list
        ids; pads −2 — they never match a list id, and pad LISTS carry
        id −1, which never matches a real probe).
      slab: [R, d] f32 padded ragged slab (R ≥ Wk).
      Wk: static window length, a multiple of 128 covering the index's
        probe window.

    Returns:
      (a1, i1, a2, i2, a3): [nqp, 128] per-lane-class top-2 approximate
      squared distances ``xx + yy − 2·x·y`` with GLOBAL slab-row ids
      (−1 = empty), and the running 3rd-min certificate input.
      Never-probed/pad entries stay (+inf, −1).
    """
    if Wk % _LANES:
        raise ValueError(f"fine_scan_list_major: Wk={Wk} must be a "
                         f"multiple of {_LANES}")
    Lp = sched.shape[1]
    if Lp % LISTS_PER_CELL:
        raise ValueError(f"fine_scan_list_major: schedule length {Lp} "
                         f"must be a multiple of {LISTS_PER_CELL}")
    nqp, d = x.shape

    def kernel_nq8(sched_ref, x_ref, xx_ref, probes_ref, slab_ref,
                   *out_refs):
        _list_kernel_body(sched_ref, None, x_ref, xx_ref, probes_ref,
                          slab_ref, *out_refs, Wk=Wk, q8=False,
                          passes=3)

    return _fine_scan_pallas_call(
        kernel_nq8, 1, n_cells, Lp, nqp, Wk, d, False,
        (sched, x, xx, probes, slab))


@functools.partial(jax.jit, static_argnames=("Wk", "passes"))
def fine_scan_list_major_q8(sched, scale_l, n_cells, x, xx, probes,
                            slab_q, Wk: int, passes: int = 3
                            ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                       jax.Array, jax.Array]:
    """INT8 list-major fine scan: same schedule/pool contract as
    :func:`fine_scan_list_major`, but the streamed window is the
    quantized slab (~¼ the probed bytes) and ``scale_l`` [Lp] f32
    carries each probed list's symmetric scale, applied to the
    accumulated partials in-register (the PR-9 ``_contract_q8``
    dequant-in-register path). Scores approximate ``‖ŷ‖² − 2·x·ŷ``
    against the dequantized rows ŷ — the caller's certificate widens by
    the recorded per-list Eq bound exactly like the query-major
    ``_fine_scan_q8``."""
    if Wk % _LANES:
        raise ValueError(f"fine_scan_list_major_q8: Wk={Wk} must be a "
                         f"multiple of {_LANES}")
    Lp = sched.shape[1]
    if Lp % LISTS_PER_CELL:
        raise ValueError(f"fine_scan_list_major_q8: schedule length "
                         f"{Lp} must be a multiple of {LISTS_PER_CELL}")
    nqp, d = x.shape

    def kernel_q8(sched_ref, scale_ref, x_ref, xx_ref, probes_ref,
                  slab_ref, *out_refs):
        _list_kernel_body(sched_ref, scale_ref, x_ref, xx_ref,
                          probes_ref, slab_ref, *out_refs, Wk=Wk,
                          q8=True, passes=passes)

    return _fine_scan_pallas_call(
        kernel_q8, 2, n_cells, Lp, nqp, Wk, d, True,
        (sched, scale_l, x, xx, probes, slab_q))


#: the most rows of one list a schedule entry covers: a longer list
#: streams as several entries (same list id, consecutive windows), so
#: the kernel window — and its scoped VMEM — stays bounded whatever the
#: list-size skew (a 7k+-row list at the SIFT-1M smoke shape overran
#: the 15 MiB budget)
SEGMENT_ROWS = 2048


def pad_window(W: int) -> int:
    """The kernel window for a probe window ``W``: capped at
    :data:`SEGMENT_ROWS` and rounded up to the 128-lane quantum (the
    fold iterates lane chunks)."""
    return round_up(min(max(W, 1), SEGMENT_ROWS), _LANES)
