"""List-major IVF-PQ ADC scan kernel (Pallas/Mosaic).

The compressed sibling of :mod:`raft_tpu.ops.fine_scan_pallas`: the
grid walks the PROBED LISTS in the same 8-list cells over the same
host-built schedule (``ann.ivf_flat.build_list_schedule`` — reused
verbatim), but the streamed operand is the PRODUCT-QUANTIZED codes
slab (~1/16 of the f32 bytes at 8-bit codes with ``pq_dim = d/4``,
~1/32 at 4-bit) plus the 4-byte ``‖ŷ‖²`` reconstruction-norm and
4-byte per-row quantization-error sidecars, never the f32 rows.

Scoring is asymmetric-distance computation (ADC) by TABLE LOOKUP, the
classic IVF-PQ structure (ref: neighbors/ivf_pq.cuh / cuVS
``ivf_pq::search``) re-shaped for the MXU:

- the per-query lookup table ``lut [nqp, pq_dim·K]`` holds every
  query-to-codeword dot product ``x_s · cb_s[j]`` (``K = 2^pq_bits``)
  — computed ONCE on entry by the caller and held VMEM-RESIDENT for
  the whole cell sweep (the "in-VMEM ADC" of the issue);
- a streamed code block decodes to one-hot lanes (``code == iota`` —
  exact 0/1 in bf16) and ONE hi/lo-split MXU contraction against the
  resident table evaluates every query's ADC sum for every row:
  ``Σ_s lut[q, s, code[w, s]]`` — the gather becomes a matmul, which
  is the only shape a TPU vector unit streams at full rate;
- the residual-coding cross term ``x · c_list`` rides the resident
  per-scheduled-list ``cdot [nqp, Lp]`` table (per query × probed
  list — tiny next to the slab), so the ADC score is exactly

  ``d2(x, ŷ) = ‖x‖² + ‖ŷ‖² − 2·x·c_l − 2·Σ_s x_s·cb_s[code_{w,s}]``

  against the RECONSTRUCTED row ``ŷ = c_l + concat_s cb_s[code]``.

What FOLDS into the pool is the per-row ADAPTIVE certificate score —
the certified true-distance lower bound

  ``lb(x, y) = max(√max(d2(x, ŷ), 0) − Eq_y, 0)²``

where ``Eq_y`` is the row's RECORDED round-trip error bound streamed
from the 4-byte sidecar (``|√d2(x,y) − √d2(x,ŷ)| ≤ ‖y − ŷ‖ ≤ Eq_y``
by the triangle inequality, and ``z ↦ (max(√z − Eq, 0))²`` is
1-Lipschitz so the kernel's own score error passes through
undiminished). The pool therefore ranks rows by how close they COULD
be, and its running rest-min is directly the per-query completeness
bound — no per-list worst-case widening term survives to the caller,
only the kernel-precision envelope.

Masks and outputs follow the fine-scan contract, generalized to a
static ``pool_depth``: probe-table membership + window-column masks to
the never-wins +inf, scores fold into the per-query 128-lane-class
top-``pool_depth`` pools with global slab rows, plus the running
(depth+1)-min certificate input. ``pool_depth=2`` is the ordinary
256-slot pool; the ``pq_widen`` rung re-runs at 4/8 for a 512/1024-
slot pool before the caller escalates to the exact f32 rerun. The
caller (``ann.ivf_pq``) exact-rescores the pooled candidates from the
retained f32 slab — failed queries widen, then rerun the exact f32
scan, so returned ids never degrade (see ``search_ivf_pq``).

4-bit codes stream PACKED (two codes per byte, low nibble = even
subspace) and unpack in-register — the HBM stream is the honest
``pq_dim/2`` bytes per row the cost model prices.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops.fine_scan_pallas import (LISTS_PER_CELL,
                                           _split_hi_lo)
from raft_tpu.ops.utils import interpret_mode

_LANES = 128
_NT = (((1,), (1,)), ((), ()))

#: supported code widths: 4-bit codes pack two per byte
PQ_BITS = (4, 8)

#: supported pool depths (top-N per 128-lane class): 2 is the base
#: 256-slot pool, 4/8 are the pq_widen rungs (512/1024 slots)
PQ_POOL_DEPTHS = (2, 4, 8)


def pq_window(Wk: int) -> int:
    """The DMA window of a probe window ``Wk``: one extra 128-lane
    quantum, because window starts are aligned DOWN to 128 rows (the
    transposed code and sidecar views are sliced along lanes)."""
    return Wk + _LANES


def kernel_rows(R: int) -> int:
    """Row count of the kernel views of an ``R``-row slab (lane-padded
    so every aligned window stays inside)."""
    return -(-R // _LANES) * _LANES


def pq_scan_vmem_footprint(Wk: int, nqp: int, pq_dim: int, K: int,
                           Lp: int, pq_bits: int = 8,
                           pool_depth: int = 2) -> int:
    """Estimated scoped-VMEM bytes of one PQ ADC cell at probe window
    ``Wk``: 2 DMA slots for the transposed code window and the two f32
    sidecar rows (sublane-padded), the resident ADC table (f32 + its
    bf16 hi/lo split), the resident probe + centroid-dot tables, the
    one-hot staging block of one subspace group (x2 for the compare
    temporaries), ~3 live [nqp, Wa] f32 score temporaries and the
    (2·depth+1)-buffer fold state. Checked against described-v5e
    compiles in tests/test_tpu_aot.py, not calibrated on a chip."""
    Wa = pq_window(Wk)
    code_rows = -(-_code_rows(pq_dim, pq_bits) // 32) * 32
    bytes_ = 2 * code_rows * Wa                  # 2 code DMA slots
    bytes_ += 2 * 2 * 8 * Wa * 4                 # 2×(‖ŷ‖², Eq) DMA slots
    bytes_ += nqp * pq_dim * K * (4 + 2 + 2)     # lut f32 + hi/lo bf16
    bytes_ += nqp * _LANES * 4                   # probe table
    bytes_ += nqp * Lp * 4 * 2                   # x·c table + lane mask
    bytes_ += 2 * _onehot_rows(K) * Wa * 2       # one-hot group (bf16)
    bytes_ += 3 * nqp * Wa * 4                   # d2/lb + temporaries
    bytes_ += (2 * pool_depth + 1) * nqp * _LANES * 4 * 2  # fold state
    return bytes_


def _code_rows(pq_dim: int, pq_bits: int) -> int:
    return pq_dim if pq_bits == 8 else -(-pq_dim // 2)


#: one-hot rows (subspace × codeword) contracted per MXU step: bounds
#: the [rows, Wa] bf16 staging block whatever pq_dim·K is
_ONEHOT_ROWS = 512


def _onehot_rows(K: int) -> int:
    return max(1, _ONEHOT_ROWS // K) * K


def kernel_layout(codes, yy_pq, eq_rows):
    """The kernel views of a PQ slab: codes transposed to
    ``[code rows (padded to 32), R']`` int8 and the two sidecars as
    ``[1, R']`` f32 rows, ``R' = kernel_rows(R)`` (pad columns zero).
    Mosaic DMAs a window of these along lanes; the row-major [R, 32]
    codes and [R, 1] sidecars are narrower than one lane tile, which
    it cannot slice. Built once per index (``IvfPqIndex``)."""
    R = codes.shape[0]
    pad = kernel_rows(R) - R
    rows = -(-codes.shape[1] // 32) * 32
    ct = jnp.pad(jnp.asarray(codes, jnp.int8).T,
                 ((0, rows - codes.shape[1]), (0, pad)))
    yy = jnp.pad(jnp.reshape(jnp.asarray(yy_pq, jnp.float32), (1, -1)),
                 ((0, 0), (0, pad)))
    eq = jnp.pad(jnp.reshape(jnp.asarray(eq_rows, jnp.float32), (1, -1)),
                 ((0, 0), (0, pad)))
    return ct, yy, eq


def _pq_pool_out_shape(nqp: int, depth: int):
    """``depth`` (score, row) pool pairs + the running rest-min."""
    out = []
    for _ in range(depth):
        out.append(jax.ShapeDtypeStruct((nqp, _LANES), jnp.float32))
        out.append(jax.ShapeDtypeStruct((nqp, _LANES), jnp.int32))
    out.append(jax.ShapeDtypeStruct((nqp, _LANES), jnp.float32))
    return out


def _fold_pool_deep(acc, d2, base_row, nqp: int, Wk: int, depth: int):
    """Fold a masked [nqp, Wk] score window into the per-query
    ``depth``-deep 128-lane-class pool — the fine-scan ``_fold_pool``
    insertion cascade generalized from top-2 to top-``depth``, plus
    the running (depth+1)-min (certificate input — every row outside a
    lane's top-``depth`` scored ≥ that lane's rest-min). ``acc`` is
    the flat ``(a_1, i_1, …, a_depth, i_depth, rest)`` tuple."""
    a = [acc[2 * t] for t in range(depth)]
    i = [acc[2 * t + 1] for t in range(depth)]
    rest = acc[2 * depth]
    lane = jax.lax.broadcasted_iota(jnp.int32, (nqp, _LANES), 1)
    for r in range(Wk // _LANES):
        c = d2[:, r * _LANES:(r + 1) * _LANES]
        ci = base_row + r * _LANES + lane
        lt = [c < a[t] for t in range(depth)]
        lt_rest = c < rest
        rest = jnp.where(lt[depth - 1], a[depth - 1],
                         jnp.where(lt_rest, c, rest))
        for t in range(depth - 1, 0, -1):
            a[t] = jnp.where(lt[t - 1], a[t - 1],
                             jnp.where(lt[t], c, a[t]))
            i[t] = jnp.where(lt[t - 1], i[t - 1],
                             jnp.where(lt[t], ci, i[t]))
        a[0] = jnp.where(lt[0], c, a[0])
        i[0] = jnp.where(lt[0], ci, i[0])
    out = []
    for t in range(depth):
        out += [a[t], i[t]]
    return tuple(out) + (rest,)


def _decode_subspaces(codes, pq_dim: int, pq_bits: int):
    """Per-subspace ``[1, Wa]`` int32 code rows of a streamed window of
    the transposed codes view. 8-bit codes are stored BIASED
    (code − 128) so the full 0..255 range fits int8; 4-bit codes are
    packed two per byte (low nibble = even subspace) and unpack with
    pure arithmetic — no bitwise ops on the possibly-negative int8
    lanes."""
    v = codes.astype(jnp.int32)
    if pq_bits == 8:
        return [v[s:s + 1, :] + 128 for s in range(pq_dim)]
    vu = jnp.where(v < 0, v + 256, v)
    cols = []
    for s in range(pq_dim):
        byte = vu[s // 2:s // 2 + 1, :]
        cols.append(byte % 16 if s % 2 == 0 else byte // 16)
    return cols


def _adc_scores(lut_hi, lut_lo, codes, pq_dim: int, K: int,
                pq_bits: int, Wa: int):
    """``Σ_s lut[q, s, code[s, w]]`` for every (query, row) of one
    window — the table gather evaluated as one-hot MXU contractions
    (one-hot lanes are exact in bf16, so only the hi/lo split of the
    table itself carries rounding), one subspace group of
    ``_onehot_rows(K)`` one-hot rows at a time."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (K, Wa), 0)
    cols = _decode_subspaces(codes, pq_dim, pq_bits)
    G = _onehot_rows(K) // K
    acc = None
    for s0 in range(0, pq_dim, G):
        grp = range(s0, min(s0 + G, pq_dim))
        onehot = jnp.concatenate(
            [(cols[t] == iota).astype(jnp.bfloat16) for t in grp],
            axis=0)                                 # [|grp|·K, Wa]
        lo, hi = s0 * K, (s0 + len(grp)) * K
        part = jnp.dot(lut_hi[:, lo:hi], onehot,
                       preferred_element_type=jnp.float32)
        part = part + jnp.dot(lut_lo[:, lo:hi], onehot,
                              preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    return acc                                      # [nqp, Wa]


def _pq_kernel_body(sched_ref, xx_ref, probes_ref, cdot_ref, lut_ref,
                    codes_ref, yy_ref, eq_ref, *out_refs, Wa: int,
                    pq_dim: int, K: int, pq_bits: int, depth: int):
    """One grid cell: stream LISTS_PER_CELL probed lists' 128-aligned
    code windows of ``Wa`` lanes (+ the norm and error sidecar rows)
    through the 2-slot DMA pipeline,
    evaluate the ADC scores against the resident lookup table, subtract
    each row's recorded error bound into the certified lower-bound
    score, mask non-member queries / out-of-window columns to +inf and
    fold into the revisited per-query pools."""
    s = pl.program_id(0)
    nqp = xx_ref.shape[0]
    inf = jnp.full((nqp, _LANES), jnp.inf, jnp.float32)
    neg1 = jnp.full((nqp, _LANES), -1, jnp.int32)

    @pl.when(s == 0)
    def _():
        for t in range(depth):
            out_refs[2 * t][...] = inf
            out_refs[2 * t + 1][...] = neg1
        out_refs[2 * depth][...] = inf

    def body(cscratch, yscratch, escratch, csem, ysem, esem):
        def dma(slot, j):
            # the wrapper aligned every window start to 128 lanes
            st = pl.multiple_of(sched_ref[0, j], _LANES)
            return (pltpu.make_async_copy(
                codes_ref.at[:, pl.ds(st, Wa)],
                cscratch.at[slot], csem.at[slot]),
                pltpu.make_async_copy(
                    yy_ref.at[:, pl.ds(st, Wa)],
                    yscratch.at[slot], ysem.at[slot]),
                pltpu.make_async_copy(
                    eq_ref.at[:, pl.ds(st, Wa)],
                    escratch.at[slot], esem.at[slot]))

        def start(slot, j):
            for cp in dma(slot, j):
                cp.start()

        def wait(slot, j):
            for cp in dma(slot, j):
                cp.wait()

        j0 = s * LISTS_PER_CELL
        start(0, j0)
        xx = xx_ref[...]                                 # [nqp, 1]
        probes = probes_ref[...]                         # [nqp, Pp]
        cdot = cdot_ref[...]                             # [nqp, Lp]
        lut_hi, lut_lo = _split_hi_lo(lut_ref[...])      # [nqp, S·K]
        colv = jax.lax.broadcasted_iota(jnp.int32, (nqp, Wa), 1)
        lanes_l = jax.lax.broadcasted_iota(jnp.int32, cdot.shape, 1)
        def one_list(jj, acc):
            # a rolled loop: the unrolled 8-list cell took minutes to
            # compile at the SIFT-1M window
            j = j0 + jj
            slot = jj % 2

            @pl.when(jj + 1 < LISTS_PER_CELL)
            def _():
                start(1 - slot, j + 1)                   # prefetch next

            wait(slot, j)
            st = sched_ref[0, j]
            lsize = sched_ref[1, j]
            off = sched_ref[2, j]
            lid = sched_ref[3, j]
            adc = _adc_scores(lut_hi, lut_lo, cscratch[slot], pq_dim,
                              K, pq_bits, Wa)
            yyw = yscratch[slot]                         # [1, Wa] ‖ŷ‖²
            eqw = escratch[slot]                         # [1, Wa] Eq_row
            # column j of the centroid-dot table by a lane mask: Mosaic
            # has no dynamic_slice on values
            qc = jnp.sum(jnp.where(lanes_l == j, cdot, 0.0), axis=1,
                         keepdims=True)                  # [nqp, 1]
            d2 = xx + yyw - 2.0 * qc - 2.0 * adc
            # the certified lower bound on the TRUE distance: pull the
            # ADC score toward 0 by the row's recorded round-trip
            # error (triangle inequality on the norms; 1-Lipschitz in
            # the score, so the kernel-precision envelope carries over
            # unchanged) — +inf masks propagate through the sqrt
            rad = jnp.sqrt(jnp.maximum(d2, 0.0))
            lb = jnp.maximum(rad - eqw, 0.0) ** 2
            member = jnp.sum((probes == lid).astype(jnp.float32),
                             axis=1, keepdims=True)      # [nqp, 1]
            lb = jnp.where(member > 0.0, lb, jnp.inf)
            valid = (colv >= off) & (colv < off + lsize)
            lb = jnp.where(valid, lb, jnp.inf)
            return _fold_pool_deep(acc, lb, st, nqp, Wa, depth)

        acc = jax.lax.fori_loop(0, LISTS_PER_CELL, one_list,
                                tuple(ref[...] for ref in out_refs))
        for t, ref in enumerate(out_refs):
            ref[...] = acc[t]

    pl.run_scoped(
        body,
        cscratch=pltpu.VMEM((2, codes_ref.shape[0], Wa), jnp.int8),
        yscratch=pltpu.VMEM((2, 1, Wa), jnp.float32),
        escratch=pltpu.VMEM((2, 1, Wa), jnp.float32),
        csem=pltpu.SemaphoreType.DMA((2,)),
        ysem=pltpu.SemaphoreType.DMA((2,)),
        esem=pltpu.SemaphoreType.DMA((2,)))


@functools.partial(jax.jit,
                   static_argnames=("Wk", "pq_bits", "pool_depth"))
def pq_scan_list_major(sched, xx, probes, cdot, lut, codes, yy_pq,
                       eq_rows, Wk: int, pq_bits: int = 8,
                       pool_depth: int = 2) -> Tuple[jax.Array, ...]:
    """List-major ADC scan over the product-quantized codes slab.

    Args:
      sched: [4, Lp] int32 schedule rows — exactly
        ``ann.ivf_flat.build_list_schedule``'s output (window start,
        real length, in-window offset, list id; pads ``(0,0,0,−1)``).
      xx: [nqp, 1] exact f32 query squared norms (nqp a multiple of 8).
      probes: [nqp, 128] int32 probe table (pads −2).
      cdot: [nqp, Lp] f32 per-(query, scheduled list) centroid dot
        products ``x · c_{lid(j)}`` (column j pairs with schedule
        column j; pad-list columns are never read through the mask).
      lut: [nqp, pq_dim·K] f32 ADC table — ``lut[q, s·K + j] =
        x_{q,s} · cb_s[j]`` flattened subspace-major.
      codes, yy_pq, eq_rows: the :func:`kernel_layout` views of the
        slab — transposed int8 codes ``[rows, R']`` (8-bit: biased
        code−128 per subspace row; 4-bit: packed nibble pairs), the
        reconstructed row norms ``‖ŷ‖²`` and the recorded per-row
        round-trip error bounds ``‖y − ŷ‖`` (the adaptive-certificate
        sidecar) as ``[1, R']`` rows; pad columns zero.
      Wk: static probe window, a multiple of 128. Each window start is
        aligned down to 128 here and the window widened to
        :func:`pq_window` lanes, the in-window offsets shifted to
        match.
      pq_bits: 4 or 8 (static — decides the decode path).
      pool_depth: static per-lane-class pool depth ∈ (2, 4, 8) —
        2 is the base 256-slot pool, 4/8 the ``pq_widen`` rungs.

    Returns:
      (a_1, i_1, …, a_depth, i_depth, rest): [nqp, 128] per-lane-class
      top-``pool_depth`` certified-lower-bound scores with GLOBAL slab
      rows, plus the running rest-min certificate input.
    """
    if Wk % _LANES:
        raise ValueError(f"pq_scan_list_major: Wk={Wk} must be a "
                         f"multiple of {_LANES}")
    if pq_bits not in PQ_BITS:
        raise ValueError(f"pq_scan_list_major: pq_bits must be one of "
                         f"{PQ_BITS}, got {pq_bits}")
    if pool_depth not in PQ_POOL_DEPTHS:
        raise ValueError(f"pq_scan_list_major: pool_depth must be one "
                         f"of {PQ_POOL_DEPTHS}, got {pool_depth}")
    Lp = sched.shape[1]
    if Lp % LISTS_PER_CELL:
        raise ValueError(f"pq_scan_list_major: schedule length {Lp} "
                         f"must be a multiple of {LISTS_PER_CELL}")
    nqp = xx.shape[0]
    K = 1 << pq_bits
    pq_dim = lut.shape[1] // K
    if lut.shape[1] != pq_dim * K or \
            codes.shape[0] < _code_rows(pq_dim, pq_bits):
        raise ValueError(f"pq_scan_list_major: lut width "
                         f"{lut.shape[1]} / code rows {codes.shape[0]} "
                         f"do not describe pq_dim·K with K={K}")
    Wa = pq_window(Wk)
    R = codes.shape[1]
    if R % _LANES or R < Wa:
        raise ValueError(f"pq_scan_list_major: kernel view of {R} rows "
                         f"must be a multiple of {_LANES} and cover the "
                         f"window {Wa}")
    start = sched[0]
    ws = jnp.maximum(jnp.minimum(start // _LANES * _LANES, R - Wa), 0)
    sched = sched.at[0].set(ws).at[2].add(start - ws)

    def kernel(sched_ref, xx_ref, probes_ref, cdot_ref, lut_ref,
               codes_ref, yy_ref, eq_ref, *out_refs):
        _pq_kernel_body(sched_ref, xx_ref, probes_ref, cdot_ref,
                        lut_ref, codes_ref, yy_ref, eq_ref, *out_refs,
                        Wa=Wa, pq_dim=pq_dim, K=K, pq_bits=pq_bits,
                        depth=pool_depth)

    n_cells = Lp // LISTS_PER_CELL
    n_out = 2 * pool_depth + 1
    out_spec = pl.BlockSpec((nqp, _LANES), lambda s, *_: (0, 0),
                            memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_cells,),
        in_specs=[
            pl.BlockSpec((nqp, 1), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),           # xx
            pl.BlockSpec((nqp, _LANES), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),           # probes
            pl.BlockSpec((nqp, Lp), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),           # cdot
            pl.BlockSpec((nqp, pq_dim * K), lambda s, *_: (0, 0),
                         memory_space=pltpu.VMEM),           # lut
            pl.BlockSpec(memory_space=pl.ANY),            # codes DMA
            pl.BlockSpec(memory_space=pl.ANY),            # yy DMA
            pl.BlockSpec(memory_space=pl.ANY),            # eq DMA
        ],
        out_specs=[out_spec] * n_out,
    )
    L = n_cells * LISTS_PER_CELL
    cost = pl.CostEstimate(
        # 2 hi/lo ADC contractions over the pq_dim·K one-hot lanes
        flops=2 * nqp * L * Wa * pq_dim * K * 2,
        bytes_accessed=(L * Wa * (codes.shape[0] + 8)
                        + nqp * pq_dim * K * 4
                        + nqp * _LANES * 8 * n_out),
        # one sqrt per (query, streamed row) for the certified bound
        transcendentals=nqp * L * Wa,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_pq_pool_out_shape(nqp, pool_depth),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=cost,
        interpret=interpret_mode(),
    )(sched, xx, probes, cdot, lut, codes, yy_pq, eq_rows)
