"""Streaming Pallas kernels for UNEXPANDED pairwise metrics.

(ref: the contraction tiling substrate serves every metric on the GPU —
cpp/include/raft/linalg/detail/contractions.cuh:313 keeps x/y tiles in
smem and accumulates [tile, tile] registers for L1/Linf/Canberra/… the
same way it does for L2. This kernel is that substrate's TPU role: the
|x−y| forms never touch HBM at [n, m, d] scale — terms are formed on
VMEM-resident tiles and fold into [Qb, 128] accumulators.)

TPU-first shape of the problem: unexpanded metrics have no matmul form,
so the O(n·m·d) per-feature terms run on the VPU — the performance
ceiling is the VPU's elementwise rate, not HBM or the MXU (measured
attribution lives in BENCH_UNEXPANDED.json). The kernel's job is to hit
that ceiling: stream y tiles through VMEM once per query block, keep
accumulators in VMEM, and let the two Mosaic-legal broadcast idioms do
the outer [Qb] × [128] pairing:

- the y feature row arrives as ``dc`` separate FULL-BLOCK ``(1, 128)``
  refs (block index maps select the feature) — offset-0 loads whose
  sublane broadcast Mosaic lowers natively (the SpMV kernels' idiom;
  a SLICED [1, N] broadcast is an invalid layout, measured round 2);
- the x column broadcast across lanes rides the MXU: a one-hot
  selector matmul ``x_split [Qb, 3·dc] @ OH_f [3·dc, 128]`` both
  SELECTS feature f and SUMS the exact bf16x3 split (hi+mid+lo) in
  f32 accumulation — one dot per feature, exact to f32, and the MXU
  work co-issues under the VPU fold (the round-3 co-issue lever).

Exactness: the bf16x3 split reconstructs f32 x exactly (8+8+8 mantissa
bits ≥ 24 with sign absorption; split under an optimization_barrier so
XLA:TPU's bf16-propagation pass cannot fold it — the round-3 hardware
fuzz finding); y enters untouched in f32. Terms and accumulation are
plain f32 VPU ops, so results match the jitted XLA path bit-for-bit up
to reduction order (tested against numpy oracles).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.distance.types import DistanceType
from raft_tpu.ops.utils import interpret_mode

_LANES = 128
_QB = 256          # query block (sublane dim of the accumulator)
_DC = 16           # features folded per grid step (y refs per kernel)

_SUPPORTED = (
    DistanceType.L1,
    DistanceType.Linf,
    DistanceType.L2Unexpanded,
    DistanceType.L2SqrtUnexpanded,
    DistanceType.LpUnexpanded,
    DistanceType.Canberra,
    DistanceType.HammingUnexpanded,
    DistanceType.BrayCurtis,
    DistanceType.KLDivergence,
    DistanceType.JensenShannon,
)


def _interpret_dispatch_enabled() -> bool:
    """Interpreted Pallas is a TEST vehicle (orders of magnitude slower
    than the jitted XLA path): production non-TPU callers keep the XLA
    path unless the suite explicitly opts in (tests/conftest.py sets
    this; round-4 advisor finding)."""
    import os

    return os.environ.get("RAFT_TPU_PALLAS_INTERPRET_DISPATCH",
                          "0") == "1"


def unexpanded_eligible(t: DistanceType, n: int, m: int, d: int,
                        x_dtype, y_dtype) -> bool:
    """Whether the streaming kernel path serves this call. Small shapes
    stay on the fused-XLA path (kernel dispatch isn't worth it below
    ~1M output cells); non-f32-representable inputs keep XLA's native
    dtype semantics. Shape/dtype-only, so the decision is valid under
    trace (the finiteness envelope is handled in-program by the
    dispatcher's lax.cond)."""
    if t not in _SUPPORTED:
        return False
    for dt in (x_dtype, y_dtype):
        if not (jnp.issubdtype(dt, jnp.floating)
                and jnp.finfo(dt).bits <= 32):
            return False
    if interpret_mode():
        return _interpret_dispatch_enabled() and n * m * d <= 2 ** 22
    return n * m >= (1 << 20)


def _kl(a, b):
    r = jnp.where((a > 0) & (b > 0), a / jnp.where(b > 0, b, 1.0), 1.0)
    return jnp.where(a > 0, a * jnp.log(r), 0.0)


def _term(t: DistanceType, p: float, xb, yb):
    """One feature's [Qb, 128] term(s). The Pallas twin of
    distance.pairwise._unexp_terms (same math, tested to agree)."""
    diff = xb - yb
    if t in (DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded):
        return (diff * diff,)
    if t in (DistanceType.L1, DistanceType.Linf):
        return (jnp.abs(diff),)
    if t == DistanceType.LpUnexpanded:
        return (jnp.abs(diff) ** p,)
    if t == DistanceType.Canberra:
        denom = jnp.abs(xb) + jnp.abs(yb)
        safe = jnp.where(denom == 0, 1.0, denom)
        return (jnp.where(denom == 0, 0.0, jnp.abs(diff) / safe),)
    if t == DistanceType.HammingUnexpanded:
        return ((xb != yb).astype(jnp.float32),)
    if t == DistanceType.BrayCurtis:
        return (jnp.abs(diff), jnp.abs(xb + yb))
    if t == DistanceType.KLDivergence:
        return (_kl(xb, yb),)
    if t == DistanceType.JensenShannon:
        mid = 0.5 * (xb + yb)
        return (_kl(xb, mid) + _kl(yb, mid),)
    raise NotImplementedError(t)


def _unexpanded_kernel(*refs, t: DistanceType, p: float, dc: int,
                       Qb: int, n_dch: int, d_true: int, n_acc: int):
    """Grid (iq, it, idch), idch innermost: out blocks [Qb, 128] are
    revisited across the d-chunk sweep (zero-init on first visit,
    finalize on last — Mosaic's sequential grid as the accumulator)."""
    y_refs = refs[:dc]
    xs_ref = refs[dc]
    out_refs = refs[dc + 1:dc + 1 + n_acc]
    idch = pl.program_id(2)

    xsplit = xs_ref[...]                        # [Qb, 3·dc] bf16
    rows3 = 3 * dc
    row_mod = jax.lax.broadcasted_iota(jnp.int32, (rows3, _LANES), 0) % dc

    combine = (jnp.maximum if t == DistanceType.Linf else jnp.add)
    accs = [jnp.zeros((Qb, _LANES), jnp.float32) for _ in range(n_acc)]
    for f in range(dc):
        # one-hot selector: picks feature f from each of the 3 split
        # planes and sums them exactly in the f32 MXU accumulator
        oh = jnp.where(row_mod == f, 1.0, 0.0).astype(jnp.bfloat16)
        xb = jax.lax.dot(xsplit, oh,
                         preferred_element_type=jnp.float32)  # [Qb, 128]
        yb = jnp.broadcast_to(y_refs[f][...], (Qb, _LANES))
        for a, tm in zip(range(n_acc), _term(t, p, xb, yb)):
            accs[a] = combine(accs[a], tm)

    @pl.when(idch == 0)
    def _init():
        for r, a in zip(out_refs, accs):
            r[...] = a

    @pl.when(idch != 0)
    def _fold():
        for r, a in zip(out_refs, accs):
            r[...] = combine(r[...], a)

    if n_dch > 0:
        @pl.when(idch == n_dch - 1)
        def _finalize():
            a = out_refs[0][...]
            if t == DistanceType.L2SqrtUnexpanded:
                out_refs[0][...] = jnp.sqrt(jnp.maximum(a, 0.0))
            elif t == DistanceType.LpUnexpanded:
                out_refs[0][...] = jnp.maximum(a, 0.0) ** (1.0 / p)
            elif t == DistanceType.HammingUnexpanded:
                out_refs[0][...] = a / d_true
            elif t == DistanceType.BrayCurtis:
                out_refs[0][...] = a / jnp.maximum(out_refs[1][...],
                                                   1e-30)
            elif t == DistanceType.JensenShannon:
                out_refs[0][...] = jnp.sqrt(jnp.maximum(0.5 * a, 0.0))


def _split3(x):
    """Exact bf16x3 split of f32 ``x`` → [n, 3, d] bf16 (hi, mid, lo).
    Barriers keep XLA:TPU's bf16-propagation pass from folding the
    residuals to zero (round-3 hardware fuzz finding)."""
    hi = x.astype(jnp.bfloat16)
    hi_b = jax.lax.optimization_barrier(hi)
    r1 = x - hi_b.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    mid_b = jax.lax.optimization_barrier(mid)
    lo = (r1 - mid_b.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.stack([hi, mid, lo], axis=1)


@functools.partial(jax.jit,
                   static_argnames=("t", "p", "d_true", "Qb", "dc"))
def _unexpanded_pallas_impl(x, y, t: DistanceType, p: float, d_true: int,
                            Qb: int, dc: int):
    """The WHOLE op — cast, pad, split, kernel, output slice — as one
    program: every eager op around a kernel is a separate host
    dispatch."""
    n0, d0 = x.shape
    m0 = y.shape[0]
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    npad, mpad, dpad = (-n0) % Qb, (-m0) % _LANES, (-d0) % dc
    if npad:
        x = jnp.concatenate([x, jnp.zeros((npad, d0), x.dtype)])
    if dpad:
        # zero features are term-identities for every supported metric
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], dpad), x.dtype)], axis=1)
        y = jnp.concatenate(
            [y, jnp.zeros((m0, dpad), y.dtype)], axis=1)
    if mpad:
        y = jnp.concatenate(
            [y, jnp.zeros((mpad, y.shape[1]), y.dtype)])
    n, d = x.shape
    m = y.shape[0]
    n_dch = d // dc
    n_acc = 2 if t == DistanceType.BrayCurtis else 1

    # x: exact bf16x3 split, d-chunk-major column groups [n, nd·3·dc]
    xs = _split3(x)                                   # [n, 3, d]
    xs = xs.reshape(n, 3, n_dch, dc).transpose(0, 2, 1, 3)
    xs = xs.reshape(n, n_dch * 3 * dc)
    yT = y.T                                          # [d, m]

    grid = (n // Qb, m // _LANES, n_dch)
    y_specs = [
        pl.BlockSpec((1, _LANES),
                     functools.partial(
                         lambda iq, it, idch, f=0: (idch * dc + f, it),
                         f=f),
                     memory_space=pltpu.VMEM)
        for f in range(dc)]
    x_spec = pl.BlockSpec((Qb, 3 * dc), lambda iq, it, idch: (iq, idch),
                          memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec((Qb, _LANES), lambda iq, it, idch: (iq, it),
                            memory_space=pltpu.VMEM)

    outs = pl.pallas_call(
        functools.partial(_unexpanded_kernel, t=t, p=p, dc=dc, Qb=Qb,
                          n_dch=n_dch, d_true=d_true, n_acc=n_acc),
        grid=grid,
        in_specs=y_specs + [x_spec],
        out_specs=[out_spec] * n_acc,
        out_shape=[jax.ShapeDtypeStruct((n, m), jnp.float32)] * n_acc,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(*_y_chunk_views(yT, dc), xs)
    return outs[0][:n0, :m0]


def _y_chunk_views(yT, dc):
    """The dc y-row refs all view the SAME [d, m] array — the per-ref
    BlockSpec index maps select different feature rows."""
    return [yT] * dc


def unexpanded_pairwise_tiled(x, y, t: DistanceType, p: float
                              ) -> jax.Array:
    """Full [n, m] unexpanded distance matrix via the streaming kernel
    — ONE jitted dispatch (cast/pad/split/slice all inside).

    Envelope: FINITE inputs only — a non-finite x value would turn the
    one-hot selector dot into 0·inf = NaN for its whole feature chunk
    (distance.pairwise guards this with an in-program lax.cond on
    finiteness; direct callers with possibly non-finite data should use
    the XLA path)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    n, d = x.shape
    m = y.shape[0]
    if d == 0:
        return jnp.zeros((n, m), jnp.float32)
    Qb = min(_QB, max(8, -(-n // 8) * 8))
    dc = _DC if d >= _DC else max(1, d)
    return _unexpanded_pallas_impl(x, y, t, float(p), d, Qb, dc)
