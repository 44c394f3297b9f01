"""Pairwise distances.

(ref: the pre-cuVS ``raft::distance::pairwise_distance`` surface, built on
the contraction tiling substrate that survives at
cpp/include/raft/linalg/detail/contractions.cuh:313 — rebuilt TPU-first per
SURVEY §7 stage 10 / BASELINE configs 1-2.)

TPU design: "expanded" metrics (L2/cosine/correlation/IP/hellinger/russell-
rao/jaccard/dice) contract on the MXU as X·Yᵀ plus rank-1 norm corrections —
that's where the 10M×256 GB/s target comes from. "Unexpanded" metrics
(L1/Linf/Canberra/Minkowski/Hamming/KL/JS/BrayCurtis) need the |x−y| form,
which has no matmul decomposition: the streaming Pallas kernel
(ops/unexpanded_pallas.py) forms per-feature terms on VMEM-resident tiles
and folds them into [Qb, 128] accumulators — no [n, m, d] broadcast at any
memory level (the role the reference's smem tiling policies play — SURVEY
§2.3 contractions row, contractions.cuh:313). Ineligible calls take a
single fully-jitted XLA program whose broadcast-reduce fuses per row tile.
"""

from __future__ import annotations

from typing import Union

import functools

import jax
import jax.numpy as jnp

from raft_tpu.core.error import expects
from raft_tpu.core.resources import ensure_resources
from raft_tpu.distance.types import METRIC_NAMES, DistanceType
from raft_tpu.observability import instrument
from raft_tpu.resilience import fault_point


def _as_type(metric: Union[str, DistanceType]) -> DistanceType:
    if isinstance(metric, DistanceType):
        return metric
    expects(metric in METRIC_NAMES, "unknown metric %r", metric)
    return METRIC_NAMES[metric]


def _expanded_l2(x, y, sqrt: bool):
    xx = jnp.sum(x * x, axis=1)[:, None]
    yy = jnp.sum(y * y, axis=1)[None, :]
    d2 = xx + yy - 2.0 * jnp.matmul(x, y.T, preferred_element_type=jnp.float32)
    d2 = jnp.maximum(d2, 0.0)
    return jnp.sqrt(d2) if sqrt else d2


def _cosine(x, y):
    xn = jnp.sqrt(jnp.sum(x * x, axis=1))[:, None]
    yn = jnp.sqrt(jnp.sum(y * y, axis=1))[None, :]
    denom = jnp.maximum(xn * yn, 1e-30)
    sim = jnp.matmul(x, y.T, preferred_element_type=jnp.float32) / denom
    return 1.0 - sim


def _correlation(x, y):
    xc = x - jnp.mean(x, axis=1, keepdims=True)
    yc = y - jnp.mean(y, axis=1, keepdims=True)
    return _cosine(xc, yc)


def _is_batch_traced(*arrays) -> bool:
    """Best-effort vmap detection: True when any operand is a batching
    tracer at dispatch time (vmap(pairwise_distance), or vmap inside an
    enclosing jit). ``vmap(jit(f))`` callers trace f under the jit
    trace — invisible here — and should pass ``batched=True``."""
    # jax 0.9 no longer exports BatchTracer; a batching tracer is the
    # public Tracer that carries a ``batch_dim``.
    return any(isinstance(a, jax.core.Tracer) and hasattr(a, "batch_dim")
               for a in arrays)


@instrument("distance.pairwise_distance")
def pairwise_distance(res, x, y=None, metric: Union[str, DistanceType] = "euclidean",
                      p: float = 2.0, precision=None,
                      assume_finite: bool = False,
                      batched: bool = None) -> jax.Array:
    """Full [n, m] distance matrix. (ref: pre-cuVS
    raft::distance::pairwise_distance; pylibraft.distance.pairwise_distance)

    Precision note (expanded metrics): with ``precision=None`` the MXU
    contraction runs at JAX's default matmul precision — one-pass bf16 on
    TPU, which is the same precision CLASS as the reference's default on
    A100 (cuBLAS runs f32 GEMMs on TF32 tensor cores, 10-bit mantissa).
    Pass ``precision=jax.lax.Precision.HIGHEST`` for f32-grade
    contractions (3-pass bf16 split — BEYOND the reference's default), or
    use ``jax.default_matmul_precision`` to set it globally.

    ``assume_finite=True`` promises the inputs contain no inf/NaN,
    letting the unexpanded metrics skip the in-program finiteness guard
    in front of the streaming Pallas kernel (non-finite values would
    poison its one-hot selector contraction; with the default guard
    they are routed to the XLA path, which preserves inf/NaN
    semantics).

    ``batched=True`` tells the unexpanded dispatch the caller is
    vmapped: under vmap the guard's ``lax.cond`` lowers to ``select``
    and BOTH branches execute per batch element (round-5 finding), so
    batched callers are short-circuited straight to the XLA path
    (inf/NaN-correct, one branch). ``None`` auto-detects a batching
    trace on the operands; ``vmap(jit(...))`` callers — invisible to
    the detection — should pass it explicitly (or vouch with
    ``assume_finite=True``, which skips the guard entirely and keeps
    the Pallas kernel).

    Examples
    --------
    >>> import numpy as np
    >>> from raft_tpu.distance import pairwise_distance
    >>> x = np.array([[0.0, 0.0], [3.0, 4.0]])
    >>> np.asarray(pairwise_distance(None, x, metric="euclidean")).round(1).tolist()
    [[0.0, 5.0], [5.0, 0.0]]
    """
    fault_point("pairwise_distance")
    x = jnp.asarray(x)
    y = x if y is None else jnp.asarray(y)
    expects(x.ndim == 2 and y.ndim == 2 and x.shape[1] == y.shape[1],
            "pairwise_distance: inputs must be [n,d],[m,d]")
    t = _as_type(metric)
    if batched is None:
        batched = _is_batch_traced(x, y)
    if precision is not None:
        if isinstance(precision, jax.lax.Precision):
            precision = precision.name.lower()
        with jax.default_matmul_precision(precision):
            return _pairwise_dispatch(res, x, y, t, p, assume_finite,
                                      batched)
    return _pairwise_dispatch(res, x, y, t, p, assume_finite, batched)


_UNEXPANDED_TYPES = frozenset({
    DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded,
    DistanceType.L1, DistanceType.Linf, DistanceType.LpUnexpanded,
    DistanceType.Canberra, DistanceType.HammingUnexpanded,
    DistanceType.BrayCurtis, DistanceType.KLDivergence,
    DistanceType.JensenShannon,
})


def _pairwise_dispatch(res, x, y, t: DistanceType, p: float,
                       assume_finite: bool = False,
                       batched: bool = False) -> jax.Array:
    if t not in _UNEXPANDED_TYPES:
        # ONE jitted program for the expanded metrics: eagerly, the
        # 5-6 ops would each be a separate host dispatch (ref
        # contractions.cuh:1's single-launch small-shape path)
        return _pairwise_expanded_jit(x, y, t, p)
    # unexpanded (broadcast-form) metrics: every one of them accumulates
    # elementwise over features, so the [tile, m, d] broadcast is folded
    # over FEATURE CHUNKS with a [tile, m]-shaped carry — the d-axis
    # analog of the reference's k-blocked smem policy
    # (linalg/detail/contractions.cuh:313). Peak temp = [tile, m, dc].
    return _unexpanded(res, x, y, t, p, assume_finite, batched)


@functools.partial(jax.jit, static_argnames=("t", "p"))
def _pairwise_expanded_jit(x, y, t: DistanceType, p: float) -> jax.Array:

    if t == DistanceType.L2Expanded:
        return _expanded_l2(x, y, sqrt=False)
    if t == DistanceType.L2SqrtExpanded:
        return _expanded_l2(x, y, sqrt=True)
    if t == DistanceType.InnerProduct:
        return jnp.matmul(x, y.T, preferred_element_type=jnp.float32)
    if t == DistanceType.CosineExpanded:
        return _cosine(x, y)
    if t == DistanceType.CorrelationExpanded:
        return _correlation(x, y)
    if t == DistanceType.HellingerExpanded:
        ip = jnp.matmul(jnp.sqrt(jnp.abs(x)), jnp.sqrt(jnp.abs(y)).T,
                        preferred_element_type=jnp.float32)
        return jnp.sqrt(jnp.maximum(1.0 - jnp.minimum(ip, 1.0), 0.0))
    if t == DistanceType.RussellRaoExpanded:
        d = x.shape[1]
        ip = jnp.matmul((x != 0).astype(jnp.float32), (y != 0).astype(jnp.float32).T,
                        preferred_element_type=jnp.float32)
        return (d - ip) / d
    if t in (DistanceType.JaccardExpanded, DistanceType.DiceExpanded):
        xb = (x != 0).astype(jnp.float32)
        yb = (y != 0).astype(jnp.float32)
        inter = jnp.matmul(xb, yb.T, preferred_element_type=jnp.float32)
        nx = jnp.sum(xb, axis=1)[:, None]
        ny = jnp.sum(yb, axis=1)[None, :]
        if t == DistanceType.JaccardExpanded:
            union = jnp.maximum(nx + ny - inter, 1e-30)
            return 1.0 - inter / union
        return 1.0 - 2.0 * inter / jnp.maximum(nx + ny, 1e-30)
    raise ValueError(f"_pairwise_expanded_jit: unexpanded metric {t}")


def _kl_term(a, b):
    r = jnp.where((a > 0) & (b > 0), a / jnp.where(b > 0, b, 1.0), 1.0)
    return jnp.where(a > 0, a * jnp.log(r), 0.0)


def _unexp_terms(xs, ys, t: DistanceType, p: float, acc_dtype):
    """Per-feature terms on a broadcastable (xs, ys) pair — the ONE
    definition of every unexpanded metric's inner form, shared by the
    jitted XLA path and the Pallas kernel's emulation tests."""
    diff = xs - ys
    if t in (DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded):
        return (diff * diff,)
    if t == DistanceType.L1 or t == DistanceType.Linf:
        return (jnp.abs(diff),)
    if t == DistanceType.LpUnexpanded:
        return (jnp.abs(diff) ** p,)
    if t == DistanceType.Canberra:
        denom = jnp.abs(xs) + jnp.abs(ys)
        safe = jnp.where(denom == 0, 1.0, denom)
        return (jnp.where(denom == 0, 0.0, jnp.abs(diff) / safe),)
    if t == DistanceType.HammingUnexpanded:
        return ((xs != ys).astype(acc_dtype),)
    if t == DistanceType.BrayCurtis:
        return (jnp.abs(diff), jnp.abs(xs + ys))
    if t == DistanceType.KLDivergence:
        return (_kl_term(xs, ys),)
    if t == DistanceType.JensenShannon:
        mid = 0.5 * (xs + ys)
        return (_kl_term(xs, mid) + _kl_term(ys, mid),)
    raise NotImplementedError(t)


def _unexp_finalize(accs, t: DistanceType, p: float, d: int):
    a = accs[0]
    if t == DistanceType.L2SqrtUnexpanded:
        return jnp.sqrt(a)
    if t == DistanceType.LpUnexpanded:
        return a ** (1.0 / p)
    if t == DistanceType.HammingUnexpanded:
        return a / d
    if t == DistanceType.BrayCurtis:
        return a / jnp.maximum(accs[1], 1e-30)
    if t == DistanceType.JensenShannon:
        return jnp.sqrt(jnp.maximum(0.5 * a, 0.0))
    return a


@functools.partial(jax.jit,
                   static_argnames=("t", "p", "d_true", "tile", "dc"))
def _unexpanded_jit(x, y, t: DistanceType, p: float, d_true: int,
                    tile: int, dc: int = 16) -> jax.Array:
    """The whole unexpanded pairwise op as ONE compiled program: a map
    over row tiles whose body folds FEATURE CHUNKS of ``dc`` with a
    [tile, m] carry — the d-axis analog of the reference's k-blocked
    smem policy (linalg/detail/contractions.cuh:313). The explicit
    chunk fold makes peak temp [tile, m, dc] by construction instead of
    trusting XLA to fuse a [tile, m, d] broadcast into the reduction
    (round-4 advisor: multi-term metrics / non-TPU backends may not
    fuse, and an unfused broadcast would be d/dc times the budgeted
    memory). Single dispatch — no host round-trip per eager op."""
    n, d0 = x.shape
    m = y.shape[0]
    acc_dtype = jnp.promote_types(jnp.promote_types(x.dtype, y.dtype),
                                  jnp.float32)
    reduce_d = jnp.max if t == DistanceType.Linf else jnp.sum
    combine = jnp.maximum if t == DistanceType.Linf else jnp.add
    n_acc = 2 if t == DistanceType.BrayCurtis else 1

    dc = max(1, min(dc, d0))
    dpad = (-d0) % dc
    if dpad:
        # zero features are term identities for every unexpanded metric
        # (tested: test_kernel_odd_shapes_and_padding)
        x = jnp.concatenate([x, jnp.zeros((n, dpad), x.dtype)], axis=1)
        y = jnp.concatenate([y, jnp.zeros((m, dpad), y.dtype)], axis=1)
    n_ch = (d0 + dpad) // dc
    yc = y.astype(acc_dtype).reshape(m, n_ch, dc).transpose(1, 0, 2)

    def one_tile(xt):
        xc = xt.astype(acc_dtype).reshape(tile, n_ch, dc)
        xc = xc.transpose(1, 0, 2)                   # [n_ch, tile, dc]

        def fold(carry, ch):
            xcc, ycc = ch                # [tile, dc], [m, dc]
            terms = _unexp_terms(xcc[:, None, :], ycc[None, :, :],
                                 t, p, acc_dtype)
            return tuple(combine(c, reduce_d(tm, axis=2))
                         for c, tm in zip(carry, terms)), None

        init = tuple(jnp.zeros((tile, m), acc_dtype)
                     for _ in range(n_acc))
        accs, _ = jax.lax.scan(fold, init, (xc, yc))
        return _unexp_finalize(accs, t, p, d_true)

    n_tiles = -(-n // tile)
    npad = n_tiles * tile - n
    xp = jnp.concatenate([x, jnp.zeros((npad, x.shape[1]), x.dtype)]) \
        if npad else x
    out = jax.lax.map(one_tile, xp.reshape(n_tiles, tile, x.shape[1]))
    return out.reshape(n_tiles * tile, m)[:n]


@functools.partial(jax.jit,
                   static_argnames=("t", "p", "d_true", "tile", "dc"))
def _unexpanded_guarded(x, y, t: DistanceType, p: float, d_true: int,
                        tile: int, dc: int) -> jax.Array:
    """Kernel-or-XLA chosen by an IN-PROGRAM finiteness check: the
    streaming Pallas path is reachable from jitted callers (the round-4
    dispatch required concrete inputs, so every estimator pipeline got
    the fallback), and eager callers pay one dispatch with no host
    sync instead of two blocking isfinite scans. Non-finite inputs take
    the XLA branch, whose semantics cover inf/NaN (the kernel's one-hot
    selector dot would turn them into whole-chunk NaNs).

    Cost note for ``vmap`` callers: under vmap, ``lax.cond`` lowers to
    ``select`` — BOTH branches execute for every batch element, so a
    vmapped caller pays kernel + XLA fallback distance computation and
    keeps only one result. The dispatcher therefore SHORT-CIRCUITS
    known-batched callers straight to ``_unexpanded_jit`` (detected
    via the operands' batching trace, or the explicit ``batched=``
    kwarg) — this guarded path is only entered unbatched. A batched
    pipeline that can vouch for finite inputs should instead pass
    ``assume_finite=True`` (skips the guard AND keeps the kernel)."""
    finite = jnp.isfinite(x).all() & jnp.isfinite(y).all()
    from raft_tpu.ops.unexpanded_pallas import unexpanded_pairwise_tiled

    return jax.lax.cond(
        finite,
        lambda a, b: unexpanded_pairwise_tiled(a, b, t, p),
        lambda a, b: _unexpanded_jit(a, b, t, p, d_true, tile, dc=dc),
        x, y)


def _unexpanded(res, x, y, t: DistanceType, p: float,
                assume_finite: bool = False,
                batched: bool = False) -> jax.Array:
    n, d = x.shape
    m = y.shape[0]
    acc_dtype = jnp.promote_types(jnp.promote_types(x.dtype, y.dtype),
                                  jnp.float32)
    if d == 0:
        return jnp.zeros((n, m), acc_dtype)

    # Pallas streaming path (TPU): [Qb, T] VMEM accumulators, terms
    # formed on VMEM-resident tiles — no [tile, m, d] broadcast at any
    # memory level (the contraction-substrate role, contractions.cuh:313)
    from raft_tpu.ops.unexpanded_pallas import (unexpanded_eligible,
                                                unexpanded_pairwise_tiled)

    # fallback tiling: budget the materialized [tile, m, dc] chunk temp
    # (×3 for term intermediates) — holds whether or not XLA fuses
    itemsize = jnp.dtype(acc_dtype).itemsize
    res = ensure_resources(res)
    dc = max(1, min(16, d))
    budget_rows = res.workspace.batch_rows(m * dc * 3 * itemsize)
    tile = int(max(1, min(n, budget_rows)))

    if unexpanded_eligible(t, n, m, d, x.dtype, y.dtype):
        if assume_finite:
            # caller vouches for the kernel envelope: skip even the
            # in-program finiteness reduction
            return unexpanded_pairwise_tiled(x, y, t, p)
        if batched:
            # known-batched caller: the guard's cond would lower to
            # select under vmap and execute BOTH branches per batch
            # element — the XLA path alone (inf/NaN-correct) is
            # strictly cheaper than kernel + XLA with one discarded
            return _unexpanded_jit(x, y, t, float(p), d, tile, dc=dc)
        return _unexpanded_guarded(x, y, t, float(p), d, tile, dc)
    return _unexpanded_jit(x, y, t, float(p), d, tile, dc=dc)
