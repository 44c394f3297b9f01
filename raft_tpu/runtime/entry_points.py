"""Runtime entry points (the libraft.so surface).

(ref: cpp/include/raft_runtime/solver/lanczos.hpp:23 ``lanczos_solver``;
raft_runtime/random/rmat_rectangular_generator.hpp; the randomized_svds
instantiations in cpp/src. See package docstring for the AOT-cache design.)
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.error import device_errors
from raft_tpu.core.resources import ensure_resources
from raft_tpu.core.sparse_types import COOMatrix, CSRMatrix
from raft_tpu.resilience import fault_point, run_with_policy


def _aot_call(res, name: str, statics: tuple, fn, *args):
    """AOT lower+compile ``fn`` once per (entry, statics, arg shapes) and
    reuse the executable from the handle's CompileCache — the TPU-native
    analog of the reference's precompiled libraft.so instantiations
    (ref: cpp/CMakeLists.txt:275-309). ``res.compile_cache.hits`` counts
    reuse (tested in tests/test_runtime_aot.py).

    Every compile miss also records the executable's static cost — XLA
    ``cost_analysis`` FLOPs/bytes and ``memory_analysis`` peak HBM — into
    ``res.profiler``, keyed by the same (entry, statics, shapes, sharding)
    signature as the cache, so roofline attribution covers every runtime
    entry without a second lowering (cache hits reuse the stored record).

    Resilience contract: compile AND dispatch run inside
    ``device_errors`` — callers never see raw jaxlib exceptions, only
    the classified error classes (OutOfMemoryError / DeviceError /
    DeadlineExceededError) — and the whole attempt is retried under the
    handle's ``runtime`` RetryPolicy (a failed compile is NOT cached,
    so a retry recompiles). Fault sites: ``aot_compile`` (inside the
    compile miss) and ``aot_dispatch`` (before every execution).
    Dispatch is async — an OOM XLA reports at completion time surfaces
    at the caller's sync point, already classified if the caller syncs
    through ``res.sync``/``device_errors``."""
    args = tuple(jnp.asarray(a) for a in args)
    # sharding/placement is part of the compiled executable's signature —
    # a cache hit with differently-committed args would raise at dispatch
    key = (name, statics,
           tuple((a.shape, str(a.dtype),
                  str(getattr(a, "sharding", None))) for a in args))

    def _compile():
        import time

        fault_point("aot_compile")
        t0 = time.perf_counter()
        with device_errors(f"{name} [compile]"):
            compiled = jax.jit(fn).lower(*args).compile()
        # compile wall time: timeline event + histogram on the COMPILE
        # bucket preset (DEFAULT_TIME_BUCKETS tops out at 30 s — a cold
        # north-star compile can exceed it; the preset reaches 300 s)
        try:
            from raft_tpu.observability.metrics import (
                COMPILE_TIME_BUCKETS, get_registry)
            from raft_tpu.observability.timeline import emit_compile

            dt = time.perf_counter() - t0
            emit_compile(name, seconds=dt, hit=False)
            get_registry().histogram(
                "raft_tpu_compile_seconds", {"entry": name},
                help="AOT compile wall time (compile bucket preset)",
                buckets=COMPILE_TIME_BUCKETS).observe(dt)
        except Exception:
            pass
        try:
            res.profiler.capture(name, compiled, key=str(key[1:]))
        except Exception:
            pass  # cost capture must never fail the entry point
        return compiled

    def _attempt(attempt):
        compiled = res.compile_cache.get_or_compile(key, _compile)
        fault_point("aot_dispatch")
        try:
            from raft_tpu.observability.timeline import emit_dispatch

            emit_dispatch(name)
        except Exception:
            pass
        with device_errors(name):
            return compiled(*args)

    return run_with_policy(f"runtime.{name}", _attempt,
                           policy=res.resilience.policy_for("runtime"))


def knn_query(res, index, x, k: int, rescore: Optional[bool] = None,
              certify: str = "kernel") -> Tuple[jax.Array, jax.Array]:
    """AOT serving entry: certified fused KNN against a PREPARED
    :class:`~raft_tpu.distance.knn_fused.KnnIndex`, compiled once per
    (index geometry, query-batch shape) and served from the handle's
    CompileCache — the data plane of the serving engine
    (:mod:`raft_tpu.serving`).

    Unlike :func:`raft_tpu.distance.knn_fused.knn_fused` (which jits
    lazily on first call), this entry lowers+compiles through
    :func:`_aot_call`, so the serving engine can PRE-WARM every bucket
    shape of its ladder at start-up and no live request ever pays a
    trace/compile: the cache key covers the query shape, so each bucket
    owns exactly one executable, and an index-snapshot swap of the same
    geometry re-uses them all (the index operands are ARGUMENTS, not
    baked-in constants). Feature/row padding to the kernel's block
    geometry happens INSIDE the compiled program — the key is the raw
    bucket shape the engine dispatches.
    """
    from raft_tpu.distance.knn_fused import (_LANES, _POOL_PAD, KnnIndex,
                                             _knn_fused_core,
                                             pool_select_algo,
                                             resolve_pool_algo)
    from raft_tpu.core.error import expects

    res = ensure_resources(res)
    expects(isinstance(index, KnnIndex),
            "knn_query: index must be a prepared KnnIndex (see "
            "distance.prepare_knn_index)")
    expects(getattr(index, "rows_valid", None) is None,
            "knn_query: ragged-layout indexes (rows_valid) query "
            "through knn_fused / the mutable plane, not the AOT entry")
    idx = index
    if certify not in ("kernel", "f32"):
        raise ValueError(f"knn_query: certify must be 'kernel' or "
                         f"'f32', got {certify!r}")
    x = jnp.asarray(x, jnp.float32)
    Q, d_x = x.shape
    expects(d_x == idx.d_orig, "knn_query: query width %d != index %d",
            d_x, idx.d_orig)
    expects(k <= idx.n_rows, "knn_query: k=%d > index size %d", k,
            idx.n_rows)
    if Q == 0:
        return (jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    if rescore is None:
        rescore = idx.yp is not None
    if rescore and idx.yp is None:
        raise ValueError("knn_query: rescore=True needs a yp-storing "
                         "index (store_yp=True)")
    if idx.passes == 3:
        certify = "kernel"      # p3 is already f32-certified
    if certify == "f32" and not rescore:
        raise ValueError("knn_query: certify='f32' needs the exact "
                         "rescore (store_yp=True)")
    # pool geometry + effective selection algo, resolved per call like
    # knn_fused's own wrapper (the non-jitted decision point)
    n_tiles = idx.yyh_k.shape[1] // idx.T
    S_pool = -(-n_tiles // idx.g) * _LANES
    packed = idx.g * (idx.T // _LANES) <= (1 << idx.pbits)
    pool_len = S_pool if packed else 2 * S_pool
    if k > 2 * S_pool:
        raise NotImplementedError(
            f"knn_query: k={k} too large for pool {2 * S_pool}")
    pool_algo = resolve_pool_algo(pool_select_algo(), pool_len,
                                  min(k + _POOL_PAD, pool_len))
    Qb_eff = min(idx.Qb, ((Q + 7) // 8) * 8)
    has_yp = idx.yp is not None
    has_ylo = idx.y_lo is not None
    T_, g_, passes_ = idx.T, idx.g, idx.passes
    metric_, m_, pbits_ = idx.metric, idx.n_rows, idx.pbits
    order_ = idx.grid_order
    dtype_ = getattr(idx, "db_dtype", "bf16")
    quant = dtype_ == "int8"
    if quant and not rescore:
        raise ValueError("knn_query: an int8-streamed index is always "
                         "exact-rescored")

    def run(xq, *ops):
        it = iter(ops)
        yp = next(it) if has_yp else None
        if quant:
            y_hi = y_lo = None
            y_q, scale_k, eq = next(it), next(it), next(it)
            stream_w = y_q.shape[1]
        else:
            y_q = scale_k = eq = None
            y_hi = next(it)
            y_lo = next(it) if has_ylo else None
            stream_w = y_hi.shape[1]
        yyh_k = next(it)
        yy_raw = next(it)
        dpad = stream_w - xq.shape[1]
        if dpad:
            xq = jnp.concatenate(
                [xq, jnp.zeros((xq.shape[0], dpad), jnp.float32)], axis=1)
        qpad = (-Q) % Qb_eff
        if qpad:
            xq = jnp.concatenate(
                [xq, jnp.zeros((qpad, xq.shape[1]), jnp.float32)])
        vals, ids, n_fail, margin = _knn_fused_core(
            xq, yp, y_hi, y_lo, yyh_k, yy_raw,
            k=k, T=T_, Qb=Qb_eff, g=g_, passes=passes_, metric=metric_,
            m=m_, rescore=rescore, pbits=pbits_, certify=certify,
            pool_algo=pool_algo, grid_order=order_, db_dtype=dtype_,
            with_stats=True, y_q=y_q, y_scale_k=scale_k, eq_groups=eq)
        if qpad:
            vals, ids, margin = vals[:Q], ids[:Q], margin[:Q]
        if metric_ == "ip":
            vals = -vals        # internal −x·y ascending → IP descending
        return vals, ids, n_fail, margin

    statics = (k, T_, Qb_eff, g_, passes_, metric_, m_, bool(rescore),
               pbits_, certify, pool_algo, order_, dtype_, has_yp,
               has_ylo, Q)
    ops = [o for o in (idx.yp,) if o is not None]
    if quant:
        ops += [idx.y_q, idx.y_scale_k, idx.eq_groups]
    else:
        ops += [o for o in (idx.y_hi, idx.y_lo) if o is not None]
    ops += [idx.yyh_k, idx.yy_raw]
    vals, ids, n_fail, margin = _aot_call(res, "knn_query", statics,
                                          run, x, *ops)
    # certificate/fixup telemetry for the AOT serving plane: the
    # failure count stays a device scalar here (quality.drain resolves
    # it later — the live request path never syncs for telemetry); the
    # per-query margin is likewise only HELD (by reference) when an
    # explain capture is active, resolved at capture finalize
    try:
        from raft_tpu.distance.knn_fused import (fixup_tiers_for,
                                                 rescore_pool_width)
        from raft_tpu.observability import explain
        from raft_tpu.observability.quality import record_pending

        record_pending(
            "runtime.knn_query", n_fail,
            n_queries=Q + ((-Q) % Qb_eff),
            pool_width=rescore_pool_width(k, S_pool, packed),
            fix_tiers=fixup_tiers_for(idx.yyh_k.shape[1]),
            db_dtype=dtype_, passes=passes_)
        if explain.active() is not None:
            explain.note_margin("runtime.knn_query", margin)
            explain.note(plane="brute", db_dtype=dtype_,
                         grid_order=order_, passes=passes_,
                         pool_algo=pool_algo, certify=certify, k=k)
    except Exception:
        pass
    return vals, ids


def lanczos_solver(res, rows, cols, vals, n: int, n_components: int,
                   max_iterations: int = 1000, ncv: Optional[int] = None,
                   tolerance: float = 1e-6, which: str = "SA", seed: int = 42,
                   v0=None) -> Tuple[jax.Array, jax.Array]:
    """Flat-argument Lanczos entry (the ABI the Cython layer called).
    (ref: raft_runtime/solver/lanczos.hpp:23 — COO rows/cols/vals in,
    eigenpairs out.)"""
    from raft_tpu.sparse.solver.lanczos import lanczos_compute_eigenpairs
    from raft_tpu.sparse.solver.lanczos_types import LANCZOS_WHICH, LanczosSolverConfig

    res = ensure_resources(res)
    A = COOMatrix(jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
                  jnp.asarray(vals), (n, n))
    config = LanczosSolverConfig(
        n_components=n_components, max_iterations=max_iterations, ncv=ncv,
        tolerance=tolerance, which=LANCZOS_WHICH[which], seed=seed)
    return lanczos_compute_eigenpairs(res, A, config, v0=v0)


def randomized_svds(res, indptr, indices, vals, shape: Tuple[int, int],
                    n_components: int, n_oversamples: int = 10,
                    n_power_iters: int = 2, seed: int = 42):
    """Flat-argument sparse randomized SVD entry.
    (ref: raft_runtime ``randomized_svds`` float/double instantiations.)"""
    from raft_tpu.sparse.solver.randomized_svds import SvdsConfig
    from raft_tpu.sparse.solver.randomized_svds import randomized_svds as _svds

    res = ensure_resources(res)
    shape = tuple(int(s) for s in shape)
    cfg = SvdsConfig(n_components=n_components, n_oversamples=n_oversamples,
                     n_power_iters=n_power_iters, seed=seed)

    def run(ip, ix, v):
        return _svds(res, CSRMatrix(ip, ix, v, shape), cfg)

    return _aot_call(
        res, "randomized_svds",
        (shape, n_components, n_oversamples, n_power_iters, seed), run,
        jnp.asarray(indptr, jnp.int32), jnp.asarray(indices, jnp.int32),
        jnp.asarray(vals))


def rmat_rectangular_generator(res, theta, r_scale: int, c_scale: int,
                               n_edges: int, seed: int = 42):
    """(ref: raft_runtime/random/rmat_rectangular_generator.hpp — the 4
    type-combo instantiations collapse into one dtype-generic entry.)"""
    from raft_tpu.random.rmat import rmat_rectangular_gen
    from raft_tpu.random.rng_state import RngState

    res = ensure_resources(res)
    if theta is None:
        def run_default():
            return rmat_rectangular_gen(res, RngState(seed), n_edges,
                                        r_scale, c_scale)

        return _aot_call(res, "rmat_rectangular_generator",
                         (r_scale, c_scale, n_edges, seed, "default"),
                         run_default)

    def run(th):
        return rmat_rectangular_gen(res, RngState(seed), n_edges, r_scale,
                                    c_scale, theta=th)

    return _aot_call(res, "rmat_rectangular_generator",
                     (r_scale, c_scale, n_edges, seed), run,
                     jnp.asarray(theta, jnp.float32))
