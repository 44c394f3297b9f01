"""Fused L2 nearest-neighbor and brute-force KNN.

(ref: the pre-cuVS ``raft::distance::fusedL2NN`` — per-query argmin over a
distance matrix that is never materialized — and brute-force knn
(distance + matrix::select_k). BASELINE config 2: "fused L2-NN + select_k
top-64 on 1M×128". Rebuilt TPU-first per SURVEY §7 stage 10.)

Design: stream over column tiles of Y. Each tile does one MXU contraction
X·Y_tileᵀ plus norm corrections, then folds into a running (value, index)
minimum — or a running top-k via merge-and-reselect for knn. Peak memory is
[n, tile] + [n, k], never [n, m]; the tile size comes from the handle's
workspace budget (the reference sizes its smem tiles the same way,
linalg/detail/contractions.cuh). The per-tile loop is a ``lax.fori_loop``
over a static tile count so the whole sweep is one compiled program.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.core.error import expects
from raft_tpu.core.kvp import KeyValuePair
from raft_tpu.core.resources import ensure_resources
from raft_tpu.observability import instrument
from raft_tpu.resilience import fault_point


def _pad_rows(y, tile):
    """Pad to a tile multiple with zeros; padded columns are masked out via
    the m_real bound in every sweep (zeros keep the matmul NaN-free)."""
    m = y.shape[0]
    pad = (-m) % tile
    if pad:
        y = jnp.concatenate([y, jnp.zeros((pad, y.shape[1]), y.dtype)])
    return y, m + pad


@partial(jax.jit, static_argnames=("tile", "sqrt"))
def _fused_l2nn(x, y_padded, m_real: jax.Array, tile: int, sqrt: bool):
    n = x.shape[0]
    xx = jnp.sum(x * x, axis=1)
    n_tiles = y_padded.shape[0] // tile

    def body(i, carry):
        best_v, best_i = carry
        yt = jax.lax.dynamic_slice_in_dim(y_padded, i * tile, tile, axis=0)
        yy = jnp.sum(yt * yt, axis=1)
        d2 = xx[:, None] + yy[None, :] - 2.0 * jnp.matmul(
            x, yt.T, preferred_element_type=jnp.float32)
        col = i * tile + jnp.arange(tile)
        valid = col[None, :] < m_real
        d2 = jnp.where(valid, d2, jnp.inf)
        tv = jnp.min(d2, axis=1)
        ti = jnp.argmin(d2, axis=1).astype(jnp.int32) + i * tile
        take = (tv < best_v) | ((tv == best_v) & (ti < best_i))
        return (jnp.where(take, tv, best_v), jnp.where(take, ti, best_i))

    best_v = jnp.full((n,), jnp.inf, jnp.float32)
    best_i = jnp.full((n,), jnp.iinfo(jnp.int32).max, jnp.int32)
    best_v, best_i = jax.lax.fori_loop(0, n_tiles, body, (best_v, best_i))
    best_v = jnp.maximum(best_v, 0.0)
    if sqrt:
        best_v = jnp.sqrt(best_v)
    return best_v, best_i


@instrument("distance.fused_l2_nn_argmin")
def fused_l2_nn_argmin(res, x, y, sqrt: bool = False,
                       tile: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """For each row of x, the nearest row of y under (squared) L2.
    Returns (min_dist [n], argmin [n]). (ref: pre-cuVS fusedL2NN /
    pylibraft.distance.fused_l2_nn_argmin)"""
    fault_point("fused_l2nn")
    res = ensure_resources(res)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    expects(x.shape[1] == y.shape[1], "fused_l2_nn: dim mismatch")
    if tile is None:
        # [n, tile] f32 intermediate within workspace budget
        tile = max(128, min(y.shape[0],
                            res.workspace.allocation_limit // (8 * max(x.shape[0], 1))))
        tile = min(tile, 8192)
    y_padded, _ = _pad_rows(y, tile)
    return _fused_l2nn(x, y_padded, jnp.asarray(y.shape[0]), int(tile), sqrt)


def fused_l2_nn(res, x, y, sqrt: bool = False) -> KeyValuePair:
    """KVP-returning variant mirroring the reference's out type."""
    v, i = fused_l2_nn_argmin(res, x, y, sqrt)
    return KeyValuePair(key=i, value=v)


def _merge_topk(best_v, best_i, tile_v, tile_i, k: int, select_min: bool):
    """Merge a running top-k with a new tile and reselect (delegates to the
    one top-k implementation in matrix/select_k)."""
    from raft_tpu.matrix.select_k import _xla_select_k

    allv = jnp.concatenate([best_v, tile_v], axis=1)
    alli = jnp.concatenate([best_i, tile_i], axis=1)
    return _xla_select_k(allv, alli, k, select_min)


@partial(jax.jit, static_argnames=("k", "tile"))
def _knn_sweep(x_sq, x, y_padded, m_real, k: int, tile: int):
    """Streamed fused top-k with threshold-gated merging — the same pruning
    idea as the reference's filtered warpsort queues
    (select_warpsort.cuh ``warp_sort_filtered``): a tile only pays for the
    O(n·tile·log) top-k merge when some query's running k-th-best improves;
    otherwise the tile costs one MXU contraction + a fused compare. After
    the first few tiles almost everything is pruned, so the sweep runs at
    matmul speed instead of sort speed."""
    n = x.shape[0]
    n_tiles = y_padded.shape[0] // tile

    def body(i, carry):
        best_v, best_i = carry                         # [n, k]
        yt = jax.lax.dynamic_slice_in_dim(y_padded, i * tile, tile, axis=0)
        yy = jnp.sum(yt * yt, axis=1)
        d2 = x_sq[:, None] + yy[None, :] - 2.0 * jnp.matmul(
            x, yt.T, preferred_element_type=jnp.float32)
        col = i * tile + jnp.arange(tile, dtype=jnp.int32)
        valid = col[None, :] < m_real
        d2 = jnp.where(valid, d2, jnp.inf)
        threshold = best_v[:, k - 1]                   # current k-th best
        improves = jnp.any(d2 < threshold[:, None])
        cols = jnp.broadcast_to(col[None, :], d2.shape)

        def do_merge(_):
            return _merge_topk(best_v, best_i, d2, cols, k, True)

        def skip(_):
            return best_v, best_i

        return jax.lax.cond(improves, do_merge, skip, None)

    best_v = jnp.full((n, k), jnp.inf, jnp.float32)
    best_i = jnp.full((n, k), -1, jnp.int32)
    return jax.lax.fori_loop(0, n_tiles, body, (best_v, best_i))


@partial(jax.jit, static_argnames=("k", "tile"))
def _knn_certified_approx(x, y_padded, m_real, k: int, tile: int):
    """Certified-approx KNN sweep (the fast path for big indexes).

    Sweep A streams tiles through TPU's native bucketed ``approx_min_k``
    merge — sort-free, ~6× cheaper than exact top-k merges. Sweep B then
    CERTIFIES the result with one exact fused count pass: for each query
    it counts entries with d2 ≤ θ (θ = the approx k-th). If the count is
    exactly k, the approx set provably IS the exact top-k (any missed
    entry would have to be ≤ θ and would make the count exceed k). If any
    query fails certification, a ``lax.cond`` branch runs the exact merge
    sweep instead — so the returned result is always exact and the whole
    function stays traceable under jit with no host synchronization.

    (ref: the role of the kAuto heuristic + filtered warpsort queues in
    matrix/detail/select_k-inl.cuh — cheap path when it provably works,
    exact fallback otherwise.)
    """
    q = x.shape[0]
    x_sq = jnp.sum(x * x, axis=1)
    n_tiles = y_padded.shape[0] // tile

    def body_approx(i, best):
        yt = jax.lax.dynamic_slice_in_dim(y_padded, i * tile, tile, axis=0)
        yy = jnp.sum(yt * yt, axis=1)
        d2 = x_sq[:, None] + yy[None, :] - 2.0 * jnp.matmul(
            x, yt.T, preferred_element_type=jnp.float32)
        col = i * tile + jnp.arange(tile)
        d2 = jnp.where(col[None, :] < m_real, d2, jnp.inf)
        merged_v = jnp.concatenate([best[0], d2], axis=1)
        merged_i = jnp.concatenate(
            [best[1], jnp.broadcast_to(col[None, :], d2.shape).astype(jnp.int32)],
            axis=1)
        nv, pos = jax.lax.approx_min_k(merged_v, k)
        return nv, jnp.take_along_axis(merged_i, pos, axis=1)

    best_v = jnp.full((q, k), jnp.inf, jnp.float32)
    best_i = jnp.full((q, k), -1, jnp.int32)
    best_v, best_i = jax.lax.fori_loop(0, n_tiles, body_approx,
                                       (best_v, best_i))
    theta = best_v[:, -1]

    def body_count(i, cnt):
        yt = jax.lax.dynamic_slice_in_dim(y_padded, i * tile, tile, axis=0)
        yy = jnp.sum(yt * yt, axis=1)
        d2 = x_sq[:, None] + yy[None, :] - 2.0 * jnp.matmul(
            x, yt.T, preferred_element_type=jnp.float32)
        col = i * tile + jnp.arange(tile)
        ok = (d2 <= theta[:, None]) & (col[None, :] < m_real)
        return cnt + jnp.sum(ok.astype(jnp.int32), axis=1)

    counts = jax.lax.fori_loop(0, n_tiles, body_count,
                               jnp.zeros((q,), jnp.int32))
    all_certified = jnp.all(counts == k)

    # traced fallback: when any query fails the certificate, run the exact
    # merge sweep — lax.cond keeps knn fully jittable with no host sync
    def exact(_):
        return _knn_sweep(x_sq, x, y_padded, m_real, k, tile)

    def keep(_):
        return best_v, best_i

    return jax.lax.cond(all_certified, keep, exact, None)


@instrument("distance.knn")
def knn(res, index, queries, k: int, metric: str = "sqeuclidean",
        tile: Optional[int] = None, algo: str = "auto",
        certify: str = "kernel") -> Tuple[jax.Array, jax.Array]:
    """Brute-force k nearest neighbors. Returns (distances [nq, k],
    indices [nq, k]), nearest first.
    (ref: pre-cuVS brute_force::knn = pairwise distance + select_k, fused)

    ``algo``:
      - ``"auto"``: the fused Pallas pipeline (certified-exact slotted
        top-k, see knn_fused) on TPU when shapes fit its envelope;
        the streamed XLA sweep otherwise.
      - ``"fused"`` / ``"fused_fast"``: force the Pallas pipeline
        (bf16x3 exact / 1-pass bf16).
      - ``"streamed"``: force the streamed XLA sweep.

    ``tile`` sizes the streamed sweep only; the fused pipeline has its own
    tiling and bounds its workspace by chunking queries internally.

    ``metric="cosine"`` solves certified-exact squared-L2 on
    row-normalized operands (monotone-equivalent ranking) and returns
    ``1 − cos_sim = d2/2`` — so the fused Pallas pipeline serves cosine
    too. Degenerate zero-norm rows normalize to the zero vector
    (distance 0.5 to every unit vector) where the pairwise convention
    reports 1.0.

    ``index`` may be a :class:`~raft_tpu.distance.knn_fused.KnnIndex`
    (built once with ``prepare_knn_index`` — the build/query split for
    repeated query batches); the metric must match what the index was
    prepared for ("l2" serves sqeuclidean/euclidean/l2, "ip" serves
    inner_product; prepare on pre-normalized data for cosine).

    ``certify="f32"`` (fused pipeline, passes=1 indexes): adaptive
    precision — f32-certified results at 1-pass kernel cost (see
    knn_fused).
    """
    res = ensure_resources(res)
    from raft_tpu.distance.knn_fused import KnnIndex, knn_fused

    expects(certify in ("kernel", "f32"),
            "knn: certify must be 'kernel' or 'f32', got %r", certify)
    if isinstance(index, KnnIndex):
        queries = jnp.asarray(queries, jnp.float32)
        if metric in ("sqeuclidean", "euclidean", "l2"):
            expects(index.metric == "l2",
                    "knn: index prepared for %r, metric %r needs 'l2'",
                    index.metric, metric)
            dists, idx = knn_fused(queries, index, k, certify=certify)
            if metric in ("euclidean", "l2"):
                dists = jnp.sqrt(jnp.maximum(dists, 0.0))
            return dists, idx
        expects(metric == "inner_product" and index.metric == "ip",
                "knn: prepared-index metric %r cannot serve %r",
                index.metric, metric)
        return knn_fused(queries, index, k, certify=certify)
    index = jnp.asarray(index, jnp.float32)
    queries = jnp.asarray(queries, jnp.float32)
    expects(metric in ("sqeuclidean", "euclidean", "l2", "inner_product",
                       "cosine"),
            "knn: unsupported metric %r", metric)
    if metric == "cosine":
        def _unit(a):
            # same zero-norm guard as pairwise._cosine (1e-30), so both
            # cosine surfaces share one degenerate-input convention
            n = jnp.sqrt(jnp.sum(a * a, axis=1, keepdims=True))
            return a / jnp.maximum(n, 1e-30)

        d2, idx = knn(res, _unit(index), _unit(queries), k,
                      metric="sqeuclidean", tile=tile, algo=algo,
                      certify=certify)
        return d2 * 0.5, idx
    expects(k <= index.shape[0], "knn: k larger than index size")
    expects(algo in ("auto", "fused", "fused_fast", "streamed"),
            "knn: unknown algo %r", algo)
    n = index.shape[0]

    forced_fused = algo in ("fused", "fused_fast")
    # the fused pipeline's candidate pool is 2·128·ceil(n_tiles/g)
    # entries per query under its active (possibly tuned) tiling —
    # mirror knn_fused's own envelope so auto never round-trips an
    # exception
    from raft_tpu.distance.knn_fused import fused_config

    # auto-routing only ever runs passes=3, and FORCED fused requests
    # rely on knn_fused's own envelope errors (re-raised below), so the
    # pool precheck mirrors the passes=3 defaults. (The tuned config
    # may carry a database-major grid_order; the pool geometry below is
    # order-invariant — ceil(ceil(n/T)/g) == ceil(n/(g·T)), so the
    # db-padded index yields the same group count.)
    _cfg = fused_config(3)
    _T, _g = _cfg.T, _cfg.g
    # pool = 2·128 per tile-GROUP (g = tiles per group), matching
    # knn_fused's own pool construction — NOT 2·128/g per tile
    _n_tiles = -(-max(n, _T) // _T)
    fused_pool = 2 * (-(-_n_tiles // _g)) * 128
    # d ≤ 512 takes the single-shot kernel; wider features take the
    # d-chunked kernel (VMEM scratch accumulator) up to a pragmatic cap;
    # fused_eligible is THE shared backend/shape gate (also used by
    # models.NearestNeighbors.fit's prepare decision)
    from raft_tpu.distance.knn_fused import fused_eligible

    auto_fused = (algo == "auto" and fused_eligible(n, queries.shape[1])
                  and k <= fused_pool)
    if forced_fused or auto_fused:
        from raft_tpu.distance.knn_fused import knn_fused

        try:
            dists, idx = knn_fused(
                queries, index, k,
                passes=1 if algo == "fused_fast" else 3,
                metric="ip" if metric == "inner_product" else "l2",
                certify=certify)
            if metric in ("euclidean", "l2"):
                dists = jnp.sqrt(jnp.maximum(dists, 0.0))
            return dists, idx
        except NotImplementedError:
            if algo != "auto":
                raise

    expects(certify == "kernel",
            "knn: certify='f32' is a fused-pipeline contract, but this "
            "call routed to the streamed sweep (shape/backend outside "
            "the fused envelope) — it cannot be honored silently")
    if tile is None:
        tile = max(128, min(index.shape[0],
                            res.workspace.allocation_limit
                            // (8 * max(queries.shape[0], 1))))
        tile = min(tile, 8192)
    y_padded, _ = _pad_rows(index, int(tile))
    if metric == "inner_product":
        return _ip_sweep(queries, y_padded, jnp.asarray(index.shape[0]),
                         k, int(tile))
    x_sq = jnp.sum(queries * queries, axis=1)
    use_certified = n >= 16 * int(tile) and k <= 256
    if use_certified:
        dists, idx = _knn_certified_approx(
            queries, y_padded, jnp.asarray(n), k, int(tile))
    else:
        dists, idx = _knn_sweep(x_sq, queries, y_padded, jnp.asarray(n),
                                k, int(tile))
    if metric in ("euclidean", "l2"):
        dists = jnp.sqrt(jnp.maximum(dists, 0.0))
    return dists, idx


@partial(jax.jit, static_argnames=("k", "tile"))
def _ip_sweep(x, y_padded, m_real, k: int, tile: int):
    n = x.shape[0]
    n_tiles = y_padded.shape[0] // tile

    def body(i, carry):
        best_v, best_i = carry
        yt = jax.lax.dynamic_slice_in_dim(y_padded, i * tile, tile, axis=0)
        ip = jnp.matmul(x, yt.T, preferred_element_type=jnp.float32)
        col = i * tile + jnp.arange(tile, dtype=jnp.int32)
        valid = col[None, :] < m_real
        ip = jnp.where(valid, ip, -jnp.inf)
        threshold = best_v[:, k - 1]
        improves = jnp.any(ip > threshold[:, None])
        cols = jnp.broadcast_to(col[None, :], ip.shape)

        def do_merge(_):
            return _merge_topk(best_v, best_i, ip, cols, k, False)

        def skip(_):
            return best_v, best_i

        return jax.lax.cond(improves, do_merge, skip, None)

    best_v = jnp.full((n, k), -jnp.inf, jnp.float32)
    best_i = jnp.full((n, k), -1, jnp.int32)
    return jax.lax.fori_loop(0, n_tiles, body, (best_v, best_i))


_SHARDED_KNN_CACHE: dict = {}


@instrument("distance.knn_sharded")
def knn_sharded(res, index, queries, k: int, mesh=None, axis: str = "x",
                metric: str = "sqeuclidean", algo: str = "auto"
                ) -> Tuple[jax.Array, jax.Array]:
    """Data-parallel brute-force KNN over a device mesh: queries are
    row-sharded over ``axis``, the index is replicated, and every shard
    runs the (fused or streamed) single-chip pipeline locally — no
    cross-shard communication is needed because each query's top-k
    depends only on the full index. (ref: the MNMG data-parallel model,
    SURVEY §2.12 — raft-dask shards work across workers the same way.)

    Returns globally-assembled (distances [nq, k], indices [nq, k]).
    """
    from jax.sharding import PartitionSpec as P

    from raft_tpu.parallel import replicated, shard_array

    res = ensure_resources(res)
    if mesh is None:
        mesh = res.mesh
    expects(mesh is not None, "knn_sharded: pass mesh= or set it on res")
    expects(axis in mesh.axis_names,
            "knn_sharded: axis %r not in mesh axes %s", axis,
            tuple(mesh.axis_names))
    ndev = mesh.shape[axis]
    if ndev == 1:
        import warnings

        warnings.warn(
            "knn_sharded over a 1-device mesh shards nothing — set a "
            "multi-device mesh on the handle or pass mesh=",
            RuntimeWarning, stacklevel=2)
    index = jnp.asarray(index, jnp.float32)
    queries = jnp.asarray(queries, jnp.float32)
    nq = queries.shape[0]
    queries, _ = _pad_rows(queries, ndev)

    # cache the shard_map-wrapped callable: a fresh closure per call would
    # defeat the jit cache and recompile every invocation. The workspace
    # budget is in the key because knn() sizes its tile from it at trace
    # time.
    key = (mesh, axis, k, metric, algo, res.workspace.allocation_limit)
    fn = _SHARDED_KNN_CACHE.get(key)
    if fn is None:
        # capture only the scalar budget, not the caller's handle — a
        # cached closure holding res would pin it for process lifetime
        # and silently reuse the FIRST caller's handle on key collisions
        ws_limit = res.workspace.allocation_limit

        def shard_fn(q_shard, idx_repl):
            from raft_tpu.core.resources import (
                DeviceResources, WorkspaceResource)

            local = DeviceResources()
            local.set_workspace_resource(WorkspaceResource(ws_limit))
            return knn(local, idx_repl, q_shard, k=k, metric=metric,
                       algo=algo)

        fn = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=(P(axis), P(axis)),
            check_vma=False))
        _SHARDED_KNN_CACHE[key] = fn
    qs = shard_array(queries, mesh, axis)
    ir = jax.device_put(index, replicated(mesh))
    d, i = fn(qs, ir)
    return d[:nq], i[:nq]


class ShardedKnnIndex(NamedTuple):
    """A row-sharded, row-padded KNN index prepared ONCE
    (:func:`prepare_index_sharded`) — the build/query split for the
    model-parallel mode: queries against it never re-pad or re-shard
    the index."""

    idx_s: jax.Array       # [n_pad, d] f32, sharded over (mesh, axis)
    n: int                 # true (unpadded) row count
    mesh: object           # the Mesh it was sharded over
    axis: str


def prepare_index_sharded(res, index, mesh=None, axis: str = "x"
                          ) -> ShardedKnnIndex:
    """Pad the index rows to a shard multiple ON HOST and place the
    shards directly (device_put with a NamedSharding streams each
    shard from host memory — the full matrix never materializes on one
    device, which is the point of the bigger-than-HBM index mode)."""
    import numpy as np

    from raft_tpu.parallel import shard_array

    res = ensure_resources(res)
    if mesh is None:
        mesh = res.mesh
    expects(mesh is not None,
            "prepare_index_sharded: pass mesh= or set it on res")
    expects(axis in mesh.axis_names,
            "prepare_index_sharded: axis %r not in mesh axes %s", axis,
            tuple(mesh.axis_names))
    arr = np.asarray(index, np.float32)
    n = arr.shape[0]
    ndev = int(mesh.shape[axis])
    npad = (-n) % ndev
    if npad:
        arr = np.concatenate(
            [arr, np.zeros((npad, arr.shape[1]), np.float32)])
    return ShardedKnnIndex(shard_array(arr, mesh, axis), n, mesh, axis)


def knn_index_sharded(res, index, queries, k: int, mesh=None,
                      axis: str = "x", metric: str = "sqeuclidean",
                      algo: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Model-parallel brute-force KNN: the INDEX rows are sharded over
    ``axis`` (the mode for indexes too large for one chip's HBM — each
    chip holds n/ndev rows), queries replicated. Every shard selects
    its local top-k, local ids shift to global by the shard's row
    offset, the per-shard candidates ride ONE ``all_gather`` over the
    mesh axis (k·nq values — the only cross-chip traffic), and a final
    merge top-k assembles the exact global result. (ref: the
    raft-dask/legacy ``knn_merge_parts`` pattern — per-worker partial
    KNN + cross-worker merge; SURVEY §2.12's MNMG model with the model
    axis sharded instead of the data axis.)

    Exact for every metric/algo the single-chip ``knn`` serves: each
    shard over-selects by the pad count (zero-padded rows — all in the
    last shard — can rank inside a local top-k, so selecting
    k + n_pads locally guarantees ≥ k REAL candidates per shard), the
    merge masks pads by global id, and the global top-k is then a
    subset of the union of per-shard real candidates."""
    from jax.sharding import PartitionSpec as P

    from raft_tpu.parallel import replicated, shard_array

    res = ensure_resources(res)
    if mesh is None:
        mesh = res.mesh
    expects(mesh is not None,
            "knn_index_sharded: pass mesh= or set it on res")
    expects(axis in mesh.axis_names,
            "knn_index_sharded: axis %r not in mesh axes %s", axis,
            tuple(mesh.axis_names))
    ndev = mesh.shape[axis]
    queries = jnp.asarray(queries, jnp.float32)
    if isinstance(index, ShardedKnnIndex):
        expects(index.axis == axis,
                "knn_index_sharded: index prepared for axis %r, got %r",
                index.axis, axis)
        # the PREPARED mesh wins — a mismatched mesh= would silently
        # re-lay-out the whole index across devices on every query
        # (full cross-device transfer at bigger-than-HBM scale)
        expects(index.mesh == mesh,
                "knn_index_sharded: index prepared for a different "
                "mesh — re-prepare or pass its mesh")
        idx_prepared, n = index.idx_s, index.n
        index_p = idx_prepared
    else:
        index = jnp.asarray(index, jnp.float32)
        n = index.shape[0]
        index_p, _ = _pad_rows(index, ndev)
        idx_prepared = None
    expects(k <= n, "knn_index_sharded: k larger than index size")
    rows_per = index_p.shape[0] // ndev
    n_pads = index_p.shape[0] - n
    k_loc = k + n_pads                      # over-select past any pads
    expects(k_loc <= rows_per,
            "knn_index_sharded: k=%d (+%d pad slots) exceeds the "
            "per-shard row count %d — use fewer shards or the "
            "query-sharded mode", k, n_pads, rows_per)

    # rows_per is baked into the cached closure (the global-id shift):
    # the index geometry MUST be part of the key
    key = ("idx", mesh, axis, k_loc, rows_per, n, metric, algo,
           res.workspace.allocation_limit)
    fn = _SHARDED_KNN_CACHE.get(key)
    if fn is None:
        ws_limit = res.workspace.allocation_limit

        def shard_fn(idx_shard, q_repl):
            from raft_tpu.core.resources import (
                DeviceResources, WorkspaceResource)

            local = DeviceResources()
            local.set_workspace_resource(WorkspaceResource(ws_limit))
            d_loc, i_loc = knn(local, idx_shard, q_repl, k=k_loc,
                               metric=metric, algo=algo)
            gid = i_loc + jax.lax.axis_index(axis) * rows_per
            dg = jax.lax.all_gather(d_loc, axis, axis=1,
                                    tiled=True)          # [nq, ndev·k]
            ig = jax.lax.all_gather(gid, axis, axis=1, tiled=True)
            return dg, ig

        fn = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=(P(), P()),
            check_vma=False))
        _SHARDED_KNN_CACHE[key] = fn

    idx_s = (idx_prepared if idx_prepared is not None
             else shard_array(index_p, mesh, axis))
    qr = jax.device_put(queries, replicated(mesh))
    dg, ig = fn(idx_s, qr)
    # merge: exact top-k of the gathered per-shard candidates; padded
    # rows (global id ≥ n) masked out
    dg = jnp.where(ig < n, dg, jnp.inf if metric != "inner_product"
                   else -jnp.inf)
    if metric == "inner_product":
        top, pos = jax.lax.top_k(dg, k)
    else:
        neg, pos = jax.lax.top_k(-dg, k)
        top = -neg
    return top, jnp.take_along_axis(ig, pos, axis=1)
