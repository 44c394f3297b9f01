#!/usr/bin/env python
"""Bring-up smoke of raft_tpu on a TPU v5e — the main path once, at the
ann-benchmarks SIFT-1M shape (2^20 x 128 f32, L2), through the public
entry points, checked against a plain ``jax.numpy`` reference.

    python chip_smoke.py              # one chip: exact KNN + served IVF-Flat
    python chip_smoke.py --chips 4    # four chips: sharded exact KNN +
                                      # the comms collectives, nothing else

Data comes from ``raft_tpu.random.make_blobs`` (seed 0); nothing is
downloaded. The last stdout line is ``{"ok": true, "device": {...}}``;
any failed check, exception or recorded degradation exits non-zero
before it, and a machine without a TPU fails at the first phase. The
wall times printed are smoke timings, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Config:
    """Smoke sizes. The defaults are the SIFT-1M deployment; tests run
    the same phases at a tiny size on the CPU."""

    n_rows: int = 1 << 20           # 1,048,576 x 128 f32 = 512 MiB
    dim: int = 128
    n_clusters: int = 64            # blobs, as BASELINE config 2
    cluster_std: float = 2.0
    n_queries: int = 2048           # exact KNN batch
    k: int = 64
    n_check: int = 256              # queries checked against the reference
    k_serve: int = 10
    n_lists: int = 1024
    n_probes: int = 32
    n_requests: int = 64            # ragged, 1..31 rows each (~1,000 total)
    max_request: int = 31
    recall_floor: float = 0.95
    rtol: float = 1e-4
    sharded_rows: int = 4 << 20     # --chips 4: 4M x 128 f32 = 2 GiB


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# -- device -----------------------------------------------------------------
def device_phase(n_chips: int) -> dict:
    """Print what JAX sees; refuse anything but ``n_chips`` TPU v5e."""
    import jax

    from raft_tpu.utils.arch import TPU_SPECS, chip_spec

    devs = jax.devices()
    dev = devs[0]
    stats = dev.memory_stats() or {}
    say(f"platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"bytes_limit={stats.get('bytes_limit')}")
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    spec = chip_spec(dev)
    say(f"chip_spec={spec}")
    check(spec == TPU_SPECS[(5, "e")],
          f"chip_spec resolved {spec.name!r}, not the v5e entry")
    check(len(devs) == n_chips,
          f"expected {n_chips} chips, JAX found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# -- data and reference -------------------------------------------------------
def make_base(res, cfg: Config, n_rows: int):
    """(X [n_rows, d], centers) — the blob base set, seed 0."""
    from raft_tpu.random import make_blobs

    X, _, centers = make_blobs(res, 0, n_rows, cfg.dim,
                               n_clusters=cfg.n_clusters,
                               cluster_std=cfg.cluster_std,
                               return_centers=True)
    return X, centers


def make_queries(res, cfg: Config, centers, n: int, seed: int):
    """Queries drawn around the base set's blob centers."""
    from raft_tpu.random import make_blobs

    Q, _ = make_blobs(res, seed, n, cfg.dim, centers=centers,
                      cluster_std=cfg.cluster_std)
    return Q


def reference_knn(Q, X, k: int, row_offset: int = 0):
    """Plain jax.numpy exact KNN: f32 at Precision.HIGHEST, the full
    distance matrix, then ``lax.top_k``. Independent of raft_tpu."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ref(q, x):
        d2 = (jnp.sum(q * q, axis=1)[:, None] + jnp.sum(x * x, axis=1)[None]
              - 2.0 * jnp.matmul(q, x.T,
                                 precision=jax.lax.Precision.HIGHEST))
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx

    d, i = ref(Q, X)
    return np.asarray(d), np.asarray(i) + row_offset


def compare_knn(d_sys, i_sys, d_ref, i_ref, X_host, Q_host, rtol: float):
    """Distances agree to ``rtol``; id sets agree except among ties —
    an id only one side returned must lie at the k-th distance.
    Returns (worst relative distance error, id mismatches)."""
    d_sys = np.asarray(d_sys, np.float64)
    d_ref = np.asarray(d_ref, np.float64)
    check(d_sys.shape == d_ref.shape and np.all(np.isfinite(d_sys)),
          f"distances not finite / shape {d_sys.shape} != {d_ref.shape}")
    scale = np.maximum(np.abs(d_ref), 1e-6)
    worst = float(np.max(np.abs(d_sys - d_ref) / scale))
    check(worst <= rtol, f"distance error {worst:.3g} > rtol {rtol}")
    mismatches = 0
    for q in range(d_ref.shape[0]):
        a, b = set(i_sys[q].tolist()), set(i_ref[q].tolist())
        odd = sorted(a ^ b)
        if not odd:
            continue
        mismatches += len(odd) // 2
        true_d = np.sum((X_host[odd].astype(np.float64)
                         - Q_host[q].astype(np.float64)) ** 2, axis=1)
        kth = d_ref[q, -1]
        check(bool(np.all(np.abs(true_d - kth) <= rtol * max(kth, 1e-6))),
              f"query {q}: ids {odd} differ and are not ties at the "
              f"k-th distance {kth}")
    return worst, mismatches


# -- exact KNN (BASELINE config 2) ------------------------------------------
def exact_phase(res, cfg: Config, require_tpu: bool = True):
    """Fused exact KNN through ``distance.knn`` on a prepared index."""
    import jax

    from raft_tpu import distance
    from raft_tpu.distance.knn_fused import KnnIndex, fused_eligible
    from raft_tpu.ops.utils import interpret_mode

    X, centers = make_base(res, cfg, cfg.n_rows)
    Q = make_queries(res, cfg, centers, cfg.n_queries, seed=1)
    jax.block_until_ready((X, Q))
    say(f"exact: {cfg.n_rows}x{cfg.dim} f32 base, {cfg.n_queries} "
        f"queries, k={cfg.k}")
    if require_tpu:
        # the auto route's own gate, and Mosaic (not interpreted) kernels
        check(fused_eligible(cfg.n_rows, cfg.dim),
              "algo='auto' would not route to the fused pipeline")
        check(not interpret_mode(), "Pallas kernels would be interpreted")
    index = distance.prepare_knn_index(X)
    check(isinstance(index, KnnIndex), "prepare_knn_index built no index")
    say(f"exact: index T={index.T} Qb={index.Qb} g={index.g} "
        f"passes={index.passes} grid_order={index.grid_order} "
        f"db_dtype={index.db_dtype}")
    t0 = time.perf_counter()
    d, i = distance.knn(res, index, Q, k=cfg.k, algo="auto")
    jax.block_until_ready((d, i))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    d, i = distance.knn(res, index, Q, k=cfg.k, algo="auto")
    jax.block_until_ready((d, i))
    warm = time.perf_counter() - t0
    say(f"exact: smoke timings, not benchmark numbers: cold (compile) "
        f"{cold:.3f} s, warm {warm:.3f} s")
    n = cfg.n_check
    d_ref, i_ref = reference_knn(Q[:n], X, cfg.k)
    worst, mism = compare_knn(np.asarray(d)[:n], np.asarray(i)[:n],
                              d_ref, i_ref, np.asarray(X),
                              np.asarray(Q[:n]), cfg.rtol)
    say(f"exact: {n} queries match the f32 HIGHEST reference — worst "
        f"relative distance error {worst:.3e}, {mism} tied-id swaps")
    return X, centers, index, {"worst_rel_err": worst, "tied_swaps": mism}


# -- served IVF-Flat ----------------------------------------------------------
def serve_phase(res, cfg: Config, X, centers, index):
    """IVF-Flat behind ServingEngine: ragged requests, recall@k against
    the exact fused results, list-major fine scan, no degradation."""
    from raft_tpu import distance
    from raft_tpu.observability.explain import clear_records, explain_records
    from raft_tpu.resilience import degradation_count
    from raft_tpu.serving.engine import ServingEngine

    rng = np.random.default_rng(0)
    sizes = rng.integers(1, cfg.max_request + 1, cfg.n_requests)
    Qs = np.asarray(make_queries(res, cfg, centers, int(sizes.sum()),
                                 seed=2))
    _, exact_ids = distance.knn(res, index, Qs, k=cfg.k_serve)
    exact_ids = np.asarray(exact_ids)
    degraded0 = degradation_count()
    clear_records()
    t0 = time.perf_counter()
    eng = ServingEngine(X, k=cfg.k_serve, res=res, algorithm="ivf_flat",
                        n_lists=cfg.n_lists, n_probes=cfg.n_probes,
                        explain_frac=1.0)
    eng.start()
    say(f"serve: IVF-Flat n_lists={cfg.n_lists} n_probes={cfg.n_probes} "
        f"buckets={eng.buckets}; build + warm-up {time.perf_counter() - t0:.1f} s "
        f"(smoke timing)")
    try:
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        t0 = time.perf_counter()
        futs = [eng.submit(Qs[a:b]) for a, b in zip(bounds[:-1], bounds[1:])]
        got = [f.result(timeout=600) for f in futs]
        wall = time.perf_counter() - t0
    finally:
        eng.stop()
    ids = np.concatenate([g[1] for g in got])
    vals = np.concatenate([g[0] for g in got])
    check(ids.shape == exact_ids.shape, f"served ids {ids.shape}")
    check(bool(np.all(np.isfinite(vals))), "served distances not finite")
    recall = float(np.mean([len(set(a) & set(b)) / cfg.k_serve
                            for a, b in zip(ids, exact_ids)]))
    say(f"serve: {len(futs)} requests, {len(ids)} queries in {wall:.2f} s "
        f"(smoke timing); recall@{cfg.k_serve} = {recall:.4f}")
    check(recall >= cfg.recall_floor,
          f"recall@{cfg.k_serve} {recall:.4f} < {cfg.recall_floor}")
    records = [r for r in explain_records() if r.get("plane") == "ivf_flat"]
    check(bool(records), "no explain record of an IVF-Flat batch")
    for r in records:
        scans = r.get("fine_scan")
        scans = scans if isinstance(scans, list) else [scans]
        check(all(s == "list" for s in scans) and
              "fine_scan_degrade" not in r,
              f"a batch did not run the list-major fine scan: {scans}")
    check(degradation_count() == degraded0,
          f"{degradation_count() - degraded0} degradations recorded")
    say(f"serve: {len(records)} batches, all list-major, no degradation")
    return {"recall": recall}


# -- four chips (BASELINE config 5) -------------------------------------------
def sharded_phase(res, cfg: Config):
    """Row-sharded exact KNN over every device, against the plain
    reference per row block, plus one all-reduce and one all-gather."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.comms import MeshComms, Op
    from raft_tpu.distance.knn_sharded import (knn_fused_sharded,
                                               prepare_knn_index_sharded)
    from raft_tpu.parallel import make_mesh

    devs = jax.devices()
    p = len(devs)
    mesh = make_mesh({"x": p}, devices=devs)
    X, centers = make_base(res, cfg, cfg.sharded_rows)
    Q = make_queries(res, cfg, centers, cfg.n_queries, seed=1)
    X_host, Q_host = np.asarray(X), np.asarray(Q)
    del X
    say(f"sharded: {cfg.sharded_rows}x{cfg.dim} f32 over {p} devices, "
        f"{cfg.n_queries} queries, k={cfg.k}")
    sidx = prepare_knn_index_sharded(X_host, mesh=mesh, axis="x", res=res)
    owners = {s.device for s in sidx.y_hi_s.addressable_shards}
    check(len(owners) == p, f"index shards sit on {len(owners)} devices")
    t0 = time.perf_counter()
    d, i = knn_fused_sharded(Q, sidx, cfg.k, mesh=mesh, axis="x", res=res)
    jax.block_until_ready((d, i))
    say(f"sharded: smoke timing, not a benchmark number: cold "
        f"{time.perf_counter() - t0:.3f} s")
    n = cfg.n_check
    block = -(-cfg.sharded_rows // p)
    parts = [reference_knn(jax.device_put(Q_host[:n], dev),
                           jax.device_put(X_host[r * block:(r + 1) * block],
                                          dev), cfg.k, r * block)
             for r, dev in enumerate(devs)]
    d_all = np.concatenate([pd for pd, _ in parts], axis=1)
    i_all = np.concatenate([pi for _, pi in parts], axis=1)
    order = np.argsort(d_all, axis=1, kind="stable")[:, :cfg.k]
    d_ref = np.take_along_axis(d_all, order, axis=1)
    i_ref = np.take_along_axis(i_all, order, axis=1)
    worst, mism = compare_knn(np.asarray(d)[:n], np.asarray(i)[:n], d_ref,
                              i_ref, X_host, Q_host[:n], cfg.rtol)
    say(f"sharded: {n} queries match the per-block f32 HIGHEST reference "
        f"— worst relative distance error {worst:.3e}, {mism} tied-id "
        f"swaps")

    comms = MeshComms("x", size=p)
    vals = np.arange(p * 8 * 128, dtype=np.float32).reshape(p * 8, 128)

    def collectives(v):
        return comms.allreduce(v, Op.SUM), comms.allgather(v)

    fn = jax.jit(jax.shard_map(collectives, mesh=mesh, in_specs=P("x"),
                               out_specs=(P("x"), P("x")), check_vma=False))
    red, gat = fn(jax.device_put(vals, NamedSharding(mesh, P("x"))))
    blocks = vals.reshape(p, 8, 128)
    want_red = np.tile(blocks.sum(axis=0), (p, 1))
    check(np.array_equal(np.asarray(red), want_red), "all-reduce mismatch")
    want_gat = np.tile(blocks[None], (p, 1, 1, 1)).reshape(p * p, 8, 128)
    check(np.array_equal(np.asarray(gat).reshape(p * p, 8, 128), want_gat),
          "all-gather mismatch")
    say(f"sharded: all-reduce and all-gather over {p} devices match numpy")
    return {"worst_rel_err": worst, "tied_swaps": mism}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded path and its comparison only")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the list-major Pallas fine scan is the served path under test
    os.environ["RAFT_TPU_IVF_FINE_SCAN"] = "list"
    from raft_tpu.utils.compile_cache import use_compile_cache

    say(f"compile cache: {use_compile_cache()}")
    import raft_tpu
    from raft_tpu.resilience import degradation_count

    cfg = Config()
    try:
        device = device_phase(args.chips)
        res = raft_tpu.DeviceResources(seed=0)
        if args.chips == 4:
            sharded_phase(res, cfg)
        else:
            X, centers, index, _ = exact_phase(res, cfg)
            serve_phase(res, cfg, X, centers, index)
        check(degradation_count() == 0,
              f"{degradation_count()} degradations recorded")
    except Exception as e:  # the smoke's one boundary: report and fail
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
