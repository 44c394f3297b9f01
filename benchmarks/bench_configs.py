#!/usr/bin/env python
"""BASELINE.json eval-config benchmarks — all five driver configs in one
runner, one JSON artifact (``CONFIG_BENCH.json``).

Configs (BASELINE.json "configs"):
  1. pylibraft pairwise_distance (L2) on make_blobs 5k×50
  2. fused L2-NN + select_k top-64 on 1M×128   (bench.py's metric)
  3. SVD / randomized-SVD + Lanczos on 100k×1k dense
  4. sparse spectral embedding (COO Laplacian + Lanczos), 1M-edge graph
  5. MNMG allreduce/allgather across an ICI mesh. A bus-bandwidth claim
     requires >1 physical chips; otherwise only code-path timings are
     recorded and the row is tagged ``representative: false``.

Fails without a TPU like bench.py; JAX_PLATFORMS=cpu runs a tiny-scale
dry-run to validate the harness without recording an artifact.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate

import numpy as np

OUT = os.path.join(os.path.dirname(__file__), os.pardir, "CONFIG_BENCH.json")


def main():
    dry = gate()

    import jax
    import jax.numpy as jnp

    import raft_tpu
    from raft_tpu import distance, linalg
    from raft_tpu.benchmark import Fixture
    from raft_tpu.random import RngState, make_blobs

    res = raft_tpu.device_resources()
    fx = Fixture(res=res, reps=1 if dry else 3)
    out = {"platform": res.platform, "dry_run": dry, "configs": {}}

    def record(name, payload):
        # one config failing (or a wedge killing the process) must not
        # lose the others: record + flush the artifact incrementally
        out["configs"][name] = payload
        print(json.dumps({name: payload}), flush=True)
        if not dry:
            with open(OUT, "w") as f:
                json.dump(out, f, indent=1)

    def config(name):
        def deco(fn):
            try:
                record(name, fn())
            except Exception as e:  # noqa: BLE001 — record, keep going
                record(name, {"error": f"{type(e).__name__}: {e}"[:300]})
        return deco

    @config("1_pairwise_l2_5kx50")
    def _():
        X1, _ = make_blobs(res, RngState(0), 5000 if not dry else 500, 50,
                           n_clusters=8)
        r = fx.run(lambda a: distance.pairwise_distance(res, a, a[:1000]), X1)
        n1 = X1.shape[0]
        return {"ms": round(r["seconds"] * 1e3, 3),
                "gbps_distmatrix": round(n1 * 1000 * 4 / r["seconds"] / 1e9,
                                         2)}

    @config("2_fused_l2nn_selectk_1Mx128")
    def _():
        n2, d2, q2 = (1_000_000, 128, 2048) if not dry else (20_000, 64, 256)
        X2, _ = make_blobs(res, RngState(1), n2, d2, n_clusters=64)
        Q2 = X2[:q2]
        r = fx.run(lambda q: distance.knn(res, X2, q, k=64), Q2)
        return {"ms": round(r["seconds"] * 1e3, 3),
                "gbps_effective": round(q2 * n2 * 4 / r["seconds"] / 1e9, 2)}

    n3, d3 = (100_000, 1000) if not dry else (2000, 100)
    X3, _ = make_blobs(res, RngState(2), n3, d3, n_clusters=16)

    @config("3_rsvd_100kx1k")
    def _():
        r = fx.run(lambda a: linalg.randomized_svd(res, a, k=16)[1], X3)
        return {"ms": round(r["seconds"] * 1e3, 3)}

    @config("3_lanczos_dense_gram")
    def _():
        # Lanczos on the gram operator (symmetric), jitted-loop variant
        from raft_tpu.sparse.solver.lanczos import lanczos_compute_eigenpairs
        from raft_tpu.sparse.solver.lanczos_types import LanczosSolverConfig

        G = (X3[:, : min(d3, 256)].T @ X3[:, : min(d3, 256)]) / n3
        cfg = LanczosSolverConfig(n_components=8, max_iterations=300,
                                  ncv=32, tolerance=1e-6, seed=0,
                                  jit_loop=True)
        r = fx.run(lambda g: lanczos_compute_eigenpairs(res, g, cfg)[0], G)
        return {"ms": round(r["seconds"] * 1e3, 3)}

    @config("4_spectral_embedding_1Medge")
    def _():
        from raft_tpu.core.sparse_types import COOMatrix
        from raft_tpu.models import SpectralEmbedding
        from raft_tpu.random.rmat import rmat_rectangular_gen

        scale, n_edges = (17, 1_000_000) if not dry else (10, 10_000)
        src, dst = rmat_rectangular_gen(res, RngState(3), n_edges, scale,
                                        scale)
        rows = jnp.concatenate([src, dst]).astype(jnp.int32)
        cols = jnp.concatenate([dst, src]).astype(jnp.int32)
        adj = COOMatrix(rows, cols, jnp.ones_like(rows, jnp.float32),
                        (1 << scale, 1 << scale))
        # both pipeline variants: CSR segment-sum matvec vs the tiled-ELL
        # Pallas kernel (end-to-end incl. the one-time host conversion)
        r = fx.run(lambda a: SpectralEmbedding(
            n_components=4, max_iterations=400, res=res,
            jit_loop=True, tiled=False).fit_transform(a), adj)
        out_row = {"ms_csr": round(r["seconds"] * 1e3, 3)}
        if not dry:
            r2 = fx.run(lambda a: SpectralEmbedding(
                n_components=4, max_iterations=400, res=res,
                jit_loop=True, tiled=True).fit_transform(a), adj)
            out_row["ms_tiled"] = round(r2["seconds"] * 1e3, 3)
        return out_row

    @config("5_mnmg_allreduce_allgather")
    def _():
        # DEVICE collectives (shard_map + lax.psum/all_gather — the path
        # that rides ICI), not the host-staged HostComms wrappers: round
        # 2 timed HostComms here and recorded a 3.3 s host-staging
        # artifact that said nothing about collectives. The full
        # sizes-sweep harness is benchmarks/bench_busbw.py; this row is
        # its 64 MB point so CONFIG_BENCH stays one-command.
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P)

        devices = jax.devices()
        ndev = len(devices)
        mesh = Mesh(np.array(devices), ("x",))
        per_rank = (1 << 18) if dry else (64 << 20)
        xs = jax.device_put(jnp.ones((ndev, per_rank // 4), jnp.float32),
                            NamedSharding(mesh, P("x", None)))
        jax.block_until_ready(xs)
        ar = jax.jit(jax.shard_map(lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                               in_specs=P("x", None),
                               out_specs=P("x", None)))
        ag = jax.jit(jax.shard_map(
            lambda a: jax.lax.all_gather(a, "x", axis=0, tiled=True),
            mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)))
        if devices[0].platform != "tpu":
            # CPU in-process collectives deadlock with several sharded
            # executions in flight (Fixture reps are unblocked)
            ar_f = lambda a: jax.block_until_ready(ar(a))  # noqa: E731
            ag_f = lambda a: jax.block_until_ready(ag(a))  # noqa: E731
        else:
            ar_f, ag_f = ar, ag
        r = fx.run(ar_f, xs)
        busbw = 2 * (ndev - 1) / ndev * per_rank / r["seconds"] / 1e9
        r2 = fx.run(ag_f, xs)
        return {
            "n_devices": ndev,
            # real ICI bus bandwidth needs >1 physical TPU chips; anything
            # else is a code-path timing, never a bandwidth claim
            "representative": devices[0].platform == "tpu" and ndev > 1,
            "bytes_per_rank": per_rank,
            "allreduce_ms": round(r["seconds"] * 1e3, 3),
            "allreduce_busbw_gbps": round(busbw, 2) if ndev > 1 else None,
            "allgather_ms": round(r2["seconds"] * 1e3, 3),
            "sweep_harness": "benchmarks/bench_busbw.py"}

    if dry:
        print(json.dumps({"dry_run": True, **out}))
        return 0
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
