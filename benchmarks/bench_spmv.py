#!/usr/bin/env python
"""SpMV path comparison at BASELINE-config-4 scale (1M-edge graph):
tiled-ELL Pallas kernels vs the gather+segment_sum XLA path.

(ref: the cusparse SpMV role — cusparse_wrappers.h:1; the measurement
justifies which path sparse.linalg.spmv should prefer on TPU.)

Writes ``SPMV_BENCH.json``. Probe-guarded; refuses to record CPU numbers
as if they were TPU evidence.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate
import time

import numpy as np

OUT = os.path.join(os.path.dirname(__file__), os.pardir, "SPMV_BENCH.json")


def main():
    dry = gate()

    import jax  # noqa: F401

    import raft_tpu
    from raft_tpu.benchmark import Fixture
    from raft_tpu.core.sparse_types import COOMatrix
    from raft_tpu.random import RngState
    from raft_tpu.random.rmat import rmat_rectangular_gen
    from raft_tpu.sparse import convert, linalg, prepare_spmv

    res = raft_tpu.device_resources()
    assert dry or res.platform == "tpu"

    # 1M-edge RMAT graph, symmetrized (BASELINE config 4's operand)
    scale = 10 if dry else 17        # 131072 nodes (1024 in dry-run)
    n_edges = 10_000 if dry else 1_000_000
    src, dst = rmat_rectangular_gen(res, RngState(7), n_edges, scale, scale)
    import jax.numpy as jnp

    rows = jnp.concatenate([src, dst]).astype(jnp.int32)
    cols = jnp.concatenate([dst, src]).astype(jnp.int32)
    vals = jnp.ones_like(rows, jnp.float32)
    A = COOMatrix(rows, cols, vals, (1 << scale, 1 << scale))
    Acsr = convert.coo_to_csr(A)
    x = jnp.asarray(np.random.default_rng(1).normal(size=1 << scale)
                    .astype(np.float32))
    jax.block_until_ready((Acsr.values, x))

    fx = Fixture(res=res, reps=1 if dry else 5)
    out = {"platform": res.platform, "nnz": int(2 * n_edges),
           "n": int(1 << scale), "unit": "ms"}

    def flush():
        if not dry:  # incremental: a wedge loses only the current point
            with open(OUT, "w") as f:
                json.dump(out, f, indent=1)

    dt = fx.run(lambda v: linalg.spmv(res, Acsr, v), x)["seconds"]
    out["segment_sum_ms"] = round(dt * 1e3, 3)
    flush()

    t0 = time.time()
    tiled = prepare_spmv(Acsr, layout="ell")
    out["prepare_s"] = round(time.time() - t0, 2)
    flush()
    dt = fx.run(lambda v: linalg.spmv(res, tiled, v), x)["seconds"]
    out["tiled_ell_ms"] = round(dt * 1e3, 3)
    out["tiled_speedup"] = round(out["segment_sum_ms"] / out["tiled_ell_ms"],
                                 2)
    flush()

    t0 = time.time()
    pairs = prepare_spmv(Acsr, layout="pairs")   # single-kernel pair layout
    out["prepare_pairs_s"] = round(time.time() - t0, 2)
    flush()
    dt = fx.run(lambda v: linalg.spmv(res, pairs, v), x)["seconds"]
    out["pair_tiled_ms"] = round(dt * 1e3, 3)
    out["pair_speedup_vs_segment"] = round(
        out["segment_sum_ms"] / out["pair_tiled_ms"], 2)
    out["pair_speedup_vs_ell"] = round(
        out["tiled_ell_ms"] / out["pair_tiled_ms"], 2)

    if dry:
        print(json.dumps({"dry_run": True, **out}))
        return 0
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
