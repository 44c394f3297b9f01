#!/usr/bin/env python
"""TPU smoke lane for Pallas kernels: compile + run every custom kernel
NON-interpreted on the real chip and record pass/fail (+ wall time) per
kernel to ``PALLAS_SMOKE.json``.

Why this exists: CI runs on the virtual CPU mesh where every Pallas call
takes ``interpret=True`` — semantics are covered, Mosaic lowering is not
(``tests/test_tpu_aot.py`` compiles the main-path kernels for a
described chip; this lane also runs them). Run it through the chip
tool:

    python benchmarks/pallas_smoke.py

Without a TPU it fails (``benchmarks/_common.gate``) rather than faking
a result.
"""

import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), os.pardir, "PALLAS_SMOKE.json")


def _smoke_fused_l2_topk():
    from raft_tpu.distance.knn_fused import knn_fused

    rng = np.random.default_rng(1)
    x = rng.normal(size=(256, 128)).astype(np.float32)
    y = rng.normal(size=(16384, 128)).astype(np.float32)
    for passes in (1, 3):
        vals, ids = knn_fused(x, y, k=16, passes=passes)
        d2 = ((x[:, None, :] - y[np.asarray(ids)]) ** 2).sum(-1)
        np.testing.assert_allclose(np.asarray(vals), d2, rtol=1e-3,
                                   atol=1e-3)


def _smoke_spmv_tiled():
    import scipy.sparse as sp

    from raft_tpu.sparse import CSRMatrix, linalg, prepare_spmv

    m = sp.random(4096, 4096, density=0.01, random_state=2,
                  dtype=np.float32, format="csr")
    A = CSRMatrix(np.asarray(m.indptr, np.int32),
                  np.asarray(m.indices, np.int32),
                  m.data.astype(np.float32), m.shape)
    x = np.random.default_rng(3).normal(size=4096).astype(np.float32)
    # default v2 ELL layout AND the single-kernel pair layout
    y = np.asarray(linalg.spmv(None, prepare_spmv(A), x))
    y2 = np.asarray(linalg.spmv(None, prepare_spmv(A, layout="pairs"), x))
    ref = m @ x
    np.testing.assert_allclose(y2, ref, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(y, ref, rtol=2e-4, atol=2e-4)


def _smoke_spmm_tiled():
    import scipy.sparse as sp

    from raft_tpu.sparse import CSRMatrix, linalg, prepare_spmv

    m = sp.random(2048, 2048, density=0.01, random_state=4,
                  dtype=np.float32, format="csr")
    A = CSRMatrix(np.asarray(m.indptr, np.int32),
                  np.asarray(m.indices, np.int32),
                  m.data.astype(np.float32), m.shape)
    B = np.random.default_rng(5).normal(size=(2048, 32)).astype(np.float32)
    Y = np.asarray(linalg.spmm(None, prepare_spmv(A), B))
    np.testing.assert_allclose(Y, m @ B, rtol=5e-4, atol=5e-4)


def _smoke_fused_l2_topk_dchunk():
    """Wide-feature (d > 512) variant: the d-chunked kernel with the VMEM
    scratch score accumulator."""
    from raft_tpu.distance.knn_fused import knn_fused

    rng = np.random.default_rng(9)
    x = rng.normal(size=(128, 768)).astype(np.float32)
    y = rng.normal(size=(8192, 768)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=8, passes=3)
    d2 = ((x[:, None, :] - y[np.asarray(ids)]) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(vals), d2, rtol=1e-3, atol=1e-2)


def _smoke_sddmm_tiled():
    import scipy.sparse as sp

    from raft_tpu.sparse import CSRMatrix, linalg, prepare_sddmm

    m = sp.random(2048, 2048, density=0.01, random_state=7,
                  dtype=np.float32, format="csr")
    S = CSRMatrix(np.asarray(m.indptr, np.int32),
                  np.asarray(m.indices, np.int32),
                  m.data.astype(np.float32), m.shape)
    rng = np.random.default_rng(8)
    A = rng.normal(size=(2048, 128)).astype(np.float32)
    B = rng.normal(size=(128, 2048)).astype(np.float32)
    out = linalg.sddmm(None, A, B, prepare_sddmm(S))
    want = (A @ B)[np.asarray(S.row_ids()), np.asarray(S.indices)]
    np.testing.assert_allclose(np.asarray(out.values), want,
                               rtol=1e-3, atol=1e-3)


def _smoke_histogram_blocked():
    from raft_tpu.ops.histogram_pallas import histogram_blocked

    bins = np.random.default_rng(6).integers(
        0, 64, size=(8192, 128)).astype(np.int32)
    got = np.asarray(histogram_blocked(bins, 64))
    want = np.stack([np.bincount(bins[:, c], minlength=64)
                     for c in range(bins.shape[1])], axis=1)
    np.testing.assert_array_equal(got, want)


def _smoke_select_k_slotted_pallas():
    from raft_tpu.matrix import SelectAlgo, select_k

    v = np.random.default_rng(5).normal(size=(64, 65536)).astype(np.float32)
    ov, oi = select_k(None, v, k=32, algo=SelectAlgo.SLOTTED)
    ref = np.sort(v, axis=1)[:, :32]
    np.testing.assert_allclose(np.asarray(ov), ref, rtol=1e-6)
    # returned positions must reproduce the values
    got = np.take_along_axis(v, np.asarray(oi), axis=1)
    np.testing.assert_allclose(got, ref, rtol=1e-6)


def _smoke_unexpanded_pairwise():
    # round-4 kernel: dc multi-ref (1,128) blocks + one-hot selector
    # dot over the bf16x3 split — the Mosaic-lowering risk points
    from scipy.spatial.distance import cdist

    from raft_tpu.distance.types import DistanceType as DT
    from raft_tpu.ops.unexpanded_pallas import unexpanded_pairwise_tiled

    rng = np.random.default_rng(5)
    x = rng.normal(size=(300, 96)).astype(np.float32)
    y = rng.normal(size=(2000, 96)).astype(np.float32)
    for t, ref, p in ((DT.L1, "cityblock", 2.0),
                      (DT.Linf, "chebyshev", 2.0),
                      (DT.Canberra, "canberra", 2.0),
                      (DT.LpUnexpanded, "minkowski", 3.0)):
        kw = {"p": 3.0} if ref == "minkowski" else {}
        out = np.asarray(unexpanded_pairwise_tiled(x, y, t, p))
        np.testing.assert_allclose(out, cdist(x, y, ref, **kw),
                                   rtol=1e-3, atol=1e-3)
    # BrayCurtis: the structurally different two-output pallas_call
    xa, ya = np.abs(x), np.abs(y)
    out = np.asarray(unexpanded_pairwise_tiled(xa, ya, DT.BrayCurtis,
                                               2.0))
    np.testing.assert_allclose(out, cdist(xa, ya, "braycurtis"),
                               rtol=1e-3, atol=1e-3)


def _smoke_unexpanded_guarded_dispatch():
    # round-5: the finiteness guard is a lax.cond INSIDE the program —
    # a jitted public-API caller must lower the kernel branch through
    # real Mosaic, and the XLA branch must serve non-finite inputs
    import jax
    from scipy.spatial.distance import cdist

    from raft_tpu import distance

    rng = np.random.default_rng(7)
    x = rng.normal(size=(1024, 64)).astype(np.float32)  # n*m = 2^20:
    y = rng.normal(size=(1024, 64)).astype(np.float32)  # TPU-eligible

    def f(a, b):
        return distance.pairwise_distance(None, a, b, metric="l1")

    assert "pallas_call" in str(jax.make_jaxpr(f)(x, y))
    out = np.asarray(jax.jit(f)(x, y))
    np.testing.assert_allclose(out, cdist(x, y, "cityblock"),
                               rtol=1e-3, atol=1e-3)
    xinf = x.copy()
    xinf[0, 0] = np.inf
    out = np.asarray(jax.jit(f)(xinf, y))
    assert np.all(np.isinf(out[0])) and np.all(np.isfinite(out[1:]))


KERNELS = {
    "select_k_slotted_pallas": _smoke_select_k_slotted_pallas,
    "fused_l2_topk": _smoke_fused_l2_topk,
    "fused_l2_topk_dchunk": _smoke_fused_l2_topk_dchunk,
    "spmv_tiled": _smoke_spmv_tiled,
    "spmm_tiled": _smoke_spmm_tiled,
    "sddmm_tiled": _smoke_sddmm_tiled,
    "histogram_blocked": _smoke_histogram_blocked,
    "unexpanded_pairwise": _smoke_unexpanded_pairwise,
    "unexpanded_guarded_dispatch": _smoke_unexpanded_guarded_dispatch,
}


def main():
    if gate():
        raise SystemExit("pallas_smoke: runs on the chip only")
    results = {}
    for name, fn in KERNELS.items():
        t0 = time.time()
        try:
            fn()
            results[name] = {"status": "pass",
                             "seconds": round(time.time() - t0, 2)}
        except Exception:
            results[name] = {"status": "fail",
                             "error": traceback.format_exc()[-2000:]}
    payload = {"platform": "tpu", "kernels": results}
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps(payload))
    return 0 if all(r.get("status") != "fail" for r in results.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
