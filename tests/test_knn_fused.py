"""Certified fused KNN pipeline tests (interpret-mode kernel + XLA glue).

Mirrors the reference's select_k/fused-distance test strategy
(cpp/tests/matrix/select_k.cu, cpp/tests/distance/fused_l2_nn.cu): exact
results vs an oracle across shapes, plus adversarial inputs that force the
certificate/fixup paths (near-duplicate points sharing slots).

Precision note: the pipeline's score function is the expanded squared L2
in f32 (reference parity). The oracle is f64; assertions use the expanded-
f32 cancellation floor ``ulp(‖x‖²+‖y‖²)`` as tolerance, which is tight
(≈1e-5 for unit-scale data) for everything but near-duplicates.
"""

import numpy as np
import pytest

from raft_tpu.distance.knn_fused import knn_fused

rng = np.random.default_rng(7)


def _oracle(x, y, k):
    xx = (x.astype(np.float64) ** 2).sum(1)
    yy = (y.astype(np.float64) ** 2).sum(1)
    d2 = xx[:, None] + yy[None, :] - 2.0 * (
        x.astype(np.float64) @ y.astype(np.float64).T)
    d2 = np.maximum(d2, 0)
    ids = np.argsort(d2, axis=1, kind="stable")[:, :k]
    scale = float(np.max(xx[:, None] + yy[None, :]))
    return np.take_along_axis(d2, ids, axis=1), ids, 8 * scale * 2.0 ** -24


@pytest.mark.parametrize("Q,m,d,k", [
    (64, 5000, 32, 8),
    (100, 3000, 130, 16),     # d not a lane multiple
    (8, 2048, 128, 64),
    (300, 5000, 32, 8),       # Q not a block multiple
    (16, 300, 20, 5),         # single tile
])
def test_exact_mode_random(Q, m, d, k):
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=3, T=512, Qb=64, g=8)
    ref_vals, ref_ids, tol = _oracle(x, y, k)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    # random data is well-separated: ids must match exactly
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(ref_ids, 1))


def test_exact_mode_clustered_forces_fixup():
    # near-duplicate points share slots -> certificate fails -> fixup/
    # fallback; the result must still be exact to the cancellation floor
    Q, m, d, k = 256, 4096, 64, 32
    base = rng.normal(size=(50, d)).astype(np.float32)
    y = base[rng.integers(0, 50, m)] + 1e-3 * rng.normal(
        size=(m, d)).astype(np.float32)
    x = base[rng.integers(0, 50, Q)] + 1e-3 * rng.normal(
        size=(Q, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=3, T=512, Qb=64, g=8)
    ref_vals, _, tol = _oracle(x, y, k)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)


def test_fast_mode_recall():
    Q, m, d, k = 64, 8192, 64, 16
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=1, T=512, Qb=64, g=8)
    _, ref_ids, _ = _oracle(x, y, k)
    recall = np.mean([len(set(np.asarray(ids)[i]) & set(ref_ids[i])) / k
                      for i in range(Q)])
    assert recall >= 0.99


@pytest.mark.parametrize("Q,m,d,k", [
    (64, 5000, 32, 8),
    (100, 3000, 130, 16),
    (8, 2048, 128, 64),
])
def test_adaptive_precision_f32_certified(Q, m, d, k):
    # certify="f32" at passes=1: the f32-widened certificate + exact
    # fixup must deliver the SAME guarantee as passes=3 (exact w.r.t.
    # f32 scores, verified against the f64 oracle)
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=1, T=512, Qb=64, g=8,
                          certify="f32")
    ref_vals, ref_ids, tol = _oracle(x, y, k)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(ref_ids, 1))


def test_adaptive_precision_clustered():
    # clustered near-duplicates: bf16 ranking genuinely diverges from
    # f32 — the adaptive margin must catch those queries and fix them up
    Q, m, d, k = 128, 4096, 64, 16
    base = rng.normal(size=(40, d)).astype(np.float32)
    y = base[rng.integers(0, 40, m)] + 1e-3 * rng.normal(
        size=(m, d)).astype(np.float32)
    x = base[rng.integers(0, 40, Q)] + 1e-3 * rng.normal(
        size=(Q, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=1, T=512, Qb=64, g=8,
                          certify="f32")
    ref_vals, _, tol = _oracle(x, y, k)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)


def test_adaptive_deep_fixup_tier():
    # enough near-duplicate structure that the adaptive margin fails
    # MANY queries (>128): the new 512 tier must absorb them instead of
    # the full streamed fallback, and results stay f32-exact. The
    # failure count is asserted via the _diag path so the test really
    # covers the 512-tier routing (n_fail in (128, 512]).
    from raft_tpu.distance.knn_fused import (_knn_fused_core,
                                             prepare_knn_index)

    Q, m, d, k = 640, 2048, 24, 8
    rng_t = np.random.default_rng(7)   # pinned: n_fail targeted in-band
    base = rng_t.normal(size=(64, d)).astype(np.float32)
    y = base[rng_t.integers(0, 64, m)] + 3e-3 * rng_t.normal(
        size=(m, d)).astype(np.float32)
    x = base[rng_t.integers(0, 64, Q)] + 3e-3 * rng_t.normal(
        size=(Q, d)).astype(np.float32)
    idx = prepare_knn_index(y, passes=1, T=512, Qb=64, g=8)
    import jax.numpy as jnp

    xp = jnp.asarray(np.pad(x, ((0, 0), (0, (-d) % 128))))
    _, _, n_fail, *_ = _knn_fused_core(
        xp, idx.yp, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw,
        k=k, T=idx.T, Qb=idx.Qb, g=idx.g, passes=1, metric="l2",
        m=m, rescore=True, pbits=idx.pbits, certify="f32", _diag=True)
    assert 128 < int(n_fail) <= 512, int(n_fail)

    vals, ids = knn_fused(x, idx, k=k, certify="f32")
    ref_vals, _, tol = _oracle(x, y, k)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)


def test_adaptive_rejects_lite():
    x = rng.normal(size=(8, 32)).astype(np.float32)
    y = rng.normal(size=(512, 32)).astype(np.float32)
    with pytest.raises(ValueError, match="certify"):
        knn_fused(x, y, k=4, passes=1, rescore=False, certify="f32")
    with pytest.raises(ValueError, match="certify"):
        knn_fused(x, y, k=4, certify="bogus")


def test_query_chunking_matches_single_shot(monkeypatch):
    import raft_tpu.distance.knn_fused as kf

    monkeypatch.setattr(kf, "_Q_CHUNK", 64)
    x = rng.normal(size=(150, 32)).astype(np.float32)   # 3 chunks
    y = rng.normal(size=(3000, 32)).astype(np.float32)
    vals, ids = kf.knn_fused(x, y, k=8, passes=3, T=512, Qb=64, g=8)
    ref_vals, ref_ids, tol = _oracle(x, y, 8)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(ref_ids, 1))


def test_bad_group_size_raises():
    # g is tiles-per-group now: any g ≥ 1 is legal (48 > n_tiles just
    # means one group); g < 1 is rejected
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(2048, 8)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=4, T=512, Qb=16, g=48)
    ref_vals, ref_ids, tol = _oracle(x, y, 4)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    with pytest.raises(ValueError, match="tiles per group"):
        knn_fused(x, y, k=4, T=512, Qb=16, g=0)


def test_k_equals_m_small_index():
    # k == m on a single padded tile: the pool (2·128) covers all 64
    # points, so the result is simply all points sorted
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(64, 8)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=64, T=512, Qb=64, g=8)
    ref_vals, ref_ids, tol = _oracle(x, y, 64)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(ref_ids, 1))


def test_k_larger_than_index_raises():
    with pytest.raises(ValueError):
        knn_fused(rng.normal(size=(4, 8)).astype(np.float32),
                  rng.normal(size=(16, 8)).astype(np.float32), k=32)


def test_knn_auto_routes_and_matches():
    # public API: algo="fused" must agree with algo="streamed"
    from raft_tpu import distance

    x = rng.normal(size=(32, 48)).astype(np.float32)
    y = rng.normal(size=(5000, 48)).astype(np.float32)
    vf, if_ = distance.knn(None, y, x, k=8, algo="fused")
    vs, is_ = distance.knn(None, y, x, k=8, algo="streamed")
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vs), atol=1e-4)
    assert np.array_equal(np.asarray(if_), np.asarray(is_))


def test_knn_fused_euclidean_sqrt():
    from raft_tpu import distance

    x = rng.normal(size=(16, 32)).astype(np.float32)
    y = rng.normal(size=(4096, 32)).astype(np.float32)
    v, _ = distance.knn(None, y, x, k=4, metric="euclidean", algo="fused")
    v2, _ = distance.knn(None, y, x, k=4, metric="sqeuclidean", algo="fused")
    np.testing.assert_allclose(np.asarray(v) ** 2, np.asarray(v2),
                               rtol=1e-5, atol=1e-5)


def test_fused_inner_product_matches_oracle():
    """metric='inner_product' on the fused pipeline (−x·y scoring via
    zeroed norm terms + y/2 operands) matches an f64 oracle and the
    streamed IP sweep."""
    from raft_tpu import distance

    x = rng.normal(size=(48, 32)).astype(np.float32)
    y = rng.normal(size=(4096, 32)).astype(np.float32)
    ip = x.astype(np.float64) @ y.astype(np.float64).T
    want_idx = np.argsort(-ip, axis=1, kind="stable")[:, :8]
    want = np.take_along_axis(ip, want_idx, axis=1)
    vf, if_ = distance.knn(None, y, x, k=8, metric="inner_product",
                           algo="fused")
    vs, is_ = distance.knn(None, y, x, k=8, metric="inner_product",
                           algo="streamed")
    assert np.array_equal(np.sort(np.asarray(if_), 1), np.sort(want_idx, 1))
    assert np.array_equal(np.sort(np.asarray(is_), 1), np.sort(want_idx, 1))
    np.testing.assert_allclose(np.asarray(vf), want, rtol=1e-4, atol=1e-4)
    # fused values are exact-rescored and DESCENDING like the IP sweep
    assert (np.diff(np.asarray(vf), axis=1) <= 1e-6).all()


def test_fused_ip_clustered_forces_fixup():
    """Near-duplicate index points share slots → the IP certificate
    fails → fixup path; the result must still be oracle-exact.
    Q=256 exceeds the first two fixup tiers (16, 128) so the tiered
    scatter branch is reachable (smaller Q can only take the full
    fallback)."""
    Q, m, d, k = 256, 4096, 64, 16
    base = rng.normal(size=(40, d)).astype(np.float32)
    y = base[rng.integers(0, 40, m)] + 1e-3 * rng.normal(
        size=(m, d)).astype(np.float32)
    x = base[rng.integers(0, 40, Q)] + 1e-3 * rng.normal(
        size=(Q, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=3, T=512, Qb=64, g=8,
                          metric="ip")
    ip = x.astype(np.float64) @ y.astype(np.float64).T
    want = np.sort(ip, axis=1)[:, ::-1][:, :k]
    scale = float(np.abs(ip).max())
    np.testing.assert_allclose(np.asarray(vals), want,
                               atol=8 * scale * 2.0 ** -24)


def test_fused_defaults_table(tmp_path, monkeypatch):
    """fused_defaults() reads the measured-best tuning point PER PASSES
    MODE (the round-2 driver bench crashed because the passes=1 winner
    was a passes=3 VMEM OOM), and degrades on malformed tables."""
    import json

    from raft_tpu.distance import knn_fused as kf

    tbl = tmp_path / "TUNE_FUSED.json"
    tbl.write_text(json.dumps({"rows": [
        {"T": 2048, "Qb": 1024, "g": 32, "passes": 1, "seconds": 0.11},
        {"T": 2048, "Qb": 512, "g": 32, "passes": 3, "seconds": 0.122},
        {"T": 2048, "Qb": 256, "g": 32, "passes": 3, "seconds": 0.121},
        {"T": 2048, "Qb": 1024, "g": 32, "passes": 3,
         "error": "vmem oom"},
    ], "best": {"T": 2048, "Qb": 1024, "g": 32, "passes": 1}}))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(tbl))
    # monkeypatch restores the cache even if an assert below fails
    monkeypatch.setattr(kf, "_TUNED", ...)
    # passes=3 gets its own winner, NOT the (OOM-at-p3) p1 winner
    assert kf.fused_defaults(3) == (2048, 256, 32)
    assert kf.fused_defaults(1) == (2048, 1024, 32)

    # legacy table with only a "best" entry: seeds only its own mode
    tbl.write_text(json.dumps(
        {"best": {"T": 4096, "Qb": 512, "g": 16, "passes": 1}}))
    kf._TUNED = ...
    assert kf.fused_defaults(1) == (4096, 512, 16)
    assert kf.fused_defaults(3) == (2048, 256, 16)   # hand default

    tbl.write_text("{not json")
    kf._TUNED = ...
    assert kf.fused_defaults() == (2048, 256, 16)

    # semantically invalid values (T=0 would div-by-zero in knn) degrade
    tbl.write_text(json.dumps({"best": {"T": 0, "Qb": 512, "g": 16}}))
    kf._TUNED = ...
    assert kf.fused_defaults() == (2048, 256, 16)


def test_vmem_footprint_guard():
    """The footprint estimator rejects the configs Mosaic measurably
    rejected on v5e (scoped-vmem stack OOM) and accepts the configs that
    measurably compiled; knn_fused shrinks an over-budget config instead
    of shipping a guaranteed compile failure."""
    from raft_tpu.distance import knn_fused as kf
    from raft_tpu.ops.fused_l2_topk_pallas import (
        VMEM_BUDGET, vmem_footprint)

    # group kernel (the production default): big-tile p3 prunes to a
    # smaller Qb; the post-mask-removal p1 point fits
    assert vmem_footprint(2048, 512, 128, passes=1) <= VMEM_BUDGET
    assert vmem_footprint(2048, 512, 128, passes=3) > VMEM_BUDGET
    assert vmem_footprint(2048, 256, 128, passes=3) <= VMEM_BUDGET

    # the guard inside knn_fused: an explicit over-budget config still
    # produces correct (shrunk-config) results rather than an OOM
    x = rng.normal(size=(64, 32)).astype(np.float32)
    y = rng.normal(size=(4096, 32)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=4, passes=3, T=2048, Qb=1024, g=32)
    d2 = ((x[:, None, :].astype(np.float64)
           - y[None, :, :].astype(np.float64)) ** 2).sum(-1)
    want = np.sort(d2, axis=1)[:, :4]
    np.testing.assert_allclose(np.asarray(vals), want, rtol=1e-5,
                               atol=1e-4)


def test_knn_cosine_matches_pairwise():
    """metric='cosine' (normalized certified-L2 route) agrees with an
    f64 numpy cosine oracle, on both the fused and streamed paths."""
    from raft_tpu import distance

    x = rng.normal(size=(24, 40)).astype(np.float32)
    y = rng.normal(size=(5000, 40)).astype(np.float32)
    # f64 oracle (backend-independent — the jax pairwise matrix would be
    # bf16-grade on TPU)
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    sim = (x64 / np.linalg.norm(x64, axis=1, keepdims=True)) @ (
        y64 / np.linalg.norm(y64, axis=1, keepdims=True)).T
    full = 1.0 - sim
    want_idx = np.argsort(full, axis=1, kind="stable")[:, :6]
    want = np.take_along_axis(full, want_idx, axis=1)
    for algo in ("fused", "streamed"):
        v, i = distance.knn(None, y, x, k=6, metric="cosine", algo=algo)
        # compare id SETS (f32-vs-f64 rounding can swap near-ties)
        assert np.array_equal(np.sort(np.asarray(i), 1),
                              np.sort(want_idx, 1)), algo
        np.testing.assert_allclose(np.sort(np.asarray(v), 1),
                                   np.sort(want, 1), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("d,metric", [(700, "l2"), (1024, "l2"),
                                      (700, "ip")])
def test_wide_features_dchunk_kernel(d, metric):
    """d > 512 routes through the d-chunked kernel (VMEM scratch score
    accumulator) and stays oracle-exact in both metrics."""
    Q, m, k = 40, 3000, 8
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=3, T=512, Qb=64, g=8,
                          metric=metric)
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    if metric == "ip":
        ip = x64 @ y64.T
        ref = np.sort(ip, axis=1)[:, ::-1][:, :k]
        # f32 rescore error grows ~d·2⁻²⁴ relative — scale tol with d
        # (the small-d fuzz constant 8 is exceeded at d=700)
        tol = (8 + d / 4) * float(np.abs(ip).max()) * 2.0 ** -24 + 1e-6
    else:
        xx = (x64 ** 2).sum(1); yy = (y64 ** 2).sum(1)
        d2 = np.maximum(xx[:, None] + yy[None, :] - 2.0 * (x64 @ y64.T), 0)
        ref = np.sort(d2, axis=1)[:, :k]
        tol = 8 * float(np.max(xx[:, None] + yy[None, :])) * 2.0 ** -24
    np.testing.assert_allclose(np.asarray(vals), ref, atol=tol)
    for q in range(Q):
        assert np.unique(np.asarray(ids)[q]).size == k


def test_wide_features_fast_mode_recall():
    Q, m, d, k = 32, 4096, 768, 8
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=1, T=512, Qb=64, g=8)
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    d2 = ((x64 ** 2).sum(1)[:, None] + (y64 ** 2).sum(1)[None, :]
          - 2.0 * (x64 @ y64.T))
    ref_ids = np.argsort(d2, axis=1)[:, :k]
    recall = np.mean([len(set(np.asarray(ids)[i]) & set(ref_ids[i])) / k
                      for i in range(Q)])
    assert recall >= 0.97


def test_group_kernel_vs_numpy_oracle():
    """fused_l2_group_topk's per-(lane, tile-group) top-2 + 3rd-min
    against a direct numpy computation of the same partition."""
    import jax.numpy as jnp

    from raft_tpu.ops.fused_l2_topk_pallas import (
        _LANES, fused_l2_group_topk, split_hi_lo)

    Q, m, d, T, Qb, tpg = 16, 5 * 512, 128, 512, 16, 2
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    M = ((m + T - 1) // T) * T                  # pad rows like knn_fused
    yp = np.concatenate([y, np.zeros((M - m, d), np.float32)])
    n_tiles = M // T
    G = -(-n_tiles // tpg)

    y_hi, y_lo = split_hi_lo(jnp.asarray(yp))
    xx = jnp.sum(jnp.asarray(x) ** 2, axis=1, keepdims=True)
    # half-score operand: yy/2 with +inf on padded columns (the kernel
    # does no masking of its own)
    yyh = jnp.broadcast_to(
        jnp.where((jnp.arange(M) < m)[None, :],
                  0.5 * jnp.sum(jnp.asarray(yp) ** 2, axis=1)[None, :],
                  jnp.inf), (8, M))
    a1, id1, a2, id2, a3 = fused_l2_group_topk(
        jnp.asarray(x), y_hi, y_lo, yyh,
        jnp.full((1,), m, jnp.int32), T=T, Qb=Qb, passes=3, tpg=tpg)
    # recover true squared distances: d2 = 2·r + ‖x‖²
    a1, a2, a3 = (np.asarray(2.0 * v + xx) for v in (a1, a2, a3))
    id1, id2 = map(np.asarray, (id1, id2))
    assert a1.shape == (Q, G * _LANES)

    # numpy oracle: same expanded-L2 score in f64 (tolerance = expanded
    # f32 floor), same (lane, group) partition
    d2 = ((x.astype(np.float64) ** 2).sum(1)[:, None]
          + (yp.astype(np.float64) ** 2).sum(1)[None, :]
          - 2.0 * x.astype(np.float64) @ yp.astype(np.float64).T)
    d2[:, m:] = np.inf
    # raw kernel scores are bf16x3-grade (rescoring happens downstream in
    # knn_fused): tolerance is the kernel's own analytic error bound
    from raft_tpu.distance.knn_fused import _err_bound_coeff
    tol = _err_bound_coeff(d) * float(
        np.linalg.norm(x, axis=1).max()
        * np.linalg.norm(yp, axis=1).max())
    for g in range(G):
        cols = []
        for j in range(g * tpg, min((g + 1) * tpg, n_tiles)):
            cols.append(np.arange(j * T, (j + 1) * T))
        cols = np.concatenate(cols)
        for lane in range(0, _LANES, 37):       # sample lanes
            lane_cols = cols[cols % _LANES == lane]
            sub = d2[:, lane_cols]              # [Q, tiles*T/128]
            order = np.argsort(sub, axis=1)
            s = g * _LANES + lane
            want1 = np.take_along_axis(sub, order[:, :1], 1)[:, 0]
            want2 = np.take_along_axis(sub, order[:, 1:2], 1)[:, 0]
            want3 = np.take_along_axis(sub, order[:, 2:3], 1)[:, 0]
            np.testing.assert_allclose(a1[:, s], want1, atol=tol)
            np.testing.assert_allclose(a2[:, s], want2, atol=tol)
            np.testing.assert_allclose(a3[:, s], want3, atol=tol)
            # ids: the claimed top-2 columns must reproduce the values
            got_c1 = np.take_along_axis(
                d2, id1[:, s][:, None].astype(np.int64), 1)[:, 0]
            got_c2 = np.take_along_axis(
                d2, id2[:, s][:, None].astype(np.int64), 1)[:, 0]
            np.testing.assert_allclose(got_c1, want1, atol=tol)
            np.testing.assert_allclose(got_c2, want2, atol=tol)
            assert (id1[:, s] % _LANES == lane).all()
            assert (id2[:, s] % _LANES == lane).all()


def test_packed_kernel_decode_vs_unpacked():
    """The packed group kernel's (value, embedded code) must decode to
    the same candidates the unpacked kernel reports explicitly."""
    import jax.numpy as jnp

    from raft_tpu.ops.fused_l2_topk_pallas import (
        _LANES, _PACK_MASK, _PACK_PAD, fused_l2_group_topk,
        fused_l2_group_topk_packed, split_hi_lo)
    import jax

    Q, m, d, T, Qb, tpg = 16, 5 * 512 - 37, 64, 512, 16, 2
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    M = ((m + T - 1) // T) * T
    yp = np.concatenate([y, np.zeros((M - m, d), np.float32)])
    n_ch = T // _LANES

    y_hi, y_lo = split_hi_lo(jnp.asarray(yp))
    base = 0.5 * jnp.sum(jnp.asarray(yp) ** 2, axis=1)[None, :]
    valid = (jnp.arange(M) < m)[None, :]
    m_real = jnp.full((1,), m, jnp.int32)
    xj = jnp.asarray(x)

    yyh_inf = jnp.broadcast_to(jnp.where(valid, base, jnp.inf), (8, M))
    a1, id1, a2, id2, a3 = fused_l2_group_topk(
        xj, y_hi, y_lo, yyh_inf, m_real, T=T, Qb=Qb, passes=3, tpg=tpg)

    yyh_pad = jnp.broadcast_to(
        jnp.where(valid, base, _PACK_PAD), (8, M))
    a1p, a2p, a3p = fused_l2_group_topk_packed(
        xj, y_hi, y_lo, yyh_pad, m_real, T=T, Qb=Qb, passes=3, tpg=tpg)

    S_ = a1.shape[1]
    for (ap, au, idu) in ((a1p, a1, id1), (a2p, a2, id2)):
        ap, au, idu = map(np.asarray, (ap, au, idu))
        live = ap < _PACK_PAD * 0.25
        # liveness must agree between the packed sentinel and the
        # unpacked +inf convention
        assert np.array_equal(live, np.isfinite(au))
        # values agree to the packing tolerance |v|*2^-15
        np.testing.assert_allclose(
            ap[live], au[live],
            atol=float(np.abs(au[live]).max()) * 2.0 ** -14)
        # decoded columns == the unpacked kernel's explicit ids
        codes = (np.asarray(ap).view(np.int32) & _PACK_MASK)
        slot = np.broadcast_to(np.arange(S_)[None, :], ap.shape)
        col = ((slot // _LANES) * tpg + codes // n_ch) * T \
            + (codes % n_ch) * _LANES + (slot % _LANES)
        assert np.array_equal(col[live], idu[live])
    # a3 values agree (certificate input)
    a3p_, a3_ = np.asarray(a3p), np.asarray(a3)
    fin = np.isfinite(a3_) & (a3p_ < _PACK_PAD * 0.25)
    np.testing.assert_allclose(
        a3p_[fin], a3_[fin],
        atol=float(np.abs(a3_[fin]).max()) * 2.0 ** -14)


def test_packed_envelope_fallback():
    """g*(T/128) beyond the code space must route to the unpacked
    kernel and still produce exact results."""
    import raft_tpu.distance.knn_fused as kf

    x = rng.normal(size=(16, 16)).astype(np.float32)
    y = rng.normal(size=(9000, 16)).astype(np.float32)
    # T=512 -> 4 chunks; g=4096 -> 16384 codes > 2^13 (the auto-pbits
    # clamp) -> unpacked path (g=128's 512 codes now just widen pbits)
    vals, ids = kf.knn_fused(x, y, k=8, passes=3, T=512, Qb=16, g=4096)
    ref_vals, ref_ids, tol = _oracle(x, y, 8)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(ref_ids, 1))


def test_prepared_index_matches_unprepared():
    """KnnIndex (build/query split) must produce identical results to
    the per-call path, for l2 and ip, through both knn_fused and the
    public distance.knn surface."""
    from raft_tpu import distance
    from raft_tpu.distance.knn_fused import prepare_knn_index

    x = rng.normal(size=(48, 40)).astype(np.float32)
    y = rng.normal(size=(6000, 40)).astype(np.float32)

    for metric in ("l2", "ip"):
        idx = prepare_knn_index(y, metric=metric)
        v1, i1 = knn_fused(x, idx, k=8)
        v2, i2 = knn_fused(x, y, k=8, metric=metric)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    idx = distance.prepare_knn_index(y)
    v3, i3 = distance.knn(None, idx, x, k=8)
    v4, i4 = distance.knn(None, y, x, k=8, algo="fused")
    np.testing.assert_allclose(np.asarray(v3), np.asarray(v4))
    assert np.array_equal(np.asarray(i3), np.asarray(i4))
    # metric mismatch is rejected
    import pytest as _pytest
    with _pytest.raises(Exception):
        distance.knn(None, idx, x, k=8, metric="inner_product")


def test_prepared_index_query_chunking(monkeypatch):
    """Q > _Q_CHUNK with a prepared index shares the operands across
    chunks and still matches the oracle."""
    import raft_tpu.distance.knn_fused as kf

    monkeypatch.setattr(kf, "_Q_CHUNK", 64)
    x = rng.normal(size=(150, 32)).astype(np.float32)
    y = rng.normal(size=(4096, 32)).astype(np.float32)
    idx = kf.prepare_knn_index(y)
    vals, ids = kf.knn_fused(x, idx, k=8)
    ref_vals, ref_ids, tol = _oracle(x, y, 8)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    assert np.array_equal(np.sort(np.asarray(ids), 1), np.sort(ref_ids, 1))


def test_empty_query_batch():
    """Q == 0 returns empty [0, k] outputs instead of the historical
    ZeroDivisionError in the Qb/qpad arithmetic."""
    y = rng.normal(size=(2048, 16)).astype(np.float32)
    vals, ids = knn_fused(np.zeros((0, 16), np.float32), y, k=4)
    assert vals.shape == (0, 4) and ids.shape == (0, 4)


def test_lite_index_no_rescore():
    # store_yp=False drops the f32 matrix (and the lo split for p1);
    # rescore=False results are the exact top-k of the kernel score
    # function — validated against a high-recall f64 oracle and the
    # documented 2^-15 value-perturbation contract
    from raft_tpu.distance.knn_fused import prepare_knn_index

    Q, m, d, k = 64, 8192, 64, 16
    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    for passes, min_recall in ((1, 0.97), (3, 0.99)):
        idx = prepare_knn_index(y, passes=passes, store_yp=False,
                                T=512, Qb=64, g=8)
        assert idx.yp is None
        if passes == 1:
            assert idx.y_lo is None
        vals, ids = knn_fused(x, idx, k)
        ref_vals, ref_ids, _ = _oracle(x, y, k)
        recall = np.mean([len(set(np.asarray(ids)[i]) & set(ref_ids[i])) / k
                          for i in range(Q)])
        assert recall >= min_recall, (passes, recall)
        # values sit within the kernel-score envelope of the f64 truth:
        # bf16 contraction error (p1) resp. bf16x3 + pack error (p3)
        xf, yf = x.astype(np.float64), y.astype(np.float64)
        d2_full = np.maximum(
            (xf ** 2).sum(1)[:, None] + (yf ** 2).sum(1)[None, :]
            - 2.0 * xf @ yf.T, 0.0)
        truth = np.take_along_axis(d2_full, np.asarray(ids), axis=1)
        scale = float(np.max(ref_vals)) + 1.0
        tol = scale * (2.0 ** -6 if passes == 1 else 2.0 ** -12)
        assert np.max(np.abs(np.asarray(vals) - truth)) <= tol
    # explicit rescore=True on a lite index must refuse
    idx1 = prepare_knn_index(y, passes=1, store_yp=False, T=512, Qb=64, g=8)
    with pytest.raises(ValueError):
        knn_fused(x, idx1, k, rescore=True)


# ------------------------------------------------------------------
# certificate fixup: the threshold block filter (fixup_select)
# ------------------------------------------------------------------
def _tile_case(case, F=16, M=4096, k=8):
    """(d2 [F, M], cand [F, k]) for one select case; cand are the rows'
    candidate columns as the fixup holds them."""
    import jax

    r = np.random.default_rng(3)
    d2 = r.random((F, M)).astype(np.float32)
    if case == "ties":          # many equal values, across blocks
        d2 = np.round(d2 * 40) / 40
    elif case == "prefix_pads":
        d2[:, 3000:] = np.inf
    elif case == "ragged":
        d2[:, r.random(M) < 0.2] = np.inf
    elif case == "ip":          # negative scores (−x·y)
        d2 = -d2
    _, ids = jax.lax.top_k(-d2, 4 * k)
    cand = np.asarray(ids[:, :k]).copy()
    if case == "loose":         # t far past the k-th value
        cand[:, -1] = np.asarray(ids)[:, -1]
    elif case == "short":       # fewer than k real candidates: t = +inf
        cand[0, 0] = -1
    elif case == "duplicate":   # a candidate twice
        cand[:, 1] = cand[:, 0]
    return d2, cand


@pytest.mark.parametrize("case,B,nb,exact", [
    ("random", 128, 16, True),
    ("random", 256, 16, True),
    ("prefix_pads", 128, 16, True),
    ("ragged", 128, 16, True),
    ("ip", 128, 16, True),
    ("duplicate", 128, 16, True),
    ("ties", 128, 16, False),     # > nb marked blocks
    ("loose", 128, 16, False),
    ("short", 128, 16, False),
    ("random", 128, 2, False),
])
def test_fixup_select_matches_flat_top_k(case, B, nb, exact):
    """The filter's answer, where it claims exactness, and the blocked
    fallback's always, equal the flat top_k over the tile bit for bit,
    ties to the lowest column included."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import blocked_top_k, fixup_select

    k = 8
    d2, cand = _tile_case(case, k=k)
    F, M = d2.shape
    nt, ni = jax.lax.top_k(-jnp.asarray(d2), k)
    want_v, want_i = np.asarray(-nt), np.asarray(ni)
    d2b = jnp.asarray(d2).reshape(F, M // B, B).transpose(1, 0, 2)
    v, i, ok = jax.jit(fixup_select, static_argnums=(2, 3))(
        d2b, jnp.asarray(cand), k, nb)
    assert bool(ok) == exact
    if exact:
        assert np.array_equal(np.asarray(v).view(np.int32),
                              want_v.view(np.int32))
        assert np.array_equal(np.asarray(i), want_i)
    fv, fi = jax.jit(blocked_top_k, static_argnums=1)(d2b, k)
    assert np.array_equal(np.asarray(fv).view(np.int32),
                          want_v.view(np.int32))
    assert np.array_equal(np.asarray(fi), want_i)


def test_fixup_filter_geometry():
    """The filter engages by shape alone: the exact cells' per-chip
    tiles take it, tiles it cannot shrink keep the flat top_k."""
    from raft_tpu.distance.knn_fused import (_FIXUP_BLOCK,
                                             fixup_filter_geometry)

    assert fixup_filter_geometry(8388608, 100) == (_FIXUP_BLOCK, 256)
    assert fixup_filter_geometry(1048576, 64) == (_FIXUP_BLOCK, 128)
    assert fixup_filter_geometry(128 * _FIXUP_BLOCK, 64) is None
    assert fixup_filter_geometry(1048576 + 1, 64) is None
    assert fixup_filter_geometry(1048576, _FIXUP_BLOCK + 1) is None


@pytest.fixture
def fixup_filter(monkeypatch):
    """Filter blocks of 128 columns, so that tiles of a few thousand
    rows take the threshold filter; ``force_fallback()`` makes every
    select report inexact, so the fixup takes its exact fallback. The
    core's compiled programs are dropped around each change."""
    import jax.numpy as jnp

    from raft_tpu.distance import knn_fused as kf
    from raft_tpu.observability.metrics import (MetricsRegistry,
                                                set_registry)
    from raft_tpu.observability import quality

    class Paths:
        def force_fallback(self):
            select = kf.fixup_select
            monkeypatch.setattr(
                kf, "fixup_select",
                lambda *a: (*select(*a)[:2], jnp.bool_(False)))
            kf._knn_fused_core.clear_cache()

        def counters(self):
            from raft_tpu.observability import get_registry

            quality.drain()
            reg, site = get_registry(), {"site": "distance.knn_fused"}
            return (reg.counter(quality.CERT_FIXUPS, site).value,
                    reg.counter(quality.FIXUP_OVERFLOW, site).value)

    monkeypatch.setattr(kf, "_FIXUP_BLOCK", 128)
    prev = set_registry(MetricsRegistry())
    quality.clear()
    kf._knn_fused_core.clear_cache()
    yield Paths()
    kf._knn_fused_core.clear_cache()
    quality.clear()
    set_registry(prev)


def _fixup_data(data, metric="l2", m=3000, d=32, Q=32):
    """(x, y, rows_valid or None) whose certificate fails for some
    queries while their candidates stay near the answer: overlapping
    clusters (tighter for ip, whose scores spread less), optionally
    with exact duplicate rows."""
    r = np.random.default_rng(11)
    noise = 3e-3 if metric == "ip" else 3e-2
    base = r.normal(size=(40, d)).astype(np.float32)
    y = base[r.integers(0, 40, m)] + noise * r.normal(
        size=(m, d)).astype(np.float32)
    x = base[r.integers(0, 40, Q)] + noise * r.normal(
        size=(Q, d)).astype(np.float32)
    if data == "ties":
        # every row of the first third twice more, in other blocks, so
        # equal distances straddle the k-th place
        t = m // 3
        y[t:2 * t] = y[:t]
        y[2 * t:3 * t] = y[:t]
    elif data == "overflow":
        # one row 40 times, spread over more blocks than the filter
        # keeps (nb = 16 at k = 8); a query at it ties them all at t
        y[::m // 40][:40] = y[7]
        x[0] = y[7]
    rv = None
    if data == "ragged":
        rv = r.random(m) > 0.15
    return x, y, rv


@pytest.mark.parametrize("metric,lite,data", [
    ("l2", False, "prefix"),
    ("ip", False, "prefix"),
    ("l2", True, "prefix"),
    ("ip", True, "prefix"),
    ("l2", False, "ragged"),
    ("l2", False, "ties"),
    ("l2", False, "overflow"),
])
def test_fixup_filter_matches_flat_select(fixup_filter, metric, lite,
                                          data):
    """Through the whole certified pipeline, on data whose certificate
    fails: the fixup's filtered select returns the same (vals, ids),
    bit for bit, as its exact fallback (the flat top_k over the same
    tile, test above), in rescore and lite modes, for l2 and ip, with
    prefix pads (m=3000 of M=3072 prepared rows) and a ragged mask, on
    exact duplicates tying at the threshold, and where a row has more
    marked blocks than the filter keeps (it overflows into the
    fallback, counted once)."""
    from raft_tpu.distance.knn_fused import prepare_knn_index

    k = 8
    x, y, rv = _fixup_data(data, metric)
    idx = prepare_knn_index(y, passes=3, metric=metric, T=512, Qb=16,
                            g=8, store_yp=not lite, rows_valid=rv)
    assert idx.yyh_k.shape[1] > 2048        # the filter engages
    v1, i1 = knn_fused(x, idx, k)
    fixups, over = fixup_filter.counters()
    assert fixups > 0
    assert over == (1 if data == "overflow" else 0)
    fixup_filter.force_fallback()
    v2, i2 = knn_fused(x, idx, k)
    assert fixup_filter.counters()[1] > over
    assert np.array_equal(np.asarray(v1).view(np.int32),
                          np.asarray(v2).view(np.int32))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    if not lite and metric == "l2" and rv is None:
        ref_vals, _, tol = _oracle(x, y, k)
        np.testing.assert_allclose(np.asarray(v1), ref_vals, atol=tol)
