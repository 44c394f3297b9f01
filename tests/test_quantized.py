"""Quantized-index streaming (int8 slab + certified f32 rescore).

Pins the ISSUE-9 contracts:

- the per-group quantization bound Eq ENVELOPES the worst-case int8
  round-trip error, attacked with adversarial values at the scale
  boundaries (property test);
- int8-streamed + f32-rescored search returns id sets identical to the
  f32 oracle on brute (db/dbuf × passes × metric), sharded p ∈ {2, 4}
  (both merges), and the IVF degenerate-exact point;
- the envelope resolution (query-order/int8 requests, lite-index
  rejection), the dtype-aware footprint/traffic models, the schema-4
  tune-table loading (schema-3 backward compat + wrong-dtype row
  rejection), the serving engine's db_dtype passthrough, and the
  bench_report quantized gate.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.distance import knn_fused as kf
from raft_tpu.distance.knn_fused import (KnnIndex, knn_fused,
                                         prepare_knn_index,
                                         q8_eq_bound, quantize_rows_q8)

rng = np.random.default_rng(77)


def _id_sets_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return all(set(r1.tolist()) == set(r2.tolist())
               for r1, r2 in zip(a, b))


# ------------------------------------------------------------------
# property test: Eq envelopes the worst-case round-trip error
# ------------------------------------------------------------------
def _roundtrip_err(z, gid, n_groups):
    y_q, scales = quantize_rows_q8(jnp.asarray(z),
                                   jnp.asarray(gid, jnp.int32),
                                   n_groups)
    deq = (np.asarray(y_q, np.float32)
           * np.asarray(scales)[np.asarray(gid)][:, None])
    return (np.linalg.norm(z - deq, axis=1), np.asarray(scales),
            np.asarray(q8_eq_bound(scales, z.shape[1])))


@pytest.mark.parametrize("case", ["boundary", "halfstep", "random",
                                  "mixed_magnitude", "tiny", "negative"])
def test_eq_bound_envelopes_worst_case(case):
    """Adversarial inputs at the quantization grid's worst points: the
    row L2 round-trip error must stay under the recorded per-group Eq
    for EVERY row — the certificate's soundness rides on this."""
    d, rows_per_group, G = 48, 16, 4
    M = rows_per_group * G
    gid = np.arange(M) // rows_per_group
    if case == "boundary":
        # every element exactly at ±group max: the f32 divide can land
        # epsilon past the last code level (the clip-edge case)
        base = rng.uniform(0.5, 100.0, G).astype(np.float32)
        z = np.sign(rng.normal(size=(M, d))).astype(np.float32) \
            * base[gid][:, None]
    elif case == "halfstep":
        # magnitudes at (i + 0.5)·scale — the maximal rounding error
        # everywhere at once
        base = rng.uniform(1.0, 10.0, G).astype(np.float32)
        steps = rng.integers(0, 127, (M, d)).astype(np.float32) + 0.5
        z = steps * (base[gid][:, None] / 127.0)
        # one boundary element per row pins the group scale
        z[:, 0] = base[gid]
    elif case == "random":
        z = rng.normal(size=(M, d)).astype(np.float32) * 10.0
    elif case == "mixed_magnitude":
        # 6-decade magnitude spread WITHIN a group: worst relative case
        z = rng.normal(size=(M, d)).astype(np.float32)
        z *= 10.0 ** rng.integers(-3, 3, (M, 1)).astype(np.float32)
    elif case == "tiny":
        z = rng.normal(size=(M, d)).astype(np.float32) * 1e-30
    else:
        z = -np.abs(rng.normal(size=(M, d))).astype(np.float32) * 5.0
    err, scales, eq = _roundtrip_err(z, gid, G)
    assert np.all(err <= eq[gid] + 1e-30), (
        f"{case}: round-trip error {err.max()} exceeds Eq "
        f"{eq[gid][np.argmax(err - eq[gid])]}")


def test_eq_bound_zero_and_empty_groups():
    d, G = 16, 3
    z = np.zeros((24, d), np.float32)
    z[:8] = rng.normal(size=(8, d))          # group 0 real, 1-2 zero
    gid = np.arange(24) // 8
    err, scales, eq = _roundtrip_err(z, gid, G)
    assert np.all(err <= eq[gid])
    assert np.all(scales[1:] == 1.0)          # empty → inert scale


def test_quantize_respects_valid_mask():
    """Garbage rows masked invalid must not inflate the group scale."""
    d = 16
    z = np.ones((8, d), np.float32)
    z[7] = 1e6                                # garbage pad row
    valid = np.ones(8, bool)
    valid[7] = False
    _, scales = quantize_rows_q8(jnp.asarray(z),
                                 jnp.zeros(8, jnp.int32), 1,
                                 valid=jnp.asarray(valid))
    assert float(scales[0]) == pytest.approx(1.0 / 127.0)


# ------------------------------------------------------------------
# brute-force id parity vs the f32 oracle
# ------------------------------------------------------------------
@pytest.mark.parametrize("passes", [1, 3])
@pytest.mark.parametrize("order", ["db", "dbuf"])
def test_brute_parity_int8_vs_f32(passes, order):
    m, d, nq, k = 4096, 64, 64, 8
    Y = rng.normal(size=(m, d)).astype(np.float32)
    X = rng.normal(size=(nq, d)).astype(np.float32)
    vf, idf = knn_fused(X, Y, k, passes=passes, T=256, Qb=32, g=4,
                        grid_order=order)
    idx8 = prepare_knn_index(Y, passes=passes, T=256, Qb=32, g=4,
                             grid_order=order, db_dtype="int8")
    assert idx8.db_dtype == "int8"
    assert idx8.y_hi is None and idx8.y_q.dtype == jnp.int8
    v8, id8 = knn_fused(X, idx8, k)
    assert _id_sets_equal(idf, id8)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(v8),
                               rtol=1e-5, atol=1e-5)


def test_brute_parity_clustered_offset_data():
    """Clustered, norm-offset data — the regime that historically broke
    loose certificate margins; ids must still match the oracle exactly
    (failures route through the exact fixup, never a wrong answer)."""
    m, d, nq, k = 4096, 32, 48, 10
    centers = rng.normal(size=(8, d)).astype(np.float32) * 5.0 + 20.0
    Y = (centers[rng.integers(0, 8, m)]
         + rng.normal(size=(m, d)).astype(np.float32) * 0.05)
    X = (centers[rng.integers(0, 8, nq)]
         + rng.normal(size=(nq, d)).astype(np.float32) * 0.05)
    vf, idf = knn_fused(X, Y, k, passes=3, T=256, Qb=32, g=2,
                        grid_order="db")
    v8, id8 = knn_fused(X, Y, k, passes=3, T=256, Qb=32, g=2,
                        grid_order="db", db_dtype="int8")
    assert _id_sets_equal(idf, id8)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(v8),
                               rtol=1e-5, atol=1e-5)


def test_int8_fixup_scores_the_f32_rows(monkeypatch):
    """The int8 index's stored norms are the DEQUANTIZED rows' (ŷ), not
    the f32 rows' its rescore and fixup score: on data where they
    differ widely (one row per group 60× the rest, so the others
    quantize coarsely), the certificate fails, the fixup runs — through
    the threshold filter (blocks of 128 columns) — and its answers are
    the f32 oracle's: norms taken from yp, exact."""
    from raft_tpu.observability import get_registry, quality
    from raft_tpu.observability.metrics import (MetricsRegistry,
                                                set_registry)

    monkeypatch.setattr(kf, "_FIXUP_BLOCK", 128)
    kf._knn_fused_core.clear_cache()
    prev = set_registry(MetricsRegistry())
    quality.clear()
    try:
        m, d, nq, k = 3000, 32, 32, 8
        r = np.random.default_rng(5)
        Y = r.normal(size=(m, d)).astype(np.float32)
        Y[::1024] *= 60.0
        X = Y[r.integers(1, 1000, nq)] + 0.05 * r.normal(
            size=(nq, d)).astype(np.float32)
        idx8 = prepare_knn_index(Y, passes=3, T=256, Qb=16, g=4,
                                 grid_order="db", db_dtype="int8")
        yp = np.asarray(idx8.yp, np.float64)
        gap = np.abs(np.asarray(idx8.yy_raw[0]) - (yp ** 2).sum(1))[:m]
        assert np.median(gap) > 1e-2          # ŷ norms are not y's
        v8, id8 = knn_fused(X, idx8, k)
        quality.drain()
        assert get_registry().counter(
            quality.CERT_FIXUPS, {"site": "distance.knn_fused"}).value > 0
        d2 = ((X[:, None, :].astype(np.float64)
               - Y[None, :, :].astype(np.float64)) ** 2).sum(-1)
        ref = np.sort(d2, 1)[:, :k]
        tol = 8 * float((X ** 2).sum(1).max()
                        + (Y[1:1024] ** 2).sum(1).max()) * 2.0 ** -24
        np.testing.assert_allclose(np.asarray(v8), ref, atol=tol)
        np.testing.assert_allclose(
            np.take_along_axis(d2, np.asarray(id8), 1), ref, atol=tol)
    finally:
        kf._knn_fused_core.clear_cache()
        quality.clear()
        set_registry(prev)


def test_brute_parity_ip_metric():
    m, d, nq, k = 4096, 64, 32, 8
    Y = rng.normal(size=(m, d)).astype(np.float32)
    X = rng.normal(size=(nq, d)).astype(np.float32)
    vf, idf = knn_fused(X, Y, k, passes=1, T=256, Qb=32, g=4,
                        metric="ip", grid_order="db")
    v8, id8 = knn_fused(X, Y, k, passes=1, T=256, Qb=32, g=4,
                        metric="ip", grid_order="db", db_dtype="int8")
    assert _id_sets_equal(idf, id8)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(v8),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------
# envelope resolution
# ------------------------------------------------------------------
def test_int8_query_order_takes_db():
    Y = rng.normal(size=(1024, 32)).astype(np.float32)
    idx = prepare_knn_index(Y, passes=1, T=256, Qb=32, g=2,
                            grid_order="query", db_dtype="int8")
    assert idx.grid_order == "db"
    assert idx.db_dtype == "int8"


def test_int8_wide_features_downgrade_to_bf16():
    Y = rng.normal(size=(512, 600)).astype(np.float32)
    idx = prepare_knn_index(Y, passes=1, db_dtype="int8")
    assert idx.db_dtype == "bf16"             # d > 512 → d-chunked


def test_int8_lite_index_rejected():
    Y = rng.normal(size=(512, 32)).astype(np.float32)
    with pytest.raises(ValueError, match="store_yp"):
        prepare_knn_index(Y, db_dtype="int8", store_yp=False)


def test_int8_rescore_false_rejected():
    Y = rng.normal(size=(1024, 32)).astype(np.float32)
    idx = prepare_knn_index(Y, passes=1, T=256, Qb=32, g=2,
                            db_dtype="int8")
    with pytest.raises(ValueError, match="rescore"):
        knn_fused(np.ones((8, 32), np.float32), idx, 4, rescore=False)


def test_unknown_db_dtype_rejected():
    Y = rng.normal(size=(512, 32)).astype(np.float32)
    with pytest.raises(ValueError, match="db_dtype"):
        prepare_knn_index(Y, db_dtype="int4")


def test_eq_groups_recorded_on_index():
    Y = rng.normal(size=(2048, 32)).astype(np.float32)
    idx = prepare_knn_index(Y, passes=1, T=256, Qb=32, g=2,
                            db_dtype="int8")
    G = idx.y_q.shape[0] // (idx.g * idx.T)
    assert idx.eq_groups.shape == (G,)
    assert bool(jnp.all(idx.eq_groups > 0))
    assert idx.y_scale_k.shape == (G, 8, 128)


# ------------------------------------------------------------------
# footprint / traffic models
# ------------------------------------------------------------------
def test_footprint_int8_smaller_than_bf16():
    from raft_tpu.distance.knn_fused import footprint_for

    for order in ("db", "dbuf"):
        for passes in (1, 3):
            f8 = footprint_for(512, 64, 128, passes, g=4,
                               grid_order=order, db_dtype="int8")
            fb = footprint_for(512, 64, 128, passes, g=4,
                               grid_order=order, db_dtype="bf16")
            assert f8 < fb, (order, passes)


def test_quantized_bytes_ratio():
    from raft_tpu.observability.costmodel import (fused_traffic_model,
                                                  quantized_bytes_ratio)

    r1 = quantized_bytes_ratio(256, 100_000, 128, 64, 1024, 256, 8, 1)
    r3 = quantized_bytes_ratio(256, 100_000, 128, 64, 1024, 256, 8, 3)
    assert r1 == pytest.approx(0.5)
    assert r3 == pytest.approx(0.25)
    m8 = fused_traffic_model(256, 100_000, 128, 64, 1024, 256, 8, 1,
                             "db", "int8")
    assert m8["db_dtype"] == "int8" and m8["y_bytes_per_el"] == 1


def test_ivf_traffic_model_dtype_aware():
    from raft_tpu.observability.costmodel import ivf_traffic_model

    f32 = ivf_traffic_model(256, 20_000, 128, 10, 64, 8, 320, 20_480)
    q8 = ivf_traffic_model(256, 20_000, 128, 10, 64, 8, 320, 20_480,
                           db_dtype="int8")
    assert q8["fine_gather_bytes"] < f32["fine_gather_bytes"]
    assert 0.0 < q8["quantized_gather_ratio"] <= 0.55
    assert q8["rescore_bytes"] > 0 and f32["rescore_bytes"] == 0.0
    with pytest.raises(ValueError):
        ivf_traffic_model(1, 1, 1, 1, 1, 1, 1, 1, db_dtype="int4")


# ------------------------------------------------------------------
# sharded parity p ∈ {2, 4} × both merges
# ------------------------------------------------------------------
@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("merge", ["allgather", "tournament"])
def test_sharded_parity_int8(p, merge):
    from raft_tpu.distance.knn_sharded import (knn_fused_sharded,
                                               prepare_knn_index_sharded)
    from raft_tpu.parallel import make_mesh

    m, d, nq, k = 6000, 64, 48, 8
    Y = rng.normal(size=(m, d)).astype(np.float32)
    X = rng.normal(size=(nq, d)).astype(np.float32)
    vf, idf = knn_fused(X, Y, k, passes=3, T=256, Qb=32, g=2,
                        grid_order="db")
    mesh = make_mesh({"x": p}, devices=jax.devices()[:p])
    idx8 = prepare_knn_index_sharded(Y, mesh=mesh, passes=3, T=256,
                                     Qb=32, g=2, grid_order="db",
                                     db_dtype="int8")
    assert idx8.db_dtype == "int8"
    v8, id8 = knn_fused_sharded(X, idx8, k, mesh=mesh, merge=merge)
    assert _id_sets_equal(idf, id8)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(v8),
                               rtol=1e-5, atol=1e-5)


def test_query_sharded_int8_replicated_index():
    from raft_tpu.distance.knn_sharded import knn_fused_sharded
    from raft_tpu.parallel import make_mesh

    m, d, nq, k = 4096, 32, 32, 6
    Y = rng.normal(size=(m, d)).astype(np.float32)
    X = rng.normal(size=(nq, d)).astype(np.float32)
    vf, idf = knn_fused(X, Y, k, passes=1, T=256, Qb=32, g=2,
                        grid_order="db")
    idx8 = prepare_knn_index(Y, passes=1, T=256, Qb=32, g=2,
                             grid_order="db", db_dtype="int8")
    mesh = make_mesh({"x": 2}, devices=jax.devices()[:2])
    v8, id8 = knn_fused_sharded(X, idx8, k, mesh=mesh,
                                shard_mode="query")
    assert _id_sets_equal(idf, id8)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(v8),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------
# IVF int8
# ------------------------------------------------------------------
def _ivf_fixture(db_dtype):
    from raft_tpu.ann import build_ivf_flat

    m, d = 4000, 32
    Y = rng.normal(size=(m, d)).astype(np.float32)
    X = rng.normal(size=(24, d)).astype(np.float32)
    ix = build_ivf_flat(None, Y, n_lists=16, max_iter=4, seed=0,
                        db_dtype=db_dtype)
    return Y, X, ix


def test_ivf_int8_probe_parity():
    from raft_tpu.ann import search_ivf_flat

    Y, X, ix8 = _ivf_fixture("int8")
    from raft_tpu.ann import build_ivf_flat

    ixf = build_ivf_flat(None, Y, n_lists=16, max_iter=4, seed=0)
    vf, idf = search_ivf_flat(None, ixf, X, 8, n_probes=4)
    v8, id8 = search_ivf_flat(None, ix8, X, 8, n_probes=4)
    assert _id_sets_equal(idf, id8)
    np.testing.assert_allclose(np.sort(np.asarray(vf), axis=1),
                               np.sort(np.asarray(v8), axis=1),
                               rtol=1e-5, atol=1e-6)


def test_ivf_int8_degenerate_exact_vs_oracle():
    from raft_tpu.ann import search_ivf_flat

    Y, X, ix8 = _ivf_fixture("int8")
    v8, id8 = search_ivf_flat(None, ix8, X, 8, n_probes=16)
    vo, ido = knn_fused(X, Y, 8, passes=3, T=256, Qb=32, g=4)
    assert _id_sets_equal(ido, id8)


def test_ivf_int8_layout():
    _, _, ix8 = _ivf_fixture("int8")
    R = ix8.slab_rows
    assert ix8.db_dtype == "int8"
    assert ix8.slab_q.shape == ix8.slab.shape
    assert ix8.slab_q.dtype == jnp.int8
    assert ix8.row_scale.shape == (R,)
    assert ix8.eq_rows.shape == (R,)
    # pad rows quantize to 0 and keep 0 dequantized norms
    pads = np.asarray(ix8.ids) < 0
    assert np.all(np.asarray(ix8.yy_q)[pads] == 0.0)


def test_ivf_unknown_dtype_rejected():
    from raft_tpu.ann import build_ivf_flat

    with pytest.raises(ValueError, match="db_dtype"):
        build_ivf_flat(None, np.ones((64, 8), np.float32), n_lists=4,
                       db_dtype="bf16")


# ------------------------------------------------------------------
# tune-table loading (schema 4 + backward compat)
# ------------------------------------------------------------------
def _write_table(path, tbl):
    with open(path, "w") as f:
        json.dump(tbl, f)


def test_fused_config_dtype_keyed(tmp_path, monkeypatch):
    from raft_tpu.tune.fused import TUNE_SCHEMA_VERSION

    tbl = {
        "schema": TUNE_SCHEMA_VERSION,
        "shape": [256, 100_000, 128, 64],
        "rows": [],
        "best_by_passes_dtype": {
            "1:bf16": {"T": 1024, "Qb": 256, "g": 8, "passes": 1,
                       "grid_order": "db", "db_dtype": "bf16"},
            "1:int8": {"T": 2048, "Qb": 512, "g": 8, "passes": 1,
                       "grid_order": "db", "db_dtype": "int8"},
        },
    }
    path = tmp_path / "tune.json"
    _write_table(path, tbl)
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    monkeypatch.setattr(kf, "_TUNED", ...)
    cfg_b = kf.fused_config(1, "bf16")
    cfg_q = kf.fused_config(1, "int8")
    assert (cfg_b.T, cfg_b.grid_order) == (1024, "db")
    assert (cfg_q.T, cfg_q.Qb, cfg_q.grid_order) == (2048, 512, "db")
    monkeypatch.setattr(kf, "_TUNED", ...)


def test_fused_config_schema3_rows_are_bf16(tmp_path, monkeypatch):
    """A committed schema-3 table (no db_dtype anywhere) loads exactly
    as before, and the int8 lookup derives a database-major geometry
    from the bf16 winner instead of failing."""
    tbl = {
        "schema": 3,
        "shape": [256, 100_000, 128, 64],
        "rows": [],
        "best_by_passes": {
            "1": {"T": 1024, "Qb": 256, "g": 8, "passes": 1,
                  "grid_order": "query"},
        },
    }
    path = tmp_path / "tune3.json"
    _write_table(path, tbl)
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    monkeypatch.setattr(kf, "_TUNED", ...)
    cfg_b = kf.fused_config(1, "bf16")
    assert (cfg_b.T, cfg_b.grid_order) == (1024, "query")
    cfg_q = kf.fused_config(1, "int8")
    assert cfg_q.grid_order == "db"           # derived, never "query"
    assert cfg_q.T == 1024
    monkeypatch.setattr(kf, "_TUNED", ...)


def test_fused_config_rejects_unknown_dtype_rows(tmp_path, monkeypatch):
    from raft_tpu.observability import get_registry
    from raft_tpu.tune.fused import (TABLE_DEGRADED,
                                     _reset_degraded_warnings)

    tbl = {
        "schema": 4,
        "shape": [256, 100_000, 128, 64],
        "rows": [
            {"T": 1024, "Qb": 256, "g": 8, "passes": 1,
             "grid_order": "db", "db_dtype": "int4", "seconds": 0.5},
        ],
    }
    path = tmp_path / "tune_bad.json"
    _write_table(path, tbl)
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    monkeypatch.setattr(kf, "_TUNED", ...)
    _reset_degraded_warnings()
    reg = get_registry()

    def _count():
        return sum(m.value for m in reg.collect()
                   if m.name == TABLE_DEGRADED
                   and m.labels.get("table") == "fused"
                   and m.labels.get("reason") == "row_rejected")

    before = _count()
    cfg = kf.fused_config(1, "bf16")
    assert cfg == kf._BUILTIN_CONFIG          # nothing valid loaded
    assert _count() > before                  # skip reason was counted
    monkeypatch.setattr(kf, "_TUNED", ...)


def test_candidate_space_skips_int8_query_order():
    from raft_tpu.tune.fused import candidate_space

    kept, skipped = candidate_space(128)
    assert all(not (c.db_dtype == "int8" and c.grid_order == "query")
               for c in kept)
    reasons = {r.get("skipped") for r in skipped}
    assert "q8_envelope" in reasons


# ------------------------------------------------------------------
# serving passthrough + AOT entry
# ------------------------------------------------------------------
def test_serving_engine_int8_plane():
    from raft_tpu.serving import ServingEngine

    m, d, k = 2048, 32, 6
    Y = rng.normal(size=(m, d)).astype(np.float32)
    X = rng.normal(size=(5, d)).astype(np.float32)
    vo, io = knn_fused(X, Y, k, passes=3, T=256, Qb=32, g=2,
                       grid_order="db")
    eng = ServingEngine(Y, k=k, buckets=(8,), passes=3, T=256, Qb=32,
                        g=2, grid_order="db", db_dtype="int8")
    snap = eng._store.current()
    assert snap.index.db_dtype == "int8"
    eng.start()
    try:
        vals, ids = eng.submit(X).result(timeout=60)
        assert _id_sets_equal(io, ids)
        # background rebuild keeps the dtype through the swap
        eng.update_index(Y[: m // 2])
        eng._store.wait_for_builds(timeout=60)
        assert eng._store.current().index.db_dtype == "int8"
    finally:
        eng.stop()


def test_knn_query_aot_entry_int8(res):
    from raft_tpu.runtime.entry_points import knn_query

    m, d, nq, k = 2048, 32, 16, 6
    Y = rng.normal(size=(m, d)).astype(np.float32)
    X = rng.normal(size=(nq, d)).astype(np.float32)
    vo, io = knn_fused(X, Y, k, passes=1, T=256, Qb=32, g=2,
                       grid_order="db")
    idx8 = prepare_knn_index(Y, passes=1, T=256, Qb=32, g=2,
                             grid_order="db", db_dtype="int8")
    v8, id8 = knn_query(res, idx8, X, k)
    assert _id_sets_equal(io, id8)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(v8),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------
# bench_report quantized gate
# ------------------------------------------------------------------
def test_check_quantized_gate_matrix():
    import tools.bench_report as br

    ok_rec = {"quantized": {"ok": True, "quantized_y_ratio": 0.25}}
    bad_parity = {"quantized": {"ok": False,
                                "quantized_y_ratio": 0.25}}
    bad_ratio = {"quantized": {"ok": True, "quantized_y_ratio": 0.7}}
    no_block = {"metric": "x"}

    s, _ = br.check_quantized([("bench", ok_rec)])
    assert s == br.PASS
    s, msg = br.check_quantized([("bench", ok_rec),
                                 ("ann", bad_parity)])
    assert s == br.REGRESS and "id-parity" in msg
    s, msg = br.check_quantized([("multichip", bad_ratio)])
    assert s == br.REGRESS and "0.700" in msg
    s, _ = br.check_quantized([("bench", no_block), ("ann", None)])
    assert s == br.SKIP
    s, msg = br.check_quantized([("bench", no_block),
                                 ("ann", ok_rec)])
    assert s == br.PASS and "no block: bench" in msg
    # gather-ratio key (the ANN block) gates identically
    s, _ = br.check_quantized(
        [("ann", {"quantized": {"ok": True,
                                "quantized_gather_ratio": 0.3}})])
    assert s == br.PASS


def test_check_quantized_pq_tier_gate_matrix():
    """ISSUE 15 satellite: the quantized gate extended to the PQ tier
    — the modeled codes-stream ratio must clear the much tighter 0.10×
    ceiling AND the id-parity-after-rescore flag is AND-ed in."""
    import tools.bench_report as br

    ok_pq = {"pq": {"ok": True, "pq_bytes_ratio": 0.0625}}
    s, msg = br.check_quantized([("ann", ok_pq)])
    assert s == br.PASS and "pq=0.0625" in msg
    # parity-after-rescore failure regresses even at a great ratio
    s, msg = br.check_quantized(
        [("ann", {"pq": {"ok": False, "pq_bytes_ratio": 0.03}})])
    assert s == br.REGRESS and "id-parity-after-rescore" in msg
    # ratio over the PQ ceiling regresses (0.12 passes the int8 gate's
    # 0.55 but NOT the pq tier's 0.10)
    s, msg = br.check_quantized(
        [("ann", {"pq": {"ok": True, "pq_bytes_ratio": 0.12}})])
    assert s == br.REGRESS and "0.1200" in msg
    # a missing ratio in an ok block is a broken artifact, not a pass
    s, msg = br.check_quantized([("ann", {"pq": {"ok": True}})])
    assert s == br.REGRESS and "pq_bytes_ratio" in msg
    # pq and int8 blocks gate together on one record
    both = {"quantized": {"ok": True, "quantized_gather_ratio": 0.3},
            "pq": {"ok": True, "pq_bytes_ratio": 0.05}}
    s, msg = br.check_quantized([("ann", both)])
    assert s == br.PASS and "pq=0.0500" in msg


def test_committed_artifacts_carry_quantized_blocks():
    """The committed MULTICHIP/ANN artifacts must pass the gate they
    exist to feed."""
    import os

    import tools.bench_report as br

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    recs = []
    m = br.load_multichip(os.path.join(root, "MULTICHIP_SHARDED.json"))
    a = br.load_ann(os.path.join(root, "BENCH_ANN.json"))
    recs = [("multichip", m), ("ann", a)]
    s, msg = br.check_quantized(recs)
    assert s == br.PASS, msg
