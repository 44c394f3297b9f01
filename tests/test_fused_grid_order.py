"""Database-major fused-kernel tests (ISSUE 3 tentpole).

Interpret-mode parity of the grid-order variants — "db" (super-blocked,
y group resident) and "dbuf" (explicit double-buffered y-tile DMA) —
against the query-major packed kernel and an XLA/numpy reference,
across a (T, Qb, grid_order) matrix, plus the revisited-slot (a3 /
certificate-input) semantics under the inverted iteration order, the
end-to-end certified pipeline on both new orders, and the VMEM
footprint + HBM traffic models that gate/justify them.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.ops.fused_l2_topk_pallas import (
    _LANES, _PACK_MASK, _PACK_PAD, VMEM_BUDGET, fused_l2_group_topk_packed,
    fused_l2_group_topk_packed_db, fused_l2_group_topk_packed_dbuf,
    split_hi_lo, vmem_footprint)

rng = np.random.default_rng(11)


def _operands(Q, m, d, T, tpg, metric_scale=1.0):
    """Packed-kernel operands with db-compatible padding (whole tpg·T
    groups), built exactly the way _prepare_ops does."""
    x = metric_scale * rng.normal(size=(Q, d)).astype(np.float32)
    y = metric_scale * rng.normal(size=(m, d)).astype(np.float32)
    M = -(-m // (tpg * T)) * (tpg * T)
    yp = np.concatenate([y, np.zeros((M - m, d), np.float32)])
    y_hi, y_lo = split_hi_lo(jnp.asarray(yp))
    base = 0.5 * jnp.sum(jnp.asarray(yp) ** 2, axis=1)[None, :]
    valid = (jnp.arange(M) < m)[None, :]
    yyh = jnp.broadcast_to(jnp.where(valid, base, _PACK_PAD), (8, M))
    m_real = jnp.full((1,), m, jnp.int32)
    xj = jnp.asarray(x)
    xxh = 0.5 * jnp.sum(xj * xj, axis=1, keepdims=True)
    return x, yp, xj, y_hi, y_lo, yyh, m_real, xxh


@pytest.mark.parametrize("T,Qb,order", [
    (256, 16, "db"), (256, 16, "dbuf"),
    (512, 16, "db"), (512, 16, "dbuf"),
    (512, 32, "db"), (512, 32, "dbuf"),
    (256, 8, "db"), (256, 8, "dbuf"),       # minimal query block
])
@pytest.mark.parametrize("passes", [1, 3])
def test_db_variants_bitexact_vs_query_major(T, Qb, order, passes):
    """The grid re-order must not change a single bit: same packed
    values, same embedded codes, same a3 certificate inputs — the fold
    is associative-free (pure min/max network over the same partition),
    so any divergence is an indexing bug."""
    Q, m, tpg = 32, 3 * T * 2 - 57, 2          # 2 groups + ragged tail
    _, _, xj, y_hi, y_lo, yyh, m_real, xxh = _operands(Q, m, 64, T, tpg)
    pair = passes == 1 and (T // _LANES) % 2 == 0
    ref = fused_l2_group_topk_packed(
        xj, y_hi, y_lo, yyh, m_real, T=T, Qb=Qb, passes=passes,
        tpg=tpg, pair=pair, stream=True, xxh=xxh)
    kern = (fused_l2_group_topk_packed_db if order == "db"
            else fused_l2_group_topk_packed_dbuf)
    got = kern(xj, y_hi, y_lo, yyh, m_real, T=T, Qb=Qb, passes=passes,
               tpg=tpg, pair=pair, xxh=xxh)
    for name, a, b in zip(("a1p", "a2p", "a3p"), ref, got):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{order}/{name}")


def test_db_revisited_slot_semantics_vs_numpy():
    """The a3 output (the certificate's revisited-slot accumulator —
    the group 3rd-min every non-candidate is bounded by) must equal the
    true per-(lane, group) 3rd-smallest under the NEW iteration order,
    checked against numpy on the same partition. This is the db-order
    rendering of the m2min-revisit correctness requirement: the
    query-major kernel accumulates it across revisited output blocks;
    the db kernels fold whole groups in-cell — same math must fall
    out."""
    Q, m, d, T, Qb, tpg = 16, 4 * 512 - 91, 32, 512, 16, 2
    x, yp, xj, y_hi, y_lo, yyh, m_real, xxh = _operands(Q, m, d, T, tpg)
    M = yp.shape[0]
    n_tiles = M // T
    G = -(-n_tiles // tpg)

    for kern in (fused_l2_group_topk_packed_db,
                 fused_l2_group_topk_packed_dbuf):
        a1p, a2p, a3p = kern(xj, y_hi, y_lo, yyh, m_real, T=T, Qb=Qb,
                             passes=3, tpg=tpg, xxh=xxh)
        # unpack to half-scores (strip embedded codes), then d2 = 2·v
        a3 = np.asarray(jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(a3p, jnp.int32)
            & ~jnp.int32(_PACK_MASK), jnp.float32))
        d2 = ((x.astype(np.float64) ** 2).sum(1)[:, None]
              + (yp.astype(np.float64) ** 2).sum(1)[None, :]
              - 2.0 * x.astype(np.float64) @ yp.astype(np.float64).T)
        d2[:, m:] = np.inf
        from raft_tpu.distance.knn_fused import _err_bound_coeff
        tol = (_err_bound_coeff(d) * float(
            np.linalg.norm(x, axis=1).max()
            * np.linalg.norm(yp, axis=1).max())
            + float(np.abs(d2[np.isfinite(d2)]).max()) * 2 ** -13)
        for g_i in range(G):
            cols = np.arange(g_i * tpg * T, min((g_i + 1) * tpg * T, M))
            for lane in range(0, _LANES, 41):
                lane_cols = cols[cols % _LANES == lane]
                sub = np.sort(d2[:, lane_cols], axis=1)
                want3 = sub[:, 2]
                got3 = 2.0 * a3[:, g_i * _LANES + lane]
                fin = np.isfinite(want3)
                np.testing.assert_allclose(got3[fin], want3[fin],
                                           atol=tol)


def _oracle(x, y, k):
    xx = (x.astype(np.float64) ** 2).sum(1)
    yy = (y.astype(np.float64) ** 2).sum(1)
    d2 = np.maximum(xx[:, None] + yy[None, :] - 2.0 * (
        x.astype(np.float64) @ y.astype(np.float64).T), 0)
    ids = np.argsort(d2, axis=1, kind="stable")[:, :k]
    scale = float(np.max(xx[:, None] + yy[None, :]))
    return (np.take_along_axis(d2, ids, axis=1), ids,
            8 * scale * 2.0 ** -24)


@pytest.mark.parametrize("order", ["db", "dbuf"])
@pytest.mark.parametrize("Q,m,d,k", [
    (64, 5000, 32, 8),
    (100, 3000, 130, 16),     # d not a lane multiple, Q not block mult
    (8, 2048, 128, 64),
])
def test_knn_fused_db_orders_exact(order, Q, m, d, k):
    from raft_tpu.distance.knn_fused import knn_fused

    x = rng.normal(size=(Q, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=k, passes=3, T=512, Qb=64, g=4,
                          grid_order=order)
    ref_vals, ref_ids, tol = _oracle(x, y, k)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    assert np.array_equal(np.sort(np.asarray(ids), 1),
                          np.sort(ref_ids, 1))


@pytest.mark.parametrize("order", ["db", "dbuf"])
def test_knn_fused_db_clustered_forces_fixup(order):
    # near-duplicates share buckets → certificate failures → the fixup
    # cascade must still deliver exactness on the new grid orders
    from raft_tpu.distance.knn_fused import knn_fused

    Q, m, d, k = 256, 4096, 64, 32
    base = rng.normal(size=(50, d)).astype(np.float32)
    y = base[rng.integers(0, 50, m)] + 1e-3 * rng.normal(
        size=(m, d)).astype(np.float32)
    x = base[rng.integers(0, 50, Q)] + 1e-3 * rng.normal(
        size=(Q, d)).astype(np.float32)
    vals, _ = knn_fused(x, y, k=k, passes=3, T=512, Qb=64, g=4,
                        grid_order=order)
    ref_vals, _, tol = _oracle(x, y, k)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)


def test_prepared_index_freezes_grid_order():
    from raft_tpu.distance.knn_fused import knn_fused, prepare_knn_index

    y = rng.normal(size=(3000, 40)).astype(np.float32)
    x = rng.normal(size=(48, 40)).astype(np.float32)
    ref_vals, ref_ids, tol = _oracle(x, y, 8)
    for order in ("db", "dbuf"):
        idx = prepare_knn_index(y, passes=1, T=512, Qb=64, g=4,
                                grid_order=order)
        assert idx.grid_order == order
        # db orders pad the index rows to WHOLE groups
        assert idx.y_hi.shape[0] % (idx.g * idx.T) == 0
        vals, ids = knn_fused(x, idx, k=8, certify="f32")
        np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
        assert np.array_equal(np.sort(np.asarray(ids), 1),
                              np.sort(ref_ids, 1))


def test_grid_order_envelope_downgrades():
    from raft_tpu.distance.knn_fused import (knn_fused,
                                             prepare_knn_index,
                                             resolve_grid_order)

    # unpacked config (code space exceeded) downgrades to query-major
    assert resolve_grid_order("db", 64, packed=False) == "query"
    # wide features route to the d-chunked kernel → query-major
    assert resolve_grid_order("dbuf", 700, packed=True) == "query"
    assert resolve_grid_order("db", 64, packed=True) == "db"
    with pytest.raises(ValueError, match="grid_order"):
        resolve_grid_order("bogus", 64, packed=True)
    with pytest.raises(ValueError, match="grid_order"):
        prepare_knn_index(rng.normal(size=(512, 8)).astype(np.float32),
                          grid_order="bogus")

    # end-to-end: the downgraded call still returns exact results
    x = rng.normal(size=(16, 16)).astype(np.float32)
    y = rng.normal(size=(9000, 16)).astype(np.float32)
    vals, ids = knn_fused(x, y, k=8, passes=3, T=512, Qb=16, g=4096,
                          grid_order="db")     # g=4096 → unpacked
    ref_vals, ref_ids, tol = _oracle(x, y, 8)
    np.testing.assert_allclose(np.asarray(vals), ref_vals, atol=tol)
    assert np.array_equal(np.sort(np.asarray(ids), 1),
                          np.sort(ref_ids, 1))


def test_db_footprint_models():
    """The VMEM models that gate the sweep: the db super-block must be
    priced (large g·T blows the budget), dbuf must price the whole
    query batch's fold state instead of the y block."""
    from raft_tpu.distance.knn_fused import footprint_for

    # db: y super-block dominates — g=32, T=4096 is far over budget
    assert vmem_footprint(4096, 256, 128, passes=1, kernel="stream_db",
                          g=32) > VMEM_BUDGET
    # ...while a small group fits
    assert vmem_footprint(1024, 256, 128, passes=1, kernel="stream_db",
                          g=8) <= VMEM_BUDGET
    # dbuf: only 2 tiles resident — g no longer moves the y term
    small_g = vmem_footprint(1024, 2048, 128, passes=1,
                             kernel="stream_dbuf", g=4)
    big_g = vmem_footprint(1024, 2048, 128, passes=1,
                           kernel="stream_dbuf", g=32)
    assert big_g - small_g == 8 * (32 - 4) * 1024 * 4 * 2  # yyh only
    # footprint_for prices dbuf at the _Q_CHUNK worst case regardless
    # of the Qb argument
    assert footprint_for(1024, 8, 128, 1, 4, "dbuf") == \
        footprint_for(1024, 1024, 128, 1, 4, "dbuf")


def test_traffic_model_stream_once():
    """The acceptance-criterion numbers: on the driver shape the
    database-major orders reduce modeled y HBM traffic to ≤ 2× the
    single-stream M·d bytes (factor 1.0 of the bf16 stream), where
    query-major pays nq streams."""
    from raft_tpu.observability.costmodel import fused_traffic_model

    Q, m, d, k = 2048, 1_000_000, 128, 64
    q_model = fused_traffic_model(Q, m, d, k, 2048, 256, 16, 1, "query")
    assert q_model["y_stream_factor"] == 8.0          # nq = 2048/256
    for order in ("db", "dbuf"):
        model = fused_traffic_model(Q, m, d, k, 2048, 256, 16, 1, order)
        assert model["y_stream_factor"] == 1.0
        # ≤ 2× single-stream in RAW M·d bytes (bf16 stream = 2×)
        assert model["y_bytes"] <= 2.0 * m * 128 * 1.0 * 2
        # the saved traffic dwarfs the added x/out revisit traffic
        assert model["total_bytes"] < 0.5 * q_model["total_bytes"]
    # query chunking re-streams y once per chunk in db orders
    two_chunks = fused_traffic_model(4096, m, d, k, 2048, 256, 16, 1,
                                     "db")
    assert two_chunks["y_stream_factor"] == 2.0


def test_fixture_run_merges_model():
    """benchmark.Fixture.run(model=...) lands the analytic prediction
    next to the measurement under model_* keys — the BENCH-artifact
    contract the tuner relies on."""
    from raft_tpu.benchmark import Fixture

    fx = Fixture(reps=1)
    r = fx.run(jax.jit(lambda v: v * 2.0), jnp.arange(8.0),
               name="model_merge_probe",
               model={"total_bytes": 64.0, "y_stream_factor": 1.0,
                      "model_pretagged": 3.0})
    assert r["model_total_bytes"] == 64.0
    assert r["model_y_stream_factor"] == 1.0
    assert r["model_pretagged"] == 3.0            # no double prefix
