"""IVF-Flat (raft_tpu.ann) — the padded ragged slab layout, the
recall/probe trade vs the brute-force oracle, the degenerate-exact
invariant (n_probes = n_lists ≡ exact search), the ragged
rows_valid path through _prepare_ops/_knn_fused_core, and the
list-sharded search at shard ∈ {1, 2, 4} (ISSUE 8 acceptance)."""

import jax
import numpy as np
import pytest

from raft_tpu.ann import (IvfFlatIndex, build_ivf_flat, search_ivf_flat,
                          shard_ivf_lists)
from raft_tpu.distance.fused_l2nn import knn
from raft_tpu.parallel import make_mesh
from raft_tpu.random import make_blobs

rng = np.random.default_rng(17)


@pytest.fixture(scope="module")
def fixture():
    """One shared (X, queries, oracle, index) — building per-test would
    re-run k-means a dozen times for identical data."""
    from raft_tpu.core import DeviceResources

    res = DeviceResources(seed=0)
    X, _ = make_blobs(res, 23, 6000, 24, n_clusters=24, cluster_std=1.0,
                      proportions=rng.uniform(0.5, 2.0, 24))
    X = np.asarray(X, np.float32)
    Q = X[rng.choice(6000, 128, replace=False)] \
        + rng.normal(0, 0.05, (128, 24)).astype(np.float32)
    ov, oi = knn(res, X, Q, 10)
    idx = build_ivf_flat(res, X, n_lists=24, max_iter=6, seed=1)
    return res, X, Q, np.asarray(oi), idx


def _id_sets(ids):
    return [set(r.tolist()) for r in np.asarray(ids)]


# ------------------------------------------------------------ layout
def test_layout_invariants(fixture):
    res, X, _, _, idx = fixture
    offsets = np.asarray(idx.offsets)
    sizes = np.asarray(idx.sizes)
    padded = np.asarray(idx.padded_sizes)
    ids = np.asarray(idx.ids)
    slab = np.asarray(idx.slab)
    q = idx.row_quantum
    # ragged offsets: consecutive, sized by the quantum-padded lists
    assert offsets[0] == 0
    assert (np.diff(offsets) == padded).all()
    assert offsets[-1] == idx.slab_rows
    assert ((padded % q == 0) | (padded == 0)).all()
    assert (padded >= sizes).all() and (padded < sizes + q).all()
    assert sizes.sum() == idx.n_rows
    # ids partition 0..m-1 exactly once; -1 exactly on pad rows
    real = ids[ids >= 0]
    assert len(real) == idx.n_rows
    assert (np.sort(real) == np.arange(idx.n_rows)).all()
    # slab rows carry the original vectors; pad rows are zero
    assert np.array_equal(slab[ids >= 0], X[real])
    assert not slab[ids < 0].any()
    # every real slab row sits inside its list's REAL span
    for l in range(idx.n_lists):
        span = ids[offsets[l]:offsets[l + 1]]
        assert (span[:sizes[l]] >= 0).all()
        assert (span[sizes[l]:] == -1).all()


def test_ragged_list_lengths(fixture):
    _, _, _, _, idx = fixture
    sizes = np.asarray(idx.sizes)
    # the imbalanced-proportions oracle must actually produce ragged
    # lists (the whole point of the padded ragged layout)
    assert sizes.max() > sizes.min()
    assert np.unique(np.asarray(idx.padded_sizes)).size > 1


# ----------------------------------------------------------- search
def test_recall_floor_and_monotonicity(fixture):
    res, _, Q, oi, idx = fixture
    oracle = _id_sets(oi)
    recalls = []
    for P in (1, 2, 4, 8):
        _, i = search_ivf_flat(res, idx, Q, 10, n_probes=P)
        r = np.mean([len(oracle[q] & s) / 10
                     for q, s in enumerate(_id_sets(i))])
        recalls.append(r)
    # ISSUE-8 acceptance: recall@10 >= 0.95 at some swept n_probes
    assert max(recalls) >= 0.95
    # more probes can only add candidates — recall is non-decreasing
    assert all(b >= a - 1e-9 for a, b in zip(recalls, recalls[1:]))


def test_values_match_oracle_on_hits(fixture):
    res, _, Q, _, idx = fixture
    from raft_tpu.core import DeviceResources

    ov, oi = knn(DeviceResources(), np.asarray(idx.slab)[
        np.asarray(idx.ids) >= 0], Q, 10)
    v, i = search_ivf_flat(res, idx, Q, 10, n_probes=8)
    v, i = np.asarray(v), np.asarray(i)
    # where the approximate search found the true neighbor, its d2 is
    # BITWISE the oracle's (same expanded-L2 f32 HIGHEST score)
    ov = np.asarray(ov)
    for q in range(0, 128, 16):
        both = set(i[q]) & set(np.asarray(oi)[q])
        for gid in both:
            a = v[q][list(i[q]).index(gid)]
            b = ov[q][list(np.asarray(oi)[q]).index(gid)]
            assert a == b


def test_degenerate_exact_invariant(fixture):
    res, _, Q, oi, idx = fixture
    from raft_tpu.observability import get_flight_recorder

    rec = get_flight_recorder()
    before = sum(1 for e in rec.events()
                 if e.get("name") == "ivf_exact_degrade")
    v, i = search_ivf_flat(res, idx, Q, 10, n_probes=idx.n_lists)
    # ISSUE-8 acceptance: n_probes = n_lists exactly matches the
    # oracle's id sets
    assert _id_sets(i) == _id_sets(oi)
    if rec.enabled:
        after = sum(1 for e in rec.events()
                    if e.get("name") == "ivf_exact_degrade")
        assert after == before + 1            # the logged reason


def test_k_beyond_probe_capacity_degrades_exact(fixture):
    res, X, Q, _, _ = fixture
    from raft_tpu.core import DeviceResources

    res2 = DeviceResources()
    # tiny quantum → tiny windows: k larger than P·W must route exact
    idx = build_ivf_flat(res2, X[:512], n_lists=64, max_iter=3, seed=0)
    W = idx.probe_window
    k = W + 1                                 # > 1 probe's capacity
    v, i = search_ivf_flat(res2, idx, Q[:8], k, n_probes=1)
    ov, oi = knn(res2, X[:512], Q[:8], k)
    assert _id_sets(i) == _id_sets(oi)


def test_single_list_edge(fixture):
    res, X, Q, _, _ = fixture
    idx = build_ivf_flat(res, X[:256], n_lists=1, max_iter=2, seed=0)
    assert idx.n_lists == 1
    v, i = search_ivf_flat(res, idx, Q[:16], 5, n_probes=1)
    ov, oi = knn(res, X[:256], Q[:16], 5)
    assert _id_sets(i) == _id_sets(oi)


def test_empty_lists_are_inert(fixture):
    res, _, _, _, _ = fixture
    # 4 distinct points, 8 lists: centroids collapse, several lists
    # stay empty (padded size 0 — zero slab rows), search must ignore
    # them and still return exact results
    base = np.eye(4, 8, dtype=np.float32) * 10
    X = np.repeat(base, 16, axis=0)
    idx = build_ivf_flat(res, X, n_lists=8, max_iter=4, seed=0,
                         balanced=False)
    assert (np.asarray(idx.padded_sizes) == 0).any()
    Q = base + 0.01
    v, i = search_ivf_flat(res, idx, Q, 3, n_probes=2)
    # every query's nearest 3 are copies of its own base row (d2 tiny)
    assert np.asarray(v).max() < 1.0


def test_search_validation(fixture):
    res, _, Q, _, idx = fixture
    with pytest.raises(Exception):
        search_ivf_flat(res, idx, Q[:, :5], 10)       # wrong width
    with pytest.raises(Exception):
        search_ivf_flat(res, idx, Q, idx.n_rows + 1)  # k > rows
    with pytest.raises(Exception):
        search_ivf_flat(res, idx, Q, 10, n_probes=0)
    # requests larger than available candidates fill with (-inf? no:
    # +inf, -1) — never crash
    v, i = search_ivf_flat(res, idx, Q[:4], 10, n_probes=1)
    assert np.asarray(v).shape == (4, 10)


def test_zero_queries(fixture):
    res, _, Q, _, idx = fixture
    v, i = search_ivf_flat(res, idx, Q[:0], 5, n_probes=2)
    assert v.shape == (0, 5) and i.shape == (0, 5)


# ------------------------------------------- ragged _prepare_ops path
def test_prepare_ops_rows_valid_sentinels():
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import _PACK_PAD, _prepare_ops

    y = rng.normal(size=(300, 128)).astype(np.float32)
    mask = np.zeros(300, bool)
    mask[:100] = True
    mask[150:260] = True
    yp, y_hi, y_lo, yyh_k, yy_raw = _prepare_ops(
        jnp.asarray(y), 256, 2, "l2", pbits=8,
        rows_valid=jnp.asarray(mask))
    M = yp.shape[0]
    yyh = np.asarray(yyh_k)[0]
    padded_mask = np.concatenate([mask, np.zeros(M - 300, bool)])
    # masked-out rows carry the never-wins sentinel, real rows the norm
    assert (yyh[~padded_mask] == _PACK_PAD).all()
    assert (yyh[padded_mask] < _PACK_PAD).all()


def test_core_rows_valid_matches_dense_oracle():
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import (_knn_fused_core,
                                             _prepare_ops, knn_fused)

    m_slab, d = 384, 32
    mask = np.zeros(m_slab, bool)
    mask[:60] = True
    mask[100:220] = True
    mask[300:380] = True
    y_real = rng.normal(size=(mask.sum(), d)).astype(np.float32)
    slab = np.zeros((m_slab, d), np.float32)
    slab[mask] = y_real
    x = rng.normal(size=(16, d)).astype(np.float32)
    dpad = 128 - d
    slab_p = np.concatenate(
        [slab, np.zeros((m_slab, dpad), np.float32)], 1)
    x_p = np.concatenate([x, np.zeros((16, dpad), np.float32)], 1)
    ops = _prepare_ops(jnp.asarray(slab_p), 256, 2, "l2", pbits=8,
                       rows_valid=jnp.asarray(mask))
    M = ops[0].shape[0]
    rv = jnp.asarray(np.concatenate([mask, np.zeros(M - m_slab, bool)]))
    vals, ids = _knn_fused_core(
        jnp.asarray(x_p), *ops, k=5, T=256, Qb=16, g=2, passes=3,
        metric="l2", m=M, rescore=True, pbits=8, rows_valid=rv)
    ov, oi = knn_fused(x, y_real, k=5, T=256, Qb=16, g=2)
    slab_to_real = -np.ones(m_slab, np.int64)
    slab_to_real[mask] = np.arange(mask.sum())
    assert np.array_equal(slab_to_real[np.asarray(ids)], np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ov))


def test_core_rows_valid_rejects_unpacked():
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import (_knn_fused_core,
                                             _prepare_ops)

    y = rng.normal(size=(256, 128)).astype(np.float32)
    mask = jnp.asarray(np.ones(256, bool))
    ops = _prepare_ops(jnp.asarray(y), 256, 512, "l2", pbits=8,
                       rows_valid=mask)
    M = ops[0].shape[0]
    rv = jnp.asarray(np.ones(M, bool))
    with pytest.raises(ValueError, match="packed"):
        # g·(T/128) = 1024 > 2^8: outside the packed envelope
        _knn_fused_core(jnp.asarray(y), *ops, k=5, T=256, Qb=16,
                        g=512, passes=3, metric="l2", m=M,
                        rescore=True, pbits=8, rows_valid=rv)


# ----------------------------------------------------------- sharded
@pytest.mark.parametrize("p", [1, 2, 4])
@pytest.mark.parametrize("merge", ["allgather", "tournament"])
def test_sharded_matches_unsharded(fixture, p, merge):
    res, _, Q, oi, idx = fixture
    mesh = make_mesh({"x": p}, devices=jax.devices()[:p])
    sidx = shard_ivf_lists(idx, mesh, "x")
    uv, ui = search_ivf_flat(res, idx, Q, 10, n_probes=6)
    sv, si = search_ivf_flat(res, sidx, Q, 10, n_probes=6, merge=merge)
    assert _id_sets(si) == _id_sets(ui)
    # values for matched ids are bitwise equal (yy gathered, not
    # recomputed — the parity the sharded layout promises)
    np.testing.assert_array_equal(np.sort(np.asarray(sv), axis=1),
                                  np.sort(np.asarray(uv), axis=1))


@pytest.mark.parametrize("p", [1, 2, 4])
def test_sharded_recall_floor(fixture, p):
    # ISSUE-8 acceptance: recall@10 >= 0.95 at some swept n_probes on
    # the 8-virtual-device CPU suite at shard ∈ {1, 2, 4}
    res, _, Q, oi, idx = fixture
    mesh = make_mesh({"x": p}, devices=jax.devices()[:p])
    sidx = shard_ivf_lists(idx, mesh, "x")
    oracle = _id_sets(oi)
    best = 0.0
    for P in (4, 8):
        _, i = search_ivf_flat(res, sidx, Q, 10, n_probes=P)
        best = max(best, float(np.mean(
            [len(oracle[q] & s) / 10
             for q, s in enumerate(_id_sets(i))])))
    assert best >= 0.95


def test_sharded_degenerate_routes_exact(fixture):
    res, _, Q, oi, idx = fixture
    mesh = make_mesh({"x": 2}, devices=jax.devices()[:2])
    sidx = shard_ivf_lists(idx, mesh, "x")
    _, i = search_ivf_flat(res, sidx, Q, 10, n_probes=idx.n_lists)
    assert _id_sets(i) == _id_sets(oi)


def test_shard_layout_covers_all_rows(fixture):
    _, _, _, _, idx = fixture
    mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
    sidx = shard_ivf_lists(idx, mesh, "x")
    ids_g = np.asarray(jax.device_get(sidx.ids_s))
    real = ids_g[ids_g >= 0]
    assert (np.sort(real) == np.arange(idx.n_rows)).all()
    assert sidx.lists_per * sidx.n_shards >= idx.n_lists


# --------------------------------------------------------- wrappers
def test_nearest_neighbors_ivf_flat_wrapper(fixture):
    res, X, Q, oi, _ = fixture
    from raft_tpu import models

    nn = models.NearestNeighbors(n_neighbors=10, metric="sqeuclidean",
                                 algorithm="ivf_flat", n_lists=24,
                                 n_probes=24, res=res).fit(X)
    d, i = nn.kneighbors(Q)
    assert _id_sets(i) == _id_sets(oi)        # degenerate-exact
    with pytest.raises(ValueError):
        models.NearestNeighbors(algorithm="bogus")
    with pytest.raises(ValueError):
        models.NearestNeighbors(algorithm="ivf_flat", metric="cosine")


def test_env_knobs(fixture, monkeypatch):
    res, X, Q, oi, idx = fixture
    # RAFT_TPU_ANN_NPROBES retunes default-probes callers per call
    monkeypatch.setenv("RAFT_TPU_ANN_NPROBES", str(idx.n_lists))
    _, i = search_ivf_flat(res, idx, Q, 10)       # no n_probes arg
    assert _id_sets(i) == _id_sets(oi)            # env forced exact
    monkeypatch.setenv("RAFT_TPU_ANN_NPROBES", "garbage")
    v, _ = search_ivf_flat(res, idx, Q[:4], 5)    # degrades to default
    assert np.asarray(v).shape == (4, 5)
    # RAFT_TPU_IVF_ROW_QUANTUM reshapes the slab padding
    monkeypatch.setenv("RAFT_TPU_IVF_ROW_QUANTUM", "32")
    idx32 = build_ivf_flat(res, X[:512], n_lists=4, max_iter=2, seed=0)
    assert idx32.row_quantum == 32
    padded = np.asarray(idx32.padded_sizes)
    assert ((padded % 32 == 0) | (padded == 0)).all()


def test_ivf_build_validation(fixture):
    res, X, _, _, _ = fixture
    with pytest.raises(Exception):
        build_ivf_flat(res, X[:8], n_lists=9)
    with pytest.raises(Exception):
        build_ivf_flat(res, X[:8], n_lists=0)


# ------------------------------------------------ spans and counters
#: the host boundaries of a search, each a span under the search's own
_CHILD_SPANS = {
    "list": {"ann.coarse_probe", "ann.fine_scan_plan", "ann.fine_scan",
             "ann.certificate_sync"},
    "query": {"ann.coarse_probe", "ann.fine_scan_plan", "ann.fine_scan"},
}


@pytest.fixture()
def flight():
    from raft_tpu.observability import FlightRecorder, set_flight_recorder

    rec = FlightRecorder(capacity=8192)
    prev = set_flight_recorder(rec)
    yield rec
    set_flight_recorder(prev)


def _ann_spans(rec):
    return [e for e in rec.events()
            if e["kind"] == "span" and e["name"].startswith("ann.")]


def _probed_rows():
    from raft_tpu.ann.ivf_flat import PROBED_ROWS
    from raft_tpu.observability import get_registry

    return get_registry().counter(PROBED_ROWS).value


@pytest.mark.parametrize("schedule", ["list", "query"])
def test_search_spans_cover_each_host_boundary(fixture, flight,
                                               monkeypatch, schedule):
    """One span per host boundary, each under ``ann.search_ivf_flat``,
    one ``ann.fine_scan`` per chunk; the list-major search fetches no
    probe table (its program counts the probed rows, read with the
    certificate), and an explicitly query-major call counts none."""
    from raft_tpu.ann import ivf_flat
    from raft_tpu.ann.ivf_flat import _coarse_probe

    res, _, Q, _, idx = fixture
    monkeypatch.setattr(ivf_flat, "_FINE_TILE", 1)    # 8-query tiles
    monkeypatch.setattr(ivf_flat, "_list_major_chunk",
                        lambda index, nq: 8)          # 8-query chunks
    x = Q[:32]
    rows0 = _probed_rows()
    flight.clear()
    search_ivf_flat(res, idx, x, 10, n_probes=3, fine_scan=schedule)
    spans = _ann_spans(flight)
    children = [e for e in spans if e["name"] != "ann.search_ivf_flat"]
    assert [e["name"] for e in spans].count("ann.search_ivf_flat") == 1
    assert {e["name"] for e in children} == _CHILD_SPANS[schedule]
    assert all(e["range"] == "ann.search_ivf_flat" for e in children)
    names = [e["name"] for e in children]
    assert names.count("ann.fine_scan") == 4           # 32 / 8 chunks
    probes = np.asarray(_coarse_probe(res, idx.centroids, x, 3))
    host_rows = int(np.asarray(idx.sizes)[probes].sum())
    if schedule == "list":
        assert names.count("ann.certificate_sync") == 4
        assert names.count("ann.fine_scan_plan") == 1
        assert _probed_rows() - rows0 == host_rows
    else:
        assert _probed_rows() == rows0


def test_list_major_rerun_is_one_span_per_chunk(fixture, flight,
                                                monkeypatch):
    """A chunk whose certificate fails reruns inside one
    ``ann.fine_scan_rerun`` span, with no scan span of its own nested
    in it, and answers as the query-major scan does."""
    import jax.numpy as jnp

    from raft_tpu.ann import ivf_flat

    res, _, Q, _, idx = fixture
    monkeypatch.setattr(ivf_flat, "_list_major_chunk",
                        lambda index, nq: 8)
    scan = ivf_flat._fine_scan_list

    def uncertified(*a, **kw):
        vals, ids, ok, margin, stats = scan(*a, **kw)
        return vals, ids, jnp.zeros_like(ok), margin, stats

    monkeypatch.setattr(ivf_flat, "_fine_scan_list", uncertified)
    flight.clear()
    vl, il = search_ivf_flat(res, idx, Q[:32], 10, n_probes=3,
                             fine_scan="list")
    names = [e["name"] for e in _ann_spans(flight)]
    assert names.count("ann.fine_scan_rerun") == 4
    assert names.count("ann.fine_scan") == 4
    vq, iq = search_ivf_flat(res, idx, Q[:32], 10, n_probes=3,
                             fine_scan="query")
    assert np.array_equal(np.asarray(il), np.asarray(iq))


def test_warm_search_pays_no_observability_sync(fixture, flight,
                                                monkeypatch):
    """The fine scan's cost capture happens at warm-up, and a warm
    query-major search moves nothing to the host: neither a cost
    capture nor a device reduction for the flight marker."""
    from raft_tpu.ann.ivf_flat import warm_fine_scan

    res, _, Q, _, idx = fixture
    captured = []
    monkeypatch.setattr(type(res.profiler), "capture_fn",
                        lambda self, entry, *a, **kw: captured.append(
                            entry))
    warm_fine_scan(res, idx, 32, 10, 3)
    assert "ann.ivf_fine_scan" in captured
    captured.clear()
    flight.clear()
    with jax.transfer_guard_device_to_host("disallow"):
        out = search_ivf_flat(res, idx, Q[:32], 10, n_probes=3,
                              fine_scan="query")
    jax.block_until_ready(out)
    assert captured == []
    marker = [e for e in flight.events() if e["name"] == "ivf_search"]
    assert len(marker) == 1 and "probed_frac" not in marker[0]


# ------------------------------------------------ list-major chunking
@pytest.fixture(scope="module")
def idx8(fixture):
    res, X, _, _, _ = fixture
    return build_ivf_flat(res, X, n_lists=24, max_iter=6, seed=1,
                          db_dtype="int8")


def test_list_major_chunk_follows_the_vmem_envelope(fixture):
    """The list-major chunk is the search's rows rounded up to 8, up to
    the largest power-of-two multiple of 8 whose kernel cell fits the
    scoped-VMEM budget."""
    from raft_tpu.ann.ivf_flat import _list_major_chunk
    from raft_tpu.ops.fine_scan_pallas import (fine_scan_vmem_footprint,
                                               pad_window)
    from raft_tpu.ops.fused_l2_topk_pallas import vmem_budget

    _, _, _, _, idx = fixture
    assert _list_major_chunk(idx, 1) == 8
    assert _list_major_chunk(idx, 20) == 24
    assert _list_major_chunk(idx, 32) == 32
    big = _list_major_chunk(idx, 1 << 16)
    Wk = pad_window(idx.probe_window)
    assert big % 8 == 0 and (big // 8) & (big // 8 - 1) == 0
    assert fine_scan_vmem_footprint(Wk, big, idx.d_orig) <= vmem_budget()
    assert fine_scan_vmem_footprint(Wk, 2 * big, idx.d_orig) \
        > vmem_budget()


def test_list_major_search_is_one_chunk(fixture, flight):
    """An unforced 32-row list-major search fits one kernel call: one
    scan dispatch, one certificate sync, the search's one plan, and the
    schedule marker names the chunk's rows."""
    res, _, Q, _, idx = fixture
    flight.clear()
    search_ivf_flat(res, idx, Q[:32], 10, n_probes=3, fine_scan="list")
    names = [e["name"] for e in _ann_spans(flight)]
    assert names.count("ann.fine_scan") == 1
    assert names.count("ann.certificate_sync") == 1
    assert names.count("ann.fine_scan_plan") == 1
    marker = [e for e in flight.events()
              if e["name"] == "ivf_fine_scan_schedule"]
    assert len(marker) == 1 and marker[0]["chunk_rows"] == 32


@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_list_major_ids_do_not_depend_on_the_chunk(fixture, idx8,
                                                   monkeypatch, dtype):
    """A query's pool does not depend on which queries share its
    chunk: ids at the whole-search chunk equal those at 8-row chunks,
    and the query-major oracle's (f32 bit for bit; int8 as id sets,
    the quantized contract)."""
    from raft_tpu.ann import ivf_flat

    res, _, Q, _, idx = fixture
    index = idx if dtype == "f32" else idx8
    _, whole = search_ivf_flat(res, index, Q[:32], 10, n_probes=3,
                               fine_scan="list")
    _, oracle = search_ivf_flat(res, index, Q[:32], 10, n_probes=3,
                                fine_scan="query")
    monkeypatch.setattr(ivf_flat, "_list_major_chunk",
                        lambda index, nq: 8)
    _, eight = search_ivf_flat(res, index, Q[:32], 10, n_probes=3,
                               fine_scan="list")
    whole, eight = np.asarray(whole), np.asarray(eight)
    assert np.array_equal(whole, eight)
    if dtype == "f32":
        assert np.array_equal(whole, np.asarray(oracle))
    else:
        assert _id_sets(whole) == _id_sets(oracle)


def test_list_major_rerun_is_only_the_failed_tiles(fixture, flight,
                                                   monkeypatch):
    """Rows whose certificate fails rerun query-major in the 8-row
    query-major tiles that hold them, and only those; the answer is the
    query-major oracle's."""
    from raft_tpu.ann import ivf_flat

    res, _, Q, _, idx = fixture
    monkeypatch.setattr(ivf_flat, "_FINE_TILE", 1)    # 8-query tiles
    scan = ivf_flat._fine_scan_list
    failed = np.zeros(32, bool)
    failed[[3, 20, 22]] = True

    def uncertified(*a, **kw):
        vals, ids, ok, margin, stats = scan(*a, **kw)
        return vals, ids, jax.device_put(~failed), margin, stats

    tiles = []
    operands = ivf_flat._tile_operands

    def spy(*a, **kw):
        tiles.append((int(a[4]), kw["rows"]))
        return operands(*a, **kw)

    monkeypatch.setattr(ivf_flat, "_fine_scan_list", uncertified)
    monkeypatch.setattr(ivf_flat, "_tile_operands", spy)
    flight.clear()
    _, il = search_ivf_flat(res, idx, Q[:32], 10, n_probes=3,
                            fine_scan="list")
    names = [e["name"] for e in _ann_spans(flight)]
    assert names.count("ann.fine_scan") == 1
    assert names.count("ann.fine_scan_rerun") == 1
    assert tiles == [(0, 8), (16, 8)]
    fallback = [e for e in flight.events()
                if e["name"] == "ivf_list_fallback"]
    assert len(fallback) == 1 and fallback[0]["n_fail"] == 3
    _, iq = search_ivf_flat(res, idx, Q[:32], 10, n_probes=3,
                            fine_scan="query")
    assert np.array_equal(np.asarray(il), np.asarray(iq))


@pytest.mark.parametrize("forced_rerun", [False, True])
def test_warm_list_major_search_compiles_nothing(fixture, monkeypatch,
                                                 forced_rerun):
    """After ``warm_fine_scan`` a live 32-row list-major search lowers
    and compiles no program, a rerun of failed rows included."""
    from raft_tpu.ann import ivf_flat
    from raft_tpu.ann.ivf_flat import warm_fine_scan

    res, _, Q, _, idx = fixture
    monkeypatch.setattr(ivf_flat, "_FINE_TILE", 1)    # 8-query tiles
    assert warm_fine_scan(res, idx, 32, 10, 3) >= 1
    if forced_rerun:
        scan = ivf_flat._fine_scan_list
        ok = np.ones(32, bool)
        ok[[5, 30]] = False

        def uncertified(*a, **kw):
            vals, ids, _, margin, stats = scan(*a, **kw)
            return vals, ids, jax.device_put(ok), margin, stats

        monkeypatch.setattr(ivf_flat, "_fine_scan_list", uncertified)
    events = []

    def on_event(event, duration, **_):
        if event in ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                     "/jax/core/compile/backend_compile_duration"):
            events.append(event)

    misses = res.compile_cache.misses
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        out = search_ivf_flat(res, idx, Q[32:64], 10, n_probes=3,
                              fine_scan="list")
        jax.block_until_ready(out)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert events == []
    assert res.compile_cache.misses == misses


def test_kernel_schedule_streams_only_its_cells(fixture):
    """Every chunk's schedule is padded to the index's longest, and the
    kernel, given the chunk's own cell count, pools exactly what the
    unpadded schedule pools."""
    import jax.numpy as jnp

    from raft_tpu.ann.ivf_flat import (_coarse_probe, _kernel_schedule,
                                       _max_entries, _pad_kernel_operands,
                                       build_list_schedule)
    from raft_tpu.ops.fine_scan_pallas import (LISTS_PER_CELL,
                                               fine_scan_list_major,
                                               pad_window)

    res, _, Q, _, idx = fixture
    x = jnp.asarray(Q[:16])
    probes = _coarse_probe(res, idx.centroids, x, 3)
    sched = build_list_schedule(idx, np.asarray(probes))
    full, scale, n_cells = _kernel_schedule(idx, sched)
    n = full.shape[1]
    assert full.shape == (4, n) and scale.shape == (n,)
    assert n == -(-_max_entries(idx) // LISTS_PER_CELL) * LISTS_PER_CELL
    n_ent = int((sched.sched[3] >= 0).sum())
    assert n_cells == -(-n_ent // LISTS_PER_CELL)
    assert (full[3, n_ent:] == -1).all()
    assert np.array_equal(full[:, :n_ent], sched.sched[:, :n_ent])
    xp, pp, _ = _pad_kernel_operands(x, probes)
    xx = jnp.sum(xp * xp, axis=1, keepdims=True)
    Wk = pad_window(idx.probe_window)
    short = sched.sched.shape[1] // LISTS_PER_CELL
    a = fine_scan_list_major(jnp.asarray(sched.sched), short, xp, xx, pp,
                             idx.slab, Wk=Wk)
    b = fine_scan_list_major(jnp.asarray(full), n_cells, xp, xx, pp,
                             idx.slab, Wk=Wk)
    for u, v in zip(a, b):
        assert np.array_equal(np.asarray(u), np.asarray(v))
