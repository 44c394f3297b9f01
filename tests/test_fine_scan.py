"""List-major fine scan (ISSUE 14) — the stream-once IVF schedule:
bit-exact id parity vs the query-major oracle across the full matrix
(f32/int8 × ragged/imbalanced lists × degenerate-exact × the
single-hot-list adversarial case), the fine_scan_list degradation rung
(injected error → query-major with a logged degradation + identical
ids), the schedule builder's group-table invariants, the
resolve_fine_scan envelope/crossover, the histogram-aware traffic
model, the schema-5 fine_scan tune column, and the bench_report
overread gate."""

import json
import os

import jax
import numpy as np
import pytest

from raft_tpu import resilience
from raft_tpu.ann import (build_ivf_flat, build_list_schedule,
                          resolve_fine_scan, search_ivf_flat,
                          shard_ivf_lists, warm_fine_scan)
from raft_tpu.ann.ivf_flat import _LIST_K_MAX
from raft_tpu.parallel import make_mesh
from raft_tpu.random import make_blobs
from raft_tpu.resilience import policy

rng = np.random.default_rng(29)


@pytest.fixture(scope="module")
def fixture():
    from raft_tpu.core import DeviceResources

    res = DeviceResources(seed=4)
    m, d = 3000, 16
    X, _ = make_blobs(res, 31, m, d, n_clusters=12, cluster_std=1.2,
                      proportions=rng.uniform(0.4, 2.5, 12))
    X = np.asarray(X, np.float32)
    Q = X[rng.choice(m, 48, replace=False)] \
        + rng.normal(0, 0.05, (48, d)).astype(np.float32)
    idx = build_ivf_flat(res, X, n_lists=12, max_iter=5, seed=2)
    idx8 = build_ivf_flat(res, X, n_lists=12, max_iter=5, seed=2,
                          db_dtype="int8")
    return res, X, Q, idx, idx8


@pytest.fixture(autouse=True)
def _no_faults():
    yield
    resilience.configure_faults("")


def _ids(a):
    return np.asarray(a[1])


# ------------------------------------------------- parity matrix
@pytest.mark.parametrize("dtype", ["f32", "int8"])
@pytest.mark.parametrize("P", [1, 3, 6])
def test_list_major_id_parity(fixture, dtype, P):
    """The acceptance bit: list-major ids identical to the query-major
    oracle over ragged imbalanced lists, both storage dtypes."""
    res, _, Q, idx, idx8 = fixture
    index = idx8 if dtype == "int8" else idx
    vq, iq = search_ivf_flat(res, index, Q, 10, n_probes=P,
                             fine_scan="query")
    vl, il = search_ivf_flat(res, index, Q, 10, n_probes=P,
                             fine_scan="list")
    iq, il = np.asarray(iq), np.asarray(il)
    if dtype == "f32":
        # f32 list-major rescores with the query-major formula over
        # the same rows and reorders into its candidate order —
        # positions AND values are bitwise identical, ties included
        assert np.array_equal(iq, il)
        assert np.array_equal(np.asarray(vq), np.asarray(vl))
    else:
        # the int8 contract is the PR-9 one: id SETS identical (the
        # quantized gather's own tie order at exact f32 value ties is
        # quantization-noise-dependent — it already diverges from the
        # f32 scan there; the list-major path canonicalizes ties to
        # the f32 position order instead)
        assert all(set(a) == set(b) for a, b in zip(iq, il))
        np.testing.assert_allclose(np.asarray(vq), np.asarray(vl),
                                   rtol=1e-4, atol=1e-3)


def test_single_hot_list_adversarial(fixture):
    """Every query probes the SAME list (queries drawn from one
    centroid's neighborhood, P=1) — the maximal-overread case the
    list-major schedule exists for, and the maximal-group-width case
    for the query-group table."""
    res, X, _, idx, idx8 = fixture
    centroid = np.asarray(idx.centroids)[0]
    Qh = (centroid[None, :]
          + rng.normal(0, 0.02, (32, X.shape[1]))).astype(np.float32)
    for index, exact_pos in ((idx, True), (idx8, False)):
        vq, iq = search_ivf_flat(res, index, Qh, 5, n_probes=1,
                                 fine_scan="query")
        vl, il = search_ivf_flat(res, index, Qh, 5, n_probes=1,
                                 fine_scan="list")
        iq, il = np.asarray(iq), np.asarray(il)
        if exact_pos:
            assert np.array_equal(iq, il)
        else:
            assert all(set(a) == set(b) for a, b in zip(iq, il))
    # and the schedule really is one hot list wide
    from raft_tpu.ann.ivf_flat import _coarse_probe

    probes = np.asarray(_coarse_probe(res, idx.centroids, Qh, 1))
    sched = build_list_schedule(idx, probes)
    assert sched.n_lists_probed == len(np.unique(probes))
    assert sched.q_max >= 32 and sched.q_max % 8 == 0


def test_degenerate_exact_unchanged(fixture):
    """n_probes = n_lists still degrades to the certified exact plane
    whatever fine_scan asks for — one schedule, oracle-exact ids."""
    res, X, Q, idx, _ = fixture
    from raft_tpu.distance.fused_l2nn import knn

    _, oi = knn(res, X, Q, 10)
    oracle = [set(r) for r in np.asarray(oi)]
    for fs in ("query", "list", "auto"):
        _, i = search_ivf_flat(res, idx, Q, 10, n_probes=idx.n_lists,
                               fine_scan=fs)
        assert all(set(r) == oracle[q]
                   for q, r in enumerate(np.asarray(i)))


@pytest.mark.parametrize("p", [2, 4])
def test_sharded_int8_id_parity(fixture, p):
    """ISSUE-14 satellite: the sharded IVF fine scan now streams the
    int8 sidecar — id parity vs the unsharded scan at p ∈ {2, 4}."""
    res, _, Q, _, idx8 = fixture
    vu, iu = search_ivf_flat(res, idx8, Q, 10, n_probes=4,
                             fine_scan="query")
    mesh = make_mesh({"x": p}, devices=jax.devices()[:p])
    sidx = shard_ivf_lists(idx8, mesh, "x")
    assert sidx.slab_qs is not None and sidx.eq_s is not None
    vs, is_ = search_ivf_flat(res, sidx, Q, 10, n_probes=4)
    iu, is_ = np.asarray(iu), np.asarray(is_)
    assert all(set(a) == set(b) for a, b in zip(iu, is_))
    np.testing.assert_allclose(np.sort(np.asarray(vs), axis=1),
                               np.sort(np.asarray(vu), axis=1),
                               rtol=1e-4, atol=1e-3)


# ------------------------------------------- degradation rung
def test_fine_scan_list_fault_degrades(fixture):
    """An injected error at the fine_scan_list site degrades to the
    query-major scan for that call: identical ids/values, one counted
    degradation, and no exception out of search_ivf_flat."""
    res, _, Q, idx, _ = fixture
    vq, iq = search_ivf_flat(res, idx, Q, 10, n_probes=3,
                             fine_scan="query")
    before = policy.degradation_count()
    resilience.configure_faults("fine_scan_list:error")
    vl, il = search_ivf_flat(res, idx, Q, 10, n_probes=3,
                             fine_scan="list")
    resilience.configure_faults("")
    assert policy.degradation_count() > before
    assert np.array_equal(np.asarray(iq), np.asarray(il))
    assert np.array_equal(np.asarray(vq), np.asarray(vl))


def test_fine_scan_list_kernel_error_propagates(fixture, monkeypatch):
    """Only injected/classified device failures degrade: any other
    error of the list-major path (a kernel the compiler refuses)
    reaches the caller, and no degradation is recorded."""
    import raft_tpu.ann.ivf_flat as ivf_flat_mod

    res, _, Q, idx, _ = fixture

    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ivf_flat_mod, "_search_list_major", refused)
    before = policy.degradation_count()
    with pytest.raises(RuntimeError, match="Mosaic"):
        search_ivf_flat(res, idx, Q, 10, n_probes=3, fine_scan="list")
    assert policy.degradation_count() == before


@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_long_lists_stream_as_segments(fixture, monkeypatch, dtype):
    """A list longer than the kernel window takes one schedule entry
    per window of rows — every real row covered exactly once — and
    the list-major ids stay identical to the query-major scan's."""
    import raft_tpu.ops.fine_scan_pallas as fsp
    from raft_tpu.ann.ivf_flat import _coarse_probe

    res, _, Q, idx, idx8 = fixture
    index = idx if dtype == "f32" else idx8
    monkeypatch.setattr(fsp, "SEGMENT_ROWS", 128)
    assert index.probe_window > 128          # the fixture's lists split
    probes = np.asarray(_coarse_probe(res, index.centroids, Q, 3))
    s = build_list_schedule(index, probes).sched
    offs, sizes = np.asarray(index.offsets), np.asarray(index.sizes)
    for lid in np.unique(probes):
        ent = s[:, s[3] == lid]
        assert ent[1].sum() == sizes[lid]
        rows = np.concatenate([np.arange(st + off, st + off + n)
                               for st, n, off, _ in ent.T])
        assert np.array_equal(np.sort(rows),
                              offs[lid] + np.arange(sizes[lid]))
        assert np.all(ent[2] + ent[1] <= 128)
    vq, iq = search_ivf_flat(res, index, Q, 10, n_probes=3,
                             fine_scan="query")
    vl, il = search_ivf_flat(res, index, Q, 10, n_probes=3,
                             fine_scan="list")
    assert all(set(a) == set(b) for a, b in zip(np.asarray(iq),
                                                 np.asarray(il)))
    np.testing.assert_allclose(np.asarray(vl), np.asarray(vq),
                               rtol=1e-4, atol=1e-3)


def test_fine_scan_list_site_registered():
    assert "fine_scan_list" in resilience.KNOWN_SITES
    assert "autotune_fine_scan" in resilience.KNOWN_SITES


# ------------------------------------------- schedule builder
def test_schedule_builder_invariants(fixture):
    res, _, Q, idx, _ = fixture
    from raft_tpu.ann.ivf_flat import _coarse_probe
    from raft_tpu.ops.fine_scan_pallas import (LISTS_PER_CELL,
                                               pad_window)

    probes = np.asarray(_coarse_probe(res, idx.centroids, Q, 4))
    sched = build_list_schedule(idx, probes)
    s = sched.sched
    Lp = sched.n_lists_probed
    assert s.shape[0] == 4 and s.shape[1] % LISTS_PER_CELL == 0
    # cell count is a power of two (or the index's own cap)
    cells = s.shape[1] // LISTS_PER_CELL
    cap = -(-idx.n_lists // LISTS_PER_CELL)
    assert cells == cap or (cells & (cells - 1)) == 0
    Wk = pad_window(idx.probe_window)
    offs = np.asarray(idx.offsets)
    sizes = np.asarray(idx.sizes)
    for g in range(s.shape[1]):
        st, lsize, off, lid = s[:, g]
        if lid < 0:        # pad entry
            assert lsize == 0
            continue
        # clamped window stays inside the slab and covers the list
        assert 0 <= st <= idx.slab_rows - Wk
        assert st + off == offs[lid]
        assert lsize == sizes[lid]
        assert off + lsize <= Wk
    # the query-group table: one row per probed list, every (q, list)
    # probe accounted for exactly once, q_max padded to the 8 quantum
    assert sched.group.shape == (Lp, sched.q_max)
    assert sched.q_max % 8 == 0
    assert sched.group_mask.sum() == (probes >= 0).sum()
    inv = {int(l): g for g, l in enumerate(s[3, :Lp])}
    for q in range(probes.shape[0]):
        for l in probes[q]:
            g = inv[int(l)]
            hits = sched.group[g][sched.group_mask[g]]
            assert q in hits


# ------------------------------------------- chooser + model
def test_resolve_envelope_downgrades(fixture):
    res, _, Q, idx, _ = fixture
    W = idx.probe_window
    # k beyond the candidate pool → query, even when list is forced
    assert resolve_fine_scan(idx, 48, _LIST_K_MAX + 1, 3, W,
                             "list") == "query"
    # probe table cap
    assert resolve_fine_scan(idx, 48, 10, 129, W, "list") == "query"
    # explicit query always wins
    assert resolve_fine_scan(idx, 48, 10, 3, W, "query") == "query"
    with pytest.raises(ValueError):
        resolve_fine_scan(idx, 48, 10, 3, W, "bogus")


def test_resolve_env_knob(fixture, monkeypatch):
    res, _, Q, idx, _ = fixture
    monkeypatch.setenv("RAFT_TPU_IVF_FINE_SCAN", "query")
    assert resolve_fine_scan(idx, 48, 10, 3, idx.probe_window) \
        == "query"
    monkeypatch.setenv("RAFT_TPU_IVF_FINE_SCAN", "list")
    assert resolve_fine_scan(idx, 48, 10, 3, idx.probe_window) \
        == "list"


def test_resolve_crossover_uses_actual_probes(fixture):
    """The hot shared probe table picks list; a cold all-distinct one
    (every query probing its own lists — no re-read to save) picks
    query. Both through the ACTUAL-probe crossover path."""
    res, _, Q, idx, _ = fixture
    hot = np.zeros((64, 2), np.int32)
    hot[:, 1] = 1
    assert resolve_fine_scan(idx, 64, 10, 2, idx.probe_window, "auto",
                             probes_np=hot) == "list"
    # two queries probing the four LARGEST lists (distinct — nothing
    # shared to re-read, and the padded windows match the gather's
    # static max window): gather ≈ stream, the margin keeps query
    big = np.argsort(np.asarray(idx.padded_sizes))[-4:].astype(
        np.int32)
    cold = big.reshape(2, 2)
    assert resolve_fine_scan(idx, 2, 10, 2, idx.probe_window, "auto",
                             probes_np=cold) == "query"


def test_traffic_model_histogram():
    """The histogram-aware model (ISSUE-14 satellite): skewed lists
    raise the size-biased probed fraction above the uniform-window
    estimate, and the per-chunk union keeps list-major stream bytes
    at/below the gather bytes."""
    from raft_tpu.observability.costmodel import (choose_fine_scan,
                                                  ivf_traffic_model)

    sizes = [10] * 15 + [850]          # one hot list
    padded = [16] * 15 + [856]
    uni = ivf_traffic_model(256, 1000, 64, 10, 16, 2, 856,
                            16 * 856 // 8)
    hist = ivf_traffic_model(256, 1000, 64, 10, 16, 2, 856,
                             16 * 856 // 8, list_sizes=sizes,
                             padded_sizes=padded)
    assert hist["fine_stream_bytes"] < uni["fine_stream_bytes"]
    assert hist["gather_overread"] > 1.0
    assert hist["list_rescore_bytes"] > 0
    assert choose_fine_scan(hist) in ("query", "list")
    # hot shared traffic → the crossover picks list
    assert choose_fine_scan(hist) == "list"


# ------------------------------------------- tune column (schema 5)
def test_fine_scan_tune_rows_and_loader(tmp_path, monkeypatch):
    from raft_tpu.tune import (TUNE_SCHEMA_VERSION, autotune_fine_scan,
                               fine_scan_config, validate_tune_table)
    from raft_tpu.tune import ivf as tune_ivf

    assert TUNE_SCHEMA_VERSION >= 5
    rows = autotune_fine_scan((256, 20_000, 64, 10), lists=(16,))
    assert rows and all(r["fine_scan"] in ("query", "list")
                        for r in rows)
    tbl = {"schema": TUNE_SCHEMA_VERSION, "rows": [],
           "fine_scan": rows}
    assert validate_tune_table(tbl) == []
    path = tmp_path / "TUNE_FUSED.json"
    path.write_text(json.dumps(tbl))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    tune_ivf._cache.clear()
    want = {(r["n_lists"], r["n_probes"]): r["fine_scan"]
            for r in rows}
    for (L, P), sched in want.items():
        assert fine_scan_config(L, P) == sched
    assert fine_scan_config(9999, 1) is None
    # malformed column → structural validation error
    bad = dict(tbl, fine_scan=[{"n_lists": "x"}])
    assert validate_tune_table(bad)
    # corrupt table degrades to None (cost model decides)
    path.write_text("{not json")
    tune_ivf._cache.clear()
    assert fine_scan_config(16, 1) is None


def test_resolve_consults_tuned_table(fixture, tmp_path, monkeypatch):
    res, _, Q, idx, _ = fixture
    from raft_tpu.tune import TUNE_SCHEMA_VERSION
    from raft_tpu.tune import ivf as tune_ivf

    tbl = {"schema": TUNE_SCHEMA_VERSION, "rows": [],
           "fine_scan": [{"n_lists": idx.n_lists, "n_probes": 3,
                          "fine_scan": "query"}]}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(tbl))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    tune_ivf._cache.clear()
    assert resolve_fine_scan(idx, 48, 10, 3, idx.probe_window,
                             "auto") == "query"
    monkeypatch.delenv("RAFT_TPU_TUNE_FUSED")
    tune_ivf._cache.clear()


# ------------------------------------------- serving warmup
def test_warm_fine_scan_compiles_rungs(fixture):
    res, _, _, idx, _ = fixture
    rungs = warm_fine_scan(res, idx, 16, 5, 3)
    assert rungs >= 1
    # degenerate geometry has one schedule — nothing to warm
    assert warm_fine_scan(res, idx, 16, 5, idx.n_lists) == 0


# ------------------------------------------- bench_report gate
def test_bench_report_fine_scan_gate():
    import tools.bench_report as br

    good = {"frontier": [
        {"n_lists": 16, "n_probes": 4, "fine_scan": "list",
         "model_stream_bytes": 100.0, "model_gather_bytes": 1000.0,
         "gather_overread": 5.0},
        {"n_lists": 16, "n_probes": 1, "fine_scan": "query",
         "gather_overread": 1.1},
    ]}
    err, best = br._ann_fine_scan_check(good)
    assert err is None and best == 5.0
    bad = {"frontier": [
        {"n_lists": 16, "n_probes": 4, "fine_scan": "list",
         "model_stream_bytes": 900.0, "model_gather_bytes": 1000.0,
         "gather_overread": 5.0}]}
    err, _ = br._ann_fine_scan_check(bad)
    assert err and "FINE-SCAN BYTES" in err
    # rounds predating the columns carry no overread evidence
    err, best = br._ann_fine_scan_check({"frontier": [
        {"n_lists": 16, "n_probes": 4, "recall_at_k": 1.0}]})
    assert err is None and best is None


def test_bench_report_overread_trend():
    """The trend gate: a newest round whose best list-major overread
    fell > ANN_OVERREAD_SLACK below the previous comparable round
    regresses; within slack passes."""
    import tools.bench_report as br

    def round_(ovr, n=1):
        return {"ok": True, "k": 10, "recall_floor": 0.95,
                "degenerate_exact": True, "measured": False,
                "frontier": [
                    {"n_lists": 16, "n_probes": 4, "recall_at_k": 1.0,
                     "fine_scan": "list", "model_stream_bytes": 10.0,
                     "model_gather_bytes": 10.0 * ovr,
                     "gather_overread": ovr}]}

    prev, good, bad = round_(5.0), round_(4.5), round_(2.0)
    status, msg = br.check_ann([(1, "a", prev), (2, "b", good)])
    assert status == br.PASS, msg
    status, msg = br.check_ann([(1, "a", prev), (2, "b", bad)])
    assert status == br.REGRESS and "OVERREAD TREND" in msg


def test_committed_artifact_has_fine_scan_columns():
    """The regenerated BENCH_ANN.json carries the schedule + both
    schedules' modeled bytes at every frontier point, with at least
    one list-major pick realizing an overread win > 1."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_ANN.json")
    with open(path) as f:
        rec = json.load(f)
    pts = rec["frontier"]
    assert all("fine_scan" in p for p in pts)
    non_exact = [p for p in pts if p["fine_scan"] != "exact"]
    assert all("model_stream_bytes" in p and "model_gather_bytes" in p
               for p in non_exact)
    listed = [p for p in non_exact if p["fine_scan"] == "list"]
    assert listed, "no frontier point chose the list-major schedule"
    assert max(p["gather_overread"] for p in listed) > 1.0
    import tools.bench_report as br

    err, best = br._ann_fine_scan_check(rec)
    assert err is None and best is not None
