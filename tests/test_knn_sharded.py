"""Sharded stream-once KNN tests (ISSUE 4 tentpole).

The 8-virtual-device CPU rendering of the acceptance criteria: the
database-sharded fused pipeline must be BIT-EXACT against the
single-device ``knn_fused`` oracle for p ∈ {2, 4, 8} × both merge
strategies × ragged (k, nq) shapes, plus the query-sharded serving
mode, the micro-batched overlap schedule, the ICI cost-model merge
crossover, the collective counters the merge rounds flow through, the
``NearestNeighbors`` ``n_shards=`` routing, and the off-TPU
deterministic ``autotune_sharded`` ranking.
"""

import json

import numpy as np
import pytest

import jax

from raft_tpu.distance.knn_fused import knn_fused, prepare_knn_index
from raft_tpu.distance.knn_sharded import (default_micro_batches,
                                           knn_fused_sharded,
                                           prepare_knn_index_sharded,
                                           resolve_merge_strategy)
from raft_tpu.parallel import make_mesh

rng = np.random.default_rng(7)

# the shared parity shape: m large enough that every shard at p=8 owns
# real rows (rows_per = 512 at T=256), k and nq NOT divisible by any p
M, D, K, NQ = 4100, 32, 7, 33
CFG = dict(T=256, Qb=32, g=2)


@pytest.fixture(scope="module")
def data():
    y = rng.normal(size=(M, D)).astype(np.float32)
    x = rng.normal(size=(NQ, D)).astype(np.float32)
    ov, oi = knn_fused(x, y, k=K, passes=3, **CFG)
    return x, y, np.asarray(ov), np.asarray(oi)


def _mesh(p):
    return make_mesh({"x": p}, devices=jax.devices()[:p])


# ------------------------------------------------ bit-exact parity
@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("merge", ["allgather", "tournament"])
def test_sharded_bitexact_vs_oracle(data, p, merge):
    """The acceptance criterion: same bits as the single-device oracle
    for every shard count × merge strategy, with k and nq not divisible
    by p."""
    x, y, ov, oi = data
    mesh = _mesh(p)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    sv, si = knn_fused_sharded(x, idx, K, mesh=mesh, merge=merge)
    assert np.array_equal(np.asarray(sv), ov)
    # well-separated random data: the id SETS must match exactly
    assert np.array_equal(np.sort(np.asarray(si), 1), np.sort(oi, 1))


def test_sharded_micro_batches_and_db_order(data):
    """Micro-batching (the overlap schedule) and the stream-once db
    grid order change scheduling only — not one bit of the result."""
    x, y, ov, oi = data
    mesh = _mesh(4)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3,
                                    grid_order="db", **CFG)
    for nb in (1, 3):
        sv, si = knn_fused_sharded(x, idx, K, mesh=mesh,
                                   merge="tournament", micro_batches=nb)
        assert np.array_equal(np.asarray(sv), ov)
        assert np.array_equal(np.sort(np.asarray(si), 1), np.sort(oi, 1))


def test_sharded_raw_matrix_and_auto_merge(data):
    """Raw-matrix entry (prepare inline) + merge='auto' (the ICI
    cost-model crossover) must land on the same bits."""
    x, y, ov, _ = data
    mesh = _mesh(4)
    sv, _ = knn_fused_sharded(x, y, K, mesh=mesh, merge="auto",
                              passes=3, **CFG)
    assert np.array_equal(np.asarray(sv), ov)


def test_sharded_ip_metric(data):
    x, y, _, _ = data
    ov, oi = knn_fused(x, y, k=K, passes=3, metric="ip", **CFG)
    mesh = _mesh(4)
    idx = prepare_knn_index_sharded(y, mesh=mesh, metric="ip", **CFG)
    sv, si = knn_fused_sharded(x, idx, K, mesh=mesh)
    assert np.array_equal(np.asarray(sv), np.asarray(ov))
    assert np.array_equal(np.sort(np.asarray(si), 1),
                          np.sort(np.asarray(oi), 1))


def test_sharded_lite_mode_pack_tolerance(data):
    """store_yp=False (the bigger-than-HBM mode): the merged id SET
    matches the lite oracle exactly; values agree within the packed-
    code perturbation (2^(pbits−23)) — the embedded tiebreak codes are
    slot-relative, so global and per-shard orderings may swap
    near-equal candidates between positions."""
    x, y, _, _ = data
    yl = y[:4096]                      # whole groups on every shard
    ov, oi = knn_fused(x, yl, k=K, passes=1, rescore=False,
                       grid_order="db", **CFG)
    mesh = _mesh(4)
    idx = prepare_knn_index_sharded(yl, mesh=mesh, passes=1,
                                    store_yp=False, grid_order="db",
                                    **CFG)
    sv, si = knn_fused_sharded(x, idx, K, mesh=mesh)
    assert np.array_equal(np.sort(np.asarray(si), 1),
                          np.sort(np.asarray(oi), 1))
    ov = np.asarray(ov)
    tol = 4.0 * np.abs(ov).max() * 2.0 ** (idx.pbits - 23)
    np.testing.assert_allclose(np.asarray(sv), ov, atol=tol)


def test_sharded_ragged_shards_exact_values():
    """Shards with few/zero real rows (m ≪ p·rows_per): pad rows must
    never win, and the result must match a float64-oracle top-k (the
    per-shard fixup may take a different — equally exact — contraction
    than the oracle's rescore, so parity here is to the mathematical
    answer, not bit-for-bit)."""
    m, k, nq = 1100, 5, 18
    y = rng.normal(size=(m, 16)).astype(np.float32)
    x = rng.normal(size=(nq, 16)).astype(np.float32)
    mesh = _mesh(8)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    sv, si = knn_fused_sharded(x, idx, k, mesh=mesh, merge="tournament")
    d2 = ((x[:, None, :].astype(np.float64)
           - y[None, :, :].astype(np.float64)) ** 2).sum(-1)
    ref_ids = np.argsort(d2, axis=1, kind="stable")[:, :k]
    assert np.array_equal(np.sort(np.asarray(si), 1),
                          np.sort(ref_ids, 1))
    ref_vals = np.take_along_axis(d2, ref_ids, axis=1)
    np.testing.assert_allclose(np.asarray(sv), ref_vals, rtol=1e-4,
                               atol=1e-4)
    assert int(np.asarray(si).max()) < m          # no pad ids leak


def test_query_sharded_mode(data):
    """The serving shape: replicated prepared index, data-parallel
    queries, nq not divisible by p — same bits as the oracle."""
    x, y, ov, oi = data
    mesh = _mesh(8)
    qidx = prepare_knn_index(y, passes=3, **CFG)
    sv, si = knn_fused_sharded(x, qidx, K, mesh=mesh,
                               shard_mode="query")
    assert np.array_equal(np.asarray(sv), ov)
    assert np.array_equal(np.sort(np.asarray(si), 1), np.sort(oi, 1))


def test_query_sharded_raw_matrix(data):
    x, y, ov, _ = data
    mesh = _mesh(4)
    sv, _ = knn_fused_sharded(x, y, K, mesh=mesh, shard_mode="query",
                              passes=3, **CFG)
    assert np.array_equal(np.asarray(sv), ov)


# ------------------------------------------------ strategy resolution
def test_resolve_merge_strategy_downgrades_non_pow2(data):
    """A tournament request on p=3 downgrades (visibly) to allgather
    and still produces the oracle's bits."""
    x, y, ov, _ = data
    assert resolve_merge_strategy("tournament", 3, 64, 8) == "allgather"
    assert resolve_merge_strategy("tournament", 4, 64, 8) == "tournament"
    with pytest.raises(ValueError):
        resolve_merge_strategy("bogus", 4, 64, 8)
    mesh = _mesh(3)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    sv, _ = knn_fused_sharded(x, idx, K, mesh=mesh, merge="tournament")
    assert np.array_equal(np.asarray(sv), ov)


def test_choose_merge_strategy_crossover():
    """The ICI cost model must place the crossover where the wire/round
    trade-off puts it: one allgather round wins at tiny p or payload;
    log₂(p) rounds of k-blocks win when (p−1)·block wire time dominates
    the extra rounds."""
    from raft_tpu.observability.costmodel import choose_merge_strategy
    from raft_tpu.utils.arch import ChipSpec

    slow_wire = ChipSpec("t", 1e12, 1e12, 1e12, 1e9, ici_bw=1e6,
                         ici_latency=0.0)
    fast_wire = ChipSpec("t", 1e12, 1e12, 1e12, 1e9, ici_bw=1e15,
                         ici_latency=1.0)
    # wire-dominated: tournament's log2(p) blocks beat (p−1) blocks
    assert choose_merge_strategy(8, 4096, 64, slow_wire) == "tournament"
    # latency-dominated: one allgather round beats 3 serialized rounds
    assert choose_merge_strategy(8, 4096, 64, fast_wire) == "allgather"
    # non-power-of-two and tiny p can only allgather
    assert choose_merge_strategy(6, 4096, 64, slow_wire) == "allgather"
    assert choose_merge_strategy(2, 4096, 64, slow_wire) == "allgather"


def test_ici_traffic_model_bytes():
    from raft_tpu.observability.costmodel import ici_traffic_model

    ag = ici_traffic_model(8, 100, 64, "allgather")
    tr = ici_traffic_model(8, 100, 64, "tournament")
    block = 100 * 64 * 8
    assert ag["wire_bytes_per_device"] == 7 * block
    assert ag["rounds"] == 1 and ag["select_width"] == 8 * 64
    assert tr["wire_bytes_per_device"] == 3 * block
    assert tr["rounds"] == 3 and tr["select_width"] == 2 * 64
    with pytest.raises(ValueError):
        ici_traffic_model(6, 100, 64, "tournament")
    with pytest.raises(ValueError):
        ici_traffic_model(8, 100, 64, "bogus")


def test_arch_ici_peaks_present():
    """Every TPU generation entry carries an ICI peak (the busbw
    denominator of the MULTICHIP artifacts); the CPU spec's synthetic
    fabric keeps the ranking path deterministic off-TPU."""
    from raft_tpu.utils.arch import CPU_SPEC, TPU_SPECS

    for key, spec in TPU_SPECS.items():
        assert spec.ici_bw > 0, key
        assert spec.ici_latency > 0, key
    assert 0 < CPU_SPEC.ici_bw < CPU_SPEC.hbm_bw


def test_default_micro_batches_bounds():
    from raft_tpu.distance.knn_fused import _Q_CHUNK

    assert default_micro_batches(16, 256) == 1
    assert default_micro_batches(2048, 256) == 4
    # blocks never exceed the fused pipeline's query-chunk budget
    assert default_micro_batches(5 * _Q_CHUNK, 256) >= 5


# ------------------------------------------------ merge observability
def test_merge_rounds_flow_through_collective_counters(data):
    """The sharded-merge satellite: tournament rounds count under
    ``collective_permute`` (with payload bytes) and the allgather merge
    under ``allgather`` — the exporters see the merge, not silence."""
    from raft_tpu.observability import get_registry
    from raft_tpu.observability.hooks import COMMS_BYTES, COMMS_CALLS

    x, y, _, _ = data
    mesh = _mesh(2)
    # fresh k forces a fresh trace (counters fire at trace time)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    reg = get_registry()
    before = {(m.name, m.labels.get("collective")): m.value
              for m in reg.collect() if m.name == COMMS_CALLS}
    knn_fused_sharded(x, idx, 9, mesh=mesh, merge="tournament")
    knn_fused_sharded(x, idx, 10, mesh=mesh, merge="allgather")
    after = {(m.name, m.labels.get("collective")): m.value
             for m in reg.collect() if m.name in (COMMS_CALLS,
                                                  COMMS_BYTES)}
    cp = after.get((COMMS_CALLS, "collective_permute"), 0)
    ag = after.get((COMMS_CALLS, "allgather"), 0)
    assert cp > before.get((COMMS_CALLS, "collective_permute"), 0)
    assert ag > before.get((COMMS_CALLS, "allgather"), 0)
    assert after.get((COMMS_BYTES, "collective_permute"), 0) > 0


def test_device_send_counts_under_own_label():
    from jax.sharding import PartitionSpec as P

    from raft_tpu.comms import MeshComms
    from raft_tpu.observability import get_registry
    from raft_tpu.observability.hooks import COMMS_CALLS

    mesh = _mesh(2)
    comms = MeshComms("x", size=2)

    def fn(v):
        return comms.device_send(v, 1)

    out = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("x"),),
                                out_specs=P("x"), check_vma=False))(
        np.arange(8, dtype=np.float32))
    assert out.shape == (8,)
    labels = {m.labels.get("collective")
              for m in get_registry().collect()
              if m.name == COMMS_CALLS}
    assert "device_send" in labels


# ------------------------------------------------ envelopes & errors
def test_sharded_envelope_errors(data):
    x, y, _, _ = data
    mesh = _mesh(8)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    with pytest.raises(NotImplementedError):
        # per-shard pool: rows_per=768 at T=256 → 3 tiles, g=2 →
        # 2·ceil(3/2)·128 = 512 candidates < k
        knn_fused_sharded(x, idx, 520, mesh=mesh)
    with pytest.raises(Exception):
        knn_fused_sharded(x, idx, K, mesh=mesh, shard_mode="bogus")
    with pytest.raises(ValueError):
        prepare_knn_index_sharded(y, mesh=mesh, metric="cosine")
    with pytest.raises(ValueError):
        # lite index cannot serve a forced rescore
        lite = prepare_knn_index_sharded(y, mesh=mesh, passes=1,
                                         store_yp=False, **CFG)
        knn_fused_sharded(x, lite, K, mesh=mesh, rescore=True)


def test_empty_query_batch(data):
    _, y, _, _ = data
    mesh = _mesh(2)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    v, i = knn_fused_sharded(np.zeros((0, D), np.float32), idx, K,
                             mesh=mesh)
    assert v.shape == (0, K) and i.shape == (0, K)


# ------------------------------------------------ models routing
def test_nearest_neighbors_n_shards_routes_sharded(data):
    from raft_tpu import models

    x, y, ov, oi = data
    nn = models.NearestNeighbors(n_neighbors=K, n_shards=4).fit(y)
    from raft_tpu.distance.knn_sharded import ShardedFusedIndex

    assert isinstance(nn._index, ShardedFusedIndex)
    d2, ids = nn.kneighbors(x)
    # the model defaults (tuned table config) may differ from CFG —
    # parity is to the exact answer, not to the oracle's bits
    ref = knn_fused(x, y, k=K, passes=3)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(ref[0]),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.sort(np.asarray(ids), 1),
                          np.sort(np.asarray(ref[1]), 1))
    g = nn.kneighbors_graph(x)
    assert g.shape == (NQ, M)


def test_nearest_neighbors_n_shards_validation():
    from raft_tpu import models

    with pytest.raises(ValueError):
        models.NearestNeighbors(n_shards=999)


def test_nearest_neighbors_default_unchanged(data):
    """n_shards=None keeps the single-device path byte-for-byte."""
    from raft_tpu import models

    x, y, _, _ = data
    nn = models.NearestNeighbors(n_neighbors=4).fit(y)
    assert nn.n_shards is None and nn.mesh is None


# ------------------------------------------------ autotune_sharded
def test_autotune_sharded_deterministic_ranking(tmp_path):
    """The satellite acceptance: off-TPU the sharded tuner ranks by the
    deterministic model, twice identically, with schema-3 provenance
    stamped measured=false, and the loader consumes the table."""
    from raft_tpu.tune.fused import TUNE_SCHEMA_VERSION, \
        validate_tune_table
    from raft_tpu.tune.sharded import autotune_sharded

    out = tmp_path / "TUNE_SHARDED.json"
    shape = (2048, 10_000_000, 256, 64)
    tbl = autotune_sharded(shape=shape, p=8, out_path=str(out))
    assert validate_tune_table(tbl) == []
    on_disk = json.loads(out.read_text())
    assert on_disk["schema"] == TUNE_SCHEMA_VERSION
    assert on_disk["n_shards"] == 8
    prov = on_disk["provenance"]
    assert prov["measured"] is False
    assert prov["target_chip"].startswith("tpu")
    tbl2 = autotune_sharded(shape=shape, p=8, out_path=None)
    strip = lambda t: {k: v for k, v in t.items() if k != "provenance"}
    assert strip(tbl) == strip(tbl2)
    best = tbl["best"]
    assert best["merge"] in ("allgather", "tournament")
    assert best["micro_batches"] >= 1
    assert "model_ici_bytes_per_device" in best
    assert "model_busbw_frac" in best
    # prediction keys are honestly named — never written as measured
    assert not any("seconds" in r and "predicted_seconds" not in r
                   for r in tbl["rows"])


def test_sharded_candidate_space_prunes_with_reasons():
    from raft_tpu.distance.knn_fused import fit_config
    from raft_tpu.tune.sharded import _GRID_ORDER, sharded_candidate_space

    kept, skipped = sharded_candidate_space(256, 8)
    assert kept and skipped
    for c in kept:
        assert fit_config(c.T, c.Qb, 256, c.passes, c.g,
                          _GRID_ORDER, c.db_dtype) == (c.T, c.Qb)
    assert all("skipped" in row for row in skipped)
    assert "vmem_footprint" in {r["skipped"] for r in skipped}
    # non-power-of-two shard counts shed every tournament candidate
    kept6, skipped6 = sharded_candidate_space(256, 6)
    assert all(c.merge == "allgather" for c in kept6)
    assert "merge_pow2" in {r["skipped"] for r in skipped6}


def test_sharded_config_loader(tmp_path, monkeypatch):
    import raft_tpu.tune.sharded as ts
    from raft_tpu.tune.sharded import autotune_sharded

    out = tmp_path / "TUNE_SHARDED.json"
    autotune_sharded(shape=(256, 100_000, 128, 16), p=8,
                     out_path=str(out))
    monkeypatch.setenv("RAFT_TPU_TUNE_SHARDED", str(out))
    monkeypatch.setattr(ts, "_TUNED_SHARDED", ...)
    cfg = ts.sharded_config(8)
    assert cfg and cfg["merge"] in ("allgather", "tournament")
    # tuned for a different shard count → defaults
    assert ts.sharded_config(4) == {}
    # corrupt table degrades to {} instead of raising
    out.write_text("{not json")
    monkeypatch.setattr(ts, "_TUNED_SHARDED", ...)
    assert ts.sharded_config(8) == {}


def test_check_instrumented_covers_sharded_sites():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        ci = __import__("check_instrumented")
    finally:
        sys.path.pop(0)
    assert ci.check_sharded_merge() == []
    assert "raft_tpu/distance/knn_sharded.py" in ci.HOT_PATHS
    assert "raft_tpu/tune/sharded.py" in ci.COST_CAPTURE_SITES
    # a module with the merge calls stripped is a violation
    errs = ci.check_sharded_merge(
        sites={"raft_tpu/parallel/mesh.py": ("collective_permute",)})
    assert errs and "collective_permute" in errs[0]


# ------------------------------------------------ device-resident build
def _no_host_copies(mp):
    """Make the build's device-to-host copies raise. On an accelerator
    ``jax.transfer_guard_device_to_host("disallow")`` does so; on the CPU,
    where device memory is host memory and the guard never fires, the
    sharded module's numpy refuses device arrays and their host views
    raise instead."""
    from jax._src import array as jarray

    from raft_tpu.distance import knn_sharded

    def refuse(*_, **__):
        raise AssertionError("device-to-host copy in the sharded build")

    class _Numpy:
        def __getattr__(self, name):
            fn = getattr(np, name)
            if name not in ("array", "asarray", "ascontiguousarray"):
                return fn

            def guarded(a, *args, **kwargs):
                if isinstance(a, jax.Array):
                    refuse()
                return fn(a, *args, **kwargs)

            return guarded

    mp.setattr(knn_sharded, "np", _Numpy())
    mp.setattr(jarray.ArrayImpl, "_value", property(refuse))
    mp.setattr(jarray.ArrayImpl, "copy_to_host_async", refuse)


def test_host_views_refused_inside_the_guard(monkeypatch):
    """The build tests' guard bites: a build that pulls its input to
    numpy (as the host round trip did), or reads a host view of it,
    raises."""
    from raft_tpu.distance import knn_sharded

    y = jax.device_put(np.ones((8, 4), np.float32))
    with monkeypatch.context() as mp:
        _no_host_copies(mp)
        for host_copy in (lambda: knn_sharded.np.asarray(y, np.float32),
                          y.tolist, lambda: float(y[0, 0])):
            with pytest.raises(AssertionError):
                host_copy()
        assert knn_sharded.np.asarray([1.0]).sum() == 1.0
    assert np.asarray(y).sum() == 32


# rows per shard: a whole number of the config's row multiple (T=256 at
# the query-major order), or not, so that each shard pads on its device
SHARES = {"whole": 512, "padded": 300}


@pytest.mark.parametrize("share", sorted(SHARES))
@pytest.mark.parametrize("p", [2, 4, 8])
def test_build_from_row_sharded_array(p, share, monkeypatch):
    """A row-sharded device array builds with no copy to the host, and
    answers bit for bit as the same rows given as numpy, over both
    merges, and as the single-device oracle."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = p * SHARES[share]
    r = np.random.default_rng(100 * p + SHARES[share])
    y = r.normal(size=(n, D)).astype(np.float32)
    x = r.normal(size=(NQ, D)).astype(np.float32)
    mesh = _mesh(p)
    y_dev = jax.device_put(y, NamedSharding(mesh, P("x")))
    with monkeypatch.context() as mp:
        _no_host_copies(mp)
        with jax.transfer_guard_device_to_host("disallow"):
            idx = prepare_knn_index_sharded(y_dev, mesh=mesh, passes=3,
                                            **CFG)
    host = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    assert idx.rows_per == host.rows_per == 512
    assert idx.share == host.share == SHARES[share]
    assert np.array_equal(
        idx.row_positions(),
        np.add.outer(512 * np.arange(p), np.arange(SHARES[share])).ravel())
    ov, oi = knn_fused(x, y, k=K, passes=3, **CFG)
    for merge in ("allgather", "tournament"):
        dv, di = knn_fused_sharded(x, idx, K, mesh=mesh, merge=merge)
        hv, hi = knn_fused_sharded(x, host, K, mesh=mesh, merge=merge)
        assert np.array_equal(np.asarray(dv), np.asarray(hv))
        assert np.array_equal(np.asarray(di), np.asarray(hi))
        assert np.array_equal(np.asarray(dv), np.asarray(ov))
        assert np.array_equal(np.sort(np.asarray(di), 1),
                              np.sort(np.asarray(oi), 1))


@pytest.mark.parametrize("m", [17, 1001])
def test_sharded_host_tail_shards(m):
    """A host input whose rows the shard count does not divide: the last
    shards are part pads, some (m=17 over 8: shares of 3) none but pads.
    No pad id leaks and the answer is the float64 top-k."""
    k, nq = 5, 11
    r = np.random.default_rng(m)
    y = r.normal(size=(m, 16)).astype(np.float32)
    x = r.normal(size=(nq, 16)).astype(np.float32)
    mesh = _mesh(8)
    idx = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    share = -(-m // 8)
    assert idx.share == share and len(idx.row_positions()) == m
    assert np.array_equal(
        np.asarray(idx.yp_s)[idx.row_positions(), :16], y)
    sv, si = knn_fused_sharded(x, idx, k, mesh=mesh, merge="allgather")
    d2 = ((x[:, None, :].astype(np.float64)
           - y[None, :, :].astype(np.float64)) ** 2).sum(-1)
    ref_ids = np.argsort(d2, axis=1, kind="stable")[:, :k]
    assert np.array_equal(np.sort(np.asarray(si), 1), np.sort(ref_ids, 1))
    np.testing.assert_allclose(np.asarray(sv),
                               np.take_along_axis(d2, ref_ids, axis=1),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m", [17, 1001])
def test_uneven_device_array_pads_on_its_devices(m, monkeypatch):
    """A device array whose rows the shard count does not divide is
    padded and resharded on the devices, with no copy to the host, and
    builds the index the same rows as numpy build."""
    k, nq = 5, 11
    r = np.random.default_rng(m + 1)
    y = r.normal(size=(m, 16)).astype(np.float32)
    x = r.normal(size=(nq, 16)).astype(np.float32)
    mesh = _mesh(8)
    y_dev = jax.device_put(y, jax.devices()[3])
    with monkeypatch.context() as mp:
        _no_host_copies(mp)
        with jax.transfer_guard_device_to_host("disallow"):
            idx = prepare_knn_index_sharded(y_dev, mesh=mesh, passes=3,
                                            **CFG)
    host = prepare_knn_index_sharded(y, mesh=mesh, passes=3, **CFG)
    assert (idx.share, idx.rows_per, idx.n_rows) == \
        (host.share, host.rows_per, host.n_rows)
    for a, b in ((idx.yp_s, host.yp_s), (idx.yy_s, host.yy_s)):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    dv, di = knn_fused_sharded(x, idx, k, mesh=mesh, merge="allgather")
    hv, hi = knn_fused_sharded(x, host, k, mesh=mesh, merge="allgather")
    assert np.array_equal(np.asarray(dv), np.asarray(hv))
    assert np.array_equal(np.asarray(di), np.asarray(hi))


# ------------------------------------------------ against the benchmark's
# plain reference, on the benchmark's own row-sharded data
@pytest.fixture(scope="module")
def bench_reference():
    """``bench_suite.reference`` (it imports nothing of raft_tpu) with
    row blocks of 256 rows, and the bigann32m-exact4 configuration."""
    import os
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, root)
    try:
        from bench_suite import reference
    finally:
        sys.path.remove(root)
    with open(os.path.join(root, "bench_suite", "configs",
                           "bigann32m-exact4.json")) as f:
        cfg = json.load(f)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "ROW_BLOCK", 256)
        yield reference, cfg


def _compare(reference, q, base, dist, ids, k):
    """(dist_err, rank_gap) of answers as the benchmark's check reads
    them: each reported distance against its id's true distance, and the
    served ids' sorted true distances against the reference's, both
    relative to the row's exact k-th distance."""
    _, ref_ids = reference.exact_topk(q, base, k)
    t_srv = reference.true_distances(q, base, ids)
    t_ref = reference.true_distances(q, base, ref_ids)
    scale = t_ref[:, -1:]
    dist_err = float(np.max(np.abs(np.asarray(dist, np.float64) - t_srv)
                            / scale))
    rank_gap = float(np.max((np.sort(t_srv, 1) - np.sort(t_ref, 1))
                            / scale))
    return dist_err, max(rank_gap, 0.0), ref_ids


def test_sharded_search_against_the_plain_reference(bench_reference):
    """``knn_fused_sharded`` at the configuration's k over the
    benchmark's row-sharded data on four devices, against
    ``bench_suite.reference`` at f32 ``highest``.

    Tolerances, each the configuration's own limit:
    - ``dist_err`` ≤ 1.1e-5: the program's distances are f32 in the
      expanded form ‖q‖² + ‖x‖² − 2q·x, whose rounding is a few f32 ulps
      of ‖q‖² + ‖x‖², about 1e-6 of the k-th distance here; a one-pass
      bf16 cross term errs by ≈ 2⁻⁹ of q·x, thousands of times more;
    - ``rank_gap`` ≤ 3e-6: ids may differ from the reference's only where
      two true distances at the k-th place lie closer than the expanded
      form's rounding of them (the ``dist_err`` above).
    """
    reference, cfg = bench_reference
    data = dict(cfg["data"], n_rows=4096, n_pool=96, n_centers=32)
    k = int(cfg["k"])
    limits = {n: v["max"] for n, v in cfg["check"]["limits"].items()}
    base, pool = reference.make_data(2 ** 31 + 5, data, jax.devices()[:4])
    mesh, axis = base.sharding.mesh, base.sharding.spec[0]
    idx = prepare_knn_index_sharded(base, mesh=mesh, axis=axis)
    assert idx.share == 1024
    q = np.asarray(pool)
    dist, ids = knn_fused_sharded(q, idx, k, mesh=mesh, axis=axis)
    ids = np.asarray(ids)
    dist_err, rank_gap, ref_ids = _compare(reference, q, base, dist, ids, k)
    assert dist_err <= limits["dist_err"]
    assert rank_gap <= limits["rank_gap"]
    # ids equal except exact ties at the k-th place: a row whose sets
    # differ has its (k)th and (k+1)th true distances equal in f32
    differ = [r for r in range(len(q))
              if set(ids[r]) != set(ref_ids[r])]
    if differ:
        d_next, _ = reference.exact_topk(q[differ], base, k + 1)
        assert np.all(np.isclose(d_next[:, k], d_next[:, k - 1],
                                 rtol=1e-6, atol=0))
    # a one-pass bf16 cross term, below the f32 the configuration
    # states, fails the comparison
    bd, bi = reference.exact_topk(q, base, k, precision="bf16")
    b_err, b_gap, _ = _compare(reference, q, base, bd, bi, k)
    assert b_err > limits["dist_err"] or b_gap > limits["rank_gap"]
    assert b_err > 100 * dist_err
