"""IVF-PQ compressed tier (ISSUE 15): codebook training + packed
codes, the list-major ADC scan with the in-VMEM lookup table, the
mandatory certified f32 rescore (recall floor, id parity vs the flat
scan / exact oracle, certificate-failure rerun, the pq_scan
degradation rung), the per-subspace error-envelope property tests
(the bound the certificate rides), the resolve_pq_scan chooser + the
schema-6 pq tune column, the serving snapshot plane, and the
mutable-plane tombstone masking on the codes slab."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import resilience
from raft_tpu.ann import (IvfPqIndex, build_ivf_pq, pack_pq_codes,
                          resolve_pq_scan, search_ivf_flat,
                          search_ivf_pq, unpack_pq_codes, warm_pq_scan)
from raft_tpu.ann import ivf_pq as ivf_pq_mod
from raft_tpu.random import make_blobs

rng = np.random.default_rng(41)


def _dup_data(G=96, g=12, d=16, sep=4.0, jitter=0.05, seed=7):
    """Duplicate-group data — the near-dup serving regime where the
    completeness certificate has real margin: G well-separated base
    points, each repeated g times with tiny jitter."""
    r = np.random.default_rng(seed)
    base = r.normal(0, sep, (G, d)).astype(np.float32)
    X = (np.repeat(base, g, axis=0)
         + r.normal(0, jitter, (G * g, d))).astype(np.float32)
    X = X[r.permutation(G * g)]
    return base, X


@pytest.fixture(scope="module")
def fixture():
    from raft_tpu.core import DeviceResources

    res = DeviceResources(seed=5)
    base, X = _dup_data()
    nq = 40
    r = np.random.default_rng(3)
    Q = base[r.choice(base.shape[0], nq, replace=False)] \
        + r.normal(0, 0.02, (nq, X.shape[1])).astype(np.float32)
    idx4 = build_ivf_pq(res, X, n_lists=96, pq_bits=4, max_iter=5,
                        seed=2)
    idx8 = build_ivf_pq(res, X, n_lists=96, pq_bits=8, max_iter=5,
                        seed=2)
    return res, X, Q, idx4, idx8


@pytest.fixture(autouse=True)
def _no_faults():
    yield
    resilience.configure_faults("")


def _sets(ids):
    return [set(int(v) for v in row if v >= 0)
            for row in np.asarray(ids)]


def _oracle(res, X, Q, k):
    from raft_tpu.distance.fused_l2nn import knn

    _, oi = knn(res, X, Q, k)
    return _sets(oi)


# --------------------------------------------------------- build shape
def test_build_shapes_and_packing(fixture):
    _, X, _, idx4, idx8 = fixture
    R = idx8.slab_rows
    assert idx8.codes.shape == (R, idx8.pq_dim)
    assert idx4.codes.shape == (R, idx4.pq_dim // 2)
    assert idx8.yy_pq.shape == (R, 1)
    assert idx8.pq_eq_sub.shape == (idx8.pq_dim,)
    assert idx8.codebooks.shape == (idx8.pq_dim, 256, idx8.dsub)
    assert idx4.codebooks.shape == (idx4.pq_dim, 16, idx4.dsub)
    # the shared layout carries the PQ sidecar alongside the f32 slab
    lay = idx8.layout()
    assert lay.pq_codes is idx8.codes
    assert lay.pq_meta["pq_bits"] == 8


def test_pack_unpack_roundtrip():
    codes = rng.integers(0, 256, (40, 8))
    assert (unpack_pq_codes(pack_pq_codes(codes, 8), 8, 8)
            == codes).all()
    codes4 = rng.integers(0, 16, (40, 8))
    assert (unpack_pq_codes(pack_pq_codes(codes4, 4), 8, 4)
            == codes4).all()


def test_build_validation(res):
    X = rng.normal(size=(64, 8)).astype(np.float32)
    with pytest.raises(Exception):
        build_ivf_pq(res, X, n_lists=4, pq_bits=5)
    with pytest.raises(Exception):
        build_ivf_pq(res, X, n_lists=4, pq_dim=3)   # 3 does not divide 8
    with pytest.raises(Exception):
        # 64 rows < 2^8 codewords
        build_ivf_pq(res, X, n_lists=4, pq_bits=8)


# ------------------------------------------- recall floor + monotonic
def test_recall_floor_and_monotonicity(fixture):
    res, X, Q, idx4, idx8 = fixture
    k = 8
    oracle = _oracle(res, X, Q, k)

    def recall(idx, P):
        _, ids = search_ivf_pq(res, idx, Q, k, n_probes=P)
        s = _sets(ids)
        return float(np.mean([len(oracle[q] & s[q]) / k
                              for q in range(len(oracle))]))

    r4 = [recall(idx4, P) for P in (1, 4, 16)]
    r8 = [recall(idx8, P) for P in (1, 4, 16)]
    # monotone (non-strict) in n_probes for both code widths
    assert r4 == sorted(r4)
    assert r8 == sorted(r8)
    # the certified rescore makes post-rescore recall probe-determined,
    # so 8-bit ≥ 4-bit holds (equality is the certified outcome)
    for a, b in zip(r8, r4):
        assert a >= b - 1e-9
    assert r8[-1] >= 0.95
    assert r4[-1] >= 0.95


# --------------------------------------------- id parity after rescore
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("P", [2, 5])
def test_id_parity_vs_flat_scan(fixture, bits, P):
    """The certified rescore pins the PQ id sets to the flat scan's
    over the same probe lists — compression must never change WHICH
    rows come back, only how few bytes finding them streamed."""
    res, X, Q, idx4, idx8 = fixture
    idx = idx4 if bits == 4 else idx8
    k = 6
    _, pi = search_ivf_pq(res, idx, Q, k, n_probes=P, pq_scan="pq")
    _, fi = search_ivf_flat(res, idx, Q, k, n_probes=P,
                            fine_scan="query")
    assert _sets(pi) == _sets(fi)


def test_certificate_passes_on_margin_data(fixture):
    """On the duplicate-group regime the completeness certificate must
    actually certify (not silently rerun every chunk — the tier's
    bytes win depends on it)."""
    from raft_tpu.ann.ivf_pq import pq_scan_chunk
    from raft_tpu.ann.ivf_flat import _coarse_probe

    res, X, Q, _, idx8 = fixture
    P, k = 4, 6
    probes = _coarse_probe(res, idx8.centroids, jnp.asarray(Q), P)
    st = jnp.take(idx8.offsets[:-1], probes)
    ps = jnp.take(idx8.padded_sizes, probes)
    _, _, ok, margin = pq_scan_chunk(
        idx8, jnp.asarray(Q), np.asarray(probes), probes, st, ps,
        k, P, idx8.probe_window)
    assert float(jnp.mean(ok.astype(jnp.float32))) >= 0.9
    # the margin output agrees sign-for-sign with the certificate
    assert bool(jnp.all((margin >= 0) == ok))


def test_exact_oracle_parity_at_degenerate(fixture):
    res, X, Q, idx4, _ = fixture
    k = 5
    oracle = _oracle(res, X, Q, k)
    _, ids = search_ivf_pq(res, idx4, Q, k, n_probes=idx4.n_lists)
    assert _sets(ids) == oracle


def test_degenerate_fallback_k_over_capacity(fixture):
    """k beyond the probed capacity degrades to certified-exact."""
    res, X, Q, _, idx8 = fixture
    W = idx8.probe_window
    k = W + 1                      # over one probe's capacity
    oracle = _oracle(res, X, Q, k)
    _, ids = search_ivf_pq(res, idx8, Q[:8], k, n_probes=1)
    assert _sets(ids) == oracle[:8] or all(
        s == o for s, o in zip(_sets(ids), oracle[:8]))


# ------------------------------------------- certificate failure path
def test_certificate_failure_reruns_identical_ids(fixture, monkeypatch):
    """A failed completeness certificate must rerun the exact f32 scan
    — forced total failure returns ids identical to the flat oracle."""
    res, X, Q, _, idx8 = fixture
    k, P = 6, 4
    monkeypatch.setattr(ivf_pq_mod, "_pq_certify",
                        lambda bound, theta, widen: bound < bound)
    _, pi = search_ivf_pq(res, idx8, Q, k, n_probes=P, pq_scan="pq")
    _, fi = search_ivf_flat(res, idx8, Q, k, n_probes=P,
                            fine_scan="query")
    assert _sets(pi) == _sets(fi)


def test_pq_scan_fault_degrades_to_flat(fixture):
    """The pq_scan fault site: an injected error at the ADC dispatch
    records a degradation and returns the flat scan's ids — the rung
    never surfaces to the caller."""
    from raft_tpu.resilience.policy import degradation_count

    res, X, Q, _, idx8 = fixture
    k, P = 6, 4
    _, fi = search_ivf_flat(res, idx8, Q, k, n_probes=P,
                            fine_scan="query")
    before = degradation_count()
    resilience.configure_faults("pq_scan:error")
    try:
        _, pi = search_ivf_pq(res, idx8, Q, k, n_probes=P,
                              pq_scan="pq")
    finally:
        resilience.configure_faults("")
    assert degradation_count() == before + 1
    assert _sets(pi) == _sets(fi)


def test_pq_scan_kernel_error_propagates(fixture, monkeypatch):
    """Only injected/classified device failures degrade: an error the
    ADC path raises for any other reason (a kernel the compiler
    refuses) reaches the caller, and no degradation is recorded."""
    from raft_tpu.resilience.policy import degradation_count

    res, X, Q, _, idx8 = fixture

    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ivf_pq_mod, "_search_pq", refused)
    before = degradation_count()
    with pytest.raises(RuntimeError, match="Mosaic"):
        search_ivf_pq(res, idx8, Q, 6, n_probes=4, pq_scan="pq")
    assert degradation_count() == before


# ----------------------------------------------- error envelope tests
class TestPqErrorEnvelope:
    """The recorded per-subspace bounds must ENVELOPE every encoded
    row's true (f64) round-trip error — the certificate is only as
    sound as these numbers (the PR-9 Eq property tests generalized to
    codebook residual norms)."""

    def _check_envelope(self, res, X, n_lists=8, pq_bits=4, **kw):
        idx = build_ivf_pq(res, X, n_lists=n_lists, pq_bits=pq_bits,
                           max_iter=4, seed=1, **kw)
        L = idx.n_lists
        padded = np.asarray(idx.padded_sizes)
        gid = np.repeat(np.arange(L), padded)
        slab = np.asarray(idx.slab, np.float64)
        ids = np.asarray(idx.ids)
        valid = ids >= 0
        cents = np.asarray(idx.centroids, np.float64)
        cb = np.asarray(idx.codebooks, np.float64)
        codes = unpack_pq_codes(np.asarray(idx.codes), idx.pq_dim,
                                idx.pq_bits)
        S, dsub = idx.pq_dim, idx.dsub
        recon = cents[gid].copy()
        for s in range(S):
            recon[:, s * dsub:(s + 1) * dsub] += cb[s][codes[:, s]]
        err = slab - recon
        e_sub = np.sqrt(
            np.sum(err.reshape(-1, S, dsub) ** 2, axis=2))
        e_row = np.sqrt(np.sum(err ** 2, axis=1))
        eq_sub = np.asarray(idx.pq_eq_sub, np.float64)
        eq_rows = np.asarray(idx.pq_eq_rows, np.float64)
        eq_list = np.asarray(idx.pq_eq_list, np.float64)
        # per-subspace: every valid row's true subspace error ≤ bound
        for s in range(S):
            assert e_sub[valid, s].max(initial=0.0) <= eq_sub[s] + 1e-12
        # per-row and per-list roll-ups envelope too
        assert (e_row[valid] <= eq_rows[valid] + 1e-12).all()
        for l in range(L):
            w = int(padded[l])
            if w:
                sl = slice(int(np.asarray(idx.offsets)[l]),
                           int(np.asarray(idx.offsets)[l]) + w)
                assert e_row[sl][valid[sl]].max(initial=0.0) \
                    <= eq_list[l] + 1e-12
        # the row bound is itself enveloped by the subspace roll-up
        # (√2 covers the additive headroom's triangle inequality)
        assert (eq_rows[valid]
                <= np.sqrt(2.0) * np.sqrt(np.sum(eq_sub ** 2))
                + 1e-9).all()

    def test_envelope_blobs(self, res):
        X, _ = make_blobs(res, 9, 600, 8, n_clusters=6)
        self._check_envelope(res, np.asarray(X, np.float32))

    def test_envelope_mixed_magnitude(self, res):
        """Subspaces at wildly different scales — one huge, one tiny —
        attack the shared-f32 norm arithmetic."""
        X = rng.normal(size=(400, 8)).astype(np.float32)
        X[:, :2] *= 1e4
        X[:, 2:4] *= 1e-4
        self._check_envelope(res, X, n_lists=4)

    def test_envelope_tiny_inputs(self, res):
        X = (rng.normal(size=(300, 8)) * 1e-20).astype(np.float32)
        self._check_envelope(res, X, n_lists=2)

    def test_envelope_boundary_codewords(self, res):
        """Rows sitting exactly ON codeword boundaries (duplicated
        half-way points) — the assignment may tie-break either way and
        the bound must still hold."""
        base = rng.normal(size=(32, 8)).astype(np.float32)
        mid = (base[:16] + base[16:]) / 2.0
        X = np.concatenate([base, mid, mid])
        self._check_envelope(res, X, n_lists=2)

    def test_envelope_8bit(self, res):
        X = rng.normal(size=(600, 8)).astype(np.float32) * 3.0
        self._check_envelope(res, X, n_lists=4, pq_bits=8)


# ------------------------------------------------------- the chooser
def test_resolve_validation(fixture):
    res, X, Q, _, idx8 = fixture
    with pytest.raises(ValueError):
        resolve_pq_scan(idx8, 8, 4, 2, idx8.probe_window, "bogus")
    assert resolve_pq_scan(idx8, 8, 4, 2, idx8.probe_window,
                           "flat") == "flat"


def test_resolve_envelope_downgrades(fixture):
    res, X, Q, _, idx8 = fixture
    W = idx8.probe_window
    # k over the pool → flat even when pq is requested
    assert resolve_pq_scan(idx8, 8, 97, 2, W, "pq") == "flat"
    # probe table over 128 lanes → flat
    assert resolve_pq_scan(idx8, 8, 4, 129, W, "pq") == "flat"


def test_resolve_env_knob(fixture, monkeypatch):
    res, X, Q, _, idx8 = fixture
    monkeypatch.setenv("RAFT_TPU_IVF_PQ_SCAN", "flat")
    assert resolve_pq_scan(idx8, 8, 4, 2, idx8.probe_window) == "flat"


def test_auto_uses_tuned_pq_column(fixture, tmp_path, monkeypatch):
    """Schema-6 pq column: an exact-geometry row decides; absent
    column (committed back-compat) falls to the cost model."""
    from raft_tpu.tune.ivf import pq_scan_config

    res, X, Q, _, idx8 = fixture
    tbl = {"schema": 6, "pq": [
        {"n_lists": idx8.n_lists, "n_probes": 3, "pq_bits": 8,
         "pq_scan": "pq"}]}
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(tbl))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    assert pq_scan_config(idx8.n_lists, 3, 8) == "pq"
    assert pq_scan_config(idx8.n_lists, 3, 4) is None
    # schema-5 table without the column → None (cost model decides)
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps({"schema": 5, "fine_scan": []}))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(legacy))
    assert pq_scan_config(idx8.n_lists, 3, 8) is None


def test_tune_pq_rows_validate():
    """autotune_pq_scan rows validate under the schema-6 writer
    contract and rank deterministically off-TPU."""
    from raft_tpu.tune.fused import validate_tune_table
    from raft_tpu.tune.ivf import autotune_pq_scan

    rows = autotune_pq_scan(shape=(64, 4096, 16, 8), lists=(16,))
    assert rows and all(r["pq_scan"] in ("pq", "flat") for r in rows)
    assert not validate_tune_table({"schema": 6, "pq": rows})
    assert validate_tune_table(
        {"schema": 6, "pq": [{"n_lists": 1}]})   # malformed row


def test_costmodel_pq_keys():
    from raft_tpu.observability.costmodel import (ivf_traffic_model,
                                                  pq_bytes_ratio,
                                                  pq_index_bytes)

    # a slab-stream-dominated regime (10M rows): the codes stream must
    # beat the f32 stream; at tiny scale the shared pool rescore
    # dominates both and the chooser rightly stays flat
    model = ivf_traffic_model(256, 10_000_000, 128, 10, 1024, 8,
                              9768, 10_002_432, pq_dim=32, pq_bits=8)
    assert model["pq_bytes_ratio"] == pytest.approx(1.0 / 16.0)
    assert model["pq_stream_bytes"] < model["fine_stream_bytes"]
    assert pq_bytes_ratio(128, 32, 4) == pytest.approx(1.0 / 32.0)
    # the 100M-row acceptance point: codes+sidecar+coarse+codebooks
    # fit one v5e HBM with the f32 slab > 3 chips' worth
    from raft_tpu.utils.arch import TPU_SPECS

    scale = pq_index_bytes(100_000_000, 128, 50_000, 32, 8)
    assert scale["total_bytes"] <= TPU_SPECS[(5, "e")].hbm_bytes
    assert scale["f32_slab_bytes"] > TPU_SPECS[(5, "e")].hbm_bytes


# ------------------------------------------------------- serving plane
def test_serving_snapshot_swap(fixture):
    """The engine serves the PQ plane behind the same bucket ladder:
    warmup compiles every rung, queries match the flat scan, and a
    background update_index swap changes the served generation without
    breaking parity."""
    from raft_tpu.serving import ServingEngine

    res, X, Q, _, _ = fixture
    k = 5
    eng = ServingEngine(np.asarray(X), k=k, algorithm="ivf_pq",
                        n_lists=96, n_probes=4, pq_bits=8,
                        buckets=(16,), res=res)
    eng.start()
    try:
        out = eng.submit(Q[:16]).result(timeout=60)
        assert out[1].shape == (16, k)
        snap0 = eng._store.current()
        assert isinstance(snap0.index, IvfPqIndex)
        _, fi = search_ivf_pq(res, snap0.index, Q[:16], k, n_probes=4)
        assert _sets(out[1]) == _sets(fi)
        # rebuild-and-swap: new rows, new generation, engine keeps
        # serving and the snapshot type stays PQ
        base2, X2 = _dup_data(seed=11)
        eng.update_index(X2)
        eng._store.wait_for_builds(timeout=120)
        snap1 = eng._store.current()
        assert snap1.generation > snap0.generation
        assert isinstance(snap1.index, IvfPqIndex)
        out2 = eng.submit(np.asarray(X2[:8])).result(timeout=60)
        assert out2[1].shape == (8, k)
    finally:
        eng.stop()


def test_warm_pq_scan_smoke(fixture):
    res, X, Q, _, idx8 = fixture
    rungs = warm_pq_scan(res, idx8, 16, 5, 4)
    assert rungs >= 0


# ------------------------------------------------- mutable tombstones
def test_mutable_tombstone_masking_on_codes_slab(fixture):
    """Deletes on a PQ base mask the CODES slab without a repack: the
    ADC scan must never resurface a tombstoned row, and the surviving
    ids must match a from-scratch rebuild over the live rows."""
    from raft_tpu.mutable import MutableIndex, apply_delete, search_view

    res, X, Q, _, _ = fixture
    k = 6
    mi = MutableIndex(np.asarray(X), algorithm="ivf_pq", n_lists=96,
                      n_probes=4, pq_bits=8, res=res,
                      auto_compact=False, compact_threshold=10_000)
    v0, i0 = search_view(mi, Q, k, n_probes=4)
    victims = sorted({int(v) for v in np.asarray(i0)[:, 0] if v >= 0})
    assert victims
    found = apply_delete(mi, victims)
    assert found == len(victims)
    v1, i1 = search_view(mi, Q, k, n_probes=4)
    survivors = {int(v) for row in np.asarray(i1) for v in row}
    assert not (set(victims) & survivors)
    # parity vs the from-scratch oracle over the live rows (the brute
    # knn; ids compared tie-tolerantly — near-duplicate data carries
    # exact-value ties the two exact pipelines may order differently)
    from raft_tpu.distance.fused_l2nn import knn

    live = np.asarray(
        [i for i in range(X.shape[0]) if i not in set(victims)])
    ov, oi = knn(res, X[live], Q, k + 2)
    ov, oi = np.asarray(ov), np.asarray(oi)
    ev, ei = search_view(mi, Q, k, exact=True)
    ev, ei = np.asarray(ev), np.asarray(ei)
    np.testing.assert_allclose(ev, ov[:, :k], rtol=1e-3, atol=1e-3)
    for q in range(ei.shape[0]):
        wide = {int(live[oi[q, j]]) for j in range(k + 2)
                if ov[q, j] <= ov[q, k - 1] + 1e-3}
        assert {int(v) for v in ei[q]} <= wide


# ------------------------------------------------------ models wrapper
def test_nearest_neighbors_wrapper(fixture):
    from raft_tpu.models import NearestNeighbors

    res, X, Q, _, _ = fixture
    nn = NearestNeighbors(n_neighbors=5, algorithm="ivf_pq",
                          n_lists=96, n_probes=96, pq_bits=4, res=res)
    nn.fit(X)
    d0, i0 = nn.kneighbors(Q[:8])
    oracle = _oracle(res, X, Q[:8], 5)
    assert _sets(i0) == oracle
    with pytest.raises(ValueError):
        NearestNeighbors(algorithm="ivf_pq", n_shards=2)
    with pytest.raises(ValueError):
        NearestNeighbors(algorithm="ivf_pq", metric="cosine")
