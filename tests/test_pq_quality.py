"""PQ quality round (ISSUE 19): the adaptive per-row certificate, the
widen-and-re-ADC middle rung (rung telemetry, forced failure, the
pq_widen fault site, the widen-cap knob), the learned OPQ rotation
(orthogonality, envelope soundness on rotated/anisotropic builds, id
parity rotated-vs-unrotated, the mutable plane under the env-knob
mode), the schema-7 pq_mode tune column, and the rerun-aware chooser
(expected_pq_rerun_frac sources, choose_pq_scan pricing, the
pq_chooser_downgrade marker)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import resilience
from raft_tpu.ann import (build_ivf_pq, resolve_pq_scan,
                          search_ivf_flat, search_ivf_pq,
                          unpack_pq_codes)
from raft_tpu.ann import ivf_pq as ivf_pq_mod
from raft_tpu.observability import quality

rng = np.random.default_rng(19)


def _dup_data(G=96, g=12, d=16, sep=4.0, jitter=0.05, seed=7):
    """Duplicate-group data (test_ivf_pq's margin regime): the
    certificate has real margin, so the base rung genuinely certifies
    and the forced-failure tests exercise the LADDER, not the data."""
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
    nq = 32
    r = np.random.default_rng(3)
    Q = base[r.choice(base.shape[0], nq, replace=False)] \
        + r.normal(0, 0.02, (nq, X.shape[1])).astype(np.float32)
    idx_plain = build_ivf_pq(res, X, n_lists=96, pq_bits=8,
                             max_iter=5, seed=2)
    idx_opq = build_ivf_pq(res, X, n_lists=96, pq_bits=8, max_iter=5,
                           seed=2, pq_mode="opq", opq_iters=2)
    return res, X, Q, idx_plain, idx_opq


@pytest.fixture(autouse=True)
def _clean_planes():
    yield
    resilience.configure_faults("")
    quality.clear()


def _sets(ids):
    return [set(int(v) for v in row if v >= 0)
            for row in np.asarray(ids)]


def _rung_counts():
    """Cumulative per-rung PQ ladder counters at the ivf_pq site."""
    from raft_tpu.observability import get_registry
    from raft_tpu.observability.quality import PQ_RUNGS

    out = {"certified": 0, "widened": 0, "exact_rerun": 0}
    for mtr in get_registry().collect():
        if mtr.name != PQ_RUNGS or getattr(mtr, "labels", {}).get(
                "site") != "ann.search_ivf_pq":
            continue
        r = mtr.labels.get("rung")
        if r in out:
            out[r] += int(mtr.value)
    return out


# ------------------------------------------------- the learned rotation
def test_rotation_orthogonality(fixture):
    """The stored OPQ rotation must be orthogonal to f32 rounding —
    ‖RᵀR − I‖∞ ≤ 1e-6 (the property the norm-preservation arguments in
    the certificate ride on)."""
    _, _, _, _, idx_opq = fixture
    R = np.asarray(idx_opq.pq_rot, np.float64)
    d = R.shape[0]
    assert R.shape == (d, d)
    assert np.abs(R.T @ R - np.eye(d)).max() <= 1e-6
    # and it made it onto the shared serving layout
    lay = idx_opq.layout()
    assert lay.pq_rot is idx_opq.pq_rot
    assert lay.pq_meta["pq_mode"] == "opq"


def test_plain_build_has_no_rotation(fixture):
    _, _, _, idx_plain, _ = fixture
    assert idx_plain.pq_mode == "plain"
    assert idx_plain.pq_rot is None
    assert idx_plain.layout().pq_rot is None


@pytest.mark.parametrize("mode", ["opq", "opq_aniso"])
def test_envelope_rotated_builds(res, mode):
    """The recorded error bounds must envelope the true (f64)
    reconstruction error on ROTATED and anisotropic builds exactly as
    on plain ones — the certificate is mode-blind because these
    numbers are computed on the actual c + r̂'·Rᵀ reconstruction."""
    X = rng.normal(size=(500, 8)).astype(np.float32)
    X[:, :2] *= 30.0                      # anisotropy worth rotating
    idx = build_ivf_pq(res, X, n_lists=4, pq_bits=4, max_iter=4,
                       seed=1, pq_mode=mode, opq_iters=2)
    assert idx.pq_mode == mode
    rot = np.asarray(idx.pq_rot, np.float64)
    L = idx.n_lists
    padded = np.asarray(idx.padded_sizes)
    gid = np.repeat(np.arange(L), padded)
    slab = np.asarray(idx.slab, np.float64)
    valid = np.asarray(idx.ids) >= 0
    cents = np.asarray(idx.centroids, np.float64)
    cb = np.asarray(idx.codebooks, np.float64)
    codes = unpack_pq_codes(np.asarray(idx.codes), idx.pq_dim,
                            idx.pq_bits)
    S, dsub = idx.pq_dim, idx.dsub
    recon_rot = np.zeros_like(slab)
    for s in range(S):
        recon_rot[:, s * dsub:(s + 1) * dsub] = cb[s][codes[:, s]]
    recon = cents[gid] + recon_rot @ rot.T
    e_row = np.sqrt(np.sum((slab - recon) ** 2, axis=1))
    eq_rows = np.asarray(idx.pq_eq_rows, np.float64)
    eq_list = np.asarray(idx.pq_eq_list, np.float64)
    assert (e_row[valid] <= eq_rows[valid] + 1e-12).all()
    offs = np.asarray(idx.offsets)
    for l in range(L):
        w = int(padded[l])
        if w:
            sl = slice(int(offs[l]), int(offs[l]) + w)
            assert e_row[sl][valid[sl]].max(initial=0.0) \
                <= eq_list[l] + 1e-12


@pytest.mark.parametrize("P", [2, 5])
def test_rotated_id_parity_vs_flat(fixture, P):
    """Rotation changes the bytes the ADC orders by, never the ids
    that come back: both quantizer modes must match the flat scan over
    the same probes (same coarse seed → same probe lists)."""
    res, X, Q, idx_plain, idx_opq = fixture
    k = 6
    _, fi = search_ivf_flat(res, idx_plain, Q, k, n_probes=P,
                            fine_scan="query")
    want = _sets(fi)
    for idx in (idx_plain, idx_opq):
        _, pi = search_ivf_pq(res, idx, Q, k, n_probes=P, pq_scan="pq")
        assert _sets(pi) == want


def test_rotated_degenerate_probes_exact(fixture):
    """n_probes = n_lists on the rotated build must equal the brute
    oracle — the degenerate-exact invariant is mode-blind."""
    from raft_tpu.distance.fused_l2nn import knn

    res, X, Q, _, idx_opq = fixture
    k = 5
    _, oi = knn(res, X, Q, k)
    _, ids = search_ivf_pq(res, idx_opq, Q, k,
                           n_probes=idx_opq.n_lists)
    assert _sets(ids) == _sets(oi)


def test_env_knob_sets_mode(res, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_ANN_PQ_MODE", "opq")
    X = rng.normal(size=(400, 8)).astype(np.float32)
    idx = build_ivf_pq(res, X, n_lists=4, pq_bits=4, max_iter=3,
                       seed=0, opq_iters=1)
    assert idx.pq_mode == "opq" and idx.pq_rot is not None
    with pytest.raises(Exception):
        build_ivf_pq(res, X, n_lists=4, pq_bits=4, pq_mode="bogus")


def test_opq_train_fault_surfaces_at_build(res):
    """A failing rotation train must surface at build — never a
    silently-plain index."""
    X = rng.normal(size=(400, 8)).astype(np.float32)
    resilience.configure_faults("opq_train:error")
    try:
        with pytest.raises(Exception):
            build_ivf_pq(res, X, n_lists=4, pq_bits=4, max_iter=3,
                         seed=0, pq_mode="opq", opq_iters=1)
        # plain builds never reach the site
        idx = build_ivf_pq(res, X, n_lists=4, pq_bits=4, max_iter=3,
                           seed=0, pq_mode="plain")
    finally:
        resilience.configure_faults("")
    assert idx.pq_mode == "plain"


# ------------------------------------------------------ the widen rung
def test_widen_rung_recovers_without_exact_rerun(fixture, monkeypatch):
    """A failed BASE certificate walks the widen rung: with the first
    certify call forced false, the 2x re-ADC pool re-certifies on
    margin data — ids stay identical to the flat scan, the ladder
    telemetry records the widened queries, and NO resilience
    degradation is recorded (healthy widening is telemetry, not an
    outage — the bench refusal path depends on this)."""
    from raft_tpu.resilience.policy import degradation_count

    res, X, Q, _, idx_opq = fixture
    if not quality.quality_enabled():
        pytest.skip("quality plane disabled")
    k, P = 6, 4
    real = ivf_pq_mod._pq_certify
    calls = {"n": 0}

    def first_fails(bound, theta, widen):
        calls["n"] += 1
        return bound < bound if calls["n"] == 1 \
            else real(bound, theta, widen)

    monkeypatch.setattr(ivf_pq_mod, "_pq_certify", first_fails)
    before, deg0 = _rung_counts(), degradation_count()
    _, pi = search_ivf_pq(res, idx_opq, Q, k, n_probes=P, pq_scan="pq")
    after = _rung_counts()
    assert calls["n"] >= 2                 # the widen rung actually ran
    assert degradation_count() == deg0
    assert after["widened"] - before["widened"] > 0
    _, fi = search_ivf_flat(res, idx_opq, Q, k, n_probes=P,
                            fine_scan="query")
    assert _sets(pi) == _sets(fi)
    # the running rerun-fraction gauge reflects the tally
    m = quality.measured_rerun_frac("ann.search_ivf_pq", min_checks=1)
    assert m is not None and 0.0 <= m <= 1.0


def test_widen_disabled_goes_straight_to_exact(fixture, monkeypatch):
    """RAFT_TPU_ANN_PQ_WIDEN=1 disables the middle rung: a failed
    certificate escalates straight to the exact rerun (ids identical;
    zero widened queries recorded)."""
    res, X, Q, _, idx8 = fixture
    if not quality.quality_enabled():
        pytest.skip("quality plane disabled")
    k, P = 6, 4
    monkeypatch.setenv("RAFT_TPU_ANN_PQ_WIDEN", "1")
    monkeypatch.setattr(ivf_pq_mod, "_pq_certify",
                        lambda bound, theta, widen: bound < bound)
    before = _rung_counts()
    _, pi = search_ivf_pq(res, idx8, Q, k, n_probes=P, pq_scan="pq")
    after = _rung_counts()
    assert after["widened"] == before["widened"]
    assert after["exact_rerun"] - before["exact_rerun"] == len(Q)
    _, fi = search_ivf_flat(res, idx8, Q, k, n_probes=P,
                            fine_scan="query")
    assert _sets(pi) == _sets(fi)


def test_pq_widen_fault_degrades_to_exact(fixture, monkeypatch):
    """The pq_widen fault site: an injected error at the re-ADC
    dispatch records ONE degradation, skips the remaining rungs, and
    the exact rerun still returns identical ids."""
    from raft_tpu.resilience.policy import degradation_count

    res, X, Q, _, idx8 = fixture
    k, P = 6, 4
    monkeypatch.setattr(ivf_pq_mod, "_pq_certify",
                        lambda bound, theta, widen: bound < bound)
    deg0 = degradation_count()
    resilience.configure_faults("pq_widen:error")
    try:
        _, pi = search_ivf_pq(res, idx8, Q, k, n_probes=P,
                              pq_scan="pq")
    finally:
        resilience.configure_faults("")
    assert degradation_count() == deg0 + 1
    _, fi = search_ivf_flat(res, idx8, Q, k, n_probes=P,
                            fine_scan="query")
    assert _sets(pi) == _sets(fi)


# ------------------------------------------------- the quality ladder
def test_record_pq_rungs_and_measured_frac():
    if not quality.quality_enabled():
        pytest.skip("quality plane disabled")
    quality.clear()
    site = "ann.search_ivf_pq"
    base = _rung_counts()
    quality.record_pq_rungs(site, certified=10, widened=4,
                            exact_rerun=2)
    # below the evidence floor the measured branch abstains
    assert quality.measured_rerun_frac(site) is None
    assert quality.measured_rerun_frac(site, min_checks=1) \
        == pytest.approx(2 / 16)
    quality.record_pq_rungs(site, certified=40, widened=0,
                            exact_rerun=8)
    assert quality.measured_rerun_frac(site) == pytest.approx(10 / 64)
    counts = _rung_counts()
    assert {r: counts[r] - base[r] for r in counts} \
        == {"certified": 50, "widened": 4, "exact_rerun": 10}
    # the quality block surfaces the ladder + running fraction
    blk = quality.quality_block()
    assert blk["sites"][site]["pq_rerun_frac"] == pytest.approx(10 / 64)
    assert blk["sites"][site]["pq_rungs"]["certified"] >= 50
    quality.clear()
    assert quality.measured_rerun_frac(site, min_checks=1) is None


# ------------------------------------------------- the rerun-aware chooser
def test_choose_pq_scan_prices_reruns():
    """The PR-15 blind spot: best-case codes bytes must not win when
    the expected certificate-rerun cost erases them."""
    from raft_tpu.observability.costmodel import choose_pq_scan

    model = {"pq_stream_bytes": 1e6, "fine_stream_bytes": 32e6,
             "fine_gather_bytes": 64e6}
    assert choose_pq_scan(model) == "pq"
    assert choose_pq_scan(model, rerun_frac=0.9) == "flat"
    # the model's own key prices in the same way; an explicit override
    # wins over it
    assert choose_pq_scan(dict(model, pq_rerun_frac=0.9)) == "flat"
    assert choose_pq_scan(dict(model, pq_rerun_frac=0.9),
                          rerun_frac=0.0) == "pq"


def test_expected_rerun_frac_sources(fixture):
    """measured beats modeled beats unmodeled, in that order."""
    from raft_tpu.ann.ivf_pq import expected_pq_rerun_frac

    _, _, _, _, idx_opq = fixture
    quality.clear()
    frac, src = expected_pq_rerun_frac(idx_opq)
    assert src in ("modeled", "unmodeled")
    assert 0.0 <= frac <= 1.0
    if not quality.quality_enabled():
        return
    quality.record_pq_rungs("ann.search_ivf_pq", certified=0,
                            widened=0, exact_rerun=100)
    frac, src = expected_pq_rerun_frac(idx_opq)
    assert (frac, src) == (1.0, "measured")
    quality.clear()


def test_resolve_auto_logs_chooser_downgrade(fixture, tmp_path,
                                             monkeypatch):
    """When rerun pricing flips the model's pick pq → flat, the auto
    chooser logs the downgrade and drops a pq_chooser_downgrade
    marker (the operator-visible trace of the PR-15 blind-spot
    fix)."""
    from raft_tpu.observability import get_flight_recorder

    res, X, Q, _, idx8 = fixture
    rec = get_flight_recorder()
    if not rec.enabled:
        pytest.skip("flight recorder disabled")
    # empty tune table so the cost model decides
    path = tmp_path / "tune.json"
    path.write_text(json.dumps({"schema": 7}))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    monkeypatch.delenv("RAFT_TPU_IVF_PQ_SCAN", raising=False)
    from raft_tpu.observability import costmodel

    monkeypatch.setattr(
        costmodel, "choose_pq_scan",
        lambda model, rerun_frac=None:
            "pq" if rerun_frac == 0.0 else "flat")

    def downgrades():
        return sum(1 for e in rec.events()
                   if e.get("kind") == "marker"
                   and e.get("name") == "pq_chooser_downgrade")

    before = downgrades()
    pick = resolve_pq_scan(idx8, len(Q), 6, 4, idx8.probe_window)
    assert pick == "flat"
    assert downgrades() == before + 1


# ------------------------------------------------- schema-7 tune column
def test_tune_schema7_pq_mode_column(tmp_path, monkeypatch):
    """Mode-specific rows win; schema-6 rows (no pq_mode) match every
    mode; the writer stamps the column and still validates."""
    from raft_tpu.tune.fused import validate_tune_table
    from raft_tpu.tune.ivf import autotune_pq_scan, pq_scan_config

    tbl = {"schema": 7, "pq": [
        {"n_lists": 64, "n_probes": 3, "pq_bits": 8, "pq_mode": "opq",
         "pq_scan": "pq"},
        {"n_lists": 64, "n_probes": 3, "pq_bits": 8,
         "pq_scan": "flat"}]}
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(tbl))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(path))
    assert pq_scan_config(64, 3, 8, pq_mode="opq") == "pq"
    # other modes fall to the mode-less wildcard row
    assert pq_scan_config(64, 3, 8, pq_mode="plain") == "flat"
    assert pq_scan_config(64, 3, 8, pq_mode="opq_aniso") == "flat"
    assert pq_scan_config(64, 4, 8, pq_mode="opq") is None
    # a pure schema-6 table keeps deciding for every mode
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps({"schema": 6, "pq": [
        {"n_lists": 64, "n_probes": 3, "pq_bits": 8,
         "pq_scan": "pq"}]}))
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(legacy))
    assert pq_scan_config(64, 3, 8, pq_mode="opq_aniso") == "pq"
    # the offline writer stamps the mode column and validates
    rows = autotune_pq_scan(shape=(64, 4096, 16, 8), lists=(16,),
                            pq_mode="opq")
    assert rows and all(r["pq_mode"] == "opq" for r in rows)
    assert not validate_tune_table({"schema": 7, "pq": rows})


# ------------------------------------------------- mutable-plane parity
def test_mutable_plane_under_rotated_mode(res, monkeypatch):
    """The mutable plane builds through the env-knob mode: deletes on
    a ROTATED PQ base mask the codes slab without a repack and never
    resurface tombstoned rows."""
    from raft_tpu.mutable import MutableIndex, apply_delete, search_view

    monkeypatch.setenv("RAFT_TPU_ANN_PQ_MODE", "opq")
    _, X = _dup_data(G=48, g=8, d=16, seed=13)
    r = np.random.default_rng(5)
    Q = X[r.choice(X.shape[0], 16, replace=False)] \
        + r.normal(0, 0.02, (16, X.shape[1])).astype(np.float32)
    k = 6
    mi = MutableIndex(np.asarray(X), algorithm="ivf_pq", n_lists=48,
                      n_probes=4, pq_bits=4, res=res,
                      auto_compact=False, compact_threshold=10_000)
    base = mi._plane.index
    assert base.pq_mode == "opq" and base.pq_rot is not None
    _, i0 = search_view(mi, Q, k, n_probes=4)
    victims = sorted({int(v) for v in np.asarray(i0)[:, 0] if v >= 0})
    assert victims
    assert apply_delete(mi, victims) == len(victims)
    _, i1 = search_view(mi, Q, k, n_probes=4)
    survivors = {int(v) for row in np.asarray(i1) for v in row}
    assert not (set(victims) & survivors)


def test_check_ann_diffuse_rerun_gates(tmp_path):
    """The diffuse-rerun gate: ceiling violation REGRESSES, a >0.05
    absolute rise vs the previous comparable round REGRESSES, and
    pre-ISSUE-19 artifacts (no diffuse points) skip the gate."""
    import tools.bench_report as br

    def ann_rec(rerun, recall=0.97):
        return {"ok": True, "k": 10, "recall_floor": 0.95,
                "frontier": [{"recall_at_k": 0.99, "n_probes": 8}],
                "degenerate_exact": True,
                "pq": {"ok": True, "frontier": [
                    {"dist": "diffuse", "recall_at_k": recall,
                     "cert_rerun_frac": rerun},
                    {"dist": "clustered", "recall_at_k": 0.99,
                     "cert_rerun_frac": 0.9}]}}

    good = ann_rec(0.04)
    status, msg = br.check_ann([(1, "a", good)])
    assert status == br.PASS and "diffuse rerun 0.04" in msg
    # ceiling violation
    status, msg = br.check_ann([(1, "a", ann_rec(0.2))])
    assert status == br.REGRESS and "DIFFUSE RERUN" in msg
    # no diffuse point at the floor
    status, msg = br.check_ann([(1, "a", ann_rec(0.04, recall=0.5))])
    assert status == br.REGRESS and "DIFFUSE RECALL" in msg
    # trend: a > PQ_RERUN_SLACK absolute rise regresses
    prev = ann_rec(0.01)
    worse = ann_rec(0.09)
    status, msg = br.check_ann([(1, "a", prev), (2, "b", worse)])
    assert status == br.REGRESS and "TREND" in msg
    status, _ = br.check_ann([(1, "a", prev), (2, "b", ann_rec(0.05))])
    assert status == br.PASS
    # a pre-ISSUE-19 artifact (no diffuse points) skips the gate
    old = ann_rec(0.9)
    old["pq"]["frontier"] = [p for p in old["pq"]["frontier"]
                             if p["dist"] != "diffuse"]
    status, _ = br.check_ann([(1, "a", old)])
    assert status == br.PASS
