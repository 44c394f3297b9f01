"""Explain-plane tests (ISSUE 16 tentpole, layer 1).

Pins the capture contract: deterministic hash sampling, zero-footprint
disabled mode, the bounded record ring, margin parity between the
``with_stats`` output and the ``_diag`` oracle on the brute core, and
the end-to-end record a live ``knn_query`` produces (plane, resolution
notes, per-site margin summaries, the ``raft_tpu_certificate_margin``
histogram)."""

import numpy as np
import pytest

from raft_tpu.observability import explain, span
from raft_tpu.observability.explain import (MARGIN_HISTOGRAM,
                                            RING_CAPACITY, capture,
                                            clear_records,
                                            explain_records, want)
from raft_tpu.observability.metrics import (MetricsRegistry,
                                            get_registry, set_registry)

rng = np.random.default_rng(3)


@pytest.fixture(autouse=True)
def _clean_ring():
    clear_records()
    yield
    clear_records()
    # a failed test must never leak an active capture into the next
    explain._tls.capture = None


# ------------------------------------------------------------------
# sampling
# ------------------------------------------------------------------

def test_want_deterministic_and_bounded():
    picks = [rid for rid in range(4096) if want(rid, 0.1)]
    assert picks == [rid for rid in range(4096) if want(rid, 0.1)]
    # Knuth hash ~uniform: 10% ± a generous band
    assert 200 < len(picks) < 650
    assert not any(want(rid, 0.0) for rid in range(256))
    assert all(want(rid, 1.0) for rid in range(256))
    # a rid sampled at f is sampled at every f' > f (nested samples)
    assert set(picks) <= {rid for rid in range(4096)
                          if want(rid, 0.5)}


# ------------------------------------------------------------------
# disabled mode
# ------------------------------------------------------------------

def test_disabled_hooks_are_noops():
    assert explain.active() is None
    explain.note(plane="brute")          # no capture: swallowed
    explain.note_margin("site", np.ones(4))
    # a span closing with no capture active times into nothing
    with span("ann.fine_scan"):
        pass
    assert explain.active() is None
    assert explain.end_capture(None) is None
    assert explain_records() == []


def test_no_nested_capture():
    cap = explain.begin_capture([1])
    try:
        assert cap is not None
        assert explain.begin_capture([2]) is None   # outer owns it
        assert explain.active() is cap
    finally:
        explain.end_capture(cap)
    assert explain.active() is None


# ------------------------------------------------------------------
# capture mechanics
# ------------------------------------------------------------------

def test_note_collects_repeats_and_finalize_builds_record():
    with capture(rids=[7, 8]) as scope:
        explain.note(plane="ivf_flat", n_probes=4)
        explain.note(fine_scan="list_major")      # differing repeats
        explain.note(fine_scan="query_major")     # collect into a list
        explain.note(n_probes=4)                  # equal repeat: kept
        with span("ann.coarse_probe"):       # spans time the stages
            pass
        with span("ann.coarse_probe"):       # repeats sum
            pass
        explain.note_margin("ann.search_ivf_flat",
                            np.array([0.5, -0.25, np.inf]))
    rec = scope.record
    assert rec is not None
    assert rec["rids"] == [7, 8] and rec["outcome"] == "ok"
    assert rec["plane"] == "ivf_flat" and rec["n_probes"] == 4
    assert rec["fine_scan"] == ["list_major", "query_major"]
    assert list(rec["stages"]) == ["ann.coarse_probe"]
    assert rec["stages"]["ann.coarse_probe"] >= 0.0
    m = rec["margins"]["ann.search_ivf_flat"]
    # the inf is filtered, the negative counted
    assert m["n"] == 2 and m["n_negative"] == 1
    assert m["min"] == pytest.approx(-0.25)
    assert explain_records() == [rec]


def test_capture_error_outcome():
    with pytest.raises(RuntimeError):
        with capture(rids=1) as scope:
            raise RuntimeError("boom")
    assert scope.record["outcome"] == "error"
    assert explain_records(outcome="error") == [scope.record]
    assert explain_records(outcome="ok") == []


def test_ring_is_bounded_and_newest_first():
    for i in range(RING_CAPACITY + 50):
        with capture(rids=i):
            explain.note(seq=i)
    recs = explain_records()
    assert len(recs) == RING_CAPACITY
    assert recs[0]["seq"] == RING_CAPACITY + 49      # newest first
    assert recs[-1]["seq"] == 50                      # oldest dropped
    assert explain_records(limit=3) == recs[:3]


def test_margin_histogram_observed():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        with capture(rids=1):
            explain.note_margin("site.a", np.array([-0.5, 2.0, 30.0]))
        hist = reg.histogram(
            MARGIN_HISTOGRAM, {"site": "site.a"},
            buckets=explain.MARGIN_BUCKETS)
        assert hist.count == 3
        assert hist.sum == pytest.approx(31.5)
    finally:
        set_registry(prev)


# ------------------------------------------------------------------
# margin parity vs the _diag oracle (brute core)
# ------------------------------------------------------------------

def test_with_stats_margin_matches_diag_oracle():
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import (_knn_fused_core,
                                             prepare_knn_index)

    Q, m, d, k = 64, 2048, 24, 8
    rng_t = np.random.default_rng(7)   # near-duplicate structure so
    base = rng_t.normal(size=(64, d)).astype(np.float32)
    y = base[rng_t.integers(0, 64, m)] + 3e-3 * rng_t.normal(
        size=(m, d)).astype(np.float32)
    x = base[rng_t.integers(0, 64, Q)] + 3e-3 * rng_t.normal(
        size=(Q, d)).astype(np.float32)
    idx = prepare_knn_index(y, passes=1, T=512, Qb=64, g=8)
    xp = jnp.asarray(np.pad(x, ((0, 0), (0, (-d) % 128))))
    args = dict(k=k, T=idx.T, Qb=idx.Qb, g=idx.g, passes=1,
                metric="l2", m=m, rescore=True, pbits=idx.pbits,
                certify="f32")
    _, _, n_fail, bound, theta, err = _knn_fused_core(
        xp, idx.yp, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw,
        _diag=True, **args)
    _, _, n_fail_s, margin = _knn_fused_core(
        xp, idx.yp, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw,
        with_stats=True, **args)
    ref = np.asarray(bound) - (np.asarray(theta) + np.asarray(err))
    np.testing.assert_allclose(np.asarray(margin), ref, rtol=1e-6)
    assert int(n_fail) == int(n_fail_s)
    # some queries on this adversarial set DO fail the certificate —
    # and a failed certificate is exactly a negative margin
    assert int(n_fail) > 0
    assert int((np.asarray(margin) < 0).sum()) == int(n_fail)


# ------------------------------------------------------------------
# end-to-end: a live search fills the record
# ------------------------------------------------------------------

def test_knn_query_capture_end_to_end():
    from raft_tpu.core.resources import DeviceResources
    from raft_tpu.distance.knn_fused import prepare_knn_index
    from raft_tpu.runtime.entry_points import knn_query

    y = rng.normal(size=(2048, 32)).astype(np.float32)
    x = rng.normal(size=(16, 32)).astype(np.float32)
    idx = prepare_knn_index(y, passes=3, T=256, Qb=32, g=2)
    res = DeviceResources()
    with capture(rids=42) as scope:
        knn_query(res, idx, x, 8)
    rec = scope.record
    assert rec["plane"] == "brute"
    assert rec["k"] == 8 and "db_dtype" in rec and "grid_order" in rec
    m = rec["margins"]["runtime.knn_query"]
    # margins are per real query row — pad rows sliced off
    assert m["n"] == 16


def test_uncaptured_search_leaves_no_record():
    from raft_tpu.core.resources import DeviceResources
    from raft_tpu.distance.knn_fused import prepare_knn_index
    from raft_tpu.runtime.entry_points import knn_query

    y = rng.normal(size=(2048, 32)).astype(np.float32)
    x = rng.normal(size=(8, 32)).astype(np.float32)
    idx = prepare_knn_index(y, passes=3, T=256, Qb=32, g=2)
    knn_query(DeviceResources(), idx, x, 8)
    assert explain_records() == []


# ------------------------------------------------------------------
# engine integration: frac + per-request flag
# ------------------------------------------------------------------

def test_engine_explain_flag_produces_record():
    from raft_tpu.distance.knn_fused import prepare_knn_index
    from raft_tpu.serving import ServingEngine

    y = rng.normal(size=(2048, 32)).astype(np.float32)
    idx = prepare_knn_index(y, passes=3, T=256, Qb=32, g=2)
    eng = ServingEngine(idx, k=8, buckets=(8, 16),
                        flush_interval_s=0.002, explain_frac=0.0)
    eng.start()
    try:
        # unflagged at frac=0: sampled out, no record
        eng.submit(x=rng.normal(size=(4, 32)).astype(np.float32)
                   ).result(timeout=60)
        eng.flush()
        assert explain_records() == []
        fut = eng.submit(rng.normal(size=(4, 32)).astype(np.float32),
                         explain=True)
        eng.flush()
        fut.result(timeout=60)
    finally:
        eng.stop()
    recs = explain_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["outcome"] == "ok" and rec["plane"] == "brute"
    assert rec["margins"]["runtime.knn_query"]["n"] >= 4
    # the batch's spans are its stages: the dispatch and, inside it,
    # the wait for the device
    assert {"serving.execute_batch", "serving.device_wait"} \
        <= set(rec["stages"])
    assert rec["stages"]["serving.device_wait"] \
        <= rec["stages"]["serving.execute_batch"]
    st = eng.stats()
    assert st["explain"] == {"frac": 0.0, "records": 1}
