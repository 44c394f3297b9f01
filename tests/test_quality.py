"""Quality-of-results telemetry (ISSUE 10).

The tentpole's acceptance surface: deterministic certificate/fixup
counters under a forced-failure construction, online recall
shadow-sampling parity vs the offline oracle, per-request flow-event
well-formedness (every ``s`` has exactly one ``f``; shed/expired flows
terminate with the right annotation), the shared interpolating
``percentile()`` (pinned equal between ``observability.metrics`` and
the import-free ``tools/bench_report.py`` mirror), the statusz
snapshot, and the new static + artifact gates
(``check_instrumented.QUALITY_SITES``, ``bench_report`` [quality]).
"""

import collections
import os
import sys
import threading
import time

import numpy as np
import pytest

from raft_tpu import observability as obs
from raft_tpu import resilience
from raft_tpu.core import interruptible
from raft_tpu.observability import quality
from raft_tpu.observability.flight import (FlightRecorder,
                                           set_flight_recorder)
from raft_tpu.observability.metrics import (Histogram, MetricsRegistry,
                                            percentile, set_registry)
from raft_tpu.observability.quality import (ShadowSampler,
                                            fixup_tier_for,
                                            quality_block, recall_at_k,
                                            record_certificate)

rng = np.random.default_rng(11)


def _tools_import(name):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def clean_world():
    """Fresh registry + recorder per test; pending quality records
    cleared both ways so cross-test telemetry cannot leak."""
    prev_reg = set_registry(MetricsRegistry())
    prev_rec = set_flight_recorder(FlightRecorder(capacity=4096))
    quality.clear()
    resilience.clear_faults()
    yield
    resilience.clear_faults()
    interruptible.yield_no_throw()
    quality.clear()
    set_registry(prev_reg)
    set_flight_recorder(prev_rec)


# ------------------------------------------------------------------
# the shared percentile helper
# ------------------------------------------------------------------

def test_percentile_matches_numpy():
    vals = rng.normal(size=257).tolist()
    for q in (0, 1, 25, 50, 75, 90, 99, 100):
        assert percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-12)


def test_percentile_edges():
    assert percentile([3.0], 99) == 3.0
    assert percentile([1.0, 2.0], 50) == 1.5
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 150)


def test_percentile_pinned_equal_with_bench_report():
    """The import-free mirror in tools/bench_report.py must compute
    bit-identical values — the satellite's 'pinned equal by a test'."""
    br = _tools_import("bench_report")
    for n in (1, 2, 7, 100, 333):
        vals = rng.normal(size=n).tolist()
        for q in (0, 10, 50, 90, 99, 100):
            assert br.percentile(vals, q) == percentile(vals, q)


def test_percentile_replaces_index_pick():
    """The old min(len−1, int(n·0.99)) pick reported the MAX for
    n < 100; the interpolated p99 must not."""
    vals = list(range(50))   # old pick: vals[49] = 49
    assert percentile(vals, 99) < 49


def test_histogram_percentile_estimates():
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    assert h.percentile(50) is None
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 2.0          # rank 2 falls in the (1, 2] bucket
    assert h.percentile(100) == 4.0
    h.observe(100.0)                   # +Inf bucket clamps to last bound
    assert h.percentile(100) == 4.0


def test_summary_table_has_percentile_columns():
    reg = MetricsRegistry()
    hist = reg.histogram("t_seconds", buckets=(0.1, 1.0))
    hist.observe(0.05)
    hist.observe(0.5)
    out = obs.summary_table(reg)
    assert "p50=" in out and "p99=" in out


# ------------------------------------------------------------------
# certificate / fixup counters
# ------------------------------------------------------------------

def test_fixup_tier_mirror():
    tiers = (16, 128, 512, 1024)
    assert fixup_tier_for(0, tiers, 2048) == 0
    assert fixup_tier_for(3, tiers, 2048) == 16
    assert fixup_tier_for(16, tiers, 2048) == 16
    assert fixup_tier_for(17, tiers, 2048) == 128
    assert fixup_tier_for(600, tiers, 2048) == 1024
    assert fixup_tier_for(1500, tiers, 2048) == 2048  # full fallback
    assert fixup_tier_for(5, (), 640) == 640          # empty ladder


def _forced_failure_problem():
    """Clustered near-duplicates under certify='f32' (the adaptive
    margin): a construction measured to fail the certificate for >128
    queries — the docstring's three-true-neighbors-per-group failure
    mode driven hard (same pinned rng as test_adaptive_deep_fixup_tier,
    so the count is deterministic and in the 512-tier band)."""
    Q, m, d, k = 640, 2048, 24, 8
    rng_t = np.random.default_rng(7)
    base = rng_t.normal(size=(64, d)).astype(np.float32)
    y = base[rng_t.integers(0, 64, m)] + 3e-3 * rng_t.normal(
        size=(m, d)).astype(np.float32)
    x = base[rng_t.integers(0, 64, Q)] + 3e-3 * rng_t.normal(
        size=(Q, d)).astype(np.float32)
    return x, y, k


def test_forced_failure_fixup_counter_exact():
    """The acceptance criterion: a forced-certificate-failure run shows
    a NONZERO raft_tpu_certificate_fixups_total with exactly the count
    the _diag oracle reports, and the fixup-rows histogram saw the
    tier that absorbed it."""
    import jax.numpy as jnp

    from raft_tpu.distance.knn_fused import (_knn_fused_core, knn_fused,
                                             prepare_knn_index)

    x, y, k = _forced_failure_problem()
    d = x.shape[1]
    idx = prepare_knn_index(y, passes=1, T=512, Qb=64, g=8)
    xp = jnp.asarray(np.pad(x, ((0, 0), (0, (-d) % 128))))
    _, _, expected, *_ = _knn_fused_core(
        xp, idx.yp, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw,
        k=k, T=idx.T, Qb=idx.Qb, g=idx.g, passes=1, metric="l2",
        m=y.shape[0], rescore=True, pbits=idx.pbits, certify="f32",
        _diag=True)
    expected = int(expected)
    assert expected > 0

    knn_fused(x, idx, k=k, certify="f32")
    assert quality.pending_count() >= 1
    assert quality.drain() >= 1
    reg = obs.get_registry()
    fixups = reg.counter(quality.CERT_FIXUPS,
                         {"site": "distance.knn_fused"})
    checks = reg.counter(quality.CERT_CHECKS,
                         {"site": "distance.knn_fused"})
    assert fixups.value == expected
    assert checks.value == x.shape[0]
    hist = reg.histogram(quality.FIXUP_ROWS,
                         {"site": "distance.knn_fused"},
                         buckets=quality.COUNT_BUCKETS)
    assert hist.count == 1
    assert hist.sum == fixup_tier_for(expected, (16, 128, 512, 1024),
                                      x.shape[0])
    # a nonzero failure batch also lands on the flight timeline
    ev = [e for e in obs.get_flight_recorder().events()
          if e["kind"] == "quality"]
    assert ev and ev[-1]["n_fail"] == expected


def test_fixup_overflow_counter(monkeypatch):
    """raft_tpu_cert_fixup_overflow_total counts the fixups whose select
    overflowed the threshold block filter: 0 on an ordinary forced
    fixup, 1 where a query ties one row's 40 copies, spread over more
    blocks than the filter keeps (blocks of 128 columns here, so the
    3,072-row tile takes the filter)."""
    from raft_tpu.distance import knn_fused as kf

    monkeypatch.setattr(kf, "_FIXUP_BLOCK", 128)
    kf._knn_fused_core.clear_cache()
    try:
        r = np.random.default_rng(11)
        m, d, k = 3000, 32, 8
        base = r.normal(size=(40, d)).astype(np.float32)
        y = base[r.integers(0, 40, m)] + 3e-2 * r.normal(
            size=(m, d)).astype(np.float32)
        x = base[r.integers(0, 40, 32)] + 3e-2 * r.normal(
            size=(32, d)).astype(np.float32)
        idx = kf.prepare_knn_index(y, passes=3, T=512, Qb=16, g=8)
        site = {"site": "distance.knn_fused"}
        reg = obs.get_registry()
        kf.knn_fused(x, idx, k)
        quality.drain()
        assert reg.counter(quality.CERT_FIXUPS, site).value > 0
        assert reg.counter(quality.FIXUP_OVERFLOW, site).value == 0
        y[::75][:40] = y[7]
        x[0] = y[7]
        kf.knn_fused(x, kf.prepare_knn_index(y, passes=3, T=512, Qb=16,
                                             g=8), k)
        quality.drain()
        assert reg.counter(quality.FIXUP_OVERFLOW, site).value == 1
        block = quality_block()
        assert block["sites"]["distance.knn_fused"]["fixup_overflows"] == 1
    finally:
        kf._knn_fused_core.clear_cache()


def test_clean_run_counts_checks_not_fixups():
    from raft_tpu.distance.knn_fused import knn_fused

    x = rng.normal(size=(16, 32)).astype(np.float32)
    y = rng.normal(size=(1024, 32)).astype(np.float32)
    knn_fused(x, y, k=4, passes=3, T=256, Qb=16, g=2)
    quality.drain()
    reg = obs.get_registry()
    assert reg.counter(quality.CERT_CHECKS,
                       {"site": "distance.knn_fused"}).value == 16
    assert reg.counter(quality.CERT_FIXUPS,
                       {"site": "distance.knn_fused"}).value == 0
    block = quality_block()
    assert block["fixup_rate"] == 0.0
    assert block["certificate_checks"] == 16
    assert "fixup_rate" in block["sites"]["distance.knn_fused"]


def test_quality_disabled_records_nothing(monkeypatch):
    from raft_tpu.distance.knn_fused import knn_fused

    monkeypatch.setenv("RAFT_TPU_DISABLE_QUALITY", "1")
    x = rng.normal(size=(8, 32)).astype(np.float32)
    y = rng.normal(size=(512, 32)).astype(np.float32)
    knn_fused(x, y, k=4, passes=3, T=256, Qb=8, g=2)
    assert quality.pending_count() == 0
    assert quality.drain() == 0
    assert quality_block() is None


def test_sharded_fixup_counters():
    """The sharded plane reports per-shard failure counts summed
    host-side — counters appear under its own site label."""
    import jax

    from raft_tpu.distance.knn_sharded import knn_fused_sharded
    from raft_tpu.parallel import make_mesh

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh({"x": 2}, devices=jax.devices()[:2])
    x = rng.normal(size=(24, 32)).astype(np.float32)
    y = rng.normal(size=(2048, 32)).astype(np.float32)
    knn_fused_sharded(x, y, 4, mesh=mesh, axis="x", T=256, Qb=8, g=2)
    quality.drain()
    reg = obs.get_registry()
    assert reg.counter(
        quality.CERT_CHECKS,
        {"site": "distance.knn_fused_sharded"}).value > 0


def test_ivf_q8_records_checks_and_reruns():
    """The IVF q8 scan records its certificate checks at the sync it
    already pays; a failure increments the rerun counter."""
    from raft_tpu.ann import build_ivf_flat, search_ivf_flat
    from raft_tpu.core.resources import DeviceResources

    res = DeviceResources()
    y = rng.normal(size=(1024, 32)).astype(np.float32)
    q = rng.normal(size=(8, 32)).astype(np.float32)
    idx = build_ivf_flat(res, y, n_lists=8, max_iter=4, seed=0,
                         db_dtype="int8")
    search_ivf_flat(res, idx, q, 4, n_probes=3)
    reg = obs.get_registry()
    checks = reg.counter(quality.CERT_CHECKS,
                         {"site": "ann.search_ivf_flat"})
    assert checks.value == 8
    # synthetic failure: the rerun counter + histogram path
    record_certificate("ann.search_ivf_flat", n_queries=4, n_fail=2,
                       pool_width=36, fixup_rows=2, rerun=True)
    assert reg.counter(quality.IVF_RERUNS,
                       {"site": "ann.search_ivf_flat"}).value == 1
    block = quality_block()
    assert block["sites"]["ann.search_ivf_flat"]["cert_reruns"] == 1


# ------------------------------------------------------------------
# shadow sampler
# ------------------------------------------------------------------

def test_recall_at_k():
    true = np.array([[1, 2, 3, 4]])
    assert recall_at_k(np.array([[1, 2, 3, 4]]), true) == 1.0
    assert recall_at_k(np.array([[1, 2, 9, -1]]), true) == 0.5
    assert recall_at_k(np.array([[7, 8, 9, 10]]), true) == 0.0


def test_shadow_sampler_unit_recall_and_breach():
    """A fake oracle with known overlap: the rolling gauge must equal
    the analytic recall, and dropping below the floor must emit a
    drift flight event + breach counter."""
    true_ids = np.arange(8)[None, :]

    def oracle(x):
        return None, np.broadcast_to(true_ids, (x.shape[0], 8))

    s = ShadowSampler(oracle, k=8, frac=1.0, floor=0.9, min_samples=1)
    s.start()
    try:
        x = np.zeros((1, 4), np.float32)
        s.submit(1, x, np.arange(8)[None, :])            # recall 1.0
        assert s.flush()
        assert s.snapshot()["shadow_recall"] == 1.0
        assert s.snapshot()["shadow_breaches"] == 0
        s.submit(2, x, np.array([[0, 1, 2, 3, 90, 91, 92, 93]]))
        assert s.flush()
        snap = s.snapshot()
        assert snap["shadow_samples"] == 2
        assert snap["shadow_recall"] == pytest.approx(0.75)
        assert snap["shadow_breaches"] == 1
    finally:
        s.stop()
    drift = [e for e in obs.get_flight_recorder().events()
             if e["kind"] == "drift" and e["name"] == "serving.shadow"]
    assert drift and drift[-1]["recall"] == pytest.approx(0.75)
    reg = obs.get_registry()
    assert reg.counter(quality.SHADOW_BREACHES).value == 1
    assert reg.gauge(quality.SHADOW_RECALL).value == pytest.approx(0.75)


def test_shadow_sampler_bounded_queue_drops():
    release = threading.Event()

    def slow_oracle(x):
        release.wait(5)
        return None, np.zeros((x.shape[0], 2), np.int64)

    s = ShadowSampler(slow_oracle, k=2, frac=1.0, max_queue=2)
    s.start()
    try:
        x = np.zeros((1, 4), np.float32)
        for rid in range(6):
            s.submit(rid, x, np.zeros((1, 2), np.int64))
        assert s.snapshot()["shadow_dropped"] >= 3
    finally:
        release.set()
        s.stop()


def test_shadow_want_deterministic():
    s = ShadowSampler(lambda x: (None, None), k=1, frac=0.5)
    picks = [s.want(i) for i in range(200)]
    assert picks == [s.want(i) for i in range(200)]
    assert 40 < sum(picks) < 160          # roughly the fraction
    s_off = ShadowSampler(lambda x: (None, None), k=1, frac=0.0)
    assert not any(s_off.want(i) for i in range(50))


# ------------------------------------------------------------------
# serving engine integration: shadow parity + flow tracing + statusz
# ------------------------------------------------------------------

M, D, K = 2100, 32, 5
CFG = dict(passes=3, T=256, Qb=32, g=2)


@pytest.fixture(scope="module")
def data():
    from raft_tpu.distance.knn_fused import prepare_knn_index

    y = rng.normal(size=(M, D)).astype(np.float32)
    idx = prepare_knn_index(y, **CFG)
    return y, idx


def _flows(recorder=None):
    rec = recorder if recorder is not None else obs.get_flight_recorder()
    by_id = collections.defaultdict(list)
    for e in rec.events():
        if e["kind"] == "flow":
            by_id[e["flow_id"]].append(e)
    return by_id


def test_shadow_recall_parity_and_flow_wellformed(data):
    """The deterministic serving round of the acceptance criteria: the
    shadow sampler's rolling recall must equal the offline oracle
    recall (1.0 — the brute plane IS the oracle), and every sampled
    request renders as one s → t… → f flow whose phases cross the
    client and batcher lanes."""
    from raft_tpu.serving import ServingEngine

    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8, 32),
                        flush_interval_s=0.005, shadow_frac=1.0)
    eng.start()
    try:
        xs = [rng.normal(size=(n, D)).astype(np.float32)
              for n in (1, 4, 8, 3)]
        futs = [eng.submit(x) for x in xs]
        assert eng.flush()
        served = [f.result(timeout=30) for f in futs]
        assert eng.shadow.flush()
        snap = eng.shadow.snapshot()
        assert snap["shadow_samples"] == 4
        # offline parity: recompute recall of the served ids vs the
        # SAME offline oracle the sampler re-scored against — the
        # rolling gauge must equal this exactly
        from raft_tpu.distance.knn_fused import knn_fused

        offline = []
        for x, (v, i) in zip(xs, served):
            _, oi = knn_fused(x, idx, K)
            offline.append(recall_at_k(i, np.asarray(oi)))
        assert offline == [1.0] * 4      # brute plane == the oracle
        assert snap["shadow_recall"] == pytest.approx(
            float(np.mean(offline)))
        st = eng.stats()
        assert st["shadow_recall"] == snap["shadow_recall"]
        assert "p50_ms" in st and "p99_ms" in st
    finally:
        eng.stop()
    flows = _flows()
    assert len(flows) == 4
    for rid, evs in flows.items():
        phases = [e["ph"] for e in evs]
        assert phases[0] == "s" and phases.count("s") == 1
        assert phases[-1] == "f" and phases.count("f") == 1
        assert evs[-1]["outcome"] == "ok"
        assert "t" in phases                 # batcher-thread steps
        # the flow crosses lanes: enqueue on the client thread, steps
        # on the batcher thread
        assert evs[0]["lane"] != evs[1]["lane"]


def test_flow_shed_terminates_with_annotation(data):
    from raft_tpu.serving import OverloadShedError, ServingEngine

    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8,), max_queue_rows=8)
    # not started: the queue holds, so the cap is deterministic
    eng.submit(np.ones((8, D), np.float32))
    with pytest.raises(OverloadShedError):
        eng.submit(np.ones((4, D), np.float32))
    flows = _flows()
    shed = [evs for evs in flows.values()
            if evs[-1].get("outcome") == "shed"]
    assert len(shed) == 1
    assert [e["ph"] for e in shed[0]] == ["s", "f"]


def test_flow_expired_terminates_with_annotation(data):
    from raft_tpu.serving import ServingEngine

    _, idx = data
    fake = [0.0]
    eng = ServingEngine(idx, k=K, buckets=(8,), flush_interval_s=60.0,
                        clock=lambda: fake[0])
    eng.start()
    try:
        from raft_tpu.core.error import DeadlineExceededError

        fut = eng.submit(np.ones((2, D), np.float32), deadline_s=0.05)
        fake[0] = 1.0
        eng.flush()
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)
    finally:
        eng.stop()
    flows = _flows()
    expired = [evs for evs in flows.values()
               if evs[-1].get("outcome") == "expired"]
    assert len(expired) == 1
    assert expired[0][-1]["ph"] == "f"


def test_flow_reject_oversize(data):
    from raft_tpu.serving import RequestTooLargeError, ServingEngine

    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8,))
    with pytest.raises(RequestTooLargeError):
        eng.submit(np.ones((9, D), np.float32))
    flows = _flows()
    assert len(flows) == 1
    evs = next(iter(flows.values()))
    assert [e["ph"] for e in evs] == ["s", "f"]
    assert evs[-1]["outcome"] == "reject"


def test_perfetto_export_binds_flows(data):
    """Flow events survive the Perfetto export with the Chrome binding
    keys: matching (cat, name, id) across s/t/f, bp=e on the
    terminus."""
    from raft_tpu.serving import ServingEngine

    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8,), flush_interval_s=0.005)
    eng.start()
    try:
        eng.submit(np.ones((2, D), np.float32))
        eng.flush()
    finally:
        eng.stop()
    trace = obs.export_perfetto()
    flow_te = [t for t in trace["traceEvents"]
               if t.get("ph") in ("s", "t", "f")]
    assert flow_te
    ids = {t["id"] for t in flow_te}
    assert len(ids) == 1
    assert {t["name"] for t in flow_te} == {"request"}
    assert all(t["cat"] == "flow" for t in flow_te)
    terminus = [t for t in flow_te if t["ph"] == "f"]
    assert len(terminus) == 1 and terminus[0]["bp"] == "e"
    import json

    json.dumps(trace)   # must stay serializable


def test_statusz_renders_quality_and_latency(data):
    from raft_tpu.serving import ServingEngine

    from raft_tpu.core.resources import DeviceResources

    statusz = _tools_import("statusz")
    _, idx = data
    # a fresh handle so the METRICS slot resolves THIS test's registry
    # (the process-global handle cached an earlier one)
    eng = ServingEngine(idx, k=K, buckets=(8,), flush_interval_s=0.005,
                        shadow_frac=1.0, res=DeviceResources())
    eng.start()
    try:
        eng.submit(rng.normal(size=(3, D)).astype(np.float32))
        eng.flush()
        eng.shadow.flush()
        page = statusz.render_statusz(engine=eng)
    finally:
        eng.stop()
    assert "fixup_rate" in page
    assert "shadow recall" in page
    assert "p50=" in page and "p99=" in page
    assert "raft_tpu_serving_latency_seconds" in page
    assert "flight tail" in page


# ------------------------------------------------------------------
# gates: check_instrumented QUALITY_SITES + bench_report [quality]
# ------------------------------------------------------------------

def test_quality_sites_gate_clean_on_repo():
    ci = _tools_import("check_instrumented")
    assert ci.check_quality_sites() == []


def test_quality_sites_gate_flags_missing(tmp_path):
    ci = _tools_import("check_instrumented")
    mod = tmp_path / "naked.py"
    mod.write_text("def f():\n    return 1\n")
    errs = ci.check_quality_sites(root=str(tmp_path),
                                  sites={"naked.py": ("record_pending",)})
    assert errs and "record_pending" in errs[0]
    errs = ci.check_quality_sites(root=str(tmp_path),
                                  sites={"gone.py": ("record_pending",)})
    assert errs and "missing" in errs[0]


def test_shadow_floor_pinned_with_bench_report():
    br = _tools_import("bench_report")
    assert br.QUALITY_RECALL_FLOOR == quality.DEFAULT_SHADOW_FLOOR


def test_bench_report_quality_gate_matrix():
    br = _tools_import("bench_report")
    ok_block = {"fixup_rate": 0.001, "certificate_checks": 1000,
                "certificate_fixups": 1}
    # pass: fixup_rate present, recalls at/above floor
    st, msg = br.check_quality([
        ("bench", {"quality": dict(ok_block)}),
        ("serving", {"quality": dict(ok_block, shadow_recall=1.0)}),
        ("ann", {"quality": dict(ok_block, offline_recall=0.97)}),
    ])
    assert st == br.PASS, msg
    # missing fixup_rate → regression
    st, msg = br.check_quality([("bench", {"quality": {"sites": {}}})])
    assert st == br.REGRESS and "fixup_rate" in msg
    # shadow recall below the floor → regression
    st, msg = br.check_quality([
        ("serving", {"quality": dict(ok_block, shadow_recall=0.80)})])
    assert st == br.REGRESS and "shadow_recall" in msg
    # offline recall below the floor → regression
    st, msg = br.check_quality([
        ("ann", {"quality": dict(ok_block, offline_recall=0.90)})])
    assert st == br.REGRESS
    # no family carries a block → skip (pre-quality artifact sets)
    st, msg = br.check_quality([("bench", {"value": 1.0}),
                                ("ann", None)])
    assert st == br.SKIP
    # families without blocks are noted, not failed
    st, msg = br.check_quality([
        ("bench", {"quality": dict(ok_block)}), ("multichip", None)])
    assert st == br.PASS and "multichip" in msg


def test_committed_artifacts_carry_gated_quality_blocks():
    """The committed BENCH/ANN/SERVING artifacts must pass the quality
    gate end to end (acceptance: the quality block rides an
    already-gated schema without regressing existing gates)."""
    import json

    br = _tools_import("bench_report")
    root = os.path.join(os.path.dirname(__file__), "..")
    fams = []
    for family, name in (("bench", "BENCH_LAST_GOOD.json"),
                         ("serving", "BENCH_SERVING.json"),
                         ("ann", "BENCH_ANN.json")):
        path = os.path.join(root, name)
        if os.path.exists(path):
            with open(path) as f:
                fams.append((family, json.load(f)))
    st, msg = br.check_quality(fams)
    assert st in (br.PASS, br.SKIP), msg
    # the freshly-stamped artifacts must carry the block
    carried = [f for f, rec in fams
               if isinstance(rec, dict)
               and isinstance(rec.get("quality"), dict)]
    assert "serving" in carried and "ann" in carried
