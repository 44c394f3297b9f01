"""Serving-engine tests (ISSUE 7 tentpole).

The batcher edge cases the satellite list pins — empty-queue flush
timer, batch exactly at a bucket boundary, oversized-request rejection,
snapshot swap mid-batch consistency — plus the AOT warm-up contract
(zero compile misses in steady state), admission control (overload
shed, queue-expired deadlines), fault injection at the serving sites,
the bucket-ladder env parsing, the bench_report serving gate, and the
closed-loop load generator's fast deterministic variant (the wall-clock
Poisson soak is ``slow``-marked and stays out of tier-1).
"""

import threading
import time

import numpy as np
import pytest

from raft_tpu import resilience
from raft_tpu.core import interruptible
from raft_tpu.core.error import DeadlineExceededError
from raft_tpu.core.resources import DeviceResources
from raft_tpu.distance.knn_fused import (knn_fused, pad_query_rows,
                                         prepare_knn_index)
from raft_tpu.observability import get_registry
from raft_tpu.resilience import InjectedDeviceError
from raft_tpu.serving import (OverloadShedError, RequestTooLargeError,
                              ServingEngine, SnapshotStore, bucket_for,
                              bucket_ladder, default_bucket_ladder)

rng = np.random.default_rng(7)

M, D, K = 4100, 32, 7
CFG = dict(passes=3, T=256, Qb=32, g=2)


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.clear_faults()
    yield
    resilience.clear_faults()
    interruptible.yield_no_throw()


@pytest.fixture(scope="module")
def data():
    y = rng.normal(size=(M, D)).astype(np.float32)
    idx = prepare_knn_index(y, **CFG)
    return y, idx


@pytest.fixture()
def engine(data):
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8, 32),
                        flush_interval_s=0.005)
    eng.start()
    yield eng
    eng.stop()


def _oracle(x, idx):
    ov, oi = knn_fused(x, idx, k=K)
    return np.asarray(ov), np.asarray(oi)


# ------------------------------------------------------------------
# bucket ladder
# ------------------------------------------------------------------

def test_bucket_ladder_default_and_env(monkeypatch):
    assert default_bucket_ladder(256) == (16, 64, 256)
    assert bucket_ladder(256, "8, 32,128") == (8, 32, 128)
    # rounding UP to the row quantum, dedup, sort
    assert bucket_ladder(256, "3,9,9,120") == (8, 16, 120)
    # invalid specs degrade to the default ladder, never raise
    too_many = ",".join(str(8 * i) for i in range(1, 100))
    for bad in ("x,y", "-8,16", "0", too_many):
        assert bucket_ladder(256, bad) == default_bucket_ladder(256)
    monkeypatch.setenv("RAFT_TPU_SERVING_BUCKETS", "16,48")
    assert bucket_ladder(256) == (16, 48)


def test_bucket_for():
    assert bucket_for(1, (8, 32)) == 8
    assert bucket_for(8, (8, 32)) == 8
    assert bucket_for(9, (8, 32)) == 32
    assert bucket_for(33, (8, 32)) is None


def test_pad_query_rows_rejects_oversize():
    x = np.ones((4, D), np.float32)
    assert pad_query_rows(x, 4) is x
    assert np.asarray(pad_query_rows(x, 8)).shape == (8, D)
    with pytest.raises(ValueError):
        pad_query_rows(x, 2)


# ------------------------------------------------------------------
# correctness through the batcher
# ------------------------------------------------------------------

def test_engine_matches_oracle_ragged(data, engine):
    _, idx = data
    futs, refs = [], []
    for n in (1, 5, 8, 3, 12):
        x = rng.normal(size=(n, D)).astype(np.float32)
        refs.append((x, _oracle(x, idx)))
        futs.append(engine.submit(x))
    assert engine.flush()
    for fut, (x, (ov, oi)) in zip(futs, refs):
        v, i = fut.result(timeout=30)
        assert np.array_equal(v, ov)
        assert np.array_equal(i, oi)


def test_empty_queue_flush_timer_is_noop(data):
    """An idle engine's flush timer must dispatch NOTHING (no empty
    batches, no errors) — and the engine still serves afterwards."""
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8, 32),
                        flush_interval_s=0.002)
    eng.start()
    try:
        before = eng.stats().get("batches", 0)
        time.sleep(0.05)                  # ~25 empty flush windows
        assert eng.stats().get("batches", 0) == before
        x = rng.normal(size=(4, D)).astype(np.float32)
        v, i = eng.query(x, timeout=30)
        ov, oi = _oracle(x, idx)
        assert np.array_equal(v, ov) and np.array_equal(i, oi)
    finally:
        eng.stop()


def test_batch_exactly_at_bucket_boundary(data, engine):
    """Requests summing EXACTLY to a bucket coalesce into one batch
    with zero pad rows."""
    _, idx = data
    s0 = engine.stats()
    futs = []
    xs = [rng.normal(size=(8, D)).astype(np.float32) for _ in range(4)]
    for x in xs:
        futs.append(engine.submit(x))
    assert engine.flush()
    s1 = engine.stats()
    assert s1["batches"] - s0.get("batches", 0) == 1
    assert s1.get("padded_rows", 0) == s0.get("padded_rows", 0)
    for fut, x in zip(futs, xs):
        v, i = fut.result(timeout=30)
        ov, oi = _oracle(x, idx)
        assert np.array_equal(v, ov) and np.array_equal(i, oi)


def test_oversize_request_rejected_classified(engine):
    """A request larger than the top bucket is REJECTED with a
    classified error — never silently truncated."""
    with pytest.raises(RequestTooLargeError):
        engine.submit(np.ones((33, D), np.float32))
    # the engine is untouched: a sane request still round-trips
    v, _ = engine.query(np.ones((2, D), np.float32), timeout=30)
    assert v.shape == (2, K)


def test_overload_shed_is_a_degradation_rung(data):
    """A full queue SHEDS at admission (classified error + counted as
    a degradation rung), instead of queueing unboundedly."""
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8,), max_queue_rows=8)
    # NOT started: the queue cannot drain, so the cap must trip
    eng.submit(np.ones((8, D), np.float32))
    before = 0.0
    for m in get_registry().collect():
        if m.name == resilience.DEGRADATIONS \
                and m.labels.get("site") == "serving.engine":
            before += m.value
    with pytest.raises(OverloadShedError):
        eng.submit(np.ones((1, D), np.float32))
    after = 0.0
    for m in get_registry().collect():
        if m.name == resilience.DEGRADATIONS \
                and m.labels.get("site") == "serving.engine":
            after += m.value
    assert after == before + 1
    assert eng.stats().get("shed", 0) >= 1


# ------------------------------------------------------------------
# snapshots
# ------------------------------------------------------------------

def test_snapshot_swap_mid_batch_consistent_ids(data):
    """Requests in flight across a swap each see EXACTLY ONE snapshot:
    every response matches the old index's oracle or the new one's —
    never a mix within a request."""
    y, idx = data
    y2 = rng.normal(size=(M, D)).astype(np.float32)
    idx2 = prepare_knn_index(y2, **CFG)
    eng = ServingEngine(idx, k=K, buckets=(8, 32),
                        flush_interval_s=0.005)
    eng.start()
    try:
        xs = [rng.normal(size=(4, D)).astype(np.float32)
              for _ in range(8)]
        oracles = [(_oracle(x, idx), _oracle(x, idx2)) for x in xs]
        futs = [eng.submit(x) for x in xs[:4]]
        swapper = threading.Thread(
            target=lambda: eng.update_index(y2, block=True))
        swapper.start()
        futs += [eng.submit(x) for x in xs[4:]]
        swapper.join(60)
        eng.flush()
        for fut, ((ov1, oi1), (ov2, oi2)) in zip(futs, oracles):
            v, i = fut.result(timeout=60)
            old = np.array_equal(v, ov1) and np.array_equal(i, oi1)
            new = np.array_equal(v, ov2) and np.array_equal(i, oi2)
            assert old or new, "response mixes snapshots"
        # post-swap traffic serves the NEW index
        x = xs[0]
        v, i = eng.query(x, timeout=30)
        (_, _), (ov2, oi2) = oracles[0]
        assert np.array_equal(v, ov2) and np.array_equal(i, oi2)
        assert eng.snapshot.generation == 1
    finally:
        eng.stop()


def test_snapshot_build_failure_keeps_current(data):
    """An injected rebuild failure leaves the live snapshot untouched
    (counted, logged — never surfaced into the query path)."""
    y, idx = data
    store = SnapshotStore(lambda yy, **kw: prepare_knn_index(yy, **CFG),
                          initial_index=idx)
    cur = store.current()
    resilience.configure_faults("serving_snapshot:error")
    store.update(y, block=True)
    assert store.current() is cur
    assert isinstance(store.last_error, InjectedDeviceError)
    resilience.clear_faults()
    store.update(y, block=True)
    assert store.current() is not cur
    assert store.current().generation == 2


def _metric_value(name, default=None):
    for m in get_registry().collect():
        if m.name == name:
            return m.value
    return default


def test_snapshot_store_gauges_and_coalesced_counter(data):
    """ISSUE-11 satellite: the store exposes its generation and
    in-flight-rebuild state as gauges, and a build whose swap lost the
    generation race is COUNTED instead of silently dropped
    (snapshot.py's last-wins branch)."""
    from raft_tpu.serving.snapshot import (REBUILD_INFLIGHT,
                                           SNAPSHOT_COALESCED,
                                           SNAPSHOT_GENERATION)

    y, idx = data
    gate = threading.Event()
    order = []

    def builder(yy, **kw):
        tag = yy.shape[0]
        if tag == 64:          # the SLOW build — held until released
            assert gate.wait(timeout=30)
        order.append(tag)
        return prepare_knn_index(yy, **CFG)

    store = SnapshotStore(builder, initial_index=idx)
    coalesced0 = _metric_value(SNAPSHOT_COALESCED, 0.0) or 0.0
    slow = rng.normal(size=(64, D)).astype(np.float32)
    fast = rng.normal(size=(72, D)).astype(np.float32)
    t = store.update(slow, block=False)       # gen 1, held
    store.update(fast, block=True)            # gen 2, swaps first
    assert store.current().generation == 2
    assert _metric_value(SNAPSHOT_GENERATION) == 2
    gate.set()
    t.join(30)
    # the gen-1 build finished AFTER gen 2 swapped: coalesced, counted,
    # and the serving snapshot is still gen 2
    assert store.current().generation == 2
    assert (_metric_value(SNAPSHOT_COALESCED, 0.0) or 0.0) \
        == coalesced0 + 1
    assert _metric_value(REBUILD_INFLIGHT) == 0


# ------------------------------------------------------------------
# AOT warm-up: zero compile misses in steady state
# ------------------------------------------------------------------

def test_warmup_then_zero_compile_misses(data):
    """THE serving latency contract: after start-up warm-up, no live
    request pays a trace/compile — neither in the handle's CompileCache
    nor as a compile-miss event in the flight recorder."""
    from raft_tpu.observability import get_flight_recorder

    _, idx = data
    res = DeviceResources()
    eng = ServingEngine(idx, k=K, res=res, buckets=(8, 32),
                        flush_interval_s=0.002)
    eng.start()
    try:
        assert res.compile_cache.misses == len(eng.buckets)
        misses0 = res.compile_cache.misses

        def flight_misses():
            return sum(1 for e in get_flight_recorder().events()
                       if e.get("kind") == "compile"
                       and not e.get("hit", False))

        f0 = flight_misses()
        for n in (1, 3, 8, 8, 2, 12, 32, 5):
            eng.query(rng.normal(size=(n, D)).astype(np.float32),
                      timeout=30)
        assert res.compile_cache.misses == misses0
        assert flight_misses() == f0
    finally:
        eng.stop()


# ------------------------------------------------------------------
# deadlines + fault injection at the serving sites
# ------------------------------------------------------------------

def test_request_deadline_expires_in_queue(data):
    """Admission control: a request whose budget lapses while QUEUED is
    failed with DeadlineExceededError at assembly — no wasted dispatch."""
    _, idx = data
    fake = [0.0]
    eng = ServingEngine(idx, k=K, buckets=(8,), flush_interval_s=60.0,
                        clock=lambda: fake[0])
    eng.start()
    try:
        fut = eng.submit(np.ones((2, D), np.float32), deadline_s=0.05)
        fake[0] = 1.0                       # budget long gone
        eng.flush()
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)
        assert eng.stats().get("expired_in_queue", 0) >= 1
    finally:
        eng.stop()


def test_injected_flush_hang_converts_via_deadline(data):
    """serving_flush:hang + a per-request deadline = the batch deadline
    fires on the batcher thread and the request fails typed — the
    engine survives and keeps serving."""
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8,), flush_interval_s=0.002)
    eng.start()
    try:
        resilience.configure_faults("serving_flush:hang@call=1")
        t0 = time.monotonic()
        fut = eng.submit(np.ones((2, D), np.float32), deadline_s=0.4)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)
        assert time.monotonic() - t0 < 5.0
        resilience.clear_faults()
        v, _ = eng.query(np.ones((2, D), np.float32), timeout=30)
        assert v.shape == (2, K)
    finally:
        eng.stop()


def test_injected_flush_error_fails_batch_engine_survives(data):
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8,), flush_interval_s=0.002)
    eng.start()
    try:
        resilience.configure_faults("serving_flush:error@call=1")
        fut = eng.submit(np.ones((2, D), np.float32))
        with pytest.raises(InjectedDeviceError):
            fut.result(timeout=30)
        resilience.clear_faults()
        v, _ = eng.query(np.ones((2, D), np.float32), timeout=30)
        assert v.shape == (2, K)
    finally:
        eng.stop()


def test_injected_enqueue_fault_surfaces_to_submitter(data):
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8,))
    resilience.configure_faults("serving_enqueue:error")
    with pytest.raises(InjectedDeviceError):
        eng.submit(np.ones((2, D), np.float32))


# ------------------------------------------------------------------
# closed-loop load: fast deterministic variant (tier-1) + slow soak
# ------------------------------------------------------------------

def _closed_loop(eng, idx, n_requests, clients, think_s=0.0):
    sizes = np.clip(np.random.default_rng(3).poisson(4, n_requests),
                    1, eng.buckets[-1])
    xs = [rng.normal(size=(int(n), D)).astype(np.float32)
          for n in sizes]
    lat, errors = [], []
    lock = threading.Lock()
    counter = {"next": 0}

    def client():
        while True:
            with lock:
                i = counter["next"]
                if i >= n_requests:
                    return
                counter["next"] = i + 1
            t0 = time.perf_counter()
            try:
                eng.submit(xs[i]).result(timeout=60)
            except Exception as e:           # pragma: no cover
                with lock:
                    errors.append(repr(e))
                continue
            with lock:
                lat.append(time.perf_counter() - t0)
            if think_s:
                time.sleep(np.random.default_rng(i).exponential(think_s))

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.flush()
    return xs, lat, errors


def test_closed_loop_deterministic_fast(data):
    """The tier-1 variant of the Poisson load test: seeded arrival
    sizes, zero think time, no wall-clock dependence — full completion,
    correct bits on a sample, p50/p99 computable."""
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8, 32),
                        flush_interval_s=0.002)
    eng.start()
    try:
        xs, lat, errors = _closed_loop(eng, idx, n_requests=24,
                                       clients=4)
        assert not errors
        assert len(lat) == 24
        p99 = sorted(lat)[int(len(lat) * 0.99)]
        assert p99 > 0
        for x in xs[:3]:
            v, i = eng.query(x, timeout=30)
            ov, oi = _oracle(x, idx)
            assert np.array_equal(v, ov) and np.array_equal(i, oi)
    finally:
        eng.stop()


@pytest.mark.slow
def test_closed_loop_poisson_soak(data):
    """Wall-clock Poisson soak (slow — excluded from tier-1): real
    exponential think times, more clients/requests, latency histogram
    populated through the registry."""
    _, idx = data
    eng = ServingEngine(idx, k=K, buckets=(8, 32),
                        flush_interval_s=0.002)
    eng.start()
    try:
        _, lat, errors = _closed_loop(eng, idx, n_requests=96,
                                      clients=8, think_s=0.002)
        assert not errors and len(lat) == 96
        stats = eng.stats()
        assert stats["requests_ok"] >= 96
        assert "p99_ms" in stats
    finally:
        eng.stop()


# ------------------------------------------------------------------
# batcher spans and the queue-wait histogram
# ------------------------------------------------------------------

def test_served_request_spans_and_queue_wait(data):
    """One request served through the flush window: one
    ``serving.flush_wait`` (the wait for co-riders) and one
    ``serving.device_wait`` (inside ``serving.execute_batch``) on the
    batcher thread, and one queue-wait sample no longer than the
    request's latency. The request is queued before the batcher starts
    and comes due only when the test clock moves past the window."""
    from raft_tpu.observability import FlightRecorder, set_flight_recorder
    from raft_tpu.serving.engine import LATENCY, QUEUE_WAIT

    _, idx = data
    reg = get_registry()
    wait_h, lat_h = reg.histogram(QUEUE_WAIT), reg.histogram(LATENCY)
    waits0, wait_sum0 = wait_h.count, wait_h.sum
    lats0, lat_sum0 = lat_h.count, lat_h.sum
    now = [0.0]
    eng = ServingEngine(idx, k=K, buckets=(8,), flush_interval_s=0.2,
                        clock=lambda: now[0])
    rec = FlightRecorder(capacity=4096)
    prev = set_flight_recorder(rec)
    try:
        fut = eng.submit(rng.normal(size=(3, D)).astype(np.float32))
        eng.start()
        time.sleep(0.5)          # the batcher finds it not yet due
        now[0] = 0.25
        fut.result(timeout=60)
    finally:
        set_flight_recorder(prev)
        eng.stop()
    spans = [e for e in rec.events() if e["kind"] == "span"
             and e["name"] in ("serving.flush_wait", "serving.device_wait")]
    assert sorted(e["name"] for e in spans) == ["serving.device_wait",
                                                "serving.flush_wait"]
    assert all(e["lane"] == "serving-batcher" for e in spans)
    by_name = {e["name"]: e for e in spans}
    assert by_name["serving.device_wait"]["range"] \
        == "serving.execute_batch"
    # the span lasted until the clock moved
    assert by_name["serving.flush_wait"]["dur"] > 0.3
    assert wait_h.count == waits0 + 1 and lat_h.count == lats0 + 1
    wait = wait_h.sum - wait_sum0
    assert 0.0 <= wait <= lat_h.sum - lat_sum0


# ------------------------------------------------------------------
# the ANN tier behind the same bucket ladder (ISSUE 8)
# ------------------------------------------------------------------

def test_ivf_flat_serving_plane(data):
    """algorithm='ivf_flat': the SnapshotStore holds an IVF snapshot
    and the engine serves approximate queries behind the same bucket
    ladder. At n_probes = n_lists the plane is degenerate-exact, so a
    served batch must match the brute-force oracle's id sets."""
    y, idx = data
    eng = ServingEngine(y, k=K, buckets=(8,), flush_interval_s=0.005,
                        algorithm="ivf_flat", n_lists=8, n_probes=8)
    eng.start()
    try:
        x = rng.normal(size=(5, D)).astype(np.float32)
        vals, ids = eng.query(x, timeout=120)
        ov, oi = _oracle(x, idx)
        for q in range(5):
            assert set(ids[q].tolist()) == set(oi[q].tolist())
        # the snapshot store really holds an IVF snapshot
        from raft_tpu.ann import IvfFlatIndex

        assert isinstance(eng.snapshot.index, IvfFlatIndex)
    finally:
        eng.stop()
    with pytest.raises(ValueError):
        ServingEngine(y, k=K, algorithm="bogus")


# ------------------------------------------------------------------
# bench_report: the serving gate
# ------------------------------------------------------------------

def _tools_import(name):
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    return importlib.import_module(f"tools.{name}")


def test_bench_report_serving_gate_matrix():
    br = _tools_import("bench_report")
    mk = lambda n, rec: (n, f"SERVING_r{n:02d}.json", rec)
    # nothing to gate
    assert br.check_serving([])[0] == br.SKIP
    # ok=false regresses
    assert br.check_serving([mk(1, {"ok": False})])[0] == br.REGRESS
    # compile miss after warmup regresses even when ok
    st, msg = br.check_serving(
        [mk(1, {"ok": True, "compile_misses_after_warmup": 2})])
    assert st == br.REGRESS and "compile" in msg
    # modeled rounds pass on ok alone — never speed-gated
    st, msg = br.check_serving(
        [mk(1, {"ok": True, "measured": False, "p99_ms": 999.0})])
    assert st == br.PASS and "modeled" in msg
    # degraded rounds are SKIPped
    st, msg = br.check_serving(
        [mk(1, {"ok": True, "resilience_degradations": 2.0})])
    assert st == br.SKIP and "degrad" in msg
    # measured trend: p99 grows past threshold → regression
    rounds = [
        mk(1, {"ok": True, "measured": True, "p99_ms": 10.0,
               "throughput_qps": 100.0}),
        mk(2, {"ok": True, "measured": True, "p99_ms": 20.0,
               "throughput_qps": 100.0}),
    ]
    st, msg = br.check_serving(rounds)
    assert st == br.REGRESS and "P99" in msg
    # throughput drop past threshold → regression
    rounds[1] = mk(2, {"ok": True, "measured": True, "p99_ms": 10.0,
                       "throughput_qps": 50.0})
    st, msg = br.check_serving(rounds)
    assert st == br.REGRESS and "THROUGHPUT" in msg
    # holding both → pass
    rounds[1] = mk(2, {"ok": True, "measured": True, "p99_ms": 10.5,
                       "throughput_qps": 97.0})
    assert br.check_serving(rounds)[0] == br.PASS


def test_bench_report_collects_bare_serving_artifact(tmp_path):
    import json

    br = _tools_import("bench_report")
    (tmp_path / "SERVING_r01.json").write_text(json.dumps(
        {"parsed": {"ok": True, "measured": True, "p99_ms": 5.0,
                    "throughput_qps": 10.0}}))
    (tmp_path / "BENCH_SERVING.json").write_text(json.dumps(
        {"ok": True, "measured": True, "p99_ms": 5.2,
         "throughput_qps": 9.9}))
    rounds = br.collect_serving(str(tmp_path))
    assert len(rounds) == 2
    # the bare artifact is the NEWEST round and gates against r01
    assert rounds[-1][1].endswith("BENCH_SERVING.json")
    assert br.check_serving(rounds)[0] == br.PASS


def test_committed_serving_artifact_schema():
    """The committed BENCH_SERVING.json must carry the SLO fields, the
    zero-compile-miss stamp, and honest measured=false off TPU."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_SERVING.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_SERVING.json committed")
    with open(path) as f:
        rec = json.load(f)
    for field in ("ok", "p50_ms", "p99_ms", "throughput_qps",
                  "compile_misses_after_warmup", "buckets", "measured"):
        assert field in rec, field
    assert rec["compile_misses_after_warmup"] == 0
    assert rec["ok"] is True
    # the SLO block (ISSUE 16): availability + burn-alert evidence
    slo = rec.get("slo")
    assert isinstance(slo, dict), "slo block missing — regenerate"
    for field in ("availability", "total_requests", "bad_requests",
                  "fast_burn_alerts", "fast_burn_by_slo", "healthy"):
        assert field in slo, field


def test_bench_report_slo_gate_matrix():
    br = _tools_import("bench_report")
    mk = lambda n, rec: (n, f"SERVING_r{n:02d}.json", rec)
    good_slo = {"availability": 0.999, "total_requests": 1000,
                "bad_requests": 1, "fast_burn_alerts": 0,
                "fast_burn_by_slo": {}, "healthy": True}
    # nothing to gate / degraded round → SKIP
    assert br.check_slo([])[0] == br.SKIP
    st, msg = br.check_slo(
        [mk(1, {"ok": True, "resilience_degradations": 2.0,
                "slo": dict(good_slo)})])
    assert st == br.SKIP and "degrad" in msg
    # artifact predating the SLO plane → MISSING_BASELINE
    st, msg = br.check_slo([mk(1, {"ok": True})])
    assert st == br.MISSING_BASELINE and "regenerate" in msg
    # failed round: the [serving] gate owns it, [slo] skips
    assert br.check_slo(
        [mk(1, {"ok": False, "slo": dict(good_slo)})])[0] == br.SKIP
    # clean round passes
    st, msg = br.check_slo([mk(1, {"ok": True, "slo": dict(good_slo)})])
    assert st == br.PASS and "availability" in msg
    # availability below the 0.99 floor regresses
    st, msg = br.check_slo([mk(1, {
        "ok": True, "slo": dict(good_slo, availability=0.97,
                                bad_requests=30)})])
    assert st == br.REGRESS and "availability" in msg
    # no traffic: no evidence, no gate
    assert br.check_slo([mk(1, {
        "ok": True,
        "slo": dict(good_slo, availability=None)})])[0] == br.SKIP
    # a page-severity fast burn on an ok MEASURED round regresses
    st, msg = br.check_slo([mk(1, {
        "ok": True, "measured": True,
        "slo": dict(good_slo, fast_burn_alerts=1,
                    fast_burn_by_slo={"availability": 1})})])
    assert st == br.REGRESS and "burn" in msg
    # modeled round: LATENCY burns are wall-clock noise — not gated ...
    st, msg = br.check_slo([mk(1, {
        "ok": True, "measured": False,
        "slo": dict(good_slo, fast_burn_alerts=1,
                    fast_burn_by_slo={"latency_p99": 1})})])
    assert st == br.PASS and "not gated" in msg
    # ... but an availability burn gates even on modeled rounds
    st, msg = br.check_slo([mk(1, {
        "ok": True, "measured": False,
        "slo": dict(good_slo, fast_burn_alerts=2,
                    fast_burn_by_slo={"latency_p99": 1,
                                      "availability": 1})})])
    assert st == br.REGRESS and "availability" in str(msg)
    # legacy block without the per-slo split: gate conservatively
    st, msg = br.check_slo([mk(1, {
        "ok": True, "measured": False,
        "slo": {"availability": 1.0, "fast_burn_alerts": 1}})])
    assert st == br.REGRESS
