"""Flight recorder + timeline + drift ledger tests (ISSUE 6 tentpole).

Ring-buffer wraparound and thread safety, the disabled-mode no-op
contract (no allocation, registry untouched), Perfetto JSON schema
validity, post-mortem dumps on classified errors and on an injected
``deadline`` fault via the RAFT_TPU_FAULTS DSL, flight tails on
DeviceError/DeadlineExceededError payloads, the model-vs-measured
drift-ledger round-trip + ``bench_report --check`` gate behavior
(within-band pass, out-of-band flag, modeled-only never gated), and
the EVENT_SITES static gate pinned consistent with
``flight.KNOWN_EVENT_KINDS``.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import raft_tpu.observability as obs
from raft_tpu import resilience
from raft_tpu.core import interruptible, nvtx
from raft_tpu.core.error import (DeadlineExceededError, DeviceError,
                                 OutOfMemoryError, classify_xla_error)
from raft_tpu.observability import (
    FlightRecorder,
    KNOWN_EVENT_KINDS,
    export_perfetto,
    export_prometheus,
    get_flight_recorder,
    instrument,
    set_flight_recorder,
)
from raft_tpu.observability import flight as flight_mod
from raft_tpu.observability import timeline
from raft_tpu.observability.timeline import DriftLedger, record_drift
from raft_tpu.resilience import deadline, fault_point


def _tools_import(name):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def clean_world():
    """Fresh recorder + ledger + registry per test; faults cleared and
    the interruptible token un-poisoned on the way out."""
    prev_rec = set_flight_recorder(FlightRecorder(capacity=4096))
    prev_led = timeline.set_drift_ledger(DriftLedger())
    flight_mod._dump_count = 0
    obs.reset()
    obs.enable()
    resilience.clear_faults()
    yield
    resilience.clear_faults()
    interruptible.yield_no_throw()
    set_flight_recorder(prev_rec)
    timeline.set_drift_ledger(prev_led)
    obs.reset()
    obs.enable()


def _kinds(events):
    return [e["kind"] for e in events]


# ------------------------------------------------------------- ring core
def test_ring_buffer_wraparound():
    rec = FlightRecorder(capacity=32)
    for i in range(100):
        rec.record("marker", f"m{i}", i=i)
    assert len(rec) == 32
    assert rec.seq == 100
    assert rec.dropped == 68
    evs = rec.events()
    # oldest events fell off the back; the newest 32 survive, in order
    assert [e["i"] for e in evs] == list(range(68, 100))
    assert rec.tail(4)[-1]["name"] == "m99"
    rec.clear()
    assert len(rec) == 0 and rec.seq == 0


def test_ring_thread_safety_under_concurrent_emitters():
    rec = FlightRecorder(capacity=8192)
    n_threads, per = 8, 200

    def emit(t):
        for i in range(per):
            rec.record("marker", f"t{t}.{i}", thread=t)

    threads = [threading.Thread(target=emit, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert rec.seq == n_threads * per
    assert len(rec) == n_threads * per
    # wraparound under contention stays consistent too
    small = FlightRecorder(capacity=64)
    threads = [threading.Thread(target=lambda: [
        small.record("marker", "x") for _ in range(per)])
        for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert small.seq == n_threads * per and len(small) == 64


def test_disabled_mode_is_noop_and_allocates_nothing():
    rec = FlightRecorder(capacity=64, enabled=False)
    rec.record("marker", "nope", payload=123)
    assert len(rec) == 0 and rec.seq == 0
    # the process-global disabled path: emit helpers bail on the one
    # boolean before touching the registry or building event dicts
    set_flight_recorder(rec)
    reg_len = len(obs.get_registry())
    timeline.emit_fault("site", "oom")
    timeline.emit_degradation("site", "merge:a->b")
    timeline.emit_span("s", "", 0.1, 0, 0, False)
    assert len(rec) == 0
    assert len(obs.get_registry()) == reg_len
    assert flight_mod.error_tail() == []
    # runtime disable/enable round-trip on a real recorder
    real = FlightRecorder(capacity=64)
    set_flight_recorder(real)
    flight_mod.disable_flight()
    timeline.emit_marker("hidden")
    assert len(real) == 0
    flight_mod.enable_flight()
    timeline.emit_marker("visible")
    assert len(real) == 1


def test_null_flight_stays_disabled_after_enable():
    prev = set_flight_recorder(flight_mod.NULL_FLIGHT)
    try:
        flight_mod.enable_flight()   # must NOT enable the shared null
        assert not flight_mod.flight_enabled()
        timeline.emit_marker("dropped")
        assert len(flight_mod.NULL_FLIGHT) == 0
    finally:
        flight_mod.NULL_FLIGHT.enabled = False
        set_flight_recorder(prev)


# ------------------------------------------------------------- perfetto
def test_perfetto_export_schema_validity():
    rec = get_flight_recorder()
    with nvtx.annotate("outer"):
        with obs.span("inner.work"):
            pass
    timeline.emit_collective("allgather", 4096, "x")
    timeline.emit_fault("merge_permute", "timeout")
    timeline.emit_degradation("site", "merge:tournament->allgather")
    trace = export_perfetto(rec)
    # must survive a JSON round-trip and satisfy the Chrome trace-event
    # required keys on EVERY event
    parsed = json.loads(json.dumps(trace, default=str))
    events = parsed["traceEvents"]
    assert events
    for ev in events:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in ev, f"missing {key} in {ev}"
    # complete slices carry dur (µs); span event has its nvtx stack
    spans = [e for e in events if e.get("cat") == "span"]
    assert spans and "dur" in spans[0]
    assert spans[0]["args"]["range"] == "outer"
    # lanes render as named tracks (thread_name metadata per tid)
    meta = [e for e in events if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert any(n.startswith("comms:") for n in names)
    tids = {e["tid"] for e in events if e["ph"] != "M"}
    assert tids <= {e["tid"] for e in meta}


def test_span_events_carry_bytes_and_range():
    @instrument("flight.op")
    def op(x):
        return x * 2

    x = np.ones((4, 8), np.float32)
    with nvtx.annotate("caller"):
        op(x)
    evs = [e for e in get_flight_recorder().events()
           if e["kind"] == "span" and e["name"] == "flight.op"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["range"] == "caller"
    assert ev["bytes_in"] == 128 and ev["bytes_out"] == 128
    assert ev["ph"] == "X" and ev["dur"] > 0


def test_compile_cache_events():
    from raft_tpu.core.resources import CompileCache

    cc = CompileCache()
    cc.get_or_compile(("k",), lambda: "exe")
    cc.get_or_compile(("k",), lambda: "exe2")
    evs = [e for e in get_flight_recorder().events()
           if e["kind"] == "compile"]
    assert [e.get("hit") for e in evs] == [False, True]


# ----------------------------------------------- resilience event wiring
def test_fault_retry_degradation_events_recorded():
    resilience.configure_faults("select_k:error@call=1")
    with pytest.raises(resilience.InjectedDeviceError):
        fault_point("select_k")
    resilience.record_retry("some.site", ValueError("boom"), attempt=1)
    resilience.record_degradation("some.site", "merge:a->b")
    evs = get_flight_recorder().events()
    kinds = _kinds(evs)
    assert "fault" in kinds and "retry" in kinds \
        and "degradation" in kinds
    fault = next(e for e in evs if e["kind"] == "fault")
    assert fault["name"] == "select_k" and fault["fault_kind"] == "error"
    deg = next(e for e in evs if e["kind"] == "degradation")
    assert deg["action"] == "merge:a->b"


def test_device_error_carries_flight_tail():
    for i in range(100):
        timeline.emit_marker(f"pre{i}")

    class FakeXla(Exception):
        pass

    FakeXla.__module__ = "jaxlib.xla_extension"
    err = classify_xla_error(FakeXla("RESOURCE_EXHAUSTED: out of memory"))
    assert isinstance(err, OutOfMemoryError)
    assert 0 < len(err.flight_tail) <= flight_mod.TAIL_EVENTS
    assert err.flight_tail[-1]["name"] == "pre99"
    # plain construction carries it too (satellite: DeviceError payload)
    assert len(DeviceError("x").flight_tail) > 0


def test_deadline_error_carries_tail_and_emits_timeline():
    timeline.emit_marker("before-deadline")
    with pytest.raises(DeadlineExceededError) as ei:
        with deadline(0.03, label="tiny"):
            time.sleep(0.08)
    err = ei.value
    assert any(e["name"] == "before-deadline" for e in err.flight_tail)
    evs = get_flight_recorder().events()
    dl = [e for e in evs if e["kind"] == "deadline"]
    assert [e["fired"] for e in dl] == [False, True]
    assert dl[1]["name"] == "tiny"


# ------------------------------------------------------------- dumps
def test_post_mortem_dump_on_classified_error(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_FLIGHT_DIR", str(tmp_path))
    timeline.emit_marker("context")

    class FakeXla(Exception):
        pass

    FakeXla.__module__ = "jaxlib.xla_extension"
    err = classify_xla_error(FakeXla("INTERNAL: device halted"))
    assert isinstance(err, DeviceError)
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight_")]
    assert len(dumps) == 1
    with open(tmp_path / dumps[0]) as f:
        trace = json.load(f)
    assert trace["raft_tpu"]["trigger"].startswith("classify-")
    assert "DeviceError" in trace["raft_tpu"]["error"]
    assert any(e.get("cat") == "marker" for e in trace["traceEvents"])
    # the same exception instance bubbling through nested scopes must
    # not dump again
    classify_xla_error(err)
    assert len([f for f in os.listdir(tmp_path)
                if f.startswith("flight_")]) == 1


def test_post_mortem_dump_on_injected_deadline_fault(tmp_path,
                                                     monkeypatch):
    """The RAFT_TPU_FAULTS DSL arms a hang; a deadline scope converts
    it and the fired deadline dumps the ring."""
    monkeypatch.setenv("RAFT_TPU_FLIGHT_DIR", str(tmp_path))
    resilience.configure_faults("host_sync:hang")
    with pytest.raises(DeadlineExceededError):
        with deadline(0.05, label="dsl-hang"):
            fault_point("host_sync")
    dumps = sorted(f for f in os.listdir(tmp_path)
                   if f.startswith("flight_"))
    assert dumps, "deadline fire must produce a post-mortem dump"
    with open(tmp_path / dumps[-1]) as f:
        trace = json.load(f)
    assert trace["raft_tpu"]["trigger"] == "deadline-dsl-hang"
    cats = [e.get("cat") for e in trace["traceEvents"]]
    assert "fault" in cats and "deadline" in cats
    # the fault precedes the fired deadline on the monotonic clock
    t_fault = min(e["ts"] for e in trace["traceEvents"]
                  if e.get("cat") == "fault")
    t_fired = max(e["ts"] for e in trace["traceEvents"]
                  if e.get("cat") == "deadline"
                  and e.get("args", {}).get("fired"))
    assert t_fault <= t_fired


def test_disabled_recorder_never_dumps(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFT_TPU_FLIGHT_DIR", str(tmp_path))
    timeline.emit_marker("something")
    flight_mod.disable_flight()
    assert flight_mod.post_mortem("manual") is None
    assert not os.listdir(tmp_path)


# ------------------------------------- acceptance: sharded fault timeline
M, D, K, NQ = 4100, 32, 7, 33
CFG = dict(T=256, Qb=32, g=2)


def test_sharded_fault_timeline_acceptance(tmp_path, monkeypatch):
    """ISSUE acceptance: an injected merge timeout (+ NaN poisoning)
    under a deadline() scope produces a post-mortem Perfetto dump that
    loads and shows the fault, the retry, and the degradation rung in
    time order."""
    from raft_tpu.distance.knn_sharded import knn_fused_sharded
    from raft_tpu.parallel import make_mesh

    monkeypatch.setenv("RAFT_TPU_FLIGHT_DIR", str(tmp_path))
    rng = np.random.default_rng(7)
    y = rng.normal(size=(M, D)).astype(np.float32)
    x = rng.normal(size=(NQ, D)).astype(np.float32)
    mesh = make_mesh({"x": 4}, devices=jax.devices()[:4])
    resilience.configure_faults(
        "merge_permute:timeout@call=1;sharded_dispatch:nan@call=2")
    with pytest.raises(DeadlineExceededError):
        with deadline(0.05, label="acceptance"):
            knn_fused_sharded(x, y, K, mesh=mesh, merge="tournament",
                              passes=3, **CFG)
            time.sleep(0.08)   # the budget IS exceeded by scope exit
    dumps = sorted(f for f in os.listdir(tmp_path)
                   if f.startswith("flight_"))
    assert dumps
    with open(tmp_path / dumps[-1]) as f:
        trace = json.load(f)          # Perfetto JSON loads
    evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    t_of = {}
    for cat in ("fault", "retry", "degradation"):
        cat_evs = [e for e in evs if e.get("cat") == cat]
        assert cat_evs, f"dump is missing {cat} events"
        t_of[cat] = min(e["ts"] for e in cat_evs)
    # time order: the injected timeout precedes the merge-ladder rung,
    # which precedes the NaN-poisoning retry of the degraded config
    assert t_of["fault"] <= t_of["degradation"] <= t_of["retry"]
    deg = next(e for e in evs if e.get("cat") == "degradation")
    assert deg["args"]["action"].startswith("merge:tournament->")


# ------------------------------------------------------------- drift
def test_drift_ledger_roundtrip(tmp_path):
    led = DriftLedger(max_entries=3)
    for i in range(5):
        led.record("site.a", predicted_seconds=1.0,
                   measured_seconds=1.0 + i, measured=True)
    led.record("site.b", predicted_seconds=2.0, measured=False)
    assert len(led.entries()["site.a"]) == 3   # bounded per site
    path = str(tmp_path / "DRIFT_LEDGER.json")
    assert led.save(path) == path
    back = DriftLedger.load(path)
    assert back.sites() == ["site.a", "site.b"]
    assert back.latest("site.a")["measured_seconds"] == 5.0
    assert back.latest("site.a")["drift_seconds_ratio"] == \
        pytest.approx(5.0)
    # corrupt file degrades to empty, never raises
    with open(path, "w") as f:
        f.write("{ torn")
    assert DriftLedger.load(path).sites() == []


def test_drift_ledger_merge_is_durable(tmp_path):
    path = str(tmp_path / "DRIFT_LEDGER.json")
    first = DriftLedger()
    first.record("s", predicted_seconds=1.0, measured_seconds=1.0,
                 measured=True)
    first.save(path)
    second = DriftLedger()
    second.record("s", predicted_seconds=1.0, measured_seconds=2.0,
                 measured=True)
    disk = DriftLedger.load(path)
    disk.merge(second)
    disk.save(path)
    hist = DriftLedger.load(path).entries()["s"]
    assert len(hist) == 2
    assert hist[-1]["measured_seconds"] == 2.0


def test_fixture_run_records_drift_and_is_not_measured_on_cpu():
    from raft_tpu.benchmark import Fixture

    fx = Fixture(reps=1, warmup=0)
    x = jnp.ones((64, 64), jnp.float32)
    fx.run(jax.jit(lambda a: a @ a), x, name="drift.bench")
    entry = timeline.get_drift_ledger().latest("drift.bench")
    assert entry is not None
    assert entry["measured"] is False        # CPU suite: model evidence
    assert entry["measured_seconds"] > 0
    assert entry["predicted_seconds"] > 0
    # the flight timeline saw it too
    assert any(e["kind"] == "drift"
               for e in get_flight_recorder().events())


def test_drift_gate_behavior(tmp_path):
    br = _tools_import("bench_report")
    # within band: pass
    ok = {"s1": [{"predicted_seconds": 1.0, "measured_seconds": 1.5,
                  "measured": True}]}
    status, msg = br.check_drift(ok)
    assert status == br.PASS
    # out of band: flagged
    bad = {"s1": [{"predicted_seconds": 1.0, "measured_seconds": 10.0,
                   "measured": True}]}
    status, msg = br.check_drift(bad)
    assert status == br.REGRESS and "s1" in msg
    # modeled-only: NEVER gated, even when wildly off
    modeled = {"s1": [{"predicted_seconds": 1.0,
                       "measured_seconds": 100.0, "measured": False}]}
    status, msg = br.check_drift(modeled)
    assert status == br.PASS and "never drift-gated" in msg
    # the newest entry wins: an old out-of-band entry superseded by a
    # within-band recalibration passes
    recal = {"s1": [
        {"predicted_seconds": 1.0, "measured_seconds": 10.0,
         "measured": True},
        {"predicted_seconds": 1.0, "measured_seconds": 1.2,
         "measured": True}]}
    assert br.check_drift(recal)[0] == br.PASS
    # widened band: the bad ledger passes
    assert br.check_drift(bad, band=20.0)[0] == br.PASS
    # missing ledger: skip (exit-0 no-op)
    assert br.check_drift(None)[0] == br.SKIP


def test_bench_report_check_wires_drift_gate(tmp_path, capsys):
    br = _tools_import("bench_report")
    with open(tmp_path / "DRIFT_LEDGER.json", "w") as f:
        json.dump({"schema": 1, "entries": {
            "bench.fused": [{"predicted_seconds": 1.0,
                             "measured_seconds": 50.0,
                             "measured": True}]}}, f)
    assert br.main(["--dir", str(tmp_path), "--check"]) == 1
    out = capsys.readouterr().out
    assert "MODEL DRIFT" in out
    # the same dir passes with measured flipped off
    with open(tmp_path / "DRIFT_LEDGER.json", "w") as f:
        json.dump({"schema": 1, "entries": {
            "bench.fused": [{"predicted_seconds": 1.0,
                             "measured_seconds": 50.0,
                             "measured": False}]}}, f)
    assert br.main(["--dir", str(tmp_path), "--check"]) == 0


def test_capture_fn_records_prediction_side():
    from raft_tpu.core.resources import DeviceResources

    res = DeviceResources(seed=0)
    x = jnp.ones((32, 32), jnp.float32)
    rec = res.profiler.capture_fn("drift.capture",
                                  lambda a: (a * 2).sum(), x)
    if rec is None:
        pytest.skip("backend exposes no cost analysis")
    entry = timeline.get_drift_ledger().latest("drift.capture")
    assert entry is not None and entry["measured"] is False
    assert entry["measured_seconds"] is None  # prediction-only


def test_ivf_search_marker_probed_frac_is_the_host_count():
    """The ``ivf_search`` marker's probed fraction is the probed-rows
    counter's host count over the batch's rows — the probe table the
    plan already fetched, no device reduction of its own."""
    from raft_tpu.ann import build_ivf_flat, search_ivf_flat
    from raft_tpu.ann.ivf_flat import PROBED_ROWS, _coarse_probe
    from raft_tpu.core.resources import DeviceResources

    res = DeviceResources(seed=0)
    rng = np.random.default_rng(5)
    X = rng.normal(size=(1500, 16)).astype(np.float32)
    Q = rng.normal(size=(16, 16)).astype(np.float32)
    idx = build_ivf_flat(res, X, n_lists=8, max_iter=3, seed=0)
    search_ivf_flat(res, idx, Q, 5, n_probes=2, fine_scan="list")
    probes = np.asarray(_coarse_probe(res, idx.centroids, Q, 2))
    host_rows = int(np.asarray(idx.sizes)[probes].sum())
    assert obs.get_registry().counter(PROBED_ROWS).value == host_rows
    marker = [e for e in get_flight_recorder().events()
              if e["kind"] == "marker" and e["name"] == "ivf_search"]
    assert len(marker) == 1
    assert marker[0]["probed_frac"] == round(host_rows / (16 * 1500), 6)


# ------------------------------------------------------- static pinning
def test_event_sites_pinned_to_known_kinds():
    ci = _tools_import("check_instrumented")
    # every emitter kind the gate table claims must exist in the live
    # vocabulary, and the static parse agrees with the import
    assert set(ci.EMITTER_KINDS.values()) <= set(KNOWN_EVENT_KINDS)
    root = os.path.join(os.path.dirname(__file__), "..")
    assert ci._known_event_kinds(root) == set(KNOWN_EVENT_KINDS)
    # every hot-path and fault-site module is event-gated
    for rel in set(ci.HOT_PATHS) | set(ci.FAULT_SITES):
        assert rel in ci.EVENT_SITES, rel
    # the repo is clean
    assert ci.check_event_sites() == []


def test_event_sites_gate_catches_silent_module(tmp_path):
    ci = _tools_import("check_instrumented")
    mod = tmp_path / "silent.py"
    mod.write_text("def hot(x):\n    return x\n")
    errors = ci.check_event_sites(
        root=str(tmp_path), sites={"silent.py": ("instrument",)},
        hot_paths={"silent.py": ("hot",)}, fault_sites={})
    assert any("instrument" in e and "silent.py" in e for e in errors)
    # a hot-path module with NO EVENT_SITES entry is itself an error
    errors = ci.check_event_sites(
        root=str(tmp_path), sites={},
        hot_paths={"silent.py": ("hot",)}, fault_sites={})
    assert any("no EVENT_SITES entry" in e for e in errors)


def test_drift_band_pinned_across_tools():
    br = _tools_import("bench_report")
    assert br.DRIFT_BAND == timeline.DRIFT_BAND


def test_env_disabled_process_gets_null_recorder():
    """RAFT_TPU_DISABLE_TRACING: the process-global recorder IS the
    shared null object — instrumented calls, fixtures and faults emit
    nothing and attach empty tails (the <2% Fixture.run overhead
    contract reduces to one boolean per would-be event)."""
    import subprocess

    code = (
        "import os\n"
        "from raft_tpu.observability import flight\n"
        "from raft_tpu.observability.timeline import (emit_fault,"
        " record_drift)\n"
        "from raft_tpu.core.error import DeviceError\n"
        "assert flight.get_flight_recorder() is flight.NULL_FLIGHT\n"
        "emit_fault('s', 'oom')\n"
        "record_drift('s', predicted_seconds=1.0, measured_seconds=1.0)\n"
        "assert len(flight.get_flight_recorder()) == 0\n"
        "assert DeviceError('x').flight_tail == []\n"
        "assert flight.post_mortem('t', directory='.') is None\n"
        "print('OK')\n")
    env = dict(os.environ, RAFT_TPU_DISABLE_TRACING="1",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


# ------------------------------------------------- histogram satellites
def test_prometheus_explicit_inf_bucket():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = export_prometheus(reg)
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert 'lat_seconds_count 3' in text


def test_compile_bucket_preset_reaches_300s():
    from raft_tpu.observability import (COMPILE_TIME_BUCKETS,
                                        DEFAULT_TIME_BUCKETS)

    assert max(DEFAULT_TIME_BUCKETS) == 30.0   # documented ceiling
    assert max(COMPILE_TIME_BUCKETS) == 300.0
    assert COMPILE_TIME_BUCKETS == tuple(sorted(COMPILE_TIME_BUCKETS))
