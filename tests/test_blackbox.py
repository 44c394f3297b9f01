"""Black-box forensics plane tests (ISSUE 17).

The crash-durable mmap ring end to end: CRC frame roundtrip and ring
wraparound, torn-tail tolerance (the WAL recovery contract applied to
a ring), clean-shutdown epilogue vs violent death, the flight-recorder
mirror, the hang watchdog's stall detection + thread-stack dumps, the
restart path (``/crashz`` + ``raft_tpu_unclean_shutdowns_total``), the
``raft_tpu_flight_dropped_total`` sync, the ``bench_report --check
[blackbox]`` gate — and the SIGKILL forensics proof itself: a worker
killed mid-traffic leaves a blackbox from which ``tools/postmortem.py``
reconstructs ≥ 64 flight events, the final metrics snapshot and
verdict ``crash`` (tests/_blackbox_worker.py documents the protocol).
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from raft_tpu.observability import blackbox as bb_mod
from raft_tpu.observability.blackbox import (BlackBox, reconstruct,
                                             scan_ring, HEADER_SIZE,
                                             REC_DUMP, REC_EPILOGUE,
                                             REC_EVENT, REC_SNAPSHOT)
from raft_tpu.observability.flight import (FlightRecorder,
                                           get_flight_recorder,
                                           set_flight_recorder,
                                           sync_dropped_metric,
                                           FLIGHT_DROPPED,
                                           KNOWN_EVENT_KINDS)
from raft_tpu.observability.metrics import (MetricsRegistry, get_registry,
                                            set_registry)
from raft_tpu.observability.timeline import (emit_epilogue, emit_marker,
                                             emit_stall)
from raft_tpu.observability.watchdog import (Watchdog, dump_stacks,
                                             format_stacks)

_TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TESTS_DIR)
_WORKER = os.path.join(_TESTS_DIR, "_blackbox_worker.py")
_POSTMORTEM = os.path.join(_REPO, "tools", "postmortem.py")

rng = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _fresh_forensics():
    """Every test starts and ends with no installed blackbox, a fresh
    flight recorder and a fresh metrics registry (process-global state:
    the snapshots a blackbox records carry the whole registry, so the
    metrics earlier files left in this worker would crowd dumps out of
    the small test rings)."""
    prev_bb = bb_mod.install(None)
    if prev_bb is not None:
        prev_bb.close(reason="test-cleanup")
    prev_rec = set_flight_recorder(FlightRecorder(capacity=512))
    prev_reg = set_registry(MetricsRegistry())
    yield
    leaked = bb_mod.install(None)
    if leaked is not None:
        leaked.close(reason="test-cleanup")
    set_registry(prev_reg)
    set_flight_recorder(prev_rec)


def _abandon(bb):
    """Release a BlackBox handle WITHOUT an epilogue — the in-test
    stand-in for dying violently (close() would flip the file's
    verdict back to clean)."""
    with bb._lock:
        bb._closed = True
    bb._mm.close()
    bb._file.close()


def _counter_value(name, **labels):
    total = 0.0
    for m in get_registry().collect():
        if m.name == name and all(m.labels.get(k) == v
                                  for k, v in labels.items()):
            total += m.value
    return total


# ------------------------------------------------------------------
# the ring writer/reader core
def test_frame_roundtrip_preserves_order_and_payload(tmp_path):
    p = str(tmp_path / "bb.bin")
    bb = BlackBox(p, nbytes=1 << 15)
    for i in range(20):
        assert bb.append_event({"kind": "marker", "name": f"m{i}",
                                "i": i})
    bb.close(reason="clean")
    rep = reconstruct(p)
    assert rep is not None and rep["verdict"] == "clean"
    assert [e["i"] for e in rep["events"] if e["kind"] == "marker"] \
        == list(range(20))
    assert rep["torn_records"] == 0
    assert rep["epilogue"]["reason"] == "clean"
    assert rep["pid"] == os.getpid()


def test_ring_wraparound_keeps_newest_records(tmp_path):
    p = str(tmp_path / "bb.bin")
    bb = BlackBox(p, nbytes=1 << 14)          # minimum ring: 16 KiB
    n = 600                                    # far beyond capacity
    for i in range(n):
        bb.append_event({"kind": "marker", "name": f"m{i}", "i": i})
    stats = bb.stats()
    assert stats["records"] == n
    assert stats["bytes_written"] > bb.ring_bytes  # proof it wrapped
    bb.close(reason="clean")
    rep = reconstruct(p)
    idxs = [e["i"] for e in rep["events"]]
    # newest survive, oldest evicted, recovered suffix is contiguous
    assert idxs[-1] == n - 1
    assert idxs[0] > 0
    assert idxs == list(range(idxs[0], n))
    assert rep["verdict"] == "clean"


def test_oversized_record_dropped_not_raised(tmp_path):
    p = str(tmp_path / "bb.bin")
    bb = BlackBox(p, nbytes=1 << 14)
    assert not bb.append_event({"kind": "marker", "name": "big",
                                "blob": "x" * (1 << 15)})
    assert bb.stats()["dropped_oversize"] == 1
    assert bb.append_event({"kind": "marker", "name": "small"})
    bb.close(reason="clean")
    assert reconstruct(p)["verdict"] == "clean"


def test_torn_tail_tolerated_prefix_intact(tmp_path):
    """Corrupt the newest frame at the write frontier (what a violent
    death mid-append leaves): every earlier record must survive, and
    with no epilogue the verdict is crash — WAL torn-tail recovery,
    on a ring."""
    p = str(tmp_path / "bb.bin")
    bb = BlackBox(p, nbytes=1 << 15)
    for i in range(30):
        bb.append_event({"kind": "marker", "name": f"m{i}", "i": i})
    frontier = HEADER_SIZE + bb.stats()["bytes_written"]
    bb._mm.flush()                     # simulate death: no close()
    with open(p, "r+b") as f:
        f.seek(frontier - 25)          # tear into the newest frame
        f.write(b"\xde\xad" * 10)
    rep = reconstruct(p)
    assert rep["verdict"] == "crash"
    assert rep["epilogue"] is None
    assert rep["torn_records"] >= 1
    idxs = [e["i"] for e in rep["events"]]
    assert idxs == list(range(29))     # every record before the tear
    _abandon(bb)


def test_scan_ring_ignores_garbage_bytes():
    recs, torn = scan_ring(b"\x00" * 4096)
    assert recs == [] and torn == 0
    recs, torn = scan_ring(b"RBX1garbage-without-a-valid-frame" * 50)
    assert recs == []
    assert torn > 0


# ------------------------------------------------------------------
# the flight mirror + event kinds
def test_mirror_captures_flight_events_and_epilogue(tmp_path):
    p = str(tmp_path / "bb.bin")
    booted = bb_mod.boot(path=p, nbytes=1 << 15)
    assert booted.created and booted.prior is None
    assert bb_mod.active() is booted.recorder
    emit_marker("hello", i=1)
    emit_stall("serving-batcher", age_s=2.5, inflight=4)
    bb_mod.shutdown(reason="clean")
    assert bb_mod.active() is None
    rep = reconstruct(p)
    kinds = [e["kind"] for e in rep["events"]]
    assert "marker" in kinds and "stall" in kinds
    assert rep["verdict"] == "clean"
    # the stall evidence never outranks a real epilogue
    assert rep["stall_events"][0]["age_s"] == 2.5


def test_new_event_kinds_registered():
    assert "stall" in KNOWN_EVENT_KINDS
    assert "epilogue" in KNOWN_EVENT_KINDS
    emit_stall("x")
    emit_epilogue("clean")
    kinds = [e["kind"] for e in get_flight_recorder().events()]
    assert kinds == ["stall", "epilogue"]


def test_disabled_mode_identity(tmp_path, monkeypatch):
    """No env knob, no constructor path → no blackbox, no file, and
    the mirror hook is a no-op None test."""
    monkeypatch.delenv("RAFT_TPU_BLACKBOX_PATH", raising=False)
    booted = bb_mod.boot()
    assert booted == (None, None, False)
    from raft_tpu.observability import flight

    assert flight._mirror is None
    emit_marker("cheap")               # must not touch any file
    assert get_flight_recorder().seq == 1
    assert list(tmp_path.iterdir()) == []


def test_boot_preserves_unclean_prior_file(tmp_path):
    p = str(tmp_path / "bb.bin")
    dead = BlackBox(p, nbytes=1 << 14)
    dead.append_event({"kind": "marker", "name": "doomed"})
    dead._mm.flush()                   # violent death: no epilogue
    booted = bb_mod.boot(path=p, nbytes=1 << 14)
    try:
        assert booted.prior is not None
        assert booted.prior["verdict"] == "crash"
        assert booted.prior["preserved_path"] == p + ".prev"
        assert os.path.exists(p + ".prev")
        # the new run's file is fresh, not the dead one's
        assert booted.recorder.stats()["records"] == 0
    finally:
        bb_mod.shutdown()
        _abandon(dead)


def test_flight_dropped_metric_sync():
    rec = FlightRecorder(capacity=16)
    set_flight_recorder(rec)
    before = _counter_value(FLIGHT_DROPPED)
    for i in range(40):
        rec.record("marker", f"m{i}")
    assert sync_dropped_metric(rec) == rec.dropped == 24
    assert _counter_value(FLIGHT_DROPPED) - before == 24
    # second sync folds only the delta — the counter stays monotone
    for i in range(4):
        rec.record("marker", f"n{i}")
    assert sync_dropped_metric(rec) == 28
    assert _counter_value(FLIGHT_DROPPED) - before == 28
    assert sync_dropped_metric(rec) == 28
    assert _counter_value(FLIGHT_DROPPED) - before == 28


# ------------------------------------------------------------------
# the hang watchdog
class _FakeEngine:
    def __init__(self):
        self.table = []

    def inflight_requests(self):
        return list(self.table)


def test_watchdog_detects_silent_heartbeat(tmp_path):
    p = str(tmp_path / "bb.bin")
    bb_mod.boot(path=p, nbytes=1 << 15)
    clock = {"t": 100.0}
    eng = _FakeEngine()
    wd = Watchdog(engine=eng, interval_s=0.05, stall_after_s=0.2,
                  clock=lambda: clock["t"])
    assert wd.enabled
    wd.beat("serving-batcher")
    clock["t"] += 0.1
    assert wd.tick() is None           # healthy: within stall_after_s
    clock["t"] += 0.5                  # heartbeat goes silent
    dump = wd.tick()
    assert dump is not None
    assert dump["trigger"]["source"] == "serving-batcher"
    assert dump["trigger"]["age_s"] == pytest.approx(0.6)
    names = [t["name"] for t in dump["threads"]]
    assert "MainThread" in names
    assert wd.tick() is None           # latched: one dump per episode
    assert wd.stalls == 1
    wd.beat("serving-batcher")         # recovery clears the latch
    assert wd.tick() is None
    clock["t"] += 0.5
    assert wd.tick() is not None       # a NEW episode dumps again
    assert wd.stalls == 2
    stalls = [e for e in get_flight_recorder().events()
              if e.get("kind") == "stall"]
    assert len(stalls) == 2
    bb_mod.shutdown(reason="clean")
    rep = reconstruct(p)
    assert len(rep["stall_dumps"]) == 2
    assert rep["verdict"] == "clean"   # it recovered and closed


def test_watchdog_detects_overdue_inflight_requests(tmp_path):
    p = str(tmp_path / "bb.bin")
    bb_mod.boot(path=p, nbytes=1 << 15)
    clock = {"t": 10.0}
    eng = _FakeEngine()
    wd = Watchdog(engine=eng, interval_s=0.05, stall_after_s=0.2,
                  clock=lambda: clock["t"])
    wd.beat()
    eng.table = [{"rid": 3, "kind": "query", "rows": 4,
                  "age_s": 1.5, "deadline_in_s": -1.0}]
    dump = wd.tick()                   # beat fresh, but deadline blown
    assert dump is not None
    assert dump["trigger"]["source"] == "inflight-deadline"
    assert dump["inflight"][0]["rid"] == 3
    bb_mod.shutdown(reason="hang-test")
    rep = reconstruct(p)
    assert rep["inflight"][0]["rid"] == 3
    bb_mod.install(None)


def test_watchdog_disabled_without_interval(monkeypatch):
    monkeypatch.delenv("RAFT_TPU_WATCHDOG_S", raising=False)
    wd = Watchdog()
    assert not wd.enabled
    assert wd.start()._thread is None  # start is a no-op
    monkeypatch.setenv("RAFT_TPU_WATCHDOG_S", "0.5")
    assert Watchdog().interval_s == 0.5


def test_stack_dump_sees_all_threads():
    d = dump_stacks()
    names = [t["name"] for t in d["threads"]]
    assert "MainThread" in names
    text = format_stacks(d)
    assert "thread dump" in text and "MainThread" in text
    assert f"pid {os.getpid()}" in text


# ------------------------------------------------------------------
# the SIGKILL forensics proof (the acceptance criterion)
def test_sigkill_mid_traffic_postmortem_reconstructs(tmp_path):
    """Kill the serving worker inside a live flush; the blackbox it
    leaves must reconstruct — through tools/postmortem.py — verdict
    ``crash``, ≥ 64 flight events and the final metrics snapshot."""
    bb_path = str(tmp_path / "blackbox.bin")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("RAFT_TPU_FAULTS", None)
    proc = subprocess.run(
        [sys.executable, _WORKER, bb_path, "40"], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == -signal.SIGKILL, (
        f"worker survived (rc={proc.returncode}):\n"
        f"{proc.stderr[-2000:]}")
    assert "COMPLETED" not in proc.stdout

    post = subprocess.run(
        [sys.executable, _POSTMORTEM, bb_path, "--json"], env=env,
        capture_output=True, text=True, timeout=120)
    assert post.returncode == 2, post.stderr[-2000:]  # unclean death
    rep = json.loads(post.stdout)
    assert rep["verdict"] == "crash"
    assert rep["epilogue"] is None
    assert len(rep["events"]) >= 64, (
        f"only {len(rep['events'])} events recovered")
    kinds = {e["kind"] for e in rep["events"]}
    assert "serving" in kinds and "flow" in kinds
    snap = rep["final_snapshot"]
    assert snap is not None
    assert any(k.startswith("raft_tpu_serving_requests_total")
               for k in snap["metrics"]), sorted(snap["metrics"])[:10]

    # human rendering + Perfetto tail export from the same file
    trace_path = str(tmp_path / "tail.json")
    post2 = subprocess.run(
        [sys.executable, _POSTMORTEM, bb_path, "--trace", trace_path,
         "--last-s", "30"], env=env, capture_output=True, text=True,
        timeout=120)
    assert post2.returncode == 2
    assert "verdict:  CRASH" in post2.stdout
    assert "epilogue: MISSING" in post2.stdout
    with open(trace_path) as f:
        trace = json.load(f)
    assert isinstance(trace["traceEvents"], list)
    assert trace["traceEvents"]
    assert trace["raft_tpu"]["verdict"] == "crash"


# ------------------------------------------------------------------
# the restart surface: /crashz, /stackz, unclean counter
@pytest.fixture(scope="module")
def index():
    from raft_tpu.distance.knn_fused import prepare_knn_index

    y = rng.normal(size=(2048, 32)).astype(np.float32)
    return prepare_knn_index(y, passes=3, T=256, Qb=32, g=2)


def test_engine_restart_surfaces_prior_crash(tmp_path, index):
    import urllib.request

    from raft_tpu.serving import ServingEngine

    p = str(tmp_path / "bb.bin")
    dead = BlackBox(p, nbytes=1 << 14)
    for i in range(5):
        dead.append_event({"kind": "marker", "name": f"m{i}"})
    dead._mm.flush()                   # epilogue-less: violent death
    before = _counter_value(bb_mod.UNCLEAN_SHUTDOWNS)
    eng = ServingEngine(index, k=8, buckets=(8, 16),
                        flush_interval_s=0.002, blackbox_path=p,
                        debug_port=0)
    eng.start()
    try:
        assert eng.crash_report is not None
        assert eng.crash_report["verdict"] == "crash"
        assert _counter_value(bb_mod.UNCLEAN_SHUTDOWNS) - before == 1
        assert eng.blackbox is not None
        port = eng.stats()["debugz_port"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/crashz", timeout=10) as r:
            crashz = json.loads(r.read())
        assert crashz["verdict"] == "crash"
        assert crashz["records"] == 5
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stackz", timeout=10) as r:
            stackz = r.read().decode()
        assert "thread dump" in stackz
        assert "serving-batcher" in stackz
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/statusz", timeout=10) as r:
            statusz = r.read().decode()
        assert "forensics (blackbox / watchdog)" in statusz
        assert "prior run       verdict=crash" in statusz
        fut = eng.submit(rng.normal(size=(4, 32)).astype(np.float32))
        eng.flush()
        fut.result(timeout=60)
    finally:
        eng.stop()
        _abandon(dead)
    # THIS run closed cleanly: its blackbox says so, and the dead
    # run's evidence was preserved next to it
    rep = reconstruct(p)
    assert rep["verdict"] == "clean"
    assert len(rep["events"]) > 0
    assert os.path.exists(p + ".prev")
    assert reconstruct(p + ".prev")["verdict"] == "crash"


def test_engine_without_blackbox_has_no_forensics(index):
    from raft_tpu.serving import ServingEngine

    eng = ServingEngine(index, k=8, buckets=(8, 16),
                        flush_interval_s=0.002)
    eng.start()
    try:
        st = eng.stats()
        assert "blackbox" not in st
        assert "prior_crash" not in st
        assert eng.blackbox is None and eng.crash_report is None
    finally:
        eng.stop()


def test_engine_watchdog_beats_under_traffic(tmp_path, index):
    from raft_tpu.serving import ServingEngine

    eng = ServingEngine(index, k=8, buckets=(8, 16),
                        flush_interval_s=0.002,
                        blackbox_path=str(tmp_path / "bb.bin"),
                        watchdog_s=0.05)
    eng.start()
    try:
        futs = [eng.submit(rng.normal(size=(n, 32)).astype(np.float32))
                for n in (1, 4, 8)]
        eng.flush()
        for f in futs:
            f.result(timeout=60)
        wd = eng._watchdog
        assert wd is not None
        st = wd.stats()
        assert st["enabled"]
        assert "serving-batcher" in st["heartbeats"]
        assert st["stalls"] == 0       # healthy traffic never stalls
        assert eng.inflight_requests() == []
        assert "watchdog" in eng.stats()
    finally:
        eng.stop()
    rep = reconstruct(str(tmp_path / "bb.bin"))
    assert rep["verdict"] == "clean"


# ------------------------------------------------------------------
# the bench gate
def _tools_import(name):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def test_check_blackbox_gate():
    br = _tools_import("bench_report")
    rounds = lambda rec: [(1, "BENCH_SERVING.json", rec)]  # noqa: E731
    ok_block = {"records": 500, "bytes_written": 100_000,
                "append_seconds": 0.001, "overhead_frac": 0.0004}
    status, msg = br.check_blackbox(rounds(
        {"ok": True, "blackbox": ok_block}))
    assert status == br.PASS and "0.04" in msg
    status, _ = br.check_blackbox(rounds({"ok": True}))
    assert status == br.MISSING_BASELINE
    status, msg = br.check_blackbox(rounds(
        {"ok": True, "blackbox": dict(ok_block, overhead_frac=0.02)}))
    assert status == br.REGRESS and "2.00" in msg
    status, _ = br.check_blackbox(rounds(
        {"ok": False, "blackbox": ok_block}))
    assert status == br.SKIP
    status, _ = br.check_blackbox(rounds(
        {"ok": True, "skipped": True}))
    assert status == br.SKIP
    status, _ = br.check_blackbox([])
    assert status == br.SKIP


def test_env_knobs_declared():
    from raft_tpu.core import env

    for name in ("RAFT_TPU_BLACKBOX_PATH", "RAFT_TPU_BLACKBOX_BYTES",
                 "RAFT_TPU_WATCHDOG_S"):
        assert name in env.KNOBS
    assert env.get("RAFT_TPU_BLACKBOX_BYTES") >= 1 << 14
