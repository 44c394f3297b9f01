"""bench_report MULTICHIP aggregation + staleness flags (ISSUE 4
satellites): the trajectory must absorb both the bare early dryrun
rounds and the perf-carrying bench_sharded rounds, --check must gate
the multichip trend, and named single-shot artifacts older than the
last-good commit must be flagged stale instead of read as current."""

import json
import os
import subprocess
import sys

import pytest


def _tools_import(name):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def _mc_record(value=40.0, ok=True, measured=True, busbw=0.3,
               skipped=False):
    return {
        "metric": "sharded_knn top-64 2048x10000000x256 over 8 shards",
        "value": value, "unit": "GB/s", "n_devices": 8, "ok": ok,
        "skipped": skipped, "measured": measured,
        "strategies": {
            "allgather": {"busbw_frac": busbw * 0.8,
                          "model_ici_bytes_per_device": 1.0e7},
            "tournament": {"busbw_frac": busbw,
                           "model_ici_bytes_per_device": 4.0e6},
        },
    }


def test_collect_multichip_mixes_schemas(tmp_path):
    br = _tools_import("bench_report")
    _write(tmp_path / "MULTICHIP_r01.json",
           {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
            "tail": ""})
    _write(tmp_path / "MULTICHIP_r02.json",
           {"n": 2, "parsed": _mc_record()})
    rounds = br.collect_multichip(str(tmp_path))
    assert [n for n, _, _ in rounds] == [1, 2]
    assert rounds[0][2]["ok"] is True
    assert rounds[1][2]["strategies"]["tournament"]["busbw_frac"] == 0.3
    out = br.multichip_trajectory(rounds)
    assert "r01" in out and "r02" in out and "30.00" in out


def test_check_multichip_gates_failure_and_trend(tmp_path):
    br = _tools_import("bench_report")
    # newest ok=false → regression
    _write(tmp_path / "MULTICHIP_r01.json", _mc_record())
    _write(tmp_path / "MULTICHIP_r02.json", _mc_record(ok=False))
    status, msg = br.check_multichip(br.collect_multichip(str(tmp_path)))
    assert status == br.REGRESS and "ok=false" in msg
    # measured value drop beyond threshold → regression
    _write(tmp_path / "MULTICHIP_r02.json", _mc_record(value=20.0))
    status, msg = br.check_multichip(br.collect_multichip(str(tmp_path)))
    assert status == br.REGRESS and "MULTICHIP REGRESSION" in msg
    # holding value but collapsed busbw fraction → regression
    _write(tmp_path / "MULTICHIP_r02.json",
           _mc_record(value=40.0, busbw=0.05))
    status, msg = br.check_multichip(br.collect_multichip(str(tmp_path)))
    assert status == br.REGRESS and "BUSBW" in msg
    # healthy round passes
    _write(tmp_path / "MULTICHIP_r02.json", _mc_record(value=41.0))
    status, _ = br.check_multichip(br.collect_multichip(str(tmp_path)))
    assert status == br.PASS


def test_check_multichip_modeled_rounds_not_speed_gated(tmp_path):
    br = _tools_import("bench_report")
    _write(tmp_path / "MULTICHIP_r01.json", _mc_record(value=40.0))
    # a modeled (off-TPU) round with a lower number is NOT a regression
    _write(tmp_path / "MULTICHIP_r02.json",
           _mc_record(value=1.0, measured=False))
    status, msg = br.check_multichip(br.collect_multichip(str(tmp_path)))
    assert status == br.PASS and "modeled" in msg
    # skipped rounds are a no-op
    _write(tmp_path / "MULTICHIP_r03.json",
           _mc_record(ok=False, skipped=True))
    status, _ = br.check_multichip(br.collect_multichip(str(tmp_path)))
    assert status == br.SKIP


def test_check_exit_code_combines_bench_and_multichip(tmp_path, capsys):
    br = _tools_import("bench_report")
    metric = "fused top-64"
    _write(tmp_path / "BENCH_r01.json",
           {"parsed": {"metric": metric, "value": 470.0, "unit": "GB/s"}})
    _write(tmp_path / "BENCH_LAST_GOOD.json",
           {"metric": metric, "value": 460.0, "unit": "GB/s"})
    _write(tmp_path / "MULTICHIP_r01.json", _mc_record())
    _write(tmp_path / "MULTICHIP_r02.json", _mc_record(ok=False))
    assert br.main(["--dir", str(tmp_path), "--check"]) == 1
    out = capsys.readouterr().out
    assert "[multichip]" in out
    # fixing the multichip round makes the combined gate pass
    _write(tmp_path / "MULTICHIP_r02.json", _mc_record(value=45.0))
    assert br.main(["--dir", str(tmp_path), "--check"]) == 0
    capsys.readouterr()


def test_artifact_staleness_flags(tmp_path):
    br = _tools_import("bench_report")
    # no git in tmp_path → unknown, never a crash
    _write(tmp_path / "SELECT_K_MATRIX.json", {"x": 1})
    entries = br.artifact_staleness(
        str(tmp_path), {"git_commit": "deadbeef"})
    by_name = {e["artifact"]: e["status"] for e in entries}
    assert by_name["SELECT_K_MATRIX.json"] == "unknown"
    assert by_name["PALLAS_SMOKE.json"] == "missing"
    # no baseline at all → unknown for existing files
    entries = br.artifact_staleness(str(tmp_path), None)
    assert {e["status"] for e in entries} <= {"unknown", "missing"}


def test_repo_staleness_section_renders():
    """On the real repo the section must render and flag at least the
    artifacts whose last-touching commit predates the last-good one
    (PALLAS_SMOKE/BUSBW_BENCH at the time this shipped)."""
    root = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "bench_report.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "multichip trajectory" in proc.stdout
    assert "named artifacts" in proc.stdout


def test_bench_sharded_artifact_schema():
    """The committed MULTICHIP_SHARDED.json must carry per-strategy
    modeled ICI bytes + busbw fraction, and be honestly stamped
    measured=false when produced off-TPU."""
    root = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(root, "MULTICHIP_SHARDED.json")
    if not os.path.exists(path):
        pytest.skip("no MULTICHIP_SHARDED.json committed")
    with open(path) as f:
        rec = json.load(f)
    assert rec["ok"] is True
    assert isinstance(rec["measured"], bool)
    for strat in ("allgather", "tournament"):
        s = rec["strategies"][strat]
        assert s["model_ici_bytes_per_device"] > 0
        assert "busbw_frac" in s
        if not rec["measured"]:
            assert rec["degraded"] is True
            assert s.get("parity_vs_oracle") is True


# ------------------------------------------------------------------
# the ANN frontier gate (ISSUE 8)
# ------------------------------------------------------------------

def _ann_record(best=0.97, ok=True, degen=True, measured=False,
                search_ms=300.0, k=10, degr=0):
    rec = {
        "metric": "ivf_flat recall@10 frontier 256x20000x32",
        "value": best, "unit": f"recall@{k}", "ok": ok, "k": k,
        "skipped": False, "measured": measured,
        "recall_floor": 0.95, "degenerate_exact": degen,
        "search_ms": search_ms,
        "frontier": [
            {"n_lists": 16, "n_probes": 1, "recall_at_k": best - 0.02,
             "probed_frac": 0.06, "search_ms": search_ms},
            {"n_lists": 16, "n_probes": 2, "recall_at_k": best,
             "probed_frac": 0.12, "search_ms": search_ms * 1.5},
        ],
    }
    if degr:
        rec["resilience_degradations"] = degr
    return rec


def test_check_ann_gates_floor_and_degenerate(tmp_path):
    br = _tools_import("bench_report")
    # recall floor violated → regress even on a modeled round
    _write(tmp_path / "BENCH_ANN.json", _ann_record(best=0.80))
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.REGRESS and "RECALL" in msg
    # degenerate-exact violated → regress
    _write(tmp_path / "BENCH_ANN.json", _ann_record(degen=False))
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.REGRESS and "DEGENERATE" in msg
    # healthy modeled round passes and is not speed-gated
    _write(tmp_path / "BENCH_ANN.json", _ann_record())
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.PASS and "not speed-gated" in msg


def test_check_ann_degraded_round_files_skip(tmp_path):
    """A degraded ROUND file is history — never gated, never
    baseline material."""
    br = _tools_import("bench_report")
    _write(tmp_path / "ANN_r01.json", _ann_record(best=0.5, ok=False,
                                                  degr=2))
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.SKIP and "degrad" in msg


def test_check_ann_degraded_named_artifact_regresses(tmp_path):
    """ISSUE 15 satellite: a degraded NAMED artifact (the committed
    BENCH_ANN.json) must REGRESS, not SKIP — committed evidence can
    never be an outage round (the refresh path refuses to write one;
    one landing anyway is a bug the gate must catch)."""
    br = _tools_import("bench_report")
    _write(tmp_path / "BENCH_ANN.json", _ann_record(degr=2))
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.REGRESS and "NAMED-ARTIFACT DEGRADED" in msg
    # the bare degraded flag (no counted steps) regresses the same way
    rec = _ann_record()
    rec["degraded"] = True
    _write(tmp_path / "BENCH_ANN.json", rec)
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.REGRESS and "NAMED-ARTIFACT DEGRADED" in msg
    # clean named artifact still passes
    _write(tmp_path / "BENCH_ANN.json", _ann_record())
    status, _ = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.PASS


def test_check_ann_recall_trend_and_measured_speed(tmp_path):
    br = _tools_import("bench_report")
    # recall drop beyond the slack vs the previous round → regress
    _write(tmp_path / "ANN_r01.json", _ann_record(best=0.99))
    _write(tmp_path / "BENCH_ANN.json", _ann_record(best=0.95))
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.REGRESS and "TREND" in msg
    # measured rounds speed-gate search_ms at the floor point
    _write(tmp_path / "ANN_r01.json", _ann_record(measured=True,
                                                  search_ms=100.0))
    _write(tmp_path / "BENCH_ANN.json", _ann_record(measured=True,
                                                    search_ms=200.0))
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.REGRESS and "SEARCH-TIME" in msg
    # within threshold: pass, with the ms trend in the message
    _write(tmp_path / "BENCH_ANN.json", _ann_record(measured=True,
                                                    search_ms=105.0))
    status, msg = br.check_ann(br.collect_ann(str(tmp_path)))
    assert status == br.PASS


def test_committed_ann_artifact_schema():
    """The committed BENCH_ANN.json must carry the frontier the gate
    reads: recall + probed fraction + modeled GB/s per point, the
    degenerate-exact verdict, and an honest measured stamp."""
    root = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(root, "BENCH_ANN.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_ANN.json committed")
    with open(path) as f:
        rec = json.load(f)
    assert rec["ok"] is True
    assert rec["degenerate_exact"] is True
    assert isinstance(rec["measured"], bool)
    # committed evidence is never an outage round (ISSUE 15): degraded
    # means "walked a resilience ladder", and the named artifact must
    # be clean — the refresh path refuses to write it otherwise
    assert rec["degraded"] is False
    assert not rec.get("resilience_degradations")
    # the PQ compressed-tier block: ratio ≤ 0.10× of f32, id parity
    # after the mandatory rescore, and the 100M-row single-chip fit
    pq = rec["pq"]
    assert pq["ok"] is True
    assert pq["pq_bytes_ratio"] <= 0.10
    assert pq["scale_model"]["fits_hbm"] is True
    assert pq["scale_model"]["rows"] >= 100_000_000
    assert pq["scale_model"]["model_index_bytes"] \
        <= pq["scale_model"]["hbm_bytes"]
    assert any(p["recall_at_k"] >= rec["recall_floor"]
               and p["pq_bytes_ratio"] <= 0.10
               and p["pq_bits"] == 8 for p in pq["frontier"])
    best = max(p["recall_at_k"] for p in rec["frontier"])
    assert best >= rec["recall_floor"]
    for p in rec["frontier"]:
        assert 0 <= p["probed_frac"] <= 1
        assert p["modeled_effective_gbps"] >= 0
        assert p["n_probes"] <= p["n_lists"] or \
            p["recall_at_k"] == best
    br = _tools_import("bench_report")
    assert "BENCH_ANN.json" in br.NAMED_ARTIFACTS


# ------------------------------------------------------------------
# mutation gate (ISSUE 11): BENCH_MUTATION / MUTATION_r*
# ------------------------------------------------------------------

def _mut_record(ok=True, recall=1.0, cycles=2, measured=False,
                p99=50.0, qps=100.0, degr=0):
    rec = {
        "metric": "mutation top-8 mixed load 120 reads over 2048x32",
        "value": qps, "unit": "req/s", "ok": ok, "skipped": False,
        "measured": measured, "recall": recall, "recall_floor": 0.95,
        "compaction_cycles": cycles, "p99_ms": p99,
        "throughput_qps": qps, "reads_during_fold": 3,
    }
    if degr:
        rec["resilience_degradations"] = degr
    return rec


def test_check_mutation_gates_ok_cycles_and_recall(tmp_path):
    br = _tools_import("bench_report")
    # nothing to gate → skip (pass-or-no-op)
    status, _ = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.SKIP
    # ok=false → regress
    _write(tmp_path / "BENCH_MUTATION.json", _mut_record(ok=False))
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.REGRESS and "ok=false" in msg
    # zero compaction cycles → regress (no fill→fold→swap evidence)
    _write(tmp_path / "BENCH_MUTATION.json", _mut_record(cycles=0))
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.REGRESS and "COMPACTION" in msg
    # recall below the floor → regress even on a modeled round
    _write(tmp_path / "BENCH_MUTATION.json", _mut_record(recall=0.90))
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.REGRESS and "RECALL" in msg
    # degraded run → skip
    _write(tmp_path / "BENCH_MUTATION.json", _mut_record(degr=1))
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.SKIP and "degrad" in msg
    # healthy modeled round passes, not speed-gated
    _write(tmp_path / "BENCH_MUTATION.json", _mut_record())
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.PASS and "not speed-gated" in msg


def test_check_mutation_measured_speed_trend(tmp_path):
    br = _tools_import("bench_report")
    _write(tmp_path / "MUTATION_r01.json",
           _mut_record(measured=True, p99=100.0, qps=100.0))
    _write(tmp_path / "BENCH_MUTATION.json",
           _mut_record(measured=True, p99=200.0, qps=100.0))
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.REGRESS and "P99" in msg
    _write(tmp_path / "BENCH_MUTATION.json",
           _mut_record(measured=True, p99=105.0, qps=50.0))
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.REGRESS and "THROUGHPUT" in msg
    _write(tmp_path / "BENCH_MUTATION.json",
           _mut_record(measured=True, p99=105.0, qps=95.0))
    status, msg = br.check_mutation(br.collect_mutation(str(tmp_path)))
    assert status == br.PASS
    out = br.mutation_trajectory(br.collect_mutation(str(tmp_path)))
    assert "r01" in out and "recall" in out


def test_committed_mutation_artifact_schema():
    """The committed BENCH_MUTATION.json must carry what the gate
    reads: ok, recall ≥ floor, ≥ 1 full compaction cycle, and an
    honest measured stamp."""
    root = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(root, "BENCH_MUTATION.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_MUTATION.json committed")
    with open(path) as f:
        rec = json.load(f)
    assert rec["ok"] is True
    assert isinstance(rec["measured"], bool)
    assert rec["recall"] >= rec["recall_floor"] >= 0.95
    assert rec["compaction_cycles"] >= 1
    assert rec["quality"]["fixup_rate"] >= 0.0
    br = _tools_import("bench_report")
    assert "BENCH_MUTATION.json" in br.NAMED_ARTIFACTS


# ------------------------------------------------------------------
# the durability/recovery gate (ISSUE 12)
def _rec_record(ok=True, zero_loss=True, rec_ms=150.0, bound=120000.0,
                qps=400.0, overhead=1.4, measured=False, degr=0):
    rec = {
        "metric": "durability sync=batch 12x16 writes + recovery over "
                  "512x32",
        "value": qps, "unit": "req/s", "ok": ok, "skipped": False,
        "measured": measured, "zero_acked_loss": zero_loss,
        "recovery_ms": rec_ms, "recovery_ms_bound": bound,
        "recovery_points": [{"wal_records": 48, "recovery_ms": rec_ms,
                             "replayed_records": 48,
                             "truncated_bytes": 0}],
        "throughput_qps": qps, "durable_overhead_x": overhead,
        "wal_sync": "batch",
    }
    if degr:
        rec["resilience_degradations"] = degr
    return rec


def test_check_recovery_gates_loss_flag_and_bound(tmp_path):
    br = _tools_import("bench_report")
    # nothing to gate → skip (pass-or-no-op)
    status, _ = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.SKIP
    # ok=false → regress
    _write(tmp_path / "BENCH_RECOVERY.json", _rec_record(ok=False))
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.REGRESS and "ok=false" in msg
    # a lost acked write (or a missing flag) → regress even modeled
    _write(tmp_path / "BENCH_RECOVERY.json",
           _rec_record(zero_loss=False))
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.REGRESS and "ACKED-LOSS" in msg
    rec = _rec_record()
    del rec["zero_acked_loss"]
    rec["recovery_ms"] = 1.0   # keep the record parseable by its keys
    _write(tmp_path / "BENCH_RECOVERY.json", rec)
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.REGRESS and "ACKED-LOSS" in msg
    # recovery over the artifact's own bound → regress
    _write(tmp_path / "BENCH_RECOVERY.json",
           _rec_record(rec_ms=130000.0))
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.REGRESS and "TIME" in msg
    # degraded run → skip
    _write(tmp_path / "BENCH_RECOVERY.json", _rec_record(degr=1))
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.SKIP and "degrad" in msg
    # healthy modeled round passes, not speed-gated
    _write(tmp_path / "BENCH_RECOVERY.json", _rec_record())
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.PASS and "not speed-gated" in msg


def test_check_recovery_measured_speed_trend(tmp_path):
    br = _tools_import("bench_report")
    _write(tmp_path / "RECOVERY_r01.json",
           _rec_record(measured=True, qps=400.0))
    _write(tmp_path / "BENCH_RECOVERY.json",
           _rec_record(measured=True, qps=100.0))
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.REGRESS and "THROUGHPUT" in msg
    _write(tmp_path / "BENCH_RECOVERY.json",
           _rec_record(measured=True, qps=390.0))
    status, msg = br.check_recovery(br.collect_recovery(str(tmp_path)))
    assert status == br.PASS
    out = br.recovery_trajectory(br.collect_recovery(str(tmp_path)))
    assert "r01" in out and "0-loss" in out


def test_committed_recovery_artifact_schema():
    """The committed BENCH_RECOVERY.json must carry what the gate
    reads: ok, zero_acked_loss, recovery time within its own bound,
    and an honest measured stamp."""
    root = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(root, "BENCH_RECOVERY.json")
    if not os.path.exists(path):
        pytest.skip("no BENCH_RECOVERY.json committed")
    with open(path) as f:
        rec = json.load(f)
    assert rec["ok"] is True
    assert rec["zero_acked_loss"] is True
    assert isinstance(rec["measured"], bool)
    assert rec["recovery_ms"] <= rec["recovery_ms_bound"]
    assert rec["recovery_points"]
    assert rec["wal_sync"] in ("always", "batch", "none")
    br = _tools_import("bench_report")
    assert "BENCH_RECOVERY.json" in br.NAMED_ARTIFACTS
