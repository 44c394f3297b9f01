#!/usr/bin/env python
"""Driver benchmark: fused L2 pairwise-distance + top-k throughput per chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N}

Config (BASELINE configs[1], scaled to one chip's HBM): brute-force KNN of
``N_QUERIES`` queries against an ``N_INDEX``×``DIM`` index, k=64, through
raft_tpu.distance.knn (streamed fused distance + top-k merge). The metric
follows the reference's select_k benchmark convention: effective bytes =
the f32 distance matrix the pipeline scans (n_queries × n_index × 4) per
unit time. Baseline: A100's 1555 GB/s HBM stream rate — the practical
ceiling for RAFT's select_k on A100 (bandwidth-bound kernel); the driver's
north star is vs_baseline ≥ 2.

It measures on a TPU and fails without one — unless ``JAX_PLATFORMS=cpu``
was set explicitly, which runs a small harness rehearsal whose line
names the ``cpu`` platform. Timings are host-clock spans ending in
``block_until_ready`` (``raft_tpu.benchmark.Fixture``).
"""

import json
import os
import sys
import time

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))
_TRACE_PATH = os.path.join(_REPO_DIR, "BENCH_TRACE.json")
_DRIFT_PATH = os.path.join(_REPO_DIR, "DRIFT_LEDGER.json")
SCHEMA = 2  # bumped when the headline metric's meaning changes
#             (v2: headline = certified-bf16 p1 since round 3; p3 extras)


def _write_flight_artifacts(drift_checked: bool) -> None:
    """Perfetto trace of the run (BENCH_TRACE.json — micro-batch
    overlap and compile/dispatch timing become visually verifiable at
    https://ui.perfetto.dev) + the durable drift ledger (this process's
    model-vs-measured entries merged into DRIFT_LEDGER.json, which
    ``bench_report --check`` gates). Must never fail the bench."""
    try:
        from raft_tpu.observability import export_perfetto
        from raft_tpu.observability.timeline import (DriftLedger,
                                                     get_drift_ledger)

        trace = export_perfetto()
        trace["raft_tpu"] = {"artifact": "bench.py",
                             "drift_checked": drift_checked}
        with open(_TRACE_PATH, "w") as f:
            json.dump(trace, f, indent=1, default=str)
            f.write("\n")
        if len(get_drift_ledger()):
            disk = DriftLedger.load(_DRIFT_PATH)
            disk.merge(get_drift_ledger())
            disk.save(_DRIFT_PATH)
    except Exception as e:
        print(f"bench: flight/drift artifact write failed: {e}",
              file=sys.stderr)


def main():
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(f"bench: no TPU (JAX found {platform!r}); set "
                         f"JAX_PLATFORMS=cpu for a CPU rehearsal")
    import raft_tpu
    from raft_tpu import distance
    from raft_tpu.random import RngState, make_blobs
    from raft_tpu.utils.provenance import git_commit

    res = raft_tpu.device_resources()

    # 1M x 128 f32 index (512 MB) on the chip; the CPU rehearsal is small
    if platform == "tpu":
        n_index, dim, n_queries, k, tile = 1_000_000, 128, 2048, 64, 8192
        reps = 3
    else:
        n_index, dim, n_queries, k, tile = 50_000, 64, 256, 64, 8192
        reps = 1

    from raft_tpu.benchmark import Fixture

    X, _ = make_blobs(res, RngState(0), n_index, dim, n_clusters=64,
                      cluster_std=2.0)
    Q = X[:n_queries]
    jax.block_until_ready(X)

    fx = Fixture(res=res, reps=reps)
    # build/query split: index operands (pad + bf16 hi/lo split + norm
    # carriers) prepared ONCE — the metric times steady-state query
    # throughput, like the reference's select_k benchmark times the
    # kernel rather than data prep. Gated by the SAME eligibility
    # predicate knn()'s auto-routing uses (on the CPU rehearsal the
    # streamed sweep runs on the raw matrix).
    # Two modes, both certified (docs/MIGRATION.md "fused KNN score
    # precision"): passes=1 — the HEADLINE — is certified-exact w.r.t.
    # the bf16 score function with f32 rescoring of the candidates
    # (recall vs f32 ≥0.99 measured); passes=3 is certified-exact
    # w.r.t. f32 scores (bf16x3 contraction), reported alongside.
    from raft_tpu.distance.knn_fused import KnnIndex, fused_eligible
    from raft_tpu.observability import costmodel

    knn_index, knn_index_p3 = X, None
    if fused_eligible(n_index, dim):
        knn_index = distance.prepare_knn_index(X, passes=1)
        knn_index_p3 = distance.prepare_knn_index(X, passes=3)
    dt_p3 = None
    dt_af = None
    # analytic HBM-traffic model for the config actually measured (the
    # predicted half of the predicted-vs-measured bytes evidence; None
    # on the raw-matrix CPU rehearsal)
    traffic_model = None
    fused_cfg = None
    if isinstance(knn_index, KnnIndex):
        fused_cfg = {"T": knn_index.T, "Qb": knn_index.Qb,
                     "g": knn_index.g,
                     "grid_order": knn_index.grid_order,
                     "passes": knn_index.passes,
                     "pbits": knn_index.pbits}
        traffic_model = costmodel.fused_traffic_model(
            n_queries, n_index, dim, k, knn_index.T, knn_index.Qb,
            knn_index.g, knn_index.passes, knn_index.grid_order)
    r1 = fx.run(lambda q: distance.knn(res, knn_index, q, k=k, tile=tile),
                Q, name="bench.fused_knn_p1", model=traffic_model)
    dt = r1["seconds"]
    if knn_index_p3 is not None:
        dt_p3 = fx.run(lambda q: distance.knn(
            res, knn_index_p3, q, k=k, tile=tile), Q)["seconds"]
        # adaptive precision: f32-certified at p1 kernel cost
        # (certify="f32" widens the certificate by the bf16 error
        # bound; margin failures pay the exact fixup)
        dt_af = fx.run(lambda q: distance.knn(
            res, knn_index, q, k=k, tile=tile, certify="f32"),
            Q)["seconds"]

    eff_bytes = n_queries * n_index * 4.0
    gbps = eff_bytes / dt / 1e9
    baseline_gbps = 1555.0  # A100 HBM2e stream rate (v5p-class anchor;
    #                         v5e HBM is ~819 GB/s — the hardware-
    #                         adjusted ceiling for this chip)
    p3_gbps = eff_bytes / dt_p3 / 1e9 if dt_p3 else None
    result = {
        "metric": f"fused_l2nn+select_k top-{k} {n_queries}x{n_index}x{dim} "
                  f"({platform}, certified bf16 p1; f32-exact p3 in "
                  f"extras)",
        "value": round(gbps, 2),
        "unit": "GB/s",
        "vs_baseline": round(gbps / baseline_gbps, 4),
        "schema": SCHEMA,
        "p1_gbps": round(gbps, 2),
        "p1_vs_baseline": round(gbps / baseline_gbps, 4),
        "p3_ms": round(dt_p3 * 1e3, 2) if dt_p3 else None,
        "p3_gbps": round(p3_gbps, 2) if p3_gbps else None,
        "p3_vs_baseline": round(p3_gbps / baseline_gbps, 4) if p3_gbps
        else None,
        "adaptive_f32_ms": round(dt_af * 1e3, 2) if dt_af else None,
        "adaptive_f32_gbps": round(eff_bytes / dt_af / 1e9, 2) if dt_af
        else None,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "git_commit": git_commit(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # perf-evidence fields (PR 2 cost capture + the ISSUE-3 traffic
    # model): the static XLA cost of the measured executable, its
    # %-of-roofline at the measured time, the analytic per-variant HBM
    # bytes of the config that ran, and the predicted-vs-measured
    # ratio. tools/bench_report.py gates the roofline_frac trend.
    for f in ("flops", "bytes_accessed", "arithmetic_intensity",
              "peak_hbm_bytes", "bound", "roofline_frac"):
        if f in r1:
            result[f] = r1[f]
    if fused_cfg is not None:
        result["fused_config"] = fused_cfg
    # quantized-index-streaming evidence (ROADMAP item 2): the headline
    # rows stream bf16; stamp the dtype, the MODELED int8/bf16
    # streamed-bytes ratio for this round's geometry, and an id-parity
    # spot check of the int8 path vs the f32 oracle on a subset —
    # bench_report --check gates ratio ≤ 0.55 and parity ok=true.
    result["db_dtype"] = "bf16"
    try:
        from raft_tpu.distance.knn_fused import knn_fused as _kf
        from raft_tpu.observability.costmodel import (
            quantized_bytes_ratio)

        qcfg = fused_cfg or {"T": 2048, "Qb": 256, "g": 16,
                             "grid_order": "db", "passes": 1}
        q_order = qcfg["grid_order"] if qcfg["grid_order"] != "query" \
            else "db"
        ratio = quantized_bytes_ratio(
            n_queries, n_index, dim, k, qcfg["T"], qcfg["Qb"],
            qcfg["g"], qcfg["passes"], q_order)
        mp, np_, kp = min(n_index, 50_000), min(n_queries, 256), k
        Yp = X[:mp]
        Qp = Q[:np_]
        _, id_f = _kf(Qp, Yp, kp, passes=1, grid_order="db")
        _, id_q = _kf(Qp, Yp, kp, passes=1, grid_order="db",
                      db_dtype="int8")
        import numpy as _np

        parity_ok = bool(_np.array_equal(
            _np.sort(_np.asarray(id_f), axis=1),
            _np.sort(_np.asarray(id_q), axis=1)))
        result["quantized"] = {
            "db_dtype": "int8",
            "quantized_y_ratio": round(float(ratio), 4),
            "parity_rows": mp, "parity_queries": np_,
            "ok": parity_ok,
        }
    except Exception:
        import traceback

        print("bench: quantized evidence failed (block omitted):\n"
              + traceback.format_exc(), file=sys.stderr)
    # quality-telemetry block (ISSUE 10): the certificate/fixup
    # counters this round's fused runs recorded (drained host-side) —
    # the first measured TPU round lands ROADMAP item 2's fixup-rate
    # evidence in this already-gated schema (bench_report [quality])
    try:
        from raft_tpu.observability.quality import quality_block

        qb = quality_block()
        if qb:
            result["quality"] = qb
    except Exception:
        import traceback

        print("bench: quality block failed (omitted):\n"
              + traceback.format_exc(), file=sys.stderr)
    if traffic_model is not None:
        result["model_total_bytes"] = traffic_model["total_bytes"]
        result["model_y_bytes"] = traffic_model["y_bytes"]
        result["model_y_stream_factor"] = traffic_model["y_stream_factor"]
        measured_bytes = result.get("bytes_accessed")
        if isinstance(measured_bytes, (int, float)) and measured_bytes > 0:
            result["model_vs_measured_bytes"] = round(
                traffic_model["total_bytes"] / measured_bytes, 4)

    # drift_checked: True only when this round's MEASURED numbers fed
    # the drift ledger (a real-hardware run of the fused path), so
    # bench_report can tell calibrated rounds from modeled ones
    result["drift_checked"] = platform == "tpu"
    if platform == "tpu":   # a CPU rehearsal leaves the artifacts alone
        _write_flight_artifacts(True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
