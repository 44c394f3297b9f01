#!/usr/bin/env python
"""Sharded fused-KNN multichip benchmark — the MULTICHIP perf artifact.

Measures (or, off-TPU, deterministically models) the database-sharded
fused KNN pipeline (:mod:`raft_tpu.distance.knn_sharded`) over every
available device, PER MERGE STRATEGY, and writes one artifact that
records next to each strategy:

- the modeled per-device ICI wire bytes
  (:func:`raft_tpu.observability.costmodel.ici_traffic_model`),
- the achieved (or modeled) **busbw fraction** — wire bytes / (time ×
  the chip generation's ICI peak from :mod:`raft_tpu.utils.arch`) —
  the ICI sibling of the HBM ``roofline_frac`` every BENCH artifact
  carries,
- end-to-end seconds and effective GB/s (the bench.py convention:
  nq·m·4 bytes scanned per unit time).

Off-TPU runs execute a small CORRECTNESS pass (8 virtual CPU devices,
parity vs the single-device oracle) and stamp ``"measured": false`` —
the numbers are the cost model's, never a CPU-interpret wall clock
masquerading as chip evidence. ``tools/bench_report.py`` aggregates
these artifacts (as ``MULTICHIP_r*.json`` driver rounds) into the
trajectory and gates the multichip trend with ``--check``.

Prints ONE JSON line and writes ``MULTICHIP_SHARDED.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
from raft_tpu.utils.provenance import git_commit  # noqa: E402
OUT_PATH = os.path.join(_REPO, "MULTICHIP_SHARDED.json")
TRACE_PATH = os.path.join(_REPO, "MULTICHIP_SHARDED_TRACE.json")
DRIFT_PATH = os.path.join(_REPO, "DRIFT_LEDGER.json")
SCHEMA = 1

# per-platform shapes: the TPU shape is the north-star workload scaled
# to p shards; the CPU shape keeps the interpret-mode kernels in
# seconds territory while still crossing every merge round
TPU_SHAPE = (2048, 10_000_000, 256, 64)
CPU_SHAPE = (64, 4096, 32, 8)


def _ensure_virtual_devices(n: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def main() -> int:
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        _ensure_virtual_devices()
    import jax

    measured = jax.default_backend() == "tpu" and len(jax.devices()) > 1
    if not measured and jax.default_backend() != "tpu":
        _ensure_virtual_devices()

    from raft_tpu.benchmark import Fixture
    from raft_tpu.core.resources import ensure_resources
    from raft_tpu.distance.knn_fused import knn_fused
    from raft_tpu.distance.knn_sharded import (knn_fused_sharded,
                                               prepare_knn_index_sharded)
    from raft_tpu.observability.costmodel import (ici_time_model,
                                                  ici_traffic_model)
    from raft_tpu.parallel import make_mesh
    from raft_tpu.tune.sharded import ShardedCandidate, sharded_time_model
    from raft_tpu.utils.arch import chip_spec

    res = ensure_resources(None)
    devs = jax.devices()
    p = len(devs)
    spec = chip_spec()
    mesh = make_mesh({"x": p}, devices=devs)
    nq, m, d, k = TPU_SHAPE if measured else CPU_SHAPE
    rng = np.random.default_rng(0)
    if measured:
        from raft_tpu.random import RngState, make_blobs

        X, _ = make_blobs(res, RngState(0), m, d, n_clusters=64,
                          cluster_std=2.0)
        Q = X[:nq]
    else:
        X = rng.normal(size=(m, d)).astype(np.float32)
        Q = rng.normal(size=(nq, d)).astype(np.float32)
    eff_bytes = nq * m * 4.0
    ok = True
    strategies = {}
    # correctness oracle for the off-TPU pass (small shape only)
    oracle = None
    if not measured:
        ov, oi = knn_fused(Q, np.asarray(X), k=k, passes=3, T=512,
                           Qb=32, g=2)
        oracle = (np.asarray(ov), np.asarray(oi))
        idx = prepare_knn_index_sharded(X, mesh=mesh, T=512, Qb=32, g=2,
                                        res=res)
    else:
        idx = prepare_knn_index_sharded(X, mesh=mesh, grid_order="db",
                                        res=res)
    fx = Fixture(res=res, reps=3 if measured else 1)

    for strat in ("allgather", "tournament"):
        entry = {}
        try:
            wire = ici_traffic_model(p, nq, k, strat)
            entry["model_ici_bytes_per_device"] = \
                wire["wire_bytes_per_device"]
            entry["model_ici_rounds"] = wire["rounds"]
            if measured:
                r = fx.run(lambda q: knn_fused_sharded(
                    q, idx, k, mesh=mesh, merge=strat)[0], Q,
                    name=f"bench_sharded.{strat}")
                secs = r["seconds"]
                entry["seconds"] = round(secs, 5)
                for f in ("bytes_accessed", "flops", "roofline_frac",
                          "bound"):
                    if f in r:
                        entry[f] = r[f]
            else:
                sv, si = knn_fused_sharded(Q, idx, k, mesh=mesh,
                                           merge=strat)
                parity = np.array_equal(np.asarray(sv), oracle[0])
                entry["parity_vs_oracle"] = bool(parity)
                ok = ok and parity
                cand = ShardedCandidate(512, 32, 2, strat, 1, 3)
                secs = sharded_time_model((nq, m, d, k), p, cand,
                                          spec)["predicted_seconds"]
                entry["predicted_seconds"] = secs
                entry["model_merge_seconds"] = ici_time_model(
                    p, nq, k, strat, spec)["merge_seconds"]
                # prediction side of the drift ledger: the modeled
                # ranking this site trusts until a measured TPU round
                # recalibrates it (measured=False — never drift-gated)
                from raft_tpu.observability.timeline import record_drift

                record_drift(f"bench_sharded.{strat}",
                             predicted_seconds=secs,
                             predicted_bytes=wire[
                                 "wire_bytes_per_device"],
                             measured=False, platform="cpu")
            entry["gbps"] = round(eff_bytes / secs / 1e9, 2) if secs \
                else None
            # busbw fraction: achieved ICI rate over the generation's
            # aggregate peak — the wire sibling of roofline_frac
            ici_bw = spec.ici_bw or spec.hbm_bw
            entry["busbw_frac"] = round(
                wire["wire_bytes_per_device"] / (secs * ici_bw), 6) \
                if secs else None
        except Exception as e:
            ok = False
            entry["error"] = f"{type(e).__name__}: {e}"[:300]
        strategies[strat] = entry

    # quantized-index-streaming evidence: modeled int8/bf16 streamed-
    # bytes ratio for the per-shard geometry + int8-vs-f32 id parity
    # through the sharded pipeline (off-TPU: the full CPU parity pass;
    # on TPU: a sampled check rides the same call path). Gated by
    # bench_report --check (ratio ≤ 0.55, ok stays true).
    quantized = None
    try:
        from raft_tpu.observability.costmodel import (
            quantized_bytes_ratio)

        ratio = quantized_bytes_ratio(
            nq, -(-m // p), d, k, idx.T, idx.Qb, idx.g, idx.passes,
            idx.grid_order if idx.grid_order != "query" else "db")
        idx_q8 = prepare_knn_index_sharded(
            X, mesh=mesh, T=idx.T, Qb=idx.Qb, g=idx.g,
            grid_order="db", db_dtype="int8", res=res)
        qv, qi = knn_fused_sharded(Q, idx_q8, k, mesh=mesh)
        fv, fi = knn_fused_sharded(Q, idx, k, mesh=mesh)
        q8_parity = bool(np.array_equal(
            np.sort(np.asarray(qi), axis=1),
            np.sort(np.asarray(fi), axis=1)))
        ok = ok and q8_parity
        quantized = {"db_dtype": "int8",
                     "quantized_y_ratio": round(float(ratio), 4),
                     "ok": q8_parity}
    except Exception as e:
        ok = False
        quantized = {"error": f"{type(e).__name__}: {e}"[:300],
                     "ok": False}

    best = max((s for s in strategies.values() if s.get("gbps")),
               key=lambda s: s["gbps"], default={})
    result = {
        "metric": f"sharded_knn top-{k} {nq}x{m}x{d} over {p} shards "
                  f"({jax.default_backend()}, best strategy)",
        "value": best.get("gbps", 0.0),
        "unit": "GB/s",
        "schema": SCHEMA,
        "n_devices": p,
        "ok": ok,
        "skipped": False,
        "measured": measured,
        # calibrated-vs-modeled provenance: measured rounds feed the
        # drift ledger; modeled rounds never drift-gate
        "drift_checked": measured,
        "degraded": not measured,
        "chip": spec.name,
        "ici_bw": spec.ici_bw,
        "db_dtype": "bf16",
        "quantized": quantized,
        "strategies": strategies,
        "platform": jax.default_backend(),
        "git_commit": git_commit(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # quality block (ISSUE 10): per-shard certificate/fixup counters
    # drained from this run's sharded dispatches — gated by
    # bench_report --check [quality]
    try:
        from raft_tpu.observability.quality import quality_block

        qb = quality_block()
        if qb is not None:
            result["quality"] = qb
    except Exception as e:
        print(f"bench_sharded: quality block failed: {e}",
              file=sys.stderr)
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    # Perfetto trace artifact: the flight-recorder ring of this run —
    # micro-batch kernel vs merge-collective overlap becomes VISUALLY
    # verifiable (open at https://ui.perfetto.dev) — plus the durable
    # drift ledger. Neither may fail the benchmark.
    try:
        from raft_tpu.observability import export_perfetto
        from raft_tpu.observability.timeline import (DriftLedger,
                                                     get_drift_ledger)

        trace = export_perfetto()
        trace["raft_tpu"] = {"artifact": "bench_sharded.py",
                             "drift_checked": measured}
        with open(TRACE_PATH, "w") as f:
            json.dump(trace, f, indent=1, default=str)
            f.write("\n")
        if len(get_drift_ledger()):
            disk = DriftLedger.load(DRIFT_PATH)
            disk.merge(get_drift_ledger())
            disk.save(DRIFT_PATH)
    except Exception as e:
        print(f"bench_sharded: flight/drift artifact write failed: {e}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
