#!/usr/bin/env python
"""Closed-loop serving SLO benchmark — the BENCH_SERVING artifact.

Drives the micro-batching query engine (:mod:`raft_tpu.serving`) with a
closed-loop Poisson load: ``--clients`` concurrent clients each submit
one request, wait for its result, think for an Exp(λ) interval, and
repeat — the classic closed-loop generator whose offered load adapts to
the service rate (no coordinated-omission artifacts from an open-loop
schedule the engine can't keep up with).

Measures CLIENT-SIDE latency per request (submit → result) and reports:

- p50/p99 latency (ms) and end-to-end throughput (req/s),
- batch-coalescing evidence: batches dispatched, mean fill, pad rows,
- the AOT warm-up contract: ``compile_misses_after_warmup`` — the
  flight-recorder count of compile-miss events during the steady-state
  window, which MUST be zero (every request rides a pre-warmed bucket;
  ``bench_report --check`` fails the serving gate otherwise),
- correctness parity: a sample of responses re-checked against the
  single-shot ``knn_fused`` oracle (ids + values bit-exact).

Off-TPU runs use a small shape and stamp ``"measured": false`` — the
latency numbers are CPU-interpret wall clock, useful as a trend within
CPU rounds but never chip evidence; ``bench_report --check`` gates
modeled rounds on ``ok`` + the compile-miss contract only.

``--deterministic`` (default off-TPU) replaces wall-clock think times
with a seeded arrival schedule and no sleeps — the reproducible variant
the tier-1 suite runs (tests/test_serving.py); the wall-clock Poisson
path is the ``slow``-marked test and the TPU round.

Prints ONE JSON line and writes ``BENCH_SERVING.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
from raft_tpu.utils.provenance import git_commit  # noqa: E402
OUT_PATH = os.path.join(_REPO, "BENCH_SERVING.json")
TRACE_PATH = os.path.join(_REPO, "BENCH_SERVING_TRACE.json")
SCHEMA = 1

# per-platform shapes: (index rows, d, k, n_requests, clients)
TPU_SHAPE = (1_000_000, 128, 64, 2000, 8)
CPU_SHAPE = (4096, 32, 8, 120, 4)


def _compile_miss_count() -> int:
    """Compile-MISS events currently in the flight ring (timed AOT
    compiles and cache-miss bridge events both carry hit=False)."""
    from raft_tpu.observability import get_flight_recorder

    return sum(1 for e in get_flight_recorder().events()
               if e.get("kind") == "compile" and not e.get("hit", False))


def _slo_block(status) -> dict:
    """The ``"slo"`` artifact block: cumulative availability over the
    whole run (bad-status fraction of ``raft_tpu_serving_requests_total``
    — same semantics as the windowed objective, un-windowed), the
    page-severity burn-alert count, and the end-of-run alert state.
    Gated by ``bench_report --check [slo]``."""
    from raft_tpu.observability.metrics import Counter, get_registry
    from raft_tpu.observability.slo import (BAD_STATUSES, BURN_ALERTS,
                                            REQUESTS)

    total = bad = alerts = 0.0
    burn_by_slo: dict = {}
    for m in get_registry().collect():
        if not isinstance(m, Counter):
            continue
        if m.name == REQUESTS:
            total += m.value
            if m.labels.get("status") in BAD_STATUSES:
                bad += m.value
        elif (m.name == BURN_ALERTS
                and m.labels.get("severity") == "page"):
            alerts += m.value
            name = m.labels.get("slo", "?")
            burn_by_slo[name] = burn_by_slo.get(name, 0) + int(m.value)
    return {
        "availability": (round(1.0 - bad / total, 6) if total else None),
        "total_requests": int(total),
        "bad_requests": int(bad),
        "fast_burn_alerts": int(alerts),
        "fast_burn_by_slo": burn_by_slo,
        "healthy": bool(status.get("healthy", True)) if status else True,
        "active_alerts": (status.get("active_alerts", [])
                          if status else []),
        "covered_s": status.get("covered_s") if status else None,
    }


def run_load(engine, queries, sizes, n_requests: int, clients: int,
             think_mean_s: float, deterministic: bool, seed: int = 0):
    """The closed loop. Returns (latencies, errors, wall_seconds)."""
    latencies, errors = [], []
    lat_lock = threading.Lock()
    counter = {"next": 0}
    rng_master = np.random.default_rng(seed)
    client_seeds = rng_master.integers(0, 2**31, clients)

    def client(cid: int):
        rng = np.random.default_rng(client_seeds[cid])
        while True:
            with lat_lock:
                i = counter["next"]
                if i >= n_requests:
                    return
                counter["next"] = i + 1
            n = int(sizes[i])
            q = queries[i][:n]
            t0 = time.perf_counter()
            try:
                fut = engine.submit(q)
                fut.result(timeout=120)
            except Exception as e:
                with lat_lock:
                    errors.append(f"{type(e).__name__}: {e}"[:200])
                continue
            dt = time.perf_counter() - t0
            with lat_lock:
                latencies.append(dt)
            if not deterministic and think_mean_s > 0:
                time.sleep(float(rng.exponential(think_mean_s)))

    t_start = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.flush()
    return latencies, errors, time.perf_counter() - t_start


def main(argv=None) -> int:
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--clients", type=int, default=None)
    p.add_argument("--requests", type=int, default=None)
    p.add_argument("--think-ms", type=float, default=1.0,
                   help="mean Exp() think time per client (wall-clock "
                        "mode)")
    p.add_argument("--deterministic", action="store_true",
                   help="seeded arrival schedule, no sleeps (the "
                        "reproducible tier-1 variant; default off-TPU)")
    p.add_argument("--shadow-frac", type=float, default=None,
                   help="online recall shadow-sampling fraction "
                        "(default: 1.0 off-TPU so the artifact carries "
                        "a well-populated shadow recall, 0.05 on TPU "
                        "where the oracle re-score costs real chip "
                        "time)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    from raft_tpu.core.resources import DeviceResources
    from raft_tpu.distance.knn_fused import knn_fused, prepare_knn_index
    from raft_tpu.resilience import degradation_count
    from raft_tpu.serving import ServingEngine

    measured = jax.default_backend() == "tpu"
    deterministic = args.deterministic or not measured
    m, d, k, n_requests, clients = TPU_SHAPE if measured else CPU_SHAPE
    if args.requests is not None:
        n_requests = args.requests
    if args.clients is not None:
        clients = args.clients

    shadow_frac = (args.shadow_frac if args.shadow_frac is not None
                   else (0.05 if measured else 1.0))
    rng = np.random.default_rng(args.seed)
    Y = rng.normal(size=(m, d)).astype(np.float32)
    # blackbox riding the load run (ISSUE 17): every flight event is
    # mirrored into a crash-durable mmap ring; the artifact stamps the
    # measured per-record overhead, gated < 1% of request wall time by
    # bench_report --check [blackbox]
    import tempfile

    bb_dir = tempfile.mkdtemp(prefix="bench-blackbox-")
    bb_path = os.path.join(bb_dir, "blackbox.bin")
    if measured:
        idx = prepare_knn_index(Y)
        engine = ServingEngine(idx, k=k, shadow_frac=shadow_frac,
                               blackbox_path=bb_path)
    else:
        idx = prepare_knn_index(Y, passes=3, T=256, Qb=32, g=2)
        engine = ServingEngine(idx, k=k, buckets=(8, 16, 32),
                               flush_interval_s=0.002,
                               shadow_frac=shadow_frac,
                               blackbox_path=bb_path)
    ladder = engine.buckets

    # request mix: ragged sizes across the ladder (Poisson-ish bulk,
    # clamped to the top bucket), pre-generated so the deterministic
    # variant replays bit-identically
    sizes = np.clip(rng.poisson(max(2, ladder[0]), n_requests), 1,
                    ladder[-1])
    queries = [rng.normal(size=(ladder[-1], d)).astype(np.float32)
               for _ in range(min(n_requests, 64))]
    queries = [queries[i % len(queries)] for i in range(n_requests)]

    degr0 = degradation_count()
    engine.start()
    misses_after_warmup0 = _compile_miss_count()

    latencies, errors, wall = run_load(
        engine, queries, sizes, n_requests, clients,
        args.think_ms / 1e3, deterministic, args.seed)
    compile_misses = _compile_miss_count() - misses_after_warmup0

    # correctness parity: a sample of requests re-solved single-shot
    ok = not errors and len(latencies) == n_requests
    parity_checked = 0
    for i in range(0, n_requests, max(1, n_requests // 8)):
        n = int(sizes[i])
        q = queries[i][:n]
        try:
            sv, si = engine.query(q, timeout=120)
            ov, oi = knn_fused(q, idx, k=k)
            if not (np.array_equal(sv, np.asarray(ov))
                    and np.array_equal(si, np.asarray(oi))):
                ok = False
                errors.append(f"parity mismatch at request {i}")
            parity_checked += 1
        except Exception as e:
            ok = False
            errors.append(f"parity probe failed: {e}"[:200])
    if engine.shadow is not None:
        engine.shadow.flush(timeout=60)
    if engine.slo is not None:
        engine.slo.tick(force=True)
    stats = engine.stats()
    ok = ok and compile_misses == 0
    bb_stats = (engine.blackbox.stats()
                if engine.blackbox is not None else None)
    engine.stop()

    from raft_tpu.observability.metrics import percentile

    lat_ms = np.sort(np.asarray(latencies)) * 1e3
    degr = degradation_count() - degr0
    result = {
        "metric": f"serving top-{k} closed-loop {n_requests} reqs x "
                  f"{clients} clients over {m}x{d} "
                  f"({jax.default_backend()})",
        "value": round(len(latencies) / wall, 2) if wall else 0.0,
        "unit": "req/s",
        "schema": SCHEMA,
        "ok": bool(ok),
        "skipped": False,
        "measured": measured,
        "degraded": not measured,
        "deterministic": deterministic,
        "p50_ms": round(percentile(lat_ms, 50), 3)
        if len(lat_ms) else None,
        "p99_ms": round(percentile(lat_ms, 99), 3)
        if len(lat_ms) else None,
        "throughput_qps": round(len(latencies) / wall, 2) if wall
        else None,
        "n_requests": n_requests,
        "n_completed": len(latencies),
        "clients": clients,
        "buckets": list(ladder),
        "batches": stats.get("batches", 0),
        "mean_batch_fill": round(
            float(np.sum(sizes)) / max(1, stats.get("batches", 1))
            / ladder[-1], 4),
        "padded_rows": stats.get("padded_rows", 0),
        "shed": stats.get("shed", 0),
        "expired_in_queue": stats.get("expired_in_queue", 0),
        "compile_misses_after_warmup": int(compile_misses),
        "warmup_compiles": stats.get("warmup_compiles", 0),
        "parity_checked": parity_checked,
        "errors": errors[:8],
        "platform": jax.default_backend(),
        "git_commit": git_commit(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # quality block (ISSUE 10): fixup-rate counters from the serving
    # AOT plane + the shadow sampler's online recall — gated by
    # bench_report --check [quality] (shadow recall ≥ the 0.95 floor)
    try:
        from raft_tpu.observability.quality import quality_block

        qb = quality_block()
        if qb is not None:
            qb["shadow_frac"] = shadow_frac
            result["quality"] = qb
    except Exception as e:
        print(f"bench_serving: quality block failed: {e}",
              file=sys.stderr)
    # SLO block (ISSUE 16): run-cumulative availability + burn-alert
    # state — gated by bench_report --check [slo] (availability ≥ 0.99
    # and no page-severity fast burn on an ok round)
    try:
        result["slo"] = _slo_block(stats.get("slo"))
    except Exception as e:
        print(f"bench_serving: slo block failed: {e}",
              file=sys.stderr)
    # blackbox block (ISSUE 17): the recorder's own overhead evidence —
    # overhead_frac = cumulative mmap-append seconds / total client
    # request wall time. Gated < 1% by bench_report --check [blackbox].
    try:
        if bb_stats is not None:
            req_wall = float(sum(latencies))
            result["blackbox"] = {
                "records": bb_stats["records"],
                "bytes_written": bb_stats["bytes_written"],
                "ring_bytes": bb_stats["ring_bytes"],
                "append_seconds": round(bb_stats["append_seconds"], 6),
                "request_wall_seconds": round(req_wall, 6),
                "overhead_frac": (
                    round(bb_stats["append_seconds"] / req_wall, 6)
                    if req_wall > 0 else None),
            }
        import shutil

        shutil.rmtree(bb_dir, ignore_errors=True)
    except Exception as e:
        print(f"bench_serving: blackbox block failed: {e}",
              file=sys.stderr)
    if degr:
        result["resilience_degradations"] = degr
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    # Perfetto trace: the enqueue → flush → dispatch pipeline of this
    # run, serving events next to compile/dispatch — visual proof of
    # the zero-compile-after-warmup contract. Never fails the bench.
    try:
        from raft_tpu.observability import export_perfetto

        trace = export_perfetto()
        trace["raft_tpu"] = {"artifact": "bench_serving.py",
                             "measured": measured}
        with open(TRACE_PATH, "w") as f:
            json.dump(trace, f, indent=1, default=str)
            f.write("\n")
    except Exception as e:
        print(f"bench_serving: trace write failed: {e}", file=sys.stderr)
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
