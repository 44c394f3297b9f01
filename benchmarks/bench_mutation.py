#!/usr/bin/env python
"""Closed-loop mixed read/write benchmark — the BENCH_MUTATION artifact.

Drives a MUTABLE serving engine (:mod:`raft_tpu.mutable` behind
``ServingEngine(mutable=True)``) with concurrent reader clients and a
writer client: readers submit query batches and wait (the closed loop
of ``bench_serving.py``), the writer streams upsert/delete batches
through the SAME queue — enough of them to push the delta slab past
``RAFT_TPU_COMPACT_THRESHOLD`` and drive at least one FULL compaction
cycle (delta fill → background fold → snapshot swap → delta rebase)
under live traffic.

Measures and gates (via ``tools/bench_report.py --check [mutation]``):

- **read p50/p99 latency** (client-side, submit → result) and
  read/write throughput — bounded p99 across the compaction cycle is
  the tentpole's latency claim (speed trend-gated on measured rounds
  only, like every artifact);
- **compaction_cycles ≥ 1** — an artifact that never folded proved
  nothing about the mutation plane;
- **recall ≥ 0.95 floor** — after the load quiesces, a sample of
  queries is re-scored against a FROM-SCRATCH rebuild oracle over the
  live rows (the bench maintains its own host-side model of what
  should be live). The brute mutable plane is exact, so this measures
  the plane end to end, not an approximation budget;
- **reads_during_fold** — reads that COMPLETED inside a
  compact_start→compact_swap window (flight-recorder timestamps):
  direct evidence that queries never block on the compactor
  (reported; the structural proof lives in tests/test_mutable.py).

Off-TPU runs use a small shape and stamp ``"measured": false``.
Prints ONE JSON line and writes ``BENCH_MUTATION.json``.
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
OUT_PATH = os.path.join(_REPO, "BENCH_MUTATION.json")
SCHEMA = 1
RECALL_FLOOR = 0.95

# per-platform shapes:
# (index rows, d, k, n_reads, readers, write_batches, upserts/batch)
TPU_SHAPE = (1_000_000, 128, 64, 1500, 6, 40, 256)
CPU_SHAPE = (2048, 32, 8, 120, 3, 10, 32)


def _fold_windows():
    """(start_ts, end_ts) pairs of completed compaction folds, from the
    mutation flight stream."""
    from raft_tpu.observability import get_flight_recorder

    starts, windows = [], []
    for e in get_flight_recorder().events():
        if e.get("kind") != "mutation":
            continue
        if e.get("name") == "compact_start":
            starts.append(e.get("ts", 0.0))
        elif e.get("name") == "compact_swap" and starts:
            windows.append((starts.pop(0), e.get("ts", 0.0)))
    return windows


def main(argv=None) -> int:
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--reads", type=int, default=None)
    p.add_argument("--readers", type=int, default=None)
    p.add_argument("--write-batches", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    from raft_tpu.distance.knn_fused import knn_fused
    from raft_tpu.resilience import degradation_count
    from raft_tpu.serving import ServingEngine

    measured = jax.default_backend() == "tpu"
    (m, d, k, n_reads, readers, write_batches, wbatch) = \
        TPU_SHAPE if measured else CPU_SHAPE
    if args.reads is not None:
        n_reads = args.reads
    if args.readers is not None:
        readers = args.readers
    if args.write_batches is not None:
        write_batches = args.write_batches
    # the compaction watermark sits well under the total write volume
    # so the load crosses at least one full cycle
    threshold = max(64, (write_batches * wbatch) // 2)

    rng = np.random.default_rng(args.seed)
    Y = rng.normal(size=(m, d)).astype(np.float32)
    kw = (dict() if measured
          else dict(passes=3, T=256, Qb=32, g=2, buckets=(8, 16, 32),
                    flush_interval_s=0.002))
    engine = ServingEngine(Y, k=k, mutable=True,
                           compact_threshold=threshold,
                           delta_cap=2 * threshold, **kw)
    ladder = engine.buckets
    model = {int(i): Y[i] for i in range(m)}
    model_lock = threading.Lock()

    degr0 = degradation_count()
    engine.start()
    # prime the delta/merge programs BEFORE the measured window so the
    # first live write doesn't pay their compiles
    prime_row = rng.normal(size=(1, d)).astype(np.float32)
    engine.upsert([m], prime_row).result(timeout=120)
    model[m] = prime_row[0]
    engine.query(rng.normal(size=(4, d)).astype(np.float32))

    sizes = np.clip(rng.poisson(max(2, ladder[0]), n_reads), 1,
                    ladder[-1])
    queries = [rng.normal(size=(int(n), d)).astype(np.float32)
               for n in sizes]

    read_lat, write_lat, errors = [], [], []
    lat_lock = threading.Lock()
    counter = {"next": 0}
    next_ext = [m + 1]

    def reader(cid: int):
        while True:
            with lat_lock:
                i = counter["next"]
                if i >= n_reads:
                    return
                counter["next"] = i + 1
            t0 = time.perf_counter()
            try:
                engine.query(queries[i], timeout=120)
            except Exception as e:
                with lat_lock:
                    errors.append(f"read: {type(e).__name__}: {e}"[:200])
                continue
            with lat_lock:
                read_lat.append(time.perf_counter() - t0)

    def writer():
        w_rng = np.random.default_rng(args.seed + 1)
        for b in range(write_batches):
            with model_lock:
                ext0 = next_ext[0]
                next_ext[0] += wbatch
                live = list(model)
            # ~25% overwrites of live ids, the rest fresh inserts
            n_over = max(1, wbatch // 4)
            over = w_rng.choice(live, n_over, replace=False)
            fresh = np.arange(ext0, ext0 + wbatch - n_over)
            ids = np.concatenate([over, fresh]).astype(np.int64)
            rows = w_rng.normal(size=(wbatch, d)).astype(np.float32)
            dels = w_rng.choice(
                [e for e in live if e not in set(int(o) for o in over)],
                max(1, wbatch // 8), replace=False)
            t0 = time.perf_counter()
            try:
                engine.upsert(ids, rows).result(timeout=120)
                engine.delete(dels).result(timeout=120)
            except Exception as e:
                with lat_lock:
                    errors.append(
                        f"write: {type(e).__name__}: {e}"[:200])
                continue
            with lat_lock:
                write_lat.append(time.perf_counter() - t0)
            with model_lock:
                for e, r in zip(ids, rows):
                    model[int(e)] = r
                for e in dels:
                    model.pop(int(e), None)

    t_start = time.perf_counter()
    threads = [threading.Thread(target=reader, args=(c,))
               for c in range(readers)]
    threads.append(threading.Thread(target=writer))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.flush()
    wall = time.perf_counter() - t_start
    engine.mutable.wait_for_compaction(timeout=300)

    cycles = engine.mutable.compactions
    st = engine.stats()

    # ---- quiescent recall vs the from-scratch rebuild oracle --------
    exts = np.asarray(sorted(model), np.int64)
    live_rows = np.stack([model[int(e)] for e in exts])
    recalls = []
    parity_ok = True
    oracle_kw = {} if measured else dict(passes=3, T=256, Qb=32, g=2)
    for i in range(0, n_reads, max(1, n_reads // 16)):
        q = queries[i]
        try:
            _, si = engine.query(q, timeout=120)
            _, oi = knn_fused(q, live_rows, k, **oracle_kw)
            oe = exts[np.asarray(oi)]
            hits = [len(set(int(v) for v in si[r] if v >= 0)
                        & set(int(v) for v in oe[r]))
                    for r in range(q.shape[0])]
            recalls.append(float(np.mean(hits)) / k)
        except Exception as e:
            parity_ok = False
            errors.append(f"recall probe: {e}"[:200])
    recall = float(np.mean(recalls)) if recalls else 0.0

    # reads completed inside a fold window (flight evidence)
    windows = _fold_windows()
    reads_during_fold = 0
    try:
        from raft_tpu.observability import get_flight_recorder

        for e in get_flight_recorder().events():
            if e.get("kind") == "serving" and e.get("name") == "flush":
                ts = e.get("ts", 0.0)
                if any(a <= ts <= b for a, b in windows):
                    reads_during_fold += 1
    except Exception:
        pass

    engine.stop()

    from raft_tpu.observability.metrics import percentile

    lat_ms = np.sort(np.asarray(read_lat)) * 1e3
    wlat_ms = np.sort(np.asarray(write_lat)) * 1e3
    ok = (not errors and parity_ok and len(read_lat) == n_reads
          and cycles >= 1 and recall >= RECALL_FLOOR)
    degr = degradation_count() - degr0
    mst = st.get("mutable", {})
    result = {
        "metric": f"mutation top-{k} mixed load {n_reads} reads x "
                  f"{readers} readers + {write_batches}x{wbatch} writes "
                  f"over {m}x{d} ({jax.default_backend()})",
        "value": round(len(read_lat) / wall, 2) if wall else 0.0,
        "unit": "req/s",
        "schema": SCHEMA,
        "ok": bool(ok),
        "skipped": False,
        "measured": measured,
        "degraded": not measured,
        "p50_ms": round(percentile(lat_ms, 50), 3)
        if len(lat_ms) else None,
        "p99_ms": round(percentile(lat_ms, 99), 3)
        if len(lat_ms) else None,
        "write_p99_ms": round(percentile(wlat_ms, 99), 3)
        if len(wlat_ms) else None,
        "throughput_qps": round(len(read_lat) / wall, 2) if wall
        else None,
        "n_reads": n_reads,
        "n_write_batches": write_batches,
        "recall": round(recall, 4),
        "recall_floor": RECALL_FLOOR,
        "compaction_cycles": int(cycles),
        "compact_threshold": threshold,
        "reads_during_fold": int(reads_during_fold),
        "delta_rows_final": mst.get("delta_rows"),
        "tombstones_final": mst.get("tombstones"),
        "generation": mst.get("generation"),
        "live_rows": int(exts.shape[0]),
        "buckets": list(ladder),
        "shed": st.get("shed", 0),
        "errors": errors[:8],
        "platform": jax.default_backend(),
        "git_commit": git_commit(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    try:
        from raft_tpu.observability.quality import quality_block

        qb = quality_block()
        if qb is not None:
            result["quality"] = qb
    except Exception as e:
        print(f"bench_mutation: quality block failed: {e}",
              file=sys.stderr)
    if degr:
        result["resilience_degradations"] = degr
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
