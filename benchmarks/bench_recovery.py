#!/usr/bin/env python
"""Durability + crash-recovery benchmark — the BENCH_RECOVERY artifact.

Measures what the ISSUE-12 durability plane costs and what it buys
(gated via ``tools/bench_report.py --check [recovery]``):

- **durable-write overhead**: the same mixed upsert/delete load driven
  through an in-memory ``MutableIndex`` and through one with
  ``durable_dir=`` + ``wal_sync="batch"`` (group-commit fsync) —
  ``durable_overhead_x`` is the wall-time ratio, ``throughput_qps``
  the durable path's write throughput (speed trend-gated on measured
  rounds only, like every artifact);
- **recovery time vs WAL tail length**: for each tail length, a
  durable index absorbs that many mutation records past its genesis
  checkpoint, the process "crashes" (the writer is dropped after its
  fsync horizon — indistinguishable from SIGKILL to the on-disk
  state), and :func:`raft_tpu.mutable.checkpoint.recover` rebuilds it;
  ``recovery_points`` records (tail, recovery ms, replayed records,
  truncated bytes) and ``recovery_ms`` the worst case, gated against
  the artifact's own ``recovery_ms_bound``;
- **zero_acked_loss**: after every recovery, the recovered live state
  (external id → row bytes) is compared EXACTLY against the host-side
  model of every acked write, and a search parity probe runs against a
  from-scratch oracle — any divergence flips the flag (and ``ok``)
  false. Platform-independent, so the gate holds on modeled rounds.

Off-TPU runs use a small shape and stamp ``"measured": false``.
Prints ONE JSON line and writes ``BENCH_RECOVERY.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
from raft_tpu.utils.provenance import git_commit  # noqa: E402
OUT_PATH = os.path.join(_REPO, "BENCH_RECOVERY.json")
SCHEMA = 1

# per-platform shapes:
# (index rows, d, k, write batches, rows/batch, recovery tails [records])
TPU_SHAPE = (1_000_000, 128, 64, 64, 256, (64, 256))
CPU_SHAPE = (512, 32, 8, 12, 16, (16, 48))
# recovery must stay a bounded restart: generous per-platform ceilings
# (the gate is against the artifact's own bound — the trend gate, not
# an absolute wall-clock promise across machines)
TPU_RECOVERY_BOUND_MS = 30_000.0
CPU_RECOVERY_BOUND_MS = 120_000.0


def _live_state(idx) -> dict:
    """ext id → row bytes of everything live (base + delta)."""
    with idx._cond:
        rows, exts = idx._materialize_locked(idx._d_count)
    return {int(e): rows[i].tobytes() for i, e in enumerate(exts)}


def _drive_writes(idx, model, rng, batches: int, wbatch: int,
                  ext0: int) -> float:
    """The mixed load: per batch, one upsert of ``wbatch`` fresh rows +
    one delete of a few existing ids. Returns the wall time; ``model``
    tracks the acked host-side truth."""
    from raft_tpu.mutable import apply_delete, apply_upsert

    t0 = time.perf_counter()
    nxt = ext0
    for b in range(batches):
        ids = np.arange(nxt, nxt + wbatch, dtype=np.int32)
        nxt += wbatch
        rows = rng.normal(size=(wbatch, idx.d_orig)).astype(np.float32)
        apply_upsert(idx, ids, rows)
        for e, r in zip(ids, rows):
            model[int(e)] = r.tobytes()
        live = sorted(model)
        dels = [live[(7 * b + j) % len(live)]
                for j in range(max(1, wbatch // 8))]
        dels = sorted(set(dels))
        apply_delete(idx, np.asarray(dels, np.int32))
        for e in dels:
            model.pop(int(e), None)
    return time.perf_counter() - t0


def main(argv=None) -> int:
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--write-batches", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    from raft_tpu.mutable import MutableIndex, recover, search_view
    from raft_tpu.resilience import degradation_count

    measured = jax.default_backend() == "tpu"
    (m, d, k, batches, wbatch, tails) = (TPU_SHAPE if measured
                                         else CPU_SHAPE)
    if args.write_batches is not None:
        batches = args.write_batches
    bound_ms = (TPU_RECOVERY_BOUND_MS if measured
                else CPU_RECOVERY_BOUND_MS)
    geom = {} if measured else dict(passes=3, T=256, Qb=32, g=2)
    # delta sized to hold the whole load (compaction off: the bench
    # measures the WAL/recovery plane, bench_mutation owns the folds)
    cap = max(1024, batches * wbatch + 64)
    common = dict(auto_compact=False, compact_threshold=cap,
                  delta_cap=cap, **geom)

    rng = np.random.default_rng(args.seed)
    Y = rng.normal(size=(m, d)).astype(np.float32)
    degr0 = degradation_count()
    errors = []
    tmp_root = tempfile.mkdtemp(prefix="bench_recovery_")

    # ---- durable-write overhead: in-memory vs sync=batch ------------
    idx_plain = MutableIndex(Y, **common)
    t_plain = _drive_writes(idx_plain, dict(), rng, batches, wbatch,
                            ext0=m)
    dur_dir = os.path.join(tmp_root, "overhead")
    idx_dur = MutableIndex(Y, durable_dir=dur_dir, wal_sync="batch",
                           **common)
    t_dur = _drive_writes(idx_dur, dict(), rng, batches, wbatch,
                          ext0=m)
    idx_dur.close()
    # one batch = one upsert request + one delete request
    writes = 2 * batches
    throughput = writes / t_dur if t_dur else 0.0
    overhead = (t_dur / t_plain) if t_plain else 0.0

    # ---- recovery time vs WAL tail length ---------------------------
    zero_acked_loss = True
    recovery_points = []
    queries = rng.normal(size=(4, d)).astype(np.float32)
    for tail in tails:
        ddir = os.path.join(tmp_root, f"tail{tail}")
        idx = MutableIndex(Y, durable_dir=ddir, wal_sync="batch",
                           **common)
        model = {int(i): Y[i].tobytes() for i in range(m)}
        tail_batches = max(1, tail // 2)     # 2 records per batch
        _drive_writes(idx, model, rng, tail_batches, wbatch, ext0=m)
        idx.close()                          # fsync horizon == crash
        t0 = time.perf_counter()
        out = recover(ddir, attach=False, **common)
        rec_s = time.perf_counter() - t0
        if out is None:
            zero_acked_loss = False
            errors.append(f"tail {tail}: recover() found no durable "
                          f"state")
            continue
        ridx, stats = out
        if _live_state(ridx) != model:
            zero_acked_loss = False
            errors.append(f"tail {tail}: recovered live state diverged "
                          f"from the acked model")
        try:
            vi = np.asarray(search_view(idx, queries, k)[1])
            ri = np.asarray(search_view(ridx, queries, k)[1])
            if not np.array_equal(vi, ri):
                zero_acked_loss = False
                errors.append(f"tail {tail}: recovered search ids "
                              f"diverged from the pre-crash index")
        except Exception as e:
            errors.append(f"tail {tail}: parity probe failed: "
                          f"{type(e).__name__}: {e}"[:200])
            zero_acked_loss = False
        recovery_points.append({
            "wal_records": int(stats["wal_last_lsn"]
                               - stats["checkpoint_lsn"]),
            "recovery_ms": round(rec_s * 1e3, 3),
            "replayed_records": stats["replayed_records"],
            "truncated_bytes": stats["truncated_bytes"],
        })
    recovery_ms = max((pt["recovery_ms"] for pt in recovery_points),
                      default=None)

    shutil.rmtree(tmp_root, ignore_errors=True)
    degr = degradation_count() - degr0
    ok = (zero_acked_loss and not errors
          and recovery_ms is not None and recovery_ms <= bound_ms)
    result = {
        "metric": f"durability sync=batch {batches}x{wbatch} writes + "
                  f"recovery over {m}x{d} "
                  f"({jax.default_backend()})",
        "value": round(throughput, 2),
        "unit": "req/s",
        "schema": SCHEMA,
        "ok": bool(ok),
        "skipped": False,
        "measured": measured,
        "degraded": not measured,
        "zero_acked_loss": bool(zero_acked_loss),
        "recovery_ms": recovery_ms,
        "recovery_ms_bound": bound_ms,
        "recovery_points": recovery_points,
        "replayed_records": (recovery_points[-1]["replayed_records"]
                             if recovery_points else None),
        "throughput_qps": round(throughput, 2),
        "throughput_base_qps": round(writes / t_plain, 2)
        if t_plain else None,
        "durable_overhead_x": round(overhead, 3),
        "wal_sync": "batch",
        "n_write_batches": batches,
        "rows_per_batch": wbatch,
        "errors": errors[:8],
        "platform": jax.default_backend(),
        "git_commit": git_commit(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if degr:
        result["resilience_degradations"] = degr
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
