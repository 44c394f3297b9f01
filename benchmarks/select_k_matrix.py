#!/usr/bin/env python
"""select_k algorithm measurement matrix → the data behind the AUTO
heuristic.

(ref: matrix/detail/select_k-inl.cuh:38 ``choose_select_k_algorithm`` —
the reference fits a decision tree over (rows, cols, k) from benchmark
sweeps; this produces the analogous measured table for the TPU
algorithms: XLA top_k, the Pallas radix kernel, and the fused-pipeline
slotted fold.)

Writes ``SELECT_K_MATRIX.json``: per (batch, len, k) the RTT-corrected
milliseconds per algorithm. Run on a healthy TPU (probe-guarded); on CPU
it refuses (CPU timings would mis-train a TPU heuristic).
"""

import itertools
import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate

import numpy as np

OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                   "SELECT_K_MATRIX.json")

# Internal wall-clock budget: checked BETWEEN measurement points; on
# expiry the partial table is kept and the script exits cleanly.
BUDGET_S = float(os.environ.get("SELECT_K_BUDGET_S", "3000"))

# The literal Pallas radix kernel was deleted in round 3 after losing
# every cell of two measured matrices (round-1 anchor: 203 ms at
# len=2^20 vs XLA 4.7; round-3: 19-121 ms where XLA/SLOTTED did 2-35).
# The RADIX enum name now aliases CHUNKED, so the sweep measures the
# three real algorithms.


def main():
    # dry mode validates the harness end to end WITHOUT recording a
    # table (CPU timings must never train the TPU heuristic)
    dry = gate()

    import jax  # noqa: F401
    import jax.numpy as jnp

    import raft_tpu
    from raft_tpu.benchmark import Fixture
    from raft_tpu.matrix import SelectAlgo, select_k

    res = raft_tpu.device_resources()
    assert dry or res.platform == "tpu"
    fx = Fixture(res=res, reps=1 if dry else 3)
    rng = np.random.default_rng(0)

    grid = (list(itertools.product((4,), (4096,), (16,))) if dry
            else list(itertools.product((16, 64, 256),
                                        (16384, 131072, 1048576),
                                        (16, 64, 256)))
            # 10M-length rows FIRST among the extensions: the north-star
            # regime (r3 verdict item 9 — AUTO had no measured cells
            # past 1M); appended last they'd be exactly what a budget
            # expiry drops. Batch bounded by HBM: [64, 10M] f32 = 2.6 GB
            + ([] if dry else [
                (b, 10_485_760, kk)
                for b in (16, 64)
                for kk in (16, 64, 256, 1024)])
            # large-k rows (ref: cpp/tests/matrix/select_large_k.cu —
            # the regime the reference's radix select exists for)
            + ([] if dry else [
                (b, ln, kk)
                for b in (16, 64, 256)
                for ln in (131072, 1048576)
                for kk in (1024, 2048) if kk * 8 <= ln]))
    results = []
    deadline = time.monotonic() + BUDGET_S

    def flush(done: bool):
        if dry:
            return
        with open(OUT, "w") as f:
            json.dump({"platform": "tpu", "unit": "ms",
                       "complete": done, "rows": results}, f, indent=1)

    completed = True
    for batch, length, k in grid:
        if time.monotonic() > deadline:
            print(json.dumps({"budget_expired_after_rows": len(results)}))
            completed = False
            break
        v = jnp.asarray(rng.normal(size=(batch, length)).astype(np.float32))
        jax.block_until_ready(v)
        row = {"batch": batch, "len": length, "k": k}
        for algo in (SelectAlgo.XLA_TOPK, SelectAlgo.SLOTTED,
                     SelectAlgo.CHUNKED):
            try:
                # an off-envelope explicit request warns and measures the
                # XLA path — recording THAT under this algo's name would
                # mis-train the AUTO table, so escalate exactly that
                # warning (not unrelated RuntimeWarnings) to an error
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "error", message=r"select_k: explicit",
                        category=RuntimeWarning)
                    r = fx.run(lambda x, a=algo: select_k(
                        res, x, k=k, algo=a)[0], v)
                    ms = round(r["seconds"] * 1e3, 3)
                row[algo.name] = ms
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                row[algo.name] = f"error: {type(e).__name__}"
        results.append(row)
        print(row, flush=True)
        flush(done=False)  # incremental: a kill/wedge loses only this row

    if dry:
        print(json.dumps({"dry_run": True, "rows": len(results)}))
        return 0
    flush(done=completed)
    print(json.dumps({"wrote": OUT, "rows": len(results),
                      "complete": completed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
