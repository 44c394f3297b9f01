#!/usr/bin/env python
"""Parameter sweep for the fused distance+top-k pipeline on real TPU.

Thin measurement-script wrapper over the :mod:`raft_tpu.tune` autotuner
(the sweep, pruning, measurement, schema validation and provenance all
live there — one implementation for the CLI, the tier-1 deterministic
fallback and this probe-gated TPU script). Sweeps
(T, Qb, g, grid_order, passes) for the bench.py shape (1M x 128 index,
2048 queries, k=64), prints one JSON line per point plus a "best" line,
and writes the schema-versioned TUNE_FUSED.json that
``fused_config()``/``RAFT_TPU_TUNE_FUSED`` consume.

Fails without a TPU like every measurement script; JAX_PLATFORMS=cpu
runs a tiny-shape harness validation (no artifact).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

# internal deadline, checked between points
BUDGET_S = float(os.environ.get("TUNE_FUSED_BUDGET_S", "2400"))


def main():
    dry = gate()

    from raft_tpu.tune.fused import DRIVER_SHAPE, autotune_fused

    if dry:
        # g=8 keeps the db super-block inside the VMEM budget so the
        # dry run exercises all three grid orders, not just query
        tbl = autotune_fused(
            shape=(256, 20_000, 128, 64), out_path=None, reps=1,
            budget_s=BUDGET_S, measure=True,
            axes={"T": (1024,), "Qb": (256,), "g": (8,),
                  "grid_order": ("query", "db", "dbuf")})
    else:
        tbl = autotune_fused(shape=DRIVER_SHAPE,
                             out_path="TUNE_FUSED.json",
                             budget_s=BUDGET_S, measure=True)
    for row in tbl.get("rows", []):
        print(json.dumps(row), flush=True)
    print(json.dumps({"best": tbl.get("best")}))


if __name__ == "__main__":
    main()
