"""Readings that the check's limits are set from, many seeds in one
process (the benchmark's own runs never run this):

    python3 bench_suite/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 5 [--control] [--faults]

For each seed: one run of the cell as ``run.py`` makes it (the program's
readings, with its end-to-end metrics), and on the same checked rows:

- with ``--control``, the control's readings: the plain reference put in
  the program's place, its cross term in three bf16 passes (``bf16x3``,
  as ``Precision.HIGH``), the nearest precision below the f32 the
  configurations state;
- with ``--faults``, the readings of answers with wrong ids reported
  with their true distances (:data:`FAULTS`).

One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp  # noqa: E402

from bench_suite import check, reference, run, spec  # noqa: E402

#: planted faults: how each picks its ids (the reference over half of the
#: base, or a one-pass bf16 selection); each reports the ids' true
#: distances, so only an id-level number can catch it
FAULTS = {
    "half_base": lambda base, pool, rows, k: check.reference_answers(
        base[:base.shape[0] // 2], pool, rows, k)[1],
    "bf16_ids": lambda base, pool, rows, k: check.reference_answers(
        base, pool, rows, k, "bf16")[1],
}


def control_numbers(m, ans, ref_ids, names, precision: str = "bf16x3"):
    """The compared numbers of the reference at ``precision`` answering
    the same rows in the program's place."""
    k = ans.ids.shape[1]
    d, i = check.reference_answers(m.base, m.pool, ans.rows, k, precision)
    return check.numbers(m.base, m.pool, check.Answers(ans.rows, d, i),
                         ref_ids, names)


def fault_numbers(m, ans, ref_ids, names, fault: str):
    """The compared numbers of ``fault``'s answers on the same rows."""
    k = ans.ids.shape[1]
    ids = FAULTS[fault](m.base, m.pool, ans.rows, k)
    queries = jnp.take(m.pool, jnp.asarray(ans.rows), axis=0)
    dist = reference.true_distances(queries, m.base, ids)
    return check.numbers(m.base, m.pool, check.Answers(ans.rows, dist, ids),
                         ref_ids, names)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices, peaks = run.device_check(cell.chips)
    names = list(cell.config["check"]["limits"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        m = run.measure(cell, seed, args.seconds, False, devices,
                        t_start=t0)
        out, ans, ref_ids = run.report(m, devices, peaks)
        line = {"workload": cell.name, "seed": seed,
                "correct": out["correct"],
                "program": {k: v["value"] for k, v in out["checks"].items()},
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "rows_checked": int(len(ans.rows))}
        if args.control:
            t1 = time.perf_counter()
            line["control"] = control_numbers(m, ans, ref_ids, names)
            line["control_s"] = time.perf_counter() - t1
        if args.faults:
            line["faults"] = {f: fault_numbers(m, ans, ref_ids, names, f)
                              for f in FAULTS}
        print("CALIBRATE " + json.dumps(line), flush=True)
        del m
    return 0


if __name__ == "__main__":
    sys.exit(main())
