#!/usr/bin/env python
"""Hardware-validation fuzz: the certified paths on REAL Mosaic.

The pytest fuzz lane runs Pallas in interpret mode — it cannot catch
Mosaic-lowering-only divergence (layout bugs, VMEM aliasing, pack-bit
arithmetic differences). This battery re-draws randomized configs and
checks knn_fused (p1/p3 × rescore/lite × l2/ip, incl. wide pbits) and
slotted/chunked select against numpy oracles ON THE CHIP. Writes
TPU_FUZZ.json. Probe-guarded; refuses to record on CPU.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "TPU_FUZZ.json")
BUDGET_S = float(os.environ.get("TPU_FUZZ_BUDGET_S", "1500"))


def main():
    dry = gate()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.distance.knn_fused import knn_fused, prepare_knn_index
    from raft_tpu.matrix import SelectAlgo, select_k

    rng = np.random.default_rng(7)
    results = {"knn": [], "select": []}
    deadline = time.monotonic() + BUDGET_S
    n_draws = 4 if dry else 24

    for i in range(n_draws):
        if time.monotonic() > deadline:
            break
        Q = int(rng.integers(8, 120))
        m = int(rng.integers(5000, 60000))
        d = int(rng.integers(4, 200))
        k = int(rng.integers(1, 65))
        passes = int(rng.choice([1, 3]))
        metric = str(rng.choice(["l2", "ip"]))
        lite = bool(rng.integers(0, 2))
        # adaptive precision (certify="f32"): p1 + rescore only — a new
        # CERTIFIED path, so it must be fuzzed on real Mosaic like the
        # others; its tolerance is the f32-exact one
        adaptive = passes == 1 and not lite and bool(rng.integers(0, 2))
        g = int(rng.choice([8, 16, 64, 192]))      # up to pbits 11-12
        T = 512 if m < 20000 else 2048
        row = {"Q": Q, "m": m, "d": d, "k": k, "passes": passes,
               "metric": metric, "lite": lite, "adaptive": adaptive,
               "g": g, "T": T}
        try:
            y = rng.normal(size=(m, d)).astype(np.float32)
            if i % 3 == 0:
                y += 25.0                           # big-norm regime
            x = (y[rng.integers(0, m, Q)]
                 + 0.3 * rng.normal(size=(Q, d)).astype(np.float32))
            idx = prepare_knn_index(y, passes=passes, metric=metric,
                                    T=T, g=g, store_yp=not lite)
            vals, ids = knn_fused(x, idx, k,
                                  certify="f32" if adaptive else "kernel")
            ids = np.asarray(ids)
            xd = x.astype(np.float64)
            yd = y.astype(np.float64)
            if metric == "ip":
                s = xd @ yd.T
                ref_sorted = -np.sort(-s, axis=1)[:, :k]
                got_true = -np.sort(
                    -np.take_along_axis(s, ids, axis=1), axis=1)
            else:
                s = np.maximum((xd ** 2).sum(1)[:, None]
                               + (yd ** 2).sum(1)[None, :]
                               - 2 * xd @ yd.T, 0)
                ref_sorted = np.sort(s, axis=1)[:, :k]
                got_true = np.sort(
                    np.take_along_axis(s, ids, axis=1), axis=1)
            # tolerances are NORM-BASED (the error of every score
            # function scales with ‖x‖·‖y‖, not with the distances —
            # the first battery mis-scaled this and flagged legitimate
            # bf16-space reorderings): f32 expanded noise for rescored
            # p3, the analytic bf16x3 + pack envelope for lite p3, the
            # single-pass bf16 envelope for p1
            np_scale = (float(np.sqrt((xd ** 2).sum(1)).max())
                        * float(np.sqrt((yd ** 2).sum(1)).max()) + 1.0)
            if (passes == 3 or adaptive) and not lite:
                tol = np_scale * d * 2.0 ** -21
            elif passes == 3:
                tol = np_scale * (2.0 ** -13 + d * 2.0 ** -19)
            else:
                tol = np_scale * 2.0 ** -7          # bf16 score space
            ok_vals = bool(np.allclose(got_true, ref_sorted, atol=tol))
            ok_uniq = all(np.unique(ids[q]).size == k for q in range(Q))
            row["ok"] = ok_vals and ok_uniq
            if not ok_vals:
                row["max_dev"] = float(np.max(np.abs(got_true - ref_sorted)))
        except Exception as e:  # noqa: BLE001 — record and continue
            row["error"] = f"{type(e).__name__}: {e}"[:200]
            # transport/infra errors are SKIPS, not correctness
            # failures — an oracle mismatch never raises UNAVAILABLE
            row["ok"] = None if "UNAVAILABLE" in str(e) else False
        results["knn"].append(row)
        print(json.dumps(row), flush=True)
        if not dry:
            with open(OUT, "w") as f:
                json.dump(results, f, indent=1)

    for i in range(0, n_draws, 2):
        if time.monotonic() > deadline:
            break
        B = int(rng.integers(1, 48))
        L = int(rng.integers(4096, 300000))
        k = int(rng.integers(1, min(1024, L // 8)))
        algo = [SelectAlgo.SLOTTED, SelectAlgo.CHUNKED][i % 2]
        smin = bool(rng.integers(0, 2))
        row = {"B": B, "L": L, "k": k, "algo": algo.name, "min": smin}
        try:
            v = rng.normal(size=(B, L)).astype(np.float32)
            ov, oi = select_k(None, v, k=k, select_min=smin, algo=algo)
            ref = (np.sort(v, axis=1)[:, :k] if smin
                   else -np.sort(-v, axis=1)[:, :k])
            row["ok"] = bool(np.array_equal(np.asarray(ov), ref))
        except Exception as e:  # noqa: BLE001
            row["error"] = f"{type(e).__name__}: {e}"[:200]
            row["ok"] = None if "UNAVAILABLE" in str(e) else False
        results["select"].append(row)
        print(json.dumps(row), flush=True)
        if not dry:
            with open(OUT, "w") as f:
                json.dump(results, f, indent=1)

    n_bad = sum(1 for s in results.values() for r in s
                if r["ok"] is False)
    n_skip = sum(1 for s in results.values() for r in s
                 if r["ok"] is None)
    print(json.dumps({"total": sum(len(s) for s in results.values()),
                      "failures": n_bad, "infra_skips": n_skip}))
    return 1 if n_bad else 0


if __name__ == "__main__":
    sys.exit(main())
