"""MST and LAP at reference scale (round-3 review item 10).

- MST on a 1M-edge RMAT graph (the reference solver's design scale:
  sparse/solver/detail/mst_solver_inl.cuh:406), objective checked
  against scipy's minimum_spanning_tree on the SAME deduped graph.
- Batched LAP at n = 1024..4096 (reference: batched n≥1k,
  solver/linear_assignment.cuh:60), optimality-gap certificates
  recorded; small-n objective checked against scipy Hungarian.

Writes BENCH_SOLVERS_SCALE.json.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                   "BENCH_SOLVERS_SCALE.json")
BUDGET_S = float(os.environ.get("RAFT_TPU_SOLVERS_BUDGET_S", "3000"))


def main():
    dry = gate()
    results = {"platform": "tpu" if not dry else "cpu-forced",
               "representative": not dry}
    import jax
    import numpy as np

    import raft_tpu
    from raft_tpu.benchmark import Fixture
    from raft_tpu.core.sparse_types import COOMatrix
    from raft_tpu.random import RngState
    from raft_tpu.random.rmat import rmat_rectangular_gen
    from raft_tpu.solver.linear_assignment import solve_lap
    from raft_tpu.sparse.solver.mst import mst

    res = raft_tpu.device_resources()
    fx = Fixture(res=res, reps=1)   # warm + RTT-corrected (solves are
    #                                 long; one corrected rep suffices)
    deadline = time.monotonic() + BUDGET_S

    def flush():
        if not dry:
            with open(OUT, "w") as f:
                json.dump(results, f, indent=1)
                f.write("\n")

    # ---- MST @ 1M RMAT edges ----
    scale = 18 if not dry else 10
    n_edges = 1_000_000 if not dry else 4_000
    src, dst = rmat_rectangular_gen(res, RngState(42), n_edges, scale,
                                    scale)
    src, dst = np.asarray(src), np.asarray(dst)
    keep = src != dst
    # dedup UNORDERED pairs (keep one weight per undirected edge) so
    # ours and scipy solve the same simple graph — scipy's csr
    # conversion SUMS duplicate entries
    lo = np.minimum(src[keep], dst[keep]).astype(np.int64)
    hi = np.maximum(src[keep], dst[keep]).astype(np.int64)
    key = lo * (1 << scale) + hi
    _, uniq = np.unique(key, return_index=True)
    us = lo[uniq].astype(np.int32)
    ud = hi[uniq].astype(np.int32)
    rng = np.random.default_rng(0)
    w = rng.random(us.size).astype(np.float32) + 0.01
    s2 = np.concatenate([us, ud]).astype(np.int32)
    d2 = np.concatenate([ud, us]).astype(np.int32)
    w2 = np.concatenate([w, w])
    n = 1 << scale
    G = COOMatrix(s2, d2, w2, (n, n))
    out = mst(res, G)          # warm (host-round Borůvka re-traces)
    r = fx.run(lambda v: mst(res, COOMatrix(s2, d2, v, (n, n)))
               .mst.weights, w2)
    dt = r["seconds"]
    ours_w = float(np.asarray(out.mst.weights[:out.mst.n_edges]).sum())
    results["mst_rmat"] = {
        "n_vertices": n, "n_edges_sym": int(s2.size),
        "seconds": round(dt, 2), "mst_edges": int(out.mst.n_edges),
        "total_weight": round(ours_w, 3)}
    flush()
    try:
        from scipy.sparse import coo_matrix
        from scipy.sparse.csgraph import minimum_spanning_tree

        # undirected view: keep min weight per unordered pair is not
        # needed (weights are equal on both directions; scipy uses the
        # summed value only when BOTH directions carry the same pair —
        # they do, so halve)
        A = coo_matrix((w, (np.minimum(us, ud), np.maximum(us, ud))),
                       shape=(n, n)).tocsr()
        ref_w = float(minimum_spanning_tree(A).sum())
        results["mst_rmat"]["scipy_weight"] = round(ref_w, 3)
        results["mst_rmat"]["matches_scipy"] = bool(
            abs(ours_w - ref_w) < 1e-4 * max(abs(ref_w), 1.0))
    except Exception as e:  # noqa: BLE001
        results["mst_rmat"]["scipy_error"] = str(e)[:200]
    flush()

    # ---- batched LAP at n = 1024..4096 ----
    sizes = ([1024, 2048, 4096] if not dry else [64])
    for nn in sizes:
        if time.monotonic() > deadline:
            # internal deadline, checked between solves
            results["budget_expired_before"] = f"lap_{nn}"
            break
        cost = rng.random((nn, nn)).astype(np.float32) * 100.0
        assign, obj = solve_lap(res, cost)            # warm
        r = fx.run(lambda c: solve_lap(res, c)[0], cost)
        row = {"n": nn, "seconds": round(r["seconds"], 2),
               "objective": round(float(obj), 3)}
        if nn <= 2048:
            try:
                from scipy.optimize import linear_sum_assignment

                ri, ci = linear_sum_assignment(cost)
                sp = float(cost[ri, ci].sum())
                row["scipy_objective"] = round(sp, 3)
                row["rel_excess"] = round(
                    (float(obj) - sp) / max(sp, 1e-9), 8)
            except Exception as e:  # noqa: BLE001
                row["scipy_error"] = str(e)[:200]
        results[f"lap_{nn}"] = row
        flush()

    # ---- exact JV tail (round 5): the tol-contract refinement ----
    # Sequential by design (n augmentations of O(n)-step Dijkstras) —
    # this measures what the ENFORCED tol contract costs on TPU when
    # the auction certificate misses, vs the auction's vector path
    for nn in ([512, 1024] if not dry else [32]):
        if time.monotonic() > deadline:
            results["budget_expired_before_jv"] = f"jv_{nn}"
            break
        from raft_tpu.solver.linear_assignment import (_certify_f64,
                                                       _jv_solve)

        cost = rng.random((nn, nn)).astype(np.float32) * 100.0
        a, u = _jv_solve(cost, nn)                    # warm/compile
        gap = _certify_f64(cost[None], np.asarray(a)[None],
                           np.asarray(u)[None])[0]
        r = fx.run(lambda c: _jv_solve(c, nn)[0], cost)
        results[f"jv_{nn}"] = {"n": nn,
                               "seconds": round(r["seconds"], 2),
                               "gap_bound": float(gap)}
        flush()

    results["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())
    flush()
    print(json.dumps(results))


if __name__ == "__main__":
    main()
