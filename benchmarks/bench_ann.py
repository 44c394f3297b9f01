#!/usr/bin/env python
"""ANN speed/recall frontier — the BENCH_ANN artifact.

Sweeps the IVF-Flat index (:mod:`raft_tpu.ann`) over ``n_lists`` ×
``n_probes`` against the brute-force oracle (the bit-exact-tested
``distance.knn``) and writes ``BENCH_ANN.json``:

- **recall@k** per frontier point (the fraction of each query's true
  top-k ids the probe search returned, averaged),
- **probed-bytes fraction** — the share of database rows a query
  actually reads (the ANN tier's whole reason to exist: brute force at
  the 2048×10M×256 north star is permanently HBM-bound, so past the
  stream-once wall the only speedup left is reading less),
- **modeled effective GB/s** — the HBM-roofline database-scan rate the
  probed-bytes model (:func:`raft_tpu.observability.costmodel.
  ivf_traffic_model`) implies on the current chip,
- the **degenerate-exact invariant**: the ``n_probes = n_lists`` point
  must match the oracle's id sets exactly (probing everything IS exact
  search — the fused certified path over the ragged slab).

Off-TPU runs use a small shape and stamp ``"measured": false`` — the
wall-clock columns are CPU noise, but recall and the probed-bytes
model are platform-independent math, so ``bench_report --check`` gates
the recall floor and the degenerate invariant on every round and only
speed-gates measured ones. ``degraded`` means the round actually WALKED
a resilience ladder (``resilience_degradations > 0``) — an off-TPU
modeled round is ``measured: false`` but NOT degraded (the historical
``degraded = not measured`` stamp conflated the two, poisoning the
committed artifact). A degraded round REFUSES to overwrite the NAMED
``BENCH_ANN.json`` (hard error listing the ladder steps): committed
evidence never silently becomes an outage artifact.

The ``pq`` block is the IVF-PQ compressed-tier evidence (ISSUE 15 +
the ISSUE 19 quality round): frontier points over ``pq_bits`` ×
``n_probes`` with post-rescore recall, the modeled codes-vs-f32
streamed-bytes ratio (gated ≤ 0.10× at 8-bit), id-parity after the
mandatory exact rescore vs the flat scan over the same probes, and a
modeled 100M-row point whose resident index bytes must fit a single
v5e's HBM. Every point stamps its certification-ladder evidence —
``cert_rerun_frac`` + the per-rung histogram (certified / widened /
exact_rerun) — and a second **diffuse-Gaussian** (worst-case,
cluster-free) distribution sweeps alongside the clustered one: the
distribution where PR 15's worst-case certificate collapsed to an
83–88% exact-rerun rate. ``bench_report --check`` gates
``cert_rerun_frac ≤ 0.10`` at recall ≥ 0.95 on the diffuse points and
trend-gates erosion vs the previous comparable round.

Prints ONE JSON line and writes ``BENCH_ANN.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
from raft_tpu.utils.provenance import git_commit  # noqa: E402
OUT_PATH = os.path.join(_REPO, "BENCH_ANN.json")
SCHEMA = 2
RECALL_FLOOR = 0.95
#: PQ streamed-bytes gate: the modeled codes-slab stream must be at
#: most this fraction of the f32 slab stream (1/16 at 8-bit codes
#: with pq_dim = d/4 — mirror of tools/bench_report.PQ_RATIO_CEIL)
PQ_RATIO_CEIL = 0.10
#: PQ certificate-rerun gate: on the diffuse-Gaussian (worst-case)
#: distribution, the exact-rerun fraction at the recall floor must be
#: at most this (mirror of tools/bench_report.PQ_RERUN_CEIL)
PQ_RERUN_CEIL = 0.10
#: the 100M-row modeled scale point (the single-chip HBM-fit claim)
PQ_SCALE_ROWS = 100_000_000
PQ_SCALE_D = 128
PQ_SCALE_LISTS = 50_000

# per-platform shapes: (rows, d, nq, k, n_lists sweep)
TPU_SHAPE = (1_000_000, 128, 2048, 10, (1024,))
CPU_SHAPE = (20_000, 32, 256, 10, (16, 64))


def _pq_cert_counts():
    """(checks, reruns) of the PQ completeness certificate so far —
    the per-point rerun fraction stamped into the pq frontier."""
    from raft_tpu.observability import get_registry
    from raft_tpu.observability.quality import CERT_CHECKS, CERT_FIXUPS

    checks = fixups = 0.0
    for mtr in get_registry().collect():
        if getattr(mtr, "labels", {}).get("site") != "ann.search_ivf_pq":
            continue
        if mtr.name == CERT_CHECKS:
            checks += mtr.value
        elif mtr.name == CERT_FIXUPS:
            fixups += mtr.value
    return checks, fixups


def _pq_rung_counts():
    """{rung: queries} of the PQ certification ladder so far — the
    per-point rung histogram stamped into the pq frontier."""
    from raft_tpu.observability import get_registry
    from raft_tpu.observability.quality import PQ_RUNGS

    out = {"certified": 0, "widened": 0, "exact_rerun": 0}
    for mtr in get_registry().collect():
        if mtr.name != PQ_RUNGS or getattr(mtr, "labels", {}).get(
                "site") != "ann.search_ivf_pq":
            continue
        rung = mtr.labels.get("rung")
        if rung in out:
            out[rung] += int(mtr.value)
    return out


def _probe_schedule(L: int):
    """Geometric n_probes sweep ending at the degenerate L point."""
    probes, p = [], 1
    while p < L:
        probes.append(p)
        p *= 2
    probes.append(L)
    return probes


def main(argv=None) -> int:
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--queries", type=int, default=None)
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--lists", type=str, default=None,
                    help="comma-separated n_lists sweep")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args(argv)

    import jax

    from raft_tpu.ann import (build_ivf_flat, resolve_fine_scan,
                              search_ivf_flat)
    from raft_tpu.core import DeviceResources
    from raft_tpu.distance.fused_l2nn import knn
    from raft_tpu.observability.costmodel import ivf_traffic_model
    from raft_tpu.random import make_blobs
    from raft_tpu.resilience import degradation_count
    from raft_tpu.utils.arch import chip_spec

    measured = jax.default_backend() == "tpu"
    m, d, nq, k, lists = TPU_SHAPE if measured else CPU_SHAPE
    m = args.rows or m
    d = args.dim or d
    nq = args.queries or nq
    k = args.k or k
    if args.lists:
        lists = tuple(int(x) for x in args.lists.split(","))
    res = DeviceResources(seed=7)
    degr0 = degradation_count()

    # the controllable oracle: mildly imbalanced blobs with per-center
    # spread, so inverted lists are ragged the way production data is
    n_centers = max(8, min(64, m // 256))
    rng = np.random.default_rng(11)
    X, _ = make_blobs(
        res, 11, m, d, n_clusters=n_centers,
        cluster_std=np.linspace(0.5, 2.0, n_centers).astype(np.float32),
        proportions=rng.uniform(0.5, 2.0, n_centers))
    X = np.asarray(X, np.float32)
    Q = X[rng.choice(m, nq, replace=False)] \
        + rng.normal(0, 0.1, (nq, d)).astype(np.float32)

    t0 = time.perf_counter()
    ov, oi = knn(res, X, Q, k)
    oi = np.asarray(oi)
    oracle_ms = (time.perf_counter() - t0) * 1e3
    oracle_sets = [set(r) for r in oi]

    spec = chip_spec()
    frontier, errors = [], []
    degenerate_exact = True
    for L in lists:
        idx = build_ivf_flat(res, X, n_lists=L, max_iter=8, seed=3)
        sizes = np.asarray(idx.sizes)
        padded = np.asarray(idx.padded_sizes)
        for P in _probe_schedule(L):
            # the fine-scan schedule the chooser resolves for this
            # point (the cost-model crossover on the ACTUAL list-size
            # histogram — ISSUE 14), stamped next to BOTH schedules'
            # modeled bytes so the frontier records the gather/stream
            # gap whichever one runs
            chosen = resolve_fine_scan(idx, nq, k, min(P, L),
                                       idx.probe_window) \
                if P < L else "exact"
            t0 = time.perf_counter()
            v, i = search_ivf_flat(res, idx, Q, k, n_probes=P)
            i = np.asarray(i)
            ms = (time.perf_counter() - t0) * 1e3
            recall = float(np.mean(
                [len(oracle_sets[q] & set(i[q])) / k
                 for q in range(nq)]))
            if P >= L:
                exact = all(set(i[q]) == oracle_sets[q]
                            for q in range(nq))
                degenerate_exact = degenerate_exact and exact
                if not exact:
                    errors.append(
                        f"degenerate point L={L} not oracle-exact")
            model = ivf_traffic_model(nq, m, d, k, L, min(P, L),
                                      idx.probe_window, idx.slab_rows,
                                      list_sizes=sizes,
                                      padded_sizes=padded)
            frontier.append({
                "n_lists": L,
                "n_probes": P,
                "recall_at_k": round(recall, 4),
                "probed_frac": round(model["probed_frac"], 5),
                "pad_frac": round(
                    float(idx.slab_rows - m) / m, 5),
                "modeled_speedup": round(model["modeled_speedup"], 2),
                "modeled_effective_gbps": round(
                    spec.hbm_bw * model["modeled_speedup"] / 1e9, 1),
                "gather_overread": round(model["gather_overread"], 2),
                "fine_scan": chosen,
                "model_stream_bytes": round(
                    model["fine_stream_bytes"]),
                "model_gather_bytes": round(
                    model["fine_gather_bytes"]),
                "search_ms": round(ms, 2),
                "list_size_min": int(sizes.min()),
                "list_size_max": int(sizes.max()),
            })

    # quantized-slab evidence: the int8 IVF index on the LAST swept
    # n_lists — id-set parity vs the f32 IVF index at a mid probe count
    # and oracle-exactness at the degenerate point, plus the modeled
    # probed-gather bytes ratio. Gated by bench_report --check.
    quantized = None
    try:
        L = lists[-1]
        idx8 = build_ivf_flat(res, X, n_lists=L, max_iter=8, seed=3,
                              db_dtype="int8")
        Pq = max(1, min(L - 1, 1 + L // 8)) if L > 1 else 1
        _, fi = search_ivf_flat(res, idx, Q, k, n_probes=Pq)
        _, qi = search_ivf_flat(res, idx8, Q, k, n_probes=Pq)
        fi, qi = np.asarray(fi), np.asarray(qi)
        parity = all(set(fi[q]) == set(qi[q]) for q in range(nq))
        _, qe = search_ivf_flat(res, idx8, Q, k, n_probes=L)
        qe = np.asarray(qe)
        q8_exact = all(set(qe[q]) == oracle_sets[q] for q in range(nq))
        model8 = ivf_traffic_model(nq, m, d, k, L, Pq,
                                   idx8.probe_window, idx8.slab_rows,
                                   db_dtype="int8",
                                   list_sizes=np.asarray(idx8.sizes),
                                   padded_sizes=np.asarray(
                                       idx8.padded_sizes))
        quantized = {
            "db_dtype": "int8",
            "n_lists": L, "n_probes": Pq,
            "fine_scan": resolve_fine_scan(idx8, nq, k, Pq,
                                           idx8.probe_window),
            "quantized_gather_ratio": round(
                model8["quantized_gather_ratio"], 4),
            "degenerate_exact": bool(q8_exact),
            "ok": bool(parity and q8_exact),
        }
        if not quantized["ok"]:
            errors.append("int8 IVF parity/degenerate check failed")
    except Exception as e:
        errors.append(f"int8 IVF evidence failed: "
                      f"{type(e).__name__}: {e}"[:200])
        quantized = {"error": str(e)[:200], "ok": False}

    # ---- IVF-PQ compressed-tier evidence (ISSUE 15) -----------------
    pq_block = None
    try:
        from raft_tpu.ann import build_ivf_pq, resolve_pq_scan, \
            search_ivf_pq
        from raft_tpu.observability.costmodel import pq_index_bytes
        from raft_tpu.utils.arch import TPU_SPECS

        L = lists[-1]
        pq_points, pq_ok = [], True

        def pq_point(idxq, flat_idx, Qd, truth_sets, P, dist):
            """One pq frontier point: forced-ADC search + certificate/
            rung evidence + id-parity vs the flat scan over the same
            probes (the chooser's own pick is stamped alongside as
            pq_scan)."""
            snap0, rung0 = _pq_cert_counts(), _pq_rung_counts()
            t0 = time.perf_counter()
            _, pi = search_ivf_pq(res, idxq, Qd, k, n_probes=P,
                                  pq_scan="pq")
            pi = np.asarray(pi)
            ms = (time.perf_counter() - t0) * 1e3
            recall = float(np.mean(
                [len(truth_sets[q] & set(pi[q])) / k
                 for q in range(nq)]))
            _, fi2 = search_ivf_flat(res, flat_idx, Qd, k, n_probes=P,
                                     fine_scan="query")
            fi2 = np.asarray(fi2)
            parity = all(set(pi[q]) == set(fi2[q]) for q in range(nq))
            model = ivf_traffic_model(
                nq, m, d, k, L, P, idxq.probe_window,
                idxq.slab_rows,
                list_sizes=np.asarray(idxq.sizes),
                padded_sizes=np.asarray(idxq.padded_sizes),
                pq_dim=idxq.pq_dim, pq_bits=idxq.pq_bits)
            snap1, rung1 = _pq_cert_counts(), _pq_rung_counts()
            checks = snap1[0] - snap0[0]
            reruns = snap1[1] - snap0[1]
            return {
                "dist": dist,
                "pq_bits": idxq.pq_bits,
                "pq_dim": idxq.pq_dim,
                "pq_mode": idxq.pq_mode,
                "n_lists": L,
                "n_probes": P,
                "recall_at_k": round(recall, 4),
                "rescore_id_parity": bool(parity),
                "pq_bytes_ratio": round(
                    model["pq_bytes_ratio"], 5),
                "model_pq_bytes": round(model["pq_stream_bytes"]),
                "model_flat_bytes": round(min(
                    model["fine_stream_bytes"],
                    model["fine_gather_bytes"])),
                "pq_scan": resolve_pq_scan(idxq, nq, k, P,
                                           idxq.probe_window),
                "cert_rerun_frac": round(reruns / max(checks, 1), 4),
                "rungs": {r: rung1[r] - rung0[r] for r in rung1},
                "search_ms": round(ms, 2),
            }

        for bits in (8, 4):
            idxq = build_ivf_pq(res, X, n_lists=L, pq_bits=bits,
                                max_iter=8, seed=3)
            for P in _probe_schedule(L)[:-1]:
                point = pq_point(idxq, idx, Q, oracle_sets, P,
                                 "clustered")
                pq_points.append(point)
                pq_ok = pq_ok and point["rescore_id_parity"]
        # the diffuse-Gaussian worst case (ISSUE 19): cluster-free
        # data where quantization error rivals neighbor distances —
        # the distribution that collapsed PR 15's worst-case
        # certificate to an 83–88% exact-rerun rate. The OPQ build +
        # adaptive per-row certificate + widen rung must keep the
        # exact-rerun fraction ≤ rerun_ceil at the recall floor.
        Xg = rng.normal(size=(m, d)).astype(np.float32)
        Qg = rng.normal(size=(nq, d)).astype(np.float32)
        _, ogi = knn(res, Xg, Qg, k)
        diffuse_sets = [set(r) for r in np.asarray(ogi)]
        idxg_flat = build_ivf_flat(res, Xg, n_lists=L, max_iter=8,
                                   seed=3)
        # pq_dim = d/2 (2-dim subspaces, 4 bits/dim): on cluster-free
        # data the d/4 default leaves quantization error at the
        # neighbor-gap scale and the certificate reruns everything —
        # the finer codebooks pay 2x the code bytes (stamped in
        # pq_bytes_ratio) to keep the compressed tier certified
        idxg = build_ivf_pq(res, Xg, n_lists=L, pq_dim=d // 2,
                            pq_bits=8, max_iter=8, seed=3,
                            pq_mode="opq")
        for P in _probe_schedule(L)[:-1]:
            point = pq_point(idxg, idxg_flat, Qg, diffuse_sets, P,
                             "diffuse")
            pq_points.append(point)
            pq_ok = pq_ok and point["rescore_id_parity"]
        diffuse_at_floor = [
            p for p in pq_points if p["dist"] == "diffuse"
            and p["recall_at_k"] >= RECALL_FLOOR]
        diffuse_rerun = min((p["cert_rerun_frac"]
                             for p in diffuse_at_floor), default=None)
        if diffuse_rerun is None:
            pq_ok = False
            errors.append("no diffuse PQ point reaches the recall "
                          "floor")
        elif diffuse_rerun > PQ_RERUN_CEIL:
            pq_ok = False
            errors.append(
                f"diffuse cert_rerun_frac {diffuse_rerun} > "
                f"{PQ_RERUN_CEIL} at the recall floor")
        best_pq = [p for p in pq_points
                   if p["pq_bits"] == 8
                   and p["recall_at_k"] >= RECALL_FLOOR
                   and p["pq_bytes_ratio"] <= PQ_RATIO_CEIL]
        if not best_pq:
            pq_ok = False
            errors.append("no 8-bit PQ point reaches the recall floor "
                          f"at ratio <= {PQ_RATIO_CEIL}")
        # the 100M-row modeled scale point: the compressed resident
        # set must fit ONE v5e's HBM (the billion-vector-serving claim
        # this tier exists for; the f32 rescore slab is the host tier
        # at that scale — only the candidate pools stream from it)
        v5e = TPU_SPECS[(5, "e")]
        scale = pq_index_bytes(PQ_SCALE_ROWS, PQ_SCALE_D,
                               PQ_SCALE_LISTS, PQ_SCALE_D // 4, 8)
        fits = scale["total_bytes"] <= v5e.hbm_bytes
        if not fits:
            pq_ok = False
            errors.append("modeled 100M-row PQ index exceeds v5e HBM")
        pq_block = {
            "ok": bool(pq_ok),
            "ratio_ceil": PQ_RATIO_CEIL,
            "rerun_ceil": PQ_RERUN_CEIL,
            "diffuse_cert_rerun_frac": diffuse_rerun,
            "pq_bytes_ratio": min(p["pq_bytes_ratio"]
                                  for p in pq_points),
            "frontier": pq_points,
            "scale_model": {
                "rows": PQ_SCALE_ROWS, "d": PQ_SCALE_D,
                "n_lists": PQ_SCALE_LISTS,
                "pq_dim": PQ_SCALE_D // 4, "pq_bits": 8,
                "model_index_bytes": round(scale["total_bytes"]),
                "model_f32_slab_bytes": round(
                    scale["f32_slab_bytes"]),
                "compression": round(scale["compression"], 2),
                "hbm_bytes": round(v5e.hbm_bytes),
                "chip": v5e.name,
                "fits_hbm": bool(fits),
            },
        }
        if not pq_ok:
            errors.append("PQ tier evidence failed")
    except Exception as e:
        errors.append(f"PQ tier evidence failed: "
                      f"{type(e).__name__}: {e}"[:200])
        pq_block = {"error": str(e)[:200], "ok": False}

    best = max(p["recall_at_k"] for p in frontier)
    at_floor = [p for p in frontier if p["recall_at_k"] >= RECALL_FLOOR]
    floor_pt = min(at_floor, key=lambda p: p["probed_frac"]) \
        if at_floor else None
    ok = (best >= RECALL_FLOOR and degenerate_exact and not errors
          and bool(quantized and quantized.get("ok"))
          and bool(pq_block and pq_block.get("ok")))
    degr = degradation_count() - degr0
    result = {
        "metric": f"ivf_flat recall@{k} frontier {nq}x{m}x{d} "
                  f"lists={list(lists)} ({jax.default_backend()})",
        "value": round(best, 4),
        "unit": f"recall@{k}",
        "schema": SCHEMA,
        "ok": bool(ok),
        "skipped": False,
        "measured": measured,
        # degraded means "this round walked a resilience ladder", NOT
        # "modeled off-TPU" — measured:false already records the
        # latter, and conflating the two turned every committed CPU
        # artifact into un-gateable outage evidence
        "degraded": bool(degr),
        "k": k,
        "recall_floor": RECALL_FLOOR,
        "degenerate_exact": bool(degenerate_exact),
        "db_dtype": "f32",
        "quantized": quantized,
        "pq": pq_block,
        "frontier": frontier,
        "probed_frac_at_floor": floor_pt["probed_frac"]
        if floor_pt else None,
        "search_ms": floor_pt["search_ms"] if floor_pt else None,
        "oracle_ms": round(oracle_ms, 2),
        "chip": spec.name,
        "errors": errors[:8],
        "platform": jax.default_backend(),
        "git_commit": git_commit(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if degr:
        result["resilience_degradations"] = degr
    # quality block (ISSUE 10): IVF certificate/rerun counters + the
    # frontier's best OFFLINE recall, in the same shape the serving
    # artifact carries its online shadow recall — one recall key
    # family, one gate (bench_report --check [quality], ≥ 0.95 floor)
    try:
        from raft_tpu.observability.quality import quality_block

        qb = quality_block()
        if qb is None:
            qb = {"fixup_rate": 0.0, "certificate_checks": 0,
                  "certificate_fixups": 0, "sites": {}}
        qb["offline_recall"] = round(best, 4)
        result["quality"] = qb
    except Exception as e:
        print(f"bench_ann: quality block failed: {e}", file=sys.stderr)
    # ---- NAMED-artifact protection: a round that walked a resilience
    # ladder REFUSES to overwrite committed evidence. A degraded run
    # is history — it may land in a driver round file, never in the
    # named baseline artifact (hard error, reasons printed).
    if degr and os.path.basename(args.out) == os.path.basename(
            OUT_PATH):
        from raft_tpu.resilience import degradation_reasons

        reasons = degradation_reasons()
        print(json.dumps(result))
        print(f"bench_ann: REFUSING to overwrite named artifact "
              f"{os.path.basename(args.out)}: this round recorded "
              f"{degr:g} resilience degradation step(s): "
              f"{'; '.join(reasons) or 'unlabeled'} — rerun without "
              f"faults/outage or write to a round file (--out)",
              file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
