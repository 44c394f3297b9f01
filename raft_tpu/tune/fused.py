"""Autotuner for the fused L2 top-k pipeline.

Sweeps ``(T, Qb, g, grid_order)`` × ``passes`` candidates for a target
shape, prunes guaranteed Mosaic compile failures with the SAME
scoped-VMEM predicate production uses (``footprint_for``/``fit_config``
— a config the runtime would silently shrink is never measured as
written), measures the survivors through ``benchmark.Fixture`` with the
PR-2 ``res.profiler`` cost capture riding along, and writes a
schema-versioned, provenance-stamped ``TUNE_FUSED.json`` that
``fused_config()``/``RAFT_TPU_TUNE_FUSED`` consume.

Every row carries the analytic HBM traffic model
(:func:`raft_tpu.observability.costmodel.fused_traffic_model`) next to
whatever XLA's ``cost_analysis`` measured, so predicted-vs-measured
divergence is part of the artifact — the evidence the grid-order work
is judged by (query-major re-fetches the database ``nq`` times;
database-major streams it once).

Off-TPU the tuner still runs END TO END deterministically: candidates
are ranked by the roofline-perfect time of their modeled traffic
(``min`` over a fixed candidate order — no timing jitter, no RNG), the
table is written with ``measured: false`` provenance, and the loader
treats its ``best_by_passes`` rows exactly like measured ones. That
path is what the tier-1 CPU suite exercises; a run on the chip
replaces the table with measured rows.

CLI::

    python -m raft_tpu.tune.fused                 # tune the driver shape
    python -m raft_tpu.tune.fused --dry           # tiny-shape validation
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import subprocess
import time
from typing import Dict, List, Optional, Sequence, Tuple

from raft_tpu.observability import instrument
from raft_tpu.resilience import fault_point

# schema 7 (this build): ``pq`` rows may carry a ``pq_mode`` field
# (plain / opq / opq_aniso) — mode-specific schedule picks written by
# :mod:`raft_tpu.tune.ivf` and read by ``ann.ivf_pq.resolve_pq_scan``.
# Schema-6 rows (no pq_mode) load unchanged and match EVERY mode.
# Schema-5 additions (the ``fine_scan`` column) and schema-4 additions
# (db_dtype rows/winners under ``best_by_passes_dtype``) unchanged.
# Committed schema ≤ 5 tables (incl. the measured v5e one) load
# unchanged: no pq column simply means the cost-model crossover
# decides.
TUNE_SCHEMA_VERSION = 7

# counter: tuned-table loads that degraded to built-in defaults, with a
# reason label ("tune.table_degraded" in the metrics docs) — the silent
# half of the degrade-to-defaults contract made loud. Reasons:
# unreadable / invalid / future_schema / row_rejected / shard_mismatch /
# missing (explicit env path only — an absent default table is the
# normal state, not a degradation).
TABLE_DEGRADED = "raft_tpu_tune_table_degraded_total"

_degraded_warned: set = set()


def table_degraded(table: str, reason: str, detail: str = "") -> None:
    """Count one degraded tuned-table load under
    :data:`TABLE_DEGRADED` ``{table, reason}`` and log at WARN once per
    (table, reason) per process — every later occurrence stays counted
    but quiet (a serving loop hitting a stale table must not spam)."""
    try:
        from raft_tpu.observability import get_registry

        reg = get_registry()
        reg.counter(TABLE_DEGRADED, {"table": table, "reason": reason},
                    help="Tuned-table loads degraded to built-in "
                         "defaults, by reason").inc()
        reg.emit({"type": "tune_table_degraded", "table": table,
                  "reason": reason, "detail": detail[:200]})
    except Exception:
        pass
    key = (table, reason)
    if key not in _degraded_warned:
        _degraded_warned.add(key)
        from raft_tpu.core.logger import log_warn

        log_warn("tune table %r degraded to built-ins (%s)%s — this "
                 "WARN fires once per process; the "
                 "tune.table_degraded counter keeps counting", table,
                 reason, f": {detail}" if detail else "")


def _reset_degraded_warnings() -> None:
    """Test hook: re-arm the once-per-process WARN."""
    _degraded_warned.clear()

# the driver benchmark shape (bench.py / BASELINE config 2, one-chip)
DRIVER_SHAPE = (2048, 1_000_000, 128, 64)

_GRID_AXES = {
    "T": (1024, 2048, 4096),
    "Qb": (256, 512, 1024),
    "g": (8, 16, 32),
    "grid_order": ("query", "db", "dbuf"),
    "passes": (1, 3),
    "db_dtype": ("bf16", "int8"),
}


@dataclasses.dataclass(frozen=True)
class Candidate:
    T: int
    Qb: int
    g: int
    passes: int
    grid_order: str = "query"
    db_dtype: str = "bf16"

    def as_row(self) -> Dict:
        return {"T": self.T, "Qb": self.Qb, "g": self.g,
                "passes": self.passes, "grid_order": self.grid_order,
                "db_dtype": self.db_dtype}


def candidate_space(d: int, axes: Optional[Dict] = None
                    ) -> Tuple[List[Candidate], List[Dict]]:
    """(kept, skipped-rows) for the sweep. Pruning is the production
    predicate chain — ``_valid_cfg`` then ``fit_config`` unshrunk at
    feature width ``d`` — so nothing the runtime would reject or
    silently reshape is ever measured; each skip is recorded with its
    reason (no silent truncation of the sweep)."""
    from raft_tpu.distance.knn_fused import (_D_SINGLE_SHOT, _valid_cfg,
                                             fit_config)

    axes = dict(_GRID_AXES, **(axes or {}))
    kept: List[Candidate] = []
    skipped: List[Dict] = []
    for T, Qb, g, order, p, dt in itertools.product(
            axes["T"], axes["Qb"], axes["g"], axes["grid_order"],
            axes["passes"], axes.get("db_dtype", ("bf16",))):
        cand = Candidate(T, Qb, g, p, order, dt)
        if not _valid_cfg(T, Qb, g, order):
            skipped.append(dict(cand.as_row(), skipped="invalid_cfg"))
            continue
        if dt == "int8" and (order == "query" or d > _D_SINGLE_SHOT):
            # the quantized kernels are packed database-major
            # single-shot only — prepare would downgrade the dtype, so
            # the point would silently measure bf16
            skipped.append(dict(cand.as_row(), skipped="q8_envelope"))
            continue
        if fit_config(T, Qb, d, p, g, order, dt) != (T, Qb):
            # over the scoped-VMEM budget: a guaranteed Mosaic compile
            # failure (or a silent shrink to a point already swept)
            skipped.append(dict(cand.as_row(),
                                skipped="vmem_footprint"))
            continue
        kept.append(cand)
    return kept, skipped


def _git_commit(repo: Optional[str] = None) -> str:
    from raft_tpu.native import _REPO_ROOT

    repo = repo or _REPO_ROOT
    try:
        r = subprocess.run(["git", "-C", repo, "rev-parse", "--short",
                            "HEAD"], capture_output=True, text=True,
                           timeout=10)
        head = r.stdout.strip() or "unknown"
        s = subprocess.run(["git", "-C", repo, "status", "--porcelain"],
                           capture_output=True, text=True, timeout=10)
        return head + "-dirty" if s.stdout.strip() else head
    except Exception:
        return "unknown"


def provenance(measured: bool) -> Dict:
    """Who/where/when a tune table came from — logged by the loader so
    a table measured on one chip generation (or never measured at all)
    can't masquerade as evidence for another."""
    import jax

    from raft_tpu.utils.arch import chip_spec, device_kind

    return {
        "chip": chip_spec().name,
        "device_kind": device_kind(),
        "platform": jax.default_backend(),
        "git_commit": _git_commit(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "measured": bool(measured),
        "schema": TUNE_SCHEMA_VERSION,
    }


def validate_tune_table(tbl) -> List[str]:
    """Structural validation shared by the writer (self-check before
    anything lands on disk) and the ``fused_config`` loader (a corrupt
    table degrades to built-ins instead of crashing knn). Legacy tables
    (no schema/provenance) validate clean — only structural corruption
    is an error; semantic per-row checks (``_valid_cfg``/``fit_config``)
    happen at load."""
    errors: List[str] = []
    if not isinstance(tbl, dict):
        return ["table is not a JSON object"]
    if "schema" in tbl and not isinstance(tbl["schema"], int):
        errors.append("schema is not an integer")
    if "provenance" in tbl and not isinstance(tbl["provenance"], dict):
        errors.append("provenance is not an object")
    shape = tbl.get("shape")
    if shape is not None and not (
            isinstance(shape, (list, tuple)) and len(shape) >= 4
            and all(isinstance(v, (int, float)) for v in shape)):
        errors.append("shape is not a [nq, m, d, k] list")
    rows = tbl.get("rows", [])
    if not isinstance(rows, list):
        errors.append("rows is not a list")
        rows = []
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            errors.append(f"rows[{i}] is not an object")
            continue
        if "seconds" in row or "predicted_seconds" in row:
            for key in ("T", "Qb", "g"):
                if not isinstance(row.get(key), int):
                    errors.append(f"rows[{i}].{key} missing/non-int")
    fs = tbl.get("fine_scan")
    if fs is not None:
        if not isinstance(fs, list):
            errors.append("fine_scan is not a list")
        else:
            for i, row in enumerate(fs):
                if not (isinstance(row, dict)
                        and isinstance(row.get("n_lists"), int)
                        and isinstance(row.get("n_probes"), int)
                        and row.get("fine_scan") in ("query", "list")):
                    errors.append(f"fine_scan[{i}] malformed")
    pq = tbl.get("pq")
    if pq is not None:
        if not isinstance(pq, list):
            errors.append("pq is not a list")
        else:
            for i, row in enumerate(pq):
                if not (isinstance(row, dict)
                        and isinstance(row.get("n_lists"), int)
                        and isinstance(row.get("n_probes"), int)
                        and isinstance(row.get("pq_bits"), int)
                        and row.get("pq_scan") in ("pq", "flat")):
                    errors.append(f"pq[{i}] malformed")
    for key in ("best", "best_by_passes", "best_by_passes_dtype"):
        entry = tbl.get(key)
        if entry is None:
            continue
        entries = (entry.values()
                   if key in ("best_by_passes", "best_by_passes_dtype")
                   and isinstance(entry, dict) else [entry])
        for e in entries:
            if not isinstance(e, dict) or not all(
                    isinstance(e.get(f), int) for f in ("T", "Qb", "g")):
                errors.append(f"{key} entry malformed")
    return errors


def target_spec():
    """The roofline the deterministic fallback ranks against: the host
    chip when it IS a TPU, else the last-measured driver chip (v5e —
    every BENCH_r* TPU round so far). Ranking against the host CPU's
    synthetic roofline would classify every candidate compute-bound and
    tie out exactly the y-traffic differences this tuner exists to
    rank."""
    import jax

    from raft_tpu.utils.arch import TPU_SPECS, chip_spec

    if jax.default_backend() == "tpu":
        return chip_spec()
    return TPU_SPECS[(5, "e")]


def predicted_row(shape: Sequence[int], cand: Candidate,
                  spec=None) -> Dict:
    """Deterministic (model-only) evidence for one candidate: the
    analytic traffic model placed on the target chip's roofline. The
    prediction key is ``predicted_seconds`` = roofline-perfect time —
    honest naming; it is never written as ``seconds``."""
    from raft_tpu.observability import costmodel

    spec = spec if spec is not None else target_spec()
    nq, m, d, k = (int(v) for v in shape[:4])
    model = costmodel.fused_traffic_model(
        nq, m, d, k, cand.T, cand.Qb, cand.g, cand.passes,
        cand.grid_order, cand.db_dtype)
    rec = costmodel.fused_traffic_record(
        nq, m, d, k, cand.T, cand.Qb, cand.g, cand.passes,
        cand.grid_order, cand.db_dtype)
    est = costmodel.roofline(rec, spec)
    row = cand.as_row()
    row.update({
        "predicted_seconds": est.roof_seconds,
        "predicted_gbps": (nq * m * 4.0 / est.roof_seconds / 1e9
                           if est.roof_seconds else None),
        "model_total_bytes": model["total_bytes"],
        "model_y_bytes": model["y_bytes"],
        "model_y_stream_factor": model["y_stream_factor"],
        "bound": est.bound,
    })
    return row


@instrument("tune.autotune_fused")
def autotune_fused(res=None, shape: Sequence[int] = DRIVER_SHAPE,
                   out_path: Optional[str] = "TUNE_FUSED.json",
                   budget_s: float = 2400.0,
                   measure: Optional[bool] = None,
                   reps: int = 3, axes: Optional[Dict] = None,
                   data=None) -> Dict:
    """Tune the fused pipeline for ``shape`` = (nq, m, d, k).

    ``measure=None`` auto-selects: real timing on TPU, the
    deterministic model-ranked fallback elsewhere. Measured mode builds
    the index ONCE per candidate (steady-state query throughput, the
    bench.py metric), times through ``benchmark.Fixture`` (cost capture
    + roofline fields ride along via ``res.profiler``), honors the
    ``budget_s`` deadline between points, and writes incrementally so a
    killed sweep loses one point. Returns the table (also written to
    ``out_path`` unless None)."""
    import jax

    from raft_tpu.core.resources import ensure_resources
    from raft_tpu.observability import costmodel

    fault_point("autotune_fused")
    res = ensure_resources(res)
    nq, m, d, k = (int(v) for v in shape[:4])
    if measure is None:
        measure = jax.default_backend() == "tpu"
    cands, skipped = candidate_space(d, axes)
    rows: List[Dict] = list(skipped)

    def _winners(ranked, key):
        """(best_by_passes — bf16 rows under bare-passes keys, the
        schema-3 contract old loaders read — and best_by_passes_dtype,
        winners per (passes, db_dtype) under 'p:dtype' keys)."""
        by_p: Dict[str, Dict] = {}
        by_pd: Dict[str, Dict] = {}
        for p in sorted({c.passes for c in cands}):
            bp = [r for r in ranked if r["passes"] == p
                  and r.get("db_dtype", "bf16") == "bf16"]
            if bp:
                by_p[str(p)] = min(bp, key=key)
            for dt in sorted({c.db_dtype for c in cands}):
                rp = [r for r in ranked if r["passes"] == p
                      and r.get("db_dtype", "bf16") == dt]
                if rp:
                    by_pd[f"{p}:{dt}"] = min(rp, key=key)
        return by_p, by_pd

    def _flush(best, best_by_passes, best_by_dtype=None):
        prov = provenance(measured=measure)
        if not measure:
            prov["target_chip"] = target_spec().name
        tbl = {
            "schema": TUNE_SCHEMA_VERSION,
            "provenance": prov,
            "shape": [nq, m, d, k],
            "rows": rows,
            "best": best,
            "best_by_passes": best_by_passes,
            "best_by_passes_dtype": best_by_dtype or {},
        }
        errors = validate_tune_table(tbl)
        if errors:     # writer self-check: never ship a corrupt table
            raise ValueError(f"autotune_fused produced an invalid "
                             f"table: {errors}")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(tbl, f, indent=1)
                f.write("\n")
        return tbl

    if not measure:
        # deterministic fallback: rank every candidate by the modeled
        # roofline-perfect time on the TARGET chip's roofline; fixed
        # iteration order, no RNG/clock
        spec = target_spec()
        rows.extend(predicted_row(shape, c, spec) for c in cands)
        ranked = [r for r in rows if "predicted_seconds" in r]
        best = min(ranked, key=lambda r: r["predicted_seconds"],
                   default=None)
        by_p, by_pd = _winners(ranked,
                               lambda r: r["predicted_seconds"])
        return _flush(best, by_p, by_pd)

    from raft_tpu.benchmark import Fixture
    from raft_tpu.distance.knn_fused import knn_fused, prepare_knn_index
    from raft_tpu.random import RngState, make_blobs

    if data is None:
        X, _ = make_blobs(res, RngState(0), m, d, n_clusters=64,
                          cluster_std=2.0)
    else:
        X = data
    Q = X[:nq]
    jax.block_until_ready(X)
    fx = Fixture(res=res, reps=reps)
    eff_bytes = nq * m * 4.0
    deadline = time.monotonic() + budget_s
    best = None
    best_by: Dict[str, Dict] = {}
    best_by_dt: Dict[str, Dict] = {}
    for cand in cands:
        if time.monotonic() > deadline:
            rows.append({"budget_expired_after":
                         len([r for r in rows if "seconds" in r])})
            break
        row = cand.as_row()
        row.update({f"model_{key}": v for key, v in
                    costmodel.fused_traffic_model(
                        nq, m, d, k, cand.T, cand.Qb, cand.g,
                        cand.passes, cand.grid_order,
                        cand.db_dtype).items()
                    if key not in ("grid_order", "db_dtype")})
        try:
            idx = prepare_knn_index(
                X, passes=cand.passes, T=cand.T, Qb=cand.Qb, g=cand.g,
                grid_order=cand.grid_order, db_dtype=cand.db_dtype)
            name = (f"tune_fused[T={cand.T},Qb={cand.Qb},g={cand.g},"
                    f"{cand.grid_order},p{cand.passes},"
                    f"{cand.db_dtype}]")
            r = fx.run(lambda q: knn_fused(q, idx, k=k)[0], Q,
                       name=name)
            row["seconds"] = round(r["seconds"], 5)
            row["gbps"] = round(eff_bytes / r["seconds"] / 1e9, 1)
            # PR-2 evidence fields (XLA cost capture via res.profiler)
            for f in ("bytes_accessed", "flops", "roofline_frac",
                      "bound"):
                if f in r:
                    row[f] = r[f]
            # one explicit capture of the winner-so-far's executable so
            # the tune artifact has a cost record even when Fixture's
            # tracing was disabled mid-sweep
            res.profiler.capture_fn(name, lambda q: knn_fused(
                q, idx, k=k)[0], Q)
        except Exception as e:   # point off-envelope / lowering failure
            row["error"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)
        ok = [r for r in rows if "seconds" in r]
        best = min(ok, key=lambda r: r["seconds"]) if ok else None
        best_by, best_by_dt = _winners(ok, lambda r: r["seconds"])
        _flush(best, best_by, best_by_dt)  # incremental: a kill loses
        #                                    one point
    return _flush(best, best_by, best_by_dt)


# kept as a module-level alias so callers can write tables produced
# elsewhere (tests, merge tooling) through the same self-check
def write_tune_table(path: str, tbl: Dict) -> None:
    errors = validate_tune_table(tbl)
    if errors:
        raise ValueError(f"write_tune_table: invalid table: {errors}")
    with open(path, "w") as f:
        json.dump(tbl, f, indent=1)
        f.write("\n")


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--shape", type=int, nargs=4,
                   default=list(DRIVER_SHAPE),
                   metavar=("NQ", "M", "D", "K"))
    p.add_argument("--out", default="TUNE_FUSED.json")
    p.add_argument("--budget-s", type=float, default=float(
        os.environ.get("TUNE_FUSED_BUDGET_S", "2400")))
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--dry", action="store_true",
                   help="tiny-shape harness validation (no artifact)")
    p.add_argument("--predict-only", action="store_true",
                   help="force the deterministic model-ranked fallback")
    args = p.parse_args(argv)
    shape = ((256, 20_000, 64, 32) if args.dry
             else tuple(args.shape))
    tbl = autotune_fused(
        shape=shape,
        out_path=None if args.dry else args.out,
        budget_s=args.budget_s,
        measure=False if args.predict_only else None,
        reps=1 if args.dry else args.reps)
    best = tbl.get("best")
    print(json.dumps({"best": best,
                      "rows": len(tbl.get("rows", [])),
                      "measured": tbl["provenance"]["measured"]}))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
