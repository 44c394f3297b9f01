"""Autotuner for the SHARDED fused-KNN pipeline (ISSUE 4).

Sweeps ``(merge strategy, micro-batch count, local T/Qb) × passes`` for
a target shape at a given shard count, pruning with the SAME predicates
production uses — ``_valid_cfg`` + ``fit_config`` unshrunk (a local
config the runtime would silently reshape is never measured as
written), and the power-of-two constraint of the tournament merge —
and writes a schema-3, provenance-stamped ``TUNE_SHARDED.json``
(:func:`raft_tpu.tune.fused.provenance` / ``validate_tune_table`` are
reused verbatim, so one loader hardening covers both tables).

Off-TPU the tuner runs END TO END deterministically, like
``autotune_fused``: every candidate is ranked by a modeled pipeline
time on the target chip —

    local   = roofline-perfect time of the PER-SHARD fused kernel
              (``costmodel.fused_traffic_record`` on the nq × m/p × d
              shard shape)
    merge   = ``costmodel.ici_time_model`` per query block ×
              micro-batches
    total   = block-pipelined: the first block's local compute, then
              nb−1 overlapped stages of max(local_block, merge_block),
              then the last merge (the double-buffered schedule
              knn_fused_sharded is shaped for)

— fixed candidate order, no RNG, no clock; ``measured: false``
provenance. A run on the chip replaces the table with measured
rows.

CLI::

    python -m raft_tpu.tune.sharded                # north-star shape
    python -m raft_tpu.tune.sharded --dry          # tiny-shape check
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from raft_tpu.observability import instrument
from raft_tpu.resilience import fault_point
from raft_tpu.tune.fused import (TUNE_SCHEMA_VERSION, provenance,
                                 table_degraded, validate_tune_table,
                                 write_tune_table)

# the north-star workload — the shape that is at
# the one-chip capacity wall and exists to be sharded
NORTHSTAR_SHAPE = (2048, 10_000_000, 256, 64)

_SHARDED_AXES = {
    "T": (512, 1024, 2048),
    "Qb": (256, 512),
    "g": (2, 4, 8),
    "merge": ("allgather", "tournament"),
    "micro_batches": (1, 2, 4, 8),
    "passes": (1, 3),
    "db_dtype": ("bf16", "int8"),
}

# the sharded sweep tunes the stream-once local kernel — the db-major
# order IS the tentpole configuration (dbuf/query remain reachable via
# knn_fused_sharded's grid_order kwarg, tuned by the fused sweep)
_GRID_ORDER = "db"


@dataclasses.dataclass(frozen=True)
class ShardedCandidate:
    T: int
    Qb: int
    g: int
    merge: str
    micro_batches: int
    passes: int
    db_dtype: str = "bf16"

    def as_row(self) -> Dict:
        return {"T": self.T, "Qb": self.Qb, "g": self.g,
                "merge": self.merge,
                "micro_batches": self.micro_batches,
                "passes": self.passes, "grid_order": _GRID_ORDER,
                "db_dtype": self.db_dtype}


def sharded_candidate_space(d: int, p: int, axes: Optional[Dict] = None
                            ) -> Tuple[List[ShardedCandidate],
                                       List[Dict]]:
    """(kept, skipped-rows) for the sharded sweep. The pruning chain is
    production's: ``_valid_cfg`` → ``fit_config`` unshrunk at feature
    width ``d`` → the tournament power-of-two constraint; each skip is
    recorded with its reason (no silent sweep truncation). ``g`` is
    swept too: the stream-once db order holds a whole [g·T, d] group
    VMEM-resident, so the single-chip tuned g can be a guaranteed
    scoped-VMEM reject at the sharded d."""
    from raft_tpu.distance.knn_fused import (_D_SINGLE_SHOT, _valid_cfg,
                                             fit_config)

    axes = dict(_SHARDED_AXES, **(axes or {}))
    kept: List[ShardedCandidate] = []
    skipped: List[Dict] = []
    pow2 = p > 0 and not (p & (p - 1))
    for T, Qb, g, merge, nb, passes, dt in itertools.product(
            axes["T"], axes["Qb"], axes["g"], axes["merge"],
            axes["micro_batches"], axes["passes"],
            axes.get("db_dtype", ("bf16",))):
        cand = ShardedCandidate(T, Qb, g, merge, nb, passes, dt)
        if not _valid_cfg(T, Qb, g, _GRID_ORDER):
            skipped.append(dict(cand.as_row(), skipped="invalid_cfg"))
            continue
        if dt == "int8" and d > _D_SINGLE_SHOT:
            skipped.append(dict(cand.as_row(), skipped="q8_envelope"))
            continue
        if fit_config(T, Qb, d, passes, g, _GRID_ORDER,
                      dt) != (T, Qb):
            skipped.append(dict(cand.as_row(),
                                skipped="vmem_footprint"))
            continue
        if merge == "tournament" and not pow2:
            skipped.append(dict(cand.as_row(), skipped="merge_pow2"))
            continue
        kept.append(cand)
    return kept, skipped


def sharded_time_model(shape: Sequence[int], p: int,
                       cand: ShardedCandidate, spec=None) -> Dict:
    """Modeled end-to-end time of one sharded candidate (see module
    doc): per-shard local roofline time + overlapped per-block merge.
    Deterministic — the off-TPU ranking key AND the modeled half of
    every measured row."""
    from raft_tpu.observability import costmodel
    from raft_tpu.tune.fused import target_spec

    spec = spec if spec is not None else target_spec()
    nq, m, d, k = (int(v) for v in shape[:4])
    m_loc = -(-m // max(p, 1))
    rec = costmodel.fused_traffic_record(
        nq, m_loc, d, k, cand.T, cand.Qb, cand.g, cand.passes,
        _GRID_ORDER, cand.db_dtype)
    local_s = costmodel.roofline(rec, spec).roof_seconds
    nb = max(1, cand.micro_batches)
    nq_b = -(-nq // nb)
    ici = costmodel.ici_time_model(p, nq_b, k, cand.merge, spec)
    merge_b = ici["merge_seconds"]
    local_b = local_s / nb
    # block pipeline: fill (one local block), nb−1 overlapped stages,
    # drain (the last merge)
    total = local_b + (nb - 1) * max(local_b, merge_b) + merge_b
    return {
        "predicted_seconds": total,
        "model_local_seconds": local_s,
        "model_merge_seconds": nb * merge_b,
        "model_ici_bytes_per_device": nb * ici["wire_bytes_per_device"],
        "model_ici_rounds": nb * ici["rounds"],
        "model_busbw_frac": ((nb * ici["wire_bytes_per_device"])
                             / ((spec.ici_bw or spec.hbm_bw) * total)
                             if total else 0.0),
        "model_local_bytes": rec.bytes_accessed,
    }


def predicted_sharded_row(shape: Sequence[int], p: int,
                          cand: ShardedCandidate, spec=None) -> Dict:
    nq, m, _, _ = (int(v) for v in shape[:4])
    row = cand.as_row()
    row.update(sharded_time_model(shape, p, cand, spec))
    t = row["predicted_seconds"]
    row["predicted_gbps"] = nq * m * 4.0 / t / 1e9 if t else None
    return row


_TUNED_SHARDED = ...    # lazy: parsed table dict, or None


def sharded_config(p: Optional[int] = None) -> Dict:
    """Best tuned (merge, micro_batches, T, Qb) row from
    ``TUNE_SHARDED.json`` (``RAFT_TPU_TUNE_SHARDED`` overrides the
    path), or {} when no table exists, the table is corrupt, or it was
    tuned for a different shard count — the same degrade-to-defaults
    contract as ``fused_config``."""
    global _TUNED_SHARDED
    if _TUNED_SHARDED is ...:
        _TUNED_SHARDED = _load_sharded_table()
    tbl = _TUNED_SHARDED
    if not tbl:
        return {}
    if p is not None and tbl.get("n_shards") not in (None, int(p)):
        table_degraded("sharded", "shard_mismatch",
                       f"table tuned for p={tbl.get('n_shards')}, "
                       f"call wants p={p}")
        return {}
    best = tbl.get("best")
    return dict(best) if isinstance(best, dict) else {}


def _load_sharded_table() -> Optional[Dict]:
    from raft_tpu.core.logger import log_info
    from raft_tpu.native import _REPO_ROOT

    path_env = os.environ.get("RAFT_TPU_TUNE_SHARDED")
    path = path_env or os.path.join(_REPO_ROOT, "TUNE_SHARDED.json")
    if fault_point("tune_table_read") == "corrupt":
        table_degraded("sharded", "unreadable",
                       f"{path}: injected corrupt table read")
        return None
    try:
        with open(path) as f:
            tbl = json.load(f)
    except FileNotFoundError:
        if path_env:
            table_degraded("sharded", "missing", path)
        return None
    except Exception as e:
        table_degraded("sharded", "unreadable",
                       f"{path}: {type(e).__name__}: {e}")
        return None
    errors = validate_tune_table(tbl)
    if errors:
        table_degraded("sharded", "invalid",
                       f"{path}: " + "; ".join(errors))
        return None
    if int(tbl.get("schema", 1)) > TUNE_SCHEMA_VERSION:
        table_degraded("sharded", "future_schema",
                       f"{path}: schema {tbl.get('schema')}")
        return None
    prov = tbl.get("provenance", {})
    log_info("sharded_config: loaded %s (schema %s, chip=%s, "
             "measured=%s)", path, tbl.get("schema", "legacy"),
             prov.get("chip", "unknown"),
             prov.get("measured", "unknown"))
    return tbl


@instrument("tune.autotune_sharded")
def autotune_sharded(res=None, shape: Sequence[int] = NORTHSTAR_SHAPE,
                     p: Optional[int] = None,
                     out_path: Optional[str] = "TUNE_SHARDED.json",
                     budget_s: float = 2400.0,
                     measure: Optional[bool] = None,
                     reps: int = 3, axes: Optional[Dict] = None,
                     mesh=None, data=None) -> Dict:
    """Tune the sharded pipeline for ``shape`` = (nq, m, d, k) over
    ``p`` shards (default: every local device).

    ``measure=None`` auto-selects: real timing on a multi-device TPU
    backend, the deterministic model-ranked fallback elsewhere.
    Measured mode prepares the sharded index once per (T, Qb, passes)
    local config (steady-state query throughput), times
    ``knn_fused_sharded`` through ``benchmark.Fixture`` with the
    ``res.profiler`` cost capture riding along, honors ``budget_s``,
    and writes incrementally. Every row carries the deterministic
    :func:`sharded_time_model` fields next to whatever was measured,
    so predicted-vs-measured divergence is part of the artifact."""
    import jax

    from raft_tpu.core.resources import ensure_resources

    fault_point("autotune_sharded")
    res = ensure_resources(res)
    nq, m, d, k = (int(v) for v in shape[:4])
    if p is None:
        p = len(jax.devices())
    if measure is None:
        measure = jax.default_backend() == "tpu" and p > 1
    cands, skipped = sharded_candidate_space(d, p, axes)
    rows: List[Dict] = list(skipped)

    def _winners(ranked, key):
        by_p: Dict[str, Dict] = {}
        by_pd: Dict[str, Dict] = {}
        for ps in sorted({c.passes for c in cands}):
            bp = [r for r in ranked if r["passes"] == ps
                  and r.get("db_dtype", "bf16") == "bf16"]
            if bp:
                by_p[str(ps)] = min(bp, key=key)
            for dt in sorted({c.db_dtype for c in cands}):
                rp = [r for r in ranked if r["passes"] == ps
                      and r.get("db_dtype", "bf16") == dt]
                if rp:
                    by_pd[f"{ps}:{dt}"] = min(rp, key=key)
        return by_p, by_pd

    def _flush(best, best_by_passes, best_by_dtype=None):
        prov = provenance(measured=measure)
        if not measure:
            from raft_tpu.tune.fused import target_spec

            prov["target_chip"] = target_spec().name
        tbl = {
            "schema": TUNE_SCHEMA_VERSION,
            "provenance": prov,
            "shape": [nq, m, d, k],
            "n_shards": p,
            "rows": rows,
            "best": best,
            "best_by_passes": best_by_passes,
            "best_by_passes_dtype": best_by_dtype or {},
        }
        errors = validate_tune_table(tbl)
        if errors:
            raise ValueError(f"autotune_sharded produced an invalid "
                             f"table: {errors}")
        if out_path:
            write_tune_table(out_path, tbl)
        return tbl

    if not measure:
        from raft_tpu.tune.fused import target_spec

        spec = target_spec()
        rows.extend(predicted_sharded_row(shape, p, c, spec)
                    for c in cands)
        ranked = [r for r in rows if "predicted_seconds" in r]
        best = min(ranked, key=lambda r: r["predicted_seconds"],
                   default=None)
        by_p, by_pd = _winners(ranked,
                               lambda r: r["predicted_seconds"])
        return _flush(best, by_p, by_pd)

    from raft_tpu.benchmark import Fixture
    from raft_tpu.distance.knn_sharded import (knn_fused_sharded,
                                               prepare_knn_index_sharded)
    from raft_tpu.parallel import make_mesh
    from raft_tpu.random import RngState, make_blobs

    if mesh is None:
        mesh = make_mesh({"x": p}, devices=jax.devices()[:p])
    if data is None:
        X, _ = make_blobs(res, RngState(0), m, d, n_clusters=64,
                          cluster_std=2.0)
    else:
        X = data
    Q = X[:nq]
    jax.block_until_ready(Q)
    fx = Fixture(res=res, reps=reps)
    eff_bytes = nq * m * 4.0
    deadline = time.monotonic() + budget_s
    best = None
    best_by: Dict[str, Dict] = {}
    best_by_dt: Dict[str, Dict] = {}
    indexes: Dict[Tuple, object] = {}   # (T, Qb, g, passes, dt) → idx
    for cand in cands:
        if time.monotonic() > deadline:
            rows.append({"budget_expired_after":
                         len([r for r in rows if "seconds" in r])})
            break
        row = predicted_sharded_row(shape, p, cand)
        try:
            ikey = (cand.T, cand.Qb, cand.g, cand.passes,
                    cand.db_dtype)
            idx = indexes.get(ikey)
            if idx is None:
                idx = prepare_knn_index_sharded(
                    X, mesh=mesh, passes=cand.passes, T=cand.T,
                    Qb=cand.Qb, g=cand.g, grid_order=_GRID_ORDER,
                    db_dtype=cand.db_dtype, res=res)
                indexes[ikey] = idx
            name = (f"tune_sharded[p={p},T={cand.T},Qb={cand.Qb},"
                    f"{cand.merge},nb={cand.micro_batches},"
                    f"p{cand.passes},{cand.db_dtype}]")
            run = fx.run(
                lambda q: knn_fused_sharded(
                    q, idx, k, mesh=mesh, merge=cand.merge,
                    micro_batches=cand.micro_batches)[0],
                Q, name=name)
            row["seconds"] = round(run["seconds"], 5)
            row["gbps"] = round(eff_bytes / run["seconds"] / 1e9, 1)
            for f in ("bytes_accessed", "flops", "roofline_frac",
                      "bound"):
                if f in run:
                    row[f] = run[f]
            res.profiler.capture_fn(
                name, lambda q: knn_fused_sharded(
                    q, idx, k, mesh=mesh, merge=cand.merge,
                    micro_batches=cand.micro_batches)[0], Q)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)
        ok = [r for r in rows if "seconds" in r]
        best = min(ok, key=lambda r: r["seconds"]) if ok else None
        best_by, best_by_dt = _winners(ok, lambda r: r["seconds"])
        _flush(best, best_by, best_by_dt)
    return _flush(best, best_by, best_by_dt)


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", type=int, nargs=4,
                    default=list(NORTHSTAR_SHAPE),
                    metavar=("NQ", "M", "D", "K"))
    ap.add_argument("--shards", type=int, default=None)
    ap.add_argument("--out", default="TUNE_SHARDED.json")
    ap.add_argument("--budget-s", type=float, default=float(
        os.environ.get("TUNE_SHARDED_BUDGET_S", "2400")))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--dry", action="store_true",
                    help="tiny-shape harness validation (no artifact)")
    ap.add_argument("--predict-only", action="store_true",
                    help="force the deterministic model-ranked fallback")
    args = ap.parse_args(argv)
    shape = ((256, 20_000, 64, 32) if args.dry else tuple(args.shape))
    tbl = autotune_sharded(
        shape=shape, p=args.shards,
        out_path=None if args.dry else args.out,
        budget_s=args.budget_s,
        measure=False if args.predict_only else None,
        reps=1 if args.dry else args.reps)
    print(json.dumps({"best": tbl.get("best"),
                      "rows": len(tbl.get("rows", [])),
                      "n_shards": tbl.get("n_shards"),
                      "measured": tbl["provenance"]["measured"]}))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
