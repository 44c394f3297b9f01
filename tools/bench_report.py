#!/usr/bin/env python
"""Perf-evidence pipeline: BENCH_*.json → one trajectory + CI gate.

The repo accumulates one ``BENCH_r<NN>.json`` per measurement round (each
wraps the benchmark's one-line JSON in ``{"n", "cmd", "rc", "tail",
"parsed"}``) plus ``BENCH_LAST_GOOD.json`` — the last known-good
flat record. This tool turns that pile of disconnected artifacts into:

1. a **trajectory report** (default): per-round series of the headline
   metric and its sub-metrics (p1/p3 GB/s), commit labels, degraded
   flags, and — for artifacts produced after the cost-model PR — the
   static FLOPs/bytes and %-of-roofline columns ``benchmark.Fixture.run``
   now emits;
2. a **regression gate** (``--check``): the newest round is compared
   against BENCH_LAST_GOOD with a configurable threshold (a degraded
   newest round is a no-op — outage artifacts are history, not gates).
   Exit 0 = pass or nothing to gate (no new comparable artifact — the
   tier-1 no-op), exit 1 = regression, exit 2 = a gateable artifact
   exists but the baseline is missing;
3. a **drift gate** (part of ``--check``): DRIFT_LEDGER.json — the
   model-vs-measured ledger ``benchmark.Fixture.run`` records — is
   scanned per site; a site whose MEASURED entry has the cost model's
   predicted seconds off by more than ``--drift-band`` (default 3x
   either way) fails the gate. Modeled-only entries (``measured:
   false`` — the CPU suite) are never drift-gated, and artifacts carry
   ``drift_checked`` so calibrated rounds are tellable from modeled
   ones.

Degraded rounds (an artifact carrying ``degraded`` or a nonzero
``resilience_degradations``) are shown in the trajectory but never
gated — they describe a fallback path, not the measured one.

Usage::

    python tools/bench_report.py                  # trajectory report
    python tools/bench_report.py --check          # CI gate (tier-1)
    python tools/bench_report.py --check --threshold 0.10
    python tools/bench_report.py --dir /path/to/artifacts --json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROUND_GLOB = "BENCH_r*.json"
MULTICHIP_GLOB = "MULTICHIP_r*.json"
SERVING_GLOB = "SERVING_r*.json"
SERVING_NAME = "BENCH_SERVING.json"
ANN_GLOB = "ANN_r*.json"
ANN_NAME = "BENCH_ANN.json"
MUTATION_GLOB = "MUTATION_r*.json"
MUTATION_NAME = "BENCH_MUTATION.json"
RECOVERY_GLOB = "RECOVERY_r*.json"
RECOVERY_NAME = "BENCH_RECOVERY.json"
# recall@k may drop at most this much ABSOLUTE between rounds (recall
# is platform-independent math, so the trend gates modeled rounds too —
# only the ms columns are speed and measured-only)
ANN_RECALL_SLACK = 0.02
#: relative slack on the ANN fine-scan overread trend: the newest
#: round's best modeled list-major overread win may not fall more than
#: this fraction below the previous comparable round's (ISSUE 14)
ANN_OVERREAD_SLACK = 0.2
BASELINE_NAME = "BENCH_LAST_GOOD.json"
DRIFT_LEDGER_NAME = "DRIFT_LEDGER.json"
DEFAULT_THRESHOLD = 0.15   # 15% relative drop (or slowdown) fails
# flag a site when the cost model's predicted seconds and the MEASURED
# seconds disagree by more than this factor either way. Mirror of
# raft_tpu.observability.timeline.DRIFT_BAND (this tool stays
# raft_tpu-import-free); tests/test_flight.py pins the two equal.
DRIFT_BAND = 3.0

# named single-shot artifacts whose numbers predate arbitrary amounts of
# later work: the report flags the ones whose last-touching commit is
# older than the last-good measurement's commit instead of silently
# presenting them as current (SELECT_K_MATRIX / PALLAS_SMOKE / TPU_FUZZ
# all predate multiple perf rounds at the time this gate shipped)
NAMED_ARTIFACTS = ("SELECT_K_MATRIX.json", "PALLAS_SMOKE.json",
                   "TPU_FUZZ.json", "BUSBW_BENCH.json",
                   "BENCH_SERVING.json", "BENCH_ANN.json",
                   "BENCH_MUTATION.json", "BENCH_RECOVERY.json",
                   "LINT_REPORT.json")

#: graftlint machine report (tools/graftlint.py --json): the [lint]
#: gate — nonzero unsuppressed error findings REGRESS the check
LINT_NAME = "LINT_REPORT.json"

# cost-model fields Fixture.run emits into BENCH artifacts (PR 2+)
COST_FIELDS = ("flops", "bytes_accessed", "arithmetic_intensity",
               "peak_hbm_bytes", "bound", "roofline_frac")

PASS, REGRESS, MISSING_BASELINE, SKIP = ("pass", "regress",
                                         "missing-baseline", "skip")

#: quantized-index-streaming gate: int8 rows must model ≤ this fraction
#: of the bf16 baseline's streamed database bytes (the point of the
#: dtype — 1/2 at passes=1 before the scale-tile overhead), and their
#: id-parity flag must hold
QUANTIZED_RATIO_CEIL = 0.55

#: PQ-tier gate (the quantized gate extended to product quantization):
#: the modeled codes-slab stream must be ≤ this fraction of the f32
#: slab stream (1/16 at 8-bit codes with pq_dim = d/4, 1/32 at 4-bit)
#: AND the id-parity-after-rescore flag must hold.
PQ_RATIO_CEIL = 0.10

#: PQ certificate-rerun gate (ISSUE 19): on the diffuse-Gaussian
#: (worst-case) benchmark distribution the certificate's exact-rerun
#: fraction at the recall floor must be ≤ this ceiling, and must not
#: rise more than ``PQ_RERUN_SLACK`` absolute vs the previous
#: comparable round.
PQ_RERUN_CEIL = 0.10
PQ_RERUN_SLACK = 0.05

#: quality-telemetry gate: any recall a ``quality`` block carries
#: (online shadow recall, offline ANN recall) must reach this floor —
#: the same 0.95 the ANN frontier gate enforces. Mirror of
#: raft_tpu.observability.quality.DEFAULT_SHADOW_FLOOR (this tool
#: stays raft_tpu-import-free); tests pin the two equal.
QUALITY_RECALL_FLOOR = 0.95


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default method). Mirror
    of ``raft_tpu.observability.metrics.percentile`` — this tool stays
    raft_tpu-import-free, so the implementation is duplicated and
    tests/test_quality.py pins the two equal on random data."""
    vs = sorted(float(v) for v in values)
    if not vs:
        raise ValueError("percentile: empty sample set")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile: q={q} outside [0, 100]")
    if len(vs) == 1:
        return vs[0]
    rank = (len(vs) - 1) * (q / 100.0)
    lo = int(rank)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (rank - lo)


def load_record(path: str) -> Optional[Dict]:
    """Flat benchmark record from a BENCH artifact: unwraps the driver's
    ``{"parsed": ...}`` envelope; None for unreadable/recordless files."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    rec = data.get("parsed", data)
    if not isinstance(rec, dict) or "metric" not in rec:
        return None
    return rec


def normalize_metric(name: str) -> str:
    """Comparison key for a metric name: the bare primitive+shape, with
    parenthesized platform notes and bracketed cache/outage annotations
    stripped — ``"fused_l2nn+select_k top-64 2048x... (tpu, ...) [CACHED
    ...]"`` and its BENCH_LAST_GOOD spelling compare equal."""
    base = re.sub(r"\s*\[[^\]]*\]", "", name)
    base = re.sub(r"\s*\([^)]*\)", "", base)
    return base.strip()


def higher_is_better(unit: str) -> bool:
    """GB/s-style rates improve upward; ms/seconds improve downward."""
    return unit.strip().lower().endswith("/s")


def collect_rounds(directory: str) -> List[Tuple[int, str, Optional[Dict]]]:
    """(round number, path, record) for every BENCH_r*.json, in round
    order; unparseable files keep their slot with record=None so the
    trajectory shows the hole instead of silently closing it."""
    out = []
    for path in glob.glob(os.path.join(directory, ROUND_GLOB)):
        m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        out.append((int(m.group(1)), path, load_record(path)))
    out.sort(key=lambda t: t[0])
    return out


def load_multichip(path: str) -> Optional[Dict]:
    """Flat multichip record: unwraps the driver's envelope like
    :func:`load_record`, but multichip rounds are NOT required to carry
    a perf metric — the early rounds are bare ``{n_devices, rc, ok}``
    dryrun verdicts and must stay visible in the trajectory."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    rec = data.get("parsed")
    if isinstance(rec, dict) and ("ok" in rec or "strategies" in rec):
        merged = dict(data)
        merged.update(rec)
        return merged
    if "ok" in data or "n_devices" in data or "strategies" in data:
        return data
    return None


def collect_multichip(directory: str
                      ) -> List[Tuple[int, str, Optional[Dict]]]:
    out = []
    for path in glob.glob(os.path.join(directory, MULTICHIP_GLOB)):
        m = re.search(r"MULTICHIP_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        out.append((int(m.group(1)), path, load_multichip(path)))
    out.sort(key=lambda t: t[0])
    return out


def _best_busbw(rec: Dict) -> Optional[float]:
    strategies = rec.get("strategies")
    if not isinstance(strategies, dict):
        return None
    fracs = [s.get("busbw_frac") for s in strategies.values()
             if isinstance(s, dict)
             and isinstance(s.get("busbw_frac"), (int, float))]
    return max(fracs) if fracs else None


def check_multichip(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
                    threshold: float = DEFAULT_THRESHOLD
                    ) -> Tuple[str, str]:
    """Gate the MULTICHIP trend: the newest parseable round must be
    ``ok`` (a failed distributed dryrun/bench is a regression, not a
    footnote), and when the newest AND a previous round both carry
    MEASURED sharded-KNN throughput, the newest must hold the value
    within ``threshold`` (modeled off-TPU rounds are evidence of model
    shape, not chip speed — never gated against measured history)."""
    newest = None
    for _, _, rec in reversed(rounds):
        if rec is not None:
            newest = rec
            break
    if newest is None:
        return SKIP, "no MULTICHIP artifact to gate"
    if newest.get("skipped"):
        return SKIP, "latest MULTICHIP round skipped (no devices)"
    mrd = newest.get("resilience_degradations")
    if isinstance(mrd, (int, float)) and mrd > 0:
        return SKIP, (
            f"latest MULTICHIP round recorded {mrd:g} resilience "
            f"degradation ladder step(s) — a degraded run is history, "
            f"never gated and never baseline material")
    if not newest.get("ok", True):
        return REGRESS, ("latest MULTICHIP round failed (ok=false) — "
                         "the distributed path regressed")
    value = newest.get("value")
    if not newest.get("measured") or not isinstance(value, (int, float)):
        return PASS, ("latest MULTICHIP round ok"
                      + ("" if newest.get("measured")
                         else " (modeled — not gated on speed)"))
    prev = None
    for _, _, rec in reversed(rounds[:-1]):
        if (rec is not None and rec.get("measured")
                and isinstance(rec.get("value"), (int, float))
                and rec.get("unit", "GB/s") == newest.get("unit",
                                                          "GB/s")):
            prev = rec
            break
    if prev is None:
        return PASS, (f"multichip ok: {value:g} "
                      f"{newest.get('unit', 'GB/s')} (first measured "
                      f"round — nothing to trend against)")
    floor = prev["value"] * (1.0 - threshold)
    if value < floor:
        return REGRESS, (
            f"MULTICHIP REGRESSION: {value:g} < {floor:g} "
            f"(previous measured {prev['value']:g} − {threshold:.0%})")
    msg = (f"multichip ok: {value:g} {newest.get('unit', 'GB/s')} vs "
           f"previous {prev['value']:g}")
    bw, pbw = _best_busbw(newest), _best_busbw(prev)
    if bw is not None and pbw is not None and pbw > 0:
        if bw < pbw * (1.0 - threshold):
            return REGRESS, (
                f"MULTICHIP BUSBW REGRESSION: busbw_frac {bw:.3g} < "
                f"{pbw * (1.0 - threshold):.3g} (previous {pbw:.3g} − "
                f"{threshold:.0%}) — the merge lost ICI ground even "
                f"though the headline holds")
        msg += f"; busbw_frac {bw:.3g} vs {pbw:.3g}"
    return PASS, msg


def load_serving(path: str) -> Optional[Dict]:
    """Flat serving-SLO record (``BENCH_SERVING.json``): unwraps
    the driver's envelope like :func:`load_multichip`. A record must
    carry at least an ``ok`` verdict or a latency/throughput field to
    count."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    rec = data.get("parsed")
    if isinstance(rec, dict) and ("ok" in rec or "p99_ms" in rec
                                  or "throughput_qps" in rec):
        merged = dict(data)
        merged.update(rec)
        return merged
    if "ok" in data or "p99_ms" in data or "throughput_qps" in data:
        return data
    return None


def collect_serving(directory: str
                    ) -> List[Tuple[int, str, Optional[Dict]]]:
    """(round, path, record) for every SERVING_r*.json, in round order,
    plus the bare BENCH_SERVING.json (when present) as the NEWEST
    entry — the current run's artifact gates even before a driver wraps
    it into a numbered round."""
    out = []
    for path in glob.glob(os.path.join(directory, SERVING_GLOB)):
        m = re.search(r"SERVING_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        out.append((int(m.group(1)), path, load_serving(path)))
    out.sort(key=lambda t: t[0])
    bare = os.path.join(directory, SERVING_NAME)
    if os.path.exists(bare):
        n = (out[-1][0] + 1) if out else 1
        out.append((n, bare, load_serving(bare)))
    return out


def check_serving(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
                  threshold: float = DEFAULT_THRESHOLD
                  ) -> Tuple[str, str]:
    """Gate the serving-SLO trend (BENCH_SERVING / SERVING_r*):

    - the newest parseable round must be ``ok`` (correctness parity +
      no compile miss after warm-up — a broken serving path is a
      regression, not a footnote);
    - degraded rounds (nonzero resilience degradations — sheds, ladder
      walks) are SKIPped: outage evidence is history, never a gate;
    - only MEASURED rounds are speed-gated: when the newest and a
      previous measured round both carry p99 latency / throughput, p99
      must not grow past ``threshold`` and throughput must not drop
      past it. Modeled (off-TPU) rounds pass on ``ok`` alone."""
    newest = None
    for _, _, rec in reversed(rounds):
        if rec is not None:
            newest = rec
            break
    if newest is None:
        return SKIP, "no serving artifact to gate"
    if newest.get("skipped"):
        return SKIP, "latest serving round skipped"
    rd = newest.get("resilience_degradations")
    if isinstance(rd, (int, float)) and rd > 0:
        return SKIP, (
            f"latest serving round recorded {rd:g} degradation "
            f"step(s) (sheds/ladder walks) — a degraded run is "
            f"history, never gated and never baseline material")
    if not newest.get("ok", True):
        return REGRESS, ("latest serving round failed (ok=false) — "
                         "the serving path regressed")
    misses = newest.get("compile_misses_after_warmup")
    if isinstance(misses, (int, float)) and misses > 0:
        return REGRESS, (
            f"latest serving round paid {misses:g} AOT compile "
            f"miss(es) AFTER warm-up — a live request traced/compiled, "
            f"the exact latency cliff the bucket ladder exists to "
            f"prevent")
    p99 = newest.get("p99_ms")
    qps = newest.get("throughput_qps")
    if not newest.get("measured"):
        return PASS, ("latest serving round ok (modeled — not gated "
                      "on speed)")
    prev = None
    for _, _, rec in reversed(rounds[:-1]):
        if (rec is not None and rec.get("measured")
                and not rec.get("skipped")
                and isinstance(rec.get("p99_ms"), (int, float))):
            prev = rec
            break
    if prev is None:
        return PASS, (f"serving ok: p99 {p99} ms, {qps} req/s (first "
                      f"measured round — nothing to trend against)")
    msgs = []
    if isinstance(p99, (int, float)) and \
            isinstance(prev.get("p99_ms"), (int, float)):
        ceil = prev["p99_ms"] * (1.0 + threshold)
        if p99 > ceil:
            return REGRESS, (
                f"SERVING P99 REGRESSION: {p99:g} ms > {ceil:g} "
                f"(previous measured {prev['p99_ms']:g} + "
                f"{threshold:.0%})")
        msgs.append(f"p99 {p99:g} vs {prev['p99_ms']:g} ms")
    if isinstance(qps, (int, float)) and \
            isinstance(prev.get("throughput_qps"), (int, float)) \
            and prev["throughput_qps"] > 0:
        floor = prev["throughput_qps"] * (1.0 - threshold)
        if qps < floor:
            return REGRESS, (
                f"SERVING THROUGHPUT REGRESSION: {qps:g} req/s < "
                f"{floor:g} (previous measured "
                f"{prev['throughput_qps']:g} − {threshold:.0%})")
        msgs.append(f"{qps:g} vs {prev['throughput_qps']:g} req/s")
    return PASS, "serving ok: " + "; ".join(msgs or ["no SLO fields"])


def serving_trajectory(rounds: Sequence[Tuple[int, str,
                                              Optional[Dict]]]) -> str:
    """Serving-SLO series: p50/p99/throughput per round, shed and
    compile-miss evidence next to the ok verdict."""
    lines = ["serving trajectory (SERVING_r*.json + BENCH_SERVING.json)",
             "========================================================="]
    if not rounds:
        return "\n".join(lines + ["(no serving artifacts found)"]) + "\n"
    cols = ("round", "ok", "p50 ms", "p99 ms", "req/s", "shed",
            "miss>warm", "measured", "metric")
    rows = []
    for n, path, rec in rounds:
        if rec is None:
            rows.append((f"r{n:02d}", "-", "-", "-", "-", "-", "-", "-",
                         f"<unparseable: {os.path.basename(path)}>"))
            continue
        rows.append((
            f"r{n:02d}", _fmt(bool(rec.get("ok"))),
            _fmt(rec.get("p50_ms")), _fmt(rec.get("p99_ms")),
            _fmt(rec.get("throughput_qps")), _fmt(rec.get("shed")),
            _fmt(rec.get("compile_misses_after_warmup")),
            _fmt(rec.get("measured")) if "measured" in rec else "-",
            normalize_metric(rec.get("metric", "serving"))))
    widths = [max(len(c), *(len(str(r[i])) for r in rows))
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    p99s = [rec["p99_ms"] for _, _, rec in rounds
            if rec is not None
            and isinstance(rec.get("p99_ms"), (int, float))]
    if p99s:
        lines.append(
            f"p99 across rounds: median {percentile(p99s, 50):.4g} ms, "
            f"p90 {percentile(p99s, 90):.4g} ms over {len(p99s)} "
            f"round(s)")
    return "\n".join(lines) + "\n"


def load_ann(path: str) -> Optional[Dict]:
    """Flat ANN speed/recall frontier record (``BENCH_ANN.json``):
    unwraps the driver's envelope like :func:`load_serving`. A record
    must carry an ``ok`` verdict or a frontier to count."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    rec = data.get("parsed")
    if isinstance(rec, dict) and ("ok" in rec or "frontier" in rec):
        merged = dict(data)
        merged.update(rec)
        return merged
    if "ok" in data or "frontier" in data:
        return data
    return None


def collect_ann(directory: str) -> List[Tuple[int, str, Optional[Dict]]]:
    """(round, path, record) for every ANN_r*.json, in round order,
    plus the bare BENCH_ANN.json (when present) as the NEWEST entry —
    same convention as :func:`collect_serving`."""
    out = []
    for path in glob.glob(os.path.join(directory, ANN_GLOB)):
        m = re.search(r"ANN_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        out.append((int(m.group(1)), path, load_ann(path)))
    out.sort(key=lambda t: t[0])
    bare = os.path.join(directory, ANN_NAME)
    if os.path.exists(bare):
        n = (out[-1][0] + 1) if out else 1
        out.append((n, bare, load_ann(bare)))
    return out


def _ann_best_recall(rec: Dict) -> Optional[float]:
    frontier = rec.get("frontier")
    if not isinstance(frontier, list):
        return None
    rs = [p.get("recall_at_k") for p in frontier
          if isinstance(p, dict)
          and isinstance(p.get("recall_at_k"), (int, float))]
    return max(rs) if rs else None


def _ann_fine_scan_check(rec: Dict):
    """(error, best_overread) for a round's fine-scan evidence: every
    frontier point the chooser scheduled list-major must realize the
    recorded ``gather_overread`` win (modeled stream bytes ≤ gather
    bytes / overread), and ``best_overread`` is the round's largest
    such win (None when the round predates the fine-scan columns)."""
    best = None
    for p in rec.get("frontier", []) or []:
        if not isinstance(p, dict) or p.get("fine_scan") != "list":
            continue
        sb = p.get("model_stream_bytes")
        gb = p.get("model_gather_bytes")
        ovr = p.get("gather_overread")
        if not all(isinstance(v, (int, float)) and v > 0
                   for v in (sb, gb, ovr)):
            continue
        if sb > gb / ovr * 1.001:
            return (
                f"ANN FINE-SCAN BYTES VIOLATION: frontier point "
                f"n_lists={p.get('n_lists')} n_probes="
                f"{p.get('n_probes')} chose the list-major schedule "
                f"but its modeled stream bytes {sb:g} exceed "
                f"gather/overread = {gb / ovr:g} — the artifact "
                f"records an overread win the schedule does not "
                f"realize"), None
        best = ovr if best is None else max(best, ovr)
    return None, best


def _ann_diffuse_rerun(rec: Dict) -> Tuple[Optional[str],
                                           Optional[float]]:
    """Min certificate exact-rerun fraction among the round's
    diffuse-Gaussian PQ frontier points that reach the recall floor.
    Returns ``(error, frac)``: ``error`` is set when diffuse points
    exist but none reach the floor; ``(None, None)`` means the round
    carries no diffuse points (a pre-ISSUE-19 artifact — the gate
    skips rather than invents a verdict)."""
    pq = rec.get("pq") or {}
    pts = [p for p in pq.get("frontier") or []
           if isinstance(p, dict) and p.get("dist") == "diffuse"]
    if not pts:
        return None, None
    floor = rec.get("recall_floor", 0.95)
    at_floor = [p["cert_rerun_frac"] for p in pts
                if isinstance(p.get("recall_at_k"), (int, float))
                and p["recall_at_k"] >= floor
                and isinstance(p.get("cert_rerun_frac"),
                               (int, float))]
    if not at_floor:
        return ("ANN PQ DIFFUSE RECALL VIOLATION: no diffuse-Gaussian "
                f"PQ frontier point reaches the recall floor {floor:g}"
                " — the compressed tier cannot serve worst-case data "
                "at the promised quality"), None
    return None, float(min(at_floor))


def check_ann(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
              threshold: float = DEFAULT_THRESHOLD) -> Tuple[str, str]:
    """Gate the ANN speed/recall frontier (BENCH_ANN / ANN_r*):

    - the newest parseable round must be ``ok``;
    - degraded ROUND files (nonzero resilience degradations) SKIP —
      outage evidence is history, never a gate; but a degraded NAMED
      artifact (the bare ``BENCH_ANN.json``) REGRESSES — committed
      baseline evidence must never be an outage round (the refresh
      path refuses to write it; one landing anyway is a bug, not
      history);
    - **recall floor**: the frontier's best recall@k must reach the
      artifact's own ``recall_floor`` (default 0.95) — recall is
      platform-independent math, so this gates modeled rounds too;
    - **degenerate-exact invariant**: the ``n_probes = n_lists`` sweep
      point must have matched the brute-force oracle's id sets
      (``degenerate_exact: true``);
    - **fine-scan schedule** (ISSUE 14): list-major frontier points
      must realize the recorded ``gather_overread`` win (modeled
      stream ≤ gather/overread), and the round's best overread win
      must not fall more than ``ANN_OVERREAD_SLACK`` below the
      previous comparable round's;
    - **PQ diffuse rerun** (ISSUE 19): among diffuse-Gaussian PQ
      frontier points, at least one must reach the recall floor and
      the min ``cert_rerun_frac`` there must be ≤ ``PQ_RERUN_CEIL``,
      and must not rise more than ``PQ_RERUN_SLACK`` absolute vs the
      previous comparable round (rounds without diffuse points skip
      this gate);
    - **recall trend**: best recall must not drop more than
      ``ANN_RECALL_SLACK`` absolute vs the previous comparable round;
    - **speed trend**: only MEASURED rounds gate search time — when the
      newest and a previous measured round both carry ``search_ms`` at
      the floor-recall point, it must not grow past ``threshold``
      (modeled rounds are never speed-gated)."""
    newest, newest_path = None, None
    for _, path, rec in reversed(rounds):
        if rec is not None:
            newest, newest_path = rec, path
            break
    if newest is None:
        return SKIP, "no ANN artifact to gate"
    if newest.get("skipped"):
        return SKIP, "latest ANN round skipped"
    rd = newest.get("resilience_degradations")
    degraded = (isinstance(rd, (int, float)) and rd > 0) \
        or bool(newest.get("degraded"))
    if degraded:
        if newest_path is not None and os.path.basename(
                newest_path) == ANN_NAME:
            return REGRESS, (
                f"ANN NAMED-ARTIFACT DEGRADED: {ANN_NAME} is stamped "
                f"degraded"
                + (f" ({rd:g} resilience degradation step(s))"
                   if isinstance(rd, (int, float)) and rd > 0 else "")
                + " — committed baseline evidence must never be an "
                  "outage round; regenerate it clean")
        return SKIP, (
            f"latest ANN round is degraded"
            + (f" ({rd:g} degradation step(s))"
               if isinstance(rd, (int, float)) and rd > 0 else "")
            + " — a degraded run is history, never gated and never "
              "baseline material")
    if not newest.get("ok", True):
        return REGRESS, ("latest ANN round failed (ok=false) — the "
                         "ANN tier regressed")
    best = _ann_best_recall(newest)
    floor = newest.get("recall_floor", 0.95)
    if isinstance(best, (int, float)) and isinstance(floor,
                                                     (int, float)):
        if best < floor:
            return REGRESS, (
                f"ANN RECALL REGRESSION: best recall@k {best:.4f} < "
                f"floor {floor:g} — no swept n_probes reaches the "
                f"recall the frontier promises")
    if "degenerate_exact" in newest and not newest["degenerate_exact"]:
        return REGRESS, (
            "ANN DEGENERATE-EXACT VIOLATION: the n_probes = n_lists "
            "sweep point did not match the brute-force oracle's id "
            "sets — probing everything must be exact search")
    # fine-scan schedule gate (ISSUE 14): wherever the chooser picked
    # the list-major schedule, its modeled bytes must realize the
    # recorded gather_overread win (stream ≤ gather / overread), and
    # the frontier's recorded overread ratio must not silently shrink
    # vs the previous comparable round — the win BENCH_ANN.json exists
    # to capture cannot regress unnoticed.
    fine_err, fine_ovr = _ann_fine_scan_check(newest)
    if fine_err:
        return REGRESS, fine_err
    # PQ diffuse-rerun gate (ISSUE 19): on the diffuse-Gaussian worst
    # case the adaptive certificate + widen rung must keep the
    # exact-rerun fraction at the recall floor under PQ_RERUN_CEIL —
    # this is the regime where the worst-case certificate collapsed
    # to an 83–88% exact-scan rate and evaporated the ADC win.
    rerun_err, rerun = _ann_diffuse_rerun(newest)
    if rerun_err:
        return REGRESS, rerun_err
    if rerun is not None and rerun > PQ_RERUN_CEIL:
        return REGRESS, (
            f"ANN PQ DIFFUSE RERUN VIOLATION: diffuse-Gaussian "
            f"cert_rerun_frac {rerun:g} at the recall floor exceeds "
            f"{PQ_RERUN_CEIL:g} — the certificate falls back to the "
            f"exact scan often enough to erase the compressed tier's "
            f"win")
    prev = None
    for _, _, rec in reversed(rounds[:-1]):
        if (rec is not None and not rec.get("skipped")
                and _ann_best_recall(rec) is not None
                and rec.get("k") == newest.get("k")):
            prev = rec
            break
    msgs = [f"best recall@{newest.get('k', '?')} "
            f"{best:.4f}" if isinstance(best, (int, float))
            else "no recall points"]
    if fine_ovr is not None:
        msgs.append(f"list-major overread {fine_ovr:g}x")
    if rerun is not None:
        msgs.append(f"diffuse rerun {rerun:g}")
    if prev is not None and isinstance(best, (int, float)):
        pbest = _ann_best_recall(prev)
        if pbest is not None and best < pbest - ANN_RECALL_SLACK:
            return REGRESS, (
                f"ANN RECALL TREND REGRESSION: best recall {best:.4f} "
                f"< previous {pbest:.4f} − {ANN_RECALL_SLACK:g}")
        if pbest is not None:
            msgs.append(f"prev {pbest:.4f}")
        _, prev_ovr = _ann_fine_scan_check(prev)
        if (fine_ovr is not None and prev_ovr is not None
                and fine_ovr < prev_ovr * (1.0 - ANN_OVERREAD_SLACK)):
            return REGRESS, (
                f"ANN FINE-SCAN OVERREAD TREND REGRESSION: the newest "
                f"round's best modeled list-major overread win "
                f"{fine_ovr:g}x fell more than "
                f"{ANN_OVERREAD_SLACK:.0%} below the previous "
                f"comparable round's {prev_ovr:g}x — the frontier "
                f"shift the list-major kernel bought is eroding")
        _, prev_rerun = _ann_diffuse_rerun(prev)
        if (rerun is not None and prev_rerun is not None
                and rerun > prev_rerun + PQ_RERUN_SLACK):
            return REGRESS, (
                f"ANN PQ DIFFUSE RERUN TREND REGRESSION: "
                f"diffuse-Gaussian cert_rerun_frac {rerun:g} rose "
                f"more than {PQ_RERUN_SLACK:g} absolute above the "
                f"previous comparable round's {prev_rerun:g} — "
                f"certificate quality on worst-case data is eroding")
    if newest.get("measured") and prev is not None \
            and prev.get("measured"):
        sm, pm = newest.get("search_ms"), prev.get("search_ms")
        if isinstance(sm, (int, float)) and isinstance(pm, (int, float)) \
                and pm > 0:
            ceil = pm * (1.0 + threshold)
            if sm > ceil:
                return REGRESS, (
                    f"ANN SEARCH-TIME REGRESSION: {sm:g} ms > {ceil:g} "
                    f"(previous measured {pm:g} + {threshold:.0%})")
            msgs.append(f"search {sm:g} vs {pm:g} ms")
    elif not newest.get("measured"):
        msgs.append("modeled — not speed-gated")
    return PASS, "ann ok: " + "; ".join(msgs)


def ann_trajectory(rounds: Sequence[Tuple[int, str,
                                          Optional[Dict]]]) -> str:
    """ANN frontier series: best recall, probed fraction at the floor,
    degenerate-exact verdict per round."""
    lines = ["ann trajectory (ANN_r*.json + BENCH_ANN.json)",
             "=============================================="]
    if not rounds:
        return "\n".join(lines + ["(no ANN artifacts found)"]) + "\n"
    cols = ("round", "ok", "best recall", "floor-probe%", "degen",
            "lists", "measured", "metric")
    rows = []
    for n, path, rec in rounds:
        if rec is None:
            rows.append((f"r{n:02d}", "-", "-", "-", "-", "-", "-",
                         f"<unparseable: {os.path.basename(path)}>"))
            continue
        best = _ann_best_recall(rec)
        pf = rec.get("probed_frac_at_floor")
        nl = sorted({p.get("n_lists") for p in rec.get("frontier", [])
                     if isinstance(p, dict)})
        rows.append((
            f"r{n:02d}", _fmt(bool(rec.get("ok"))),
            f"{best:.4f}" if isinstance(best, (int, float)) else "-",
            f"{pf * 100:.1f}" if isinstance(pf, (int, float)) else "-",
            _fmt(rec.get("degenerate_exact")),
            ",".join(str(x) for x in nl if x is not None) or "-",
            _fmt(rec.get("measured")) if "measured" in rec else "-",
            normalize_metric(rec.get("metric", "ann"))))
    widths = [max(len(c), *(len(str(r[i])) for r in rows))
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    return "\n".join(lines) + "\n"


def load_mutation(path: str) -> Optional[Dict]:
    """Flat mixed read/write record (``BENCH_MUTATION.json``):
    unwraps the driver's envelope like :func:`load_serving`. A record
    must carry an ``ok`` verdict, a recall, or a compaction count to
    count."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    rec = data.get("parsed")
    keys = ("ok", "recall", "compaction_cycles")
    if isinstance(rec, dict) and any(k in rec for k in keys):
        merged = dict(data)
        merged.update(rec)
        return merged
    if any(k in data for k in keys):
        return data
    return None


def collect_mutation(directory: str
                     ) -> List[Tuple[int, str, Optional[Dict]]]:
    """(round, path, record) for every MUTATION_r*.json, in round
    order, plus the bare BENCH_MUTATION.json (when present) as the
    NEWEST entry — same convention as :func:`collect_serving`."""
    out = []
    for path in glob.glob(os.path.join(directory, MUTATION_GLOB)):
        m = re.search(r"MUTATION_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        out.append((int(m.group(1)), path, load_mutation(path)))
    out.sort(key=lambda t: t[0])
    bare = os.path.join(directory, MUTATION_NAME)
    if os.path.exists(bare):
        n = (out[-1][0] + 1) if out else 1
        out.append((n, bare, load_mutation(bare)))
    return out


def check_mutation(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
                   threshold: float = DEFAULT_THRESHOLD
                   ) -> Tuple[str, str]:
    """Gate the mutable-index mixed read/write evidence
    (BENCH_MUTATION / MUTATION_r*):

    - the newest parseable round must be ``ok`` (rebuild-oracle recall
      held, every read completed — a broken mutation plane is a
      regression, not a footnote);
    - degraded rounds (nonzero resilience degradations) SKIP;
    - **compaction cycle**: the round must have completed ≥ 1 full
      delta-fill → fold → swap cycle under load — an artifact that
      never folded proved nothing about the tentpole;
    - **recall floor**: quiescent recall vs the from-scratch rebuild
      oracle must reach the artifact's ``recall_floor`` (0.95) —
      platform-independent, so modeled rounds gate too;
    - **speed trend**: only MEASURED rounds gate read p99 / throughput
      (same ±threshold convention as the serving gate)."""
    newest = None
    for _, _, rec in reversed(rounds):
        if rec is not None:
            newest = rec
            break
    if newest is None:
        return SKIP, "no mutation artifact to gate"
    if newest.get("skipped"):
        return SKIP, "latest mutation round skipped"
    rd = newest.get("resilience_degradations")
    if isinstance(rd, (int, float)) and rd > 0:
        return SKIP, (
            f"latest mutation round recorded {rd:g} degradation "
            f"step(s) — a degraded run is history, never gated and "
            f"never baseline material")
    if not newest.get("ok", True):
        return REGRESS, ("latest mutation round failed (ok=false) — "
                         "the mutation plane regressed")
    cycles = newest.get("compaction_cycles")
    if isinstance(cycles, (int, float)) and cycles < 1:
        return REGRESS, (
            "MUTATION COMPACTION REGRESSION: the round completed 0 "
            "compaction cycles — the delta never folded, so the "
            "artifact carries no evidence for the fill→fold→swap "
            "contract")
    recall = newest.get("recall")
    floor = newest.get("recall_floor", QUALITY_RECALL_FLOOR)
    if isinstance(recall, (int, float)) and isinstance(floor,
                                                       (int, float)):
        if recall < floor:
            return REGRESS, (
                f"MUTATION RECALL REGRESSION: rebuild-oracle recall "
                f"{recall:.4f} < floor {floor:g} — interleaved "
                f"mutations degraded served answers")
    msgs = [f"recall {recall:.4f}" if isinstance(recall, (int, float))
            else "no recall field",
            f"{cycles:g} compaction cycle(s)"
            if isinstance(cycles, (int, float)) else "no cycle count"]
    if not newest.get("measured"):
        return PASS, ("mutation ok: " + "; ".join(msgs)
                      + " (modeled — not speed-gated)")
    prev = None
    for _, _, rec in reversed(rounds[:-1]):
        if (rec is not None and rec.get("measured")
                and not rec.get("skipped")
                and isinstance(rec.get("p99_ms"), (int, float))):
            prev = rec
            break
    if prev is None:
        return PASS, ("mutation ok: " + "; ".join(msgs)
                      + " (first measured round)")
    p99, pp99 = newest.get("p99_ms"), prev.get("p99_ms")
    if isinstance(p99, (int, float)) and isinstance(pp99, (int, float)):
        ceil = pp99 * (1.0 + threshold)
        if p99 > ceil:
            return REGRESS, (
                f"MUTATION P99 REGRESSION: {p99:g} ms > {ceil:g} "
                f"(previous measured {pp99:g} + {threshold:.0%})")
        msgs.append(f"p99 {p99:g} vs {pp99:g} ms")
    qps, pqps = newest.get("throughput_qps"), prev.get("throughput_qps")
    if isinstance(qps, (int, float)) and isinstance(pqps, (int, float)) \
            and pqps > 0:
        fl = pqps * (1.0 - threshold)
        if qps < fl:
            return REGRESS, (
                f"MUTATION THROUGHPUT REGRESSION: {qps:g} req/s < "
                f"{fl:g} (previous measured {pqps:g} − {threshold:.0%})")
        msgs.append(f"{qps:g} vs {pqps:g} req/s")
    return PASS, "mutation ok: " + "; ".join(msgs)


def mutation_trajectory(rounds: Sequence[Tuple[int, str,
                                               Optional[Dict]]]) -> str:
    """Mixed read/write series: read p99, recall, compaction cycles and
    mid-fold read evidence per round."""
    lines = [
        "mutation trajectory (MUTATION_r*.json + BENCH_MUTATION.json)",
        "============================================================"]
    if not rounds:
        return "\n".join(lines + ["(no mutation artifacts found)"]) \
            + "\n"
    cols = ("round", "ok", "p99 ms", "req/s", "recall", "cycles",
            "in-fold", "measured", "metric")
    rows = []
    for n, path, rec in rounds:
        if rec is None:
            rows.append((f"r{n:02d}", "-", "-", "-", "-", "-", "-", "-",
                         f"<unparseable: {os.path.basename(path)}>"))
            continue
        rows.append((
            f"r{n:02d}", _fmt(bool(rec.get("ok"))),
            _fmt(rec.get("p99_ms")), _fmt(rec.get("throughput_qps")),
            _fmt(rec.get("recall")), _fmt(rec.get("compaction_cycles")),
            _fmt(rec.get("reads_during_fold")),
            _fmt(rec.get("measured")) if "measured" in rec else "-",
            normalize_metric(rec.get("metric", "mutation"))))
    widths = [max(len(c), *(len(str(r[i])) for r in rows))
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    return "\n".join(lines) + "\n"


def load_recovery(path: str) -> Optional[Dict]:
    """Flat durability/recovery record (``BENCH_RECOVERY.json``):
    unwraps the driver's envelope like :func:`load_serving`. A record
    must carry an ``ok`` verdict, the zero-acked-loss flag, or a
    recovery time to count."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict):
        return None
    rec = data.get("parsed")
    keys = ("ok", "zero_acked_loss", "recovery_ms")
    if isinstance(rec, dict) and any(k in rec for k in keys):
        merged = dict(data)
        merged.update(rec)
        return merged
    if any(k in data for k in keys):
        return data
    return None


def collect_recovery(directory: str
                     ) -> List[Tuple[int, str, Optional[Dict]]]:
    """(round, path, record) for every RECOVERY_r*.json, in round
    order, plus the bare BENCH_RECOVERY.json (when present) as the
    NEWEST entry — same convention as :func:`collect_serving`."""
    out = []
    for path in glob.glob(os.path.join(directory, RECOVERY_GLOB)):
        m = re.search(r"RECOVERY_r(\d+)\.json$", os.path.basename(path))
        if not m:
            continue
        out.append((int(m.group(1)), path, load_recovery(path)))
    out.sort(key=lambda t: t[0])
    bare = os.path.join(directory, RECOVERY_NAME)
    if os.path.exists(bare):
        n = (out[-1][0] + 1) if out else 1
        out.append((n, bare, load_recovery(bare)))
    return out


def check_recovery(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
                   threshold: float = DEFAULT_THRESHOLD
                   ) -> Tuple[str, str]:
    """Gate the durability/crash-recovery evidence (BENCH_RECOVERY /
    RECOVERY_r*):

    - the newest parseable round must be ``ok`` (acked-write contract
      held, recovered state matched the oracle);
    - degraded rounds (nonzero resilience degradations) SKIP;
    - **zero-acked-loss flag**: the round must carry
      ``zero_acked_loss: true`` — a recovery artifact that lost an
      acked write (or stopped stamping the flag) is THE regression
      this plane exists to prevent; platform-independent, so modeled
      rounds gate too;
    - **recovery-time bound**: ``recovery_ms`` must stay within the
      artifact's own ``recovery_ms_bound`` (the bench sets a
      platform-appropriate ceiling — an unbounded recovery breaks the
      restart-SLO story regardless of chip);
    - **speed trend**: only MEASURED rounds gate durable-write
      throughput (same ±threshold convention as the serving gate)."""
    newest = None
    for _, _, rec in reversed(rounds):
        if rec is not None:
            newest = rec
            break
    if newest is None:
        return SKIP, "no recovery artifact to gate"
    if newest.get("skipped"):
        return SKIP, "latest recovery round skipped"
    rd = newest.get("resilience_degradations")
    if isinstance(rd, (int, float)) and rd > 0:
        return SKIP, (
            f"latest recovery round recorded {rd:g} degradation "
            f"step(s) — a degraded run is history, never gated and "
            f"never baseline material")
    if not newest.get("ok", True):
        return REGRESS, ("latest recovery round failed (ok=false) — "
                         "the durability plane regressed")
    if newest.get("zero_acked_loss") is not True:
        return REGRESS, (
            "RECOVERY ACKED-LOSS REGRESSION: the round does not carry "
            "zero_acked_loss=true — an acked write was lost (or the "
            "proof stopped being stamped), the exact contract the WAL "
            "exists to keep")
    rms = newest.get("recovery_ms")
    bound = newest.get("recovery_ms_bound")
    if isinstance(rms, (int, float)) and isinstance(bound,
                                                    (int, float)):
        if rms > bound:
            return REGRESS, (
                f"RECOVERY TIME REGRESSION: {rms:g} ms > the "
                f"artifact's own bound {bound:g} ms — checkpoint + "
                f"WAL-tail replay stopped being a bounded restart")
    msgs = [f"recovery {rms:g} ms" if isinstance(rms, (int, float))
            else "no recovery_ms",
            "zero acked loss"]
    ox = newest.get("durable_overhead_x")
    if isinstance(ox, (int, float)):
        msgs.append(f"durable overhead {ox:.2f}x")
    if not newest.get("measured"):
        return PASS, ("recovery ok: " + "; ".join(msgs)
                      + " (modeled — not speed-gated)")
    prev = None
    for _, _, rec in reversed(rounds[:-1]):
        if (rec is not None and rec.get("measured")
                and not rec.get("skipped")
                and isinstance(rec.get("throughput_qps"),
                               (int, float))):
            prev = rec
            break
    qps = newest.get("throughput_qps")
    if prev is not None and isinstance(qps, (int, float)) \
            and prev["throughput_qps"] > 0:
        floor = prev["throughput_qps"] * (1.0 - threshold)
        if qps < floor:
            return REGRESS, (
                f"RECOVERY THROUGHPUT REGRESSION: durable writes "
                f"{qps:g} req/s < {floor:g} (previous measured "
                f"{prev['throughput_qps']:g} − {threshold:.0%})")
        msgs.append(f"{qps:g} vs {prev['throughput_qps']:g} req/s")
    return PASS, "recovery ok: " + "; ".join(msgs)


def recovery_trajectory(rounds: Sequence[Tuple[int, str,
                                               Optional[Dict]]]) -> str:
    """Durability series: recovery time, replayed-record tail,
    durable-write overhead and the zero-acked-loss verdict per round."""
    lines = [
        "recovery trajectory (RECOVERY_r*.json + BENCH_RECOVERY.json)",
        "============================================================"]
    if not rounds:
        return "\n".join(lines + ["(no recovery artifacts found)"]) \
            + "\n"
    cols = ("round", "ok", "0-loss", "rec ms", "replayed", "overhead x",
            "req/s", "measured", "metric")
    rows = []
    for n, path, rec in rounds:
        if rec is None:
            rows.append((f"r{n:02d}", "-", "-", "-", "-", "-", "-", "-",
                         f"<unparseable: {os.path.basename(path)}>"))
            continue
        rows.append((
            f"r{n:02d}", _fmt(bool(rec.get("ok"))),
            _fmt(rec.get("zero_acked_loss")),
            _fmt(rec.get("recovery_ms")),
            _fmt(rec.get("replayed_records")),
            _fmt(rec.get("durable_overhead_x")),
            _fmt(rec.get("throughput_qps")),
            _fmt(rec.get("measured")) if "measured" in rec else "-",
            normalize_metric(rec.get("metric", "recovery"))))
    widths = [max(len(c), *(len(str(r[i])) for r in rows))
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    return "\n".join(lines) + "\n"


def load_drift_ledger(path: str) -> Optional[Dict]:
    """DRIFT_LEDGER.json → {site: [entries...]}; None for a missing or
    unreadable ledger (the no-op case — the gate must not fail repos
    that have never run a drift-recording benchmark)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    entries = data.get("entries") if isinstance(data, dict) else None
    if not isinstance(entries, dict):
        return None
    return {str(k): v for k, v in entries.items() if isinstance(v, list)}


def check_drift(entries: Optional[Dict], band: float = DRIFT_BAND
                ) -> Tuple[str, str]:
    """Gate the model-vs-measured drift ledger.

    Per site, the NEWEST entry wins. Only entries with ``measured:
    true`` (real-hardware measurements) and both ``predicted_seconds``
    and ``measured_seconds`` are gated — modeled-only sites (the CPU
    suite, prediction-side capture_fn records) are evidence of model
    shape, never calibration failures. A gated site whose
    predicted/measured seconds ratio (either direction) exceeds
    ``band`` is flagged: the cost model that ranks tune tables and
    merge strategies is out of calibration there, and the measured
    round must recalibrate it, not just outvote it."""
    if not entries:
        return SKIP, "no drift ledger to gate"
    flagged, gated, modeled_only = [], 0, 0
    for site in sorted(entries):
        hist = [e for e in entries[site] if isinstance(e, dict)]
        if not hist:
            continue
        latest = hist[-1]
        if not latest.get("measured"):
            modeled_only += 1
            continue
        pred = latest.get("predicted_seconds")
        meas = latest.get("measured_seconds")
        if not (isinstance(pred, (int, float))
                and isinstance(meas, (int, float))
                and pred > 0 and meas > 0):
            modeled_only += 1
            continue
        gated += 1
        ratio = max(pred / meas, meas / pred)
        if ratio > band:
            flagged.append(f"{site} ({ratio:.2g}x)")
    if flagged:
        return REGRESS, (
            f"MODEL DRIFT: {len(flagged)} site(s) outside the "
            f"{band:g}x band: {', '.join(flagged)} — the cost model "
            f"is out of calibration; re-tune before trusting modeled "
            f"rankings")
    if gated == 0:
        return PASS, (f"drift ledger has no measured entries "
                      f"({modeled_only} modeled-only site(s) — never "
                      f"drift-gated)")
    return PASS, (f"drift ok: {gated} measured site(s) within the "
                  f"{band:g}x band"
                  + (f"; {modeled_only} modeled-only skipped"
                     if modeled_only else ""))


def load_lint(path: str) -> Optional[Dict]:
    """LINT_REPORT.json, or None when missing/unreadable (the gate
    then SKIPs with a pointer — an unreadable report never passes
    silently as clean)."""
    try:
        with open(path) as f:
            rec = json.load(f)
        return rec if isinstance(rec, dict) else None
    except (OSError, ValueError):
        return None


def check_lint(record: Optional[Dict]) -> Tuple[str, str]:
    """Gate the graftlint report (ISSUE 13): the committed
    LINT_REPORT.json must carry ``ok: true`` and zero unsuppressed
    error findings — a finding either gets FIXED or gets a reasoned
    baseline entry; it never rides along silently. Suppressed counts
    are reported for visibility (a growing baseline is reviewable
    drift, not a gate failure)."""
    if record is None:
        return SKIP, (f"no {LINT_NAME} — run `python tools/"
                      f"graftlint.py --json` to generate it")
    errs = record.get("unsuppressed_errors")
    if not isinstance(errs, int):
        return REGRESS, (f"{LINT_NAME} is malformed (no "
                         f"unsuppressed_errors count) — regenerate it")
    if errs > 0 or not record.get("ok", False):
        by_pass = {name: blk.get("unsuppressed_errors", 0)
                   for name, blk in (record.get("passes") or {}).items()
                   if isinstance(blk, dict)}
        detail = ", ".join(f"{k}: {v}" for k, v in sorted(
            by_pass.items()) if v)
        return REGRESS, (
            f"LINT: {errs} unsuppressed finding(s)"
            + (f" ({detail})" if detail else "")
            + " — fix them or add reasoned baseline entries "
              "(tools/graftlint_baseline.json)")
    suppressed = record.get("suppressed", 0)
    warnings = record.get("unsuppressed_warnings", 0)
    stale = len(record.get("stale_baseline_entries") or ())
    passes = ", ".join(sorted((record.get("passes") or {})))
    return PASS, (f"lint clean ({passes}; {suppressed} baselined, "
                  f"{warnings} warning(s), {stale} stale baseline "
                  f"entr{'y' if stale == 1 else 'ies'}; commit "
                  f"{record.get('commit', '?')})")


def _git_commit_time(directory: str, ref: str) -> Optional[int]:
    import subprocess

    try:
        r = subprocess.run(
            ["git", "-C", directory, "show", "-s", "--format=%ct", ref],
            capture_output=True, text=True, timeout=10)
        return int(r.stdout.strip().splitlines()[-1]) \
            if r.returncode == 0 and r.stdout.strip() else None
    except Exception:
        return None


def _git_last_touched(directory: str, name: str) -> Optional[int]:
    import subprocess

    try:
        r = subprocess.run(
            ["git", "-C", directory, "log", "-1", "--format=%ct", "--",
             name], capture_output=True, text=True, timeout=10)
        return int(r.stdout.strip()) \
            if r.returncode == 0 and r.stdout.strip() else None
    except Exception:
        return None


def artifact_staleness(directory: str,
                       baseline: Optional[Dict]) -> List[Dict]:
    """Freshness verdict for each :data:`NAMED_ARTIFACTS` file: STALE
    when its last-touching commit predates the commit the last-good
    measurement was taken at — those numbers describe an older code
    state and must not be read as current evidence. Degrades to
    ``unknown`` without git/baseline (never raises)."""
    ref = (baseline or {}).get("git_commit", "")
    ref = str(ref).replace("-dirty", "")
    ref_time = _git_commit_time(directory, ref) if ref else None
    out = []
    for name in NAMED_ARTIFACTS:
        path = os.path.join(directory, name)
        if not os.path.exists(path):
            out.append({"artifact": name, "status": "missing"})
            continue
        touched = _git_last_touched(directory, name)
        if touched is None or ref_time is None:
            out.append({"artifact": name, "status": "unknown"})
            continue
        stale = touched < ref_time
        out.append({
            "artifact": name,
            "status": "STALE" if stale else "current",
            "age_rounds_note": (
                "last touched before the last-good commit — numbers "
                "describe an older code state" if stale else ""),
        })
    return out


def check_regression(record: Optional[Dict], baseline: Optional[Dict],
                     threshold: float = DEFAULT_THRESHOLD
                     ) -> Tuple[str, str]:
    """Gate one candidate record against the baseline.

    Returns (status, message) with status one of PASS / REGRESS /
    MISSING_BASELINE / SKIP. SKIP covers: no candidate, degraded
    candidate, or metric/unit not comparable with the baseline — the
    no-op cases CI must treat as success."""
    if record is None:
        return SKIP, "no new BENCH artifact to gate"
    if record.get("degraded"):
        return SKIP, ("latest artifact is degraded (outage/CPU fallback)"
                      " — not gated")
    rd = record.get("resilience_degradations")
    if isinstance(rd, (int, float)) and rd > 0:
        return SKIP, (
            f"latest artifact recorded {rd:g} resilience degradation "
            f"ladder step(s) — numbers from a degraded run are "
            f"history, never gated and never baseline material")
    value = record.get("value")
    if not isinstance(value, (int, float)):
        return SKIP, "latest artifact has no numeric value"
    if baseline is None:
        return MISSING_BASELINE, (
            f"no {BASELINE_NAME} to gate against (candidate "
            f"{record.get('metric', '?')!r} = {value})")
    base_value = baseline.get("value")
    if not isinstance(base_value, (int, float)) or base_value <= 0:
        return MISSING_BASELINE, f"{BASELINE_NAME} has no usable value"
    if normalize_metric(record.get("metric", "")) != \
            normalize_metric(baseline.get("metric", "")) \
            or record.get("unit") != baseline.get("unit"):
        return SKIP, ("latest artifact measures a different metric/unit "
                      "than the baseline — not comparable")
    unit = record.get("unit", "")
    if higher_is_better(unit):
        floor = base_value * (1.0 - threshold)
        if value < floor:
            return REGRESS, (
                f"REGRESSION: {value:g} {unit} < {floor:g} "
                f"(last good {base_value:g} − {threshold:.0%})")
        return _check_roofline(
            record, baseline, threshold,
            f"ok: {value:g} {unit} vs last good "
            f"{base_value:g} (threshold {threshold:.0%})")
    ceil = base_value * (1.0 + threshold)
    if value > ceil:
        return REGRESS, (
            f"REGRESSION: {value:g} {unit} > {ceil:g} "
            f"(last good {base_value:g} + {threshold:.0%})")
    return _check_roofline(
        record, baseline, threshold,
        f"ok: {value:g} {unit} vs last good {base_value:g} "
        f"(threshold {threshold:.0%})")


def _check_roofline(record: Dict, baseline: Dict, threshold: float,
                    pass_msg: str) -> Tuple[str, str]:
    """Second-stage gate on the ROOFLINE-FRACTION trend: a round whose
    headline GB/s holds can still have lost ground against what the
    hardware allows (e.g. the cost model's bytes shrank — less work per
    second at the same rate). Only fires when BOTH records carry a
    numeric roofline_frac; seconds-only history stays gateable by the
    headline alone."""
    rf = record.get("roofline_frac")
    base_rf = baseline.get("roofline_frac")
    if (isinstance(rf, (int, float))
            and isinstance(base_rf, (int, float)) and base_rf > 0):
        floor = base_rf * (1.0 - threshold)
        if rf < floor:
            return REGRESS, (
                f"ROOFLINE REGRESSION: roofline_frac {rf:.3g} < "
                f"{floor:.3g} (last good {base_rf:.3g} − "
                f"{threshold:.0%}) even though the headline holds — "
                f"the chip allows more than this round achieved")
        pass_msg += (f"; roofline_frac {rf:.3g} vs last good "
                     f"{base_rf:.3g}")
    return PASS, pass_msg


def _fmt(v, nd=4) -> str:
    if isinstance(v, bool):
        return "yes" if v else "no"
    if isinstance(v, (int, float)):
        return f"{v:.{nd}g}"
    return "-" if v is None else str(v)


def trajectory(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
               baseline: Optional[Dict]) -> str:
    """Human trajectory: one row per round (headline value, p1/p3
    sub-series, commit, degraded) + roofline columns when present."""
    lines = ["perf trajectory (BENCH_r*.json)",
             "================================"]
    cols = ("round", "value", "unit", "p1 GB/s", "p3 GB/s", "p3 ms",
            "%roof", "bound", "degraded", "commit", "metric")
    rows = []
    any_cost = any(rec and any(f in rec for f in COST_FIELDS)
                   for _, _, rec in rounds)
    for n, path, rec in rounds:
        if rec is None:
            rows.append((f"r{n:02d}", "?", "-", "-", "-", "-", "-", "-",
                         "-", "-", f"<unparseable: {os.path.basename(path)}>"))
            continue
        rf = rec.get("roofline_frac")
        rows.append((
            f"r{n:02d}", _fmt(rec.get("value")), rec.get("unit", "-"),
            _fmt(rec.get("p1_gbps")), _fmt(rec.get("p3_gbps")),
            _fmt(rec.get("p3_ms")),
            f"{rf * 100:.1f}" if isinstance(rf, (int, float)) else "-",
            _fmt(rec.get("bound")), _fmt(bool(rec.get("degraded"))),
            rec.get("git_commit", "-"),
            normalize_metric(rec.get("metric", "?"))))
    if baseline is not None:
        rf = baseline.get("roofline_frac")
        rows.append((
            "LAST_GOOD", _fmt(baseline.get("value")),
            baseline.get("unit", "-"), _fmt(baseline.get("p1_gbps")),
            _fmt(baseline.get("p3_gbps")), _fmt(baseline.get("p3_ms")),
            f"{rf * 100:.1f}" if isinstance(rf, (int, float)) else "-",
            _fmt(baseline.get("bound")), "-",
            baseline.get("git_commit", "-"),
            normalize_metric(baseline.get("metric", "?"))))
    if not rows:
        return "\n".join(lines + ["(no BENCH_r*.json artifacts found)"]) + "\n"
    widths = [max(len(c), *(len(str(r[i])) for r in rows))
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    if not any_cost:
        lines.append("")
        lines.append("(no cost-model fields yet — artifacts produced "
                     "before the roofline profiler carry only seconds; "
                     "the next measurement round fills flops/bytes/%roof)")
    return "\n".join(lines) + "\n"


def multichip_trajectory(rounds: Sequence[Tuple[int, str,
                                                Optional[Dict]]]) -> str:
    """Multichip series: dryrun verdicts for the bare early rounds,
    sharded-KNN throughput + best busbw fraction once artifacts carry
    them."""
    lines = ["multichip trajectory (MULTICHIP_r*.json)",
             "========================================="]
    if not rounds:
        return "\n".join(lines + ["(no MULTICHIP_r*.json artifacts "
                                  "found)"]) + "\n"
    cols = ("round", "devices", "ok", "value", "unit", "busbw%",
            "measured", "metric")
    rows = []
    for n, path, rec in rounds:
        if rec is None:
            rows.append((f"r{n:02d}", "-", "-", "-", "-", "-", "-",
                         f"<unparseable: {os.path.basename(path)}>"))
            continue
        bw = _best_busbw(rec)
        rows.append((
            f"r{n:02d}", _fmt(rec.get("n_devices")),
            _fmt(bool(rec.get("ok"))), _fmt(rec.get("value")),
            rec.get("unit", "-"),
            f"{bw * 100:.2f}" if isinstance(bw, (int, float)) else "-",
            _fmt(rec.get("measured")) if "measured" in rec else "-",
            normalize_metric(rec.get("metric", "dryrun"))))
    widths = [max(len(c), *(len(str(r[i])) for r in rows))
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    lines.append("  ".join("-" * w for w in widths))
    for r in rows:
        lines.append("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    return "\n".join(lines) + "\n"


def check_quantized(records: Sequence[Tuple[str, Optional[Dict]]],
                    ceil: float = QUANTIZED_RATIO_CEIL
                    ) -> Tuple[str, str]:
    """Gate the quantized-index-streaming evidence across artifact
    families. ``records`` is [(family, newest record)] — each record
    that carries a ``"quantized"`` block must have ``ok: true``
    (id-parity int8-vs-f32 held) and its modeled bytes ratio
    (``quantized_y_ratio`` for the fused stream,
    ``quantized_gather_ratio`` for the IVF probe gather) ≤ ``ceil``.
    Records carrying a ``"pq"`` block (the IVF-PQ compressed tier of
    ``BENCH_ANN.json``) are additionally gated at the much
    tighter :data:`PQ_RATIO_CEIL`: ``pq_bytes_ratio`` ≤ 0.10× of the
    f32 slab stream AND the id-parity-after-rescore ``ok`` flag —
    AND-ed into the same verdict. Families without the block are
    noted; when NO family carries one the gate SKIPs (pass-or-no-op —
    pre-quantization artifact sets)."""
    checked, missing = [], []
    for family, rec in records:
        pq = rec.get("pq") if isinstance(rec, dict) else None
        if isinstance(pq, dict):
            if not pq.get("ok"):
                detail = pq.get("error") or (
                    "rescored PQ ids diverged from the flat scan, or "
                    "no point met the recall floor at the ratio ceil")
                return REGRESS, (
                    f"QUANTIZED REGRESSION [{family}/pq]: "
                    f"id-parity-after-rescore ok={pq.get('ok')} "
                    f"({detail})")
            pratio = pq.get("pq_bytes_ratio")
            if not isinstance(pratio, (int, float)):
                return REGRESS, (
                    f"QUANTIZED REGRESSION [{family}/pq]: pq block "
                    f"carries no pq_bytes_ratio")
            if pratio > PQ_RATIO_CEIL:
                return REGRESS, (
                    f"QUANTIZED REGRESSION [{family}/pq]: modeled "
                    f"codes-stream ratio {pratio:.4f} > "
                    f"{PQ_RATIO_CEIL:g}× the f32 slab — the "
                    f"compressed tier stopped paying for itself")
            checked.append(f"{family}/pq={pratio:.4f}")
        q = rec.get("quantized") if isinstance(rec, dict) else None
        if not isinstance(q, dict):
            if not isinstance(pq, dict):
                missing.append(family)
            continue
        if not q.get("ok"):
            detail = q.get("error") or ("int8 ids diverged from the "
                                        "f32 oracle")
            return REGRESS, (
                f"QUANTIZED REGRESSION [{family}]: id-parity ok="
                f"{q.get('ok')} ({detail})")
        ratio = None
        for key in ("quantized_y_ratio", "quantized_gather_ratio"):
            if isinstance(q.get(key), (int, float)):
                ratio = float(q[key])
                break
        if ratio is None:
            return REGRESS, (
                f"QUANTIZED REGRESSION [{family}]: block carries no "
                f"modeled bytes ratio")
        if ratio > ceil:
            return REGRESS, (
                f"QUANTIZED REGRESSION [{family}]: modeled streamed-"
                f"bytes ratio {ratio:.3f} > {ceil:g}× the bf16/f32 "
                f"baseline — the int8 path stopped paying for itself")
        checked.append(f"{family}={ratio:.3f}")
    if not checked:
        return SKIP, "no artifact carries a quantized block — not gated"
    note = f" (no block: {', '.join(missing)})" if missing else ""
    return PASS, ("int8 ratios " + ", ".join(checked)
                  + f" ≤ {ceil:g}, id-parity ok" + note)


def check_quality(records: Sequence[Tuple[str, Optional[Dict]]],
                  floor: float = QUALITY_RECALL_FLOOR
                  ) -> Tuple[str, str]:
    """Gate the quality-telemetry evidence across artifact families.

    ``records`` is [(family, newest record)]. Each record that carries
    a ``"quality"`` block must have a numeric ``fixup_rate`` (the
    certificate/fixup counters actually flowed — a block without it
    means the telemetry plane silently broke), and any recall the
    block carries (``shadow_recall`` from the online sampler,
    ``offline_recall`` from the ANN frontier) must reach ``floor``.
    Families without a block are noted; when NO family carries one the
    gate SKIPs (pass-or-no-op — pre-quality artifact sets). Quality is
    platform-independent math, so modeled rounds gate too — only
    SPEED is ever measured-only."""
    checked, missing = [], []
    for family, rec in records:
        q = rec.get("quality") if isinstance(rec, dict) else None
        if not isinstance(q, dict):
            missing.append(family)
            continue
        if not isinstance(q.get("fixup_rate"), (int, float)):
            return REGRESS, (
                f"QUALITY REGRESSION [{family}]: quality block carries "
                f"no fixup_rate — the certificate/fixup counters "
                f"stopped flowing into the artifact")
        notes = [f"fixup_rate={q['fixup_rate']:g}"]
        for key in ("shadow_recall", "offline_recall"):
            r = q.get(key)
            if r is None:
                continue
            if not isinstance(r, (int, float)):
                return REGRESS, (
                    f"QUALITY REGRESSION [{family}]: {key} is "
                    f"non-numeric ({r!r})")
            if r < floor:
                return REGRESS, (
                    f"QUALITY REGRESSION [{family}]: {key} "
                    f"{r:.4f} < floor {floor:g} — served answers "
                    f"degraded below the gated recall")
            notes.append(f"{key}={r:.4f}")
        checked.append(f"{family}({', '.join(notes)})")
    if not checked:
        return SKIP, "no artifact carries a quality block — not gated"
    note = f" (no block: {', '.join(missing)})" if missing else ""
    return PASS, "quality ok: " + "; ".join(checked) + note


#: availability floor for the serving SLO gate: an ok round that served
#: less than this fraction of admitted requests is a regression.
SLO_AVAILABILITY_FLOOR = 0.99


def check_slo(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
              floor: float = SLO_AVAILABILITY_FLOOR
              ) -> Tuple[str, str]:
    """Gate the serving SLO block (ISSUE 16).

    The newest parseable serving round must carry an ``"slo"`` block
    (MISSING_BASELINE without one — the artifact predates the SLO
    plane, regenerate it); degraded rounds SKIP (outage evidence is
    history, never a gate). On an ok round:

    - run-cumulative ``availability`` must reach ``floor`` (0.99 —
      admitted requests that shed/expired/errored ate more than the
      availability budget);
    - no page-severity fast-burn alert may have fired
      (``fast_burn_alerts == 0``) — an ok round that still tripped the
      pager means the burn thresholds and the serving path disagree
      about health, which is exactly what this gate exists to catch.
      On MODELED (off-TPU) rounds latency burns are excluded: latency
      is speed evidence and CPU wall clock is never chip evidence —
      the same measured-only rule every speed gate here follows."""
    newest = None
    for _, _, rec in reversed(rounds):
        if rec is not None:
            newest = rec
            break
    if newest is None:
        return SKIP, "no serving artifact to gate"
    if newest.get("skipped"):
        return SKIP, "latest serving round skipped"
    rd = newest.get("resilience_degradations")
    if isinstance(rd, (int, float)) and rd > 0:
        return SKIP, (
            f"latest serving round recorded {rd:g} degradation "
            f"step(s) — a degraded run is history, never gated")
    slo = newest.get("slo")
    if not isinstance(slo, dict):
        return MISSING_BASELINE, (
            "latest serving round carries no slo block — regenerate "
            "BENCH_SERVING.json")
    if not newest.get("ok", True):
        return SKIP, ("latest serving round failed (ok=false) — the "
                      "[serving] gate owns that regression")
    avail = slo.get("availability")
    if avail is None:
        return SKIP, "slo block has no availability evidence (no traffic)"
    if not isinstance(avail, (int, float)):
        return REGRESS, (
            f"SLO REGRESSION: availability is non-numeric ({avail!r})")
    if avail < floor:
        return REGRESS, (
            f"SLO REGRESSION: availability {avail:.4f} < floor "
            f"{floor:g} ({slo.get('bad_requests', '?')} bad of "
            f"{slo.get('total_requests', '?')} requests)")
    burns = slo.get("fast_burn_alerts")
    note = ""
    if isinstance(burns, (int, float)) and burns > 0:
        by_slo = slo.get("fast_burn_by_slo")
        if newest.get("measured") or not isinstance(by_slo, dict):
            gated = {"all": burns} if not isinstance(by_slo, dict) \
                else by_slo
        else:
            gated = {k: v for k, v in by_slo.items()
                     if k != "latency_p99" and v > 0}
        if gated:
            return REGRESS, (
                f"SLO REGRESSION: page-severity burn alert(s) fired "
                f"during an ok round ({gated}) — the pager and the "
                f"serving path disagree about health")
        note = (f" (latency fast-burn(s) {by_slo} not gated on a "
                f"modeled round — CPU wall clock is not chip evidence)")
    return PASS, (f"slo ok: availability {avail:.4f} ≥ {floor:g} "
                  f"over {slo.get('total_requests', '?')} request(s)"
                  + note)


#: blackbox overhead ceiling: the crash-durable recorder may cost at
#: most this fraction of total client request wall time.
BLACKBOX_OVERHEAD_CEILING = 0.01


def check_blackbox(rounds: Sequence[Tuple[int, str, Optional[Dict]]],
                   ceiling: float = BLACKBOX_OVERHEAD_CEILING
                   ) -> Tuple[str, str]:
    """Gate the serving blackbox block (ISSUE 17).

    The newest parseable serving round must carry a ``"blackbox"``
    block (MISSING_BASELINE without one — the artifact predates the
    forensics plane, regenerate it). On an ok round the recorder's
    measured ``overhead_frac`` (cumulative mmap-append seconds over
    total client request wall time) must stay under ``ceiling`` (1%) —
    a flight recorder that taxes the requests it exists to explain is
    a regression, not a feature."""
    newest = None
    for _, _, rec in reversed(rounds):
        if rec is not None:
            newest = rec
            break
    if newest is None:
        return SKIP, "no serving artifact to gate"
    if newest.get("skipped"):
        return SKIP, "latest serving round skipped"
    bb = newest.get("blackbox")
    if not isinstance(bb, dict):
        return MISSING_BASELINE, (
            "latest serving round carries no blackbox block — "
            "regenerate BENCH_SERVING.json")
    if not newest.get("ok", True):
        return SKIP, ("latest serving round failed (ok=false) — the "
                      "[serving] gate owns that regression")
    frac = bb.get("overhead_frac")
    if frac is None:
        return SKIP, "blackbox block has no overhead evidence (no traffic)"
    if not isinstance(frac, (int, float)):
        return REGRESS, (
            f"BLACKBOX REGRESSION: overhead_frac is non-numeric "
            f"({frac!r})")
    if frac >= ceiling:
        return REGRESS, (
            f"BLACKBOX REGRESSION: record overhead {frac:.4%} of "
            f"request wall time ≥ ceiling {ceiling:.0%} "
            f"({bb.get('records', '?')} record(s), "
            f"{bb.get('append_seconds', '?')}s appending)")
    return PASS, (f"blackbox ok: overhead {frac:.4%} < {ceiling:.0%} "
                  f"over {bb.get('records', '?')} record(s), "
                  f"{bb.get('bytes_written', '?')} bytes")


def staleness_section(entries: List[Dict]) -> str:
    lines = ["named artifacts (freshness vs the last-good commit)",
             "---------------------------------------------------"]
    for e in entries:
        note = e.get("age_rounds_note") or ""
        lines.append(f"{e['artifact']:<24} {e['status']}"
                     + (f" — {note}" if note else ""))
    return "\n".join(lines) + "\n"


def main(argv: Sequence[str] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--dir", default=_REPO_ROOT,
                   help="directory holding BENCH_*.json (default: repo root)")
    p.add_argument("--baseline", default=None,
                   help=f"baseline file (default: <dir>/{BASELINE_NAME})")
    p.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                   help="relative regression threshold (default 0.15)")
    p.add_argument("--check", action="store_true",
                   help="gate the newest non-degraded round against the "
                        "baseline; exit 1 on regression, 2 on missing "
                        "baseline, 0 otherwise")
    p.add_argument("--drift-ledger", default=None,
                   help=f"drift ledger file (default: "
                        f"<dir>/{DRIFT_LEDGER_NAME})")
    p.add_argument("--drift-band", type=float, default=DRIFT_BAND,
                   help="flag sites whose predicted/measured seconds "
                        "ratio exceeds this factor either way "
                        f"(default {DRIFT_BAND:g}; measured entries "
                        "only — modeled rounds are never drift-gated)")
    p.add_argument("--json", action="store_true",
                   help="emit the trajectory as JSON instead of a table")
    args = p.parse_args(argv)

    rounds = collect_rounds(args.dir)
    mrounds = collect_multichip(args.dir)
    srounds = collect_serving(args.dir)
    arounds = collect_ann(args.dir)
    murounds = collect_mutation(args.dir)
    rrounds = collect_recovery(args.dir)
    baseline_path = args.baseline or os.path.join(args.dir, BASELINE_NAME)
    baseline = load_record(baseline_path)
    stale = artifact_staleness(args.dir, baseline)

    if args.check:
        # newest round wins; older rounds are history, not candidates
        candidate = None
        for _, _, rec in reversed(rounds):
            if rec is not None:
                candidate = rec
                break
        status, msg = check_regression(candidate, baseline, args.threshold)
        if candidate is not None and "drift_checked" in candidate:
            msg += (" [drift-checked round]" if candidate["drift_checked"]
                    else " [modeled round — not drift-calibrated]")
        print(f"bench_report --check: {status}: {msg}")
        mstatus, mmsg = check_multichip(mrounds, args.threshold)
        print(f"bench_report --check [multichip]: {mstatus}: {mmsg}")
        sstatus, smsg = check_serving(srounds, args.threshold)
        print(f"bench_report --check [serving]: {sstatus}: {smsg}")
        astatus, amsg = check_ann(arounds, args.threshold)
        print(f"bench_report --check [ann]: {astatus}: {amsg}")
        mustatus, mumsg = check_mutation(murounds, args.threshold)
        print(f"bench_report --check [mutation]: {mustatus}: {mumsg}")
        rstatus, rmsg = check_recovery(rrounds, args.threshold)
        print(f"bench_report --check [recovery]: {rstatus}: {rmsg}")
        # multichip: the bare benchmark artifact MULTICHIP_SHARDED.json
        # is the freshest carrier of the quantized block — the numbered
        # rounds lag it by one round
        newest_m = load_multichip(
            os.path.join(args.dir, "MULTICHIP_SHARDED.json"))
        if newest_m is None:
            newest_m = next((rec for _, _, rec in reversed(mrounds)
                             if rec is not None), None)
        newest_a = next((rec for _, _, rec in reversed(arounds)
                         if rec is not None), None)
        qstatus, qmsg = check_quantized(
            [("bench", candidate), ("multichip", newest_m),
             ("ann", newest_a)])
        print(f"bench_report --check [quantized]: {qstatus}: {qmsg}")
        # quality: every family's newest artifact — blocks are stamped
        # by benchmark.Fixture.run / the bench writers (ISSUE 10)
        newest_s = next((rec for _, _, rec in reversed(srounds)
                         if rec is not None), None)
        newest_mu = next((rec for _, _, rec in reversed(murounds)
                          if rec is not None), None)
        qlstatus, qlmsg = check_quality(
            [("bench", candidate), ("multichip", newest_m),
             ("serving", newest_s), ("ann", newest_a),
             ("mutation", newest_mu)])
        print(f"bench_report --check [quality]: {qlstatus}: {qlmsg}")
        slstatus, slmsg = check_slo(srounds)
        print(f"bench_report --check [slo]: {slstatus}: {slmsg}")
        bbstatus, bbmsg = check_blackbox(srounds)
        print(f"bench_report --check [blackbox]: {bbstatus}: {bbmsg}")
        ledger_path = args.drift_ledger or os.path.join(
            args.dir, DRIFT_LEDGER_NAME)
        dstatus, dmsg = check_drift(load_drift_ledger(ledger_path),
                                    args.drift_band)
        print(f"bench_report --check [drift]: {dstatus}: {dmsg}")
        lstatus, lmsg = check_lint(
            load_lint(os.path.join(args.dir, LINT_NAME)))
        print(f"bench_report --check [lint]: {lstatus}: {lmsg}")
        for e in stale:
            if e.get("status") == "STALE":
                print(f"bench_report --check: note: {e['artifact']} is "
                      f"STALE ({e['age_rounds_note']})")
        codes = {PASS: 0, SKIP: 0, REGRESS: 1, MISSING_BASELINE: 2}
        # regression in ANY trend fails; missing baseline only when
        # nothing regressed
        rcs = (codes[status], codes[mstatus], codes[sstatus],
               codes[astatus], codes[mustatus], codes[rstatus],
               codes[qstatus], codes[qlstatus], codes[slstatus],
               codes[bbstatus], codes[dstatus], codes[lstatus])
        return 1 if 1 in rcs else max(rcs)

    if args.json:
        payload = {
            "rounds": [{"round": n, "path": os.path.basename(path),
                        "record": rec} for n, path, rec in rounds],
            "multichip_rounds": [
                {"round": n, "path": os.path.basename(path),
                 "record": rec} for n, path, rec in mrounds],
            "serving_rounds": [
                {"round": n, "path": os.path.basename(path),
                 "record": rec} for n, path, rec in srounds],
            "ann_rounds": [
                {"round": n, "path": os.path.basename(path),
                 "record": rec} for n, path, rec in arounds],
            "mutation_rounds": [
                {"round": n, "path": os.path.basename(path),
                 "record": rec} for n, path, rec in murounds],
            "recovery_rounds": [
                {"round": n, "path": os.path.basename(path),
                 "record": rec} for n, path, rec in rrounds],
            "named_artifacts": stale,
            "lint": load_lint(os.path.join(args.dir, LINT_NAME)),
            "baseline": baseline,
            "drift_ledger": load_drift_ledger(
                args.drift_ledger
                or os.path.join(args.dir, DRIFT_LEDGER_NAME)),
        }
        print(json.dumps(payload, indent=1, sort_keys=True, default=str))
        return 0

    sys.stdout.write(trajectory(rounds, baseline))
    sys.stdout.write("\n")
    sys.stdout.write(multichip_trajectory(mrounds))
    sys.stdout.write("\n")
    sys.stdout.write(serving_trajectory(srounds))
    sys.stdout.write("\n")
    sys.stdout.write(ann_trajectory(arounds))
    sys.stdout.write("\n")
    sys.stdout.write(mutation_trajectory(murounds))
    sys.stdout.write("\n")
    sys.stdout.write(recovery_trajectory(rrounds))
    sys.stdout.write("\n")
    sys.stdout.write(staleness_section(stale))
    return 0


if __name__ == "__main__":
    sys.exit(main())
