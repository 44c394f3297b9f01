"""Quality-of-results telemetry (ISSUE 10 tentpole).

The registry (PR 1) sees *performance* and the flight recorder (PR 6)
sees *when* — neither sees whether the ANSWERS are still right. This
module makes result quality a first-class telemetry plane:

- **Certificate / fixup counters** — every certified result path
  (``distance.knn_fused``, ``distance.knn_sharded``, the IVF q8 rescore
  in ``ann.ivf_flat``, the ``runtime.knn_query`` AOT serving entry)
  reports how many queries it checked, how many failed the twin-pool
  certificate (and therefore paid the exact fixup), which static fixup
  tier absorbed them, and how wide its exact-rescore pool was. ROADMAP
  item 2 needs exactly this evidence ("production fixup-rate") before
  per-query Eq tightening can be justified; until now the failure count
  lived only inside the jitted program.
- **Deferred host-side recording** — the failure count is a traced
  scalar. :func:`record_pending` keeps the DEVICE value in a bounded
  queue (no host sync on the dispatch path — async dispatch semantics
  are untouched); :func:`drain` resolves the pending scalars the next
  time anyone looks (``statusz``, ``Fixture.run``, an artifact writer,
  ``quality_block``) — by then the program has long completed, so the
  conversion costs one buffer read, zero traced-program time. Paths
  that already sync host-side (the IVF q8 certificate-failure rerun)
  record directly via :func:`record_certificate`.
- **Online recall shadow-sampling** — :class:`ShadowSampler` re-runs a
  configurable fraction of LIVE serving requests against a brute-force
  oracle on a background thread (off the hot path), maintains a rolling
  ``recall@k`` gauge, and emits a ``drift`` flight event + breach
  counter when the rolling recall drops below a floor: the online
  counterpart of ``bench_report --check``'s offline ANN recall gate. A
  bad ``RAFT_TPU_ANN_NPROBES`` setting or a corrupted index swap now
  shows up in minutes, not at the next offline benchmark round.

Env knobs (README "Quality telemetry & request tracing"):

- ``RAFT_TPU_SERVING_SHADOW_FRAC``  — fraction of live requests shadow
  sampled (default 0 = off; the serving engine reads it at start()).
- ``RAFT_TPU_SERVING_SHADOW_FLOOR`` — rolling-recall floor below which
  the sampler emits a ``drift`` flight event (default 0.95 — the same
  floor the offline ANN gate enforces).
- ``RAFT_TPU_DISABLE_QUALITY``      — turn the quality plane off
  without touching the rest of tracing (``RAFT_TPU_DISABLE_TRACING``
  disables it too, like every other observability surface).
"""

from __future__ import annotations

import collections
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from raft_tpu.observability.metrics import get_registry, tracing_enabled

# ---- the quality slice of the metric vocabulary -----------------------
#: queries whose certificate was evaluated, per site
CERT_CHECKS = "raft_tpu_certificate_checks_total"
#: queries that FAILED the certificate and paid the exact fixup
CERT_FIXUPS = "raft_tpu_certificate_fixups_total"
#: fixup batch sizes — the static tier (16/128/512/1024 or the full
#: fallback) that absorbed each nonzero failure batch
FIXUP_ROWS = "raft_tpu_certificate_fixup_rows"
#: fixups whose select overflowed the threshold block filter into the
#: exact select over the whole [F, M] tile (knn_fused.fixup_select)
FIXUP_OVERFLOW = "raft_tpu_cert_fixup_overflow_total"
#: exact-rescore candidate-pool widths (C = k + pad clamped to the pool)
RESCORE_POOL = "raft_tpu_rescore_pool_width"
#: IVF chunks whose q8 certificate failure forced an exact f32-scan rerun
IVF_RERUNS = "raft_tpu_ivf_cert_rerun_total"
#: record_pending calls skipped because they executed under tracing
#: (n_fail was a Tracer — see the guard in record_pending)
TRACE_SKIPS = "raft_tpu_certificate_trace_skips_total"
#: per-rung outcomes of the PQ certification ladder
#: (rung ∈ certified / widened / exact_rerun)
PQ_RUNGS = "raft_tpu_pq_cert_rung_total"
#: running per-site fraction of PQ queries that escalated past the
#: widen rungs to the full exact rerun (the BENCH_ANN cert_rerun_frac,
#: live)
PQ_RERUN_FRAC = "raft_tpu_pq_cert_rerun_frac"
#: shadow-sampled requests re-scored against the oracle
SHADOW_SAMPLES = "raft_tpu_serving_shadow_samples_total"
#: shadow candidates dropped because the sampler queue was full
SHADOW_DROPPED = "raft_tpu_serving_shadow_dropped_total"
#: rolling recall@k of shadow-sampled responses vs the oracle
SHADOW_RECALL = "raft_tpu_serving_shadow_recall"
#: rolling-recall drops below the floor (each emits a drift event)
SHADOW_BREACHES = "raft_tpu_serving_shadow_breaches_total"

#: power-of-two-ish count buckets for fixup batch sizes / pool widths
#: (DEFAULT_TIME_BUCKETS are seconds — wrong unit for row counts)
COUNT_BUCKETS: Tuple[float, ...] = (
    1., 2., 4., 8., 16., 32., 64., 128., 256., 512., 1024., 2048., 4096.)

DEFAULT_SHADOW_FLOOR = 0.95
SHADOW_FRAC_ENV = "RAFT_TPU_SERVING_SHADOW_FRAC"
SHADOW_FLOOR_ENV = "RAFT_TPU_SERVING_SHADOW_FLOOR"


def quality_enabled() -> bool:
    """One switch for the whole quality plane: follows the global
    tracing kill switch, plus its own opt-out."""
    return (tracing_enabled()
            and not os.environ.get("RAFT_TPU_DISABLE_QUALITY"))


def shadow_frac_default() -> float:
    """The env-configured shadow-sampling fraction (0 = off)."""
    try:
        return max(0.0, min(1.0, float(
            os.environ.get(SHADOW_FRAC_ENV, "0") or 0.0)))
    except (TypeError, ValueError):
        return 0.0


def shadow_floor_default() -> float:
    try:
        return float(os.environ.get(SHADOW_FLOOR_ENV,
                                    DEFAULT_SHADOW_FLOOR))
    except (TypeError, ValueError):
        return DEFAULT_SHADOW_FLOOR


# ---------------------------------------------------------- recording
def fixup_tier_for(n_fail: int, fix_tiers: Sequence[int],
                   n_queries: int) -> int:
    """The fixup batch size the tiered cascade dispatched for ``n_fail``
    failures — the HOST mirror of the ``jax.lax.cond`` ladder in
    ``_knn_fused_core`` (smallest eligible tier covering the count,
    else the full fallback over all ``n_queries``)."""
    if n_fail <= 0:
        return 0
    for t in sorted(int(t) for t in fix_tiers):
        if n_fail <= t and t < n_queries:
            return t
    return int(n_queries)


def record_certificate(site: str, n_queries: int, n_fail: int,
                       pool_width: Optional[int] = None,
                       fixup_rows: Optional[int] = None,
                       rerun: bool = False,
                       n_over: Optional[int] = None, **meta) -> None:
    """Host-side record of one certificate evaluation batch. ``n_over``
    (where the site reports it) counts the batch's fixups whose select
    overflowed into the whole-tile select. Never raises into the result
    path."""
    if not quality_enabled():
        return
    try:
        reg = get_registry()
        labels = {"site": site}
        reg.counter(CERT_CHECKS, labels,
                    help="Queries whose exactness certificate was "
                         "evaluated").inc(max(0, int(n_queries)))
        reg.counter(CERT_FIXUPS, labels,
                    help="Queries that failed the certificate and paid "
                         "the exact fixup").inc(max(0, int(n_fail)))
        if pool_width:
            reg.histogram(RESCORE_POOL, labels,
                          help="Exact-rescore candidate-pool widths",
                          buckets=COUNT_BUCKETS).observe(int(pool_width))
        if fixup_rows:
            reg.histogram(FIXUP_ROWS, labels,
                          help="Static fixup-tier batch sizes "
                               "dispatched for failed queries",
                          buckets=COUNT_BUCKETS).observe(int(fixup_rows))
        if n_over is not None:
            reg.counter(FIXUP_OVERFLOW, labels,
                        help="Certificate fixups whose select "
                             "overflowed the threshold block filter "
                             "into the whole-tile select").inc(
                                 max(0, int(n_over)))
        if rerun:
            reg.counter(IVF_RERUNS, labels,
                        help="IVF q8 chunks rerun through the exact "
                             "f32 scan after a certificate failure"
                        ).inc()
        if n_fail:
            from raft_tpu.observability.timeline import emit_quality

            emit_quality(site, n_fail=int(n_fail),
                         n_queries=int(n_queries),
                         fixup_rows=fixup_rows, rerun=bool(rerun),
                         **meta)
    except Exception:
        pass


# per-site running PQ rung tallies: site -> [total_queries, exact_reruns]
# — the evidence expected_pq_rerun_frac's MEASURED branch reads
_pq_tally: Dict[str, List[int]] = {}
_pq_lock = threading.Lock()


def record_pq_rungs(site: str, certified: int, widened: int,
                    exact_rerun: int) -> None:
    """Host-side record of one PQ certification-ladder batch: how many
    queries each rung resolved (``certified`` = base ADC pool cleared
    the bound, ``widened`` = a 2x/4x re-ADC pool cleared it,
    ``exact_rerun`` = escalated to the full exact scan). Maintains the
    per-rung counters and the running ``raft_tpu_pq_cert_rerun_frac``
    gauge. Never raises into the result path."""
    if not quality_enabled():
        return
    try:
        total = max(0, int(certified)) + max(0, int(widened)) \
            + max(0, int(exact_rerun))
        if not total:
            return
        with _pq_lock:
            tally = _pq_tally.setdefault(site, [0, 0])
            tally[0] += total
            tally[1] += max(0, int(exact_rerun))
            frac = tally[1] / tally[0]
        reg = get_registry()
        for rung, n in (("certified", certified), ("widened", widened),
                        ("exact_rerun", exact_rerun)):
            if n > 0:
                reg.counter(PQ_RUNGS, {"site": site, "rung": rung},
                            help="PQ queries resolved per "
                                 "certification-ladder rung"
                            ).inc(int(n))
        reg.gauge(PQ_RERUN_FRAC, {"site": site},
                  help="Running fraction of PQ queries escalating to "
                       "the full exact rerun").set(round(frac, 6))
    except Exception:
        pass


def measured_rerun_frac(site: str,
                        min_checks: int = 64) -> Optional[float]:
    """The process-measured exact-rerun fraction at ``site``, or None
    until at least ``min_checks`` queries have walked the ladder —
    the chooser's measured-beats-modeled evidence."""
    with _pq_lock:
        tally = _pq_tally.get(site)
        if tally is None or tally[0] < max(1, int(min_checks)):
            return None
        return tally[1] / tally[0]


# pending certificate stats whose failure count is still a device value:
# (site, n_fail_device, n_queries, pool_width, fix_tiers, n_over_device,
#  meta)
_PENDING_CAP = 4096
_pending: collections.deque = collections.deque(maxlen=_PENDING_CAP)
_pending_lock = threading.Lock()


def record_pending(site: str, n_fail, n_queries: int,
                   pool_width: Optional[int] = None,
                   fix_tiers: Sequence[int] = (), n_over=None,
                   **meta) -> None:
    """Queue certificate stats whose ``n_fail`` (and ``n_over``, the
    fixup select-overflow count, where the site has one) is an
    UNRESOLVED device scalar/array — no host sync here, so the dispatch
    path keeps its async semantics; :func:`drain` converts later (the
    values are tiny outputs of a program whose results the caller
    consumes anyway)."""
    if not quality_enabled():
        return
    try:
        from jax.core import Tracer

        if isinstance(n_fail, Tracer):
            # the recorder was reached AT TRACE TIME (a host wrapper
            # traced whole, e.g. knn under fused_l2nn.knn_sharded's
            # shard_map) — a tracer must never enter the pending ring:
            # drain() cannot resolve it and used to drop the entry
            # silently. Count the skip so the gap is visible.
            _count_trace_skip(site)
            return
    except ImportError:       # no jax on this host: nothing traced
        pass
    with _pending_lock:
        _pending.append((site, n_fail, int(n_queries),
                         pool_width, tuple(fix_tiers), n_over,
                         dict(meta)))


def _count_trace_skip(site: str) -> None:
    try:
        from raft_tpu.observability import get_registry

        get_registry().counter(
            TRACE_SKIPS, {"site": site},
            help="Certificate stats skipped because the recorder ran "
                 "under tracing (tracer n_fail)").inc()
    except Exception:
        pass


def drain() -> int:
    """Resolve every pending certificate record into the registry;
    returns how many were drained. Safe to call from any thread; a
    conversion failure drops that entry rather than raising."""
    n = 0
    while True:
        with _pending_lock:
            if not _pending:
                return n
            site, nf, nq, pw, tiers, no, meta = _pending.popleft()
        try:
            n_fail = int(np.sum(np.asarray(nf)))
            n_over = None if no is None else int(np.sum(np.asarray(no)))
        except Exception:
            continue
        record_certificate(
            site, nq, n_fail, pool_width=pw,
            fixup_rows=fixup_tier_for(n_fail, tiers, nq), n_over=n_over,
            **meta)
        n += 1


def pending_count() -> int:
    with _pending_lock:
        return len(_pending)


def clear() -> None:
    """Drop pending (undrained) records and the PQ rung tallies —
    tests."""
    with _pending_lock:
        _pending.clear()
    with _pq_lock:
        _pq_tally.clear()


# ------------------------------------------------------------ snapshot
def quality_block(registry=None, drain_first: bool = True
                  ) -> Optional[Dict]:
    """The ``quality`` block BENCH/MULTICHIP/ANN/SERVING artifacts carry
    (gated by ``tools/bench_report.py --check``): per-site certificate
    checks / fixups / fixup_rate, rescore-pool width stats, and the
    shadow-recall gauges when a sampler ran. None when the process
    recorded no quality telemetry at all."""
    if drain_first:
        drain()
    reg = registry if registry is not None else get_registry()
    sites: Dict[str, Dict] = {}
    pools: Dict[str, Dict] = {}
    shadow: Dict[str, float] = {}
    for metric in reg.collect():
        site = metric.labels.get("site")
        if metric.name == CERT_CHECKS and site:
            sites.setdefault(site, {})["checks"] = int(metric.value)
        elif metric.name == CERT_FIXUPS and site:
            sites.setdefault(site, {})["fixups"] = int(metric.value)
        elif metric.name == IVF_RERUNS and site:
            sites.setdefault(site, {})["cert_reruns"] = int(metric.value)
        elif metric.name == FIXUP_OVERFLOW and site:
            sites.setdefault(site, {})["fixup_overflows"] = int(
                metric.value)
        elif metric.name == PQ_RERUN_FRAC and site:
            sites.setdefault(site, {})["pq_rerun_frac"] = round(
                float(metric.value), 6)
        elif metric.name == PQ_RUNGS and site:
            sites.setdefault(site, {}).setdefault("pq_rungs", {})[
                metric.labels.get("rung", "?")] = int(metric.value)
        elif metric.name == RESCORE_POOL and site:
            cnt = metric.count
            pools[site] = {"count": cnt,
                           "mean": round(metric.sum / cnt, 2) if cnt
                           else 0.0}
        elif metric.name == SHADOW_RECALL:
            shadow["shadow_recall"] = round(float(metric.value), 4)
        elif metric.name == SHADOW_SAMPLES:
            shadow["shadow_samples"] = int(metric.value)
        elif metric.name == SHADOW_BREACHES:
            shadow["shadow_breaches"] = int(metric.value)
    if not sites and not shadow:
        return None
    checks = sum(s.get("checks", 0) for s in sites.values())
    fixups = sum(s.get("fixups", 0) for s in sites.values())
    for s in sites.values():
        c = s.get("checks", 0)
        s["fixup_rate"] = round(s.get("fixups", 0) / c, 6) if c else 0.0
    out: Dict = {
        "fixup_rate": round(fixups / checks, 6) if checks else 0.0,
        "certificate_checks": checks,
        "certificate_fixups": fixups,
        "sites": sites,
    }
    if pools:
        out["rescore_pool_widths"] = pools
    out.update(shadow)
    return out


# ------------------------------------------------- shadow recall sampler
def _sample_hash(rid: int) -> float:
    """Deterministic per-request uniform in [0, 1) (Knuth multiplicative
    hash) — the sampling decision replays bit-identically across runs,
    which the deterministic serving tests rely on."""
    return ((int(rid) * 2654435761) & 0xFFFFFFFF) / 2.0 ** 32


def recall_at_k(served_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Mean per-row |served ∩ true| / k — the same recall the
    benchmark cells (``bench_suite/``) report."""
    served = np.asarray(served_ids)
    true = np.asarray(true_ids)
    if served.ndim == 1:
        served = served[None]
    if true.ndim == 1:
        true = true[None]
    k = true.shape[1]
    hits = [len(set(int(i) for i in served[r] if i >= 0)
                & set(int(i) for i in true[r]))
            for r in range(true.shape[0])]
    return float(np.mean(hits)) / max(1, k)


class ShadowSampler:
    """Online recall shadow-sampling for the serving engine.

    A sampled (request, served ids) pair is queued (bounded — overload
    DROPS samples, counted, rather than backing up into the serving
    path) and re-scored on a daemon thread: ``oracle(x) -> (vals,
    ids)`` is the exact brute-force plane for the engine's current
    snapshot. Recall@k per sample feeds a rolling window; the window
    mean is the ``raft_tpu_serving_shadow_recall`` gauge, and a mean
    below ``floor`` (after ``min_samples``) emits a ``drift`` flight
    event + breach counter — quality drift surfaces on the same
    timeline as every other anomaly.
    """

    def __init__(self, oracle: Callable, k: int, frac: float,
                 floor: Optional[float] = None, window: int = 256,
                 max_queue: int = 64, min_samples: int = 4,
                 site: str = "serving.shadow", registry=None):
        self._oracle = oracle
        self.k = int(k)
        self.frac = max(0.0, min(1.0, float(frac)))
        self.floor = (shadow_floor_default() if floor is None
                      else float(floor))
        self.site = site
        self._reg = registry
        self._window: collections.deque = collections.deque(
            maxlen=max(1, int(window)))
        self._min_samples = max(1, int(min_samples))
        self._max_queue = max(1, int(max_queue))
        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._stop = False
        self._busy = False
        self._samples = 0
        self._dropped = 0
        self._breaches = 0
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ShadowSampler":
        if self._thread is None:
            self._stop = False
            self._thread = threading.Thread(target=self._loop,
                                            name="serving-shadow",
                                            daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None

    def flush(self, timeout: float = 30.0) -> bool:
        """Block until every queued sample is scored (tests/benchmarks;
        the live path never waits on the shadow)."""
        import time as _time

        t_end = _time.monotonic() + timeout
        with self._cond:
            self._cond.notify_all()
            while ((self._queue or self._busy)
                   and _time.monotonic() < t_end):
                self._cond.wait(0.01)
            return not self._queue and not self._busy

    # -- sampling ---------------------------------------------------------
    def want(self, rid: int) -> bool:
        """Deterministic sampling decision for request ``rid``."""
        return self.frac > 0.0 and _sample_hash(rid) < self.frac

    def submit(self, rid: int, x, served_ids) -> bool:
        """Queue one sampled request; False (and a drop count) when the
        queue is full — shadow work never backs up into serving."""
        with self._cond:
            if len(self._queue) >= self._max_queue:
                self._dropped += 1
                self._metric("counter", SHADOW_DROPPED,
                             "Shadow samples dropped (queue full)")
                return False
            self._queue.append((int(rid), np.asarray(x, np.float32),
                                np.asarray(served_ids)))
            self._cond.notify_all()
        return True

    # -- the scorer thread ------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._stop:
                    self._cond.wait(0.05)
                if self._stop and not self._queue:
                    return
                item = self._queue.popleft()
                self._busy = True
            try:
                self._score(*item)
            except Exception:
                pass
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()

    def _score(self, rid: int, x: np.ndarray,
               served_ids: np.ndarray) -> None:
        _, true_ids = self._oracle(x)
        r = recall_at_k(served_ids, np.asarray(true_ids))
        with self._cond:
            self._window.append(r)
            self._samples += 1
            rolling = float(np.mean(self._window))
            breach = (self._samples >= self._min_samples
                      and rolling < self.floor)
            if breach:
                self._breaches += 1
        self._metric("counter", SHADOW_SAMPLES,
                     "Shadow-sampled requests re-scored vs the oracle")
        self._metric("gauge", SHADOW_RECALL,
                     "Rolling recall@k of served vs oracle results",
                     value=rolling)
        if breach:
            self._metric("counter", SHADOW_BREACHES,
                         "Rolling shadow recall fell below the floor")
            try:
                from raft_tpu.observability.flight import \
                    get_flight_recorder

                rec = get_flight_recorder()
                if rec.enabled:
                    # quality drift rides the same event kind as
                    # model-vs-measured drift: one timeline, one alarm
                    rec.record("drift", self.site, lane="serving",
                               recall=round(rolling, 4),
                               floor=self.floor, rid=int(rid),
                               measured=True)
            except Exception:
                pass

    def _metric(self, kind: str, name: str, help: str,
                value: Optional[float] = None) -> None:
        try:
            reg = self._reg if self._reg is not None else get_registry()
            if kind == "gauge":
                reg.gauge(name, help=help).set(float(value))
            else:
                reg.counter(name, help=help).inc()
        except Exception:
            pass

    # -- queries ----------------------------------------------------------
    def snapshot(self) -> Dict:
        with self._cond:
            rolling = (float(np.mean(self._window)) if self._window
                       else None)
            return {"shadow_frac": self.frac,
                    "shadow_floor": self.floor,
                    "shadow_samples": self._samples,
                    "shadow_dropped": self._dropped,
                    "shadow_breaches": self._breaches,
                    "shadow_recall": (round(rolling, 4)
                                      if rolling is not None else None)}
