"""The closed-loop micro-batching query engine (ISSUE 7 tentpole).

The repo's front door so far is a library call — one caller, one batch.
This module is the millions-of-users shape: a thread-safe request queue
that COALESCES arriving queries into dynamic micro-batches, pads each
batch up to the bucket ladder (:mod:`raft_tpu.serving.buckets` — a
small fixed set of pre-AOT-compiled shapes, warmed at engine start via
``runtime.entry_points.knn_query``, so no live request ever pays a
trace/compile), and dispatches them against an immutable
:class:`~raft_tpu.serving.snapshot.IndexSnapshot` (background
rebuild-and-swap for updates — readers never block on a swap).

Resilience is the PR-5 runtime, reused:

- per-request **admission control**: an oversized request (> the top
  bucket) is rejected with a classified :class:`RequestTooLargeError`
  (never silently truncated); a full queue **sheds** the request with
  :class:`OverloadShedError` — recorded as a NEW degradation-ladder
  rung (``shed:overload``) rather than letting latency grow into a
  hang; a request whose deadline expires while still queued is failed
  with ``DeadlineExceededError`` at batch-assembly time instead of
  wasting a dispatch.
- per-batch :func:`raft_tpu.resilience.deadline` scopes: the batcher
  thread arms the MINIMUM remaining budget across the batch, so a hung
  dispatch converts into a typed error within one poll interval. The
  thread-safe re-entrant token rework (this PR) is what makes per-batch
  scopes on a worker thread safe next to callers' own scopes.
- fault sites ``serving_enqueue`` / ``serving_flush`` make both halves
  of the pipe injectable (``RAFT_TPU_FAULTS``).

Observability: every admitted request, flush, shed and swap emits a
``serving`` flight-recorder event (:func:`raft_tpu.observability.
timeline.emit_serving`); queue depth is a live gauge, request latency a
p50/p99-capable histogram, and every batch/bucket/shed transition a
labeled counter through the MetricsRegistry — the evidence surface
``benchmarks/bench_serving.py`` turns into the ``BENCH_SERVING.json``
SLO artifact. Each dispatched request's queue wait (enqueue → batch
pop) is a histogram sample (``raft_tpu_serving_queue_wait_seconds``);
on the batcher thread the ``serving.flush_wait`` span times the wait
for co-riders, and ``serving.device_wait`` (inside
``serving.execute_batch``) the wait for the device and the copy of the
answers to the host.

Quality plane (ISSUE 10):

- **per-request flow tracing** — every admitted request gets a
  monotonic id at enqueue and emits Perfetto flow points
  (:func:`~raft_tpu.observability.timeline.emit_flow`): ``s`` on the
  client thread at enqueue, ``t`` steps through batch assembly and
  dispatch on the batcher thread, ``f`` at the terminus — so one
  request renders as ONE connected flow across lanes in the trace, and
  shed / queue-expiry / requeue / deadline outcomes annotate the
  terminus instead of vanishing into counters.
- **online recall shadow-sampling** — a configurable fraction of live
  requests (``RAFT_TPU_SERVING_SHADOW_FRAC`` or ``shadow_frac=``) is
  re-scored against the exact brute-force oracle on a background
  thread (:class:`~raft_tpu.observability.quality.ShadowSampler`);
  the rolling recall@k gauge plus a ``drift`` flight event below the
  floor is the ONLINE counterpart of the offline ANN recall gate — an
  index swap or a bad ``RAFT_TPU_ANN_NPROBES`` can no longer silently
  degrade answers between benchmark rounds.

Env knobs (see README "Serving & SLO workflow" + "Quality telemetry
& request tracing"):

- ``RAFT_TPU_SERVING_BUCKETS``   — bucket ladder (buckets.py)
- ``RAFT_TPU_SERVING_FLUSH_MS``  — flush window for a partial batch
  (default 2 ms: the oldest queued request never waits longer than
  this for co-riders before dispatching)
- ``RAFT_TPU_SERVING_QUEUE_CAP`` — max queued QUERY ROWS before
  admission sheds (default 4096)
- ``RAFT_TPU_SERVING_DEADLINE_S`` — default per-request deadline
  budget (unset = requests carry no deadline unless submitted with one)
- ``RAFT_TPU_SERVING_SHADOW_FRAC`` / ``RAFT_TPU_SERVING_SHADOW_FLOOR``
  — shadow-sampling fraction (0 = off) and recall floor (0.95)
- ``RAFT_TPU_DURABLE_DIR`` / ``RAFT_TPU_WAL_SYNC`` — the durability
  plane's directory (``durable=True``) and WAL fsync policy
  (``always`` / ``batch`` [default] / ``none`` — README "Durability &
  recovery")
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from raft_tpu.core import env, interruptible
from raft_tpu.core.error import (DeadlineExceededError, LogicError,
                                 RaftException, expects)
from raft_tpu.core.logger import log_warn
from raft_tpu.core.resources import ensure_resources
from raft_tpu.observability import instrument, span
from raft_tpu.observability.metrics import percentile
from raft_tpu.observability.quality import (ShadowSampler,
                                            shadow_floor_default,
                                            shadow_frac_default)
from raft_tpu.observability.timeline import emit_flow, emit_serving
from raft_tpu.resilience import deadline, fault_point, record_degradation
from raft_tpu.serving.buckets import bucket_for, bucket_ladder
from raft_tpu.serving.snapshot import IndexSnapshot, SnapshotStore

# metric names (the serving slice of the registry vocabulary)
REQUESTS = "raft_tpu_serving_requests_total"
LATENCY = "raft_tpu_serving_latency_seconds"
QUEUE_DEPTH = "raft_tpu_serving_queue_rows"
BATCHES = "raft_tpu_serving_batches_total"
BATCH_PAD_ROWS = "raft_tpu_serving_batch_pad_rows_total"
SHED = "raft_tpu_serving_shed_total"
QUEUE_WAIT = "raft_tpu_serving_queue_wait_seconds"

FLUSH_MS_ENV = "RAFT_TPU_SERVING_FLUSH_MS"
QUEUE_CAP_ENV = "RAFT_TPU_SERVING_QUEUE_CAP"
DEADLINE_ENV = "RAFT_TPU_SERVING_DEADLINE_S"

#: bounded retries for requests bumped out of a batch by a NEIGHBOR's
#: deadline firing (the request itself still has budget) — one requeue,
#: then honest failure
_MAX_REQUEUES = 1


class RequestTooLargeError(LogicError):
    """Request exceeds the largest bucket of the serving ladder —
    rejected at admission (classified, never silently truncated; split
    client-side or raise the ladder via RAFT_TPU_SERVING_BUCKETS)."""


class OverloadShedError(RaftException):
    """Admission control shed this request: the queue is at its row
    cap. Shedding is the engine's overload degradation rung — callers
    back off / retry; the engine never converts overload into unbounded
    queueing latency."""


class ServingFuture:
    """Completion handle for one submitted request."""

    __slots__ = ("_event", "_vals", "_ids", "_error")

    def __init__(self):
        self._event = threading.Event()
        self._vals = None
        self._ids = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def _complete(self, vals, ids) -> None:
        self._vals, self._ids = vals, ids
        self._event.set()

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self._event.set()

    def exception(self, timeout: Optional[float] = None
                  ) -> Optional[BaseException]:
        if not self._event.wait(timeout):
            raise TimeoutError("serving request still pending")
        return self._error

    def result(self, timeout: Optional[float] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Block for this request's (values [n, k], ids [n, k]);
        re-raises the request's classified failure."""
        if not self._event.wait(timeout):
            raise TimeoutError("serving request still pending")
        if self._error is not None:
            raise self._error
        return self._vals, self._ids


class _Request:
    __slots__ = ("x", "n", "enqueued_at", "deadline_at", "future",
                 "requeues", "rid", "kind", "ids", "explain")

    def __init__(self, x, n, enqueued_at, deadline_at, future,
                 rid=0, kind="query", ids=None, explain=False):
        self.x = x
        self.n = n
        self.enqueued_at = enqueued_at
        self.deadline_at = deadline_at
        self.future = future
        self.requeues = 0
        self.rid = rid          # monotonic flow-trace id (enqueue order)
        self.kind = kind        # "query" | "upsert" | "delete"
        self.ids = ids          # external row ids (mutation requests)
        self.explain = explain  # capture an explain record for the
        #                         batch this request rides


@instrument("serving.execute_batch")
def execute_batch(plane, snap: IndexSnapshot, x: np.ndarray, bucket: int,
                  n_valid: int, budget_s: Optional[float] = None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Dispatch ONE coalesced micro-batch against one snapshot.

    ``x`` [n_valid, d] is the concatenated request rows; it is padded
    up to ``bucket`` (a pre-warmed shape — see the module doc) and run
    through the engine's data ``plane``. ``budget_s`` (the minimum
    remaining request budget) arms a :func:`deadline` scope on THIS
    thread; the completion wait polls the cancellation token, so a hung
    dispatch converts instead of blocking the batcher forever. Carries
    the ``serving_flush`` fault site — OOM/error/timeout/hang at the
    flush are all injectable without touching the engine."""
    emit_serving("flush", bucket=bucket, rows=n_valid,
                 generation=snap.generation,
                 budget_s=budget_s)
    from raft_tpu.distance.knn_fused import pad_query_rows

    xp = pad_query_rows(x, bucket)

    def _dispatch():
        # the fault site sits INSIDE the deadline scope: an injected
        # hang here must be cancellable exactly like a real stuck
        # dispatch (the scope converts it within one poll interval)
        fault_point("serving_flush")
        vals, ids = plane(snap, xp)
        with span("serving.device_wait"):
            interruptible.synchronize(vals, ids)
            return np.asarray(vals)[:n_valid], np.asarray(ids)[:n_valid]

    if budget_s is not None:
        with deadline(budget_s, label="serving_flush"):
            return _dispatch()
    return _dispatch()


class ServingEngine:
    """Dynamic micro-batching KNN serving engine.

    ``index`` may be a prepared :class:`~raft_tpu.distance.knn_fused.
    KnnIndex` or a raw [m, d] matrix (prepared at construction).
    ``mesh`` switches the data plane from the single-device AOT entry
    (``runtime.knn_query``) to the PR-4 query-sharded replicated-index
    mode (``knn_fused_sharded(shard_mode="query")``) — data-parallel
    queries over the mesh axis, zero cross-shard merge traffic.

    Lifecycle::

        eng = ServingEngine(index, k=64)
        eng.start()                      # warms every bucket (AOT)
        fut = eng.submit(q, deadline_s=0.05)
        vals, ids = fut.result()
        eng.update_index(new_y)          # background rebuild-and-swap
        eng.stop()

    ``clock`` is injectable (tests/benchmarks pin a deterministic
    clock for deadline/ageing accounting; the batcher's waits stay
    real-time ticks).
    """

    def __init__(self, index, k: int, *, res=None, mesh=None,
                 axis: str = "x",
                 buckets: Union[str, Sequence[int], None] = None,
                 flush_interval_s: Optional[float] = None,
                 max_queue_rows: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 passes: int = 3, metric: str = "l2",
                 T: Optional[int] = None, Qb: Optional[int] = None,
                 g: Optional[int] = None,
                 grid_order: Optional[str] = None,
                 store_yp: bool = True,
                 rescore: Optional[bool] = None,
                 certify: str = "kernel",
                 algorithm: str = "brute",
                 n_lists: Optional[int] = None,
                 n_probes: Optional[int] = None,
                 pq_dim: Optional[int] = None,
                 pq_bits: Optional[int] = None,
                 db_dtype: Optional[str] = None,
                 shadow_frac: Optional[float] = None,
                 shadow_floor: Optional[float] = None,
                 mutable: bool = False,
                 index_ids=None,
                 compact_threshold: Optional[int] = None,
                 delta_cap: Optional[int] = None,
                 durable: bool = False,
                 durable_dir: Optional[str] = None,
                 wal_sync: Optional[str] = None,
                 explain_frac: Optional[float] = None,
                 debug_port: Optional[int] = None,
                 blackbox_path: Optional[str] = None,
                 watchdog_s: Optional[float] = None,
                 slo=None,
                 clock=time.monotonic):
        from raft_tpu.ann import IvfFlatIndex
        from raft_tpu.distance.knn_fused import KnnIndex

        # algorithm="ivf_flat": the SnapshotStore holds an IVF snapshot
        # (built via ann.build_ivf_flat, swapped like any other) and
        # the data plane serves APPROXIMATE queries through
        # ann.search_ivf_flat behind the exact same bucket ladder —
        # the speed/recall knob (n_probes) rides the serving tier.
        # algorithm="ivf_pq" is the compressed tier on the same plane:
        # ann.build_ivf_pq snapshots + ann.search_ivf_pq serving (ADC
        # over the codes slab, certified exact f32 rescore).
        if algorithm not in ("brute", "ivf_flat", "ivf_pq"):
            raise ValueError(f"ServingEngine: algorithm must be "
                             f"'brute', 'ivf_flat' or 'ivf_pq', got "
                             f"{algorithm!r}")
        if algorithm in ("ivf_flat", "ivf_pq"):
            expects(mesh is None,
                    "ServingEngine: algorithm=%r serves single-device "
                    "planes (shard the lists via ann.shard_ivf_lists "
                    "outside the engine)" % (algorithm,))
            expects(metric == "l2",
                    "ServingEngine: algorithm=%r serves metric='l2' "
                    "only" % (algorithm,))
        self._algorithm = algorithm
        self._n_lists, self._n_probes = n_lists, n_probes
        self._pq_dim, self._pq_bits = pq_dim, pq_bits
        self.res = ensure_resources(res)
        self.k = int(k)
        self._mesh, self._axis = mesh, axis
        self._rescore, self._certify = rescore, certify
        self._clock = clock
        # db_dtype threads through EVERY snapshot rebuild/swap: an
        # engine serving an int8-streamed index keeps serving int8
        # after background updates (None = the per-plane default —
        # bf16-streamed brute, f32 IVF slab; env RAFT_TPU_DB_DTYPE
        # sets the fleet default without a code change)
        if db_dtype is None:
            db_dtype = env.raw("RAFT_TPU_DB_DTYPE")
        self._db_dtype = db_dtype
        self._build_kw = dict(passes=passes, metric=metric, T=T, Qb=Qb,
                              g=g, grid_order=grid_order,
                              store_yp=store_yp)
        if db_dtype is not None:
            self._build_kw["db_dtype"] = db_dtype
        # durable=True (ISSUE 12): the mutation plane writes ahead —
        # every upsert/delete is WAL-appended + fsynced (per wal_sync /
        # RAFT_TPU_WAL_SYNC) BEFORE its future resolves, the compactor
        # commits an atomic checkpoint at every swap, and constructing
        # an engine over a directory that already holds durable state
        # RECOVERS from it (newest-valid-checkpoint + WAL tail replay
        # through the warmed rebuild machinery) instead of cold-building
        # from `index`. Implies mutable=True. Default OFF: the serving
        # hot path is byte-for-byte the non-durable one.
        self._durable = bool(durable)
        self._recovery = None
        if durable:
            mutable = True
            if durable_dir is None:
                from raft_tpu.mutable.checkpoint import DURABLE_DIR_ENV

                durable_dir = env.raw(DURABLE_DIR_ENV)
            expects(durable_dir is not None,
                    "serving: durable=True needs durable_dir= (or "
                    "RAFT_TPU_DURABLE_DIR)")
        self._durable_dir = durable_dir if durable else None
        # mutable=True: the engine fronts a MutableIndex — queries see a
        # consistent view per batch, and upsert()/delete() requests ride
        # the SAME queue, admission control and deadline scopes as
        # queries (the ISSUE-11 mutation plane). The engine's store IS
        # the mutable index's SnapshotStore, so generation accounting,
        # swap events and the snapshot gauges stay one surface.
        self._mutable = None
        if mutable:
            expects(mesh is None,
                    "ServingEngine: the mutable plane is single-device "
                    "(shard outside the engine)")
            from raft_tpu.mutable import MutableIndex

            src = (index if isinstance(index, (KnnIndex, IvfFlatIndex))
                   else np.asarray(index, np.float32))
            mut_kw = dict(algorithm=algorithm, passes=passes,
                          metric=metric, T=T, Qb=Qb, g=g,
                          db_dtype=db_dtype, n_lists=n_lists,
                          n_probes=n_probes,
                          compact_threshold=compact_threshold,
                          delta_cap=delta_cap)
            if algorithm == "ivf_pq":
                mut_kw.update(pq_dim=pq_dim, pq_bits=pq_bits)
            if durable:
                from raft_tpu.mutable.checkpoint import (
                    has_durable_state, recover)

                recovered = None
                if has_durable_state(durable_dir):
                    expects(not isinstance(index,
                                           (KnnIndex, IvfFlatIndex)),
                            "serving: durable recovery rebuilds the "
                            "index from disk — pass the raw matrix "
                            "(the bootstrap fallback), not a prepared "
                            "index")
                    recovered = recover(durable_dir, res=self.res,
                                        wal_sync=wal_sync, **mut_kw)
                if recovered is not None:
                    self._mutable, self._recovery = recovered
                else:
                    self._mutable = MutableIndex(
                        src, ids=index_ids, res=self.res,
                        durable_dir=durable_dir, wal_sync=wal_sync,
                        **mut_kw)
            else:
                self._mutable = MutableIndex(src, ids=index_ids,
                                             res=self.res, **mut_kw)
            expects(self.k <= self._mutable.n_rows,
                    "ServingEngine: k=%d > index size %d", self.k,
                    self._mutable.n_rows)
            self.d = self._mutable.d_orig
            self._store = self._mutable.store
            qb_hint = self._mutable.Qb
        else:
            if isinstance(index, (KnnIndex, IvfFlatIndex)):
                from raft_tpu.ann import IvfPqIndex

                want = ("ivf_pq" if isinstance(index, IvfPqIndex)
                        else "ivf_flat"
                        if isinstance(index, IvfFlatIndex)
                        else "brute")
                if want != algorithm:
                    raise ValueError(
                        "ServingEngine: prepared index type does not "
                        "match algorithm=%r" % (algorithm,))
                initial = index
            else:
                initial = self._build_index(np.asarray(index,
                                                       np.float32))
            expects(self.k <= initial.n_rows,
                    "ServingEngine: k=%d > index size %d", self.k,
                    initial.n_rows)
            self.d = initial.d_orig
            self._store = SnapshotStore(self._build_index,
                                        initial_index=initial)
            qb_hint = initial.Qb
        if buckets is None or isinstance(buckets, str):
            self._ladder = bucket_ladder(qb_hint, buckets)
        else:
            self._ladder = bucket_ladder(
                qb_hint, ",".join(str(int(b)) for b in buckets))
        if flush_interval_s is None:
            flush_interval_s = env.get(FLUSH_MS_ENV) / 1e3
        self._flush_interval_s = max(1e-4, float(flush_interval_s))
        if max_queue_rows is None:
            max_queue_rows = env.get(QUEUE_CAP_ENV)
        self._max_queue_rows = max(self._ladder[-1], int(max_queue_rows))
        if default_deadline_s is None:
            default_deadline_s = env.get(DEADLINE_ENV)
        self._default_deadline_s = default_deadline_s

        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._depth_rows = 0
        self._stop = False
        self._busy = False
        self._force_flush = False
        self._thread: Optional[threading.Thread] = None
        self._started = False
        self._latencies: collections.deque = collections.deque(
            maxlen=4096)
        self._stats = collections.Counter()
        self._next_rid = 0       # per-request flow-trace ids
        # online recall shadow-sampling (ISSUE 10): frac 0 = off;
        # constructor args win, env sets the fleet default
        self._shadow_frac = (shadow_frac_default() if shadow_frac is None
                             else max(0.0, min(1.0, float(shadow_frac))))
        self._shadow_floor = (shadow_floor_default()
                              if shadow_floor is None
                              else float(shadow_floor))
        self._shadow: Optional[ShadowSampler] = None
        # per-query explain capture (PR 16): frac 0 = off; constructor
        # wins over RAFT_TPU_EXPLAIN_FRAC; submit(explain=True) forces
        # capture for one request regardless of the fraction
        from raft_tpu.observability.explain import explain_frac_default

        self._explain_frac = (explain_frac_default()
                              if explain_frac is None
                              else max(0.0, min(1.0,
                                                float(explain_frac))))
        # windowed SLO burn-rate engine: always on (evaluation is one
        # registry snapshot per window interval); injectable for tests
        if slo is None:
            from raft_tpu.observability.slo import SloEngine

            slo = SloEngine(registry=self.res.metrics,
                            clock=self._clock)
        self._slo = slo
        # debugz server: constructor wins over RAFT_TPU_DEBUGZ_PORT
        # (0 = ephemeral port; None/unset = no server)
        if debug_port is None:
            debug_port = env.get("RAFT_TPU_DEBUGZ_PORT")
        self._debug_port = debug_port
        self._debugz = None
        # forensics plane (ISSUE 17): crash-durable blackbox + hang
        # watchdog, both defaults-off; constructor wins over
        # RAFT_TPU_BLACKBOX_PATH / RAFT_TPU_WATCHDOG_S
        self._blackbox_path = blackbox_path
        self._watchdog_s = watchdog_s
        self._blackbox = None
        self._owns_blackbox = False
        self._watchdog = None
        self._crash_report: Optional[dict] = None

    # -- construction helpers --------------------------------------------
    def _build_index(self, y):
        if self._algorithm == "ivf_pq":
            from raft_tpu.ann import build_ivf_pq

            n_lists = self._n_lists or max(
                1, min(1024, int(round(y.shape[0] ** 0.5))))
            return build_ivf_pq(self.res, y, n_lists=n_lists,
                                pq_dim=self._pq_dim,
                                pq_bits=self._pq_bits,
                                n_probes=self._n_probes)
        if self._algorithm == "ivf_flat":
            from raft_tpu.ann import build_ivf_flat

            n_lists = self._n_lists or max(
                1, min(1024, int(round(y.shape[0] ** 0.5))))
            kw = ({"db_dtype": self._db_dtype}
                  if self._db_dtype is not None else {})
            return build_ivf_flat(self.res, y, n_lists=n_lists,
                                  n_probes=self._n_probes, **kw)
        from raft_tpu.distance.knn_fused import prepare_knn_index

        return prepare_knn_index(y, **self._build_kw)

    def _plane(self, snap, xb):
        """The data plane for one padded bucket batch: the AOT runtime
        entry on one device, the PR-4 query-sharded replicated-index
        mode over the mesh, the ANN tier's IVF probe search
        (``algorithm="ivf_flat"``), or the mutable two-slab search
        (``mutable=True`` — ``snap`` is then a MutableView)."""
        if self._mutable is not None:
            from raft_tpu.mutable import MutableView, search_view

            view = (snap if isinstance(snap, MutableView)
                    else self._mutable.view())
            return search_view(self._mutable, xb, self.k, view=view,
                               n_probes=self._n_probes, res=self.res)
        if self._algorithm == "ivf_pq":
            from raft_tpu.ann import search_ivf_pq

            return search_ivf_pq(self.res, snap.index, xb, self.k,
                                 n_probes=self._n_probes)
        if self._algorithm == "ivf_flat":
            from raft_tpu.ann import search_ivf_flat

            return search_ivf_flat(self.res, snap.index, xb, self.k,
                                   n_probes=self._n_probes)
        if self._mesh is not None:
            from raft_tpu.distance.knn_sharded import knn_fused_sharded

            return knn_fused_sharded(
                xb, snap.index, self.k, mesh=self._mesh,
                axis=self._axis, shard_mode="query",
                rescore=self._rescore, certify=self._certify,
                res=self.res)
        from raft_tpu.runtime.entry_points import knn_query

        return knn_query(self.res, snap.index, xb, self.k,
                         rescore=self._rescore, certify=self._certify)

    # -- lifecycle --------------------------------------------------------
    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._ladder

    @property
    def started(self) -> bool:
        return self._started

    @property
    def slo(self):
        """The attached :class:`~raft_tpu.observability.slo.SloEngine`
        (burn-rate alerts), or None."""
        return self._slo

    def start(self) -> "ServingEngine":
        """Warm every bucket shape (AOT compile through the runtime
        entry — live requests then always hit the compile cache) and
        start the batcher thread. Idempotent."""
        with self._cond:
            if self._started:
                return self
            self._started = True
            self._stop = False
        self._boot_forensics()
        self._warm_snapshot(self._store.current())
        if self._shadow_frac > 0.0 and self._shadow is None:
            self._shadow = ShadowSampler(
                self._shadow_oracle, self.k, self._shadow_frac,
                floor=self._shadow_floor).start()
        self._thread = threading.Thread(target=self._loop,
                                        name="serving-batcher",
                                        daemon=True)
        self._thread.start()
        if self._debug_port is not None and self._debugz is None:
            from tools.debugz import DebugzServer

            self._debugz = DebugzServer(
                engine=self, port=int(self._debug_port)).start()
        if self._watchdog is not None:
            self._watchdog.start()
        return self

    def _boot_forensics(self) -> None:
        """Open the blackbox (env/constructor-gated) — surfacing and
        preserving a prior run's unclean file first — and build the
        watchdog. Never raises: forensics must not block serving."""
        from raft_tpu.observability import blackbox as blackbox_mod
        from raft_tpu.observability.watchdog import Watchdog

        try:
            booted = blackbox_mod.boot(path=self._blackbox_path)
            self._blackbox = booted.recorder
            self._owns_blackbox = booted.created
            prior = booted.prior
            if prior is not None and prior.get("verdict") != "clean":
                # the previous run died violently: keep the evidence
                # (reconstructed + preserved as <path>.prev), serve it
                # at /crashz, and count it
                self._crash_report = prior
                self.res.metrics.counter(
                    blackbox_mod.UNCLEAN_SHUTDOWNS,
                    help="Prior-run blackboxes found without an "
                         "epilogue at engine start").inc()
                log_warn("serving: prior run died unclean (verdict "
                         "%r, %d records) — postmortem at /crashz",
                         prior.get("verdict"), prior.get("records"))
            if self._blackbox is not None:
                # the run-start snapshot: the verdict floor a killed
                # process is guaranteed to leave behind
                self._blackbox.snapshot()
        except Exception:
            self._blackbox, self._owns_blackbox = None, False
        try:
            wd = Watchdog(engine=self, interval_s=self._watchdog_s)
            self._watchdog = wd if wd.enabled else None
        except Exception:
            self._watchdog = None

    def stop(self, timeout: float = 30.0) -> None:
        """Drain the queue, then stop the batcher (and the shadow
        scorer, after it drains its own queue)."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout)
        self._thread = None
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._blackbox is not None and self._owns_blackbox:
            # the epilogue: what distinguishes this stop from a kill
            from raft_tpu.observability import blackbox as blackbox_mod

            blackbox_mod.shutdown(reason="clean")
            self._blackbox, self._owns_blackbox = None, False
        if self._debugz is not None:
            self._debugz.stop()
            self._debugz = None
        if self._shadow is not None:
            self._shadow.flush(timeout=min(10.0, timeout))
            self._shadow.stop()
        if self._durable and self._mutable is not None:
            # flush + close the WAL: a clean stop is indistinguishable
            # from a crash-after-fsync to the recovery path (restart =
            # construct a durable engine over the same directory)
            self._mutable.wait_for_compaction(timeout=min(30.0, timeout))
            self._mutable.close()
        with self._cond:
            self._started = False

    def _shadow_oracle(self, x):
        """The exact reference plane the shadow sampler re-scores
        against: brute-force certified KNN over the CURRENT snapshot
        (for the IVF plane, the degenerate ``n_probes = n_lists`` exact
        search — bit-for-bit the brute oracle over the same rows). Runs
        on the shadow thread, never on the serving path."""
        if self._mutable is not None:
            from raft_tpu.mutable import search_view

            return search_view(self._mutable, x, self.k, exact=True,
                               res=self.res)
        snap = self._store.current()
        if self._algorithm == "ivf_pq":
            # degenerate n_probes = n_lists runs the certified exact
            # scan over the retained f32 slab — the brute oracle
            from raft_tpu.ann import search_ivf_pq

            return search_ivf_pq(self.res, snap.index, x, self.k,
                                 n_probes=snap.index.n_lists)
        if self._algorithm == "ivf_flat":
            from raft_tpu.ann import search_ivf_flat

            return search_ivf_flat(self.res, snap.index, x, self.k,
                                   n_probes=snap.index.n_lists)
        from raft_tpu.distance.knn_fused import knn_fused

        return knn_fused(x, snap.index, self.k)

    @property
    def shadow(self) -> Optional[ShadowSampler]:
        return self._shadow

    def _warm_snapshot(self, snap: IndexSnapshot) -> None:
        """Pre-compile every bucket shape against ``snap`` — run at
        start-up AND against a freshly rebuilt snapshot BEFORE it is
        swapped in, so a geometry-changing update cannot push a compile
        onto the request path."""
        misses0 = self.res.compile_cache.misses
        for b in self._ladder:
            x0 = np.zeros((b, self.d), np.float32)
            vals, ids = self._plane(snap, x0)
            interruptible.synchronize(vals, ids)
            if self._algorithm == "ivf_flat" and self._mutable is None:
                # the IVF fine scan has TWO schedules (ISSUE 14): the
                # bucket warmup above compiled whichever one the
                # synthetic probe pattern resolved to; pre-compile the
                # list-major programs for every schedule-cell rung this
                # bucket can reach, so a live batch whose probe pattern
                # flips the resolve_fine_scan crossover (or lands on a
                # different cell rung) never pays a compile
                from raft_tpu.ann.ivf_flat import warm_fine_scan

                warm_fine_scan(
                    self.res, snap.index, b, self.k,
                    self._n_probes or snap.index.n_probes_default)
            if self._algorithm == "ivf_pq" and self._mutable is None:
                # same bucket-ladder contract for the compressed tier:
                # warm the ADC rungs AND the flat fallback programs so
                # neither the chooser nor a certificate rerun can push
                # a compile onto a live request
                from raft_tpu.ann import warm_pq_scan

                warm_pq_scan(
                    self.res, snap.index, b, self.k,
                    self._n_probes or snap.index.n_probes_default)
            emit_serving("warmup", bucket=b, generation=snap.generation)
        self._stats["warmed_buckets"] = len(self._ladder)
        self._stats["warmup_compiles"] += (
            self.res.compile_cache.misses - misses0)

    # -- admission --------------------------------------------------------
    def submit(self, x, deadline_s: Optional[float] = None,
               explain: bool = False) -> ServingFuture:
        """Enqueue one request of [n, d] (or [d]) query rows; returns a
        :class:`ServingFuture`. Admission control happens HERE:
        oversized requests raise :class:`RequestTooLargeError`, a full
        queue raises :class:`OverloadShedError` (counted as the
        ``shed:overload`` degradation rung). Carries the
        ``serving_enqueue`` fault site.

        ``explain=True`` forces an explain record for the batch this
        request rides (otherwise a deterministic hash-sample of rids at
        ``RAFT_TPU_EXPLAIN_FRAC`` decides)."""
        fault_point("serving_enqueue")
        x = np.asarray(x, np.float32)
        if x.ndim == 1:
            x = x[None]
        expects(x.ndim == 2 and x.shape[1] == self.d,
                "serving: request must be [n, %d] query rows (got %s)",
                self.d, x.shape)
        n = x.shape[0]
        if n == 0:
            fut = ServingFuture()
            fut._complete(np.zeros((0, self.k), np.float32),
                          np.zeros((0, self.k), np.int32))
            return fut
        # flow trace: the request's journey starts HERE (client
        # thread); every admission outcome terminates the same flow id
        with self._cond:
            self._next_rid += 1
            rid = self._next_rid
        emit_flow("enqueue", rid, ph="s", rows=n)
        if n > self._ladder[-1]:
            self._count_request("rejected")
            emit_serving("reject", rows=n, top_bucket=self._ladder[-1],
                         rid=rid)
            emit_flow("reject", rid, ph="f", outcome="reject")
            raise RequestTooLargeError(
                f"serving: request of {n} rows exceeds the largest "
                f"bucket {self._ladder[-1]} — split it client-side or "
                f"raise the ladder (RAFT_TPU_SERVING_BUCKETS)")
        now = self._clock()
        budget = (deadline_s if deadline_s is not None
                  else self._default_deadline_s)
        from raft_tpu.observability import explain as explain_mod

        req = _Request(x, n, now,
                       now + budget if budget else None,
                       ServingFuture(), rid=rid,
                       explain=(bool(explain)
                                or explain_mod.want(rid,
                                                    self._explain_frac)))
        with self._cond:
            if self._depth_rows + n > self._max_queue_rows:
                self._count_request("shed")
                self._stats["shed"] += 1
                try:
                    self.res.metrics.counter(
                        SHED, help="Requests shed by admission control "
                                   "(queue at its row cap)").inc()
                except Exception:
                    pass
                record_degradation("serving.engine", "shed:overload")
                emit_serving("shed", rows=n,
                             queue_rows=self._depth_rows, rid=rid)
                emit_flow("shed", rid, ph="f", outcome="shed")
                raise OverloadShedError(
                    f"serving: queue at capacity "
                    f"({self._depth_rows}/{self._max_queue_rows} rows)"
                    f" — request shed; back off and retry")
            self._queue.append(req)
            self._depth_rows += n
            self._gauge_depth()
            emit_serving("enqueue", rows=n,
                         queue_rows=self._depth_rows,
                         deadline_s=budget, rid=rid)
            self._cond.notify_all()
        return req.future

    def query(self, x, deadline_s: Optional[float] = None,
              timeout: Optional[float] = 60.0
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking convenience: submit + wait."""
        return self.submit(x, deadline_s=deadline_s).result(timeout)

    # -- mutations (mutable=True) ------------------------------------------
    def _submit_mutation(self, kind: str, ids, rows,
                         deadline_s: Optional[float]) -> ServingFuture:
        """Enqueue one mutation request — the SAME pipe as queries:
        admission control (queue row cap sheds, an upsert past the
        delta capacity is rejected classified), FIFO ordering with the
        queries around it, per-request deadline scopes on the batcher
        thread, and flow tracing end to end."""
        from raft_tpu.core.error import expects as _expects

        _expects(self._mutable is not None,
                 "serving: %s() needs a mutable engine "
                 "(ServingEngine(..., mutable=True))", kind)
        fault_point("serving_enqueue")
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        if rows is not None:
            rows = np.asarray(rows, np.float32)
            if rows.ndim == 1:
                rows = rows[None]
            _expects(rows.ndim == 2 and rows.shape[1] == self.d,
                     "serving: %s rows must be [n, %d] (got %s)", kind,
                     self.d, rows.shape)
            _expects(ids.shape[0] == rows.shape[0],
                     "serving: %s ids/rows length mismatch", kind)
        n = int(ids.shape[0])
        if n == 0:
            fut = ServingFuture()
            fut._complete({"applied": 0, "kind": kind}, None)
            return fut
        with self._cond:
            self._next_rid += 1
            rid = self._next_rid
        emit_flow("enqueue", rid, ph="s", rows=n, op=kind)
        if rows is not None and n > self._mutable.delta_cap:
            self._count_request("rejected")
            emit_serving("reject", rows=n, op=kind, rid=rid,
                         delta_cap=self._mutable.delta_cap)
            emit_flow("reject", rid, ph="f", outcome="reject")
            raise RequestTooLargeError(
                f"serving: upsert of {n} rows exceeds the delta "
                f"capacity {self._mutable.delta_cap} — split it or "
                f"raise RAFT_TPU_DELTA_CAP")
        now = self._clock()
        budget = (deadline_s if deadline_s is not None
                  else self._default_deadline_s)
        req = _Request(rows, n, now, now + budget if budget else None,
                       ServingFuture(), rid=rid, kind=kind, ids=ids)
        with self._cond:
            if self._depth_rows + n > self._max_queue_rows:
                self._count_request("shed")
                self._stats["shed"] += 1
                try:
                    self.res.metrics.counter(
                        SHED, help="Requests shed by admission control "
                                   "(queue at its row cap)").inc()
                except Exception:
                    pass
                record_degradation("serving.engine", "shed:overload")
                emit_serving("shed", rows=n, op=kind,
                             queue_rows=self._depth_rows, rid=rid)
                emit_flow("shed", rid, ph="f", outcome="shed")
                raise OverloadShedError(
                    f"serving: queue at capacity "
                    f"({self._depth_rows}/{self._max_queue_rows} rows)"
                    f" — {kind} shed; back off and retry")
            self._queue.append(req)
            self._depth_rows += n
            self._gauge_depth()
            emit_serving("enqueue", rows=n, op=kind,
                         queue_rows=self._depth_rows,
                         deadline_s=budget, rid=rid)
            self._cond.notify_all()
        return req.future

    def upsert(self, ids, rows, deadline_s: Optional[float] = None
               ) -> ServingFuture:
        """Enqueue an upsert of ``rows`` [n, d] under external ``ids``
        [n] (mutable engines). The future resolves to a dict with the
        applied count and the index seq/generation once the batcher
        applies it — strictly ordered against the queries around it."""
        return self._submit_mutation("upsert", ids, rows, deadline_s)

    def delete(self, ids, deadline_s: Optional[float] = None
               ) -> ServingFuture:
        """Enqueue a delete of external ``ids`` (mutable engines) —
        visible to every query batch dispatched after it."""
        return self._submit_mutation("delete", ids, None, deadline_s)

    # -- index updates ----------------------------------------------------
    @property
    def mutable(self):
        """The engine's MutableIndex (None on immutable engines)."""
        return self._mutable

    @property
    def recovery(self):
        """Stats of the startup crash recovery this engine performed
        (None when it cold-started — a fresh durable dir or
        durable=False)."""
        return dict(self._recovery) if self._recovery else None

    def update_index(self, y, block: bool = False):
        """Rebuild the index from ``y`` and swap it in — in the
        background by default; queries keep hitting the current
        snapshot until the new one is built AND pre-warmed (every
        bucket compiled against the new geometry before the swap), so
        readers never block and never pay a compile."""
        expects(self._mutable is None,
                "serving: a mutable engine updates through upsert()/"
                "delete() (compaction folds the delta in the "
                "background) — update_index is the immutable path")
        y = np.asarray(y, np.float32)
        expects(y.ndim == 2 and y.shape[1] == self.d,
                "serving: replacement index must be [m, %d] (got %s)",
                self.d, y.shape)
        expects(self.k <= y.shape[0],
                "serving: k=%d > replacement index size %d", self.k,
                y.shape[0])
        store = self._store

        def _builder(yy, **kw):
            idx = self._build_index(yy)
            if self._started:
                # pre-swap warm on a TEMP snapshot (generation stamped
                # by the store when it swaps)
                self._warm_snapshot(IndexSnapshot(idx, -1))
            return idx

        prev_builder = store._builder
        store._builder = _builder
        try:
            return store.update(y, block=block)
        finally:
            if block:
                store._builder = prev_builder

    @property
    def snapshot(self) -> IndexSnapshot:
        return self._store.current()

    # -- metrics helpers --------------------------------------------------
    def _count_request(self, status: str) -> None:
        self._stats[f"requests_{status}"] += 1
        try:
            self.res.metrics.counter(
                REQUESTS, {"status": status},
                help="Serving requests by terminal status").inc()
        except Exception:
            pass

    def _gauge_depth(self) -> None:
        try:
            self.res.metrics.gauge(
                QUEUE_DEPTH, help="Query rows currently queued"
            ).set(self._depth_rows)
        except Exception:
            pass

    def _observe_latency(self, seconds: float) -> None:
        self._latencies.append(seconds)
        try:
            self.res.metrics.histogram(
                LATENCY, help="End-to-end request latency (enqueue → "
                              "completion)").observe(seconds)
        except Exception:
            pass

    def _observe_queue_wait(self, reqs, now: float) -> None:
        if not reqs:
            return
        try:
            hist = self.res.metrics.histogram(
                QUEUE_WAIT, help="Time a dispatched request waited in "
                                 "the queue (enqueue → batch pop)")
            for req in reqs:
                hist.observe(max(0.0, now - req.enqueued_at))
        except Exception:
            pass

    def stats(self) -> dict:
        """Live counters + latency percentiles (engine-side; the
        BENCH_SERVING artifact measures client-side). Percentiles use
        the shared interpolating :func:`~raft_tpu.observability.
        metrics.percentile` (the old index pick reported the max for
        small windows)."""
        with self._cond:
            out = dict(self._stats)
            out["queue_rows"] = self._depth_rows
            lat = list(self._latencies)
        if lat:
            out["p50_ms"] = 1e3 * percentile(lat, 50)
            out["p99_ms"] = 1e3 * percentile(lat, 99)
        out["generation"] = self._store.generation
        out["compile_misses"] = self.res.compile_cache.misses
        out["buckets"] = self._ladder
        if self._mutable is not None:
            out["mutable"] = self._mutable.stats()
            if self._mutable.durability is not None:
                out["durability"] = self._mutable.durability.stats()
        if self._recovery is not None:
            out["recovery"] = dict(self._recovery)
        if self._shadow is not None:
            out.update(self._shadow.snapshot())
        if self._slo is not None:
            try:
                out["slo"] = self._slo.status()
            except Exception:
                pass
        from raft_tpu.observability.explain import explain_records

        out["explain"] = {"frac": self._explain_frac,
                          "records": len(explain_records())}
        if self._debugz is not None:
            out["debugz_port"] = self._debugz.port
        if self._blackbox is not None:
            out["blackbox"] = self._blackbox.stats()
        if self._watchdog is not None:
            out["watchdog"] = self._watchdog.stats()
        if self._crash_report is not None:
            out["prior_crash"] = {
                "verdict": self._crash_report.get("verdict"),
                "records": self._crash_report.get("records"),
                "preserved_path":
                    self._crash_report.get("preserved_path")}
        return out

    @property
    def crash_report(self) -> Optional[dict]:
        """The prior run's postmortem reconstruction when this engine's
        start() found an epilogue-less blackbox (else None) — the
        /crashz body."""
        return self._crash_report

    @property
    def blackbox(self):
        """The installed crash-durable recorder, or None."""
        return self._blackbox

    def inflight_requests(self) -> List[dict]:
        """Snapshot of queued requests (age, remaining deadline) — the
        watchdog's stall evidence and the blackbox's in-flight table.
        Takes the cond only long enough to copy the queue."""
        with self._cond:
            reqs = list(self._queue)
            busy = self._busy
        now = self._clock()
        out = [{"rid": r.rid, "kind": r.kind, "rows": r.n,
                "age_s": round(now - r.enqueued_at, 6),
                "deadline_in_s": (round(r.deadline_at - now, 6)
                                  if r.deadline_at is not None
                                  else None)}
               for r in reqs]
        if busy:
            out.append({"rid": None, "kind": "dispatch", "rows": 0,
                        "age_s": 0.0, "deadline_in_s": None})
        return out

    # the name the quality-telemetry plane documents; same snapshot
    snapshot_stats = stats

    # -- the batcher ------------------------------------------------------
    def flush(self, timeout: float = 30.0) -> bool:
        """Force-drain the queue; returns True once empty and idle.
        The deterministic lever tests and benchmarks use instead of
        sleeping through flush windows."""
        t_end = time.monotonic() + timeout
        with self._cond:
            self._force_flush = True
            self._cond.notify_all()
            while ((self._queue or self._busy)
                   and time.monotonic() < t_end):
                self._cond.wait(0.01)
            drained = not self._queue and not self._busy
            self._force_flush = False
            return drained

    def _pop_batch_locked(self):
        """Assemble the next batch under the lock: greedy pops up to
        the top bucket, failing queue-expired requests on the way (the
        admission half of the deadline contract — an expired request
        never wastes a dispatch)."""
        now = self._clock()
        batch = []
        total = 0
        expired = []
        mutation = None
        while self._queue:
            req = self._queue[0]
            if req.deadline_at is not None and req.deadline_at <= now:
                self._queue.popleft()
                self._depth_rows -= req.n
                expired.append(req)
                continue
            if req.kind != "query":
                # a mutation is a strict ordering barrier: queries
                # ahead of it dispatch first (this batch), the mutation
                # runs alone next, queries behind it see its effect
                if batch:
                    break
                self._queue.popleft()
                self._depth_rows -= req.n
                mutation = req
                break
            if total + req.n > self._ladder[-1]:
                break
            self._queue.popleft()
            self._depth_rows -= req.n
            batch.append(req)
            total += req.n
        self._gauge_depth()
        self._observe_queue_wait(
            batch + ([mutation] if mutation is not None else []), now)
        return batch, total, expired, mutation

    def _flush_due_locked(self) -> bool:
        """The queue holds a batch to dispatch now: a forced flush, a
        full top bucket, or an oldest request that has waited the flush
        interval."""
        return (self._force_flush
                or sum(r.n for r in self._queue) >= self._ladder[-1]
                or self._clock() - self._queue[0].enqueued_at
                >= self._flush_interval_s)

    def _fail_expired(self, expired) -> None:
        for req in expired:
            self._count_request("deadline")
            self._stats["expired_in_queue"] += 1
            emit_flow("expire", req.rid, ph="f", outcome="expired")
            req.future._fail(DeadlineExceededError(
                "serving: request deadline expired while queued",
                seconds=(req.deadline_at - req.enqueued_at
                         if req.deadline_at else None)))

    def _loop(self) -> None:
        while True:
            with self._cond:
                while True:
                    if self._stop:
                        break
                    if self._queue:
                        if self._flush_due_locked():
                            break
                        # the oldest request waits for co-riders: timed
                        # from the first look that finds it not yet due
                        # until the batch is due
                        with span("serving.flush_wait"):
                            while (not self._stop and self._queue
                                   and not self._flush_due_locked()):
                                self._cond.wait(self._flush_interval_s / 2)
                    else:
                        # empty-queue flush timer tick: nothing to
                        # dispatch — the timer is a no-op, not a batch
                        self._cond.wait(self._flush_interval_s)
                        if self._slo is not None:
                            # break out so the SLO tick runs OUTSIDE
                            # the cond lock (it snapshots the registry)
                            break
                if self._stop and not self._queue:
                    self._busy = False
                    self._cond.notify_all()
                    return
                batch, total, expired, mutation = \
                    self._pop_batch_locked()
                self._busy = bool(batch) or mutation is not None
            wd = self._watchdog
            if wd is not None:
                # liveness heartbeat, OUTSIDE the cond (one dict store)
                wd.beat()
            bb = self._blackbox
            if bb is not None:
                # rate-limited (snapshot_interval_s): most calls are
                # one clock read; keeps the "final metrics snapshot"
                # fresh even when no watchdog ticks
                bb.maybe_snapshot()
            self._fail_expired(expired)
            if batch or mutation is not None:
                try:
                    if batch:
                        self._run_batch(batch, total)
                    if mutation is not None:
                        self._run_mutation(mutation)
                finally:
                    with self._cond:
                        self._busy = False
                        self._cond.notify_all()
            if self._slo is not None:
                # self-rate-limited (MetricWindows.interval_s): most
                # calls are one clock read; never raises
                self._slo.tick()

    def _run_batch(self, batch, total: int) -> None:
        # ONE snapshot/view per batch — every rider sees one index
        snap = (self._mutable.view() if self._mutable is not None
                else self._store.current())
        bucket = bucket_for(total, self._ladder)
        x = (batch[0].x if len(batch) == 1
             else np.concatenate([r.x for r in batch], axis=0))
        now = self._clock()
        budgets = [r.deadline_at - now for r in batch
                   if r.deadline_at is not None]
        budget = min(budgets) if budgets else None
        if budget is not None and budget <= 0:
            # raced to expiry between assembly and dispatch
            self._fail_expired([r for r in batch
                                if r.deadline_at is not None
                                and r.deadline_at <= now])
            batch = [r for r in batch
                     if r.deadline_at is None or r.deadline_at > now]
            if not batch:
                return
            return self._run_batch(batch, sum(r.n for r in batch))
        self._stats["batches"] += 1
        self._stats["padded_rows"] += bucket - total
        # flow trace: each rider steps onto the batcher thread (batch
        # assembly), then through the dispatch — the t points connect
        # the client-thread `s` to the terminus across lanes
        for req in batch:
            emit_flow("batch", req.rid, ph="t", bucket=bucket,
                      riders=len(batch))
        try:
            self.res.metrics.counter(
                BATCHES, {"bucket": str(bucket)},
                help="Dispatched micro-batches by bucket").inc()
            self.res.metrics.counter(
                BATCH_PAD_ROWS,
                help="Pad rows dispatched (bucket − real rows)"
            ).inc(bucket - total)
        except Exception:
            pass
        for req in batch:
            emit_flow("dispatch", req.rid, ph="t",
                      generation=snap.generation)
        from raft_tpu.observability import explain as explain_mod

        # explain capture spans the dispatch: any flagged rider opens
        # one record for the whole batch (the plane/margin notes land
        # in it from the kernels below); begin_capture returns None
        # when no rider is flagged, and every hook no-ops then
        cap = (explain_mod.begin_capture([r.rid for r in batch])
               if any(r.explain for r in batch) else None)
        try:
            vals, ids = execute_batch(self._plane, snap, x, bucket,
                                      total, budget)
        except DeadlineExceededError as e:
            explain_mod.end_capture(cap, outcome="deadline",
                                    bucket=bucket, riders=len(batch))
            self._on_batch_deadline(batch, e)
            return
        except Exception as e:
            explain_mod.end_capture(cap, outcome="error",
                                    bucket=bucket, riders=len(batch))
            for req in batch:
                self._count_request("error")
                emit_flow("fail", req.rid, ph="f", outcome="error")
                req.future._fail(e)
            return
        off = 0
        done = self._clock()
        for req in batch:
            req.future._complete(vals[off:off + req.n],
                                 ids[off:off + req.n])
            emit_flow("response", req.rid, ph="f", outcome="ok")
            if self._shadow is not None and self._shadow.want(req.rid):
                # off the hot path: queue (request, served ids) for the
                # background oracle re-score; a full shadow queue drops
                # the sample, never blocks the batcher
                self._shadow.submit(req.rid, req.x,
                                    np.asarray(ids[off:off + req.n]))
            off += req.n
            self._count_request("ok")
            self._observe_latency(max(0.0, done - req.enqueued_at))
        explain_mod.end_capture(cap, outcome="ok", bucket=bucket,
                                rows=total, riders=len(batch),
                                generation=snap.generation)

    def _run_mutation(self, req) -> None:
        """Apply ONE mutation request on the batcher thread, inside its
        own deadline scope — the write half of the serving contract:
        strictly ordered against query batches, never concurrent with a
        dispatch, and an expired/hung apply fails typed exactly like a
        query batch would."""
        from raft_tpu.mutable import apply_delete, apply_upsert

        now = self._clock()
        budget = (req.deadline_at - now if req.deadline_at is not None
                  else None)
        if budget is not None and budget <= 0:
            self._fail_expired([req])
            return
        emit_flow("dispatch", req.rid, ph="t", op=req.kind)
        emit_serving("mutate", op=req.kind, rows=req.n, rid=req.rid,
                     budget_s=budget)
        self._stats[f"{req.kind}s"] += 1

        def _apply():
            if req.kind == "upsert":
                return apply_upsert(self._mutable, req.ids, req.x)
            return apply_delete(self._mutable, req.ids)

        try:
            if budget is not None:
                with deadline(budget, label="serving_mutation"):
                    applied = _apply()
            else:
                applied = _apply()
        except DeadlineExceededError as e:
            self._count_request("deadline")
            emit_flow("fail", req.rid, ph="f", outcome="deadline")
            req.future._fail(e)
            return
        except Exception as e:
            self._count_request("error")
            emit_flow("fail", req.rid, ph="f", outcome="error")
            req.future._fail(e)
            return
        done = self._clock()
        emit_flow("response", req.rid, ph="f", outcome="ok")
        self._count_request("ok")
        self._observe_latency(max(0.0, done - req.enqueued_at))
        req.future._complete(
            {"kind": req.kind, "applied": int(applied),
             "seq": self._mutable.seq,
             "generation": self._mutable.generation}, None)

    def _on_batch_deadline(self, batch, err: DeadlineExceededError
                           ) -> None:
        """A batch deadline fired: requests whose OWN budget expired
        fail with the deadline error; riders that still have budget are
        re-queued once (at the head — they have waited longest) and
        fail honestly on a second strike."""
        now = self._clock()
        requeue = []
        for req in batch:
            if req.deadline_at is not None and req.deadline_at <= now:
                self._count_request("deadline")
                emit_flow("fail", req.rid, ph="f", outcome="deadline")
                req.future._fail(err)
            elif req.requeues >= _MAX_REQUEUES:
                self._count_request("error")
                emit_flow("fail", req.rid, ph="f", outcome="error")
                req.future._fail(err)
            else:
                req.requeues += 1
                emit_flow("requeue", req.rid, ph="t",
                          outcome="requeue", attempt=req.requeues)
                requeue.append(req)
        if requeue:
            self._stats["requeued"] += len(requeue)
            with self._cond:
                for req in reversed(requeue):
                    self._queue.appendleft(req)
                    self._depth_rows += req.n
                self._gauge_depth()
                self._cond.notify_all()
