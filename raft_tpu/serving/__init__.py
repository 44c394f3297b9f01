"""raft_tpu.serving — the online micro-batching query engine (ISSUE 7).

The production front door over the KNN stack: a thread-safe request
queue coalescing arriving queries into dynamic micro-batches padded to
a small pre-AOT-compiled bucket ladder (no request ever pays a
trace/compile after warm-up), per-request admission control + deadline
scopes reusing the resilience runtime (overload SHEDS instead of
queueing unboundedly), immutable index snapshots with background
rebuild-and-swap, and the PR-4 query-sharded replicated-index mode as
the multi-chip data plane.

- :class:`~raft_tpu.serving.engine.ServingEngine` — the engine.
- :mod:`~raft_tpu.serving.buckets` — the bucket ladder
  (``RAFT_TPU_SERVING_BUCKETS``).
- :mod:`~raft_tpu.serving.snapshot` — immutable snapshots +
  :class:`~raft_tpu.serving.snapshot.SnapshotStore`.

SLO evidence: the ``sift1m-ivf.single`` benchmark cell
(``bench_suite/``) drives one-row requests through the engine and
reports p50/p95 latency and throughput.
"""

from raft_tpu.serving.buckets import (bucket_for, bucket_ladder,
                                      default_bucket_ladder)
from raft_tpu.serving.engine import (BATCHES, LATENCY, QUEUE_DEPTH,
                                     REQUESTS, SHED, OverloadShedError,
                                     RequestTooLargeError, ServingEngine,
                                     ServingFuture, execute_batch)
from raft_tpu.serving.snapshot import (IndexSnapshot, SnapshotStore,
                                       build_snapshot)

__all__ = [
    "BATCHES",
    "LATENCY",
    "QUEUE_DEPTH",
    "REQUESTS",
    "SHED",
    "IndexSnapshot",
    "OverloadShedError",
    "RequestTooLargeError",
    "ServingEngine",
    "ServingFuture",
    "SnapshotStore",
    "bucket_for",
    "bucket_ladder",
    "build_snapshot",
    "default_bucket_ladder",
    "execute_batch",
]
