"""raft_tpu.mutable — streaming upserts/deletes over immutable bases.

The mutation plane (ROADMAP item 3): every index gets an append delta
slab (8-row quantum, quantized/certified on ingest), tombstone bitmaps
applied through the ragged never-wins sentinel path (a delete is
visible on the next batch without touching the slab), a two-slab
search merged with the PR-4 rank-ordered merge, and a background
compactor that folds deltas past ``RAFT_TPU_COMPACT_THRESHOLD`` into a
fresh snapshot through the existing warmed rebuild-and-swap — readers
never block, generation semantics stay last-wins.

- :class:`~raft_tpu.mutable.index.MutableIndex` — the mutation plane
  (brute f32 / brute int8 / IVF-Flat bases).
- :mod:`~raft_tpu.mutable.layout` — :class:`IndexLayout`, the explicit
  slab struct (slab, ids, offsets/sizes, rows_valid, int8 sidecar)
  shared by the brute, IVF-Flat and quantized planes, with the
  build/search machinery re-expressed as pure ops over it.

Durability (ISSUE 12): :mod:`~raft_tpu.mutable.wal` (segmented
write-ahead log — framed records, CRC trailers, group-commit fsync,
torn-tail truncation) + :mod:`~raft_tpu.mutable.checkpoint` (atomic
manifest-verified checkpoints, two-phase ``CURRENT`` commit, and
:func:`~raft_tpu.mutable.checkpoint.recover` = newest-valid-checkpoint
load + WAL tail replay). ``MutableIndex(durable_dir=...)`` /
``ServingEngine(durable=True)`` turn it on; acked writes then survive
SIGKILL at any instruction boundary (the crash matrix in
tests/test_durability.py).
"""

from raft_tpu.mutable.checkpoint import (CheckpointStore,
                                         DurabilityPlane,
                                         has_durable_state,
                                         last_recovery, recover)
from raft_tpu.mutable.index import (COMPACT_THRESHOLD_ENV,
                                    DELTA_CAP_ENV, MutableIndex,
                                    MutableView, apply_delete,
                                    apply_upsert,
                                    compact_threshold_default,
                                    delta_cap_default, search_view)
from raft_tpu.mutable.wal import (OP_CHECKPOINT, OP_DELETE, OP_UPSERT,
                                  WalRecord, WalWriter,
                                  replay as wal_replay)
from raft_tpu.mutable.layout import (FusedOps, IndexLayout, dense_layout,
                                     fused_geometry, fused_ops_for_layout,
                                     quantize_layout,
                                     ragged_layout_from_lists,
                                     run_fused_ops)

__all__ = [
    "COMPACT_THRESHOLD_ENV",
    "CheckpointStore",
    "DELTA_CAP_ENV",
    "DurabilityPlane",
    "FusedOps",
    "IndexLayout",
    "MutableIndex",
    "MutableView",
    "OP_CHECKPOINT",
    "OP_DELETE",
    "OP_UPSERT",
    "WalRecord",
    "WalWriter",
    "apply_delete",
    "apply_upsert",
    "compact_threshold_default",
    "delta_cap_default",
    "dense_layout",
    "fused_geometry",
    "fused_ops_for_layout",
    "has_durable_state",
    "last_recovery",
    "quantize_layout",
    "ragged_layout_from_lists",
    "recover",
    "run_fused_ops",
    "search_view",
    "wal_replay",
]
