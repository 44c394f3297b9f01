"""raft_tpu — a TPU-native primitives framework with the capabilities of
rapidsai/raft, built from scratch on JAX/XLA/Pallas/pjit.

The reference (mounted at /root/reference, v26.08.00) is a CUDA/C++ header
library; this package is NOT a port of it. It re-designs the same capability
surface TPU-first:

- ``raft_tpu.core``     — resources registry / handle system, mdarray-style
  data layer over ``jax.Array``, bitset/bitmap, serialization, logging,
  tracing, cooperative interruption.  (ref: cpp/include/raft/core)
- ``raft_tpu.linalg``   — dense linear algebra: map/reduce, norms, BLAS,
  QR/eig/SVD, randomized SVD, least squares, PCA/TSVD.
  (ref: cpp/include/raft/linalg)
- ``raft_tpu.matrix``   — matrix manipulation + batched ``select_k`` top-k.
  (ref: cpp/include/raft/matrix)
- ``raft_tpu.sparse``   — COO/CSR formats, sparse linalg, Lanczos /
  randomized-SVD / MST solvers.  (ref: cpp/include/raft/sparse)
- ``raft_tpu.spectral`` — graph Laplacian / modularity analysis + embedding.
- ``raft_tpu.solver``   — linear assignment.  (ref: cpp/include/raft/solver)
- ``raft_tpu.label``    — label compaction / merging.
- ``raft_tpu.random``   — counter-based device RNG + dataset generators.
- ``raft_tpu.stats``    — statistics and model metrics.
- ``raft_tpu.distance`` — pairwise distances + fused L2 nearest-neighbor
  (pre-cuVS RAFT surface, rebuilt TPU-first).
- ``raft_tpu.comms``    — the NCCL/UCX ``comms_t`` vocabulary re-imagined
  over ``jax.lax`` collectives on a device mesh (ICI/DCN).
- ``raft_tpu.parallel`` — mesh/sharding helpers, multi-host session.
- ``raft_tpu.models``   — estimator-style wrappers (PCA, TSVD, spectral
  embedding, brute-force KNN).
- ``raft_tpu.ops``      — Pallas TPU kernels for the hot paths.
- ``raft_tpu.observability`` — unified metrics + span tracing (counters/
  gauges/histograms, nvtx-attributed spans, Prometheus/JSONL exporters).
  (ref: core/nvtx.hpp + mr/resource_monitor.hpp, unified)
"""

from raft_tpu.version import __version__

from raft_tpu.core import (
    Resources,
    DeviceResources,
    device_resources,
)

__all__ = [
    "__version__",
    "Resources",
    "DeviceResources",
    "device_resources",
]
