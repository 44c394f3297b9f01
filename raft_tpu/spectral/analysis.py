"""Partition / modularity analysis + spectral embedding.

(ref: cpp/include/raft/spectral/partition.cuh:38 ``analyzePartition``
(edge-cut + cost via indicator vectors, detail/partition.hpp:81-85),
modularity_maximization.cuh:31 ``analyzeModularity``. The eigensolver+
kmeans *clustering* driver left for cuVS; what remains — and is rebuilt
here — is the analysis plus the BASELINE "spectral embedding" pipeline:
``compute_graph_laplacian`` + ``lanczos_compute_eigenpairs`` (SURVEY §2.6).)
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp

from raft_tpu.core.sparse_types import COOMatrix, CSRMatrix
from raft_tpu.spectral.matrix_wrappers import LaplacianMatrix, ModularityMatrix

Sparse = Union[COOMatrix, CSRMatrix]


def analyze_partition(res, A: Sparse, n_clusters: int, clusters
                      ) -> Tuple[float, float]:
    """Returns (edge_cut, cost); cost = Σ_i cut(i)/|cluster_i|.
    (ref: spectral/partition.cuh:38 ``analyzePartition``)"""
    clusters = jnp.asarray(clusters)
    L = LaplacianMatrix(res, A)
    dtype = L.diagonal.dtype
    edge_cut = jnp.asarray(0.0, dtype)
    cost = jnp.asarray(0.0, dtype)
    for i in range(n_clusters):
        w = (clusters == i).astype(dtype)
        size = jnp.sum(w)
        part_cut = jnp.dot(w, L.mv(w))
        nonempty = size > 0
        cost = cost + jnp.where(nonempty, part_cut / jnp.where(nonempty, size, 1.0), 0.0)
        edge_cut = edge_cut + jnp.where(nonempty, part_cut / 2.0, 0.0)
    return float(edge_cut), float(cost)


def analyze_modularity(res, A: Sparse, n_clusters: int, clusters) -> float:
    """Modularity = Σ_i w_iᵀ B w_i / ‖d‖₁.
    (ref: modularity_maximization.cuh:31 ``analyzeModularity``;
    detail normalizes by the L1 norm of the degree vector = 2m.)"""
    clusters = jnp.asarray(clusters)
    B = ModularityMatrix(res, A)
    dtype = B.degree.dtype
    total = jnp.asarray(0.0, dtype)
    for i in range(n_clusters):
        w = (clusters == i).astype(dtype)
        total = total + jnp.dot(w, B.mv(w))
    return float(total / B.edge_sum)


def fit_embedding(res, A: Sparse, n_components: int, ncv=None,
                  tolerance: float = 1e-5, max_iterations: int = 2000,
                  seed: int = 42, drop_first: bool = True,
                  normalized: bool = True, jit_loop=None,
                  tiled="auto", mesh=None, mesh_axis: str = "x"):
    """Spectral embedding: smallest eigenvectors of the graph Laplacian.

    The BASELINE config-4 pipeline (COO Laplacian + Lanczos). Returns
    (eigenvalues, embedding [n, n_components]).

    ``tiled``: "auto" converts the Laplacian to the tiled-ELL layout
    (one-time host pass) so the Lanczos hot loop runs the Pallas SpMV
    kernel — on TPU, for graphs past ~200k nonzeros; True/False force
    either path.

    ``mesh``: a ``jax.sharding.Mesh`` makes the solve MNMG — the
    Laplacian's rows are sharded over ``mesh[mesh_axis]`` and the
    Lanczos matvec runs as a ``shard_map`` of the per-block Pallas
    SpMV (sparse/sharded.py; the reference's comms-injected MNMG
    pipeline — core/comms.hpp:234 usage model). Results match the
    single-device solve (tested on the 8-device virtual mesh).
    """
    from raft_tpu.sparse.linalg import (
        compute_graph_laplacian, laplacian_normalized, prepare_spmv)
    from raft_tpu.sparse.solver.lanczos import lanczos_compute_eigenpairs
    from raft_tpu.sparse.solver.lanczos_types import LANCZOS_WHICH, LanczosSolverConfig

    k = n_components + (1 if drop_first else 0)
    if normalized:
        L, _ = laplacian_normalized(res, A)
    else:
        L = compute_graph_laplacian(res, A)
    if tiled not in ("auto", True, False):
        raise ValueError(
            f"fit_embedding: tiled must be 'auto', True or False, "
            f"got {tiled!r}")
    if mesh is not None:
        from raft_tpu.sparse.sharded import shard_spmv_operand

        if tiled is False:
            raise ValueError(
                "fit_embedding: tiled=False conflicts with mesh= — the "
                "MNMG path IS the sharded tiled-ELL operand")
        if L.values.dtype == jnp.float64:
            raise ValueError(
                "fit_embedding: mesh= computes in f32 (tiled kernels); "
                "cast the input or drop mesh for the f64 CSR path")
        L = shard_spmv_operand(L, mesh, axis=mesh_axis)
    else:
        if tiled == "auto":
            # f64 inputs stay on the CSR path (the tiled kernel computes
            # in f32 — see the dtype policy in linalg.spmm's docstring)
            tiled = (jax.default_backend() == "tpu" and L.nnz >= 200_000
                     and L.values.dtype == jnp.float32)
        if tiled:
            L = prepare_spmv(L)
    # jit_loop=True compiles the whole solve into one program; the
    # host loop (default) keeps cancellation
    # points and the stagnation early-exit for large zero clusters
    config = LanczosSolverConfig(
        n_components=k, max_iterations=max_iterations, ncv=ncv,
        tolerance=tolerance, which=LANCZOS_WHICH.SA, seed=seed,
        jit_loop=jit_loop)
    vals, vecs = lanczos_compute_eigenpairs(res, L, config)
    if drop_first:
        return vals[1:], vecs[:, 1:]
    return vals, vecs
