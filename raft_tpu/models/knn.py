"""Brute-force nearest neighbors estimator — the flagship compute path
(fused distance + top-k; BASELINE config 2). (ref: the pre-cuVS
brute_force knn surface.)"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.distance.fused_l2nn import knn as _knn


class NearestNeighbors:
    def __init__(self, n_neighbors: int = 5, metric: str = "sqeuclidean",
                 mesh=None, mesh_axis: str = "x",
                 n_shards: Optional[int] = None,
                 merge: str = "auto",
                 algorithm: str = "brute",
                 n_lists: Optional[int] = None,
                 n_probes: Optional[int] = None,
                 pq_dim: Optional[int] = None,
                 pq_bits: Optional[int] = None,
                 res: Optional[Resources] = None):
        """``mesh``: a ``jax.sharding.Mesh`` makes ``kneighbors`` MNMG
        — the INDEX rows shard over ``mesh[mesh_axis]`` (the
        bigger-than-HBM index mode: per-shard local select + one
        all-gather merge; distance.knn_index_sharded).

        ``n_shards``: shard the index over that many devices through
        the CERTIFIED sharded fused pipeline
        (:func:`raft_tpu.distance.knn_fused_sharded` — per-shard
        stream-once fused kernel + the ``merge`` strategy: "auto" picks
        the ICI cost-model crossover between the allgather and
        tournament merges). Falls back to the streamed
        ``knn_index_sharded`` path for metrics outside the fused
        envelope. Default (both None) keeps the current single-device
        behavior.

        ``algorithm="ivf_flat"`` switches ``fit`` to building an
        IVF-Flat index (:func:`raft_tpu.ann.build_ivf_flat` — balanced
        k-means coarse quantizer + padded ragged inverted lists) and
        ``kneighbors`` to the approximate probe search with
        ``n_probes`` lists per query (``n_probes = n_lists`` degrades
        to exact — the degenerate-exact invariant). L2-family metrics
        only; the default ``"brute"`` keeps every existing path
        unchanged. With ``n_shards``, the lists distribute over the
        mesh (:func:`raft_tpu.ann.shard_ivf_lists`) and per-shard
        top-k candidates merge with the ``merge`` strategy.

        ``algorithm="ivf_pq"`` is the compressed tier
        (:func:`raft_tpu.ann.build_ivf_pq` — per-subspace product-
        quantized codes over the same inverted lists, ~16–32× fewer
        streamed bytes, every returned candidate exact-rescored from
        the retained f32 slab): ``pq_dim`` subspaces of ``pq_bits``-
        bit codes (defaults d/4 and ``RAFT_TPU_ANN_PQ_BITS``).
        Single-device; L2 family only."""
        if algorithm not in ("brute", "ivf_flat", "ivf_pq"):
            raise ValueError(
                f"NearestNeighbors: algorithm must be 'brute', "
                f"'ivf_flat' or 'ivf_pq', got {algorithm!r}")
        if algorithm in ("ivf_flat", "ivf_pq") and metric not in (
                "sqeuclidean", "euclidean", "l2"):
            raise ValueError(
                f"NearestNeighbors: algorithm={algorithm!r} serves "
                f"the L2 family only, got metric={metric!r}")
        if algorithm == "ivf_pq" and n_shards is not None:
            raise ValueError(
                "NearestNeighbors: algorithm='ivf_pq' is single-device"
                " (shard the flat tier via algorithm='ivf_flat')")
        self.res = ensure_resources(res)
        self.n_neighbors = n_neighbors
        self.metric = metric
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.merge = merge
        self.algorithm = algorithm
        self.n_lists = n_lists
        self.n_probes = n_probes
        self.pq_dim = pq_dim
        self.pq_bits = pq_bits
        if n_shards is not None and mesh is None:
            import jax

            from raft_tpu.parallel import make_mesh

            devs = jax.devices()
            if n_shards > len(devs):
                raise ValueError(
                    f"NearestNeighbors: n_shards={n_shards} > "
                    f"{len(devs)} available devices")
            mesh_axis = "x"
            self.mesh_axis = mesh_axis
            self.mesh = make_mesh({mesh_axis: n_shards},
                                  devices=devs[:n_shards])
        self.n_shards = n_shards
        self._index = None

    def fit(self, X) -> "NearestNeighbors":
        if self.algorithm == "ivf_pq":
            from raft_tpu.ann import build_ivf_pq

            X = jnp.asarray(X, jnp.float32)
            n_lists = self.n_lists or max(
                1, min(1024, int(round(X.shape[0] ** 0.5))))
            self._index = build_ivf_pq(self.res, X, n_lists=n_lists,
                                       pq_dim=self.pq_dim,
                                       pq_bits=self.pq_bits,
                                       n_probes=self.n_probes)
            self._n_index = self._index.n_rows
            self._prepared = None
            return self
        if self.algorithm == "ivf_flat":
            from raft_tpu.ann import build_ivf_flat, shard_ivf_lists

            X = jnp.asarray(X, jnp.float32)
            n_lists = self.n_lists or max(
                1, min(1024, int(round(X.shape[0] ** 0.5))))
            self._index = build_ivf_flat(self.res, X, n_lists=n_lists,
                                         n_probes=self.n_probes)
            self._n_index = self._index.n_rows
            self._prepared = None
            if self.mesh is not None:
                self._index = shard_ivf_lists(self._index, self.mesh,
                                              self.mesh_axis)
            return self
        if self.mesh is not None and self.n_shards is not None:
            # fused sharded path: build the ShardedFusedIndex once
            kernel_metric = {"sqeuclidean": "l2", "euclidean": "l2",
                             "l2": "l2",
                             "inner_product": "ip"}.get(self.metric)
            if kernel_metric is not None:
                from raft_tpu.distance.knn_sharded import \
                    prepare_knn_index_sharded

                self._index = prepare_knn_index_sharded(
                    X, mesh=self.mesh, axis=self.mesh_axis,
                    metric=kernel_metric, res=self.res)
                self._n_index = self._index.n_rows
                self._prepared = None
                return self
            # metric outside the fused envelope: the streamed sharded
            # path below still serves it
        if self.mesh is not None:
            # MNMG: pad + shard ONCE, straight from host — the full
            # matrix never materializes on one device (the
            # bigger-than-HBM index mode this exists for)
            from raft_tpu.distance.fused_l2nn import prepare_index_sharded

            self._index = prepare_index_sharded(self.res, X, self.mesh,
                                                self.mesh_axis)
            self._n_index = self._index.n
            self._prepared = None
            return self
        self._index = jnp.asarray(X, jnp.float32)
        self._n_index = self._index.shape[0]
        # build/query split: prepare the fused-pipeline index operands
        # once, mirroring knn()'s own auto-routing condition (TPU +
        # fused-eligible shape); anything else stays unprepared and
        # takes knn()'s normal dispatch
        self._prepared = None
        kernel_metric = {"sqeuclidean": "l2", "euclidean": "l2",
                         "l2": "l2", "inner_product": "ip"}.get(self.metric)
        try:
            from raft_tpu.distance.knn_fused import (
                fused_eligible, prepare_knn_index)

            if (kernel_metric is not None
                    and fused_eligible(*self._index.shape)):
                self._prepared = prepare_knn_index(
                    self._index, metric=kernel_metric)
                # the KnnIndex's row-padded yp already holds the full
                # f32 matrix; keeping self._index too would pin a
                # redundant ~512 MB copy in HBM at 1M×128
                self._index = None
        except Exception:
            self._prepared = None   # preparation is an optimization only
        return self

    @property
    def _index_matrix(self):
        from raft_tpu.distance.knn_sharded import ShardedFusedIndex

        if isinstance(self._index, ShardedFusedIndex):
            # sharded fused fit: the true rows of the row-sharded yp
            idx = self._index
            return jnp.take(idx.yp_s, jnp.asarray(idx.row_positions()),
                            axis=0)[:, :idx.d_orig]
        if self.mesh is not None:
            # sharded fit: slice the true rows of the global array
            return self._index.idx_s[:self._index.n]
        if self._index is not None:
            return self._index
        p = self._prepared
        return p.yp[:p.n_rows, :p.d_orig]

    def kneighbors(self, queries, n_neighbors: Optional[int] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        k = n_neighbors or self.n_neighbors
        if self.algorithm == "ivf_pq":
            from raft_tpu.ann import search_ivf_pq

            dists, idx = search_ivf_pq(self.res, self._index, queries,
                                       k, n_probes=self.n_probes)
            if self.metric in ("euclidean", "l2"):
                dists = jnp.sqrt(jnp.maximum(dists, 0.0))
            return dists, idx
        if self.algorithm == "ivf_flat":
            from raft_tpu.ann import search_ivf_flat

            dists, idx = search_ivf_flat(
                self.res, self._index, queries, k,
                n_probes=self.n_probes, merge=self.merge)
            if self.metric in ("euclidean", "l2"):
                dists = jnp.sqrt(jnp.maximum(dists, 0.0))
            return dists, idx
        from raft_tpu.distance.knn_sharded import ShardedFusedIndex

        if isinstance(self._index, ShardedFusedIndex):
            from raft_tpu.distance.knn_sharded import knn_fused_sharded

            dists, idx = knn_fused_sharded(
                queries, self._index, k, mesh=self.mesh,
                axis=self.mesh_axis, merge=self.merge, res=self.res)
            if self.metric in ("euclidean", "l2"):
                dists = jnp.sqrt(jnp.maximum(dists, 0.0))
            return dists, idx
        if self.mesh is not None:
            from raft_tpu.distance.fused_l2nn import knn_index_sharded

            return knn_index_sharded(self.res, self._index, queries, k,
                                     mesh=self.mesh, axis=self.mesh_axis,
                                     metric=self.metric)
        if self._prepared is not None and k <= self._prepared.n_rows:
            try:
                return _knn(self.res, self._prepared, queries, k,
                            metric=self.metric)
            except NotImplementedError:
                pass   # off-envelope k: fall through to normal dispatch
        return _knn(self.res, self._index_matrix, queries, k,
                    metric=self.metric)

    def kneighbors_graph(self, queries):
        """KNN as a CSR adjacency (for spectral embedding pipelines)."""
        from raft_tpu.core.sparse_types import CSRMatrix

        d, i = self.kneighbors(queries)
        nq, k = i.shape
        indptr = jnp.arange(nq + 1, dtype=jnp.int32) * k
        return CSRMatrix(indptr, i.reshape(-1), d.reshape(-1),
                         (nq, self._n_index))
