"""Isotropic Gaussian blob generator.

(ref: cpp/include/raft/random/make_blobs.cuh — cluster blobs with optional
given centers, per-cluster std, shuffle; the standard fixture generator for
clustering/knn tests and benchmarks.)

Ground truth is first-class: labels are always returned, ``cluster_std``
may be a per-center array, ``proportions`` produces controllably
IMBALANCED cluster sizes, and ``return_centers=True`` hands back the
true centers — together the controllable oracle the k-means and
IVF-recall suites (tests/test_kmeans.py, tests/test_ivf_flat.py)
measure against.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from raft_tpu.random.rng_state import _as_key


def _imbalanced_labels(n_samples: int, proportions) -> jnp.ndarray:
    """Per-cluster counts from sampling proportions: floor shares with
    the remainder going to the largest-proportion clusters — sizes are
    deterministic for a given (n_samples, proportions), so a test's
    ground-truth histogram is exactly reproducible."""
    import numpy as np

    p = np.asarray(proportions, np.float64)
    if (p < 0).any() or p.sum() <= 0:
        raise ValueError("make_blobs: proportions must be non-negative "
                         "and sum to a positive value")
    p = p / p.sum()
    counts = np.floor(p * n_samples).astype(np.int64)
    short = n_samples - int(counts.sum())
    if short:
        # hand leftover samples to the largest shares, ties by index
        for i in np.argsort(-p, kind="stable")[:short]:
            counts[i] += 1
    return jnp.asarray(np.repeat(np.arange(len(p)), counts),
                       jnp.int32)


def make_blobs(
    res,
    state,
    n_samples: int,
    n_features: int,
    n_clusters: int = 3,
    cluster_std=1.0,
    centers=None,
    center_box: Tuple[float, float] = (-10.0, 10.0),
    shuffle: bool = True,
    proportions=None,
    return_centers: bool = False,
    dtype=jnp.float32,
):
    """Returns ``(X [n_samples, n_features], labels [n_samples])`` —
    or ``(X, labels, centers)`` with ``return_centers=True``.
    (ref: make_blobs.cuh ``make_blobs``)

    - ``cluster_std`` — scalar, or a PER-CENTER array [n_clusters]
      (center ``i``'s points get std ``cluster_std[i]``).
    - ``proportions`` — per-cluster sampling proportions [n_clusters]
      switching on the IMBALANCED-sizes mode (deterministic counts:
      floor shares + remainder to the largest); default None keeps the
      reference's balanced round-robin assignment.
    """
    key = _as_key(state)
    k_centers, k_labels, k_noise, k_shuffle = jax.random.split(key, 4)
    if centers is None:
        centers = jax.random.uniform(
            k_centers, (n_clusters, n_features), dtype,
            minval=center_box[0], maxval=center_box[1])
    else:
        centers = jnp.asarray(centers, dtype)
        n_clusters = centers.shape[0]
    if proportions is not None:
        if len(proportions) != n_clusters:
            raise ValueError(
                f"make_blobs: proportions has {len(proportions)} "
                f"entries for {n_clusters} clusters")
        labels = _imbalanced_labels(n_samples, proportions)
    else:
        # balanced assignment like the reference (round-robin), then
        # shuffle
        labels = jnp.arange(n_samples, dtype=jnp.int32) % n_clusters
    std = jnp.asarray(cluster_std, dtype)
    per_point_std = std[labels] if std.ndim == 1 else std
    noise = jax.random.normal(k_noise, (n_samples, n_features), dtype)
    X = centers[labels] + noise * (
        per_point_std[:, None] if getattr(per_point_std, "ndim", 0) else per_point_std
    )
    if shuffle:
        perm = jax.random.permutation(k_shuffle, n_samples)
        X, labels = X[perm], labels[perm]
    if return_centers:
        return X, labels, centers
    return X, labels
