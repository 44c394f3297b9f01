"""Balanced k-means on the fused distance primitives.

(ref: cpp/include/raft/cluster/kmeans.cuh +
kmeans_balanced.cuh / detail/kmeans_balanced.cuh — the coarse trainer
behind the reference's IVF indexes. The reference's Lloyd loop is
"minClusterAndDistance (a fusedL2NN sweep) → update_centroids (a
segmented reduction)"; this module is the same decomposition on the
TPU primitives: assignment through
:func:`raft_tpu.distance.fused_l2nn.fused_l2_nn_argmin`, the centroid
update via ``jax.ops.segment_sum``, with the balanced variant applying
a per-iteration cluster-size penalty to the assignment scores the way
``kmeans_balanced``'s adjustCenters pass biases against oversized
clusters.)

Why balance matters here: the IVF-Flat index (:mod:`raft_tpu.ann`)
pads every inverted list to a row quantum and probes whole lists — a
skewed clustering both wastes pad rows and makes per-probe cost
unpredictable. The balanced penalty trades a little inertia for
near-uniform list sizes, which is exactly the trade the reference
makes for its ANN coarse quantizers.

Observability: every fit is ``@instrument``-ed, carries the
``kmeans_fit`` / ``kmeans_iteration`` fault sites
(``RAFT_TPU_FAULTS``), emits one ``marker`` flight event per Lloyd
iteration (inertia, shift, size spread — the convergence trail is
reconstructable from a post-mortem dump), and captures the assignment
step's XLA cost through ``res.profiler.capture_fn`` so the roofline
report can attribute it.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from raft_tpu.core.error import expects
from raft_tpu.core.resources import ensure_resources
from raft_tpu.observability import instrument
from raft_tpu.observability.timeline import emit_marker
from raft_tpu.resilience import fault_point

#: default balanced-penalty exponent: assignment scores are multiplied
#: by ((size + 1) / (mean_size + 1)) ** alpha — oversized clusters look
#: farther, undersized (and empty) ones look closer. 0 disables.
DEFAULT_BALANCE_ALPHA = 0.25

#: row-chunk bound for the weighted assignment sweep: the [chunk, k]
#: score tile stays under ~64 MB f32 at any k
_ASSIGN_TILE = 1 << 24


class KMeansResult(NamedTuple):
    """The fit artifact: ``centroids [k, d]``, the final ``labels [n]``,
    the (true, unpenalized) ``inertia``, iterations run, and the final
    ``cluster_sizes [k]``."""

    centroids: jax.Array
    labels: jax.Array
    inertia: float
    n_iter: int
    cluster_sizes: jax.Array


@partial(jax.jit, static_argnames=("k",))
def _kmeanspp_init(key, Xs, k: int):
    """k-means++ on the (sub)sampled rows ``Xs``: first center uniform,
    then each next center sampled ∝ current min-d2 — one fori_loop, the
    min-d2 carry updated against only the newest center (O(k·n·d)).
    (ref: detail/kmeans_init_plus_plus.cuh.)"""
    n, d = Xs.shape
    xs2 = jnp.sum(Xs * Xs, axis=1)

    def body(i, carry):
        key, centers, mind2 = carry
        key, kc = jax.random.split(key)
        # i == 0: mind2 is all-ones → uniform first pick
        logits = jnp.log(jnp.maximum(mind2, 1e-30))
        idx = jax.random.categorical(kc, logits)
        c = Xs[idx]
        centers = centers.at[i].set(c)
        d2 = jnp.maximum(
            xs2 + jnp.sum(c * c) - 2.0 * (Xs @ c), 0.0)
        return key, centers, jnp.minimum(mind2, d2)

    centers = jnp.zeros((k, d), jnp.float32)
    _, centers, _ = jax.lax.fori_loop(
        0, k, body, (key, centers, jnp.ones((n,), jnp.float32)))
    return centers


@partial(jax.jit, static_argnames=("k",))
def _assign_chunk(Xc, valid, centroids, weights, k: int):
    """One weighted-assignment chunk: expanded-L2 scores [C, k] (the
    same score function fusedL2NN evaluates), multiplied by the
    per-cluster balance weights for the ARGMIN only — the returned
    inertia is the true unpenalized d2. Returns per-chunk labels,
    inertia sum, centroid partial sums and counts (segment-sum — the
    reference's update_centroids reduction)."""
    xx = jnp.sum(Xc * Xc, axis=1, keepdims=True)
    cc = jnp.sum(centroids * centroids, axis=1)
    d2 = jnp.maximum(
        xx + cc[None, :] - 2.0 * (Xc @ centroids.T), 0.0)
    labels = jnp.argmin(d2 * weights[None, :], axis=1).astype(jnp.int32)
    best = jnp.take_along_axis(d2, labels[:, None], axis=1)[:, 0]
    w = valid.astype(jnp.float32)
    inertia = jnp.sum(best * w)
    # pads are routed to segment k (dropped by num_segments=k)
    seg = jnp.where(valid, labels, k)
    sums = jax.ops.segment_sum(Xc * w[:, None], seg, num_segments=k)
    counts = jax.ops.segment_sum(w, seg, num_segments=k)
    return labels, inertia, sums, counts


def _balance_weights(counts, alpha: float):
    """((size + 1) / (mean + 1)) ** alpha — empty clusters get weight
    < 1 (they attract their nearest points back), oversized ones > 1.
    The +1 regularization keeps the weight finite and non-zero at
    size 0, so an empty cluster can never swallow EVERY point in one
    step the way a raw 0-weight would."""
    mean = jnp.mean(counts)
    return ((counts + 1.0) / (mean + 1.0)) ** alpha


def _assign_sweep(X, centroids, weights, k: int, res):
    """Full weighted assignment over chunked rows (python chunk loop on
    a fixed-shape jitted tile — one compile per fit geometry). Returns
    (labels [n], inertia, sums [k, d], counts [k])."""
    n, d = X.shape
    chunk = max(8, min(n, _ASSIGN_TILE // max(1, 4 * k)))
    labels_out, inertia = [], 0.0
    sums = jnp.zeros((k, d), jnp.float32)
    counts = jnp.zeros((k,), jnp.float32)
    # one cost capture per fit geometry (memoized by shape signature):
    # the assignment tile is the hot ~O(n·k·d) kernel of the loop
    try:
        res.profiler.capture_fn(
            "cluster.kmeans_assign", _assign_chunk,
            X[:chunk] if n >= chunk else
            jnp.zeros((chunk, d), jnp.float32),
            jnp.ones((chunk,), jnp.bool_), centroids, weights, k=k)
    except Exception:
        pass
    for s in range(0, n, chunk):
        Xc = X[s:s + chunk]
        c = Xc.shape[0]
        valid = jnp.ones((chunk,), jnp.bool_)
        if c < chunk:
            Xc = jnp.concatenate(
                [Xc, jnp.zeros((chunk - c, d), jnp.float32)])
            valid = jnp.arange(chunk) < c
        lab, ine, sm, ct = _assign_chunk(Xc, valid, centroids, weights,
                                         k=k)
        labels_out.append(lab[:c])
        inertia = inertia + ine
        sums = sums + sm
        counts = counts + ct
    return jnp.concatenate(labels_out), inertia, sums, counts


@instrument("cluster.kmeans_fit")
def kmeans_fit(res, X, n_clusters: int, max_iter: int = 20,
               tol: float = 1e-4, seed: int = 0,
               balanced: bool = False,
               balance_alpha: float = DEFAULT_BALANCE_ALPHA,
               init: str = "kmeans++",
               init_centroids=None,
               n_init: int = 1,
               max_init_rows: Optional[int] = None) -> KMeansResult:
    """Lloyd k-means (ref: cluster/kmeans.cuh ``kmeans::fit``;
    ``balanced=True`` ≈ cluster/kmeans_balanced.cuh).

    - **init**: ``"kmeans++"`` (on a sub-sample of at most
      ``max_init_rows`` rows — default ``max(16·k, 2048)``, the
      reference's trainset_fraction idea) or ``"random"`` (uniform row
      sample). ``init_centroids`` short-circuits both. ``n_init`` > 1
      restarts from that many seeds and keeps the lowest-inertia run
      (the sklearn convention — k-means++ still lands in local optima).
    - **assignment**: the expanded-L2 score fusedL2NN evaluates;
      ``balanced=True`` multiplies the scores per cluster by
      ``((size+1)/(mean+1))**balance_alpha`` — the per-iteration
      cluster-size penalty. The reported inertia is always the TRUE
      (unpenalized) d2 sum.
    - **update**: segment-sum centroid means; empty clusters keep
      their previous centroid (the balanced penalty pulls them back).
    - **convergence**: relative inertia delta ≤ ``tol`` (checked on
      host per iteration — each iteration emits a ``marker`` flight
      event with inertia / max-centroid-shift / size spread).
    """
    fault_point("kmeans_fit")
    res = ensure_resources(res)
    if n_init > 1 and init_centroids is None:
        best = None
        for i in range(int(n_init)):
            r = kmeans_fit(res, X, n_clusters, max_iter=max_iter,
                           tol=tol, seed=seed + i, balanced=balanced,
                           balance_alpha=balance_alpha, init=init,
                           max_init_rows=max_init_rows)
            if best is None or r.inertia < best.inertia:
                best = r
        return best
    X = jnp.asarray(X, jnp.float32)
    n, d = X.shape
    k = int(n_clusters)
    expects(k >= 1, "kmeans_fit: n_clusters must be >= 1, got %d", k)
    expects(n >= k, "kmeans_fit: %d rows < n_clusters=%d", n, k)
    expects(init in ("kmeans++", "random"),
            "kmeans_fit: init must be 'kmeans++' or 'random', got %r",
            init)
    key = jax.random.PRNGKey(seed)
    if init_centroids is not None:
        centroids = jnp.asarray(init_centroids, jnp.float32)
        expects(centroids.shape == (k, d),
                "kmeans_fit: init_centroids shape %s != (%d, %d)",
                centroids.shape, k, d)
    else:
        cap = max_init_rows or max(16 * k, 2048)
        key, ks = jax.random.split(key)
        if n > cap:
            sub = X[jax.random.choice(ks, n, (cap,), replace=False)]
        else:
            sub = X
        if init == "kmeans++":
            key, ki = jax.random.split(key)
            centroids = _kmeanspp_init(ki, sub, k)
        else:
            key, ki = jax.random.split(key)
            centroids = sub[jax.random.choice(
                ki, sub.shape[0], (k,), replace=False)]

    weights = jnp.ones((k,), jnp.float32)
    counts = jnp.zeros((k,), jnp.float32)
    labels = jnp.zeros((n,), jnp.int32)
    inertia = float("inf")
    it = 0
    stable = False
    for it in range(1, max_iter + 1):
        fault_point("kmeans_iteration")
        if balanced and balance_alpha > 0.0:
            weights = _balance_weights(counts, balance_alpha)
        prev_labels = labels
        labels, ine, sums, counts = _assign_sweep(
            X, centroids, weights, k, res)
        stable = it > 1 and bool(jnp.array_equal(labels, prev_labels))
        new_centroids = jnp.where(
            counts[:, None] > 0,
            sums / jnp.maximum(counts[:, None], 1.0), centroids)
        ine = float(ine)
        shift = float(jnp.max(jnp.sum(
            (new_centroids - centroids) ** 2, axis=1)))
        centroids = new_centroids
        emit_marker("kmeans_iteration", it=it, inertia=ine,
                    max_shift2=shift,
                    size_min=float(jnp.min(counts)),
                    size_max=float(jnp.max(counts)),
                    balanced=bool(balanced))
        if inertia != float("inf") and ine >= inertia * (1.0 - tol):
            inertia = min(inertia, ine)
            break
        inertia = ine
    if not stable:
        # Stopped on the inertia tolerance or max_iter while labels were
        # still moving: the labels belong to the centroids before the
        # last update. Re-assign once so labels, inertia and sizes
        # describe the returned centroids (sklearn's final E-step).
        labels, ine, _, counts = _assign_sweep(
            X, centroids, weights, k, res)
        inertia = float(ine)
    return KMeansResult(centroids, labels, inertia, it,
                        counts.astype(jnp.int32))


@instrument("cluster.kmeans_predict")
def kmeans_predict(res, centroids, X):
    """Nearest-centroid labels for ``X`` — the fusedL2NN argmin sweep
    (ref: kmeans.cuh ``kmeans::predict`` = minClusterAndDistance).
    Balance weights are a TRAINING bias only; prediction is always the
    true nearest centroid."""
    from raft_tpu.distance.fused_l2nn import fused_l2_nn_argmin

    res = ensure_resources(res)
    X = jnp.asarray(X, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    expects(X.shape[1] == centroids.shape[1],
            "kmeans_predict: dim mismatch %d != %d", X.shape[1],
            centroids.shape[1])
    _, labels = fused_l2_nn_argmin(res, X, centroids)
    return labels


def kmeans_inertia(res, centroids, X, labels=None) -> float:
    """True d2 inertia of a labeling (computed via the argmin sweep
    when ``labels`` is None)."""
    from raft_tpu.distance.fused_l2nn import fused_l2_nn_argmin

    res = ensure_resources(res)
    X = jnp.asarray(X, jnp.float32)
    centroids = jnp.asarray(centroids, jnp.float32)
    if labels is None:
        d2, _ = fused_l2_nn_argmin(res, X, centroids)
        return float(jnp.sum(d2))
    diff = X - centroids[jnp.asarray(labels, jnp.int32)]
    return float(jnp.sum(diff * diff))
