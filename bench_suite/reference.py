"""The benchmark's own data generator and plain exact reference.

Imports nothing of ``raft_tpu``: the data and the answers it is judged
against come from here, so no change to the program can move them.

- :func:`make_data` draws the base set and the query pool on the device
  in one jitted call from ``--seed``.
- :func:`exact_topk` is the plain exact k-NN (copied in spirit from
  ``chip_smoke.reference_knn``): squared L2 in the expanded form, the
  cross term at a stated matmul precision, ``lax.top_k``, in blocks of
  queries and base rows so that it fits next to nothing else.
- :func:`true_distances` recomputes the distance of given ids in the
  difference form ``sum((q - x)**2)``, which has no cancellation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: rows of the base scanned per reference block (256 MiB of f32 scores
#: for a 512-query block)
BASE_BLOCK = 1 << 17
QUERY_BLOCK = 512


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number, 64-bit seeds included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


def _rows(keys, lift, centers, n: int, within_scale, noise_scale):
    k_lab, k_lat, k_noise = keys
    latent_dim, dim = lift.shape
    labels = jax.random.randint(k_lab, (n,), 0, centers.shape[0])
    latent = (centers[labels]
              + jax.random.normal(k_lat, (n, latent_dim)) * within_scale)
    return (jnp.dot(latent, lift, precision=jax.lax.Precision.HIGHEST)
            + jax.random.normal(k_noise, (n, dim)) * noise_scale)


@partial(jax.jit, static_argnames=("n_rows", "n_pool", "dim", "latent_dim",
                                   "n_centers"))
def _generate(data_key, run_key, n_rows: int, n_pool: int, dim: int,
              latent_dim: int, n_centers: int, center_scale: float,
              within_scale: float, noise_scale: float):
    k_map, k_ctr, *k_rows = jax.random.split(data_key, 5)
    lift = jax.random.normal(k_map, (latent_dim, dim)) / np.sqrt(latent_dim)
    centers = jax.random.normal(k_ctr, (n_centers, latent_dim)) * center_scale
    base = _rows(k_rows, lift, centers, n_rows + n_pool, within_scale,
                 noise_scale)[:n_rows]
    pool = _rows(jax.random.split(run_key, 3), lift, centers, n_pool,
                 within_scale, noise_scale)
    return base, pool


def make_data(seed: int, data: dict):
    """(base [n_rows, dim], pool [n_pool, dim]) f32 on the device.

    A mixture of ``n_centers`` Gaussian clusters in a ``latent_dim``
    space, lifted to ``dim`` by a random map, plus isotropic noise. The
    clusters overlap (``within_scale`` is close to ``center_scale``), so
    true neighbours cross inverted-list boundaries the way SIFT's do.
    The base set is the deployment's one dataset, drawn from the
    configuration's ``data_seed``: an index built over it has the same
    geometry, and the same compiled programs, on every seed. ``seed``
    draws a new query pool from the same distribution, not from the
    base."""
    return _generate(seed_key(int(data["data_seed"])), seed_key(seed),
                     int(data["n_rows"]), int(data["n_pool"]),
                     int(data["dim"]), int(data["latent_dim"]),
                     int(data["n_centers"]), float(data["center_scale"]),
                     float(data["within_scale"]),
                     float(data["noise_scale"]))


def _bf16_parts(v):
    """(hi, lo): ``v``'s bf16 rounding and the bf16 rounding of the rest,
    kept in f32 (``reduce_precision`` is not folded away as a round trip
    through bf16 can be)."""
    hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(v - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def cross_term(q, x, precision: str):
    """``q @ x.T`` in f32 at ``highest``, in three bf16 passes
    (``bf16x3``: hi*hi + hi*lo + lo*hi with f32 accumulation, what a
    TPU's ``Precision.HIGH`` computes) or in one (``bf16``: hi*hi),
    spelled out so that every backend computes the same. The parts are
    bf16 values, so their products at ``highest`` are exact."""
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(q, x.T, precision=hp)
    if precision not in ("bf16x3", "bf16"):
        raise ValueError(f"precision {precision!r}")
    qh, ql = _bf16_parts(q)
    xh, xl = _bf16_parts(x)
    if precision == "bf16":
        return jnp.matmul(qh, xh.T, precision=hp)
    return (jnp.matmul(qh, xh.T, precision=hp)
            + (jnp.matmul(qh, xl.T, precision=hp)
               + jnp.matmul(ql, xh.T, precision=hp)))


@partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(q, x, k: int, precision: str):
    d2 = (jnp.sum(q * q, axis=1)[:, None] + jnp.sum(x * x, axis=1)[None]
          - 2.0 * cross_term(q, x, precision))
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


@partial(jax.jit, static_argnames=("k",))
def _merge_topk(d, i, k: int):
    neg, pos = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(i, pos, axis=1)


def exact_topk(queries, base, k: int, precision: str = "highest"):
    """Exact top-``k`` (distances, ids) of every query row, as numpy.

    ``precision`` is the cross term's (:func:`cross_term`): ``highest``
    is the reference; ``bf16x3`` is the control, the nearest precision
    below the f32 that the configurations state; ``bf16`` picks ids as a
    one-pass bf16 selection would (a planted fault)."""
    n = base.shape[0]
    outs_d, outs_i = [], []
    for q0 in range(0, queries.shape[0], QUERY_BLOCK):
        q = queries[q0:q0 + QUERY_BLOCK]
        ds, is_ = [], []
        for b0 in range(0, n, BASE_BLOCK):
            d, i = _block_topk(q, base[b0:b0 + BASE_BLOCK], k, precision)
            ds.append(d)
            is_.append(i + b0)
        d, i = _merge_topk(jnp.concatenate(ds, axis=1),
                           jnp.concatenate(is_, axis=1), k)
        outs_d.append(np.asarray(d))
        outs_i.append(np.asarray(i))
    return np.concatenate(outs_d), np.concatenate(outs_i)


@jax.jit
def _diff_dist(q, rows):
    return jnp.sum((q[:, None, :] - rows) ** 2, axis=2)


def true_distances(queries, base, ids, block: int = 1024) -> np.ndarray:
    """Squared L2 of each (query row, id) pair in the difference form,
    [n, k] float64 on the host. ``ids`` outside the base give +inf."""
    ids = np.asarray(ids)
    n = base.shape[0]
    valid = (ids >= 0) & (ids < n)
    safe = np.where(valid, ids, 0).astype(np.int32)
    out = np.empty(ids.shape, np.float64)
    for s in range(0, ids.shape[0], block):
        rows = jnp.take(base, jnp.asarray(safe[s:s + block]), axis=0)
        out[s:s + block] = np.asarray(_diff_dist(queries[s:s + block],
                                                 rows))
    return np.where(valid, out, np.inf)
