"""The benchmark's own data generator and plain exact reference.

Imports nothing of ``raft_tpu``: the data and the answers it is judged
against come from here, so no change to the program can move them.

- :func:`make_data` draws the base set and the query pool from
  ``--seed``, in one of two placements that the configuration's
  ``data`` names:

  - by default (no ``placement`` key) the whole base and the pool on one
    device, in one jitted call;
  - ``"placement": "row_sharded"``: the base as one global array whose
    rows are split evenly over the cell's devices, each device drawing
    only its own rows, block by block (:func:`row_block`), so that no
    device ever holds more than its share and one block; the pool is
    replicated on every device.

- :func:`exact_topk` is the plain exact k-NN (copied in spirit from
  ``chip_smoke.reference_knn``): squared L2 in the expanded form, the
  cross term at a stated matmul precision, ``lax.top_k``, in blocks of
  queries and base rows so that it fits next to nothing else. On a
  row-sharded base each device takes the top-k of its own rows and the
  host merges the candidates.
- :func:`true_distances` recomputes the distance of given ids in the
  difference form ``sum((q - x)**2)``, which has no cancellation, on
  the device that holds each id's row.
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
#: the ``placement`` that splits the base's rows over the cell's devices
ROW_SHARDED = "row_sharded"
#: rows drawn per generator block of a row-sharded base (512 MiB at
#: d=128); a row's value depends on it, so it is part of the data
ROW_BLOCK = 1 << 20


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


def make_data(seed: int, data: dict, devices=None):
    """(base [n_rows, dim], pool [n_pool, dim]) f32 on the device.

    A mixture of ``n_centers`` Gaussian clusters in a ``latent_dim``
    space, lifted to ``dim`` by a random map, plus isotropic noise. The
    clusters overlap (``within_scale`` is close to ``center_scale``), so
    true neighbours cross inverted-list boundaries the way SIFT's do.
    The base set is the deployment's one dataset, drawn from the
    configuration's ``data_seed``: an index built over it has the same
    geometry, and the same compiled programs, on every seed. ``seed``
    draws a new query pool from the same distribution, not from the
    base.

    Without a ``placement`` key both sit on the default device and
    ``devices`` is not read. With ``"placement": "row_sharded"`` the
    base is row-sharded over ``devices`` (:func:`make_row_sharded`)."""
    placement = data.get("placement")
    if placement == ROW_SHARDED:
        return make_row_sharded(seed, data, devices)
    if placement is not None:
        raise ValueError(f"placement {placement!r}: the data's placement "
                         f"is absent or {ROW_SHARDED!r}")
    return _generate(seed_key(int(data["data_seed"])), seed_key(seed),
                     int(data["n_rows"]), int(data["n_pool"]),
                     int(data["dim"]), int(data["latent_dim"]),
                     int(data["n_centers"]), float(data["center_scale"]),
                     float(data["within_scale"]),
                     float(data["noise_scale"]))


@partial(jax.jit, static_argnames=("dim", "latent_dim", "n_centers"))
def _latent_map(data_key, dim: int, latent_dim: int, n_centers: int,
                center_scale: float):
    """(lift, centres) as :func:`_generate` draws them."""
    k_map, k_ctr, *_ = jax.random.split(data_key, 5)
    lift = jax.random.normal(k_map, (latent_dim, dim)) / np.sqrt(latent_dim)
    centers = jax.random.normal(k_ctr, (n_centers, latent_dim)) * center_scale
    return lift, centers


@partial(jax.jit, static_argnames=("n",))
def _draw(key, lift, centers, n: int, within_scale: float,
          noise_scale: float):
    return _rows(jax.random.split(key, 3), lift, centers, n, within_scale,
                 noise_scale)


@partial(jax.jit, static_argnames=("n",))
def _draw_block(data_key, index, lift, centers, n: int, within_scale: float,
                noise_scale: float):
    return _draw(jax.random.fold_in(data_key, index), lift, centers, n,
                 within_scale, noise_scale)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _zeros(rows: int, dim: int, sharding):
    """Zeros made where they live (``jnp.zeros(device=)`` makes them on
    the default device first)."""
    return jax.lax.with_sharding_constraint(
        jnp.zeros((rows, dim), jnp.float32), sharding)


@partial(jax.jit, donate_argnums=0)
def _write_block(buf, block, start):
    return jax.lax.dynamic_update_slice_in_dim(buf, block, start, 0)


def _scales(data: dict):
    return float(data["within_scale"]), float(data["noise_scale"])


def _latent_on(data: dict, device):
    lift, centers = _latent_map(
        seed_key(int(data["data_seed"])), int(data["dim"]),
        int(data["latent_dim"]), int(data["n_centers"]),
        float(data["center_scale"]))
    return jax.device_put(lift, device), jax.device_put(centers, device)


def row_block(data: dict, index: int, device, latent=None):
    """Rows ``[index * ROW_BLOCK, (index + 1) * ROW_BLOCK)`` of a
    row-sharded base, drawn on ``device`` alone: the block's key is
    ``fold_in(data_key, index)``, and the lift and centres are
    :func:`_generate`'s. A row's value depends only on ``data_seed`` and
    its index, never on how many devices share the base. ``latent`` is
    the (lift, centres) already on ``device``."""
    lift, centers = latent or _latent_on(data, device)
    key = jax.device_put(seed_key(int(data["data_seed"])), device)
    return _draw_block(key, jax.device_put(np.int32(index), device), lift,
                       centers, ROW_BLOCK, *_scales(data))


def make_row_sharded(seed: int, data: dict, devices):
    """(base, pool): the base a global ``[n_rows, dim]`` f32 array with
    rows split ``P("rows")`` over ``devices``, device ``j`` holding rows
    ``[j * share, (j + 1) * share)``; the pool replicated on each.

    Each device fills a buffer of its share in place, one
    :func:`row_block` at a time, the devices side by side. ``n_rows``
    must give every device a whole number of blocks; nothing is
    padded."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    if not devices:
        raise ValueError("row-sharded data needs the cell's devices")
    devices = list(devices)
    n_rows, dim = int(data["n_rows"]), int(data["dim"])
    block = ROW_BLOCK
    if n_rows <= 0 or n_rows % (block * len(devices)):
        raise ValueError(
            f"row-sharded data: n_rows {n_rows} is not a multiple of "
            f"{len(devices)} devices x the row block {block}")
    share = n_rows // len(devices)
    mesh = Mesh(np.array(devices), ("rows",))
    latents = [_latent_on(data, dev) for dev in devices]
    shards = [_zeros(share, dim, SingleDeviceSharding(dev))
              for dev in devices]
    per_device = share // block
    for b in range(per_device):
        for j, dev in enumerate(devices):
            shards[j] = _write_block(
                shards[j],
                row_block(data, j * per_device + b, dev, latents[j]),
                jax.device_put(np.int32(b * block), dev))
        # one block at a time on each device: its working set is freed
        # before the next one is drawn
        jax.block_until_ready(shards)
    base = jax.make_array_from_single_device_arrays(
        (n_rows, dim), NamedSharding(mesh, P("rows")), shards)
    pool = _draw(jax.device_put(seed_key(seed), devices[0]), *latents[0],
                 int(data["n_pool"]), *_scales(data))
    return base, jax.device_put(pool, NamedSharding(mesh, P()))


def row_shards(base):
    """[(first row, the rows one device holds)] of ``base`` in row order:
    one entry for a base on one device, one per device for a row-sharded
    base."""
    if len(base.sharding.device_set) == 1:
        return [(0, base)]
    first = {}
    for s in base.addressable_shards:
        first.setdefault(s.index[0].start or 0, s.data)
    return sorted(first.items(), key=lambda kv: kv[0])


def _device_of(x):
    return next(iter(x.sharding.device_set))


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


def _merge_shards(parts, k: int):
    """Top-``k`` of the shards' candidates on the host. Candidates are
    laid out in row order and the sort is stable, so ties go to the lower
    id, as ``lax.top_k`` gives them on one device."""
    d = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
    i = np.concatenate([np.asarray(p[1]) for p in parts], axis=1)
    pos = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, pos, 1), np.take_along_axis(i, pos, 1)


def exact_topk(queries, base, k: int, precision: str = "highest"):
    """Exact top-``k`` (distances, ids) of every query row, as numpy.

    ``precision`` is the cross term's (:func:`cross_term`): ``highest``
    is the reference; ``bf16x3`` is the control, the nearest precision
    below the f32 that the configurations state; ``bf16`` picks ids as a
    one-pass bf16 selection would (a planted fault). Each device scans
    its own rows in blocks of :data:`BASE_BLOCK`; a row-sharded base's
    per-device top-k merge on the host (:func:`_merge_shards`)."""
    shards = row_shards(base)
    # each device's copy of the queries comes from the host: a copy from
    # another device would wait for that device's queued blocks
    queries = np.asarray(queries)
    outs_d, outs_i = [], []
    for q0 in range(0, queries.shape[0], QUERY_BLOCK):
        q = queries[q0:q0 + QUERY_BLOCK]
        qx = [jax.device_put(q, _device_of(x)) for _, x in shards]
        ds, is_ = [[] for _ in shards], [[] for _ in shards]
        # block by block across the devices, so that a device whose
        # queue is full holds none of the others back
        for b0 in range(0, max(x.shape[0] for _, x in shards), BASE_BLOCK):
            for j, (first, x) in enumerate(shards):
                if b0 < x.shape[0]:
                    d, i = _block_topk(qx[j], x[b0:b0 + BASE_BLOCK], k,
                                       precision)
                    ds[j].append(d)
                    is_[j].append(i + (first + b0))
        parts = [_merge_topk(jnp.concatenate(d, axis=1),
                             jnp.concatenate(i, axis=1), k)
                 for d, i in zip(ds, is_)]
        d, i = parts[0] if len(parts) == 1 else _merge_shards(parts, k)
        outs_d.append(np.asarray(d))
        outs_i.append(np.asarray(i))
    return np.concatenate(outs_d), np.concatenate(outs_i)


@jax.jit
def _diff_dist(q, rows):
    return jnp.sum((q[:, None, :] - rows) ** 2, axis=2)


def true_distances(queries, base, ids, block: int = 1024) -> np.ndarray:
    """Squared L2 of each (query row, id) pair in the difference form,
    [n, k] float64 on the host, each taken on the device that holds the
    id's row. ``ids`` outside the base give +inf."""
    ids = np.asarray(ids)
    out = np.full(ids.shape, np.inf)
    queries = np.asarray(queries)
    for first, x in row_shards(base):
        own = (ids >= first) & (ids < first + x.shape[0])
        if not own.any():
            continue
        safe = np.where(own, ids - first, 0).astype(np.int32)
        dev = _device_of(x)
        for s in range(0, ids.shape[0], block):
            rows = jnp.take(x, jax.device_put(safe[s:s + block], dev),
                            axis=0)
            d = np.asarray(_diff_dist(
                jax.device_put(queries[s:s + block], dev), rows))
            out[s:s + block] = np.where(own[s:s + block], d,
                                        out[s:s + block])
    return out
