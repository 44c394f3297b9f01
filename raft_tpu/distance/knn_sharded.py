"""Sharded stream-once KNN — database-parallel fused top-k across the mesh.

(ref: the reference's MNMG brute-force path — each GPU runs the fused
L2/top-k over its database shard and the per-shard candidate lists meet
in ``knn_merge_parts`` (spatial/knn/detail/knn_merge_parts.cuh) over the
comms layer; FAISS's multi-GPU ``IndexShards`` applies the same
database-sharding pattern. The TPU rendering: the index rows shard over
a named mesh axis with ``shard_map``, every device runs the PR-3 packed
db-major fused kernel (:mod:`raft_tpu.distance.knn_fused`) over its
shard — so each chip streams ITS slice of the database from HBM once —
and the per-shard candidates merge over ICI.)

Two merge strategies, selected by the ICI cost model
(:func:`raft_tpu.observability.costmodel.choose_merge_strategy`):

- ``"allgather"``: one ring all-gather of every shard's [nq, k]
  candidate block (value + global id), then ONE select over the
  p·k-wide pool. Minimal rounds (one collective + one select); per-
  device egress grows with p−1.
- ``"tournament"``: a log₂(p)-round butterfly of ``collective_permute``
  pair-exchanges; each round every rank merges its k candidates with
  its partner's via a select over 2k. log₂(p) blocks of wire instead of
  p−1 — less traffic for p ≥ 4, at the price of serialized rounds.
  Needs a power-of-two shard count (requests on other counts downgrade
  to allgather with a logged reason).

Both merges are deterministic and rank-ordered (lower mesh index's
candidates first), so every shard computes the bit-identical merged
result — the output is truly replicated, and ties break the same way
on every device.

**Build** (:func:`prepare_knn_index_sharded`): each device prepares its
own rows inside ``shard_map``. A device array already row-sharded over
the axis is taken as it is, with no host round trip. Shard ``r`` holds
input rows ``[r·share, (r+1)·share)`` and pads (on its device) only
where they fall short of whole certificate groups; the search maps its
local ids and masks its pads by ``share``.

**Overlapped merge**: queries split into ``micro_batches`` blocks inside
ONE traced program. Block i's local fused kernel has no data dependence
on block i−1's merge collectives, so XLA's latency-hiding scheduler is
free to overlap the ICI rounds with the next block's MXU work — the
SPMD analog of the reference's stream-overlapped ``knn_merge_parts``
copy-in. On CPU (the tier-1 suite) the split is correctness-only.

**Query-sharded mode** (``shard_mode="query"``): the serving shape —
index replicated (it fits one chip), queries data-parallel over the
axis, no merge at all. The sharded sibling of
:func:`raft_tpu.distance.fused_l2nn.knn_sharded` but on the fused
certified pipeline with a prepared index.

Everything is CPU-testable under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (interpret-mode
Pallas inside shard_map) and bit-exact against the single-device
:func:`knn_fused` oracle — see tests/test_knn_sharded.py.
"""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.comms import MeshComms
from raft_tpu.core.error import (DeviceError, OutOfMemoryError,
                                 device_errors, expects)
from raft_tpu.core.resources import ensure_resources
from raft_tpu.observability import instrument, span
from raft_tpu.observability.costmodel import (MERGE_STRATEGIES,
                                              choose_merge_strategy)
from raft_tpu.resilience import (PoisonedOutputError, degrade_merge,
                                 fault_point, faults_active,
                                 record_degradation, record_exhausted,
                                 record_retry)
from raft_tpu.distance.knn_fused import (
    _D_SINGLE_SHOT, _DC, _LANES, _PACK_BITS, _PBITS_MAX,
    _Q_CHUNK, DB_DTYPES, GRID_ORDERS, KnnIndex, _knn_fused_core,
    _prepare_ops, _prepare_ops_q8, auto_pack_bits, fit_config,
    fixup_tiers_for, fused_config, prepare_knn_index, rescore_pool_width,
    resolve_db_dtype, resolve_grid_order)
from raft_tpu.observability.quality import record_pending

SHARD_MODES = ("db", "query")

# compiled shard_map programs, keyed by the full static geometry — a
# fresh closure per call would defeat the jit cache (same pattern as
# fused_l2nn._SHARDED_KNN_CACHE)
_SHARDED_FUSED_CACHE: dict = {}


def resolve_merge_strategy(merge: str, p: int, nq: int, k: int) -> str:
    """EFFECTIVE merge strategy for a call — decided (and logged) in the
    non-jitted wrapper like ``resolve_grid_order``, so a downgraded
    request is visible per call. ``"auto"`` takes the ICI cost-model
    crossover; a tournament request on a non-power-of-two shard count
    downgrades to allgather (the butterfly needs a partner every
    round). ``"host"`` — the bottom rung of the collective-failure
    ladder — is also requestable directly: no merge collective at all,
    per-shard candidates gathered and selected on the host."""
    if merge not in ("auto", "host") + MERGE_STRATEGIES:
        raise ValueError(f"merge must be 'auto', 'host' or one of "
                         f"{MERGE_STRATEGIES}, got {merge!r}")
    if merge == "host":
        return merge
    if merge == "auto":
        return choose_merge_strategy(p, nq, k)
    if merge == "tournament" and (p & (p - 1)):
        from raft_tpu.core.logger import log_warn

        log_warn("merge='tournament' needs a power-of-two shard count "
                 "(got p=%d) — using 'allgather' for this call", p)
        return "allgather"
    return merge


def default_micro_batches(nq: int, Qb: int) -> int:
    """Micro-batch count when the caller (or a tuned table) doesn't say:
    enough blocks that merge rounds have a next block to hide behind,
    but never blocks smaller than one kernel query block. Also bounds
    each block at ``_Q_CHUNK`` (the fused pipeline's slot-array
    budget)."""
    if nq <= max(Qb, 8):
        nb = 1
    else:
        nb = min(4, max(1, nq // max(Qb, 8)))
    return max(nb, -(-nq // _Q_CHUNK))


class ShardedFusedIndex:
    """A database-sharded fused-KNN index: the :class:`KnnIndex` operand
    set laid out as row-sharded global arrays over a mesh axis, each
    shard padded to whole certificate groups. Build once with
    :func:`prepare_knn_index_sharded`; query with
    :func:`knn_fused_sharded`. The tiling config, metric and mesh are
    frozen at build time (the per-shard row padding bakes them in).

    Shard ``r`` holds ``rows_per`` rows, of which the first
    ``clip(n_rows - r·share, 0, share)`` are real: global rows
    ``r·share`` onward. The rest are its own pads, which carry the
    never-wins sentinel."""

    def __init__(self, yp_s, y_hi_s, y_lo_s, yyh_s, yy_s, n_rows: int,
                 rows_per: int, share: int, mesh, axis: str, T: int,
                 Qb: int, g: int, passes: int, metric: str, d_orig: int,
                 pbits: int, grid_order: str, db_dtype: str = "bf16",
                 y_q_s=None, scale_s=None, eq_s=None):
        self.yp_s = yp_s                  # [p·rows_per, d_eff] or None
        self.y_hi_s, self.y_lo_s = y_hi_s, y_lo_s
        self.yyh_s, self.yy_s = yyh_s, yy_s
        self.n_rows = n_rows              # true (unpadded) global rows
        self.rows_per = rows_per          # rows per shard (padded)
        self.share = share                # input rows per shard
        self.mesh, self.axis = mesh, axis
        self.T, self.Qb, self.g = T, Qb, g
        self.passes, self.metric = passes, metric
        self.d_orig = d_orig
        self.pbits = pbits
        self.grid_order = grid_order
        # quantized-streaming state (db_dtype="int8"): each shard
        # quantizes ITS groups — scales and the per-group Eq bound are
        # per-shard values, so every shard's certificate widens by its
        # own worst group, never a remote one's
        self.db_dtype = db_dtype
        self.y_q_s = y_q_s                # [p·rows_per, d_eff] int8
        self.scale_s = scale_s            # [p·G_loc, 8, 128] f32
        self.eq_s = eq_s                  # [p·G_loc] f32

    @property
    def stream_width(self) -> int:
        src = self.y_q_s if self.db_dtype == "int8" else self.y_hi_s
        return src.shape[1]

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def row_positions(self) -> np.ndarray:
        """Positions of the real rows in the row-sharded operands, in
        global id order."""
        r, j = np.divmod(np.arange(self.n_rows, dtype=np.int64),
                         self.share)
        return r * self.rows_per + j


def _rows_sharded(y, mesh, axis: str):
    """``(ys, m)``: ``y`` as an f32 ``[p·share, d]`` array with rows split
    over ``mesh[axis]`` (shard ``r`` holds rows ``[r·share,
    (r+1)·share)``), and its real-row count ``m``; the last shards'
    missing rows are zeros.

    An f32 device array already split so is taken as it is. Any other
    device array is converted, padded and resharded on the devices; a
    host input is placed shard by shard."""
    p = int(mesh.shape[axis])
    rows = NamedSharding(mesh, P(axis))
    if not isinstance(y, jax.Array):
        y = np.asarray(y, np.float32)
    m, d = y.shape
    share = -(-m // p)
    if isinstance(y, jax.Array):
        ys = y.astype(jnp.float32)
        if m != p * share:
            ys = jnp.pad(ys, ((0, p * share - m), (0, 0)))
        if not ys.sharding.is_equivalent_to(rows, 2):
            ys = jax.device_put(ys, rows)
        return ys, m

    def block(index):
        lo = index[0].start or 0
        hi = p * share if index[0].stop is None else index[0].stop
        part = y[lo:min(hi, m)]
        if part.shape[0] == hi - lo:
            return part
        return np.concatenate(
            [part, np.zeros((hi - lo - part.shape[0], d), np.float32)])

    return jax.make_array_from_callback((p * share, d), rows, block), m


@instrument("distance.prepare_knn_index_sharded")
def prepare_knn_index_sharded(y, mesh=None, axis: str = "x",
                              passes: int = 3, metric: str = "l2",
                              T: Optional[int] = None,
                              Qb: Optional[int] = None,
                              g: Optional[int] = None,
                              store_yp: bool = True,
                              grid_order: Optional[str] = None,
                              db_dtype: str = "bf16",
                              res=None) -> ShardedFusedIndex:
    """Build a :class:`ShardedFusedIndex` over the rows of ``y`` split
    evenly over ``mesh[axis]``, each shard prepared on its own device.

    ``y`` may be a ``jax.Array`` whose rows are already split over the
    axis (a ``NamedSharding`` with ``P(axis)``): it is taken as it is,
    and nothing of it moves to the host or to another device. Any other
    input is first placed so (:func:`_rows_sharded`). Each shard pads on
    its device to ``rows_per``, a whole number of certificate groups
    (``g·T`` rows for the database-major orders, ``T`` otherwise), only
    where its rows fall short of it; the index-side operand prep (bf16
    hi/lo split, norms, sentinel carrier) then runs per shard inside
    ``shard_map``. Each device holds its share of the input and of the
    index, never the whole of either. The index records the rows per
    shard of the input (``share``), from which each shard's pad rows get
    the never-wins sentinel and its local ids map to global ones.

    The tiling config resolves against the PER-SHARD shape (pack width
    from the shard's tile count — a 10M-row index split 8 ways packs
    like a 1.25M-row one), so per-device kernels run exactly the config
    a single-chip index of that size would."""
    res = ensure_resources(res)
    if mesh is None:
        mesh = res.mesh
    expects(mesh is not None,
            "prepare_knn_index_sharded: pass mesh= or set it on res")
    expects(axis in mesh.axis_names,
            "prepare_knn_index_sharded: axis %r not in mesh axes %s",
            axis, tuple(mesh.axis_names))
    if metric not in ("l2", "ip"):
        raise ValueError(f"prepare_knn_index_sharded: metric must be "
                         f"'l2' or 'ip', got {metric!r}")
    if db_dtype not in DB_DTYPES:
        raise ValueError(f"prepare_knn_index_sharded: db_dtype must be "
                         f"one of {DB_DTYPES}, got {db_dtype!r}")
    ys, m = _rows_sharded(y, mesh, axis)
    d = ys.shape[1]
    p = int(mesh.shape[axis])
    share = ys.shape[0] // p
    dcfg = fused_config(passes, db_dtype)
    T = dcfg.T if T is None else T
    Qb = dcfg.Qb if Qb is None else Qb
    grid_order = dcfg.grid_order if grid_order is None else grid_order
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"prepare_knn_index_sharded: grid_order must "
                         f"be one of {GRID_ORDERS}, got {grid_order!r}")
    if db_dtype == "int8" and grid_order == "query":
        grid_order = "db"      # quantized kernels are database-major
    T, Qb = fit_config(T, Qb, d, passes, g or dcfg.g, grid_order,
                       db_dtype)
    n_tiles_est = max(1, -(-share // T))
    if g is None:
        g = max(dcfg.g, (1 << auto_pack_bits(n_tiles_est, T))
                // (T // _LANES))
    pbits = min(_PBITS_MAX, max(_PACK_BITS, int(math.ceil(math.log2(
        max(g * (T // _LANES), 2))))))
    packed = g * (T // _LANES) <= (1 << pbits)
    grid_order = resolve_grid_order(grid_order, d, packed)
    db_dtype = resolve_db_dtype(db_dtype, d, packed, grid_order,
                                store_yp)
    row_mult = g * T if grid_order in ("db", "dbuf") else T
    rows_per = max(1, -(-share // row_mult)) * row_mult
    dpad = (-d) % (_DC if d > _D_SINGLE_SHOT else _LANES)
    row_spec = (P(axis),)
    padded = rows_per != share or dpad
    if padded:
        with span("distance.shard_pad"):
            ys = jax.jit(jax.shard_map(
                lambda y_loc: jnp.pad(
                    y_loc, ((0, rows_per - share), (0, dpad))),
                mesh=mesh, in_specs=row_spec, out_specs=P(axis)))(ys)

    def _real_rows():
        # a traced value: one program serves every shard
        r = jax.lax.axis_index(axis).astype(jnp.int32)
        return jnp.clip(jnp.int32(m) - r * share, 0, share)

    # a padded copy is the build's own: its buffer may become yp
    donate = (0,) if padded else ()
    if db_dtype == "int8":
        fault_point("quantize_index")

        def _prep_q8(y_loc):
            return _prepare_ops_q8(y_loc, T, g, metric, pbits=pbits,
                                   grid_order=grid_order,
                                   n_valid=_real_rows())

        fn = jax.jit(jax.shard_map(
            _prep_q8, mesh=mesh, in_specs=row_spec,
            out_specs=(P(axis), P(axis), P(axis), P(None, axis),
                       P(None, axis), P(axis)),
            check_vma=False), donate_argnums=donate)
        with span("distance.shard_prep"):
            yp_s, y_q_s, scale_s, yyh_s, yy_s, eq_s = fn(ys)
        return ShardedFusedIndex(yp_s, None, None, yyh_s, yy_s, m,
                                 rows_per, share, mesh,
                                 axis, T, Qb, g, passes, metric, d, pbits,
                                 grid_order, db_dtype="int8",
                                 y_q_s=y_q_s, scale_s=scale_s, eq_s=eq_s)

    def _prep(y_loc):
        return _prepare_ops(y_loc, T, g, metric, pbits=pbits,
                            grid_order=grid_order, n_valid=_real_rows())

    fn = jax.jit(jax.shard_map(
        _prep, mesh=mesh, in_specs=row_spec,
        out_specs=(P(axis), P(axis), P(axis), P(None, axis),
                   P(None, axis)),
        check_vma=False), donate_argnums=donate)
    with span("distance.shard_prep"):
        yp_s, y_hi_s, y_lo_s, yyh_s, yy_s = fn(ys)
    if not store_yp:
        yp_s = None
        if passes == 1:
            y_lo_s = None   # the 1-pass kernel and lite fixup never read it
    return ShardedFusedIndex(yp_s, y_hi_s, y_lo_s, yyh_s, yy_s, m,
                             rows_per, share, mesh,
                             axis, T, Qb, g, passes, metric, d, pbits,
                             grid_order)


def _merge_allgather(comms: MeshComms, p: int, k: int, v, i):
    """All-gather every shard's [nq, k] candidates and select k of p·k.
    Pool order is rank-major per query — identical on every shard, so
    the merged result is replicated bit-for-bit (ties included)."""
    gv = comms.allgather(v)                                # [p, nq, k]
    gi = comms.allgather(i)
    nq = v.shape[0]
    gv = jnp.moveaxis(gv, 0, 1).reshape(nq, p * k)
    gi = jnp.moveaxis(gi, 0, 1).reshape(nq, p * k)
    neg, pos = jax.lax.top_k(-gv, k)
    return -neg, jnp.take_along_axis(gi, pos, axis=1)


def _merge_tournament(comms: MeshComms, p: int, k: int, v, i):
    """log₂(p) butterfly rounds of collective_permute pair-merges, each
    a select over 2k. Concatenation order is (lower mesh index first)
    on BOTH partners, so each round's inputs — and therefore the final
    top-k, ties included — are identical across the pair; by induction
    the result is replicated over the whole axis."""
    rr = comms.get_rank()
    rounds = int(math.log2(p)) if p > 1 else 0
    for j in range(rounds):
        dlt = 1 << j
        perm = [(s, s ^ dlt) for s in range(p)]
        ov = comms.collective_permute(v, perm)
        oi = comms.collective_permute(i, perm)
        low_first = (rr & dlt) == 0                  # traced scalar bool
        cat_v = jnp.where(low_first,
                          jnp.concatenate([v, ov], axis=1),
                          jnp.concatenate([ov, v], axis=1))
        cat_i = jnp.where(low_first,
                          jnp.concatenate([i, oi], axis=1),
                          jnp.concatenate([oi, i], axis=1))
        neg, pos = jax.lax.top_k(-cat_v, k)
        v = -neg
        i = jnp.take_along_axis(cat_i, pos, axis=1)
    return v, i


def _merge_host_pool(gv, gi, k: int):
    """Host-side merge — the bottom rung of the collective-failure
    ladder: the shard_map program returns each shard's LOCAL candidates
    (out_specs sharded over the axis → [p, nq, k] on host), and the
    final select runs outside the SPMD program, with no merge
    collective in the compiled graph at all. Pool order is rank-major
    per query — the exact pool :func:`_merge_allgather` builds — so the
    result is bit-identical to the collective merges, ties included."""
    p, nqp, kk = gv.shape
    pool_v = jnp.moveaxis(gv, 0, 1).reshape(nqp, p * kk)
    pool_i = jnp.moveaxis(gi, 0, 1).reshape(nqp, p * kk)
    neg, pos = jax.lax.top_k(-pool_v, k)
    return -neg, jnp.take_along_axis(pool_i, pos, axis=1)


@instrument("distance.knn_fused_sharded")
def knn_fused_sharded(x, y, k: int, mesh=None, axis: str = "x",
                      shard_mode: str = "db", merge: str = "auto",
                      micro_batches: Optional[int] = None,
                      passes: int = 3, metric: str = "l2",
                      T: Optional[int] = None, Qb: Optional[int] = None,
                      g: Optional[int] = None,
                      grid_order: Optional[str] = None,
                      db_dtype: str = "bf16",
                      rescore: Optional[bool] = None,
                      certify: str = "kernel", store_yp: bool = True,
                      res=None) -> Tuple[jax.Array, jax.Array]:
    """Certified fused brute-force KNN over a device mesh.

    ``shard_mode="db"`` (default): the INDEX rows shard over
    ``mesh[axis]`` — the bigger-than-HBM mode. ``y`` may be a raw
    [m, d] matrix (prepared inline) or a :class:`ShardedFusedIndex`
    (preferred for repeated query batches; its frozen config wins).
    Each shard runs the packed fused kernel over its slice (db-major
    orders stream the shard from HBM once), local ids shift to global
    by the shard's row offset, and per-shard candidates merge with the
    strategy picked by ``merge`` ("auto" = the ICI cost-model
    crossover; see the module doc). ``micro_batches`` splits the query
    batch so block i's local compute can overlap block i−1's merge
    collectives (None = :func:`default_micro_batches`, or a tuned
    table's value via :func:`raft_tpu.tune.sharded.sharded_config`).

    ``shard_mode="query"``: replicated index, data-parallel queries —
    the serving shape. ``y`` may be a raw matrix or a single-device
    :class:`KnnIndex`; ``merge``/``micro_batches`` are ignored (no
    cross-shard candidates exist).

    Returns the same contract as :func:`knn_fused`: (values [nq, k]
    ascending — IP descending —, global ids [nq, k]), exact under the
    same certificates, bit-exact vs the single-device oracle.
    """
    res = ensure_resources(res)
    if shard_mode not in SHARD_MODES:
        raise ValueError(f"knn_fused_sharded: shard_mode must be one "
                         f"of {SHARD_MODES}, got {shard_mode!r}")
    if mesh is None:
        mesh = (y.mesh if isinstance(y, ShardedFusedIndex)
                else getattr(res, "mesh", None))
    expects(mesh is not None,
            "knn_fused_sharded: pass mesh= or set it on res")
    expects(axis in mesh.axis_names,
            "knn_fused_sharded: axis %r not in mesh axes %s", axis,
            tuple(mesh.axis_names))
    p = int(mesh.shape[axis])
    x = jnp.asarray(x, jnp.float32)
    nq = x.shape[0]

    if shard_mode == "query":
        fault_point("sharded_dispatch")
        with device_errors("distance.knn_fused_sharded[query]"):
            return _knn_query_sharded(x, y, k, mesh, axis, passes,
                                      metric, T, Qb, g, grid_order,
                                      rescore, certify, res,
                                      db_dtype=db_dtype)

    if isinstance(y, ShardedFusedIndex):
        idx = y
        expects(idx.axis == axis and idx.mesh == mesh,
                "knn_fused_sharded: index prepared for a different "
                "mesh/axis — re-prepare or pass its mesh")
    else:
        idx = prepare_knn_index_sharded(
            y, mesh=mesh, axis=axis, passes=passes, metric=metric,
            T=T, Qb=Qb, g=g, store_yp=store_yp, grid_order=grid_order,
            db_dtype=db_dtype, res=res)
    m = idx.n_rows
    quant = idx.db_dtype == "int8"
    expects(k <= m, "knn_fused_sharded: k=%d > index size %d", k, m)
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    # per-shard pool envelope: every shard must be able to yield k local
    # candidates (the global top-k is a subset of the per-shard unions)
    n_tiles_loc = idx.rows_per // idx.T
    pool_loc = 2 * (-(-n_tiles_loc // idx.g)) * _LANES
    if k > pool_loc:
        raise NotImplementedError(
            f"knn_fused_sharded: k={k} too large for the per-shard "
            f"candidate pool {pool_loc} (fewer shards, or shrink g/T)")
    if rescore is None:
        rescore = idx.yp_s is not None
    if rescore and idx.yp_s is None:
        raise ValueError("knn_fused_sharded: rescore=True needs a "
                         "yp-storing index (store_yp=True)")
    if certify == "f32" and not rescore:
        raise ValueError("knn_fused_sharded: certify='f32' needs the "
                         "exact rescore (store_yp=True)")
    if quant and not rescore:
        raise ValueError("knn_fused_sharded: an int8-streamed index is "
                         "always exact-rescored")

    # ---- micro-batch request (caller / tuned table / default) -------
    nb_req = micro_batches
    if nb_req is None:
        from raft_tpu.tune.sharded import sharded_config

        tuned = sharded_config(p)
        nb_req = tuned.get("micro_batches") if tuned else None

    d_eff = idx.stream_width
    if x.shape[1] != idx.d_orig:
        raise ValueError(f"knn_fused_sharded: query width {x.shape[1]} "
                         f"!= index {idx.d_orig}")
    if d_eff != x.shape[1]:
        x = jnp.concatenate(
            [x, jnp.zeros((nq, d_eff - x.shape[1]), jnp.float32)], axis=1)

    packed = idx.g * (idx.T // _LANES) <= (1 << idx.pbits)

    has_yp = idx.yp_s is not None
    has_ylo = idx.y_lo_s is not None

    def _geometry(nb, Qb_base):
        """Static query-block geometry for one (micro-batch, Qb)
        attempt — recomputed per ladder rung."""
        nb = (default_micro_batches(nq, Qb_base) if nb is None
              else int(nb))
        nb = max(1, min(nb, nq))
        nb = max(nb, -(-nq // _Q_CHUNK))   # keep blocks under _Q_CHUNK
        qb0 = -(-nq // nb)
        Qb_eff = min(Qb_base, ((qb0 + 7) // 8) * 8)
        qb_len = -(-qb0 // Qb_eff) * Qb_eff
        return nb, Qb_eff, qb_len, nb * qb_len

    def _dispatch(merge_eff, nb_in, Qb_base):
        """Build (or reuse) and run the compiled SPMD program for one
        (merge strategy, micro-batches, Qb) point — the unit the
        degradation ladder retries with different arguments."""
        nb, Qb_eff, qb_len, nq_pad = _geometry(nb_in, Qb_base)
        xq = x
        if nq_pad != nq:
            xq = jnp.concatenate(
                [x, jnp.zeros((nq_pad - nq, d_eff), jnp.float32)])
        key = ("db", mesh, axis, k, idx.T, Qb_eff, idx.g, idx.passes,
               idx.metric, idx.rows_per, idx.share, m, nb, qb_len,
               merge_eff,
               bool(rescore), idx.pbits, certify,
               idx.grid_order, idx.db_dtype, has_yp, has_ylo)
        fn = _SHARDED_FUSED_CACHE.get(key)
        if fn is None:
            comms = MeshComms(axis, size=p)
            merge_fn = {"allgather": _merge_allgather,
                        "tournament": _merge_tournament,
                        "host": None}[merge_eff]
            rows_per, T_, g_ = idx.rows_per, idx.T, idx.g
            share = idx.share
            passes_, metric_, pbits_ = idx.passes, idx.metric, idx.pbits
            order_, dtype_ = idx.grid_order, idx.db_dtype

            def shard_fn(*ops_and_x):
                *ops, xq_l = ops_and_x
                it = iter(ops)
                yp_l = next(it) if has_yp else None
                if quant:
                    yhi_l = ylo_l = None
                    yq_l, scl_l, eq_l = next(it), next(it), next(it)
                else:
                    yq_l = scl_l = eq_l = None
                    yhi_l = next(it)
                    ylo_l = next(it) if has_ylo else None
                yyh_l = next(it)
                yy_l = next(it)
                r = jax.lax.axis_index(axis)
                off = r.astype(jnp.int32) * share
                m_loc = jnp.clip(jnp.int32(m) - off, 0, share)
                out_v, out_i = [], []
                nf = no = jnp.zeros((), jnp.int32)
                # micro-batch pipeline: block b's kernel is independent
                # of block b−1's merge collectives — the scheduler may
                # overlap
                for b in range(nb):
                    xb = jax.lax.slice_in_dim(xq_l, b * qb_len,
                                              (b + 1) * qb_len, axis=0)
                    # margin (4th with_stats output) is DCE'd here: the
                    # sharded out_specs stay (vals, ids, n_fail, n_over)
                    # — per-shard margins would need a gather the
                    # explain plane doesn't ask for
                    vals, ids, nfb, _, nob = _knn_fused_core(
                        xb, yp_l, yhi_l, ylo_l, yyh_l, yy_l,
                        k=k, T=T_, Qb=Qb_eff, g=g_, passes=passes_,
                        metric=metric_, m=rows_per, rescore=rescore,
                        pbits=pbits_, certify=certify,
                        grid_order=order_,
                        db_dtype=dtype_, with_stats=True, y_q=yq_l,
                        y_scale_k=scl_l, eq_groups=eq_l, m_valid=m_loc)
                    nf, no = nf + nfb, no + nob
                    # local → global ids; pad/sentinel candidates (id -1
                    # or non-finite value) must lose every merge
                    gid = jnp.where((ids >= 0) & jnp.isfinite(vals),
                                    ids + off, -1)
                    vals = jnp.where(gid >= 0, vals, jnp.inf)
                    if merge_fn is not None:
                        vals, gid = merge_fn(comms, p, k, vals, gid)
                    out_v.append(vals)
                    out_i.append(gid)
                cat_v = jnp.concatenate(out_v, axis=0)
                cat_i = jnp.concatenate(out_i, axis=0)
                # per-shard certificate-failure and select-overflow
                # counts: rank-major [p] on the host side of the
                # shard_map (quality telemetry)
                if merge_fn is None:   # host merge: per-shard locals out
                    return (cat_v[None], cat_i[None], nf.reshape(1),
                            no.reshape(1))
                return cat_v, cat_i, nf.reshape(1), no.reshape(1)

            if quant:
                # yp + (y_q, scale, eq) — all row/group-sharded
                row_specs = [P(axis)] * 4
            else:
                row_specs = [P(axis)] * (1 + int(has_yp) + int(has_ylo))
            in_specs = tuple(row_specs
                             + [P(None, axis), P(None, axis), P()])
            out_specs = ((P(axis), P(axis), P(axis), P(axis))
                         if merge_eff == "host"
                         else (P(), P(), P(axis), P(axis)))
            fn = jax.jit(jax.shard_map(
                shard_fn, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False))
            _SHARDED_FUSED_CACHE[key] = fn

        if quant:
            operands = [idx.yp_s, idx.y_q_s, idx.scale_s, idx.eq_s,
                        idx.yyh_s, idx.yy_s]
        else:
            operands = [o for o in (idx.yp_s, idx.y_hi_s, idx.y_lo_s)
                        if o is not None] + [idx.yyh_s, idx.yy_s]
        with span("distance.sharded_dispatch"):
            vals, ids, nf, no = fn(*operands, xq)
        if merge_eff == "host":
            vals, ids = _merge_host_pool(vals, ids, k)
        if nq_pad != nq:
            vals, ids = vals[:nq], ids[:nq]
        return vals, ids, nf, no

    # ---- resilience driver ------------------------------------------
    # The fast path is one trip through the loop body with zero extra
    # dispatches; the except arms walk the graceful-degradation ladder
    # (see raft_tpu.resilience.policy): classified OOM → halve Qb,
    # then grow micro-batches; collective failure (device error or
    # injected timeout at the merge) → tournament → allgather → host
    # merge. DeadlineExceededError is never caught here — a deadline
    # is the caller's global budget. Every rung is bit-identical in
    # ids to the undegraded oracle (tests/test_resilience.py).
    _, _, qb_len0, _ = _geometry(nb_req, idx.Qb)
    merge_eff = resolve_merge_strategy(merge, p, qb_len0, k)
    validate = (faults_active()
                or bool(os.environ.get("RAFT_TPU_VALIDATE_OUTPUTS")))
    site = "distance.knn_fused_sharded"
    Qb_base, nb_cur, retries = idx.Qb, nb_req, 0
    while True:
        try:
            poison = fault_point("sharded_dispatch")
            if merge_eff == "tournament":
                fault_point("merge_permute")
            elif merge_eff == "allgather":
                fault_point("merge_allgather")
            with device_errors(site):
                vals, ids, nf_shards, no_shards = _dispatch(
                    merge_eff, nb_cur, Qb_base)
            if poison == "nan":   # simulated kernel-output poisoning
                vals = jnp.full_like(vals, jnp.nan)
            if validate and not bool(jnp.isfinite(vals).all()):
                try:
                    from raft_tpu.resilience import POISONED

                    res.metrics.counter(
                        POISONED, {"site": site},
                        help="Outputs that failed the finiteness "
                             "guard").inc()
                except Exception:
                    pass
                raise PoisonedOutputError(
                    f"{site}: non-finite values in merged top-k")
            break
        except PoisonedOutputError as e:
            retries += 1
            pol = res.resilience.policy_for(site)
            if retries > pol.max_retries:
                record_exhausted(site)
                raise
            record_retry(site, e, retries)
        except OutOfMemoryError:
            nb_now = _geometry(nb_cur, Qb_base)[0]
            if Qb_base > 8:
                new_Qb = max(8, (Qb_base // 2) // 8 * 8)
                record_degradation(site, f"fit:Qb:{Qb_base}->{new_Qb}")
                Qb_base = new_Qb
            elif nb_now < min(nq, 64):
                record_degradation(
                    site,
                    f"fit:micro_batches:{nb_now}->{2 * nb_now}")
                nb_cur = min(nq, 2 * nb_now)
            else:
                record_exhausted(site)
                raise
        except DeviceError as e:
            nxt = degrade_merge(merge_eff)
            if nxt is None:
                record_exhausted(site)
                raise
            record_degradation(site, f"merge:{merge_eff}->{nxt}")
            merge_eff = nxt
    # quality telemetry: the per-shard certificate-failure counts stay
    # a device [p] array here — quality.drain() sums them host-side
    # later (every shard evaluates the certificate over the whole
    # padded query batch)
    try:
        record_pending(
            site, nf_shards, n_queries=p * _geometry(nb_cur, Qb_base)[3],
            pool_width=rescore_pool_width(
                k, -(-n_tiles_loc // idx.g) * _LANES, packed),
            fix_tiers=fixup_tiers_for(idx.rows_per), n_over=no_shards,
            db_dtype=idx.db_dtype, merge=merge_eff, shards=p)
    except Exception:
        pass
    if idx.metric == "ip":
        return -vals, ids           # internal −x·y ascending → IP desc
    return vals, ids


def _knn_query_sharded(x, y, k, mesh, axis, passes, metric, T, Qb, g,
                       grid_order, rescore, certify, res,
                       db_dtype: str = "bf16"):
    """Query-sharded serving mode: replicated prepared index, queries
    row-sharded over the axis, per-shard certified fused pipeline —
    zero cross-shard candidate traffic (each query's top-k depends only
    on the full index)."""
    if isinstance(y, KnnIndex):
        idx = y
    else:
        idx = prepare_knn_index(jnp.asarray(y, jnp.float32),
                                passes=passes, metric=metric, T=T,
                                Qb=Qb, g=g, grid_order=grid_order,
                                db_dtype=db_dtype)
    m = idx.n_rows
    expects(k <= m, "knn_fused_sharded: k=%d > index size %d", k, m)
    nq = x.shape[0]
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32),
                jnp.zeros((0, k), jnp.int32))
    if rescore is None:
        rescore = idx.yp is not None
    p = int(mesh.shape[axis])
    # per-shard query block: a multiple of the kernel block size,
    # bounded at _Q_CHUNK (the fused pipeline's slot-array budget —
    # bigger batches chunk BEFORE the shard_map, like knn_fused's own
    # wrapper)
    qs0 = -(-nq // p)
    if qs0 > _Q_CHUNK:
        step = p * _Q_CHUNK
        outs = [_knn_query_sharded(x[s:s + step], idx, k, mesh, axis,
                                   passes, metric, T, Qb, g, grid_order,
                                   rescore, certify, res)
                for s in range(0, nq, step)]
        return (jnp.concatenate([o[0] for o in outs]),
                jnp.concatenate([o[1] for o in outs]))
    quant = idx.db_dtype == "int8"
    d_eff = idx.stream_width
    if x.shape[1] != idx.d_orig:
        raise ValueError(f"knn_fused_sharded: query width {x.shape[1]} "
                         f"!= index {idx.d_orig}")
    if d_eff != x.shape[1]:
        x = jnp.concatenate(
            [x, jnp.zeros((nq, d_eff - x.shape[1]), jnp.float32)], axis=1)
    Qb_eff = min(idx.Qb, ((qs0 + 7) // 8) * 8)
    qs_len = -(-qs0 // Qb_eff) * Qb_eff
    nq_pad = p * qs_len
    if nq_pad != nq:
        x = jnp.concatenate(
            [x, jnp.zeros((nq_pad - nq, d_eff), jnp.float32)])

    n_tiles = idx.yyh_k.shape[1] // idx.T
    S_pool = -(-n_tiles // idx.g) * _LANES
    packed = idx.g * (idx.T // _LANES) <= (1 << idx.pbits)
    if k > 2 * S_pool:
        raise NotImplementedError(
            f"knn_fused_sharded: k={k} too large for pool {2 * S_pool}")
    has_yp = idx.yp is not None
    has_ylo = idx.y_lo is not None
    key = ("query", mesh, axis, k, idx.T, Qb_eff, idx.g, idx.passes,
           idx.metric, m, qs_len, bool(rescore), idx.pbits, certify,
           idx.grid_order, idx.db_dtype, has_yp, has_ylo)
    fn = _SHARDED_FUSED_CACHE.get(key)
    if fn is None:
        T_, g_, passes_, metric_ = idx.T, idx.g, idx.passes, idx.metric
        pbits_, order_, dtype_ = idx.pbits, idx.grid_order, idx.db_dtype

        def shard_fn(*ops_and_x):
            *ops, xq = ops_and_x
            it = iter(ops)
            yp_l = next(it) if has_yp else None
            if quant:
                yhi_l = ylo_l = None
                yq_l, scl_l, eq_l = next(it), next(it), next(it)
            else:
                yq_l = scl_l = eq_l = None
                yhi_l = next(it)
                ylo_l = next(it) if has_ylo else None
            yyh_l = next(it)
            yy_l = next(it)
            v, i, nf, _, no = _knn_fused_core(
                xq, yp_l, yhi_l, ylo_l, yyh_l, yy_l,
                k=k, T=T_, Qb=Qb_eff, g=g_, passes=passes_,
                metric=metric_, m=m, rescore=rescore, pbits=pbits_,
                certify=certify, grid_order=order_,
                db_dtype=dtype_, with_stats=True, y_q=yq_l,
                y_scale_k=scl_l, eq_groups=eq_l)
            return v, i, nf.reshape(1), no.reshape(1)

        n_repl = (1 + 3 if quant
                  else 1 + int(has_yp) + int(has_ylo)) + 2
        in_specs = tuple([P()] * n_repl + [P(axis)])
        fn = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh, in_specs=in_specs,
            out_specs=(P(axis), P(axis), P(axis), P(axis)),
            check_vma=False))
        _SHARDED_FUSED_CACHE[key] = fn

    from raft_tpu.parallel import replicated

    if quant:
        srcs = (idx.yp, idx.y_q, idx.y_scale_k, idx.eq_groups)
    else:
        srcs = tuple(o for o in (idx.yp, idx.y_hi, idx.y_lo)
                     if o is not None)
    operands = [jax.device_put(o, replicated(mesh)) for o in srcs]
    operands += [jax.device_put(idx.yyh_k, replicated(mesh)),
                 jax.device_put(idx.yy_raw, replicated(mesh))]
    xs = jax.device_put(x, NamedSharding(mesh, P(axis)))
    vals, ids, nf_shards, no_shards = fn(*operands, xs)
    try:
        record_pending(
            "distance.knn_fused_sharded", nf_shards, n_queries=nq_pad,
            pool_width=rescore_pool_width(k, S_pool, packed),
            fix_tiers=fixup_tiers_for(idx.yyh_k.shape[1]),
            n_over=no_shards, db_dtype=idx.db_dtype,
            merge="query_sharded", shards=p)
    except Exception:
        pass
    if nq_pad != nq:
        vals, ids = vals[:nq], ids[:nq]
    if idx.metric == "ip":
        return -vals, ids
    return vals, ids
