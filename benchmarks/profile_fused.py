#!/usr/bin/env python
"""Stage-by-stage profile of the fused KNN pipeline on real TPU.

The tune sweep (benchmarks/tune_fused.py) measures the END-TO-END
pipeline; this script decomposes it so kernel engineering targets the
actual bottleneck instead of a guess. Stages, each timed separately:

  matmul_*        the raw MXU contraction at the same shape (roofline)
  kernel_grp_p1/p3  fused_l2_group_topk alone (the production kernel:
                    in-kernel group fold), 1- and 3-pass
  kernel_slot_p1    the retired per-(tile,lane) slot kernel (comparison)
  kernel_slot_minonly  slot kernel, min-fold only (bounds fold cost)
  post            pool top_k + exact rescore (XLA)
  full            knn_fused end-to-end

The non-dry config is ``fused_defaults()`` — the config production
``knn_fused`` actually ships. Writes PROFILE_FUSED.json (repo root)
incrementally. Fails without a TPU; JAX_PLATFORMS=cpu runs a tiny-shape
harness validation (no artifact).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

BUDGET_S = float(os.environ.get("PROFILE_FUSED_BUDGET_S", "1800"))
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "PROFILE_FUSED.json")


def main():
    dry = gate()

    import jax
    import jax.numpy as jnp

    import raft_tpu
    from raft_tpu.benchmark import Fixture
    from raft_tpu.distance.knn_fused import fused_defaults, knn_fused
    from raft_tpu.ops import fused_l2_topk_pallas as F
    from raft_tpu.random import RngState, make_blobs

    res = raft_tpu.device_resources()
    from raft_tpu.distance.knn_fused import fit_config
    T, Qb, g = fused_defaults(3)   # production exactness mode's config
    T, Qb = fit_config(T, Qb, 128, 3, g)   # what production actually runs
    if dry:
        n_index, dim, n_q, k = 16_384, 128, 256, 64
        T, Qb = 2048, 256
    else:
        n_index, dim, n_q, k = 1_000_000, 128, 2048, 64

    X, _ = make_blobs(res, RngState(0), n_index, dim, n_clusters=64,
                      cluster_std=2.0)
    Q = X[:n_q]
    jax.block_until_ready(X)
    fx = Fixture(res=res, reps=3)

    # padded operands exactly as _knn_fused prepares them
    m = n_index
    M = ((m + T - 1) // T) * T
    yp = jnp.concatenate(
        [X, jnp.zeros((M - m, dim), jnp.float32)]) if M > m else X
    y_hi, y_lo = F.split_hi_lo(yp)
    xx = jnp.sum(Q * Q, axis=1, keepdims=True)
    yy = jnp.sum(yp * yp, axis=1)[None, :]
    m_real = jnp.full((1,), m, jnp.int32)
    jax.block_until_ready((y_hi, y_lo, xx, yy))

    out = {"shape": [n_q, n_index, dim, k], "T": T, "Qb": Qb, "g": g,
           "stages": {}}
    deadline = time.monotonic() + BUDGET_S

    def record(name, fn, *args):
        if time.monotonic() > deadline:
            return
        try:
            r = fx.run(fn, *args)
            out["stages"][name] = {"ms": round(r["seconds"] * 1e3, 3)}
        except Exception as e:
            out["stages"][name] = {
                "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps({name: out["stages"][name]}), flush=True)
        if not dry:
            with open(OUT, "w") as f:
                json.dump(out, f, indent=1)

    # --- roofline: the raw bf16 contraction, XLA-tiled. The full
    # [Q, M] f32 score matrix is ~8 GB at the production shape (it OOM'd
    # HBM and poisoned every later stage in round 2's first battery run)
    # — so stream it: scan over M-chunks with a min-reduce carry, the
    # shape of work the fused kernel actually replaces. ---
    CH = 131072 if not dry else 8192
    n_ch = M // CH   # y3 slicing truncates the (measurement-only) tail

    @jax.jit
    def raw_matmul_streamed(x, yh):
        xb = x.astype(jnp.bfloat16)

        def step(carry, ych):
            s = jax.lax.dot_general(
                xb, ych, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jnp.minimum(carry, jnp.min(s, axis=1)), None

        y3 = yh[:n_ch * CH].reshape(n_ch, CH, yh.shape[1])
        out, _ = jax.lax.scan(step, jnp.full((x.shape[0],), jnp.inf), y3)
        return out

    if n_ch:
        record("matmul_streamed", raw_matmul_streamed, Q, y_hi)
    # pure-MXU point at a 1-GB-output sub-shape, scale ×(M/CH) mentally
    @jax.jit
    def raw_matmul_sub(x, yh):
        return jax.lax.dot_general(
            x.astype(jnp.bfloat16), yh[:CH],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    record("matmul_sub131k", raw_matmul_sub, Q, y_hi)

    # --- the Pallas kernels alone: the production group-fold kernel
    # (top-2+3rd per (lane, tile-group) folded IN-KERNEL) and, for
    # comparison, the retired per-(tile,lane) slot kernel whose XLA-side
    # group fold motivated the redesign ---
    # group kernels fold the half-score yy/2 − x·y; [8, M] carrier with
    # a "never wins" sentinel on padded columns (the kernel does no
    # masking of its own — half-score 0 there would beat real
    # candidates): +inf for the unpacked kernels, the finite _PACK_PAD
    # for the packed ones (id bits OR'd into +inf would make NaN)
    valid_cols = (jnp.arange(M) < m)[None, :]
    yyh = jnp.broadcast_to(
        jnp.where(valid_cols, 0.5 * yy, jnp.inf), (8, M))
    yyh_pck = jnp.broadcast_to(
        jnp.where(valid_cols, 0.5 * yy, F._PACK_PAD), (8, M))
    # production path: packed-id STREAMED fold (the kernel knn_fused
    # ships — the big-matmul variant VMEM-rejects at stream-tuned
    # configs like (4096, 512))
    pair_ok = (T // 128) % 2 == 0
    record("kernel_pck_p1", lambda *a: F.fused_l2_group_topk_packed(
        *a, T=T, Qb=Qb, passes=1, tpg=g, stream=True, pair=pair_ok),
        Q, y_hi, y_lo, yyh_pck, m_real)
    record("kernel_pck_p3", lambda *a: F.fused_l2_group_topk_packed(
        *a, T=T, Qb=Qb, passes=3, tpg=g, stream=True),
        Q, y_hi, y_lo, yyh_pck, m_real)
    # legacy comparison kernels at a FIXED known-compiling config (their
    # [Qb, T] score buffers reject the stream-tuned configs)
    Tl, Qbl = 2048, 256
    record("kernel_grp_p1", lambda *a: F.fused_l2_group_topk(
        *a, T=Tl, Qb=Qbl, passes=1, tpg=g), Q, y_hi, y_lo, yyh, m_real)
    record("kernel_grp_p3", lambda *a: F.fused_l2_group_topk(
        *a, T=Tl, Qb=Qbl, passes=3, tpg=g), Q, y_hi, y_lo, yyh, m_real)
    record("kernel_slot_p1", lambda *a: F.fused_l2_slot_topk(
        *a, T=Tl, Qb=Qbl, passes=1), Q, y_hi, y_lo, xx, yy, m_real)
    record("kernel_slot_minonly", lambda *a: F.fused_l2_slot_topk(
        *a, T=Tl, Qb=Qbl, passes=1, track=False), Q, y_hi, y_lo, xx, yy,
        m_real)

    # --- post-stage on materialized kernel outputs (skipped — not
    # fatal — if the raw kernel fails: full_p1/p3 below go through
    # knn_fused's shrink guard and can still succeed) ---
    grp = None
    try:
        grp = jax.block_until_ready(F.fused_l2_group_topk(
            Q, y_hi, y_lo, yyh, m_real, T=Tl, Qb=Qbl, passes=1, tpg=g))
    except Exception as e:
        out["stages"]["post"] = {
            "error": f"kernel for post-stage inputs failed: "
                     f"{type(e).__name__}: {e}"[:300]}

    @jax.jit
    def post(a1, id1, a2, id2, x, y, xx):
        pool_v = jnp.concatenate([a1, a2], axis=1)
        pool_id = jnp.concatenate([id1, id2], axis=1)
        C = min(k + 32, pool_v.shape[1])
        neg_top, pos = jax.lax.top_k(-pool_v, C)
        cand_pid = jnp.take_along_axis(pool_id, pos, axis=1)
        yc = jnp.take(y, jnp.maximum(cand_pid, 0), axis=0)
        d2c = (xx + jnp.sum(yc * yc, axis=2)
               - 2.0 * jnp.einsum("qd,qcd->qc", x, yc,
                                  precision=jax.lax.Precision.HIGHEST))
        neg_k, ord_k = jax.lax.top_k(-d2c, k)
        return -neg_k, jnp.take_along_axis(cand_pid, ord_k, axis=1)

    if grp is not None:
        a1g, id1g, a2g, id2g, _ = grp
        record("post", post, a1g, id1g, a2g, id2g, Q, X, xx)

    # packed post: pool top_k on packed values + decode + exact rescore
    # (the production post — no id arrays, no pool-id gather)
    try:
        # xxh folded like production (knn_fused: packed values are d2/2)
        # — the cert stage compares bound vs theta in the SAME units
        pck = jax.block_until_ready(F.fused_l2_group_topk_packed(
            Q, y_hi, y_lo, yyh_pck, m_real, T=T, Qb=Qb, passes=1, tpg=g,
            stream=True, pair=pair_ok, xxh=0.5 * xx))
    except Exception:
        pck = None

    if pck is not None and time.monotonic() < deadline:
        from raft_tpu.distance.knn_fused import (
            _PACK_BITS, _POOL_PAD, _pool_smallest, decode_packed_pool,
            pool_select_algo, resolve_pool_algo)

        a1p_m, a2p_m = pck[0], pck[1]
        S_ = a1p_m.shape[1]
        Ca = min(k + _POOL_PAD, S_)
        C = min(k + _POOL_PAD, 2 * Ca)
        # resolve the envelope like production's wrapper, so the profile
        # labels the algorithm that actually ran
        algo = resolve_pool_algo(pool_select_algo(), S_, Ca)

        # sub-stages mirror knn_fused's PRODUCTION twin-pool post
        # (top_k over a1p only + twin pull — NOT the old 2S'-wide
        # concat), each jitted separately so the budget shows every ms.
        # sel_stage returns the Ca-th a1 value production's certificate
        # reuses — cert_stage must NOT re-run the selection (it would
        # double-count the most expensive post op in the budget)
        @jax.jit
        def sel_stage(a1p, a2p):
            a1_sel, pos1 = _pool_smallest(a1p, Ca, algo)
            a2_sel = jnp.take_along_axis(a2p, pos1, axis=1)
            cands = jnp.concatenate([a1_sel, a2_sel], axis=1)
            cpos = jnp.concatenate([pos1, pos1], axis=1)
            neg, sel = jax.lax.top_k(-cands, C)
            return (-neg, jnp.take_along_axis(cpos, sel, axis=1),
                    a1_sel[:, Ca - 1])

        cand_p, pos, a1_last = jax.block_until_ready(
            sel_stage(a1p_m, a2p_m))

        @jax.jit
        def decode_stage(cp, ps):
            return decode_packed_pool(cp, ps, S_, T, g)

        pid = jax.block_until_ready(decode_stage(cand_p, pos))

        @jax.jit
        def rescore_stage(p_id, x, y, xx):
            yc = jnp.take(y, jnp.minimum(jnp.maximum(p_id, 0),
                                         y.shape[0] - 1), axis=0)
            d2c = (xx + jnp.sum(yc * yc, axis=2)
                   - 2.0 * jnp.einsum(
                       "qd,qcd->qc", x, yc,
                       precision=jax.lax.Precision.HIGHEST))
            neg_k, ord_k = jax.lax.top_k(
                -jnp.where(p_id >= 0, d2c, jnp.inf), k)
            return -neg_k, jnp.take_along_axis(p_id, ord_k, axis=1)

        @jax.jit
        def cert_stage(cp, vals, a3p, a1_c):
            # marginal production cost only: bounds from the ALREADY
            # selected values + the per-query pack-error margin
            # (knn_fused.py half_mag/e_pack), same d2 units as theta
            # (the kernel above folds xxh like production)
            theta = vals[:, k - 1]
            bound_a1 = 2.0 * a1_c
            a3_half_min = jnp.min(a3p, axis=1)
            a3_min = jnp.minimum(2.0 * a3_half_min, bound_a1)
            bound = jnp.minimum(a3_min, 2.0 * cp[:, C - 1])
            half_mag = jnp.maximum(
                jnp.maximum(jnp.abs(cp[:, 0]), jnp.abs(cp[:, C - 1])),
                jnp.maximum(jnp.abs(a3_half_min), jnp.abs(a1_c)))
            e_pack = 8.0 * half_mag * 2.0 ** (_PACK_BITS - 23)
            return jnp.sum((bound < theta + e_pack).astype(jnp.int32))

        record(f"post_sel[{algo}]", sel_stage, a1p_m, a2p_m)
        record("post_decode", decode_stage, cand_p, pos)
        record("post_rescore", rescore_stage, pid, Q, X, xx)
        if time.monotonic() < deadline:
            vals_r = jax.block_until_ready(
                rescore_stage(pid, Q, X, xx))[0]
            record("post_cert", cert_stage, cand_p, vals_r, pck[2],
                   a1_last)

        @jax.jit
        def post_packed(a1p, a2p, x, y, xx):
            cp, ps, _ = sel_stage(a1p, a2p)
            p_id = decode_stage(cp, ps)
            return rescore_stage(p_id, x, y, xx)

        record("post_packed", post_packed, a1p_m, a2p_m, Q, X, xx)

    # --- end-to-end at the shipped defaults ---
    record("full_p1", lambda q: knn_fused(q, X, k=k, passes=1)[0], Q)
    record("full_p3", lambda q: knn_fused(q, X, k=k, passes=3)[0], Q)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
