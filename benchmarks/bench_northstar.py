#!/usr/bin/env python
"""North-star-shape measurement: fused KNN at 10M×256, k=64.

(round-2 review item 2; the BASELINE.json "metric" shape. Until this runs,
the project's central claim is unevidenced at its own declared scale.)

A 10M×256 f32 index is ~10.2 GB — more than half of v5e's 16 GB HBM
before queries and pool arrays. The measurement therefore uses the LITE
index (``prepare-style`` operands built CHUNK-WISE so the full f32
matrix never materializes): bf16 hi split (5.1 GB) + norm carriers only,
``rescore=False`` results certified against the kernel (bf16) score
function. Auto pack-width (pbits=11 at this scale) keeps the candidate
pool ~5k wide. passes=3 (bf16x3, certified vs the bf16x3 score) is
measured too when HBM admits the lo split.

Writes BENCH_NORTHSTAR.json: GB/s/chip (= Q·M·4 bytes of virtual f32
distance matrix per second, the driver metric's convention), stage
profile, n_fail, and the hardware note (v5e ≈ 819 GB/s HBM / 197 bf16
TFLOP/s — the 1555 GB/s anchor presumes v5p-class silicon).
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_NORTHSTAR.json")


def main():
    dry = gate()

    import jax
    import jax.numpy as jnp

    import raft_tpu
    from raft_tpu.benchmark import Fixture
    from raft_tpu.distance.knn_fused import (
        KnnIndex, _LANES, _PACK_PAD, _knn_fused_core, auto_pack_bits,
        fit_config, knn_fused, split_hi_lo)

    res = raft_tpu.device_resources()
    if dry:
        m, d, n_q, k, n_chunks = 65_536, 256, 256, 64, 2
    else:
        m, d, n_q, k, n_chunks = 10_000_000, 256, 2048, 64, 10

    T = 2048
    n_tiles = -(-m // T)
    M = n_tiles * T
    # the SAME auto pack-width production's prepare_knn_index derives
    pbits = auto_pack_bits(n_tiles, T)
    g = (1 << pbits) // (T // _LANES)

    out = {"shape": [n_q, m, d, k], "T": T, "g": g, "pbits": pbits,
           "hardware": "tpu v5e (1 chip; ~819 GB/s HBM, ~197 bf16 "
                       "TFLOP/s — the 1555 GB/s baseline anchor presumes "
                       "v5p-class)",
           "mode": "lite (store_yp=False, rescore=False): results are "
                   "the certified exact top-k of the kernel score "
                   "function; f32 rescoring is impossible at this scale "
                   "on one chip (the f32 index alone is ~10.2 GB)",
           "stages": {}}

    def flush():
        if not dry:
            with open(OUT, "w") as f:
                json.dump(out, f, indent=1)

    # --- chunk-wise index build (never materializes [M, d] f32) ---
    def build(passes):
        key = jax.random.PRNGKey(0)
        rows_per = m // n_chunks
        his, los, yys = [], [], []
        q_ref = None
        for c in range(n_chunks):
            key, k1, k2 = jax.random.split(key, 3)
            nrow = rows_per if c < n_chunks - 1 else m - rows_per * (
                n_chunks - 1)
            # clustered-ish: shared centers + noise (cheap blobs analog)
            centers = jax.random.normal(jax.random.PRNGKey(7), (64, d)) * 4
            assign = jax.random.randint(k1, (nrow,), 0, 64)
            yc = centers[assign] + jax.random.normal(k2, (nrow, d))
            yc = yc.astype(jnp.float32)
            if c == 0:
                q_ref = yc[:n_q]
            hi, lo = split_hi_lo(yc)
            his.append(hi)
            if passes == 3:
                los.append(lo)
            yys.append(jnp.sum(yc * yc, axis=1))
            del yc
        pad = M - m
        if pad:
            his.append(jnp.zeros((pad, d), jnp.bfloat16))
            if passes == 3:
                los.append(jnp.zeros((pad, d), jnp.bfloat16))
            yys.append(jnp.zeros((pad,), jnp.float32))
        y_hi = jnp.concatenate(his)
        del his
        y_lo = jnp.concatenate(los) if passes == 3 else None
        del los
        yy = jnp.concatenate(yys)[None, :]
        valid = (jnp.arange(M, dtype=jnp.int32) < m)[None, :]
        yyh_k = jnp.broadcast_to(
            jnp.where(valid, 0.5 * yy, _PACK_PAD), (8, M))
        # request the largest query block the stream-kernel VMEM model
        # admits (fit_config only shrinks): bigger Qb amortizes each
        # y-tile DMA over more MXU work (tuned winner at 1M×128)
        Tf, Qb = fit_config(T, 1024, d, passes, g)
        jax.block_until_ready(y_hi)
        idx = KnnIndex(None, y_hi, y_lo, yyh_k, yy, m, Tf, Qb, g,
                       passes, "l2", d, pbits=pbits)
        return idx, q_ref

    fx = Fixture(res=res, reps=3)
    for passes in (1, 3):
        t0 = time.monotonic()
        try:
            idx, Q = build(passes)
            jax.block_until_ready(Q)
            out["stages"][f"build_s_p{passes}"] = round(
                time.monotonic() - t0, 1)
            r = fx.run(lambda q, ix=idx: knn_fused(q, ix, k)[0], Q)
            ms = r["seconds"] * 1e3
            gbps = n_q * m * 4.0 / r["seconds"] / 1e9
            out["stages"][f"e2e_p{passes}"] = {
                "ms": round(ms, 3), "gbps_effective": round(gbps, 2),
                "vs_a100_anchor": round(gbps / 1555.0, 4)}
            # mirror knn_fused's Qb-vs-Q clamp (the direct core call
            # bypasses the wrapper; core requires Q % Qb == 0 — in dry
            # mode n_q can be smaller than the fitted Qb)
            nf = _knn_fused_core(
                Q, None, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw,
                k=k, T=idx.T, Qb=min(idx.Qb, n_q), g=g, passes=passes,
                metric="l2", m=m, rescore=False, pbits=pbits,
                _diag=True)[2]
            out["stages"][f"n_fail_p{passes}"] = int(nf)
            del idx
        except Exception as e:  # noqa: BLE001 — record, try other mode
            out["stages"][f"e2e_p{passes}"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}
        print(json.dumps({f"p{passes}": out["stages"].get(
            f"e2e_p{passes}")}), flush=True)
        flush()

    flush()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
