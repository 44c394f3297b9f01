"""Unexpanded pairwise metrics at scale (round-3 review item 5).

Measures the streaming Pallas kernel (ops/unexpanded_pallas.py) and the
jitted-XLA fused path at the driver shape (2048×1M×128) plus a smaller
anchor, against (a) the expanded-L2 GB/s at the same shape and (b) the
VPU elementwise roofline — the honest ceiling for |x−y| forms on TPU
(no matmul decomposition exists; the reference's contraction substrate
rides GPU FMA throughput instead, contractions.cuh:313).

Writes BENCH_UNEXPANDED.json. Effective GB/s convention matches the
driver: n·m·4 bytes (the f32 distance matrix scanned) per unit time.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                   "BENCH_UNEXPANDED.json")


def main():
    dry = gate()
    results = {"platform": "tpu" if not dry else "cpu-forced",
               "unit": "ms", "representative": not dry}
    import jax
    import jax.numpy as jnp
    import numpy as np

    import raft_tpu
    from raft_tpu.benchmark import Fixture
    from raft_tpu.distance import pairwise_distance
    from raft_tpu.distance.types import DistanceType as DT
    from raft_tpu.ops.unexpanded_pallas import unexpanded_pairwise_tiled

    res = raft_tpu.device_resources()
    fx = Fixture(res=res, reps=3)

    shapes = ([(2048, 1_000_000, 128)] if not dry
              else [(64, 4096, 32)])
    rng = np.random.default_rng(0)
    for (n, m, d) in shapes:
        key = f"{n}x{m}x{d}"
        x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
        jax.block_until_ready((x, y))

        # anchor: expanded L2 (MXU path) at the same shape
        t_l2 = fx.run(lambda a, b: pairwise_distance(res, a, b,
                                                     "sqeuclidean"),
                      x, y)["seconds"]
        results[f"{key}.expanded_l2_ms"] = round(t_l2 * 1e3, 2)
        results[f"{key}.expanded_l2_gbps"] = round(n * m * 4 / t_l2 / 1e9,
                                                   1)

        for metric, mt in (("l1", DT.L1), ("linf", DT.Linf),
                           ("canberra", DT.Canberra),
                           ("hamming", DT.HammingUnexpanded)):
            t_k = fx.run(lambda a, b, mt=mt: unexpanded_pairwise_tiled(
                a, b, mt, 2.0), x, y)["seconds"]
            results[f"{key}.{metric}_kernel_ms"] = round(t_k * 1e3, 2)
            results[f"{key}.{metric}_kernel_gbps"] = round(
                n * m * 4 / t_k / 1e9, 1)

        # the jitted-XLA fused path (fallback), L1 only at scale
        from raft_tpu.distance.pairwise import _unexpanded_jit

        t_x = fx.run(lambda a, b: _unexpanded_jit(a, b, DT.L1, 2.0, d,
                                                  min(n, 256)),
                     x, y)["seconds"]
        results[f"{key}.l1_xla_ms"] = round(t_x * 1e3, 2)
        results[f"{key}.l1_xla_gbps"] = round(n * m * 4 / t_x / 1e9, 1)

        # VPU roofline note: ~3 elementwise f32 ops per (pair, feature)
        ops = 3.0 * n * m * d
        results[f"{key}.l1_vpu_ops"] = ops
        results[f"{key}.l1_kernel_ops_per_s"] = round(
            ops / results[f"{key}.l1_kernel_ms"] * 1e3, 0)

    results["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())
    if not dry:
        # CPU-forced timings must never masquerade as chip numbers
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1)
            f.write("\n")
    print(json.dumps(results))


if __name__ == "__main__":
    main()
