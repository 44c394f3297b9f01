"""recall@k of IVF-Flat against the plain reference at each probe count,
on the configuration's generated data (how ``n_probes`` was chosen):

    python3 bench_suite/tools/probe_curve.py --config sift1m-ivf --seeds 5,6
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from bench_suite import reference, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="sift1m-ivf")
    ap.add_argument("--seeds", default="5")
    ap.add_argument("--probes", default="16,32,64,128")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import raft_tpu
    from raft_tpu.ann import build_ivf_flat, search_ivf_flat
    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    cfg = spec.load_config(args.config)
    res = raft_tpu.DeviceResources(seed=0)
    k = int(cfg["k"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        base, pool = reference.make_data(seed, cfg["data"])
        t0 = time.perf_counter()
        idx = build_ivf_flat(res, base, n_lists=int(cfg["engine"]["n_lists"]))
        build_s = time.perf_counter() - t0
        sizes = np.asarray(idx.sizes)
        _, gt = reference.exact_topk(pool, base, k)
        curve = {}
        for p in [int(x) for x in args.probes.split(",")]:
            ids = np.concatenate([
                np.asarray(search_ivf_flat(res, idx, pool[s:s + 512], k,
                                           n_probes=p)[1])
                for s in range(0, pool.shape[0], 512)])
            hits = sum(len(np.intersect1d(a, b)) for a, b in zip(ids, gt))
            curve[p] = hits / float(gt.size)
        print("PROBE_CURVE " + json.dumps({
            "seed": seed, "build_s": build_s, "recall_at_k": curve,
            "list_rows": [int(sizes.min()), int(np.median(sizes)),
                          int(sizes.max())],
            "probe_window": int(idx.probe_window)}), flush=True)
        del idx, base, pool
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
