"""What a row-sharded base costs on a host's chips, before a cell asks
for it (the benchmark's own runs never run this):

    python3 bench_suite/tools/shard_probe.py --chips 4 --rows 25165824 \\
        [--check-rows 16384] [--k 100] [--seed 1] [--build]

One process on ``--chips`` TPUs, the ``sift1m-exact`` data with
``--rows`` rows placed ``row_sharded`` (``reference.make_data``):

- the generation's seconds, and each device's bytes in use and peak
  after it;
- the check's reference and true distances (``check.reference_answers``
  and ``check.numbers``, as ``check.evaluate`` runs them) for
  ``--check-rows`` distinct query rows at ``--k``: seconds, and each
  device's peak after them;
- each device's last row block, bit for bit against that block drawn on
  the first device alone (``reference.row_block``);
- with ``--build``, the program's sharded index build
  (``distance.prepare_knn_index_sharded``) on that base, as it stands:
  seconds, each device's peak, and the error if it fails.

The result is the last stdout line, ``PROBE {...}``.
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

from bench_suite import check, reference, spec  # noqa: E402

_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def memory(devices) -> list:
    """Each device's bytes in use, peak and limit, where it reports them."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({k: stats.get(k) for k in _MEMORY_KEYS})
    return out


def probe(devices, data: dict, seed: int, check_rows: int, k: int,
          build: bool = False) -> dict:
    """The readings listed in the module docstring, as one dict."""
    import jax

    data = dict(data, placement=reference.ROW_SHARDED,
                n_pool=max(int(data["n_pool"]), check_rows))
    block = reference.ROW_BLOCK
    out = {"n_rows": int(data["n_rows"]), "dim": int(data["dim"]),
           "devices": len(devices), "row_block": block,
           "check_rows": check_rows, "k": k}
    t0 = time.perf_counter()
    base, pool = reference.make_data(seed, data, devices)
    jax.block_until_ready((base, pool))
    out["generate_s"] = time.perf_counter() - t0
    out["memory_after_generate"] = memory(devices)
    out["shard_rows"] = [[first, int(x.shape[0]), str(x.sharding)]
                         for first, x in reference.row_shards(base)]

    rows = np.arange(check_rows)
    t1 = time.perf_counter()
    ref_d, ref_i = check.reference_answers(base, pool, rows, k)
    t2 = time.perf_counter()
    values = check.numbers(base, pool, check.Answers(rows, ref_d, ref_i),
                           ref_i, ["dist_err", "rank_gap"])
    t3 = time.perf_counter()
    out.update(check_reference_s=t2 - t1, check_true_distances_s=t3 - t2,
               check_s=t3 - t1, check_values=values,
               memory_after_check=memory(devices))

    same = []
    for first, x in reference.row_shards(base):
        b = (first + int(x.shape[0])) // block - 1
        alone = np.asarray(reference.row_block(data, b, devices[0]))
        same.append(bool(np.array_equal(np.asarray(x[-block:]), alone)))
    out["last_block_bit_identical"] = same

    if build:
        from jax.sharding import Mesh

        from raft_tpu.distance import prepare_knn_index_sharded

        t4 = time.perf_counter()
        try:
            index = prepare_knn_index_sharded(
                base, mesh=Mesh(np.array(devices), ("x",)), axis="x")
            arrays = [a for a in vars(index).values()
                      if isinstance(a, jax.Array)]
            jax.block_until_ready(arrays)
            out["build_s"] = time.perf_counter() - t4
            out["index_bytes_per_device"] = [
                sum(s.data.nbytes for a in arrays for s in a.addressable_shards
                    if s.device == d) for d in devices]
        except Exception as e:  # an out-of-memory is a reading here
            out["build_s"] = time.perf_counter() - t4
            out["build_error"] = f"{type(e).__name__}: {e}"[:600]
        out["memory_after_build"] = memory(devices)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--rows", type=int, default=24 * reference.ROW_BLOCK)
    ap.add_argument("--check-rows", type=int, default=16384)
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--build", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench_suite import run

    devices, _ = run.device_check(args.chips)
    data = dict(spec.load_config("sift1m-exact")["data"], n_rows=args.rows)
    out = probe(devices, data, args.seed, args.check_rows, args.k,
                args.build)
    out["device_kind"] = devices[0].device_kind
    print("PROBE " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
