#!/usr/bin/env python
"""MNMG collective bus-bandwidth sweep — BASELINE config 5.

(ref: cpp/include/raft/comms/detail/test.hpp:31-133 — the reference's
allreduce/allgather test battery; nccl-tests bus-BW conventions.)

Measures jit-compiled DEVICE collectives (``shard_map`` + ``lax.psum`` /
``lax.all_gather`` over a mesh axis — the path that actually rides ICI),
NOT the host-staged HostComms wrappers: round 2's config-5 row timed
HostComms on one device and recorded a meaningless 3.3 s "allreduce"
(host staging + transfer, not a collective). Sweep: sizes ×
{allreduce, allgather}, nccl-tests formulas:

  allreduce: busbw = 2·S·(n−1)/n / t   (S = per-rank buffer bytes)
  allgather: busbw = S_out·(n−1)/n / t (S_out = gathered bytes)

Artifact: ``BUSBW_BENCH.json`` with ``representative: true`` ONLY on
real multi-chip TPU hardware; on the virtual 8-device CPU mesh or a
single chip the numbers are code-path timings, recorded for harness
validation. The day a multi-chip slice appears this script is config 5
in one command:  ``python benchmarks/bench_busbw.py``.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._common import gate  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BUSBW_BENCH.json")
BUDGET_S = float(os.environ.get("BUSBW_BUDGET_S", "900"))


def main():
    dry = gate()

    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import numpy as np

    import raft_tpu
    from raft_tpu.benchmark import Fixture

    res = raft_tpu.device_resources()
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("x",))
    multi_chip = devices[0].platform == "tpu" and n > 1

    # per-rank buffer sizes (bytes); small sizes escalate reps to stay
    # above the transport RTT floor
    if dry or devices[0].platform != "tpu":
        sizes = [1 << 18, 1 << 20]
    else:
        sizes = [1 << 20, 4 << 20, 16 << 20, 64 << 20, 256 << 20]

    ar_fn = jax.jit(jax.shard_map(
        lambda a: jax.lax.psum(a, "x"), mesh=mesh,
        in_specs=P("x", None), out_specs=P("x", None)))
    # each shard emits its full gathered copy (global [n·n, L]) — the
    # per-device memory an allgather implies anyway; out_specs stay
    # sharded so no statically-inferred-replication check is needed
    ag_fn = jax.jit(jax.shard_map(
        lambda a: jax.lax.all_gather(a, "x", axis=0, tiled=True),
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)))
    if devices[0].platform != "tpu":
        # the CPU in-process communicator deadlocks (rendezvous abort)
        # when Fixture's unblocked reps put several sharded executions
        # in flight at once — serialize each rep on host platforms
        def _serial(f):
            return lambda a: jax.block_until_ready(f(a))

        ar_fn, ag_fn = _serial(ar_fn), _serial(ag_fn)

    rows = []
    out = {"n_devices": n, "platform": devices[0].platform,
           "representative": multi_chip, "dry_run": dry,
           "convention": "nccl-tests", "rows": rows}
    deadline = time.monotonic() + BUDGET_S

    def flush():
        if not dry:
            with open(OUT, "w") as f:
                json.dump(out, f, indent=1)

    sharding = NamedSharding(mesh, P("x", None))
    for nbytes in sizes:
        if time.monotonic() > deadline:
            break
        per_rank_elems = nbytes // 4
        xs = jax.device_put(
            jnp.ones((n, per_rank_elems), jnp.float32), sharding)
        jax.block_until_ready(xs)
        reps = max(3, min(96, int((4 << 20) / max(nbytes, 1) * 12)))
        fx = Fixture(res=res, reps=reps)
        for op, fn in (("allreduce", ar_fn), ("allgather", ag_fn)):
            try:
                t = fx.run(fn, xs)["seconds"]
                if op == "allreduce":
                    busbw = 2.0 * nbytes * (n - 1) / n / t
                else:
                    busbw = nbytes * n * (n - 1) / n / t
                row = {"op": op, "bytes_per_rank": nbytes, "reps": reps,
                       "ms": round(t * 1e3, 4),
                       "algbw_gbps": round(nbytes / t / 1e9, 3),
                       "busbw_gbps": round(busbw / 1e9, 3)}
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                row = {"op": op, "bytes_per_rank": nbytes,
                       "error": f"{type(e).__name__}: {e}"[:300]}
            rows.append(row)
            print(json.dumps(row), flush=True)
            flush()

    flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
