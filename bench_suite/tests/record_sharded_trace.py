"""Record the small four-chip trace that ``test_sharded_cell.py`` reads
(``data/v5e4_sharded_tiny.xplane.pb.gz``):

    python3 bench_suite/tests/record_sharded_trace.py <out.xplane.pb[.gz]>

on a host with four chips: the sharded exact search
(``distance.prepare_knn_index_sharded`` over a base split by rows, then
``distance.knn_fused_sharded`` at k=100 and its defaults) over 4 x 65,536
rows of 128 f32, three 2048-query batches, each in a ``bench.step``
span, inside the ``bench.window`` span."""

import glob
import gzip
import os
import shutil
import sys
import tempfile

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROWS_PER_CHIP, DIM, BATCH, K = 65536, 128, 2048, 100


def main(out: str) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from raft_tpu import distance

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("rows",))
    base = jax.jit(
        lambda key: jax.random.normal(key, (4 * ROWS_PER_CHIP, DIM)),
        out_shardings=NamedSharding(mesh, P("rows")))(jax.random.key(0))
    queries = jax.device_put(
        np.random.default_rng(1).normal(size=(BATCH, DIM)).astype(
            np.float32), NamedSharding(mesh, P()))
    index = distance.prepare_knn_index_sharded(base, mesh=mesh, axis="rows")

    def step():
        d, i = distance.knn_fused_sharded(queries, index, K, mesh=mesh,
                                          axis="rows")
        return jax.block_until_ready((d, i))

    step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                step()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    with open(src, "rb") as f_in, (gzip.open(out, "wb") if out.endswith(".gz")
                                   else open(out, "wb")) as f_out:
        shutil.copyfileobj(f_in, f_out)
    shutil.rmtree(d)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
