"""Record the small v5e trace that ``test_trace.py`` reads:

    python3 bench_suite/tests/record_trace.py <out.xplane.pb>

on the chip: a few steps of a small jitted program, each in a
``bench.step`` span, inside the ``bench.window`` span, with host sleeps
between them so the device has idle gaps."""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    step = jax.jit(lambda a: jnp.tanh(a @ a) + 1.0)
    a = jnp.ones((256, 256), jnp.float32)
    step(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                step(a).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(d)
    print(f"{out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
