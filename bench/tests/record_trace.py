#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction test reads, on one
chip:

    python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Inside the ``bench.window`` annotation: 50 ms with nothing dispatched,
four runs of a jitted program named ``train_step`` (a chain of 1024 x 1024
bfloat16 matrix products), 200 ms with nothing on the device, four more
runs, and 50 ms more. (The trace puts the device's events about a
millisecond ahead of the host's; the margins keep every run inside.)
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def train_step(x):
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x


def main(out):
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    f = jax.jit(train_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(0.05)
            for _ in range(4):
                x = f(x)
            x.block_until_ready()
            time.sleep(0.2)
            for _ in range(4):
                x = f(x)
            x.block_until_ready()
            time.sleep(0.05)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        shutil.copy(path, out)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
