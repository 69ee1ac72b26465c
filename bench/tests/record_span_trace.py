#!/usr/bin/env python3
"""Record the small profiler trace the span tests read, on one chip:

    python3 bench/tests/record_span_trace.py bench/tests/data/span.xplane.pb

The trace of ``record_trace.py`` with the program's tracer on: inside the
``bench.window`` annotation, 50 ms with nothing dispatched, four runs of a
jitted ``train_step``, 200 ms with nothing on the device inside one
`repro.obs` span (``data.batch``, which the tracer mirrors into the
profiler's host plane), four more runs, and 50 ms more. A tracer instant
named ``bench.window`` is emitted as well: instants are not mirrored, so
the trace still holds one annotation of that name.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import obs  # noqa: E402


def train_step(x):
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x


def main(out):
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_span_trace: no TPU")
    f = jax.jit(train_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with obs.enabled_session() as ob, \
            tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            ob.tracer.instant("bench.window")
            time.sleep(0.05)
            for _ in range(4):
                x = f(x)
            x.block_until_ready()
            with ob.tracer.span("data.batch", args={"step": 1}):
                time.sleep(0.2)
            for _ in range(4):
                x = f(x)
            x.block_until_ready()
            time.sleep(0.05)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        shutil.copy(path, out)
        span, = [e for e in ob.tracer.events() if e["name"] == "data.batch"]
    print(out, os.path.getsize(out), "data.batch", span["dur"], "us")


if __name__ == "__main__":
    main(sys.argv[1])
