#!/usr/bin/env python3
"""Record the small trace ``test_chip_trace.py`` reduces (run on the chip).

    python3 benchmarks/chip/tests/record_trace.py <out.xplane.pb>

Inside a ``bench.window`` span: five calls of a Pallas kernel named
``probe_kernel`` (each in a ``bench.call`` span, ended by
``block_until_ready``), each call after a 50 ms host sleep in a
``bench.sleep`` span, and one more sleep before the window closes.  So
every call lies well inside the window, the device is idle for at least
0.3 s of it, and that idle time lies in ``bench.sleep``.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CALLS, SLEEP_S = 5, 0.05


def probe(x):
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0
    return pl.pallas_call(kernel, name="probe_kernel",
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def main() -> int:
    out = sys.argv[1]
    f = jax.jit(probe)
    x = jnp.ones((512, 1024), jnp.float32)
    f(x).block_until_ready()                      # compile outside
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(CALLS):
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(SLEEP_S)
            with jax.profiler.TraceAnnotation("bench.call"):
                x = f(x)
                x.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    shutil.copy(path, out)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
