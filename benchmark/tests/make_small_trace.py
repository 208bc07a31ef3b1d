#!/usr/bin/env python3
"""Record the small profiler capture the reducer's test reads: a known
program on whatever device JAX finds — three bursts of matrix products
with a sleep of 50 ms between them, so busy, idle and the top operation
are known in advance. Run once on the chip:

    python benchmark/tests/make_small_trace.py chiprun_out/small_trace
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    out = sys.argv[1]
    shutil.rmtree(out, ignore_errors=True)
    x = jnp.ones((1024, 1024), jnp.float32)
    f = jax.jit(lambda a: (a @ a) * 0.001 + 1.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(3):
        y = x
        for _ in range(4):
            y = f(y)
        y.block_until_ready()
        time.sleep(0.05)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    print("device", jax.devices()[0].device_kind, "trace", pb,
          [os.path.getsize(p) for p in pb])
    for p in glob.glob(os.path.join(out, "**", "*.json.gz"),
                       recursive=True):
        os.remove(p)
    return 0


if __name__ == "__main__":
    sys.exit(main())
