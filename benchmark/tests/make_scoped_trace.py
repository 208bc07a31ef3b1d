#!/usr/bin/env python3
"""Record the small SCOPED profiler capture that phase_reduce's test
reads: a known program with named scopes, run as three "frames" under
the profiler annotations the serve loop writes, on whatever device JAX
finds. Per frame: one run of a jitted program whose ``gw.aoi`` scope
does 8 matrix products (6 of them under ``gw.aoi/gw.aoi.gather``) and
whose ``gw.sync`` scope does 2, so ``gw.sync`` is a quarter of
``gw.aoi`` and the gather three quarters of it; then a sleep of 30 ms
under ``gw.decode_fanout`` inside ``gw.frame``, and one of 50 ms under
``gw.pacing_sleep`` outside it. Run once on the chip:

    python benchmark/tests/make_scoped_trace.py chiprun_out/scoped_trace

and copy the ``.xplane.pb`` to
``benchmark/tests/data/scoped/small_scoped_tpu_v5e.xplane.pb`` (a
directory of its own: ``test_trace_reduce.py`` takes whichever capture
it finds in ``data/`` itself). The last lines print what
``phase_reduce.py`` reads from it.
"""
import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def program(a):
    with jax.named_scope("gw.aoi"):
        with jax.named_scope("gw.aoi.gather"):
            for _ in range(6):
                a = (a @ a) * 0.001 + 1.0
        for _ in range(2):
            a = (a @ a) * 0.001 + 1.0
    with jax.named_scope("gw.sync"):
        for _ in range(2):
            a = (a @ a) * 0.001 + 1.0
    return a * 0.5


def main() -> int:
    out = sys.argv[1]
    shutil.rmtree(out, ignore_errors=True)
    x = jnp.ones((1024, 1024), jnp.float32)
    f = jax.jit(program)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    for tick in range(4):
        with TraceAnnotation("gw.frame", tick=tick):
            with TraceAnnotation("gw.device_step"):
                y = f(x)
            with TraceAnnotation("gw.fetch_outputs"):
                y.block_until_ready()
            with TraceAnnotation("gw.decode_fanout"):
                time.sleep(0.03)
        with TraceAnnotation("gw.pacing_sleep"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    print("device", jax.devices()[0].device_kind, "trace", pb,
          [os.path.getsize(p) for p in pb])
    for p in glob.glob(os.path.join(out, "**", "*.json.gz"),
                       recursive=True):
        os.remove(p)
    import phase_reduce

    planes = phase_reduce.read_raw(pb[0])
    for p in planes:
        for ln in p["lines"]:
            paths = sorted({ev[3].get("tf_op", "") for ev in ln["events"]
                            if ev[3].get("tf_op")})
            print("plane", p["name"], "line", ln["name"],
                  len(ln["events"]), "events; tf_op paths:", paths[:12])
    print(json.dumps(phase_reduce.reduce_phases(planes), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
