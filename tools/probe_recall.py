"""approx top-k RECALL probe (VERDICT r4 weak #4 / next-step #6).

The "approx" top-k ranking rides ``lax.approx_min_k``
(recall_target=0.98 per call) — on TPU it may MISS a true neighbor;
on CPU the lowering is exact, so CPU runs only prove the plumbing.
This probe measures the ACTUAL neighbor-set recall of
``topk_impl="approx"`` against the exact "sort" ranking at bench
density, on whichever platform it runs:

    recall = |approx_neighbors ∩ exact_neighbors| / |exact_neighbors|

aggregated over all entities and several tick states. Run it in the
TPU window (detached, never timeout-wrapped) to close the open
question of whether approx is usable there; a CPU run should report
recall == 1.0 exactly (lowering is exact) and serves as the harness
self-check.

Usage (on the chip): PROBE_TPU=1 python -u tools/probe_recall.py
Usage (CPU self-check): python -u tools/probe_recall.py
Env: PROBE_N (default 131072), PROBE_STATES (default 5), PROBE_TPU=1
to use the ambient platform — without it the probe forces the CPU.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("PROBE_TPU", "0") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax

import jax.numpy as jnp

from goworld_tpu.ops.aoi import GridSpec, grid_neighbors

N = int(os.environ.get("PROBE_N", 131072))
STATES = int(os.environ.get("PROBE_STATES", 5))
K = 32
CC = 12
extent = float(int((N * 10000 / 12) ** 0.5))


def main():
    dev = jax.devices()[0]
    print(f"device={dev} N={N} states={STATES}", flush=True)
    alive = jnp.ones(N, bool)

    specs = {
        impl: GridSpec(radius=50.0, extent_x=extent, extent_z=extent,
                       k=K, cell_cap=CC, row_block=min(N, 65536),
                       topk_impl=impl)
        for impl in ("sort", "approx")
    }
    fns = {
        impl: jax.jit(lambda p, s=s: grid_neighbors(s, p, alive))
        for impl, s in specs.items()
    }

    tot_true = 0
    tot_hit = 0
    per_state = []
    for st in range(STATES):
        key = jax.random.PRNGKey(100 + st)
        k1, k2 = jax.random.split(key)
        pos = jnp.stack([
            jax.random.uniform(k1, (N,), maxval=extent),
            jnp.zeros(N),
            jax.random.uniform(k2, (N,), maxval=extent)], axis=1)
        t0 = time.perf_counter()
        res = {}
        for impl, fn in fns.items():
            nbr, cnt = fn(pos)
            # ONE host fetch per impl per state
            res[impl] = (np.asarray(nbr), np.asarray(cnt))
        ex_nbr, ex_cnt = res["sort"]
        ap_nbr, ap_cnt = res["approx"]
        # vectorized masked intersection (a per-entity Python set loop
        # is minutes at 1M — wasted chip time): valid exact lane
        # i hits iff its id appears in any valid approx lane
        true_n = 0
        hit_n = 0
        lanes = np.arange(K)
        for lo in range(0, N, 65536):       # chunk the K x K compare
            hi = min(lo + 65536, N)
            ex_ok = lanes[None, :] < ex_cnt[lo:hi, None]
            ap_ok = lanes[None, :] < ap_cnt[lo:hi, None]
            eq = ex_nbr[lo:hi, :, None] == ap_nbr[lo:hi, None, :]
            hit = (eq & ap_ok[:, None, :]).any(axis=2) & ex_ok
            true_n += int(ex_ok.sum())
            hit_n += int(hit.sum())
        tot_true += true_n
        tot_hit += hit_n
        r = hit_n / max(true_n, 1)
        per_state.append(r)
        print(f"state {st}: recall {r:.6f} "
              f"({hit_n}/{true_n} pairs, {time.perf_counter()-t0:.1f}s)",
              flush=True)
    overall = tot_hit / max(tot_true, 1)
    verdict = ("exact (CPU lowering or lossless)" if overall == 1.0
               else "LOSSY — keep approx out of autotune's selectable "
                    "set unless the loss is acceptable for the "
                    "deployment")
    print(f"\nRECALL overall {overall:.6f} over {tot_true} true pairs; "
          f"min state {min(per_state):.6f} — {verdict}", flush=True)


if __name__ == "__main__":
    main()
