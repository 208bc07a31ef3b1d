"""A/B the window-fetch formulation: vmapped dynamic_slice (current)
vs canonical row-gather (jnp.take of 9 full table rows per query)."""
import os
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
from jax import lax

from goworld_tpu.ops.aoi import (
    GridSpec, _build_table, _cell_rows, _sort_cells, _sorted_src,
)

N = int(os.environ.get("PROBE_N", 131072))
L = 5
extent = float(int((N * 10000 / 12) ** 0.5))
spec = GridSpec(radius=50.0, extent_x=extent, extent_z=extent,
                k=32, cell_cap=12, row_block=65536)
cc = spec.cell_cap

key = jax.random.PRNGKey(0)
k1, k2 = jax.random.split(key)
pos = jnp.stack([
    jax.random.uniform(k1, (N,), maxval=extent),
    jnp.zeros(N),
    jax.random.uniform(k2, (N,), maxval=extent)], axis=1)
alive = jnp.ones(N, bool)


def front(p):
    cx, cz, srow, alive2, czp, n_rows = _cell_rows(spec, p, alive, None)
    order, sorted_row = _sort_cells(N, n_rows, srow)
    src, _ts, empty = _sorted_src(spec, p, None, order)
    table = _build_table(cc, n_rows, sorted_row, src, empty)
    return cx, cz, czp, table


def mk(form):
    def make(length):
        def run(p0):
            def body(p, _):
                cx, cz, czp, table = front(p)
                rows = jnp.arange(spec.row_block, dtype=jnp.int32)
                dxs = jnp.array([-1, 0, 1], jnp.int32)
                starts = (cx[rows][:, None] + dxs[None, :] + 1) * czp \
                    + cz[rows][:, None]            # [B, 3]
                b = rows.shape[0]
                if form == "dynslice":
                    win = jax.vmap(jax.vmap(
                        lambda s: lax.dynamic_slice(
                            table, (s, 0), (3, 3 * cc))
                    ))(starts)                     # [B, 3, 3, 3cc]
                    win = win.reshape(b, 9, 3 * cc)
                elif form == "take":
                    rows9 = (starts[:, :, None]
                             + jnp.arange(3)[None, None, :]).reshape(b, 9)
                    win = jnp.take(table, rows9, axis=0)  # [B, 9, 3cc]
                else:  # take_flat: one flattened 1-D gather per lane
                    rows9 = (starts[:, :, None]
                             + jnp.arange(3)[None, None, :]).reshape(b, 9)
                    win = table[rows9]
                s = win.sum().astype(jnp.float32)  # int32 planes
                return p + (s % 2) * 1e-7, s
            pp, ss = lax.scan(body, p0, None, length=length)
            return ss.sum() + pp.sum()
        return run
    return make


def timeit(name, mkf):
    r1, r2 = jax.jit(mkf(L)), jax.jit(mkf(2 * L))
    float(np.asarray(r1(pos)))
    float(np.asarray(r2(pos + 0.001)))
    es = []
    for i in range(2):
        t0 = time.perf_counter(); float(np.asarray(r1(pos + 0.002 * i)))
        e1 = time.perf_counter() - t0
        t0 = time.perf_counter(); float(np.asarray(r2(pos + 0.003 * i)))
        e2 = time.perf_counter() - t0
        es.append((e1, e2))
    ms = 1000.0 * max(min(e[1] for e in es) - min(e[0] for e in es),
                      1e-9) / L
    print(f"{name:22s} {ms:9.3f} ms/iter", flush=True)


print(f"device={jax.devices()[0]} N={N}", flush=True)
timeit("gather dynslice", mk("dynslice"))
timeit("gather take-rows", mk("take"))
timeit("gather bracket-idx", mk("take_flat"))
print("done", flush=True)
