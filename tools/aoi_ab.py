"""A/B of the AOI sweeps at one shard: the ranking ROADMAP S3 starts from.

Each configuration is one jitted ``grid_neighbors_flags`` call on a
seeded world — by default ``chip_smoke.py``'s: 131,072 slots, 100,000
live, radius 50, extent 10,451 — timed over ``AB_REPEATS`` calls that
end in ``block_until_ready`` (host clock), and held against the
brute-force oracle on sampled rows, so a fast wrong sweep cannot rank.
The library defaults come first. A last line says which f32 ops keep
subnormal bit patterns on this device (PR 21: a multi-column f32 row
gather flushes them to zero on a TPU v5e, which is why the candidate
planes are int32).

    python tools/aoi_ab.py                      # the device JAX finds
    AB_N=4096 AB_LIVE=3000 python tools/aoi_ab.py        # CPU rehearsal
    AB_CONFIGS='[{"sweep_impl": "cellrow"}]' python tools/aoi_ab.py

One JSON line per configuration on stdout, each stamped with the device,
and the same lines in ``chiprun_out/aoi_ab.jsonl``. A number from a CPU
run is a rehearsal, never a speed.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N = int(os.environ.get("AB_N", 131072))
LIVE = int(os.environ.get("AB_LIVE", N * 100_000 // 131072))
REPEATS = int(os.environ.get("AB_REPEATS", 3))
ROWS = int(os.environ.get("AB_ROWS", 200))
RADIUS = 50.0

# GridSpec overrides; {} is the library default (ranges / sort / argsort,
# k 64, cell_cap 32)
DEFAULT_CONFIGS = [
    {},
    {"sweep_impl": "table"},
    {"sweep_impl": "shift"},
    {"sweep_impl": "cellrow"},
    {"topk_impl": "f32"},
    {"topk_impl": "exact"},
    {"sort_impl": "pallas"},
    {"k": 32, "cell_cap": 12},
    {"k": 32, "cell_cap": 12, "sweep_impl": "cellrow"},
]


def subnormal_probe(jax, jnp, lax, np) -> dict:
    """Which ops return int words carried as f32 bits unchanged."""
    n = 4096
    ids = jnp.arange(n, dtype=jnp.int32) * 4 + 1    # all subnormal as f32
    f = lax.bitcast_convert_type(ids, jnp.float32)
    order = jnp.asarray(
        np.random.default_rng(0).permutation(n).astype(np.int32))
    want = np.asarray(ids)

    def kept(fn, *args, expect=want):
        got = np.asarray(lax.bitcast_convert_type(
            jax.jit(fn)(*args), jnp.int32))
        return int((got == expect).sum()), int(expect.size)

    return {
        "gather_one_column": kept(
            lambda a, o: a[o], f, order, expect=want[np.asarray(order)]),
        "gather_three_columns": kept(
            lambda a, o: jnp.stack([a * 0 + 1.5, a * 0 + 2.5, a], 1)[o][:, 2],
            f, order, expect=want[np.asarray(order)]),
        "transpose": kept(lambda a: jnp.stack([a, a, a], 1).T[2], f),
        "select": kept(lambda a: jnp.where(a == a, a, a), f),
    }


def main() -> int:
    import numpy as np

    from goworld_tpu.utils import compile_cache

    compile_cache.setup()
    import jax
    import jax.numpy as jnp
    from jax import lax

    from goworld_tpu.ops.aoi import GridSpec, grid_neighbors_flags
    from goworld_tpu.utils.audit import cohort_oracle
    from goworld_tpu.utils.devprof import device_stamp

    dev = device_stamp()
    configs = json.loads(os.environ.get("AB_CONFIGS", "null")) \
        or DEFAULT_CONFIGS
    # ~12 Chebyshev neighbours at radius 50 (10,451 at the default shard)
    extent = float(int((N * 10000 / 12) ** 0.5))
    rng = np.random.default_rng(0)
    pos_h = np.zeros((N, 3), np.float32)
    pos_h[:, 0] = rng.uniform(0.0, extent, N)
    pos_h[:, 2] = rng.uniform(0.0, extent, N)
    alive_h = np.zeros(N, bool)
    alive_h[:LIVE] = True
    flags_h = (rng.random(N) < 0.5).astype(np.int32)
    rows = rng.choice(LIVE, size=min(ROWS, LIVE), replace=False)
    want = cohort_oracle(pos_h, alive_h, RADIUS, rows)
    pos, alive, flags = map(jnp.asarray, (pos_h, alive_h, flags_h))

    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(REPO, "chiprun_out", "aoi_ab.jsonl"), "a")

    def emit(rec: dict) -> None:
        line = json.dumps({**rec, "device": dev})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    for over in configs:
        rec = {"n": N, "live": LIVE, "repeats": REPEATS, "config": over}
        try:
            spec = GridSpec(radius=RADIUS, extent_x=extent,
                            extent_z=extent, **over)
            sweep = jax.jit(lambda p, a, f, spec=spec: grid_neighbors_flags(
                spec, p, a, None, None, f))
            t0 = time.perf_counter()
            out = jax.block_until_ready(sweep(pos, alive, flags))
            rec["first_call_s"] = round(time.perf_counter() - t0, 2)
            ms = []
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                out = jax.block_until_ready(sweep(pos, alive, flags))
                ms.append((time.perf_counter() - t0) * 1e3)
            nbr, fl = np.asarray(out[0]), np.asarray(out[2])
            rec["sweep_ms"] = sorted(ms)
            rec["oracle_rows_differing"] = sum(
                {int(j) for j in nbr[i] if j < N} != want[int(i)]
                for i in rows)
            # the flag lanes must be the neighbours' own bits
            rec["flags_ok"] = all(
                (fl[i][nbr[i] < N] == (flags_h[nbr[i][nbr[i] < N]] & 3))
                .all() for i in rows)
            rec["oracle_rows"] = len(rows)
        except Exception as e:      # a refused option is a result too
            rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        emit(rec)
    emit({"subnormal_words_kept": subnormal_probe(jax, jnp, lax, np)})
    log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
