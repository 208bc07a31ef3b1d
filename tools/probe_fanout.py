"""Host-side fan-out budget probe (VERDICT r4 weak #3 / next-step #4).

Measures ``World._process_outputs`` — the per-tick HOST decode of
device tick outputs (AOI enter/leave pairs -> interest sets + client
create/destroy sends, batched sync fan-out, hot-attr deltas) — at the
131K-entity per-chip shard scale with thousands of connected clients,
WITHOUT a device in the loop: outputs are synthesized numpy arrays at
the exact cap volumes the device can surface per tick, so the numbers
are the host decode's worst case, not a lucky quiet tick.

The budget: the reference's per-shard frame is 16 ms (BASELINE.md AOI
p99 target). The device tick and this host decode share it.

Scenarios (all at N=131072, clients=6553 [5%], 4 gates):
  leave_full    leave_cap (4096) leave pairs, uniform watchers
  enter_few     enter_cap (4096) enter pairs, 64 distinct subjects
                (movers crossing crowds — the payload-cache-friendly
                shape real churn produces)
  enter_distinct enter_cap pairs, all-distinct subjects (cache-hostile)
  enter_clients enter_cap pairs, every watcher client-bound (worst-case
                send volume: 4096 create_entity payloads)
  sync_full     sync_cap (16384) sync records through the batched
                sync_sink path
  attr_full     attr_sync_cap hot-attr deltas
  combined      leave_full + enter_few + sync_full + attr_full in one
                call (a realistic worst tick)

Run: JAX_PLATFORMS=cpu python -u tools/probe_fanout.py
"""
import os
import sys
import time
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from goworld_tpu.core.state import WorldConfig
from goworld_tpu.entity.entity import Entity, GameClient
from goworld_tpu.entity.manager import World
from goworld_tpu.entity.space import Space
from goworld_tpu.ops.aoi import GridSpec

N = int(os.environ.get("PROBE_N", 131072))
CLIENT_FRAC = float(os.environ.get("PROBE_CLIENT_FRAC", 0.05))
GATES = 4
ITERS = int(os.environ.get("PROBE_ITERS", 10))

ENTER_CAP = 4096
LEAVE_CAP = 4096
SYNC_CAP = 16384
ATTR_CAP = 4096


class Walker(Entity):
    # two AllClients attrs (the create_entity payload body) + one hot
    ATTRS = {"name": "allclients", "level": "allclients",
             "hp": "client hot:0"}


class Arena(Space):
    pass


def build_world():
    cfg = WorldConfig(
        capacity=N,
        grid=GridSpec(radius=50.0, extent_x=10000.0, extent_z=10000.0,
                      k=32, cell_cap=12, row_block=N),
        enter_cap=ENTER_CAP, leave_cap=LEAVE_CAP, sync_cap=SYNC_CAP,
        attr_sync_cap=ATTR_CAP, delta_rows_cap=N,
    )
    world = World(cfg, n_spaces=1)
    world.register_space("Arena", Arena)
    world.register_entity("Walker", Walker)
    world.create_nil_space()
    arena = world.create_space("Arena")
    sink_counts = {"client_msgs": 0, "sync_rows": 0}
    world.client_sink = lambda g, c, m: sink_counts.__setitem__(
        "client_msgs", sink_counts["client_msgs"] + 1)

    def sync_sink(gate, cids, eids, vals):
        sink_counts["sync_rows"] += len(cids)

    world.sync_sink = sync_sink

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    n_clients = int(N * CLIENT_FRAC)
    stride = N // max(n_clients, 1)
    client_slots = []
    for i in range(N):
        client = None
        if i % stride == 0 and len(client_slots) < n_clients:
            client = GameClient(i % GATES, f"CL{i:010d}", world)
            client_slots.append(i)
        world.create_entity(
            "Walker", space=arena,
            pos=(float(rng.uniform(0, 10000)), 0.0,
                 float(rng.uniform(0, 10000))),
            attrs={"name": f"walker-{i}", "level": i % 80},
            moving=True, client=client,
        )
    print(f"built {N} entities ({len(client_slots)} clients) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    # mirror the game logic loop's default boot discipline
    # (GameServer.serve_forever gc_freeze_on_boot): without it, gen-2
    # collections walk all 131K entities' attr trees mid-decode —
    # measured ~100 ms p95 spikes vs the 16 ms frame
    import gc
    gc.collect()
    gc.freeze()
    return world, np.array(client_slots), sink_counts


def make_base(enter=None, leave=None, sync=None, attr=None):
    """Synthesized TickOutputs 'base' with [1, cap]-shaped fields."""
    z1 = lambda: np.zeros(1, np.int32)

    def pairs(spec, cap):
        if spec is None:
            return z1(), np.zeros((1, cap), np.int32), \
                np.zeros((1, cap), np.int32)
        w, j = spec
        n = len(w)
        ww = np.zeros((1, cap), np.int32)
        jj = np.zeros((1, cap), np.int32)
        ww[0, :n] = w
        jj[0, :n] = j
        return np.array([n], np.int32), ww, jj

    en, ew, ej = pairs(enter, ENTER_CAP)
    ln, lw, lj = pairs(leave, LEAVE_CAP)
    base = types.SimpleNamespace(
        enter_n=en, enter_w=ew, enter_j=ej,
        leave_n=ln, leave_w=lw, leave_j=lj,
        delta_rows_n=z1(),
        sync_n=z1(),
        sync_w=np.zeros((1, SYNC_CAP), np.int32),
        sync_j=np.zeros((1, SYNC_CAP), np.int32),
        sync_vals=np.zeros((1, SYNC_CAP, 4), np.float32),
        attr_n=z1(),
        attr_e=np.zeros((1, ATTR_CAP), np.int32),
        attr_i=np.zeros((1, ATTR_CAP), np.int32),
        attr_v=np.zeros((1, ATTR_CAP), np.float32),
        aoi_demand_max=z1(), aoi_over_k_rows=z1(),
        aoi_cell_max=z1(), aoi_over_cap_cells=z1(),
    )
    if sync is not None:
        w, j, v = sync
        n = len(w)
        base.sync_n = np.array([n], np.int32)
        base.sync_w[0, :n] = w
        base.sync_j[0, :n] = j
        base.sync_vals[0, :n] = v
    if attr is not None:
        e, i, v = attr
        n = len(e)
        base.attr_n = np.array([n], np.int32)
        base.attr_e[0, :n] = e
        base.attr_i[0, :n] = i
        base.attr_v[0, :n] = v
    return base


def timeit(world, name, base, counts):
    # interest-set mutations accumulate across iters; that's fine — the
    # decode cost we're measuring doesn't depend on set size here
    best = float("inf")
    tot = 0.0
    for _ in range(ITERS):
        t0 = time.perf_counter()
        world._process_outputs(base)
        # the journal drain (client attr fan-out) is part of every real
        # tick's host cost (World.tick runs it right after decode) —
        # time it too, and keep the journal from growing across iters
        world._drain_attr_journals()
        dt = time.perf_counter() - t0
        tot += dt
        best = min(best, dt)
    print(f"{name:15s} mean {1000 * tot / ITERS:8.2f} ms   "
          f"best {1000 * best:8.2f} ms   "
          f"(client_msgs={counts['client_msgs']} "
          f"sync_rows={counts['sync_rows']})", flush=True)
    counts["client_msgs"] = 0
    counts["sync_rows"] = 0
    return 1000 * tot / ITERS


def main():
    world, client_slots, counts = build_world()
    rng = np.random.default_rng(1)

    def uni(n):
        return rng.integers(0, N, n).astype(np.int32)

    results = {}

    # leaves: uniform watcher/subject pairs
    results["leave_full"] = timeit(
        world, "leave_full",
        make_base(leave=(uni(LEAVE_CAP), uni(LEAVE_CAP))), counts)

    # enters, few distinct subjects (64 movers x 64 watchers)
    subj64 = np.repeat(uni(64), ENTER_CAP // 64)
    results["enter_few"] = timeit(
        world, "enter_few",
        make_base(enter=(uni(ENTER_CAP), subj64)), counts)

    # enters, all-distinct subjects
    results["enter_distinct"] = timeit(
        world, "enter_distinct",
        make_base(enter=(uni(ENTER_CAP),
                         rng.permutation(N)[:ENTER_CAP].astype(np.int32))),
        counts)

    # enters where EVERY watcher has a client (max send volume)
    cw = rng.choice(client_slots, ENTER_CAP).astype(np.int32)
    results["enter_clients"] = timeit(
        world, "enter_clients",
        make_base(enter=(cw, subj64)), counts)

    # sync records: client watchers (the device only surfaces client
    # rows), batched-path
    sw = rng.choice(client_slots, SYNC_CAP).astype(np.int32)
    results["sync_full"] = timeit(
        world, "sync_full",
        make_base(sync=(sw, uni(SYNC_CAP),
                        rng.random((SYNC_CAP, 4)).astype(np.float32))),
        counts)

    # hot-attr deltas (col 0 = hp)
    results["attr_full"] = timeit(
        world, "attr_full",
        make_base(attr=(uni(ATTR_CAP),
                        np.zeros(ATTR_CAP, np.int32),
                        rng.random(ATTR_CAP).astype(np.float32))),
        counts)

    # one realistic worst tick: full leaves + cache-friendly enters +
    # full sync + full attrs
    results["combined"] = timeit(
        world, "combined",
        make_base(
            leave=(uni(LEAVE_CAP), uni(LEAVE_CAP)),
            enter=(uni(ENTER_CAP), subj64),
            sync=(sw, uni(SYNC_CAP),
                  rng.random((SYNC_CAP, 4)).astype(np.float32)),
            attr=(uni(ATTR_CAP), np.zeros(ATTR_CAP, np.int32),
                  rng.random(ATTR_CAP).astype(np.float32)),
        ), counts)

    budget = 16.0
    print(f"\nbudget check: combined {results['combined']:.2f} ms vs "
          f"{budget:.0f} ms frame "
          f"({'OVER' if results['combined'] > budget else 'within'} "
          f"budget; device tick shares the frame)", flush=True)


if __name__ == "__main__":
    main()
