#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served world still starts
on the chip.

    python chip_smoke.py              # one TPU chip: world + cluster phases
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Drives the system's main path once, through the entry points an operator
would call, at a world size an operator would call real (one space,
capacity 131,072, 100,000 live entities, radius 50, every kernel choice
at its library default). The deployment that world is a tile of runs at
60 Hz; a TPU v5e does not hold that for it yet (PERF.md), so the game is
served at the fixed bring-up rate ``REAL["tick_hz"]`` and must HOLD it:

* **world** — an in-process ``World`` (README "Hosting a world
  in-process") ticked through ``World.tick()`` (the served step: carry
  donated, outputs drained), its interest sets compared with the
  brute-force Chebyshev oracle on sampled rows;
* **cluster** — ``python -m goworld_tpu start <dir>`` (dispatcher + game
  + gate), bots at the gate socket (``net/botclient.py``) whose mirrors
  must track each other and the device tick's NPC records, one RPC round
  trip, ``reload`` (the restore process takes over the chip the frozen
  one released, and reads the tick from the compile cache), ``stop``;
  the game must hold its tick rate with its overload ladder at NORMAL;
* **mesh** (``--chips 4`` only) — one megaspace over a 2x2 mesh and four
  spaces one per device, each against its oracle, each device holding
  its quarter of the state.

The parent never imports jax or this repo: a chip belongs to one process
at a time, so every phase that touches it runs as a child, one after the
other. Each phase prints one JSON line; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``ok`` is true only if every phase passed AND the device is a TPU with
the expected chip count; the exit code is 0 only then. The sizes are
arguments (``--rehearsal`` shrinks every default for a CPU run, where
every phase can pass and the device check alone must fail).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
RADIUS = 50.0
# first words of the stream seed 7 draws (core/state.seed_key; the same
# words tests/test_step.py pins on the CPU)
SEED7_BITS = [2899676959, 3548400998, 1692160380, 1822441453]

# The deployment this world is a tile of runs at 60 Hz (ROADMAP R1). A
# TPU v5e ticks it in ~614 ms with the library-default kernels (PERF.md,
# PR 21), and a game that misses its frame climbs the overload ladder
# and sheds client position syncs by design (utils/overload.py): at
# 60 Hz it reaches REJECTING and serves no bot. So the cluster phase
# serves at a FIXED bring-up rate, the same on every run, and checks
# that the game holds it. It is not the deployment's rate; the PR that
# repairs the AOI window gather (ROADMAP S3) raises it to TARGET_HZ.
TARGET_HZ = 60.0
REAL = dict(capacity=131072, entities=100_000, ticks=40, sample=300,
            bots=16, tick_hz=1.0)
REHEARSAL = dict(capacity=2048, entities=1500, ticks=8, sample=64,
                 bots=16, tick_hz=2.0)


def say(msg: str) -> None:
    print(msg, flush=True)


def extent_for(entities: int) -> float:
    """~12 Chebyshev neighbours at radius 50 (bench.py's density):
    10451 at the 131,072 shard's 100,000-odd entities."""
    return float(int((max(entities, 1) * 1.31072 * 10000 / 12) ** 0.5))


# =======================================================================
# children that own the chip
# =======================================================================
def _oracle_mismatches(pos, alive, nbr, rows, sentinel):
    """Sampled rows of the device's interest lists vs the brute-force
    Chebyshev oracle the audit plane and the tests use."""
    from goworld_tpu.utils.audit import cohort_oracle

    want = cohort_oracle(pos, alive, RADIUS, rows)
    bad = [i for i in want
           if want[i] != {int(j) for j in nbr[i] if j < sentinel}]
    return bad, sum(len(s) for s in want.values())


def _timed_ticks(w, ticks: int, before=None, after=None):
    """The first tick (it compiles) and ``ticks`` more through
    ``World.tick()``, each ending in the outputs it fetches. Returns
    (first tick seconds, later ticks' ms sorted); ``before(t)`` /
    ``after()`` run outside the timed region."""
    t0 = time.perf_counter()
    w.tick()
    first_s = time.perf_counter() - t0
    ms = []
    for t in range(ticks):
        if before is not None:
            before(t)
        t0 = time.perf_counter()
        w.tick()
        ms.append((time.perf_counter() - t0) * 1e3)
        if after is not None:
            after()
    return first_s, sorted(ms)


def _over_caps(outs) -> int:
    """Rows truncated to k plus cells past cell_cap in the last tick:
    zero means the sweep was exact and the oracle must agree."""
    import numpy as np

    return int(np.asarray(outs.aoi_over_k_rows).sum()) \
        + int(np.asarray(outs.aoi_over_cap_cells).sum())


def child_world(a) -> int:
    """Phase 1: the in-process World on one device."""
    import numpy as np

    from goworld_tpu.utils import compile_cache

    cache_dir = compile_cache.setup()
    import jax

    from goworld_tpu.core import WorldConfig
    from goworld_tpu.core.state import seed_key
    from goworld_tpu.entity import Entity, Space, World
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.utils.devprof import device_stamp

    dev = device_stamp()
    say(f"[world] device {dev}; compile cache {cache_dir}")
    checks: dict[str, bool] = {}
    _, k7 = jax.random.split(seed_key(7))
    checks["seed_stream"] = (
        np.asarray(jax.random.bits(k7, (4,))).tolist() == SEED7_BITS)

    n, live, ext = a.capacity, a.entities, a.extent
    t0 = time.perf_counter()
    w = World(WorldConfig(capacity=n, grid=GridSpec(
        radius=RADIUS, extent_x=ext, extent_z=ext)), n_spaces=1,
        seed=a.seed)
    w.register_entity("Npc", type("Npc", (Entity,), {}))
    w.register_space("Arena", type("Arena", (Space,), {}))
    w.create_nil_space()
    arena = w.create_space("Arena")
    rng = np.random.default_rng(a.seed)
    xz = rng.uniform(0.0, ext, (live, 2))
    moving = rng.random(live) < 0.98      # most are device-driven NPCs
    for i in range(live):
        w.create_entity("Npc", space=arena,
                        pos=(xz[i, 0], 0.0, xz[i, 1]),
                        moving=bool(moving[i]))
    build_s = time.perf_counter() - t0

    # interest events the host never decoded (past enter_cap /
    # leave_cap / delta_rows_cap): the mass spawn's first tick is known
    # to overflow (PERF.md section 7); a steady tick must not
    spawn_drop: dict[str, int] = {}

    def mark(t: int) -> None:
        if t == 0:
            spawn_drop.update(w.aoi_dropped)

    first_s, steady = _timed_ticks(w, a.ticks, before=mark)
    med = steady[len(steady) // 2]
    steady_drop = {k: w.aoi_dropped[k] - v for k, v in spawn_drop.items()}
    checks["no_interest_event_dropped_in_steady_ticks"] = \
        not any(steady_drop.values())

    st = jax.tree.map(lambda x: np.asarray(x[0]),
                      {"pos": w.state.pos, "alive": w.state.alive,
                       "nbr": w.state.nbr})
    checks["alive_count"] = int(st["alive"].sum()) == live
    checks["finite"] = bool(np.isfinite(st["pos"][st["alive"]]).all())
    checks["in_bounds"] = bool(
        (st["pos"][st["alive"]][:, ::2] >= 0).all()
        and (st["pos"][st["alive"]][:, ::2] <= ext).all())
    checks["moved"] = bool(
        np.abs(st["pos"][:live, ::2] - xz).max() > 0.0)
    over = _over_caps(w.last_outputs)
    checks["aoi_caps_not_exceeded"] = over == 0
    rows = rng.choice(np.nonzero(st["alive"])[0],
                      size=min(a.sample, live), replace=False)
    bad, pairs = _oracle_mismatches(st["pos"], st["alive"], st["nbr"],
                                    rows, n)
    checks["oracle"] = not bad and pairs > 0
    say(f"[world] capacity {n}, live {int(st['alive'].sum())} "
        f"({int(moving.sum())} moving), extent {ext:.0f}, radius "
        f"{RADIUS:.0f}; built in {build_s:.1f} s")
    say(f"[world] first tick (compile + run) {first_s:.1f} s; cache "
        f"hits={compile_cache.stats['hits']} "
        f"misses={compile_cache.stats['misses']}")
    say(f"[world] {a.ticks} ticks through World.tick(): median "
        f"{med:.2f} ms/tick, min {steady[0]:.2f}, max {steady[-1]:.2f} "
        f"(host clock, information only)")
    holds = med <= 1000.0 / TARGET_HZ
    say(f"[world] this device holds {1000.0 / med:.2f} Hz for this "
        f"world: the deployment's {TARGET_HZ:g} Hz "
        f"({1000.0 / TARGET_HZ:.1f} ms frame) is "
        f"{'held' if holds else 'NOT held'}")
    say(f"[world] oracle: {len(rows)} sampled rows, {pairs} interest "
        f"pairs, {len(bad)} rows differ; over-cap gauges {over}")
    say(f"[world] interest events the host never decoded: mass spawn "
        f"(first tick) {spawn_drop}, steady ticks {steady_drop}")
    ok = all(checks.values())
    say(json.dumps({
        "phase": "world", "ok": ok, "checks": checks, "device": dev,
        "capacity": n, "live": live, "first_tick_s": round(first_s, 2),
        "tick_ms_median": med, "target_hz": TARGET_HZ,
        "holds_target_hz": holds,
        "events_dropped": {"spawn": spawn_drop, "steady": steady_drop},
        "cache": dict(compile_cache.stats), "cache_dir": cache_dir}))
    return 0 if ok else 1


def _shard_report(state, n_dev: int) -> tuple[bool, dict]:
    """Every leaf of the stacked state must hold exactly one row of its
    leading axis on each of the mesh's devices."""
    import jax

    per_dev: dict[str, int] = {}
    ok = True
    for leaf in jax.tree.leaves(state):
        shards = leaf.addressable_shards
        if len(shards) != n_dev \
                or len({s.device for s in shards}) != n_dev \
                or any(s.data.shape[0] != 1 for s in shards):
            ok = False
        for s in shards:
            key = str(s.device)
            per_dev[key] = per_dev.get(key, 0) + int(s.data.nbytes)
    if len(set(per_dev.values())) != 1 or len(per_dev) != n_dev:
        ok = False
    return ok, per_dev


def child_mesh(a) -> int:
    """--chips 4: the cross-chip paths and what they are compared
    with, nothing else."""
    import numpy as np

    from goworld_tpu.utils import compile_cache

    cache_dir = compile_cache.setup()
    import jax

    from goworld_tpu import config as config_mod
    from goworld_tpu.api import _build_world
    from goworld_tpu.entity import Entity, Space
    from goworld_tpu.utils.devprof import device_stamp

    dev = device_stamp()
    say(f"[mesh] device {dev}; compile cache {cache_dir}")
    n, per, tile = a.capacity, a.entities, a.extent
    rng = np.random.default_rng(a.seed)
    checks: dict[str, bool] = {"four_devices": dev["count"] == 4}
    if dev["count"] < 4:
        say(json.dumps({"phase": "mesh", "ok": False, "checks": checks,
                        "device": dev}))
        return 1

    # ---- (a) one megaspace over the 2x2 mesh --------------------------
    t0 = time.perf_counter()
    w = _build_world(config_mod.GameConfig(
        capacity=n, mesh_devices=4, megaspace=True, mega_shape="2x2",
        extent_x=2 * tile, extent_z=2 * tile, aoi_radius=RADIUS,
        halo_cap=2048), 1)
    w.register_entity("Npc", type("Npc", (Entity,), {}))
    w.register_space("Mega", type("Mega", (Space,), {}), megaspace=True)
    w.create_nil_space()
    arena = w.create_space("Mega")
    live = 4 * per
    xz = rng.uniform(0.0, 2 * tile, (live, 2))
    # a band of walkers ON the inner borders (they random-walk across)
    band = min(live // 50, 1024)
    half = band // 2
    xz[:half, 0] = tile + rng.uniform(-0.2, 0.2, half)
    xz[half:band, 1] = tile + rng.uniform(-0.2, 0.2, band - half)
    ents = [w.create_entity("Npc", space=arena,
                            pos=(xz[i, 0], 0.0, xz[i, 1]), moving=True)
            for i in range(live)]
    home = [e.shard for e in ents]
    # a cohort the HOST drives over a border every few ticks
    driven = ents[band:band + 64]
    build_s = time.perf_counter() - t0
    seen = {"dropped": 0, "halo_max": 0, "crossed": set()}

    def drive(t: int) -> None:
        if t % 4:
            return
        for j, e in enumerate(driven):
            x, _y, z = e.position
            if j % 2:
                e.set_position(((x + tile) % (2 * tile), 0.0, z))
            else:
                e.set_position((x, 0.0, (z + tile) % (2 * tile)))

    def watch() -> None:
        seen["crossed"].update(
            j for j, e in enumerate(driven) if e.shard != home[band + j])
        o = w.last_outputs
        seen["dropped"] += int(np.asarray(o.migrate_dropped).sum())
        seen["halo_max"] = max(seen["halo_max"],
                               int(np.asarray(o.halo_demand).max()))

    first_s, tick_ms = _timed_ticks(w, a.ticks, drive, watch)
    med = tick_ms[len(tick_ms) // 2]
    dropped, halo_max = seen["dropped"], seen["halo_max"]
    placed, per_dev = _shard_report(w.state, 4)
    checks["mega_each_device_holds_its_quarter"] = placed
    pos = np.asarray(w.state.pos).reshape(4 * n, 3)
    alive = np.asarray(w.state.alive).reshape(4 * n)
    nbr = np.asarray(w.state.nbr).reshape(4 * n, -1)
    gid = np.array([e.shard * n + e.slot for e in ents])
    migrated = np.array([i for i, e in enumerate(ents)
                         if e.shard != home[i]], dtype=np.int64)
    near = np.minimum(np.abs(pos[gid, 0] - tile),
                      np.abs(pos[gid, 2] - tile)) < RADIUS
    third = max(a.sample // 3, 1)
    rows = np.unique(np.concatenate([
        gid[rng.choice(idx, size=min(third, len(idx)), replace=False)]
        for idx in (np.nonzero(near)[0], migrated, np.arange(live))
        if len(idx)]))
    bad, pairs = _oracle_mismatches(pos, alive, nbr, rows, 4 * n)
    over = _over_caps(w.last_outputs.base)
    checks["mega_alive"] = int(alive.sum()) == live
    checks["mega_no_dropped_migrants"] = dropped == 0
    checks["mega_halo_within_cap"] = halo_max <= w.mega.halo_cap
    checks["mega_entities_migrated"] = len(migrated) >= 32
    # the host-driven cohort really changed tile at least once
    checks["mega_driven_cohort_crossed"] = \
        len(seen["crossed"]) == len(driven)
    checks["mega_caps_not_exceeded"] = over == 0
    checks["mega_oracle"] = not bad and pairs > 0
    say(f"[mesh] megaspace 2x2: {4 * n} slots ({n} per tile), {live} "
        f"live, world {2 * tile:.0f}^2; built {build_s:.1f} s, first "
        f"tick {first_s:.1f} s, median {med:.2f} ms/tick over "
        f"{a.ticks} ticks (host clock, information only)")
    say(f"[mesh] megaspace: {len(migrated)} entities changed tile, "
        f"{dropped} dropped, halo demand max {halo_max}/"
        f"{w.mega.halo_cap}; oracle over the whole world: {len(rows)} "
        f"rows ({int(near.sum())} live in border strips), {pairs} "
        f"pairs, {len(bad)} rows differ")
    say(f"[mesh] megaspace bytes per device: {per_dev}")
    mega_stats = {"first_tick_s": round(first_s, 2),
                  "tick_ms_median": med, "migrated": int(len(migrated))}
    del w, ents, driven

    # ---- (b) four spaces, one per device ------------------------------
    t0 = time.perf_counter()
    w = _build_world(config_mod.GameConfig(
        capacity=n, n_spaces=4, mesh_devices=4, extent_x=tile,
        extent_z=tile, aoi_radius=RADIUS), 1)
    w.register_entity("Npc", type("Npc", (Entity,), {}))
    w.register_space("Arena", type("Arena", (Space,), {}))
    w.create_nil_space()
    spaces = [w.create_space("Arena") for _ in range(4)]
    ents = []
    for sp in spaces:
        xz = rng.uniform(0.0, tile, (per, 2))
        ents += [w.create_entity("Npc", space=sp,
                                 pos=(xz[i, 0], 0.0, xz[i, 1]),
                                 moving=True) for i in range(per)]
    build_s = time.perf_counter() - t0
    # EnterSpace across devices rides the in-step all_to_all
    movers = ents[:32]
    dests = [spaces[(e.shard + 1 + j % 3) % 4]
             for j, e in enumerate(movers)]

    def migrate(t: int) -> None:
        if t == 0:
            for j, (e, dest) in enumerate(zip(movers, dests)):
                e.enter_space(dest.id, (tile / 2 + j, 0.0, tile / 2))

    first_s, tick_ms = _timed_ticks(w, a.ticks, migrate)
    med = tick_ms[len(tick_ms) // 2]
    placed, per_dev = _shard_report(w.state, 4)
    checks["spaces_each_device_holds_its_quarter"] = placed
    checks["spaces_migrants_arrived"] = all(
        e.space is dest and e.slot is not None
        and spaces[e.shard] is dest
        for e, dest in zip(movers, dests))
    tot_bad = tot_pairs = tot_rows = 0
    for s in range(4):
        pos = np.asarray(w.state.pos[s])
        alive = np.asarray(w.state.alive[s])
        nbr = np.asarray(w.state.nbr[s])
        rows = rng.choice(np.nonzero(alive)[0],
                          size=min(max(a.sample // 4, 1),
                                   int(alive.sum())), replace=False)
        # the arrivals' rows are judged too
        arr = [e.slot for e in movers if e.shard == s]
        rows = np.unique(np.concatenate(
            [rows, np.array(arr, dtype=rows.dtype)]))
        bad, pairs = _oracle_mismatches(pos, alive, nbr, rows, n)
        tot_bad += len(bad)
        tot_pairs += pairs
        tot_rows += len(rows)
    checks["spaces_alive"] = int(
        np.asarray(w.state.alive).sum()) == 4 * per
    checks["spaces_oracle"] = tot_bad == 0 and tot_pairs > 0
    say(f"[mesh] space-per-device: 4 spaces x {n} slots, {per} live "
        f"each; built {build_s:.1f} s, first tick {first_s:.1f} s, "
        f"median {med:.2f} ms/tick (host clock, information only); "
        f"{len(movers)} EnterSpace migrants arrived; oracle: "
        f"{tot_rows} rows, {tot_pairs} pairs, {tot_bad} rows differ")
    say(f"[mesh] space-per-device bytes per device: {per_dev}")
    ok = all(checks.values())
    say(json.dumps({
        "phase": "mesh", "ok": ok, "checks": checks, "device": dev,
        "megaspace": mega_stats,
        "spaces": {"first_tick_s": round(first_s, 2),
                   "tick_ms_median": med}}))
    return 0 if ok else 1


# =======================================================================
# the bots (a jax-free child: it imports the repo's client, the parent
# imports nothing)
# =======================================================================
async def _bots_main(a) -> int:
    import asyncio

    from goworld_tpu.net.botclient import BotClient

    n, ext = a.bots, a.extent
    window = max(2.0, 4.0 / a.tick_hz)    # spans a few served ticks
    loop = asyncio.get_running_loop()
    bots = [BotClient("127.0.0.1", a.gate_port, bot_id=i, nosync=True)
            for i in range(n)]
    bots_extra: list = []
    tasks = []
    sent: dict[int, tuple] = {}
    anchor = {}

    # pairs sit on a grid wider than two AOI boxes, so a pair sees its
    # partner and the NPCs around it, never another pair
    spacing = min(500.0, ext / 8.0)
    far = 0.45 * ext

    def set_anchors(shift: float) -> None:
        for i in range(n):
            p = i // 2
            anchor[i] = (0.05 * ext + spacing * (p % 4) + shift,
                         0.05 * ext + spacing * (p // 4))

    async def mover(i: int) -> None:
        # 10 Hz client position sync around the pair's anchor
        # (upstream's bot cadence, ClientBot.go:214-227)
        import math

        step = 0
        while True:
            ax, az = anchor[i]
            # ~2 units/s: a mirror a few ticks behind is still close
            ang = 0.025 * step + math.pi * (i % 2)
            x, z = ax + 8.0 * math.cos(ang), az + 8.0 * math.sin(ang)
            bots[i].send_position(x, 0.0, z, ang % 6.28)
            sent[i] = (x, z)
            step += 1
            await asyncio.sleep(0.1)

    async def until(pred, timeout: float) -> bool:
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if pred():
                return True
            await asyncio.sleep(0.1)
        return pred()

    def partner_tracked(i: int) -> bool:
        p = bots[i ^ 1].player
        me = bots[i].entities.get(p.eid) if p is not None else None
        if me is None or (i ^ 1) not in sent:
            return False
        x, z = sent[i ^ 1]
        ax, az = anchor[i ^ 1]
        # the partner already circles its CURRENT anchor, and this
        # mirror is within a few syncs of where it last said it was
        return max(abs(x - ax), abs(z - az)) < 9.0 \
            and max(abs(me.pos[0] - x), abs(me.pos[2] - z)) < 12.0

    def npcs(i: int) -> dict:
        return {eid: m for eid, m in bots[i].entities.items()
                if m.type_name == "Npc"}

    def strays(i: int) -> int:
        # mirrored NPCs OUTSIDE the AOI box around this bot's anchor
        # (slack: the bot circles it, records are in flight)
        x, z = anchor[i]
        return sum(max(abs(m.pos[0] - x), abs(m.pos[2] - z))
                   > RADIUS + 20.0 for m in npcs(i).values())

    async def rpc_round_trip(i: int, token: str) -> float | None:
        t0 = time.monotonic()
        bots[i].call_server("Echo_Client", token)
        ok = await until(lambda: any(
            m == "OnEcho" and args and args[0] == token
            for _e, m, args in bots[i].rpc_log), 30.0)
        return (time.monotonic() - t0) * 1e3 if ok else None

    async def step(tag: str, shift: float, extra_login: int) -> dict:
        c: dict[str, bool] = {}
        c["all_logged_in"] = await until(
            lambda: all(b.player is not None for b in bots), 90.0)
        set_anchors(shift)
        c["mirrors_track_partner_moves"] = await until(
            lambda: all(partner_tracked(i) for i in range(n)), 90.0)
        # ... and the NPCs around the new spot (whatever a mirror held
        # from an earlier spot has left by then)
        c["npc_enter_records"] = await until(
            lambda: all(not strays(i) and npcs(i) for i in range(n)),
            60.0)
        before = [set(npcs(i)) for i in range(n)]
        s0 = sum(b.sync_count for b in bots)
        p0 = [{e: m.pos for e, m in npcs(i).items()} for i in range(n)]
        await asyncio.sleep(window)
        syncs_2s = sum(b.sync_count for b in bots) - s0
        npc_moves = sum(m.pos != p0[i].get(e, m.pos)
                        for i in range(n) for e, m in npcs(i).items())
        c["npc_sync_records"] = syncs_2s > 0 and npc_moves > 0
        # every enter got its leave — also across the reload, whose
        # freeze carries what each connected client was told
        c["npc_mirrors_inside_aoi"] = not any(
            strays(i) for i in range(n))
        # jump every pair far away: the old neighbourhood must LEAVE
        # each mirror and a new one enter
        set_anchors(shift + far)
        c["npc_leave_records"] = await until(
            lambda: not any(before[i] & set(npcs(i))
                            for i in range(n)), 60.0)
        c["npc_enter_after_move"] = await until(
            lambda: all(npcs(i) and not strays(i)
                        and partner_tracked(i) for i in range(n)), 60.0)
        rtt = await rpc_round_trip(0, f"{tag}-{a.seed}")
        c["rpc_round_trip"] = rtt is not None
        for k in range(extra_login):     # fresh logins still work
            b = BotClient("127.0.0.1", a.gate_port, bot_id=1000 + k,
                          nosync=True)
            await b.connect()
            bots_extra.append(b)
            tasks.append(loop.create_task(b._recv_loop()))
            c[f"new_login_{k}"] = await until(
                lambda b=b: b.player is not None, 60.0)
        c["no_bot_errors"] = not any(b.errors for b in bots)
        return {"step": tag, "ok": all(c.values()), "checks": c,
                "bots": n, "rpc_ms": rtt, "window_s": window,
                "syncs_in_window": syncs_2s,
                "npc_mirrors_moved_in_window": npc_moves,
                "mirrors_outliving_their_leave": sum(
                    strays(i) for i in range(n)),
                "sync_records": sum(b.sync_count for b in bots),
                "npcs_mirrored": sum(len(npcs(i)) for i in range(n))}

    set_anchors(0.0)
    for b in bots:
        await b.connect()
        tasks.append(loop.create_task(b._recv_loop()))
    for i in range(n):
        tasks.append(loop.create_task(mover(i)))
    res_a = await step("before_reload", 0.0, 0)
    say("STEP " + json.dumps(res_a))
    # the parent reloads the game now; the bots stay connected
    await loop.run_in_executor(None, sys.stdin.readline)
    res_b = await step("after_reload", 37.0, 2)
    say("STEP " + json.dumps(res_b))
    for t in tasks:
        t.cancel()
    for b in bots + bots_extra:
        b._stop = True
        await b.conn.close()
    return 0 if res_a["ok"] and res_b["ok"] else 1


def child_bots(a) -> int:
    import asyncio

    return asyncio.run(_bots_main(a))


# =======================================================================
# the parent: stdlib only
# =======================================================================
SERVER_PY = '''\
"""chip_smoke fixture: one space filled with device-driven NPCs and an
Avatar per client (the boot entity)."""
import numpy as np

import goworld_tpu as gw
from goworld_tpu.utils import opmon

N_NPC, EXTENT, SEED = {n_npc}, {extent}, {seed}


@gw.register_space("Arena")
class Arena(gw.Space):
    pass


@gw.register_entity("Npc")
class Npc(gw.Entity):
    pass


@gw.register_entity("Avatar")
class Avatar(gw.Entity):
    def OnClientConnected(self):
        arena = next(sp for sp in self.world.spaces.values()
                     if sp.type_name == "Arena")
        self.enter_space(arena.id, (EXTENT / 2, 0.0, EXTENT / 2))

    def OnClientDisconnected(self):
        self.destroy()

    def Echo_Client(self, token):
        self.call_client("OnEcho", token)


@gw.on_boot
def fill(world):
    arena = world.create_space("Arena")
    rng = np.random.default_rng(SEED)
    xz = rng.uniform(0.0, EXTENT, (N_NPC, 2))
    for x, z in xz:
        world.create_entity("Npc", space=arena, pos=(x, 0.0, z),
                            moving=True)
    opmon.expose("smoke_npcs", sum(
        1 for e in world.entities.values() if e.type_name == "Npc"))


if __name__ == "__main__":
    gw.run()
'''

INI = """\
[dispatcher1]
host = 127.0.0.1
port = {dport}

[game_common]
boot_entity = Avatar
capacity = {capacity}
n_spaces = 1
aoi_radius = 50.0
extent_x = {extent}
extent_z = {extent}
tick_hz = {tick_hz}
http_port = {hport}

[game1]

[gate1]
host = 127.0.0.1
port = {gport}

[storage]
kind = memory

[kvdb]
kind = memory
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_cmd(a, name: str, **over) -> list[str]:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name]
    for key in ("capacity", "entities", "extent", "ticks", "sample",
                "bots", "tick_hz", "seed"):
        cmd += [f"--{key.replace('_', '-')}",
                str(over.get(key, getattr(a, key)))]
    if "gate_port" in over:
        cmd += ["--gate-port", str(over["gate_port"])]
    return cmd


def _env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


def run_phase_child(a, name: str, timeout: float) -> dict:
    """Run one chip-owning child to its end, echoing its lines."""
    proc = subprocess.Popen(_child_cmd(a, name), stdout=subprocess.PIPE,
                            text=True, env=_env(), cwd=HERE,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        say(f"[{name}] child killed after {timeout:.0f} s")
    res = None
    for line in out.splitlines():
        if line.startswith('{"phase"'):
            res = json.loads(line)     # the child's one result line
        else:
            say(line)
    if res is None or proc.returncode != 0:
        res = dict(res or {"phase": name}, ok=False,
                   returncode=proc.returncode)
    return res


def _gw(args: list[str], timeout: float) -> tuple[int, str, float]:
    t0 = time.monotonic()
    try:
        r = subprocess.run([sys.executable, "-m", "goworld_tpu"] + args,
                           capture_output=True, text=True, env=_env(),
                           cwd=HERE, timeout=timeout)
        rc, out = r.returncode, r.stdout + r.stderr
    except subprocess.TimeoutExpired as e:
        rc, out = 124, f"timed out after {timeout:.0f} s: {e.stdout}"
    return rc, out, time.monotonic() - t0


def _http(hport: int, path: str) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{hport}/{path}", timeout=10) as r:
        return json.loads(r.read().decode())


def _load(hport: int) -> dict | None:
    """The game's own account of its load: frames served, overload
    ladder, packets shed, interest events it never decoded."""
    try:
        ov = _http(hport, "overload")
        ops = _http(hport, "ops").get("world.tick", {})
        dropped = _http(hport, "vars").get("aoi_events_dropped", {})
    except (OSError, ValueError) as e:
        say(f"[cluster] load endpoints unreadable ({e})")
        return None
    gov = next(iter(ov.get("governors", {}).values()), None)
    if gov is None:
        say("[cluster] the game has no overload governor to read")
        return None
    return {"at": time.monotonic(), "frames": gov["observations"],
            "ladder": gov["state"], "transitions": gov["transitions"],
            "shed": ov.get("shed"), "dropped": dropped, "tick": ops}


# "holds its rate": at least four frames in five of it, counted over
# 30 frames or more so that neither a reading's +-1 frame nor one long
# frame decides it. (A frame that overruns drops the schedule, it does
# not spiral; the first staging scatter of each batch size compiles
# inside the serve loop — a login costs a frame or two on the chip.)
HOLD_SHARE, HOLD_FRAMES = 0.8, 30


def _judge_load(c: dict, suffix: str, hz: float, hport: int,
                l0: dict | None) -> float | None:
    """While the bots were served (from the reading ``l0`` to now): the
    game held its tick rate, its ladder never left NORMAL, nothing was
    shed, and no interest event went undecoded. Returns the rate it
    served at."""
    l1 = _load(hport)
    end = time.monotonic() + 2.0 * HOLD_FRAMES / hz
    while l0 and l1 and l1["frames"] - l0["frames"] < HOLD_FRAMES \
            and time.monotonic() < end:
        time.sleep(0.5)
        l1 = _load(hport)
    if l0 is None or l1 is None:
        c["load_readable" + suffix] = False
        return None
    served = (l1["frames"] - l0["frames"]) / (l1["at"] - l0["at"])
    c["holds_tick_hz" + suffix] = served >= HOLD_SHARE * hz
    c["ladder_stayed_normal" + suffix] = l1["ladder"] == "NORMAL" \
        and not l1["transitions"] and not l1["shed"]
    c["no_interest_event_dropped_while_serving" + suffix] = \
        l1["dropped"] == l0["dropped"]
    say(f"[cluster] served{suffix.replace('_', ' ')}: {served:.2f} "
        f"frames/s of {hz:g} Hz over {l1['at'] - l0['at']:.0f} s; "
        f"ladder {l1['ladder']} after {l1['frames']} frames, "
        f"transitions {l1['transitions']}, shed {l1['shed']}; interest "
        f"events never decoded: {l0['dropped']} at the first reading "
        f"(the mass spawn or the restore), {l1['dropped']} at the last; "
        f"world.tick {l1['tick']}")
    return round(served, 3)


def _procs_in(server_dir: str) -> list[int]:
    """Every live process whose cwd is the server directory (the CLI
    starts dispatcher, game and gate there)."""
    pids = []
    for p in os.listdir("/proc"):
        if p.isdigit():
            try:
                if os.path.realpath(f"/proc/{p}/cwd") \
                        == os.path.realpath(server_dir):
                    with open(f"/proc/{p}/stat") as f:
                        if f.read().rsplit(") ", 1)[1][0] != "Z":
                            pids.append(int(p))
            except OSError:
                continue
    return pids


def _maps_accelerator(pid: int) -> bool:
    """True if the process mapped jaxlib or libtpu: a dispatcher or a
    gate that did could have created a backend client."""
    try:
        with open(f"/proc/{pid}/maps") as f:
            m = f.read()
    except OSError:
        return False
    return "libtpu" in m or "jaxlib" in m


def _tail_logs(server_dir: str) -> None:
    rd = os.path.join(server_dir, "run")
    if os.path.isdir(rd):
        for name in sorted(os.listdir(rd)):
            if name.endswith(".log"):
                with open(os.path.join(rd, name), errors="replace") as f:
                    say(f"---- {name} (tail) ----\n{f.read()[-3000:]}")


def phase_cluster(a, out_dir: str) -> dict:
    """Phase 2: the served path, through the CLI and a gate socket."""
    hz = a.tick_hz
    say(f"[cluster] serving at {hz:g} Hz — the smoke's fixed rate, NOT "
        f"the deployment's {TARGET_HZ:g} Hz")
    sd = os.path.join(out_dir, "server")
    os.makedirs(sd)
    dport, gport, hport = _free_port(), _free_port(), _free_port()
    with open(os.path.join(sd, "server.py"), "w") as f:
        f.write(SERVER_PY.format(n_npc=a.entities, extent=a.extent,
                                 seed=a.seed))
    with open(os.path.join(sd, "goworld_tpu.ini"), "w") as f:
        f.write(INI.format(dport=dport, gport=gport, hport=hport,
                           capacity=a.capacity, extent=a.extent,
                           tick_hz=hz))
    c: dict[str, bool] = {}
    info: dict = {"tick_hz": hz, "target_hz": TARGET_HZ}
    bots = None
    try:
        rc, out, secs = _gw(["start", sd], 600)
        say(f"[cluster] start: rc {rc} in {secs:.1f} s: "
            + " | ".join(out.split("\n")[:6]))
        c["start"] = rc == 0
        if rc != 0:
            _tail_logs(sd)
            return {"phase": "cluster", "ok": False, "checks": c}
        v = _http(hport, "vars")
        info["game_device"] = v.get("device")
        info["start_first_tick_s"] = v.get("first_tick_s")
        info["start_cache"] = v.get("compile_cache")
        say(f"[cluster] game1 serves on {v.get('device')}; first tick "
            f"{v.get('first_tick_s')} s; cache {v.get('compile_cache')}")
        pids = {}
        for role in ("dispatcher1", "game1", "gate1"):
            with open(os.path.join(sd, "run", f"{role}.pid")) as f:
                pids[role] = int(f.read())
        c["world_is_the_size_asked"] = \
            v.get("smoke_npcs") == a.entities
        c["dispatcher_and_gate_hold_no_backend"] = not (
            _maps_accelerator(pids["dispatcher1"])
            or _maps_accelerator(pids["gate1"]))

        load0 = _load(hport)
        bots = subprocess.Popen(
            _child_cmd(a, "bots", gate_port=gport),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=_env(), cwd=HERE, start_new_session=True)
        step_a = _read_step(bots, 600)
        say(f"[cluster] bots before reload: {json.dumps(step_a)}")
        c["bots_served"] = bool(step_a and step_a["ok"])
        info["served_hz"] = _judge_load(c, "", hz, hport, load0)

        rc, out, secs = _gw(["reload", sd], 600)
        say(f"[cluster] reload: rc {rc} in {secs:.1f} s: "
            + " | ".join(out.strip().split("\n")[:6]))
        c["reload_printed_reloaded"] = rc == 0 \
            and "game1: reloaded" in out
        v = _http(hport, "vars") if rc == 0 else {}
        info["reload_first_tick_s"] = v.get("first_tick_s")
        info["reload_cache"] = cache = v.get("compile_cache") or {}
        say(f"[cluster] restored game1 serves on {v.get('device')}; "
            f"first tick {v.get('first_tick_s')} s; cache {cache}")
        c["restore_on_same_device"] = \
            v.get("device") == info["game_device"]
        # the restore read back everything the first process read or
        # kept. (Not "misses == 0": a compile that takes about the
        # cache's 1 s threshold is kept by one process and not by the
        # next — the first game process of call 6 kept one such.)
        first = info["start_cache"] or {}
        c["reload_tick_was_a_cache_hit"] = cache.get("hits", 0) >= max(
            1, first.get("hits", 0) + first.get("misses", 0))

        load0 = _load(hport) if rc == 0 else None
        bots.stdin.write("go\n")
        bots.stdin.flush()
        step_b = _read_step(bots, 600)
        say(f"[cluster] bots after reload: {json.dumps(step_b)}")
        c["bots_served_after_reload"] = bool(step_b and step_b["ok"])
        info["served_hz_after_reload"] = _judge_load(
            c, "_after_reload", hz, hport, load0)
        try:
            bots.wait(timeout=60)
        except subprocess.TimeoutExpired:
            pass
        c["bots_exit_clean"] = bots.returncode == 0
        if not all(c.values()):
            _tail_logs(sd)
    finally:
        if bots is not None:
            if bots.poll() is None:
                os.killpg(bots.pid, signal.SIGKILL)
                bots.wait()
            bots.stdin.close()
            bots.stdout.close()
        rc, out, secs = _gw(["stop", sd], 120)
        say(f"[cluster] stop: rc {rc} in {secs:.1f} s: "
            + " | ".join(out.strip().split("\n")))
        c["stop"] = rc == 0
        left = _procs_in(sd)
        c["no_process_left"] = not left
        for pid in left:                  # never leave anything running
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    return {"phase": "cluster", "ok": all(c.values()), "checks": c,
            **info}


def _read_step(proc, timeout: float) -> dict | None:
    """Next ``STEP {json}`` line of the bots child (None if it ended or
    the time ran out)."""
    import select

    end = time.monotonic() + timeout
    while time.monotonic() < end:
        ready, _, _ = select.select([proc.stdout], [], [], 1.0)
        if not ready:
            if proc.poll() is not None:
                return None
            continue
        line = proc.stdout.readline()
        if not line:
            return None
        if line.startswith("STEP "):
            return json.loads(line[5:])
        say(line.rstrip())
    return None


def parent(a) -> int:
    out_dir = a.out
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    say(f"[smoke] chips {a.chips}, capacity {a.capacity}, entities "
        f"{a.entities}, extent {a.extent:.0f}, seed {a.seed}; out "
        f"{out_dir}")
    # which codec cores this checkout ended up with (built lazily from
    # goworld_tpu/native/*.cpp; a jax-free child reports)
    subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", "cores"],
        env=_env(), cwd=HERE, timeout=900)
    phases = []
    if a.chips == 4:
        phases.append(run_phase_child(a, "mesh", 1100))
    else:
        phases.append(run_phase_child(a, "world", 900))
        try:
            phases.append(phase_cluster(a, out_dir))
        except Exception as e:      # the last line is owed whatever breaks
            import traceback

            traceback.print_exc()
            phases.append({"phase": "cluster", "ok": False,
                           "error": repr(e)})
    device = None
    for p in phases:
        say(json.dumps(p))
        device = device or p.get("device")
    game_dev = next((p["game_device"] for p in phases
                     if p.get("game_device")), None)
    on_chip = bool(device) and device["platform"] == "tpu" \
        and device["count"] == a.chips \
        and (a.chips == 4 or (game_dev or {}).get("platform") == "tpu")
    ok = on_chip and all(p["ok"] for p in phases)
    if not on_chip:
        say(f"[smoke] refused: needs {a.chips} TPU chip(s), the phases "
            f"ran on {device} (game: {game_dev})")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def child_cores(_a) -> int:
    from goworld_tpu.net import codec, kcp, snappy

    cores = {"sync codec": codec._load(), "snappy": snappy._load(),
             "kcp": kcp._load_native()}
    say("[smoke] codec cores: " + ", ".join(
        f"{name} {'native' if lib is not None else 'pure-python'}"
        for name, lib in cores.items()))
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearsal", action="store_true",
                    help="shrink every size default (CPU run)")
    ap.add_argument("--capacity", type=int)
    ap.add_argument("--entities", type=int,
                    help="live entities (per tile/space with --chips 4)")
    ap.add_argument("--extent", type=float,
                    help="world edge (tile edge with --chips 4)")
    ap.add_argument("--ticks", type=int)
    ap.add_argument("--sample", type=int, help="rows judged by the oracle")
    ap.add_argument("--bots", type=int)
    ap.add_argument("--tick-hz", type=float, dest="tick_hz",
                    help="the rate the cluster phase serves at")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"))
    ap.add_argument("--child", help=argparse.SUPPRESS)
    ap.add_argument("--gate-port", type=int, help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    for key, val in (REHEARSAL if a.rehearsal else REAL).items():
        if getattr(a, key) is None:
            setattr(a, key, val)
    if a.extent is None:
        a.extent = 10451.0 if not a.rehearsal \
            else extent_for(a.entities)
    if a.child:
        return {"world": child_world, "mesh": child_mesh,
                "bots": child_bots, "cores": child_cores}[a.child](a)
    return parent(a)


if __name__ == "__main__":
    sys.exit(main())
