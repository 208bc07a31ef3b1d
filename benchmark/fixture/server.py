"""The benchmark's game script: one world of device-driven NPCs and an
Avatar per client. Runs inside the game process (``python -m goworld_tpu
start`` executes it from the server directory) and reads its sizes and
the world's kind from ``bench_params.json`` beside it, which ``run.py``
writes from the cell's configuration file: one space on one chip, one
megaspace tiled over the cell's chips (``megaspace``, ``borders``), or
``n_spaces`` spaces on one chip, each a shard of the one vmapped tick.

Besides the world it gives the harness what only the process that owns
the chip can read (device memory as an RPC reply; the device's rows and
neighbour lists as a file beside it), and — for the tests
under ``benchmark/tests`` only — plants a fault under the timed path
when ``bench_params.json`` names one and the file ``plant.on`` exists.
"""
import dataclasses
import json
import os

import numpy as np

import goworld_tpu as gw
from goworld_tpu.utils import opmon

with open("bench_params.json") as _f:
    P = json.load(_f)
EXTENT_X, EXTENT_Z = float(P["extent_x"]), float(P["extent_z"])
RADIUS = float(P["aoi_radius"])
MEGA = bool(P.get("megaspace"))
N_SPACES = 1 if MEGA else int(P.get("n_spaces", 1))
SPACES: list = []                     # the arenas, in shard order
BORDERS = P.get("borders") or {}      # a tiled world's inner borders
# parking: the i-th login enters on a grid wider than an AOI box, so a
# login wave never puts hundreds of avatars into one neighbourhood. In a
# tiled world the logins go round the tiles, each tile with a grid of
# its own, so that a wave falls on every tile alike (bots.py sizes its
# waves by the tile); one space is one tile, and parks as ever
PARK = 2.0 * RADIUS + 20.0
_XS = [0.0] + sorted(BORDERS.get("x", ())) + [EXTENT_X]
_ZS = [0.0] + sorted(BORDERS.get("z", ())) + [EXTENT_Z]
PARKS = [(x0, z0, max(int((x1 - x0) // PARK) - 1, 1),
          max(int((z1 - z0) // PARK) - 1, 1))
         for x0, x1 in zip(_XS, _XS[1:]) for z0, z1 in zip(_ZS, _ZS[1:])]
_logins = [0]
_writes = [0]
PLANT = P.get("plant") or ""


@gw.register_space("Arena", megaspace=MEGA)
class Arena(gw.Space):
    pass


@gw.register_entity("Npc")
class Npc(gw.Entity):
    pass


@gw.register_entity("Avatar")
class Avatar(gw.Entity):
    ATTRS = {"hp": "allclients"}      # set by SetHp_Client only
    _hop = None                       # the token of an EnterSpace under way

    def OnClientConnected(self):
        if N_SPACES > 1:
            # many spaces: no counter knows where a login belongs. The
            # client says so itself (EnterSpace_Client, from the plan);
            # until then its avatar is in no space
            return
        arena = SPACES[0]
        i = _logins[0]
        _logins[0] += 1
        x0, z0, row, rows = PARKS[i % len(PARKS)]
        i //= len(PARKS)
        x = x0 + PARK * (0.5 + i % row)
        z = z0 + PARK * (0.5 + (i // row) % rows)
        self.enter_space(arena.id, (x, 0.0, z))

    def OnClientDisconnected(self):
        self.destroy()

    def Echo_Client(self, token):
        self.call_client("OnEcho", token)

    def EnterSpace_Client(self, space_index, x, z, token):
        """Change space (from no space: a login's placement). The reply
        goes out when the program says the avatar has entered
        (``OnEnterSpace``), not when the call was read."""
        self._hop = token
        if PLANT == "stay" and os.path.exists("plant.on"):
            self.OnEnterSpace()       # answers, and moves nobody
            return
        self.enter_space(SPACES[int(space_index)].id,
                         (float(x), 0.0, float(z)))

    def OnEnterSpace(self):
        if self._hop is not None:
            token, self._hop = self._hop, None
            self.call_client("OnEntered", token)

    def SetHp_Client(self, v, token):
        _writes[0] += 1
        if not (PLANT == "hp" and os.path.exists("plant.on")
                and _writes[0] % 2):
            self.attrs["hp"] = v      # (the plant: every second write lost)
        self.call_client("OnHpSet", token)

    def Stats_Client(self):
        import jax

        peak = 0
        for d in jax.local_devices():
            ms = d.memory_stats() or {}
            peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
        self.call_client("OnStats", json.dumps(
            {"memory_peak_bytes": peak,
             "npcs": sum(1 for e in self.world.entities.values()
                         if e.type_name == "Npc")}))


    def Rows_Client(self, seed, sample):
        """For the check, once the world has settled: what the device
        holds, read in the one process that can read it. Every row's
        position and liveness, each avatar's row, and the neighbour
        lists of every avatar row and of ``sample`` NPC rows a tile
        drawn from the seed go to ``rows.npz`` beside this script
        (program-prepared data: the benchmark compares the lists with
        its own brute force over these positions, and the avatars' rows
        with what it sent). Whole arrays come to the host as they are
        and are cut there, so nothing compiles for it.

        The WHOLE world is read, under one row number: tile * capacity
        + slot, the number the neighbour lists themselves hold (the
        program's gid; a tile's ghost rows are copies and no rows of
        the world). For one space that is the slot, and the file holds
        what it always held. In a tiled world half of a tile's sample
        comes from its rows within the radius of a tile border. Many
        spaces are read the same way, space * capacity + slot, with ONE
        sample of ``sample`` NPC rows over all of them, and every NPC's
        id beside its row (``npc_eids``, ``npc_rows``: the check holds
        a mirrored NPC to the space of the client that mirrors it)."""
        st = self.world.state
        pos, alive, nbr = (np.asarray(x) for x in (st.pos, st.alive, st.nbr))
        cap = pos.shape[1]
        if not MEGA:                  # spaces in shard order; one space:
            pos, alive, nbr = (x[:N_SPACES] for x in (pos, alive, nbr))
            #                           shard 0 is the world
        pos, alive, nbr = (x.reshape((-1,) + x.shape[2:])
                           for x in (pos, alive, nbr))
        avatars = sorted((e.id, int(e.shard or 0) * cap + int(e.slot))
                         for e in self.world.entities.values()
                         if e.type_name == "Avatar" and e.slot is not None)
        av_rows = np.array([r for _i, r in avatars], np.int64)
        npc_rows = np.setdiff1d(np.nonzero(alive)[0], av_rows)
        rng = np.random.default_rng([int(seed), 0x726F7773])
        if not MEGA:
            picked = [rng.choice(npc_rows, min(int(sample), len(npc_rows)),
                                 replace=False)]
        else:
            near = np.zeros(len(npc_rows), bool)
            for axis, col in (("x", 0), ("z", 2)):
                for line in BORDERS.get(axis, ()):
                    near |= np.abs(pos[npc_rows, col] - line) <= RADIUS
            picked = []
            for t in range(len(pos) // cap):
                mine = npc_rows // cap == t
                a = npc_rows[mine & near]
                a = rng.choice(a, min(int(sample) // 2, len(a)),
                               replace=False)
                b = npc_rows[mine & ~near]
                b = rng.choice(b, min(int(sample) - len(a), len(b)),
                               replace=False)
                picked += [a, b]
        rows = np.concatenate([av_rows] + picked)
        more = {}
        if N_SPACES > 1:
            npcs = sorted((e.id, int(e.shard) * cap + int(e.slot))
                          for e in self.world.entities.values()
                          if e.type_name == "Npc" and e.slot is not None)
            more = {"npc_eids": np.array([i for i, _r in npcs]),
                    "npc_rows": np.array([r for _i, r in npcs], np.int64)}
        np.savez("rows.npz", pos=pos, alive=alive, rows=rows,
                 nbr=nbr[rows], avatar_rows=av_rows,
                 avatar_eids=np.array([i for i, _r in avatars]),
                 tick=int(self.world.tick_count), **more)
        self.call_client("OnRows", "rows.npz")


class _LiveTick:
    """``/vars`` prints what it cannot encode with ``str``: this prints
    the world's tick count as it is when the page is asked for."""

    def __init__(self, world):
        self.world = world

    def __str__(self):
        return str(int(self.world.tick_count))


def _plant(world, kind: str) -> None:
    """Tests only: break the timed path underneath, once ``plant.on``
    exists. ``alter`` changes a position where it is staged, ``half``
    leaves out every second client's record, ``freeze`` stages nothing
    (the state stays as it was), ``lose`` takes one NPC out of the
    world (as a migration that drops a row would), ``caps`` sets the
    program's alarm for a tile's migrate buffer to go off at ANY
    migration (the host-side threshold only: the compiled tick keeps
    its buffers), so its own overflow lines are in its own log."""
    cls = type(world)
    stage = cls.stage_pos_sync_batch
    once = []

    def broken(self, eids, vals):
        if not os.path.exists("plant.on"):
            return stage(self, eids, vals)
        if kind == "lose" and not once:
            once.append(next(e for e in self.entities.values()
                             if e.type_name == "Npc"))
            once[0].destroy()
        if kind == "caps" and not once:
            once.append(dataclasses.replace(self.mega, migrate_cap=0))
            self.mega = once[0]
        eids = np.asarray(eids, "S16")
        vals = np.array(vals, np.float32).reshape(-1, 4)
        if kind == "alter":
            vals[:, 0] += 1.0
        elif kind == "half":
            if not once:      # every second avatar, whatever ids a run draws
                once.append({e.encode() for e in sorted(
                    e.id for e in self.entities.values()
                    if e.type_name == "Avatar")[1::2]})
            keep = np.array([e not in once[0] for e in eids.tolist()], bool)
            eids, vals = eids[keep], vals[keep]
        elif kind == "freeze":
            return 0
        return stage(self, eids, vals)

    cls.stage_pos_sync_batch = broken


@gw.on_boot
def fill(world):
    # the arenas take the shards in the order they are made
    SPACES.extend(world.create_space("Arena") for _ in range(N_SPACES))
    rng = np.random.default_rng(int(P["seed"]))
    # uniform over the world's extent (bit for bit uniform(0, extent)
    # where the world is square); of many spaces each gets as many, a
    # stretch of the one draw
    n = int(P["npcs"])
    xz = rng.uniform(0.0, 1.0, (n, 2)) * np.array([EXTENT_X, EXTENT_Z])
    for i, (x, z) in enumerate(xz):
        world.create_entity("Npc", space=SPACES[i * N_SPACES // n],
                            pos=(x, 0.0, z), moving=True)
    opmon.expose("bench_npcs", int(P["npcs"]))
    # the audit plane samples on the logic thread every so many ticks:
    # run.py opens every window at the same place against that cadence,
    # so every run holds as many samples
    opmon.expose("bench_tick", _LiveTick(world))
    # (on a megaspace the plane skips every sample before it walks
    # anything, entity/manager.py _audit_sample: no cadence to meet)
    aud = getattr(world, "audit", None)
    # (... and of many spaces it samples ONE shard, a few hundred rows
    # on the logic thread: nothing a window has to be placed against)
    opmon.expose("bench_audit_every",
                 0 if MEGA or N_SPACES > 1
                 else int(getattr(aud, "sample_every", 0) or 0))
    if P.get("plant"):
        _plant(world, P["plant"])


if __name__ == "__main__":
    gw.run()
